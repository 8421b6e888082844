"""Operations and bytes a call needs, from its shapes; and the peaks.

Copied from ``bench.py`` (``_transformer_step_flops``, ``_PEAKS``) so that a
later PR may change the program and not the yardstick. Conventions are
stated where they matter: a roofline share may not pass 100%, so nothing is
counted that the algorithm does not need.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``. A kind that is not
    in ``peaks.json`` is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(
            f"benchmark: no peaks on record for device_kind {device_kind!r} "
            f"(known: {sorted(k for k in table if not k.startswith('_'))}); "
            "add it to benchmark/peaks.json with its source")
    return table[device_kind]


def transformer_step_flops(d: int, n_layers: int, d_ff: int, vocab: int,
                           batch: int, seq: int) -> int:
    """Analytic train-step FLOPs, ``6 * N_matmul * tokens + 12 * L * B * S^2
    * d``: N_matmul counts weight-matrix parameters on the matmul path
    (qkv + attention projection + the two MLP matrices per layer, plus the
    d x vocab readout; embedding lookups move no FLOPs); forward is 2 * N *
    tokens, training three times that. The attention term is QK^T + AV with
    NO causal discount (``bench.py``'s convention, used for a model-FLOPs
    utilisation, never for a kernel's roofline)."""
    n_mm = n_layers * (4 * d * d + 2 * d * d_ff) + d * vocab
    return 6 * n_mm * batch * seq + 12 * n_layers * batch * seq * seq * d


def causal_attention_train(batch: int, seq: int, d_model: int,
                           n_layers: int, bytes_per_el: int = 2) -> dict:
    """FLOPs and HBM bytes that causal attention, forward and backward,
    needs per train step — the floor a flash kernel is held against.

    FLOPs: six products of B * H * S * S * D multiply-adds each (forward
    QK^T and PV; backward dV, dP, dQ, dK), two FLOPs a multiply-add, and
    HALF of each because a causal mask needs only the lower triangle: 6 * B
    * S^2 * d per layer. The backward's recomputation of the scores is not
    counted (a kernel that recomputes does more than the algorithm needs).
    Bytes: q, k, v read and o written once forward; q, k, v, o, do read and
    dq, dk, dv written once backward: twelve (B, S, d) arrays per layer in
    the activation type; the per-row statistics are left out."""
    return {
        "flops": 6 * batch * seq * seq * d_model * n_layers,
        "bytes": 12 * batch * seq * d_model * bytes_per_el * n_layers,
    }


def roofline_pct(flops: float, bytes_: float, seconds: float,
                 peak: dict) -> dict:
    """Share of the roofline: the least time the chip could take — the
    larger of FLOPs over peak FLOP/s and bytes over peak bytes/s — over the
    time taken, in percent, and which of the two bounds."""
    t_flops = flops / (peak["bf16_tflops"] * 1e12)
    t_bytes = bytes_ / (peak["hbm_gb_per_s"] * 1e9)
    return {"pct": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}
