"""The JoyAI-LLM-Flash training cell: ``make_gpt_moe_train_step`` ->
PrefetchLoader -> step ended by block_until_ready, steps back to back until
the clock passes ``--seconds`` (the pattern of ``drivers/train.py``), with
the model's own reference and the step's own counters.

A ``train_moe`` mix's keys: ``batch_per_chip``, ``seq``, ``mesh`` (``dp``
alone: the model runs one device's share without an exchange),
``optimizer`` (``adamw`` with ``learning_rate``), ``aggregation`` (``raw``),
``remat`` (recomputation per block), ``prefetch_depth``, ``warmup_steps``,
``trace_window_s``, the three limits of ``correct``, each with its ``_why``
— ``loss_tolerance`` (the main and the MTP term of the first batch's loss,
each, against the reference's), ``pairs_tolerance`` (the pairs the first
step computed here against the pairs the reference routes to the held
experts) and ``route_mismatch_max`` (tokens of the first sequence for which
the program's router, given the reference's own router input of the first
expert layer, picks other experts than the reference) — and a ``rehearsal``
block of tiny values. The configuration's ``gpt_config`` is the program's
``JoyAIConfig`` as data (its ``router_bias_update_rate`` among them: the
correction bias moves between steps, so the window's routing is not the
first step's); token ids are drawn below ``source_vocab_size``, the rows
of the vocabulary this chip holds.
"""

from __future__ import annotations

import math
import time
from typing import Dict

from benchmark import metrics, traffic_gen
from benchmark.configs import joyai_reference
# a program without the model cannot run the cell: fail here, before any
# device is claimed
from byteps_tpu.models.joyai import STEP_STATS, JoyAIConfig, joyai_init

SPANS = ("train.step", "train.wait_input")


def _optimizer(spec: Dict):
    import optax

    if spec["name"] != "adamw":
        raise ValueError(f"unknown optimizer {spec['name']!r}")
    return optax.adamw(spec["learning_rate"])


def _histograms() -> Dict:
    """The step's own counters as the program's registry holds them (one
    observation a step each: ``count`` and ``sum``)."""
    import byteps_tpu

    snap = byteps_tpu.metrics_snapshot()["metrics"]["histograms"]
    return {k: snap.get(k, {"count": 0, "sum": 0.0}) for k in STEP_STATS}


def run(h) -> Dict:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    import byteps_tpu.jax as bps
    from byteps_tpu.data import PrefetchLoader
    from byteps_tpu.models.train import make_gpt_moe_train_step
    from byteps_tpu.parallel.moe import sigmoid_topk_route

    t = h.traffic
    kw = dict(h.config["gpt_config"])
    kw["dtype"] = jnp.dtype(kw["dtype"]).type
    cfg = JoyAIConfig(**kw)
    dp = int(t["mesh"]["dp"])
    if dp != h.chips:
        raise SystemExit(f"benchmark: mix {t['name']!r} is laid out for "
                         f"dp={dp}, the cell has {h.chips} chip(s)")
    if t["aggregation"] != "raw":
        raise ValueError(f"unknown aggregation {t['aggregation']!r}")
    B, S = int(t["batch_per_chip"]) * dp, int(t["seq"])
    vocab = int(h.config["source_vocab_size"])
    bps.init()
    # a mesh of the dp axis alone: the factory reads every axis a mesh
    # names, and this model runs on none but dp
    mesh = Mesh(np.asarray(jax.devices()[:dp]).reshape(dp), ("dp",))
    # weights on the device, one jitted call from the seed
    init = jax.jit(functools.partial(joyai_init, cfg=cfg))
    step, params, opt_state, bsh = make_gpt_moe_train_step(
        cfg, mesh, _optimizer(t["optimizer"]), remat=bool(t["remat"]),
        init_params=init(jax.random.PRNGKey(h.seed)))

    losses, step_ms = [], []
    n_warm = int(t["warmup_steps"])
    with PrefetchLoader(traffic_gen.train_batches(h.seed, vocab, B, S),
                        bsh, depth=int(t["prefetch_depth"])) as loader:
        for i in range(n_warm):      # the step compiles on its second call too
            tok, tgt = next(loader)
            loss, params, opt_state = step(params, opt_state, tok, tgt)
            jax.block_until_ready((loss, params, opt_state))
            losses.append(loss)
            if i == 0:               # the first batch's two loss terms
                step.flush_stats()
                first = _histograms()
        step.flush_stats()
        hist0 = _histograms()
        hist_trace = None
        t0 = last = h.open_window()
        while True:
            with h.span("train.wait_input"):
                tok, tgt = next(loader)
            with h.span("train.step"):
                loss, params, opt_state = step(params, opt_state, tok, tgt)
                jax.block_until_ready((loss, params, opt_state))
            now = time.monotonic()
            step_ms.append((now - last) * 1e3)
            last = now
            losses.append(loss)
            if now - t0 >= h.seconds:
                break
            if h.trace_due(t0, now):
                # the counters as the traced steps find them: a step's
                # pairs are its own, not the window's mean, and a kernel's
                # traced time is held against the pairs of the same steps
                step.flush_stats()
                hist_trace = _histograms()
                h.start_trace()
        t_end = last
        h.close_window()
        step.flush_stats()
        hist1 = _histograms()
    peak = h.memory_peak_bytes()
    h.reduce_trace(SPANS)
    losses = [float(x) for x in losses]
    n_bad = sum(not math.isfinite(x) for x in losses)

    # outside the window: the first batch against the plain reference
    # (f32, forward only), one sequence at a time so that one program
    # serves any batch: the loss, main and MTP terms each, and the pairs
    # the held experts get
    del params, opt_state
    params0 = init(jax.random.PRNGKey(h.seed))
    tok, tgt = next(traffic_gen.train_batches(h.seed, vocab, B, S))
    ref = jax.jit(functools.partial(
        joyai_reference.forward, n_heads=cfg.n_heads, nope=cfg.qk_nope_dim,
        rope=cfg.qk_rope_dim, v_dim=cfg.v_head_dim, theta=cfg.rope_base,
        eps=cfg.norm_eps, top_k=cfg.top_k, scale=cfg.routed_scaling,
        first_expert=cfg.first_expert))
    per_seq = [ref(params0, jnp.asarray(tok[i:i + 1]),
                   jnp.asarray(tgt[i:i + 1])) for i in range(B)]
    ref_main = float(np.mean([float(r[0]) for r in per_seq]))
    ref_mtp = float(np.mean([float(r[1]) for r in per_seq]))
    ref_pairs = sum(int(r[2]) for r in per_seq)
    # the program's router on the reference's own input (first sequence,
    # first expert layer): the same f32 numbers in, so the same picks out
    # unless the router's product is coarser than the reference's
    ref_h, ref_idx = per_seq[0][3]
    moe0 = params0["blocks"][cfg.first_k_dense]["moe"]
    idx, _ = jax.jit(functools.partial(
        sigmoid_topk_route, k=cfg.top_k, scale=cfg.routed_scaling))(
            ref_h.reshape(-1, ref_h.shape[-1]), moe0["wg"],
            moe0["router_bias"])
    mismatch = int(jnp.sum(jnp.any(
        jnp.sort(idx, -1) != jnp.sort(ref_idx.reshape(idx.shape), -1), -1)))
    got_main = first["train.loss_main"]["sum"]
    got_mtp = first["train.loss_mtp"]["sum"]
    got_pairs = first["moe.pairs_here"]["sum"]
    tol = float(t["loss_tolerance"])
    bps.shutdown()
    steps = len(step_ms)
    pairs_total = (hist1["moe.pairs_total"]["sum"]
                   - hist0["moe.pairs_total"]["sum"]) / max(steps, 1)
    moe_layers = cfg.n_layers - cfg.first_k_dense + cfg.n_mtp
    return {
        "correct": (n_bad == 0 and abs(got_main - ref_main) <= tol
                    and abs(got_mtp - ref_mtp) <= tol
                    and first["train.loss_main"]["count"] == 1
                    and abs(got_pairs - ref_pairs)
                    <= float(t["pairs_tolerance"])
                    and mismatch <= int(t["route_mismatch_max"])
                    # every held pair of every step had a row in the
                    # kernels' buffer (the counter is rows + pairs routed
                    # elsewhere)
                    and pairs_total == B * S * cfg.top_k * moe_layers),
        "attempted": steps, "failed": n_bad,
        "end_to_end": {"train_tokens_per_s": metrics.train_tokens_per_s(
            steps, B * S, t0, t_end, h.chips)},
        "memory_peak_bytes": peak,
        "step_ms": step_ms, "steps": steps, "elapsed_s": t_end - t0,
        "batch": B, "seq": S,
        "histograms": {"start": hist0, "end": hist1,
                       **({"trace_start": hist_trace} if hist_trace else {})},
        "notes": {"first_loss": losses[0], "first_loss_main": got_main,
                  "first_loss_mtp": got_mtp, "reference_loss_main": ref_main,
                  "reference_loss_mtp": ref_mtp, "loss_tolerance": tol,
                  "last_loss": losses[-1], "steps": steps,
                  "pairs_total_per_step": pairs_total,
                  "first_pairs_here": got_pairs,
                  "reference_pairs_here": ref_pairs,
                  "route_mismatch": mismatch,
                  "first_load_max_over_mean":
                      first["moe.load_max_over_mean"]["sum"],
                  # the window's means are the metrics: a router in
                  # training need not route as the seeded one did
                  "pairs_here_per_step": (
                      hist1["moe.pairs_here"]["sum"]
                      - hist0["moe.pairs_here"]["sum"]) / max(steps, 1),
                  # steps the counters saw after the trace began: the
                  # trace's own count of train.step spans, or the pairs
                  # under joy_moe_gmm_roofline are not the timed steps'
                  **({"traced_steps": hist1["moe.pairs_here"]["count"]
                      - hist_trace["moe.pairs_here"]["count"]}
                     if hist_trace else {}),
                  "cache_dir": h.cache_dir},
    }
