"""LoRA adapters for the GPT family — fine-tune with only adapter
gradients on the aggregation tier.

The reference aggregates EVERY gradient byte on its PS tier each step;
for fine-tuning, low-rank adaptation shrinks the trainable surface (and
with it the DCN/ICI gradient traffic) by orders of magnitude while the
frozen base never moves. Pairs with the HF bridge
(``models/import_hf.py``): import a checkpoint, LoRA-finetune it under
compressed dp aggregation, merge and export.

Design (TPU-first, functional like everything in ``models/``):

* Adapters live in their own pytree — ``{"blocks": [{target: {"a", "b"}
  ...}]}`` — which is the ONLY tree the optimizer and the gradient
  aggregation ever see. The frozen base is an explicit input to the
  jitted step (no stale closure constants, resharding stays possible).
* The forward grafts each block's adapters into the block dict under a
  ``"lora"`` key (with the ``alpha/rank`` scale pre-multiplied into
  ``b`` at graft time — optimizer state stays on the unscaled leaves);
  the block's ``_project`` (``models/gpt.py``: every caller's one read
  of a block weight) adds ``(x @ a) @ b`` beside the frozen matmul. Two thin matmuls — the ``(d, d)`` delta is never
  materialized in training.
* Tensor parallelism: for column-parallel targets (wq/wk/wv/w1/w3)
  ``a`` is replicated and ``b`` column-sharded, so the adapter path
  needs NO extra collective. For row-parallel targets (wo/w2) ``a`` is
  row-sharded and the tiny ``(B, S, r)`` intermediate is psum'd —
  r/d_model the bytes of the base path's existing psum.
* ``b`` initializes to zero (standard LoRA): step 0 reproduces the
  frozen model exactly, which the tests pin.
* ``merge_lora`` folds ``w + scale * a @ b`` once for inference/export
  — the merged tree is a plain GPT tree (decode kernels, HF export,
  checkpointing all apply unchanged).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from byteps_tpu.models.gpt import GPTConfig

# target -> (in_dim attr, out_dim attr, orientation)
_COL_TARGETS = ("wq", "wk", "wv", "w1", "w3")
_ROW_TARGETS = ("wo", "w2")
ALL_TARGETS = _COL_TARGETS + _ROW_TARGETS


def _target_dims(cfg: GPTConfig, name: str) -> Tuple[int, int]:
    d, ff = cfg.d_model, cfg.d_ff
    hd = cfg.n_heads * cfg.head_dim
    kv_hd = cfg.kv_heads * cfg.head_dim
    return {
        "wq": (d, hd), "wk": (d, kv_hd), "wv": (d, kv_hd),
        "wo": (hd, d), "w1": (d, ff), "w3": (d, ff), "w2": (ff, d),
    }[name]


def _check_targets(cfg: GPTConfig, targets: Sequence[str]) -> Tuple[str, ...]:
    targets = tuple(targets)
    if not targets:
        raise ValueError("LoRA needs at least one target projection")
    for t in targets:
        if t not in ALL_TARGETS:
            raise ValueError(f"unknown LoRA target {t!r} — expected a "
                             f"subset of {ALL_TARGETS}")
        if t == "w3" and cfg.mlp != "swiglu":
            raise ValueError("target 'w3' needs mlp='swiglu'")
    return targets


def lora_init(rng, cfg: GPTConfig, rank: int,
              targets: Sequence[str] = ("wq", "wv")) -> Dict[str, Any]:
    """Adapter pytree: per block, per target, ``a ~ N(0, 1/rank)`` and
    ``b = 0`` — the grafted model starts exactly at the frozen base."""
    if rank < 1:
        raise ValueError(f"rank must be >= 1; got {rank}")
    targets = _check_targets(cfg, targets)
    keys = jax.random.split(rng, cfg.n_layers)

    def one_block(key):
        ks = jax.random.split(key, len(targets))
        blk = {}
        for t, k in zip(targets, ks):
            d_in, d_out = _target_dims(cfg, t)
            blk[t] = {
                "a": jax.random.normal(k, (d_in, rank), jnp.float32)
                / (rank ** 0.5),
                "b": jnp.zeros((rank, d_out), jnp.float32),
            }
        return blk

    return {"blocks": [one_block(k) for k in keys]}


def lora_param_specs(cfg: GPTConfig, tp_axis: Optional[str], rank: int,
                     targets: Sequence[str] = ("wq", "wv")
                     ) -> Dict[str, Any]:
    """PartitionSpecs mirroring :func:`lora_init`: column-parallel
    targets shard ``b``'s output dim over tp (no extra collective);
    row-parallel targets shard ``a``'s input dim (the (B,S,r)
    intermediate is psum'd in the forward)."""
    from byteps_tpu.parallel.partitioner import resolve_specs, rules_from_axes
    targets = _check_targets(cfg, targets)

    def logical(t):
        if t in _COL_TARGETS:
            return {"a": ("embed", None), "b": (None, "heads")}
        return {"a": ("heads", None), "b": (None, "embed")}

    tree = {"blocks": [{t: logical(t) for t in targets}
                       for _ in range(cfg.n_layers)]}
    return resolve_specs(tree, rules_from_axes(tp_axis=tp_axis))


def graft_lora(base_params: Dict[str, Any], adapters: Dict[str, Any],
               scale: float) -> Dict[str, Any]:
    """Frozen base + adapters → the tree the forward consumes: each
    block carries a ``"lora"`` sub-dict with the scale pre-multiplied
    into ``b`` (optimizer state stays on the unscaled adapter tree).
    Pure and cheap (scaling fuses into the step's XLA program)."""
    blocks = []
    for bp, ad in zip(base_params["blocks"], adapters["blocks"]):
        blk = dict(bp)
        blk["lora"] = {
            t: {"a": ab["a"], "b": ab["b"] * scale}
            for t, ab in ad.items()
        }
        blocks.append(blk)
    out = dict(base_params)
    out["blocks"] = blocks
    return out


def _fence(xs):
    """``optimization_barrier``: identity that pins compiler
    scheduling/fusion. It differentiates (tangents pass through their
    own barrier)."""
    return jax.lax.optimization_barrier(xs)


def lora_delta(x: jnp.ndarray, p: Dict[str, Any], name: str,
               tp_axis: Optional[str] = None) -> jnp.ndarray:
    """``scale * (x @ a) @ b`` for one target, or 0.0 when the block
    carries no adapter for it. For row-parallel targets inside a tp
    shard_map, the thin ``(..., r)`` intermediate is psum'd — the
    base matmul's own psum runs separately (both are linear, but the
    base helper adds its bias after ITS psum, so the two terms stay
    independent)."""
    lr = p.get("lora")
    if lr is None or name not in lr:
        return jnp.zeros((), x.dtype)
    a = lr[name]["a"].astype(x.dtype)
    b = lr[name]["b"].astype(x.dtype)
    # barrier-fence the thin dot pair: the rank-r dots are small enough
    # that XLA folds them into whatever fusion surrounds them, and the
    # chosen loop shape (hence accumulation order) varies with the
    # CONSUMER — the same delta can differ by 1 ulp between two
    # programs. The fences pin an isolated, context-independent island,
    # which is what lets the serve tier's segmented twin
    # (ops/segmented_lora.py) reproduce this delta BIT-exactly from its
    # packed step. Numerically the barrier is identity; AD passes
    # through.
    x, a, b = _fence((x, a, b))
    h = x @ a
    if name in _ROW_TARGETS and tp_axis is not None:
        h = jax.lax.psum(h, tp_axis)
    return _fence(h @ b)


def lora_rank(adapters: Dict[str, Any]) -> int:
    """The adapter tree's rank (every target shares one by
    construction of :func:`lora_init`)."""
    blk = adapters["blocks"][0]
    first = next(iter(blk.values()))
    return int(first["a"].shape[-1])


def lora_pool_slabs(adapters: Dict[str, Any], cfg: GPTConfig,
                    rank_bucket: int, scale: float,
                    targets: Sequence[str]) -> Dict[str, Any]:
    """Pool-loadable A/B slabs for ONE adapter — the serve tier's
    :class:`~byteps_tpu.serve.adapter_pool.AdapterPool` stacks these
    into its device-resident slot arrays.

    Per target: ``a (n_layers, d_in, rank_bucket)`` and ``b
    (n_layers, rank_bucket, d_out)`` float32, rank-padded with zeros
    (a zero A column times a zero B row contributes exactly 0.0 to the
    delta, so mixed-rank tenants share one compiled packed step without
    touching the math) and with ``scale`` pre-multiplied into ``b`` —
    the same ``b * scale`` arithmetic :func:`graft_lora` performs, so
    the pooled delta is bit-identical to the solo grafted one. The
    adapter must carry every requested target (a pooled row can't
    distinguish "no adapter" from "no target"; register base-model
    tenants with no adapter instead)."""
    targets = _check_targets(cfg, targets)
    r = lora_rank(adapters)
    if r > rank_bucket:
        raise ValueError(
            f"adapter rank {r} exceeds the pool's rank bucket "
            f"{rank_bucket}")
    out: Dict[str, Any] = {}
    for t in targets:
        d_in, d_out = _target_dims(cfg, t)
        a_l, b_l = [], []
        for blk in adapters["blocks"]:
            if t not in blk:
                raise ValueError(
                    f"adapter is missing pool target {t!r} — the pool's "
                    "targets must be a subset of every registered "
                    "adapter's")
            ab = blk[t]
            a = jnp.zeros((d_in, rank_bucket), jnp.float32)
            a = a.at[:, :r].set(ab["a"].astype(jnp.float32))
            b = jnp.zeros((rank_bucket, d_out), jnp.float32)
            # multiply in the adapter's own dtype first (graft_lora's
            # exact arithmetic), THEN upcast losslessly for storage
            b = b.at[:r, :].set((ab["b"] * scale).astype(jnp.float32))
            a_l.append(a)
            b_l.append(b)
        out[t] = {"a": jnp.stack(a_l), "b": jnp.stack(b_l)}
    return out


def merge_lora(base_params: Dict[str, Any], adapters: Dict[str, Any],
               scale: float) -> Dict[str, Any]:
    """Fold the adapters into plain GPT weights: ``w + scale * a @ b``
    per target. The result is a standard tree — decode, checkpointing,
    and HF export apply unchanged."""
    blocks = []
    for bp, ad in zip(base_params["blocks"], adapters["blocks"]):
        blk = dict(bp)
        for t, ab in ad.items():
            blk[t] = (blk[t].astype(jnp.float32)
                      + scale * ab["a"] @ ab["b"]).astype(bp[t].dtype)
        blocks.append(blk)
    out = dict(base_params)
    out["blocks"] = blocks
    return out
