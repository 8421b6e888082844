"""Flash-decode — the Pallas kernel for single-token cached attention.

No reference analog (the reference is a training system; its models
delegate attention to torch/TF). On TPU, autoregressive decode is
HBM-bandwidth-bound: every generated token reads the whole KV cache
once. The jnp fallback leaves that op to XLA's fusion of a ``(1, S)``
einsum/softmax chain over the full static cache (int8 reads stay fused
— see ``models/generate.py _cache_read``); this kernel makes the
schedule explicit instead of hoping the fusion holds: one VMEM-resident
online-softmax pass over the stored cache with no intermediate
score/probability arrays in HBM, compute skipped block-by-block past
the fill level (the jnp chain always computes all of ``S_max``), and
the dequantized view never materialized anywhere (the pallas *prefill*
path must materialize it once per prefill, taking concrete operands):

* Grid ``(B, nk)`` — one program per sequence, ``nk`` sequential key
  blocks with flash-style online-softmax state ``(m, l, acc)`` in VMEM
  scratch. Each program carries ALL kv heads of its sequence, unrolled
  as per-head 2-d MXU ops — every cache block is DMA'd exactly once,
  and the ``G = H/Hkv`` query heads of each group ride their kv head's
  block (GQA native, narrow cache read).
* The cache AND its scales are read IN PLACE via BlockSpecs on their
  stored layouts (``(B, S, Hkv, D)`` / ``(B, S, Hkv)`` — trailing block
  dims equal the array's, satisfying the mosaic minor-dim rules), so
  there is no per-step transpose/copy of anything.
* int8 dequantization happens in VMEM, block by block: each head's
  ``(bk, D)`` int8 tile is multiplied by its ``(bk, 1)`` scale column
  and rounded through the model dtype — bit-identical to
  ``_cache_read``'s semantics — so the int8 cache is read from HBM at
  half the bf16 bandwidth by construction, not by fusion luck. The
  dense (non-quantized) signature carries no scale operands at all.
* Fill-level masking: keys at global positions ``> pos`` (the query's
  position) are dead — whole dead blocks skip compute via ``pl.when``,
  the boundary block masks by global column index. ``pos`` is a runtime
  SMEM scalar, so one compiled kernel serves every decode step.

Numerics contract: identical to ``attention_lse_jnp(q, _cache_read(k),
_cache_read(v), pos, 0, causal=True)`` restricted to its live prefix —
dequant rounded to model dtype, f32 accumulation, output in q.dtype —
for EVERY dtype/quantization combination (pinned per-op and
token-for-token across backends in ``tests/test_flash_decode.py``).
Prefill (T>1) keeps the existing flash/jnp paths: its cache read is
amortized over T tokens and the (bq, bk)-tiled forward kernel already
covers it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byteps_tpu.ops.backend import interpret as _interpret
from byteps_tpu.ops.backend import use_pallas  # noqa: F401 (re-export)
from byteps_tpu.ops.flash_attention import (
    _MAX_HEAD_DIM,
    _NEG,
    _out_struct,
    _pick_block,
    _unify_vma,
)

__all__ = ["flash_decode", "decode_supported", "use_pallas"]


def decode_supported(S: int, D: int) -> bool:
    """Cache length must tile into 8..256 key blocks; head_dim ≤ 256.
    (Every block layout keeps its trailing dims mosaic-legal: the cache
    blocks end in the full (Hkv, D) planes, the scale blocks in
    (bk, Hkv) with bk a multiple of 8.)"""
    return _pick_block(S) is not None and D <= _MAX_HEAD_DIM


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, bk, nk):
    """ks_ref/vs_ref are None on the dense (non-quantized) path — the
    pallas signature then simply has no scale operands."""
    ki = pl.program_id(1)
    quantized = ks_ref is not None

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    pos = pos_ref[0, 0].astype(jnp.int32)     # query's global position
    k_start = ki * bk

    @pl.when(k_start <= pos)                  # dead blocks: no compute
    def _tile():
        # static unroll over kv heads: mosaic's matmul doesn't take the
        # stored layout's batch-dim placement, so each head runs plain
        # 2-d MXU ops on ref-sliced tiles; the block DMA happens ONCE —
        # slices read VMEM.
        Hkv = q_ref.shape[1]
        model_dt = q_ref.dtype
        for h in range(Hkv):
            qh = q_ref[0, h].astype(jnp.float32)          # (G, D)
            kh = k_ref[0, :, h, :]                        # (bk, D)
            vh = v_ref[0, :, h, :]
            if quantized:
                # VMEM dequant, rounded through the model dtype —
                # bit-identical to _cache_read's HBM materialization
                kh = (kh.astype(jnp.float32)
                      * ks_ref[0, :, h:h + 1]).astype(model_dt)
                vh = (vh.astype(jnp.float32)
                      * vs_ref[0, :, h:h + 1]).astype(model_dt)
            kh = kh.astype(jnp.float32)
            vh = vh.astype(jnp.float32)
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (G, bk)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(cols <= pos, s, _NEG)
            m_prev = m_scr[h]                             # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            p = jnp.where(s > _NEG / 2, p, 0.0)           # masked lanes
            l_scr[h] = l_scr[h] * alpha + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # (G, D)
            m_scr[h] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode(q4, k4, v4, ks, vs, pos, interpret: bool):
    """q4: (B, Hkv, G, D); k4/v4: (B, S, Hkv, D) stored layout;
    ks/vs: (B, S, Hkv) f32 stored layout, or None → o (B, Hkv, G, D)."""
    B, Hkv, G, D = q4.shape
    S = k4.shape[1]
    bk = _pick_block(S)
    nk = S // bk
    quantized = ks is not None
    base = functools.partial(
        _decode_kernel, scale=1.0 / (D ** 0.5), bk=bk, nk=nk)
    pos2 = jnp.asarray(pos, jnp.float32).reshape(1, 1)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, Hkv, G, D), lambda b, ki: (b, 0, 0, 0)),
        pl.BlockSpec((1, bk, Hkv, D), lambda b, ki: (b, ki, 0, 0)),
        pl.BlockSpec((1, bk, Hkv, D), lambda b, ki: (b, ki, 0, 0)),
    ]
    if quantized:
        in_specs += [pl.BlockSpec((1, bk, Hkv), lambda b, ki: (b, ki, 0)),
                     pl.BlockSpec((1, bk, Hkv), lambda b, ki: (b, ki, 0))]
        operands = _unify_vma(pos2, q4, k4, v4, ks, vs)
        kern = base
    else:
        # dense: no scale operands in the signature at all
        operands = _unify_vma(pos2, q4, k4, v4)

        def kern(pos_ref, q_ref, k_ref, v_ref, o_ref, m, l, acc):
            base(pos_ref, q_ref, k_ref, v_ref, None, None, o_ref,
                 m, l, acc)

    out = pl.pallas_call(
        kern,
        grid=(B, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, G, D), lambda b, ki: (b, 0, 0, 0)),
        out_shape=_out_struct((B, Hkv, G, D), q4.dtype, *operands),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),    # m
            pltpu.VMEM((Hkv, G, 1), jnp.float32),    # l
            pltpu.VMEM((Hkv, G, D), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(*operands)
    return out


def flash_decode(q, k_cache, v_cache, pos, k_scale=None, v_scale=None):
    """Single-token cached attention: ``q (B, 1, H, D)`` against the
    stored cache ``k/v (B, S, Hkv, D)`` (int8 when ``k_scale/v_scale
    (B, S, Hkv)`` are given, else any float dtype), attending to global
    key positions ``≤ pos`` (the query's position, a runtime scalar).
    Returns ``o (B, 1, H, D)`` in q.dtype. Callers gate on
    :func:`decode_supported` / :func:`use_pallas`.
    """
    B, T, H, D = q.shape
    if T != 1:
        raise ValueError(f"flash_decode is the T=1 step; got T={T}")
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    if H % Hkv != 0:
        raise ValueError(f"q heads ({H}) not a multiple of kv heads "
                         f"({Hkv})")
    if not decode_supported(S, D):
        raise ValueError(
            f"flash_decode: unsupported S={S} head_dim={D} — cache length "
            f"must divide into 8..256 blocks and head_dim ≤ "
            f"{_MAX_HEAD_DIM}; gate on decode_supported()")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    q4 = q.reshape(B, Hkv, H // Hkv, D)   # group-major head order
    ks = vs = None
    if k_scale is not None:
        ks = k_scale.astype(jnp.float32)      # stored (B, S, Hkv) layout
        vs = v_scale.astype(jnp.float32)
    o = _decode(q4, k_cache, v_cache, ks, vs, pos, _interpret())
    return o.reshape(B, 1, H, D)
