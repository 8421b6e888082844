"""Flash attention — Pallas TPU kernels for the transformer hot op.

No reference analog: the reference delegates attention math to torch/TF
kernels (its models live in example scripts, e.g.
``example/pytorch/benchmark_byteps.py``); on TPU the attention inner loop
is OURS to own, and it is the one op in the model families where the
naive form materializes a ``(B, H, S, S)`` score tensor in HBM.

Design (flash-attention-2 schedule, TPU-shaped):

* Layout ``(B*H, S, D)`` — batch×heads is the embarrassingly parallel
  grid axis; ``S`` is tiled into (bq, bk) blocks sized to the MXU
  (128 where the sequence allows); ``D`` (head_dim ≤ 256) stays whole so
  every matmul in the kernel is an MXU op on full tiles. v (and so o,
  do, dv) may have a width ``Dv`` of its own — latent attention scores
  on 192 and mixes values of 128 — which only the block shapes see.
* Forward: grid ``(BH, nq, nk)``, innermost ``nk`` sequential
  ("arbitrary") with the online-softmax state ``(m, l, acc)`` carried in
  VMEM scratch — scores for one ``(bq, bk)`` tile only ever exist in
  VMEM. Emits the per-row logsumexp for the backward and for cross-shard
  combination.
* Backward: ONE kernel (grid ``(BH, nk, nq)``) recomputes ``P = exp(S −
  lse)`` once a tile and feeds dq, dk and dv from it, dq resident in VMEM
  for its head; grouped-query shapes and a dq too long to stay keep the
  two-kernel form (``_bwd_plan``). ``delta = rowsum(dO ∘ O)`` is one jnp
  pass; the lse output's own cotangent folds in exactly (``dS = P ∘ (dP −
  Δ + dlse)``): ring attention differentiates through its merge with it.
* Causal masking compares *global* positions: the q/k sequence offsets
  are runtime scalars (SMEM), so the same compiled kernel serves the
  single-device case (offsets 0), and every step of ring attention —
  diagonal (part-masked), below-diagonal (all-live), above-diagonal
  (all-masked, skipped tile-by-tile by ``pl.when``). Rows with no live
  key yield ``o = 0, lse = −1e30`` and drop out of the ring merge.

Numerics: all accumulation in float32 regardless of input dtype (bf16
in, bf16 out, f32 state) — same contract as
:func:`byteps_tpu.parallel.ring_attention.plain_attention`, which is the
golden for the tests and the jnp fallback for shapes/platforms the
kernel doesn't cover.

Known jax limitation: ``BYTEPS_KERNEL_BACKEND=pallas`` off-TPU runs the
kernels in interpret mode, which jax cannot evaluate inside
``shard_map(check_vma=True)`` (its error suggests ``check_vma=False``;
kernel-internal program_id math can't be pvaried to the SMEM scalars'
varying axes). Compiled TPU kernels are unaffected — only the boundary
is vma-typed there (:func:`_out_struct` / :func:`_unify_vma`). Off-TPU
the default backend is jnp, so the check_vma=True train factories are
only incompatible with *forcing* pallas interpret mode under them.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_MAX_HEAD_DIM = 256     # D beyond this spills VMEM tile budgets → fallback


def _pick_block(S: int, prefer: Tuple[int, ...] = ()) -> Optional[int]:
    """Largest tile from ``prefer + (256..8)`` dividing S (None → jnp
    fallback). The default list keeps the 8..256 contract that
    ``supported()``/``flash_decode`` are documented and tuned against;
    the train kernels pass explicit larger preferences (below)."""
    cands = prefer + (256, 128, 64, 32, 16, 8)
    for b in cands:
        if S % b == 0 and S >= b:
            return b
    return None


# Block size is the dominant throughput knob on this kernel family.
# Measured on v5e at PR 42, bf16, the one-pass backward of one layer's
# call, ms by (bq, bk): GPT-2-medium (BH=128, S=1024, D=64) 512² 1.39,
# 512×1024 1.50, 1024×512 1.51, 256×512 1.63, 256² 2.07 (the two kernels
# at 512²: 2.61); JoyAI (BH=128, S=4096, D 192/128) 512² 20.7, 512×1024
# 19.9, 256×512 22.7, 256² 27.8 (two kernels: 36.3). The forward keeps
# whole-sequence k-tiles at S=1024 (3.7 against 4.6 ms at BH=32, before
# PR 1). BYTEPS_FLASH_BLOCK=N,... prepends experiment tiles (train kernels).
_FWD_PREFER = (1024, 512)
_BWD_PREFER = (512,)
_VMEM_BUDGET = 12 * 1024 * 1024   # leave headroom under the ~16MB VMEM


def _env_prefer() -> Tuple[int, ...]:
    force = os.environ.get("BYTEPS_FLASH_BLOCK")
    return tuple(int(x) for x in force.split(",")) if force else ()


def _live_bytes(bq: int, bk: int, D: int, Dv: int, itemsize: int,
                n_inter: int) -> int:
    """A train kernel's live set: ``n_inter`` (bq, bk) f32 intermediates —
    2 for the forward (s, p), 4 for a backward (s, p, dp, ds) — the q, (k,
    v)(, do) blocks double-buffered by the pallas pipeline (q and k blocks
    D wide; v, o and do blocks Dv wide), and the f32 accumulators."""
    inter = n_inter * bq * bk * 4
    io = 2 * 2 * (bq + bk) * (D + Dv) * itemsize
    scratch = (bq * Dv + bk * (D + Dv)) * 4
    return inter + io + scratch


def _train_blocks(Sq: int, Sk: int, D: int, itemsize: int,
                  prefer: Tuple[int, ...],
                  n_inter: int = 2,
                  Dv: Optional[int] = None) -> Optional[Tuple[int, int]]:
    """(bq, bk) for the train kernels — or None when either sequence has
    no dividing tile (the documented None→jnp-fallback contract that
    ``_pick_block``/``supported()`` establish; callers not pre-gated by
    ``supported()`` must get the same None, not a TypeError). Otherwise:
    the preferred large tiles, walked back down the candidate list until
    ``_live_bytes`` fits ``_VMEM_BUDGET`` — f32 or D→256 shapes degrade
    gracefully instead of blowing the Mosaic budget. The backward passes
    ``n_inter=4``, which steers it to 512 tiles while the forward keeps
    whole-sequence k-tiles. ``Dv`` is the value width where it differs
    from ``D`` (latent attention: 192 / 128)."""
    Dv = D if Dv is None else Dv

    def fits(bq: int, bk: int) -> bool:
        return _live_bytes(bq, bk, D, Dv, itemsize, n_inter) <= _VMEM_BUDGET

    prefer = _env_prefer() + prefer
    bq = _pick_block(Sq, prefer)
    bk = _pick_block(Sk, prefer)
    if bq is None or bk is None:
        return None
    while not fits(bq, bk):
        # shrink the larger tile first (s/p cost is the bq·bk product)
        nxt_q = _pick_block(Sq, tuple(p for p in prefer if p < bq))
        nxt_k = _pick_block(Sk, tuple(p for p in prefer if p < bk))
        if bq >= bk and nxt_q is not None and nxt_q < bq:
            bq = nxt_q
        elif nxt_k is not None and nxt_k < bk:
            bk = nxt_k
        elif nxt_q is not None and nxt_q < bq:
            bq = nxt_q
        else:
            break   # smallest divisible tiles; let Mosaic have it
    return bq, bk


from byteps_tpu.ops.backend import interpret as _interpret  # noqa: E402
from byteps_tpu.ops.backend import note_fallback as _note_fallback  # noqa: E402
from byteps_tpu.ops.backend import use_pallas  # noqa: E402 (re-export)


_UNSUPPORTED = ("sequence lengths must tile into 8..256 blocks and "
                f"head_dim be <= {_MAX_HEAD_DIM}")


def supported(Sq: int, Sk: int, D: int) -> bool:
    return (_pick_block(Sq) is not None and _pick_block(Sk) is not None
            and D <= _MAX_HEAD_DIM)


# --------------------------------------------------------------------------
# jnp fallback (also the numerics golden; mirrors ring_attention._block_attn)
# --------------------------------------------------------------------------
def attention_jnp(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True) -> jnp.ndarray:
    """Single-device softmax attention, (B, S, H, D) layout, f32 softmax."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Sq, Sk = q.shape[1], k.shape[1]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def _out_struct(shape, dtype, *args):
    """ShapeDtypeStruct whose vma is the union of the inputs' — required
    for pallas_call under ``shard_map(check_vma=True)`` (outputs vary over
    whatever mesh axes the inputs vary over)."""
    try:
        vma = frozenset().union(*(jax.typeof(a).vma for a in args))
    except (AttributeError, TypeError):
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _unify_vma(*xs):
    """pcast every array to the union of the group's varying axes, so the
    pallas_call boundary sees one consistent vma. (Interpret mode under
    check_vma=True still rejects kernel-internal program_id mixing — a
    known jax limitation whose error message recommends check_vma=False;
    the compiled TPU path only type-checks the boundary.)"""
    try:
        vmas = [jax.typeof(x).vma for x in xs]
    except AttributeError:
        return xs
    union = frozenset().union(*vmas)
    return tuple(
        jax.lax.pcast(x, tuple(union - v), to="varying") if union - v else x
        for x, v in zip(xs, vmas)
    )


def _zero_cotangent(x):
    """Zeros typed like ``x``, varying axes included: a custom-VJP rule
    must return each cotangent with its primal's vma, and the offsets
    reach the core already pcast to the q/k/v union (`_unify_vma`)."""
    z = jnp.zeros(x.shape, x.dtype)
    vma = tuple(getattr(jax.typeof(x), "vma", ()) or ())
    return jax.lax.pcast(z, vma, to="varying") if vma else z


def _read_offsets(qoff_ref, koff_ref):
    """Scalar SMEM loads (the only form mosaic allows)."""
    return (qoff_ref[0, 0].astype(jnp.int32),
            koff_ref[0, 0].astype(jnp.int32))


def _mask_tile(s, q_off, k_off, q_start, k_start, bq, bk, window=None,
               block=None):
    rows = q_off + q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = k_off + k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if block is not None:
        # block-causal: a query sees all of its own block of ``block``
        # positions (a power of two, counted from position 0), so every key
        # up to the block's last
        return jnp.where((rows | (block - 1)) >= cols, s, _NEG)
    if window is None:
        return jnp.where(rows >= cols, s, _NEG)
    # a window beside the causal rule: the query's own position and the
    # window - 1 before it; a key laid out before position 0 is padding
    return jnp.where((rows >= cols) & (rows - cols < window) & (cols >= 0),
                     s, _NEG)


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------
def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, bq, bk, nk,
                window=None, mask_ref=None, block=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    q_start, k_start = qi * bq, ki * bk
    q_off, k_off = _read_offsets(qoff_ref, koff_ref)

    def _tile(masked: bool):
        # operands stay in the INPUT dtype (bf16 in → MXU-native bf16
        # matmuls); preferred_element_type=f32 keeps the accumulation
        # exact, so s is bit-identical to an f32-operand dot for bf16
        # inputs (bf16→f32 casts are exact, the MXU multiplies bf16
        # pairs into an f32 accumulator either way)
        q = q_ref[0]                                         # (bq, D)
        k = k_ref[0]                                         # (bk, D)
        v = v_ref[0]                                         # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk)
        if mask_ref is not None:
            # the caller's (bq, bk) tile of allowed pairs, the same for
            # every head; it carries the causal rule
            s = jnp.where(mask_ref[...].astype(jnp.float32) > 0.5, s, _NEG)
        elif masked:
            s = _mask_tile(s, q_off, k_off, q_start, k_start, bq, bk, window,
                           block)
        m_prev = m_scr[:]                                    # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                               # (bq, bk)
        if masked or mask_ref is not None:
            # exp(_NEG - m) underflows to 0 except when the whole row is
            # masked (m == _NEG) — zero those lanes explicitly
            p = jnp.where(s > _NEG / 2, p, 0.0)
        l_scr[:] = l_scr[:] * alpha + p.sum(axis=-1, keepdims=True)
        # p rounds to the input dtype for the MXU (standard flash-on-TPU
        # practice; p ∈ [0,1] so bf16 rounding is ≤ 2⁻⁸ relative — the
        # same order as the bf16 output rounding); f32 inputs keep f32 p
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, Dv)
        m_scr[:] = m_new

    if causal:
        # tile live iff some global q_pos >= some global k_pos; INTERIOR
        # (min q_pos ≥ max k_pos, every pair live) skips the mask iotas
        # and the underflow where() — with big tiles the diagonal is a
        # 1/nk fraction, so most tiles take the cheap path
        live = q_off + q_start + bq - 1 >= k_off + k_start
        interior = q_off + q_start >= k_off + k_start + bk - 1
        if block is not None:
            # the tile's last query sees to the end of its block; a tile
            # every pair of which is causal is interior as it was
            live = ((q_off + q_start + bq - 1) | (block - 1)) \
                >= k_off + k_start
        if window is not None:
            # tiles wholly behind the window are skipped like those wholly
            # after the diagonal; an interior tile lies inside it for every
            # pair and holds no padding key
            live &= (q_off + q_start) - (k_off + k_start + bk - 1) < window
            interior &= ((q_off + q_start + bq - 1) - (k_off + k_start)
                         < window) & (k_off + k_start >= 0)

        @pl.when(live & interior)
        def _():
            _tile(False)

        @pl.when(live & jnp.logical_not(interior))
        def _():
            _tile(True)
    else:
        _tile(False)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:]                                          # (bq, 1)
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l > 0.0, m_scr[:] + jnp.log(l_safe), _NEG)


def _kv_index(heads: int, kv_heads: int):
    """Grid-index map from a (batch·H) query row to its (batch·Hkv) kv
    row — the GQA head-group association done by pure index arithmetic,
    so grouped attention reads the NARROW k/v (no repeated copies
    anywhere). Identity when heads == kv_heads."""
    if heads == kv_heads:
        return lambda b: b
    g = heads // kv_heads
    return lambda b: (b // heads) * kv_heads + (b % heads) // g


@functools.partial(jax.jit, static_argnames=("causal", "interpret",
                                             "heads", "kv_heads", "window",
                                             "name", "block"))
def _fwd(q3, k3, v3, qoff, koff, causal: bool, interpret: bool,
         heads: int, kv_heads: int, window: Optional[int] = None,
         mask=None, name: str = "flash_fwd", block: Optional[int] = None):
    """q3: (B·H, S, D), k3: (B·Hkv, S, D), v3: (B·Hkv, S, Dv) →
    (o (B·H, Sq, Dv), lse (B·H, Sq, 1) f32). The softmax scale is
    ``D ** -0.5``, the q/k width."""
    BH, Sq, D = q3.shape
    Sk, Dv = k3.shape[1], v3.shape[2]
    blocks = _train_blocks(Sq, Sk, D, q3.dtype.itemsize, _FWD_PREFER, Dv=Dv)
    if blocks is None:
        raise ValueError(
            f"flash forward kernel has no dividing tile for Sq={Sq}, "
            f"Sk={Sk} — gate call sites with supported() (jnp fallback)")
    bq, bk = blocks
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / (D ** 0.5)
    kv = _kv_index(heads, kv_heads)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, nk=nk)
    if window is not None:         # the causal-only trace stays as it was
        kern = functools.partial(kern, window=window)
    if block is not None:
        kern = functools.partial(kern, block=block)
    extra_specs, extra = [], ()
    if mask is not None:
        # one (Sq, Sk) int8 plane of allowed pairs for every head: the
        # block index leaves the head out
        def kern(qoff_ref, koff_ref, q_ref, k_ref, v_ref, mask_ref, *rest,
                 _inner=kern):
            _inner(qoff_ref, koff_ref, q_ref, k_ref, v_ref, *rest,
                   mask_ref=mask_ref)
        extra_specs = [pl.BlockSpec((bq, bk), lambda b, qi, ki: (qi, ki))]
        extra = (mask,)
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (kv(b), ki, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, qi, ki: (kv(b), ki, 0)),
        ] + extra_specs,
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            _out_struct((BH, Sq, Dv), q3.dtype, q3, k3, v3, qoff, koff),
            _out_struct((BH, Sq, 1), jnp.float32, q3, k3, v3, qoff, koff),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # m (row max)
            pltpu.VMEM((bq, 1), jnp.float32),    # l (row sum)
            pltpu.VMEM((bq, Dv), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(qoff, koff, q3, k3, v3, *extra)


# --------------------------------------------------------------------------
# backward kernels
# --------------------------------------------------------------------------
def _dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               dl_ref, dlse_ref, dq_ref, dq_scr,
               *, scale, causal, bq, bk, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    q_start, k_start = qi * bq, ki * bk
    q_off, k_off = _read_offsets(qoff_ref, koff_ref)

    def _tile(masked: bool):
        # input-dtype operands on every MXU dot (see _fwd_kernel note);
        # s/p/ds math stays f32, ds rounds to the input dtype only at
        # the dq GEMM boundary
        q = q_ref[0]                                         # (bq, D)
        k = k_ref[0]                                         # (bk, D)
        v = v_ref[0]                                         # (bk, Dv)
        do = do_ref[0]                                       # (bq, Dv)
        lse = lse_ref[0]                                     # (bq, 1)
        delta = dl_ref[0]                                    # (bq, 1)
        dlse = dlse_ref[0]                                   # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            s = _mask_tile(s, q_off, k_off, q_start, k_start, bq, bk)
        p = jnp.exp(s - lse)                                  # (bq, bk)
        if masked:
            p = jnp.where(s > _NEG / 2, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bq, bk)
        ds = (p * (dp - delta + dlse)).astype(k_ref.dtype)
        dq_scr[:] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bq, D)

    if causal:
        live = q_off + q_start + bq - 1 >= k_off + k_start
        interior = q_off + q_start >= k_off + k_start + bk - 1

        @pl.when(live & interior)
        def _():
            _tile(False)

        @pl.when(live & jnp.logical_not(interior))
        def _():
            _tile(True)
    else:
        _tile(False)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dl_ref, dlse_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, bq, bk, nq, group=1):
    ki = pl.program_id(1)
    j = pl.program_id(2)            # (group member, q block) flattened
    qi = j % nq

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    q_start, k_start = qi * bq, ki * bk
    q_off, k_off = _read_offsets(qoff_ref, koff_ref)

    def _tile(masked: bool):
        # input-dtype operands on every MXU dot (see _fwd_kernel note)
        q = q_ref[0]                                         # (bq, D)
        k = k_ref[0]                                         # (bk, D)
        v = v_ref[0]                                         # (bk, Dv)
        do = do_ref[0]                                       # (bq, Dv)
        lse = lse_ref[0]                                     # (bq, 1)
        delta = dl_ref[0]                                    # (bq, 1)
        dlse = dlse_ref[0]                                   # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            s = _mask_tile(s, q_off, k_off, q_start, k_start, bq, bk)
        p = jnp.exp(s - lse)                                  # (bq, bk)
        if masked:
            p = jnp.where(s > _NEG / 2, p, 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, Dv)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bq, bk)
        ds = (p * (dp - delta + dlse)).astype(q_ref.dtype)
        dk_scr[:] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, D)

    if causal:
        live = q_off + q_start + bq - 1 >= k_off + k_start
        interior = q_off + q_start >= k_off + k_start + bk - 1

        @pl.when(live & interior)
        def _():
            _tile(False)

        @pl.when(live & jnp.logical_not(interior))
        def _():
            _tile(True)
    else:
        _tile(False)

    @pl.when(j == nq * group - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


from byteps_tpu.common.metrics import get_registry  # noqa: E402

_FUSED_VMEM_CAP = 32 * 1024 * 1024   # half the smallest VMEM a TPU core has


def _bwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dd_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                *, scale, causal, bq, bk, nq, nk):
    """One pass over a head's tiles, kv block outer, q block inner: the
    tile's scores, probabilities, dp and ds are computed once and feed all
    three gradients. dk/dv accumulate over the inner axis as in
    ``_dkv_kernel``; dq for the WHOLE head accumulates in ``dq_scr`` at
    rows ``qi·bq`` and leaves through an output block whose index does not
    change inside the head. The tile is held TRANSPOSED, ``(bk, bq)``:
    ``pᵀ do`` and ``dsᵀ q`` are then plain products (the two-kernel form
    transposes both) and only ``ds k`` contracts over rows; the per-query
    statistics arrive as lane-dense ``(1, bq)`` rows."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)

    @pl.when(ki == 0)
    def _init_dq():
        dq_scr[rows, :] = jnp.zeros((bq, dq_scr.shape[1]), jnp.float32)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    q_start, k_start = qi * bq, ki * bk
    q_off, k_off = _read_offsets(qoff_ref, koff_ref)

    def _tile(masked: bool):
        # input-dtype operands on every MXU dot (see _fwd_kernel note);
        # p and ds round to the input dtype only at a product's boundary
        q = q_ref[0]                                         # (bq, D)
        k = k_ref[0]                                         # (bk, D)
        v = v_ref[0]                                         # (bk, Dv)
        do = do_ref[0]                                       # (bq, Dv)
        lse = lse_ref[0]                                     # (1, bq)
        dd = dd_ref[0]                                       # (1, bq)
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (bk, bq)
        if masked:
            kpos = k_off + k_start + jax.lax.broadcasted_iota(
                jnp.int32, (bk, bq), 0)
            qpos = q_off + q_start + jax.lax.broadcasted_iota(
                jnp.int32, (bk, bq), 1)
            st = jnp.where(qpos >= kpos, st, _NEG)
        pt = jnp.exp(st - lse)                                # (bk, bq)
        if masked:
            pt = jnp.where(st > _NEG / 2, pt, 0.0)
        dv_scr[:] += jax.lax.dot_general(
            pt.astype(do_ref.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, Dv)
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, bq)
        dst = (pt * (dpt - dd)).astype(q_ref.dtype)
        dk_scr[:] += scale * jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, D)
        dq_scr[rows, :] += scale * jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bq, D)

    if causal:
        live = q_off + q_start + bq - 1 >= k_off + k_start
        interior = q_off + q_start >= k_off + k_start + bk - 1

        @pl.when(live & interior)
        def _():
            _tile(False)

        @pl.when(live & jnp.logical_not(interior))
        def _():
            _tile(True)
    else:
        _tile(False)

    @pl.when(qi == nq - 1)
    def _finish_dkv():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(ki == nk - 1)
    def _finish_dq():
        dq_ref[0, rows, :] = dq_scr[rows, :].astype(dq_ref.dtype)


def _bwd_plan(Sq: int, Sk: int, D: int, Dv: int, itemsize: int,
              group: int) -> Optional[Tuple[int, int, Optional[int]]]:
    """``(bq, bk, need)`` of the backward from the shapes alone, None where
    no tile divides a sequence. ``need`` is the fused kernel's live set in
    bytes — ``_live_bytes`` of a backward tile plus the head's resident dq
    (f32) and its output block (twice: the pipeline's two buffers) — or None
    where the two-kernel form runs: a grouped-query shape (one kv row's
    tiles meet ``group`` q rows, whose dq would all have to stay), a q tile
    that is neither whole lanes nor the whole sequence (the statistics' row
    blocks), and a set over ``_FUSED_VMEM_CAP``."""
    blocks = _train_blocks(Sq, Sk, D, itemsize, _BWD_PREFER, n_inter=4,
                           Dv=Dv)
    if blocks is None:
        return None
    bq, bk = blocks
    lanes = -(-D // 128) * 128      # a VMEM row holds whole 128-lane vregs
    need = (_live_bytes(bq, bk, D, Dv, itemsize, n_inter=4)
            + Sq * lanes * (4 + 2 * itemsize))
    fused = (group == 1 and (bq % 128 == 0 or bq == Sq)
             and need <= _FUSED_VMEM_CAP)
    return bq, bk, need if fused else None


@functools.partial(jax.jit, static_argnames=("causal", "interpret",
                                             "heads", "kv_heads"))
def _bwd(q3, k3, v3, o3, lse, qoff, koff, do3, dlse,
         causal: bool, interpret: bool, heads: int, kv_heads: int):
    BH, Sq, D = q3.shape
    BHkv, Sk, Dv = k3.shape[0], k3.shape[1], v3.shape[2]
    group = heads // kv_heads
    plan = _bwd_plan(Sq, Sk, D, Dv, q3.dtype.itemsize, group)
    if plan is None:
        raise ValueError(
            f"flash backward kernel has no dividing tile for Sq={Sq}, "
            f"Sk={Sk} — gate call sites with supported() (jnp fallback)")
    bq, bk, need = plan
    nq, nk = Sq // bq, Sk // bk
    kv = _kv_index(heads, kv_heads)
    scale = 1.0 / (D ** 0.5)
    # which form this program took, counted where it is chosen: at trace time
    get_registry().counter(
        "flash.bwd_split" if need is None else "flash.bwd_fused").inc()
    # delta_i = Σ_d dO_id · O_id  (one fused elementwise pass, f32)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # (BH, Sq, 1)

    if need is not None:
        # dS = P ∘ (dP − (Δ − dlse)): the two statistics of a query ride
        # that one pass, as rows along the lanes
        like = _unify_vma(qoff, koff, q3, k3, v3, do3,
                          lse.reshape(BH, 1, Sq),
                          (delta - dlse).reshape(BH, 1, Sq))
        return pl.pallas_call(
            functools.partial(_bwd_kernel, scale=scale, causal=causal,
                              bq=bq, bk=bk, nq=nq, nk=nk),
            grid=(BH, nk, nq),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, bq, D), lambda b, ki, qi: (b, qi, 0)),
                pl.BlockSpec((1, bk, D), lambda b, ki, qi: (b, ki, 0)),
                pl.BlockSpec((1, bk, Dv), lambda b, ki, qi: (b, ki, 0)),
                pl.BlockSpec((1, bq, Dv), lambda b, ki, qi: (b, qi, 0)),
                pl.BlockSpec((1, 1, bq), lambda b, ki, qi: (b, 0, qi)),
                pl.BlockSpec((1, 1, bq), lambda b, ki, qi: (b, 0, qi)),
            ],
            out_specs=[
                pl.BlockSpec((1, Sq, D), lambda b, ki, qi: (b, 0, 0)),
                pl.BlockSpec((1, bk, D), lambda b, ki, qi: (b, ki, 0)),
                pl.BlockSpec((1, bk, Dv), lambda b, ki, qi: (b, ki, 0)),
            ],
            out_shape=[
                _out_struct((BH, Sq, D), q3.dtype, *like),
                _out_struct((BH, Sk, D), k3.dtype, *like),
                _out_struct((BH, Sk, Dv), v3.dtype, *like),
            ],
            scratch_shapes=[
                pltpu.VMEM((Sq, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, Dv), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=(None if need <= _VMEM_BUDGET
                                  else need + 2 * 1024 * 1024)),
            interpret=interpret,
            # the name the two-kernel form's larger half has: what reads a
            # device trace by kernel name sums the backward either way
            name="flash_bwd_dkv",
        )(*like)

    q3, k3, v3, do3, lse, delta, dlse, qoff, koff = _unify_vma(
        q3, k3, v3, do3, lse, delta, dlse, qoff, koff)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (kv(b), ki, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, qi, ki: (kv(b), ki, 0)),
            pl.BlockSpec((1, bq, Dv), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=_out_struct((BH, Sq, D), q3.dtype,
                              q3, k3, v3, do3, lse, delta, dlse, qoff, koff),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qoff, koff, q3, k3, v3, do3, lse, delta, dlse)

    # dkv iterates every (group member, q block) for its kv head: the q
    # row for grid point (b, ki, j) is the (j // nq)-th member of kv row
    # b's group, q block j % nq — one scratch accumulation covers the
    # whole group, so dk/dv come out kv-narrow with no reduction pass
    def qrow(b, j):
        return (b // kv_heads) * heads + (b % kv_heads) * group + j // nq

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, group=group),
        grid=(BHkv, nk, nq * group),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, D), lambda b, ki, j: (qrow(b, j), j % nq, 0)),
            pl.BlockSpec((1, bk, D), lambda b, ki, j: (b, ki, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, ki, j: (b, ki, 0)),
            pl.BlockSpec((1, bq, Dv), lambda b, ki, j: (qrow(b, j), j % nq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, ki, j: (qrow(b, j), j % nq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, ki, j: (qrow(b, j), j % nq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, ki, j: (qrow(b, j), j % nq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, ki, j: (b, ki, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, ki, j: (b, ki, 0)),
        ],
        out_shape=[
            _out_struct((BHkv, Sk, D), k3.dtype,
                        q3, k3, v3, do3, lse, delta, dlse, qoff, koff),
            _out_struct((BHkv, Sk, Dv), v3.dtype,
                        q3, k3, v3, do3, lse, delta, dlse, qoff, koff),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qoff, koff, q3, k3, v3, do3, lse, delta, dlse)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom-VJP core on the (BH, S, D) layout
# --------------------------------------------------------------------------
# qoff/koff are (1, 1) float32 on purpose: they are *traced* values (ring
# attention passes axis_index-derived offsets), and float avoids the
# symbolic-zero cotangent dance custom_vjp requires for int-dtype
# arguments — their gradient is identically zero.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_core(q3, k3, v3, qoff, koff, causal: bool, interpret: bool,
                heads: int, kv_heads: int):
    return _fwd(q3, k3, v3, qoff, koff, causal, interpret, heads, kv_heads)


def _flash_core_fwd(q3, k3, v3, qoff, koff, causal, interpret, heads,
                    kv_heads):
    o, lse = _fwd(q3, k3, v3, qoff, koff, causal, interpret, heads,
                  kv_heads)
    return (o, lse), (q3, k3, v3, o, lse, qoff, koff)


def _flash_core_bwd(causal, interpret, heads, kv_heads, res, cts):
    q3, k3, v3, o3, lse, qoff, koff = res
    do3, dlse = cts
    dlse = jnp.asarray(dlse, jnp.float32)
    dq, dk, dv = _bwd(q3, k3, v3, o3, lse, qoff, koff, do3, dlse,
                      causal, interpret, heads, kv_heads)
    return dq, dk, dv, _zero_cotangent(qoff), _zero_cotangent(koff)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _to3(x: jnp.ndarray) -> jnp.ndarray:
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _from3(x3: jnp.ndarray, B: int, H: int) -> jnp.ndarray:
    BH, S, D = x3.shape
    return x3.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def flash_attention_lse(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        q_offset, k_offset,
                        causal: bool = True
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Flash attention with logsumexp, for cross-shard combination.

    q/k/v: (B, S, H, D); offsets are (possibly traced) global sequence
    positions of element 0 of the q/k blocks — causal masking compares
    ``q_offset + i >= k_offset + j``. Returns ``(o (B, Sq, H, D),
    lse (B, Sq, H) f32)``; rows with no live key give ``o = 0,
    lse = −1e30`` so a ring merge drops them. Callers must check
    :func:`supported` / :func:`use_pallas` first.
    """
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv != 0:
        raise ValueError(f"q heads ({H}) not a multiple of kv heads "
                         f"({Hkv})")
    if v.shape[2] != Hkv:
        raise ValueError(f"k has {Hkv} heads but v has {v.shape[2]} — "
                         "GQA narrows k and v together")
    if not supported(Sq, k.shape[1], D):
        raise ValueError(
            f"flash_attention_lse: unsupported shape Sq={Sq} Sk={k.shape[1]} "
            f"head_dim={D} — sequence lengths must divide into 8..256 tiles "
            f"and head_dim must be ≤ {_MAX_HEAD_DIM}; gate on "
            "byteps_tpu.ops.flash_attention.supported() or use "
            "flash_attention()/attention_jnp() which fall back")
    qoff = jnp.asarray(q_offset, jnp.float32).reshape(1, 1)
    koff = jnp.asarray(k_offset, jnp.float32).reshape(1, 1)
    q3, k3, v3, qoff, koff = _unify_vma(_to3(q), _to3(k), _to3(v),
                                        qoff, koff)
    o3, lse3 = _flash_core(q3, k3, v3, qoff, koff, causal, _interpret(),
                           H, Hkv)
    o = _from3(o3, B, H)
    lse = lse3.reshape(B, H, Sq).transpose(0, 2, 1)           # (B, Sq, H)
    return o, lse


def attention_lse_jnp(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      q_offset, k_offset, causal: bool = True
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """jnp twin of :func:`flash_attention_lse` — same (o, lse) contract,
    same global-offset causal masking and −1e30 ≡ no-live-keys signal, any
    shape. The golden for the kernel and the fallback for ring schedules
    off-TPU. Grouped-query attention is native: when q carries G× the
    k/v head count, each kv head serves its group through the einsum —
    no materialized head repeat (the GQA decode hot path).

    ``q_offset`` may be a per-batch ``(B,)`` vector: row ``b``'s queries
    sit at global positions ``q_offset[b] + arange(Sq)``. That is the
    serve tier's packed-decode contract — one device batch holds
    requests at heterogeneous sequence positions (serve/paged_cache.py),
    and each row masks against its own fill level."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    scale = 1.0 / (D ** 0.5)
    if Hkv != H:
        if H % Hkv != 0:
            raise ValueError(f"q heads ({H}) not a multiple of kv heads "
                             f"({Hkv})")
        g = H // Hkv
        qg = q.reshape(B, Sq, Hkv, g, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        s = s.reshape(B, H, Sq, Sk)
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
    if causal:
        if jnp.ndim(q_offset) == 1:
            # per-batch offsets: (B, Sq, Sk) mask broadcast over heads
            rows = (jnp.asarray(q_offset)[:, None, None]
                    + jnp.arange(Sq)[None, :, None])
            cols = k_offset + jnp.arange(Sk)[None, None, :]
            s = jnp.where((rows >= cols)[:, None], s, _NEG)
        else:
            rows = q_offset + jnp.arange(Sq)[:, None]
            cols = k_offset + jnp.arange(Sk)[None, :]
            s = jnp.where((rows >= cols)[None, None], s, _NEG)
    m = s.max(axis=-1)                                   # (B, H, Sq)
    live = m > _NEG / 2
    m_safe = jnp.where(live, m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    if causal:
        p = jnp.where(s > _NEG / 2, p, 0.0)
    l = p.sum(axis=-1)
    l_safe = jnp.where(l > 0.0, l, 1.0)
    pn = p / l_safe[..., None]
    if Hkv != H:
        pn = pn.reshape(B, Hkv, H // Hkv, Sq, Sk)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", pn, v.astype(jnp.float32))
        o = o.reshape(B, Sq, H, D)
    else:
        o = jnp.einsum("bhqk,bkhd->bqhd", pn, v.astype(jnp.float32))
    o = jnp.where(live.transpose(0, 2, 1)[..., None], o, 0.0)
    lse = jnp.where(live, m_safe + jnp.log(l_safe), _NEG)
    return o.astype(q.dtype), lse.transpose(0, 2, 1)     # (B, Sq, H)


def attention_lse(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  q_offset, k_offset, causal: bool = True
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Backend-dispatching (o, lse) attention with global offsets — the
    building block ring schedules merge with :func:`merge_attention`.
    Grouped-query attention (q heads a multiple of k/v heads) is native
    on both backends — the kernel associates each query head with its kv
    head by grid-index arithmetic, so the narrow k/v is read directly.
    A per-batch ``(B,)`` ``q_offset`` vector (the serve tier's packed
    decode) always takes the jnp twin — the kernel's grid masking is
    scalar-offset only."""
    if use_pallas():
        if jnp.ndim(q_offset) != 0:
            _note_fallback("attention_lse", q.shape + k.shape,
                           "per-row q_offset; the kernel masks by one "
                           "scalar offset")
        elif not supported(q.shape[1], k.shape[1], q.shape[-1]):
            _note_fallback("attention_lse", q.shape + k.shape, _UNSUPPORTED)
        else:
            return flash_attention_lse(q, k, v, q_offset, k_offset,
                                       causal=causal)
    return attention_lse_jnp(q, k, v, q_offset, k_offset, causal=causal)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True) -> jnp.ndarray:
    """Softmax attention, (B, S, H, D) layout, flash kernel when possible.

    Drop-in numerics-equivalent of :func:`attention_jnp` (f32 accumulate,
    output in input dtype); falls back to it off-TPU (unless
    ``BYTEPS_KERNEL_BACKEND=pallas`` forces interpret mode) and for
    sequence lengths not divisible into MXU tiles. Differentiable via the
    flash backward kernels — O(S·D) memory in both passes.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if use_pallas():
        if supported(Sq, Sk, D):
            o, _ = flash_attention_lse(q, k, v, 0, 0, causal=causal)
            return o
        _note_fallback("flash_attention", q.shape + k.shape, _UNSUPPORTED)
    if k.shape[2] != H:
        o, _ = attention_lse_jnp(q, k, v, 0, 0, causal=causal)
        return o
    return attention_jnp(q, k, v, causal=causal)


def attention_window_jnp(q, k, v, q_offset, k_offset, window: int):
    """jnp twin of :func:`flash_attention_window`: query ``i`` (global
    position ``q_offset + i``) sees the keys at global positions ``p`` with
    ``0 <= p <= its own`` and ``its own - p < window``; ``(B, S, H, D)``
    layout, v of its own width, f32 softmax."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    # GQA: query head j reads kv head j // (H / Hkv), by a grouped product
    qg = q.reshape(B, Sq, Hkv, H // Hkv, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    rows = q_offset + jnp.arange(Sq)[:, None]
    cols = k_offset + jnp.arange(k.shape[1])[None, :]
    ok = (rows >= cols) & (rows - cols < window) & (cols >= 0)
    p = jax.nn.softmax(jnp.where(ok[None, None, None], s, _NEG), axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def flash_attention_window(q, k, v, q_offset, k_offset, window: int):
    """Causal attention inside a window of ``window`` keys (the query's own
    position counted), forward only — serving's sliding layers. The flash
    forward kernel with the window a trace-time constant of its mask: tiles
    wholly behind the window are skipped as those after the diagonal are.
    Offsets are the global positions of element 0 of q and of k (traced
    scalars); keys laid out before position 0 are padding and never seen.
    Every query has a live key (its own), so no row is empty. k and v may
    carry fewer heads than q (GQA: the kernel maps a query head to its kv
    head by index, as the causal forward does). Falls back to
    :func:`attention_window_jnp` off the Pallas backend and for shapes the
    kernel does not tile."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv != 0 or v.shape[2] != Hkv:
        raise ValueError(f"q heads ({H}) not a multiple of the k/v heads "
                         f"({Hkv}, {v.shape[2]})")
    if use_pallas():
        if supported(Sq, k.shape[1], D):
            qoff = jnp.asarray(q_offset, jnp.float32).reshape(1, 1)
            koff = jnp.asarray(k_offset, jnp.float32).reshape(1, 1)
            o3, _ = _fwd(_to3(q), _to3(k), _to3(v), qoff, koff, True,
                         _interpret(), H, Hkv, window=int(window))
            return _from3(o3, B, H)
        _note_fallback("flash_attention_window", q.shape + k.shape,
                       _UNSUPPORTED)
    return attention_window_jnp(q, k, v, q_offset, k_offset, window)


def attention_block_causal_jnp(q, k, v, q_offset, k_offset, block: int):
    """jnp twin of :func:`flash_attention_block_causal`: query ``i`` (global
    position ``q_offset + i``) sees the keys at global positions ``p`` with
    ``p // block <= its own // block``; ``(B, S, H, D)`` layout, f32
    softmax."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, Sq, Hkv, H // Hkv, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    rows = (q_offset + jnp.arange(Sq)[:, None]) // block
    cols = (k_offset + jnp.arange(k.shape[1])[None, :]) // block
    p = jax.nn.softmax(jnp.where((rows >= cols)[None, None, None], s, _NEG),
                       axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def flash_attention_block_causal(q, k, v, q_offset, k_offset, block: int):
    """Block-causal attention, forward only — the prefill of a model that
    generates by diffusion over blocks: a query sees every key of an earlier
    block of ``block`` positions (a power of two; blocks counted from global
    position 0) and all of its own. The flash forward kernel with ``block`` a
    trace-time constant of its mask and of its tile skip, as the window is.
    Offsets are the global positions of element 0 of q and of k (traced
    scalars). GQA by index. Falls back to
    :func:`attention_block_causal_jnp` off the Pallas backend and for shapes
    the kernel does not tile."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv != 0 or v.shape[2] != Hkv:
        raise ValueError(f"q heads ({H}) not a multiple of the k/v heads "
                         f"({Hkv}, {v.shape[2]})")
    if block < 1 or block & (block - 1):
        raise ValueError(f"block must be a power of two; got {block}")
    if use_pallas():
        if supported(Sq, k.shape[1], D):
            qoff = jnp.asarray(q_offset, jnp.float32).reshape(1, 1)
            koff = jnp.asarray(k_offset, jnp.float32).reshape(1, 1)
            o3, _ = _fwd(_to3(q), _to3(k), _to3(v), qoff, koff, True,
                         _interpret(), H, Hkv, block=int(block))
            return _from3(o3, B, H)
        _note_fallback("flash_attention_block_causal", q.shape + k.shape,
                       _UNSUPPORTED)
    return attention_block_causal_jnp(q, k, v, q_offset, k_offset, block)


def attention_masked_jnp(q, k, v, mask):
    """jnp twin of :func:`flash_attention_masked`: softmax over the keys
    ``mask (Sq, Sk)`` (non-zero: allowed) lets each query see, the same for
    every batch row and head; f32 softmax, v of its own width."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where((mask != 0)[None, None], s, _NEG), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def flash_attention_masked(q, k, v, mask, q_offset, k_offset,
                           name: str = "flash_fwd_masked"):
    """Attention over an arbitrary set of keys a query, forward only:
    ``mask (Sq, Sk)`` int8 says which pairs exist (it carries the causal
    rule; every query must keep a key) and is the same for every head, so a
    head's kernel step reads one ``(bq, bk)`` int8 tile beside its k and v
    tiles. Tiles wholly after the diagonal of the global positions
    (``q_offset``, ``k_offset``) are skipped. Serving's selected attention
    over materialised keys (``serve/latent_step.py``: the mask is the
    learned indexer's pick). ``name`` is the kernel's in a device trace.
    Falls back to :func:`attention_masked_jnp` off the Pallas backend and
    for shapes the kernel does not tile."""
    B, Sq, H, D = q.shape
    if use_pallas():
        if supported(Sq, k.shape[1], D) and k.shape[2] == H and Sq % 32 == 0:
            qoff = jnp.asarray(q_offset, jnp.float32).reshape(1, 1)
            koff = jnp.asarray(k_offset, jnp.float32).reshape(1, 1)
            o3, _ = _fwd(_to3(q), _to3(k), _to3(v), qoff, koff, True,
                         _interpret(), H, H, mask=mask.astype(jnp.int8),
                         name=name)
            return _from3(o3, B, H)
        _note_fallback(name, q.shape + k.shape, _UNSUPPORTED)
    return attention_masked_jnp(q, k, v, mask)


def merge_attention(o_a, lse_a, o_b, lse_b):
    """Combine two attention partials over disjoint key sets.

    o: (B, S, H, D) normalized outputs; lse: (B, S, H) logsumexps
    (−1e30 ≡ no live keys). Returns the merged (o, lse). Exact (not an
    approximation) and differentiable — gradients flow into both o's and
    both lse's, which the flash backward folds into dS.
    """
    m = jnp.maximum(lse_a, lse_b)
    wa = jnp.exp(lse_a - m)
    wb = jnp.exp(lse_b - m)
    denom = wa + wb
    safe = jnp.where(denom > 0.0, denom, 1.0)
    o = (o_a.astype(jnp.float32) * wa[..., None]
         + o_b.astype(jnp.float32) * wb[..., None]) / safe[..., None]
    lse = jnp.where(denom > 0.0, m + jnp.log(safe), _NEG)
    return o.astype(o_a.dtype), lse
