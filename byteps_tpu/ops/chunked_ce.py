"""Fused readout→cross-entropy: the [B, S, V] logits never exist in HBM.

The round-5 xprof attribution (docs/performance.md §attribution) measured
the flagship's f32 ``[8, 512, 32768]`` CE-loss chain at 21.7% of the step
— ~5.4 ms of pure HBM streaming through logits + softmax intermediates
(the readout matmul itself already runs at MXU rate). The remedy is the
same trick the flash kernels use for attention: process the readout GEMM
and the softmax **blockwise** with online max/sum-exp accumulation, so
only one row-block's logits are live at a time, and **recompute** them in
the backward instead of saving them.

:func:`chunked_ce_nll` is the drop-in for
``_nll(head_dot(h, head), targets)`` (models/gpt.py): per-token NLL with
a custom VJP that

* scans the flattened ``(N, d)`` hidden states in row blocks
  (``row_block`` rows at a time; ≤64 MiB of f32 logits live per block by
  default — see ``_default_row_block`` — instead of the full N·V array),
* optionally sub-chunks the vocab axis inside each row block
  (``vocab_block``) with online max/sum-exp accumulation — the long-V
  memory lever,
* recomputes each block's logits in the backward from the saved
  ``(h, head)`` residuals + the per-row logsumexp (an (N,) f32 vector —
  the only extra forward output),
* keeps the ``head_dot`` precision contract: dot operands in the
  ACTIVATION dtype, f32 accumulation, activation-dtype ``dh``, f32
  ``dhead`` (the optimizer's master-weight gradient loses nothing).

**Vocab-parallel (tp) variant**: with ``tp_axis`` set, each device
computes only its ``V/ntp`` column slice of the readout (riding the same
col-parallel split the block matmuls use — the head weight stays
replicated, sliced at ``axis_index(tp)``), and the per-block row
max / sum-exp / target-logit are combined over tp (pmax + psum) before
the log-partition. FLOPs and live logits both drop by ntp; the backward
assembles ``dh``/``dhead`` with one psum each, so gradients keep the
replicated-weight contract the dense path has (VMA and no-VMA modes both
— see models/train.py's grad-assembly notes).

Numerics: the single-device, single-vocab-chunk path mirrors
``log_softmax``'s exact operation order (max, exp-shift, sum, log) and is
**bit-exact** with the dense ``_nll(head_dot(...))`` chain at f32; vocab
sub-chunking and the tp combine change the sum-exp association order and
are pinned to f32-roundoff tolerance instead
(tests/test_chunked_ce.py). The dense twin :func:`dense_ce_nll` is the
golden and the ``chunked_ce=False`` escape hatch on every train-step
factory routes production back to it.

Design note — why lax.scan blocks, not a Mosaic kernel: the measured
cost was the *materialization* (N·V f32 arrays streamed ~8×/step), not
the per-element math. Blockwise XLA already deletes that — the per-block
softmax stats and dlogits are elementwise/reduce consumers XLA fuses
onto the block GEMM's output, so the remaining traffic is the ~4 passes
a hand kernel would also pay for the GEMM operands/results it spills at
these shapes (one (512, 32768) f32 tile is 32× VMEM — a Pallas CE kernel
still round-trips HBM per vocab tile, saving ~1 pass). The scan form
keeps the path portable (CPU tier-1 pins it bit-exactly), VJP-exact
under remat/pipeline, and free of Mosaic compile risk on backends this
repo can't test against; if a future attribution shows the residual
passes matter, the flash kernels' (forward, backward)-style split is the
shape a kernel port would take.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _f32_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """`a @ b` with f32 accumulation — the head_dot contract's dot."""
    from byteps_tpu.ops.flash_attention import _unify_vma

    au, bu = _unify_vma(a, b)
    return jax.lax.dot_general(
        au, bu, (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _vma(x) -> frozenset:
    try:
        return frozenset(jax.typeof(x).vma)
    except (AttributeError, TypeError):
        return frozenset()


def _default_row_block(n_rows: int, v_loc: int) -> int:
    """Largest power-of-two row count keeping one block's f32 logits
    ≤ 64 MiB — small enough that the full (B, S, V) chain never exists
    (the flagship's was 537 MB ×~8 HBM passes), large enough that the
    per-block readout GEMM keeps an MXU-efficient row dimension and the
    scan stays at ~8 steps (flagship V=32768 → 512 rows; gpt2m V=50304 →
    256). Clamped to [16, n_rows]."""
    budget = (64 * 1024 * 1024) // 4         # f32 elements per block
    if n_rows * max(v_loc, 1) <= budget:
        # whole batch in one block: no padding and no block-level
        # reassociation, so per-device numerics cannot depend on how a
        # mesh happens to split N — the cross-mesh equivalence pins
        # (dp vs dp×tp, etc.) see exactly the dense path's GEMM shapes
        return max(n_rows, 1)
    rb = 16
    while rb * 2 * max(v_loc, 1) <= budget:
        rb *= 2
    return rb


def _vocab_slices(v_loc: int, vocab_block: Optional[int]):
    """Static (start, width) slices covering the local vocab."""
    if not vocab_block or vocab_block >= v_loc:
        return [(0, v_loc)]
    return [(s, min(vocab_block, v_loc - s))
            for s in range(0, v_loc, vocab_block)]


def _local_head(head: jnp.ndarray, bias, tp_axis: Optional[str]):
    """This device's column slice of the (replicated) head/bias plus its
    vocab offset: the whole head when ``tp_axis`` is None or V doesn't
    split evenly; otherwise the ``V/ntp`` slice at ``axis_index(tp)``."""
    V = head.shape[1]
    if tp_axis is None:
        return head, bias, jnp.int32(0), V
    ntp = jax.lax.axis_size(tp_axis)
    if ntp == 1 or V % ntp != 0:
        return head, bias, jnp.int32(0), V
    v_loc = V // ntp
    off = (jax.lax.axis_index(tp_axis) * v_loc).astype(jnp.int32)
    head_loc = jax.lax.dynamic_slice(head, (jnp.int32(0), off),
                                     (head.shape[0], v_loc))
    bias_loc = (None if bias is None
                else jax.lax.dynamic_slice(bias, (off,), (v_loc,)))
    return head_loc, bias_loc, off, v_loc


def _block_stats(h_blk, head_loc, bias_loc, tgt_blk, off, vocab_block):
    """One row block's (m, s, t): running row max, sum-exp at that max,
    and the (shift-free) target logit masked to this vocab shard.

    Single vocab slice → exactly log_softmax's op order (bit-exact with
    the dense chain); multiple slices → online max/sum-exp accumulation.
    """
    rows = h_blk.shape[0]
    v_loc = head_loc.shape[1]
    head_c = head_loc.astype(h_blk.dtype)
    local_t = tgt_blk.astype(jnp.int32) - off
    in_range = (local_t >= 0) & (local_t < v_loc)
    slices = _vocab_slices(v_loc, vocab_block)
    if len(slices) == 1:
        z = _f32_dot(h_blk, head_c)
        if bias_loc is not None:
            z = z + bias_loc
        m = z.max(axis=-1)
        s = jnp.exp(z - m[:, None]).sum(axis=-1)
        tv = jnp.take_along_axis(
            z, jnp.clip(local_t, 0, v_loc - 1)[:, None], axis=-1)[:, 0]
        t = jnp.where(in_range, tv, 0.0)
        return m, s, t
    m = jnp.full((rows,), -jnp.inf, jnp.float32)
    s = jnp.zeros((rows,), jnp.float32)
    t = jnp.zeros((rows,), jnp.float32)
    for start, width in slices:
        z = _f32_dot(h_blk, head_c[:, start:start + width])
        if bias_loc is not None:
            z = z + bias_loc[start:start + width]
        m_new = jnp.maximum(m, z.max(axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.exp(z - m_new[:, None]).sum(axis=-1)
        m = m_new
        sel = local_t - start
        hit = in_range & (sel >= 0) & (sel < width)
        tv = jnp.take_along_axis(
            z, jnp.clip(sel, 0, width - 1)[:, None], axis=-1)[:, 0]
        t = t + jnp.where(hit, tv, 0.0)
    return m, s, t


def _pad_rows(x, rb: int):
    n = x.shape[0]
    nb = -(-n // rb)
    pad = nb * rb - n
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    return x, nb


def _fwd_scan(h2, head, bias, tgt, tp_axis, row_block, vocab_block):
    """(nll (N,), lse (N,)) via a row-block scan; collectives over tp
    combine the per-shard stats before the log-partition."""
    N = h2.shape[0]
    head_loc, bias_loc, off, v_loc = _local_head(head, bias, tp_axis)
    tp_split = v_loc != head.shape[1]   # vocab-parallel actually active
    rb = row_block or _default_row_block(N, head_loc.shape[1])
    h_pad, nb = _pad_rows(h2, rb)
    t_pad, _ = _pad_rows(tgt, rb)
    h_blks = h_pad.reshape(nb, rb, h2.shape[1])
    t_blks = t_pad.reshape(nb, rb)

    def body(carry, blk):
        h_blk, tgt_blk = blk
        m, s, t = _block_stats(h_blk, head_loc, bias_loc, tgt_blk, off,
                               vocab_block)
        if tp_split:
            m_g = jax.lax.pmax(m, tp_axis)
            s = jax.lax.psum(s * jnp.exp(m - m_g), tp_axis)
            t = jax.lax.psum(t, tp_axis)
            m = m_g
        # nll = logsumexp − target logit, associated exactly as
        # -log_softmax[target] is: log(Σexp(z−m)) − (z_t − m)
        lse = m + jnp.log(s)
        nll = jnp.log(s) - (t - m)
        return carry, (nll, lse)

    if nb == 1:
        _, (nll, lse) = body(None, (h_blks[0], t_blks[0]))
        return nll[:N], lse[:N]
    _, (nll, lse) = jax.lax.scan(body, None, (h_blks, t_blks))
    return nll.reshape(-1)[:N], lse.reshape(-1)[:N]


def _bwd_scan(h2, head, bias, tgt, lse, g, tp_axis, row_block, vocab_block):
    """Recompute-in-backward: per row block, rebuild the logits from
    (h, head), form ``dlogits = (softmax − onehot(target)) · g`` and
    accumulate ``dh`` (stacked) and ``dhead``/``dbias`` (f32 carries)."""
    N, d = h2.shape
    head_loc, bias_loc, off, v_loc = _local_head(head, bias, tp_axis)
    head_c = head_loc.astype(h2.dtype)
    rb = row_block or _default_row_block(N, v_loc)
    h_pad, nb = _pad_rows(h2, rb)
    t_pad, _ = _pad_rows(tgt, rb)
    lse_pad, _ = _pad_rows(lse, rb)
    g_pad, _ = _pad_rows(g.astype(jnp.float32), rb)
    h_blks = h_pad.reshape(nb, rb, d)
    t_blks = t_pad.reshape(nb, rb)
    lse_blks = lse_pad.reshape(nb, rb)
    g_blks = g_pad.reshape(nb, rb)
    slices = _vocab_slices(v_loc, vocab_block)

    def body(carry, blk):
        dhead_acc, dbias_acc = carry
        h_blk, tgt_blk, lse_blk, g_blk = blk
        local_t = tgt_blk.astype(jnp.int32) - off
        in_range = (local_t >= 0) & (local_t < v_loc)
        dh_blk = jnp.zeros((rb, d), jnp.float32)
        dhs, dbs = [], []
        for start, width in slices:
            z = _f32_dot(h_blk, head_c[:, start:start + width])
            if bias_loc is not None:
                z = z + bias_loc[start:start + width]
            p = jnp.exp(z - lse_blk[:, None])
            sel = local_t - start
            hit = in_range & (sel >= 0) & (sel < width)
            onehot = (jax.nn.one_hot(jnp.clip(sel, 0, width - 1), width,
                                     dtype=jnp.float32)
                      * hit[:, None].astype(jnp.float32))
            dz = ((p - onehot) * g_blk[:, None]).astype(h_blk.dtype)
            # dh accumulates over vocab slices; dhead/dbias over row blocks
            dh_blk = dh_blk + jax.lax.dot_general(
                dz, head_c[:, start:start + width],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dhs.append(jax.lax.dot_general(
                h_blk, dz, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            if bias_loc is not None:
                dbs.append(dz.astype(jnp.float32).sum(axis=0))
        dhead_acc = dhead_acc + jnp.concatenate(dhs, axis=1)
        if dbias_acc is not None:
            dbias_acc = dbias_acc + jnp.concatenate(dbs, axis=0)
        return (dhead_acc, dbias_acc), dh_blk

    # the f32 accumulators must carry the union vma of everything the body
    # touches or the scan carry would not be a type fixed point
    from byteps_tpu.ops.flash_attention import _unify_vma

    zeros_head = jnp.zeros((d, v_loc), jnp.float32)
    zeros_bias = jnp.zeros((v_loc,), jnp.float32)
    zeros_head, zeros_bias, *_rest = _unify_vma(
        zeros_head, zeros_bias, h_blks, t_blks, lse_blks, g_blks, head_c)
    init = (zeros_head, zeros_bias if bias_loc is not None else None)
    if nb == 1:
        (dhead_loc, dbias_loc), dh = body(
            init, (h_blks[0], t_blks[0], lse_blks[0], g_blks[0]))
        dh2 = dh[:N]
    else:
        (dhead_loc, dbias_loc), dh = jax.lax.scan(
            body, init, (h_blks, t_blks, lse_blks, g_blks))
        dh2 = dh.reshape(-1, d)[:N]

    tp_split = v_loc != head.shape[1]       # vocab-parallel actually active
    if tp_split:
        # each device computed only its vocab slice's contribution to dh —
        # the sum over the full vocab needs the tp psum (the row-parallel
        # adjoint); dhead slices scatter into the full (d, V) then psum
        dh2 = jax.lax.psum(dh2, tp_axis)
        zf, dhead_loc = _unify_vma(
            jnp.zeros((d, head.shape[1]), jnp.float32), dhead_loc)
        dhead = jax.lax.dynamic_update_slice(zf, dhead_loc,
                                             (jnp.int32(0), off))
        if dbias_loc is not None:
            zb, dbias_loc = _unify_vma(
                jnp.zeros((head.shape[1],), jnp.float32), dbias_loc)
            dbias = jax.lax.dynamic_update_slice(zb, dbias_loc, (off,))
        else:
            dbias = None
    else:
        dhead, dbias = dhead_loc, dbias_loc

    # replicated-weight adjoint: psum the head/bias grads over every axis
    # the activations vary on that the head doesn't (head_dot's contract),
    # plus tp when the vocab split was active
    extra = _vma(h2) - _vma(head)
    if tp_split:
        extra = extra | {tp_axis}
    sum_axes = tuple(sorted(extra))
    if sum_axes:
        dhead = jax.lax.psum(dhead, sum_axes)
        if dbias is not None:
            dbias = jax.lax.psum(dbias, sum_axes)
    return dh2.astype(h2.dtype), dhead, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _chunked_ce(h2, head, bias, tgt, tp_axis, row_block, vocab_block):
    nll, _lse = _fwd_scan(h2, head, bias, tgt, tp_axis, row_block,
                          vocab_block)
    return nll


def _chunked_ce_fwd(h2, head, bias, tgt, tp_axis, row_block, vocab_block):
    nll, lse = _fwd_scan(h2, head, bias, tgt, tp_axis, row_block,
                         vocab_block)
    return nll, (h2, head, bias, tgt, lse)


def _chunked_ce_bwd(tp_axis, row_block, vocab_block, res, g):
    h2, head, bias, tgt, lse = res
    # the forward inherits its caller's scope; a custom_vjp's backward
    # is traced apart from it
    with jax.named_scope("readout_ce"):
        dh2, dhead, dbias = _bwd_scan(h2, head, bias, tgt, lse, g, tp_axis,
                                      row_block, vocab_block)
    if bias is None:
        dbias = None
    # int targets take a symbolic-zero (float0) cotangent
    dtgt = np.zeros(tgt.shape, jax.dtypes.float0)
    return dh2, dhead.astype(head.dtype), dbias, dtgt


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


def dense_ce_nll(h: jnp.ndarray, head: jnp.ndarray,
                 targets: jnp.ndarray,
                 bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The jnp golden twin: per-token NLL through the dense
    ``head_dot`` readout + ``log_softmax`` chain (materializes the full
    f32 (..., V) logits). Identical numerics contract, used by the
    ``chunked_ce=False`` factory escape hatch and every parity pin."""
    from byteps_tpu.models.gpt import head_dot

    logits = head_dot(h, head)
    if bias is not None:
        logits = logits + bias
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def chunked_ce_nll(h: jnp.ndarray, head: jnp.ndarray, targets: jnp.ndarray,
                   bias: Optional[jnp.ndarray] = None,
                   tp_axis: Optional[str] = None,
                   row_block: Optional[int] = None,
                   vocab_block: Optional[int] = None) -> jnp.ndarray:
    """Per-token cross-entropy of the fused readout, logits never
    materialized.

    ``h (..., d)`` activations (any float dtype), ``head (d, V)`` f32
    readout weight (tied ``wte.T`` or untied ``lm_head``), ``targets
    (...)`` int ids, optional ``bias (V,)`` f32 logit bias (BERT's
    ``mlm_bias``). Returns f32 NLL shaped like ``targets``; equals
    ``dense_ce_nll(h, head, targets, bias)`` bit-exactly on the
    single-device single-vocab-chunk path and to f32 roundoff otherwise.

    ``tp_axis`` (inside shard_map) activates the vocab-parallel variant:
    per-device V/ntp column slices with tp-combined max/sum-exp — requires
    V divisible by the tp size (falls back to replicated compute
    otherwise). ``row_block``/``vocab_block`` override the block sizes
    (defaults: ≤64 MiB of live f32 logits per row block, no vocab
    sub-chunking).
    """
    if h.shape[:-1] != targets.shape:
        raise ValueError(
            f"h leading dims {h.shape[:-1]} must match targets shape "
            f"{targets.shape}")
    if head.ndim != 2 or h.shape[-1] != head.shape[0]:
        raise ValueError(
            f"head must be (d, V) with d == h.shape[-1]; got {head.shape} "
            f"vs d={h.shape[-1]}")
    if bias is not None and bias.shape != (head.shape[1],):
        raise ValueError(
            f"bias must be (V,) = ({head.shape[1]},); got {bias.shape}")
    lead = targets.shape
    h2 = h.reshape(-1, h.shape[-1])
    tgt = targets.reshape(-1)
    nll = _chunked_ce(h2, head, bias, tgt, tp_axis, row_block, vocab_block)
    return nll.reshape(lead)
