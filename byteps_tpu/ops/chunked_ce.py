"""Fused readout→cross-entropy: the [B, S, V] logits never exist in HBM.

The readout GEMM and the softmax are processed **blockwise**, as the flash
kernels process attention: only one block's logits are live at a time
(≤ 64 MiB in f32, ``_BLOCK_ELEMS``), and the backward **recomputes** them
instead of saving them.

:func:`chunked_ce_nll` is the drop-in for
``_nll(head_dot(h, head), targets)`` (models/gpt.py): per-token NLL with
a custom VJP that

* in the forward scans the flattened ``(N, d)`` hidden states in row blocks
  (``row_block`` rows at a time — see ``_default_row_block``), optionally
  sub-chunking the vocab axis inside each row block (``vocab_block``) with
  online max/sum-exp accumulation — the long-V memory lever,
* recomputes each block's logits in the backward from the saved
  ``(h, head)`` residuals + the per-row logsumexp (an (N,) f32 vector —
  the only extra forward output),
* keeps the ``head_dot`` precision contract: dot operands in the
  ACTIVATION dtype, f32 accumulation, activation-dtype ``dh``, f32
  ``dhead`` (the optimizer's master-weight gradient loses nothing).

**The backward's scan axis** (:func:`_bwd_axis`). With the saved logsumexp
``dz = (exp(z − lse) − onehot) · g`` needs no running statistics, so the
``(N, V_loc)`` plane can be cut either way, and what differs is which
gradient the loop has to carry — read and written whole once a block:

* row blocks: ``dh`` of a block is complete (stacked), ``dhead`` is the
  carry, ``d · V_loc`` f32 elements × ``nb`` blocks a pass. At
  GPT-2-medium's training shape (N = 8,192, d = 1,024, V = 50,304; 32
  blocks of 256 rows) that is a 206 MB array read and written 32 times,
  13.2 GB a step, for products that contract over 256 rows each;
* vocab blocks over all N rows: a block's slice of ``dhead``/``dbias`` is
  complete after one product and is written once, ``dh`` is the carry,
  ``N · d`` elements × ``nv`` blocks: 33.5 MB × 25 blocks of 2,048
  columns there, 1.7 GB.

Both cuts hold the same elements a block, so both run ``≈ N·V_loc / 2²⁴``
blocks and the carried traffic stands as ``V_loc : N``. The rule: the
vocab cut where its carried elements a pass are strictly fewer, else the
row cut; a plane that fits one block is a single un-scanned body. It is
taken while tracing, from the shapes alone (JoyAI's N = 16,384 ≥ V_loc =
16,256 keeps its rows), and the backward's scope says which ran:
``readout_ce.bwd_vocab`` or ``readout_ce.bwd_rows``. The forward keeps
its row blocks: its carry is nothing, what it re-reads once a block is
the head.

**Vocab-parallel (tp) variant**: with ``tp_axis`` set, each device
computes only its ``V/ntp`` column slice of the readout (riding the same
col-parallel split the block matmuls use — the head weight stays
replicated, sliced at ``axis_index(tp)``), and the per-block row
max / sum-exp / target-logit are combined over tp (pmax + psum) before
the log-partition. FLOPs and live logits both drop by ntp; the backward
plans on its ``V/ntp`` columns and assembles ``dh``/``dhead`` with one
psum each after the loop, so gradients keep the replicated-weight
contract the dense path has (VMA and no-VMA modes both — see
models/train.py's grad-assembly notes).

Numerics: the single-device, single-vocab-chunk path mirrors
``log_softmax``'s exact operation order (max, exp-shift, sum, log) and is
**bit-exact** with the dense ``_nll(head_dot(...))`` chain at f32; vocab
sub-chunking and the tp combine change the sum-exp association order and
are pinned to f32-roundoff tolerance instead
(tests/test_chunked_ce.py). Between the backward's two cuts ``dz`` is the
same element by element and every product is made; only the order of the
f32 partial sums moves. The dense twin :func:`dense_ce_nll` is the
golden and the ``chunked_ce=False`` escape hatch on every train-step
factory routes production back to it.

Why lax.scan blocks and not a Mosaic kernel: the per-block softmax
statistics and ``dz`` are elementwise/reduce consumers that XLA fuses
onto the block GEMM's output (the compiled vocab-major body is three
fusions, and no f32 logits reach memory), and the scan form keeps the
path portable (CPU tier-1 pins it), VJP-exact under remat/pipeline, and
free of Mosaic compile risk.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from byteps_tpu.ops.flash_attention import _unify_vma


def _f32_dot(a: jnp.ndarray, b: jnp.ndarray, contract=None) -> jnp.ndarray:
    """`a @ b`, or the product over ``contract``'s pair of dimensions, with
    f32 accumulation — the head_dot contract's dot."""
    au, bu = _unify_vma(a, b)
    return jax.lax.dot_general(
        au, bu, (contract or ((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _vma(x) -> frozenset:
    try:
        return frozenset(jax.typeof(x).vma)
    except (AttributeError, TypeError):
        return frozenset()


_BLOCK_ELEMS = (64 * 1024 * 1024) // 4     # f32 logits live per block
_LANES = 128                               # a derived vocab block's multiple


def _default_row_block(n_rows: int, v_loc: int) -> int:
    """Largest power-of-two row count keeping one block's f32 logits
    ≤ 64 MiB (GPT-2-medium's V=50304 → 256 rows; JoyAI's 16256 → 1024),
    or every row where the whole plane fits. Clamped to [16, n_rows]."""
    budget = _BLOCK_ELEMS                    # f32 elements per block
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


def _local_width(V: int, tp_axis: Optional[str]) -> int:
    """Columns of the readout one device computes: ``V/ntp`` where
    ``tp_axis`` splits the vocabulary evenly, else all of them."""
    if tp_axis is None:
        return V
    ntp = jax.lax.axis_size(tp_axis)
    return V if ntp == 1 or V % ntp != 0 else V // ntp


def _local_head(head: jnp.ndarray, bias, tp_axis: Optional[str]):
    """This device's column slice of the (replicated) head/bias plus its
    vocab offset: the whole head when ``tp_axis`` is None or V doesn't
    split evenly; otherwise the ``V/ntp`` slice at ``axis_index(tp)``."""
    V = head.shape[1]
    v_loc = _local_width(V, tp_axis)
    if v_loc == V:
        return head, bias, jnp.int32(0), V
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


def _bwd_axis(N: int, d: int, v_loc: int, row_block: Optional[int],
              vocab_block: Optional[int]):
    """``(axis, block)`` of the backward's scan over the ``(N, v_loc)``
    logits plane, from the shapes alone: ``("rows", rb)`` cuts it into
    blocks of ``rb`` rows and carries ``dhead``, ``d · v_loc`` f32 elements
    read and written once a block; ``("vocab", vb)`` cuts it into blocks
    of ``vb`` columns and carries ``dh``, ``N · d``. The vocab cut runs
    only where its carried elements a pass are strictly fewer.

    Both cuts hold the same elements a block. Without overrides that is
    ``_BLOCK_ELEMS``: the row block ``_default_row_block``'s, the vocab
    block the largest whole number of 128-lane tiles. An override names
    its own axis's block, taken as it is, and through it the elements
    (``row_block · v_loc``, or ``N · vocab_block`` where that alone is
    given); the other axis gets the block those afford, so a row block of
    a few rows affords no vocab block at all and keeps the row cut. A
    plane that fits in one block is the row cut's single block."""
    rb = row_block or _default_row_block(N, v_loc)
    if row_block:
        budget = row_block * v_loc
    elif vocab_block:
        budget = N * vocab_block
    else:
        budget = _BLOCK_ELEMS
    if N * v_loc <= budget:
        return "rows", rb
    if vocab_block and not row_block:
        vb, nb = vocab_block, -(-N // max(budget // v_loc, 1))
    else:
        vb, nb = budget // N // _LANES * _LANES, -(-N // rb)
    if vb and N * d * -(-v_loc // vb) < d * v_loc * nb:
        return "vocab", vb
    return "rows", rb


def _dlogits(h_blk, head_blk, bias_blk, lse_blk, g_blk, local_t, start,
             in_range):
    """One tile's ``dz = (softmax − onehot(target)) · g`` in the
    activation type: the logits rebuilt from (h, head), the saved
    logsumexp in place of running statistics, so the same element by
    element however the plane is cut. ``local_t`` is each row's target
    column in this shard, ``start`` the tile's first."""
    width = head_blk.shape[1]
    z = _f32_dot(h_blk, head_blk)
    if bias_blk is not None:
        z = z + bias_blk
    p = jnp.exp(z - lse_blk[:, None])
    sel = local_t - start
    hit = in_range & (sel >= 0) & (sel < width)
    onehot = (jax.nn.one_hot(jnp.clip(sel, 0, width - 1), width,
                             dtype=jnp.float32)
              * hit[:, None].astype(jnp.float32))
    return ((p - onehot) * g_blk[:, None]).astype(h_blk.dtype)


def _bwd_rows(h2, head_c, bias_loc, tgt, off, lse, g, rb, vocab_block):
    """The row cut: per block of ``rb`` rows, ``dh`` is complete (stacked)
    and ``dhead``/``dbias`` accumulate in f32 carries."""
    N, d = h2.shape
    v_loc = head_c.shape[1]
    h_pad, nb = _pad_rows(h2, rb)
    t_pad, _ = _pad_rows(tgt, rb)
    lse_pad, _ = _pad_rows(lse, rb)
    g_pad, _ = _pad_rows(g, rb)
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
            head_blk = head_c[:, start:start + width]
            dz = _dlogits(
                h_blk, head_blk,
                None if bias_loc is None else bias_loc[start:start + width],
                lse_blk, g_blk, local_t, start, in_range)
            # dh accumulates over vocab slices; dhead/dbias over row blocks
            dh_blk = dh_blk + _f32_dot(dz, head_blk, ((1,), (1,)))
            dhs.append(_f32_dot(h_blk, dz, ((0,), (0,))))
            if bias_loc is not None:
                dbs.append(dz.astype(jnp.float32).sum(axis=0))
        dhead_acc = dhead_acc + jnp.concatenate(dhs, axis=1)
        if dbias_acc is not None:
            dbias_acc = dbias_acc + jnp.concatenate(dbs, axis=0)
        return (dhead_acc, dbias_acc), dh_blk

    # the f32 accumulators must carry the union vma of everything the body
    # touches or the scan carry would not be a type fixed point
    zeros_head, zeros_bias, *_rest = _unify_vma(
        jnp.zeros((d, v_loc), jnp.float32), jnp.zeros((v_loc,), jnp.float32),
        h_blks, t_blks, lse_blks, g_blks, head_c)
    init = (zeros_head, zeros_bias if bias_loc is not None else None)
    if nb == 1:
        (dhead_loc, dbias_loc), dh = body(
            init, (h_blks[0], t_blks[0], lse_blks[0], g_blks[0]))
        return dh[:N], dhead_loc, dbias_loc
    (dhead_loc, dbias_loc), dh = jax.lax.scan(
        body, init, (h_blks, t_blks, lse_blks, g_blks))
    return dh.reshape(-1, d)[:N], dhead_loc, dbias_loc


def _bwd_vocab(h2, head_c, bias_loc, tgt, off, lse, g, vb):
    """The vocab cut: per block of ``vb`` columns over all rows, that
    block's slice of ``dhead``/``dbias`` is complete after one product and
    is written once; ``dh`` accumulates in the f32 carry. The equal blocks
    are scanned and what is left of ``v_loc`` is one static tail block —
    not padding: a padded column has ``z = 0`` and ``exp(−lse) ≠ 0`` and
    would leak into ``dh``."""
    N, d = h2.shape
    v_loc = head_c.shape[1]
    local_t = tgt.astype(jnp.int32) - off
    in_range = (local_t >= 0) & (local_t < v_loc)
    n_full = v_loc // vb

    def block(carry, start, width):
        dh_acc, dhead, dbias = carry
        head_blk = jax.lax.dynamic_slice_in_dim(head_c, start, width, axis=1)
        bias_blk = (None if bias_loc is None else
                    jax.lax.dynamic_slice_in_dim(bias_loc, start, width))
        dz = _dlogits(h2, head_blk, bias_blk, lse, g, local_t, start,
                      in_range)
        dh_acc = dh_acc + _f32_dot(dz, head_blk, ((1,), (1,)))
        dhead = jax.lax.dynamic_update_slice_in_dim(
            dhead, _f32_dot(h2, dz, ((0,), (0,))), start, axis=1)
        if dbias is not None:
            dbias = jax.lax.dynamic_update_slice_in_dim(
                dbias, dz.astype(jnp.float32).sum(axis=0), start, axis=0)
        return dh_acc, dhead, dbias

    # as in the row cut: the carry takes the union vma of what the body
    # touches, the slices written once included
    zeros_dh, zeros_head, zeros_bias, *_rest = _unify_vma(
        jnp.zeros((N, d), jnp.float32), jnp.zeros((d, v_loc), jnp.float32),
        jnp.zeros((v_loc,), jnp.float32), h2, local_t, lse, g, head_c)
    carry = (zeros_dh, zeros_head,
             zeros_bias if bias_loc is not None else None)
    carry, _ = jax.lax.scan(
        lambda c, start: (block(c, start, vb), None), carry,
        jnp.arange(n_full, dtype=jnp.int32) * vb)
    if v_loc > n_full * vb:
        carry = block(carry, n_full * vb, v_loc - n_full * vb)
    return carry


def _bwd_scan(h2, head, bias, tgt, lse, g, tp_axis, vocab_block, axis,
              block):
    """Recompute-in-backward: rebuild the logits from (h, head) block by
    block, form ``dlogits = (softmax − onehot(target)) · g`` and from it
    ``dh``, ``dhead`` and ``dbias``, along :func:`_bwd_axis`'s ``axis``."""
    d = h2.shape[1]
    head_loc, bias_loc, off, v_loc = _local_head(head, bias, tp_axis)
    head_c = head_loc.astype(h2.dtype)
    g = g.astype(jnp.float32)
    if axis == "vocab":
        dh2, dhead_loc, dbias_loc = _bwd_vocab(
            h2, head_c, bias_loc, tgt, off, lse, g, block)
    else:
        dh2, dhead_loc, dbias_loc = _bwd_rows(
            h2, head_c, bias_loc, tgt, off, lse, g, block, vocab_block)

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
    axis, block = _bwd_axis(*h2.shape, _local_width(head.shape[1], tp_axis),
                            row_block, vocab_block)
    # the forward inherits its caller's scope; a custom_vjp's backward
    # is traced apart from it. The axis is decided once a compile: the
    # scope says in a device trace and an HLO dump which loop a program has
    with jax.named_scope(f"readout_ce.bwd_{axis}"):
        dh2, dhead, dbias = _bwd_scan(h2, head, bias, tgt, lse, g, tp_axis,
                                      vocab_block, axis, block)
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
    sub-chunking); what an override means to the backward's choice of
    scan axis is :func:`_bwd_axis`'s to say.
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
