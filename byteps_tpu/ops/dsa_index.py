"""Indexer scores of learned sparse attention (DeepSeek-V3.2's lightning
indexer) for a block of queries against every cached key of one request.

``I[t, s] = Σ_j w[t, j] · relu(q[t, j, :] · k[s, :])`` over the ``H`` indexer
heads, for the ``C`` consecutive queries at positions ``pos0 ..`` and ``L``
keys at positions ``0 ..``; a key after its query reads ``-1e30``. The plain
form makes a ``(H, C, L)`` f32 intermediate — 17 GB at 64 heads, a
2,048-token chunk and 32,768 keys — so the kernel keeps one ``(tk, tq)``
tile of it in VMEM: queries lie along the lanes (scores come out transposed,
``(L, C)``, and are turned once at the end), so that a head's weights are one
sublane-broadcast row ``w[j, tile]``; the head loop runs over the resident
``(H, tq, D)`` query block, one MXU product a head. Key tiles wholly after
the query tile are skipped and never fetched.

The jnp twin carries the running sum through a scan over heads, so it makes
no ``(H, C, L)`` buffer either; the CPU tests hold the kernel to it in
interpret mode.

The pick that follows the scores is here too (:func:`select_mask`): the
``topk`` keys of largest score a query, as an int8 mask and with no sort —
counting passes over the scores' order-preserving integer image, which the
kernel ``dsa_select_mask`` keeps in VMEM a tile of rows at a time (HBM sees
the scores once and the mask once) and the twin re-reads from HBM as XLA
loops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byteps_tpu.ops.backend import interpret as _interpret
from byteps_tpu.ops.backend import note_fallback, use_pallas

__all__ = ["index_scores", "index_scores_jnp", "unsupported_reason",
           "select_mask", "select_mask_counted", "select_mask_jnp",
           "select_unsupported_reason"]

_NEG = -1e30
_TQ, _TK = 128, 512
#: the selection's row tile: whole int8 tiles (32 sublanes), two of them
#: where the rows allow, since a pass ends in one reduction across lanes
#: whatever the tile holds; the lanes one trip of a pass's loop takes, as
#: ONE array (on the chip, PR 56, 2,048 x 32,768: 64 x 256 read 2.84 ms, 64
#: x 512 3.01, 64 x 128 3.74; the same trip written out as sixteen 128-lane
#: slices read 2.25, and took three times as long to lower — 0.35 s against
#: 0.1, four times a chunk program, every program of every warm start);
#: and the VMEM a tile may ask for: a key costs it two buffers of f32
#: scores and of the int8 mask and the int32 image (29 MB at 64 x 32,768;
#: v5e has 128 MiB)
_SEL_ROWS, _SEL_WIDTHS = (64, 32), (256, 128)
_SEL_BYTES_A_KEY, _SEL_VMEM = 2 * 4 + 2 * 1 + 4, 48 << 20


def index_scores_jnp(q, k, w, pos0):
    """The twin: ``q (C, H, D)``, ``k (L, D)``, ``w (C, H)`` f32 →
    ``(C, L)`` f32."""
    C, L = q.shape[0], k.shape[0]

    def head(acc, qw):
        qh, wh = qw
        s = jax.lax.dot_general(qh, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return acc + wh[:, None] * jax.nn.relu(s), None

    acc, _ = jax.lax.scan(head, jnp.zeros((C, L), jnp.float32),
                          (q.transpose(1, 0, 2), w.T.astype(jnp.float32)))
    live = jnp.arange(L)[None, :] <= pos0 + jnp.arange(C)[:, None]
    return jnp.where(live, acc, _NEG)


def unsupported_reason(C: int, L: int, H: int, D: int):
    """Why the kernel does not take these shapes (None when it does)."""
    if C % _TQ or L % _TK:
        return (f"queries ({C}) and keys ({L}) must be whole tiles of "
                f"{_TQ} and {_TK}")
    if D % 128 or H % 8:
        return f"head width {D} / head count {H} are not whole vector tiles"
    return None


def _kernel(pos0_ref, q_ref, k_ref, w_ref, o_ref, *, tq, tk, H):
    i, j = pl.program_id(0), pl.program_id(1)
    q_start = pos0_ref[0] + i * tq
    k_start = j * tk

    @pl.when(k_start <= q_start + tq - 1)
    def _():
        k = k_ref[...]                                       # (tk, D)

        def head(h, acc):
            s = jax.lax.dot_general(
                k, q_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # (tk, tq)
            return acc + jnp.maximum(s, 0.0) * w_ref[pl.ds(h, 1), :]

        acc = jax.lax.fori_loop(0, H, head,
                                jnp.zeros((tk, tq), jnp.float32))
        keys = k_start + jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
        qs = q_start + jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
        o_ref[...] = jnp.where(keys <= qs, acc, _NEG)

    @pl.when(k_start > q_start + tq - 1)
    def _():
        o_ref[...] = jnp.full((tk, tq), _NEG, jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scores(q, k, w, pos0, interpret: bool):
    C, H, D = q.shape
    L = k.shape[0]
    tq, tk = _TQ, _TK

    def last_live(i, pos0_ref):
        return (pos0_ref[0] + (i + 1) * tq - 1) // tk

    out_t = pl.pallas_call(
        functools.partial(_kernel, tq=tq, tk=tk, H=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(C // tq, L // tk),
            in_specs=[
                pl.BlockSpec((H, tq, D), lambda i, j, p: (0, i, 0)),
                # a tile after the diagonal re-names the last live one:
                # the same block index, so nothing is fetched for it
                pl.BlockSpec((tk, D), lambda i, j, p: (
                    jnp.minimum(j, last_live(i, p)), 0)),
                pl.BlockSpec((H, tq), lambda i, j, p: (0, i)),
            ],
            out_specs=pl.BlockSpec((tk, tq), lambda i, j, p: (j, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((L, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="dsa_index_scores",
    )(jnp.asarray(pos0, jnp.int32).reshape(1), q.transpose(1, 0, 2), k,
      w.T.astype(jnp.float32))
    return out_t.T


def index_scores(q, k, w, pos0):
    """``(C, L)`` f32 indexer scores of ``q (C, H, D)`` at positions ``pos0
    ..`` against ``k (L, D)`` at ``0 ..`` with head weights ``w (C, H)``;
    ``-1e30`` where the key lies after the query. Pallas on the TPU (or
    forced, interpreted), else the twin."""
    if use_pallas():
        why = unsupported_reason(q.shape[0], k.shape[0], q.shape[1],
                                 q.shape[2])
        if why is None:
            return _scores(q, k.astype(q.dtype), w, pos0, _interpret())
        note_fallback("dsa_index_scores", q.shape + k.shape, why)
    return index_scores_jnp(q, k, w, pos0)


def select_mask_jnp(scores, topk: int):
    """``(N, L)`` bool: for each query the ``topk`` keys of largest score
    among its live ones (score above -1e30), every live key while there are
    no more than ``topk`` — exactly the set ``jax.lax.top_k`` picks (of equal
    scores the lower position first), as a mask and with no sort: the
    ``topk``-th largest score of a row is found bit by bit on the scores'
    order-preserving integer image (32 counting passes), then the position
    up to which its ties are in (one pass a bit of ``L``)."""
    L = scores.shape[-1]
    live = scores > _NEG / 2
    if topk >= L:
        return live
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    # float order as unsigned order; a dead key sorts below everything
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)
    u = jnp.where(live, jax.lax.bitcast_convert_type(key, jnp.uint32)
                  ^ jnp.uint32(0x80000000), jnp.uint32(0))

    def count(m):
        return jnp.sum(m, axis=-1, dtype=jnp.int32)

    def value_bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(u >= cand[:, None]) >= topk, cand, thr)

    thr = jax.lax.fori_loop(0, 32, value_bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = (u > thr[:, None]) & live
    tied = (u == thr[:, None]) & live
    need = topk - count(above)               # ties that are in: the first
    pos = jnp.arange(L, dtype=jnp.int32)
    nbits = max(1, (L - 1).bit_length())

    def pos_bit(i, last):
        cand = last | (jnp.int32(1) << (nbits - 1 - i))
        return jnp.where(count(tied & (pos < cand[:, None])) < need, cand,
                         last)

    last = jax.lax.fori_loop(0, nbits, pos_bit,
                             jnp.zeros(scores.shape[:-1], jnp.int32))
    return above | (tied & (pos <= last[:, None]))


def _select_rows(C: int, L: int):
    """Rows of the selection kernel's tile for ``(C, L)`` scores, None
    where no tile of whole rows fits."""
    return next((t for t in _SEL_ROWS
                 if C % t == 0 and t * L * _SEL_BYTES_A_KEY <= _SEL_VMEM),
                None)


def select_unsupported_reason(C: int, L: int):
    """Why the selection kernel does not take these shapes (None when it
    does)."""
    if L % 128 or _select_rows(C, L) is None:
        return (f"queries ({C}) must be whole tiles of {_SEL_ROWS[-1]} and "
                f"keys ({L}) of 128, {_SEL_VMEM >> 20} MiB of them a tile "
                "at the most")
    return None


def _select_kernel(s_ref, o_ref, n_ref, img_ref, *, topk, width):
    """One ``(tq, L)`` row tile: the twin's passes over an image that stays
    in VMEM, ``width`` lanes a trip of a pass's loop. The image is the
    signed one (a dead key the least integer), so a candidate threshold,
    kept in the twin's unsigned bits, has its top bit flipped before it is
    compared. After the value passes the image is rewritten once — -1 where
    a key lies above its row's threshold, its position where it ties, the
    greatest integer elsewhere — so that a position pass is one comparison
    again and the mask is ``image <= last``.

    Two things the tile sees in its scores shorten it: no pass reads past
    the last trip that holds a live key, and where no row has more keys at
    or above its threshold than it takes, every tie is in and the position
    passes do not run. ``n_ref[:, tile]``: the keys the tile's rows picked,
    and 1 where the position passes ran."""
    tq, L = s_ref.shape
    nbits = max(1, (L - 1).bit_length())
    i32 = jnp.iinfo(jnp.int32)
    imin, imax = jnp.int32(i32.min), jnp.int32(i32.max)
    wide = jnp.zeros((tq, width), jnp.int32)
    row = jnp.zeros((tq, 1), jnp.int32)

    def trips(body, carry, lo=0, hi=L // width):
        """``body(lanes, offset, carry)`` over the trips ``lo .. hi``."""
        def trip(j, carry):
            off = pl.multiple_of(j * width, width)
            return body(pl.ds(off, width), off, carry)
        return jax.lax.fori_loop(lo, hi, trip, carry)

    def image(lanes, off, seen):
        s = s_ref[:, lanes]
        live = s > _NEG / 2
        bits = jax.lax.bitcast_convert_type(s, jnp.int32)
        key = jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)
        img_ref[:, lanes] = jnp.where(live, key, imin)
        return jnp.maximum(seen, jnp.where(live, off, -1))

    # the trips up to the last live key: what follows is dead in every row
    # and counts for nothing in any pass
    hi = (jnp.max(trips(image, wide - 1)) + width) // width

    def count(below, cand):
        """``(tq, 1)``: keys a row whose image is below ``cand`` (or not
        below): lane-wide partial counts, reduced across lanes once."""
        cand = cand + wide               # across the lanes once a pass

        def body(lanes, _, acc):
            x = img_ref[:, lanes]
            hit = x < cand if below else x >= cand
            return acc + hit.astype(jnp.int32)
        return jnp.sum(trips(body, wide, hi=hi), axis=-1, keepdims=True)

    def value_bit(i, thr):
        cand = thr | jnp.left_shift(jnp.int32(1), 31 - i)
        return jnp.where(count(False, cand ^ imin) >= topk, cand, thr)

    thr = jax.lax.fori_loop(0, 32, value_bit, row) ^ imin

    def rank(lanes, off, taken):
        x = img_ref[:, lanes]
        pos = off + jax.lax.broadcasted_iota(jnp.int32, (tq, width), 1)
        live = x != imin
        img_ref[:, lanes] = jnp.where(
            x > thr, -1, jnp.where((x == thr) & live, pos, imax))
        return taken + ((x >= thr) & live).astype(jnp.int32)

    taken = jnp.sum(trips(rank, wide, hi=hi), axis=-1, keepdims=True)

    def pos_bit(i, last):
        cand = last | jnp.left_shift(jnp.int32(1), nbits - 1 - i)
        return jnp.where(count(True, cand) < topk, cand, last)

    ties_left_out = jnp.max(taken) > topk
    last = jax.lax.cond(
        ties_left_out,
        lambda: jax.lax.fori_loop(0, nbits, pos_bit, row),
        lambda: row + (imax - 1))
    n_ref[0, pl.program_id(0)] = jnp.sum(jnp.minimum(taken, topk))
    n_ref[1, pl.program_id(0)] = ties_left_out.astype(jnp.int32)

    def mask(lanes, _, carry):
        o_ref[:, lanes] = (img_ref[:, lanes] <= last).astype(jnp.int8)
        return carry

    def dead(lanes, _, carry):
        o_ref[:, lanes] = jnp.zeros((tq, width), jnp.int8)
        return carry

    trips(mask, 0, hi=hi)
    trips(dead, 0, lo=hi)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select(scores, topk: int, interpret: bool):
    C, L = scores.shape
    tq = _select_rows(C, L)
    width = next(w for w in _SEL_WIDTHS if L % w == 0)
    vmem = tq * L * _SEL_BYTES_A_KEY + (8 << 20)
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, width=width),
        grid=(C // tq,),
        in_specs=[pl.BlockSpec((tq, L), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tq, L), lambda i: (i, 0)),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((C, L), jnp.int8),
                   jax.ShapeDtypeStruct((2, C // tq), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((tq, L), jnp.int32)],
        # not "parallel": the counts are one SMEM array every tile writes a
        # column of, which two cores would each write back whole
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="dsa_select_mask",
    )(scores)


def select_mask_counted(scores, topk: int):
    """``(mask, picked, tie_tiles)``: ``(N, L)`` int8, 1 where a query picks
    a key — :func:`select_mask_jnp`'s set —, how many keys that is in all,
    and how many row tiles of the kernel ran the position passes (0 where
    the twin ran). Pallas on the TPU (or forced, interpreted) for scores of
    whole tiles, else the twin; with no more keys than ``topk`` every live
    key, and no kernel."""
    N, L = scores.shape
    if topk < L and use_pallas():
        why = select_unsupported_reason(N, L)
        if why is None:
            mask, counts = _select(scores, topk, _interpret())
            return mask, jnp.sum(counts[0]), jnp.sum(counts[1])
        note_fallback("dsa_select_mask", scores.shape, why)
    mask = select_mask_jnp(scores, topk)
    return mask.astype(jnp.int8), jnp.sum(mask), jnp.int32(0)


def select_mask(scores, topk: int):
    """:func:`select_mask_counted`'s mask alone."""
    return select_mask_counted(scores, topk)[0]
