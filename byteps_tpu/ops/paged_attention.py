"""Paged decode attention — the Pallas kernel that reads the serve
tier's KV pool in place.

``serve/paged_cache.py``'s packed decode step used to index the pool by
the block table into a dense ``(R, W·bs, h, D)`` copy of K and of V, per
layer, every step, and hand that to the jnp ``attention_lse``, at the
width of the widest resident request for every row of the batch, padded
rows included (PERF.md §5). This kernel attends over the pool where it
lies:

* **No slice of the pool is materialised.** The whole ``(L, NB, bs,
  Hkv·D)`` pool is the operand (``memory_space=ANY``: it stays in HBM);
  the layer and the physical block are picked in the DMA's source
  slice, from a scalar-prefetched layer index and block table. A block
  is one dense tile-aligned ``(bs, Hkv·D)`` plane, 40 KB contiguous at
  GPT-2-large's sizes.
* **Dead blocks cost nothing.** One invocation walks the rows; row ``r``
  walks ``ceil(length[r] / T)`` chunks of ``T = ppb·bs`` keys — several
  pages a chunk (:func:`_pages_per_chunk`: 512 keys where the table is as
  wide), so that a loop step's own cost is paid once per 512 KB of K and
  V and not once a page — and a page is fetched (one DMA for K, one for
  V) only where it starts below the fill level. A padded row (length 1,
  scratch table) reads one page. Chunks are double buffered across rows:
  while chunk ``c`` is computed, chunk ``c+1`` — or the next row's first
  — is in flight.
* **A window layer's row starts where its window does.** With ``first``
  (one scalar-prefetched position a row) a row walks the chunks from the
  one holding ``first[r]`` on: pages wholly below it are not fetched (the
  cache has handed their blocks back; the table still has an entry a
  logical block, 0 where one was released), keys below it are masked like
  keys past the fill level. Without ``first`` the trace is the kernel
  without a window: the branch is taken in Python.
* **Garbage never reaches the output.** The gathered view zeroed every
  position at or past the fill level; here scores there take ``_NEG``
  and their ``p`` is forced to 0, and V's rows there are 0 before
  ``p·V``, so whatever a recycled block holds (NaN, inf) cannot enter
  (0 × NaN). Only a chunk that holds a row's end pays for that — its
  fill level, its first live key, a page that was not fetched; a chunk
  whose every key is live takes the path with no compare and no select.
  V is cleaned in the buffer: the buffers start as zeros, a dead page is
  never fetched, and the one score tile of rows in which a fetched page
  can have dead rows (the tile the row's end lies in) is selected to 0
  before the chunk's products; so what a dead page's rows of a buffer
  hold is zeros or an earlier chunk's cleaned rows, finite either way.

A decode query is one row per head, which gives the MXU nothing to
tile head by head, so a chunk's two products take several heads' rows
at once, in one of two layouts chosen from the head size while tracing
(:func:`_form`; the DMA walk, the online softmax over all rows and the
masking are shared, and only ``heads`` in the kernel knows which):

* **grouped**, where a k/v head's ``D`` columns are whole lane tiles of
  the page as it lies (``D % 128 == 0``): q goes in as ``(R, Hkv·M, D)``,
  the ``M = nq·G`` query rows of k/v head ``j`` (its ``G = H/Hkv`` query
  heads, of each of the row's ``nq`` queries, padded to whole sublanes)
  in rows ``[j·M, (j+1)·M)``; scores are ``q[j] (M, D) × K[:, j·D:(j+1)·D]ᵀ``
  and the output ``p[j] (M, T) × V[:, j·D:(j+1)·D]``, head by head. No zero
  is multiplied and ``acc`` is ``D`` wide.
* **diagonal**, for narrow heads (GPT-2-large: 20 heads of 64, ``G`` =
  1): q is laid out block-diagonally as ``(R, nq·H, Hkv·D)`` (row ``h``
  holds its query in its kv head's ``D`` columns and zeros elsewhere),
  scores are ONE ``(H, Hkv·D) × (T, Hkv·D)ᵀ`` product per chunk — the
  zeros pick each head's own keys — and ``p·V`` is one ``(H, T) × (T,
  Hkv·D)`` product whose diagonal blocks are the output; the wrapper
  picks them out. ``Hkv`` times the needed FLOPs, and one ``(20, 1280)``
  product instead of twenty one-row ones.

Either way the chunk's time is its bytes: K and V pass through the MXU
as its stationary operand once, the same number of tile loads as
bytes/32 KB, and at 512 keys a chunk a row of several chunks runs at
about 90% of the memory's rate on a v5e (PERF.md §5, PR 53). Online
softmax ``(m, l, acc)`` in VMEM with keys on lanes, f32 scores and
accumulation, K and V in the pool's dtype (``p`` rounded to it for
``p·V``, as the MXU rounds the twin's), output in ``q.dtype``: the
contract of ``ops/flash_decode.py``, and of ``_gather_view`` +
``attention_lse_jnp`` restricted to the live prefix (pinned in
``tests/test_paged_attention.py``). Quantised pools (int8 + per-row
scales) are not taken: :func:`unsupported_reason` says so and the step
keeps its jnp twin.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byteps_tpu.common.metrics import get_registry
from byteps_tpu.ops.backend import interpret as _interpret
from byteps_tpu.ops.flash_attention import _NEG, _out_struct, _unify_vma

__all__ = ["paged_attention_decode", "unsupported_reason"]

_CHUNK_TOKENS = 512                  # keys per chunk: 2 MB of the budget
_SCORE_TILE = 128                    # keys of one lane tile of scores
_BUFFER_BUDGET = 8 * 1024 * 1024     # VMEM for the 2 slots of K and of V
_SUBLANES = 8                        # a k/v head's query rows come in these


def _tile_pages(bs: int) -> int:
    """Pages of one lane tile of scores (a page of 128 rows or more: one)."""
    return max(1, _SCORE_TILE // bs)


def _pages_per_chunk(W: int, bs: int, row_bytes: int) -> int:
    """Pages fetched and computed per loop iteration: ``_CHUNK_TOKENS``
    keys, never more than the table is wide, the four chunk buffers
    inside their budget (0: not even one page fits); from one lane tile
    of scores up, whole lane tiles."""
    ppb = min(W, max(1, _CHUNK_TOKENS // bs),
              _BUFFER_BUDGET // (4 * bs * row_bytes))
    tile = _tile_pages(bs)
    return ppb if ppb < tile else ppb // tile * tile


def _form(head_dim: int) -> str:
    """How a chunk's two products are laid out. ``grouped``: a k/v head's
    query rows meet that head's columns of the page and nothing else; it
    needs those columns to be whole lane tiles of the page as it lies.
    ``diagonal``: all heads in one product over the whole row, each query
    in its head's columns of a block-diagonal layout — the right form for
    narrow heads, where a k/v head's columns are a fraction of a lane tile
    and the heads' rows together are one MXU pass. The head size alone
    decides: at every ``nq * G`` rows a head that the cells bring (8, 5
    padded to 8, 32) grouped read as fast as diagonal or faster on the
    chip (PERF.md §6, PR 53)."""
    return "grouped" if head_dim % 128 == 0 else "diagonal"


def unsupported_reason(block_size: int, kv_heads: int, head_dim: int,
                       dtype) -> Optional[str]:
    """Why the kernel does not take a pool of these shapes (``None``: it
    does) — the dispatcher's ``note_fallback`` text. A block must be
    whole VMEM tiles, because it is DMA'd as it lies."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        return "int8 pool: the kernel does not dequantise"
    rows = 8 * 4 // dtype.itemsize            # sublanes of one tile
    if block_size % rows != 0:
        return f"block_size must be a multiple of {rows} for {dtype.name}"
    if (kv_heads * head_dim) % 128 != 0:
        return "kv_heads * head_dim must be a multiple of 128"
    if _pages_per_chunk(1, block_size,
                        kv_heads * head_dim * dtype.itemsize) < 1:
        return "one block of K and V does not fit the VMEM buffers"
    return None


class _Shifted:
    """A scalar-prefetch ref read ``base`` elements further on."""

    def __init__(self, ref, base):
        self._ref, self._base = ref, base

    def __getitem__(self, i):
        return self._ref[self._base + i]


def _kernel(len_ref, *refs, scale: float, R: int, W: int, bs: int, ppb: int,
            nh: int = 1, windowed: bool = False, groups: int = 1):
    # a windowed trace prefetches one scalar array more: each row's first
    # live key. Without it nothing below differs from the kernel without
    first_ref = refs[0] if windowed else None
    (tab_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems,
     m_scr, l_scr, acc_scr) = refs[1:] if windowed else refs
    # ``nh`` products a chunk, each of M query rows over Dw columns of the
    # page: the k/v heads with their own columns (grouped), or one over
    # the whole row (diagonal). The two calls of ``heads`` are all that
    # knows the form: scores, softmax and accumulator are of all rows
    H, Dw = acc_scr.shape
    M = H // nh
    T = bs * ppb
    tile = bs * min(ppb, _tile_pages(bs))             # rows a scrub visits
    layer = layer_ref[0]
    if groups > 1:
        # the batch in ``groups`` grid steps of ``R`` rows each (a block of
        # queries a row makes q and o ``groups`` times what VMEM held): the
        # scalars are read at the row's place in the batch, q and o at its
        # place in the step's block
        base = pl.program_id(0) * R
        len_ref, tab_ref = _Shifted(len_ref, base), _Shifted(tab_ref,
                                                             base * W)
        if windowed:
            first_ref = _Shifted(first_ref, base)

    def first_chunk(r):
        # the chunk that holds the row's first live key: the ones before it
        # are neither fetched nor scored
        return first_ref[r] // T if windowed else 0

    def page_copies(r, c, slot, i):
        blk = tab_ref[r * W + jnp.minimum(c * ppb + i, W - 1)]
        rows = pl.ds(pl.multiple_of(i * bs, bs), bs)
        return (pltpu.make_async_copy(k_hbm.at[layer, blk],
                                      kbuf.at[slot, rows], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, blk],
                                      vbuf.at[slot, rows], sems.at[1, slot]))

    def dma(r, c, slot, wait: bool):
        # a page is live where it starts below the row's fill level (and,
        # under a window, ends above the first live key): the chunk's live
        # pages are ``[lo, hi)`` of its ``ppb``. Start and wait walk the
        # SAME pages, so every DMA issued is awaited and no dead page is
        # ever fetched; a dead page's rows of the buffer keep what an
        # earlier chunk left there
        hi = jnp.clip(pl.cdiv(len_ref[r], bs) - c * ppb, 0, ppb)
        lo = jnp.clip(first_ref[r] // bs - c * ppb, 0, ppb) if windowed else 0

        def page(i, carry):
            for cp in page_copies(r, c, slot, i):
                cp.wait() if wait else cp.start()
            return carry

        jax.lax.fori_loop(lo, hi, page, 0)

    def heads(product, rows, pages):
        # ``product`` of each head's rows of ``rows (H, ·)`` with its columns
        # of the chunk ``pages (T, nh·Dw)``, the heads' results one under
        # another again
        out = [product(rows[j * M:(j + 1) * M], pages[:, j * Dw:(j + 1) * Dw])
               for j in range(nh)]
        return out[0] if nh == 1 else jnp.concatenate(out, axis=0)

    def scrub(c, slot, length, first):
        # V's rows at or past the fill level, or below the first live key,
        # of a page that WAS fetched (a partly filled block's tail) may hold
        # NaN/inf, and 0 x NaN is NaN: they are selected to 0 in the buffer,
        # a score tile's rows at a time and only the tile a row's end lies
        # in. The buffers start as zeros, so the rows of a page that was
        # not fetched hold zeros or an earlier chunk's rows, scrubbed when
        # they were its: finite either way
        for lo in range(0, T, tile):
            at = c * T + lo
            ends = jnp.logical_and(at < length, at + tile > length)
            if windowed:
                ends |= jnp.logical_and(at < first, at + tile > first)

            @pl.when(ends)
            def _(lo=lo, at=at):
                v = vbuf[slot, lo:lo + tile]
                row_at = at + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
                rows_live = row_at < length
                if windowed:
                    rows_live &= row_at >= first
                vbuf[slot, lo:lo + tile] = jnp.where(rows_live, v,
                                                     jnp.zeros((), v.dtype))

    def chunk(r, c, slot, length, first, masked: bool):
        # ``masked``: the chunk holds a row's end (its fill level, its first
        # live key, a page that was not fetched), so some keys are dead:
        # their scores take ``_NEG`` and their ``p`` is forced to 0
        if masked:
            scrub(c, slot, length, first)
            at = c * T + jax.lax.broadcasted_iota(jnp.int32, (H, T), 1)
            live = at < length
            if windowed:
                live &= at >= first
        q = q_ref[r]                                          # (H, Dw)
        k = kbuf[slot]                                        # (T, nh·Dw)
        v = vbuf[slot]
        ct = jnp.promote_types(q.dtype, k.dtype)
        s = heads(lambda q, k: jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32),
            q.astype(ct), k.astype(ct)) * scale               # (H, T)
        if masked:
            s = jnp.where(live, s, _NEG)
        m_prev = m_scr[...]                                   # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(live, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + heads(
            lambda p, v: jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32),
            p.astype(v.dtype), v)                             # (H, Dw)
        m_scr[...] = m_new

    vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
    dma(0, first_chunk(0), 0, wait=False)

    def row(r, n):
        length = len_ref[r]
        nchunks = jnp.maximum(pl.cdiv(length, T), 1)
        first = first_ref[r] if windowed else 0
        m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        def step(c, n):
            slot = jax.lax.rem(n, 2)
            last = c + 1 == nchunks

            @pl.when(jnp.logical_not(last))
            def _():
                dma(r, c + 1, 1 - slot, wait=False)

            @pl.when(jnp.logical_and(last, r + 1 < R))
            def _():
                nxt = r + 1
                dma(nxt, first_chunk(nxt), 1 - slot, wait=False)

            dma(r, c, slot, wait=True)
            # every key of the chunk is live: no mask, no select
            whole = (c + 1) * T <= length
            if windowed:
                whole &= c * T >= first

            @pl.when(whole)
            def _():
                chunk(r, c, slot, length, first, masked=False)

            @pl.when(jnp.logical_not(whole))
            def _():
                chunk(r, c, slot, length, first, masked=True)

            return n + 1

        n = jax.lax.fori_loop(first_chunk(r), nchunks, step, n)
        l = l_scr[...]
        o_ref[r] = (acc_scr[...] / jnp.where(l > 0.0, l, 1.0)
                    ).astype(o_ref.dtype)
        return n

    jax.lax.fori_loop(0, R, row, jnp.int32(0))


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "ppb", "groups"))
def _paged(q, k_pool, v_pool, tables, lengths, layer, scale: float,
           interpret: bool, ppb: int, first=None, groups: int = 1):
    """q: (R, H, Dw) — ``nh = Hkv·D / Dw`` products a chunk, each of ``H /
    nh`` query rows over ``Dw`` columns of a pool row (grouped: a k/v
    head's rows together, ``Dw = D``; diagonal: one product, the queries
    block-diagonal over the whole row); pools (L, NB, bs, Hkv·D) → o like q.
    ``ppb``: pages a chunk (:func:`_pages_per_chunk`). ``first (R,)``: each
    row's first live key (None: key 0, and the trace of the kernel without
    a window). ``groups``: grid steps the ``R`` rows
    are walked in (it divides ``R``)."""
    R, H, Dw = q.shape
    bs, HD = k_pool.shape[2:]
    W = tables.shape[1]
    windowed = first is not None
    R = R // groups                    # rows a grid step
    kern = functools.partial(_kernel, scale=scale, R=R, W=W, bs=bs, ppb=ppb)
    if HD != Dw:
        kern = functools.partial(kern, nh=HD // Dw)
    if windowed:
        kern = functools.partial(kern, windowed=True)
    if groups > 1:                     # one group: the trace without them
        kern = functools.partial(kern, groups=groups)
    operands = _unify_vma(
        lengths.astype(jnp.int32),
        *((first.astype(jnp.int32),) if windowed else ()),
        tables.reshape(-1).astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), q, k_pool, v_pool)
    whole = pl.BlockSpec((R, H, Dw), (lambda i, *_: (0, 0, 0))
                         if groups == 1 else (lambda i, *_: (i, 0, 0)))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # lengths, [first,] tables, layer
            num_scalar_prefetch=4 if windowed else 3,
            grid=(groups,),
            in_specs=[whole,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, ppb * bs, HD), k_pool.dtype),
                pltpu.VMEM((2, ppb * bs, HD), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),        # (k|v, slot)
                pltpu.VMEM((H, 1), jnp.float32),        # m
                pltpu.VMEM((H, 1), jnp.float32),        # l
                pltpu.VMEM((H, Dw), jnp.float32),       # acc
            ]),
        out_shape=_out_struct(q.shape, q.dtype, *operands),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attn_decode",
    )(*operands)


def paged_attention_decode(q, k_pool, v_pool, tables, lengths, layer,
                           first=None):
    """One layer's packed decode attention over the paged pool.

    ``q (R, H, D)``: one query per row — or ``(R, nq, H, D)``, a block of
    ``nq`` queries a row that all see the same keys ``[0, lengths[r])`` (a
    block-diffusion step: the block's own keys are scattered before the
    read), returned as ``(R, nq, H, D)``; ``k_pool``/``v_pool`` the WHOLE
    ``(L, NB, bs, Hkv·D)`` pools (a float dtype) and ``layer`` which of
    the ``L`` to read; ``tables (R, W)`` int32 physical blocks;
    ``lengths (R,)`` int32 live keys per row (``pos + 1``, at least 1,
    at most ``W·bs``): row ``r`` attends to logical positions ``[0,
    lengths[r])``, position ``p`` living at ``(tables[r, p // bs], p %
    bs)``. Returns ``o (R, H, D)`` in ``q.dtype``. Table entries past a
    row's last live block are never read, and nothing stored at or past
    a fill level can reach the output. ``first (R,)`` int32, a window
    layer's: row ``r`` attends to ``[first[r], lengths[r])`` alone; a chunk
    wholly below ``first[r]`` is not visited, a page wholly below it not
    fetched (its table entry may be a released block's, whatever it reads),
    a key below it scores ``_NEG`` with ``p`` forced to 0. ``None`` traces
    the kernel without any of that. The layout of a chunk's products
    (:func:`_form`) and the keys a chunk (:func:`_pages_per_chunk`) follow
    from the shapes, and the trace counts them: ``paged_attn.plan.<form>.
    <keys>`` once a trace of a layer. Callers gate on
    :func:`unsupported_reason` / ``backend.use_pallas``."""
    nq = q.shape[1] if q.ndim == 4 else 1
    R, H, D = q.shape[0], q.shape[-2], q.shape[-1]
    bs, HD = k_pool.shape[2:]
    Hkv = HD // D
    if HD != Hkv * D or H % Hkv != 0:
        raise ValueError(f"q heads ({H}) x head_dim ({D}) do not fit a "
                         f"pool row of {HD}")
    why = unsupported_reason(bs, Hkv, D, k_pool.dtype)
    if why is not None:
        raise ValueError(f"paged_attention_decode: {why}; gate on "
                         "unsupported_reason()")
    G = H // Hkv             # head h belongs to kv head h // G (group-major)
    form = _form(D)
    ppb = _pages_per_chunk(tables.shape[1], bs, HD * k_pool.dtype.itemsize)
    get_registry().counter(f"paged_attn.plan.{form}.{ppb * bs}").inc()
    # a block of queries a row: its nq query rows of a head ride beside the
    # head's own, query-major
    qh = q.reshape(R, nq, Hkv, G, D)
    if form == "grouped":
        # (R, Hkv, nq·G, D): a k/v head's rows together, padded to whole
        # sublanes (zero queries: finite, and dropped below)
        M = nq * G
        qk = qh.transpose(0, 2, 1, 3, 4).reshape(R, Hkv, M, D)
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, -M % _SUBLANES), (0, 0)))
        qk = qk.reshape(R, -1, D)
    else:
        # (R, nq·H, Hkv·D): row h holds its query in its kv head's D columns
        # and zeros elsewhere
        own = (jnp.arange(Hkv)[:, None, None]
               == jnp.arange(Hkv)[None, None, :])[..., None]  # (Hkv,1,Hkv,1)
        qk = jnp.where(own, qh[:, :, :, :, None, :], jnp.zeros((), q.dtype))
        qk = qk.reshape(R, nq * H, HD)
    # q and o are whole in VMEM: nq times the rows go in as many grid steps
    groups = nq if R % nq == 0 else 1
    o = _paged(qk, k_pool, v_pool, tables, lengths, layer, 1.0 / (D ** 0.5),
               _interpret(), ppb, first=first, groups=groups)
    if form == "grouped":
        o = o.reshape(R, Hkv, -1, D)[:, :, :M]
        o = o.reshape(R, Hkv, nq, G, D).transpose(0, 2, 1, 3, 4)
    else:
        # the diagonal blocks; a select, so an off-diagonal product (some
        # other head's V) never meets arithmetic
        o = jnp.where(own, o.reshape(R, nq, Hkv, G, Hkv, D),
                      jnp.zeros((), o.dtype)).sum(axis=4)
    return o.reshape(q.shape)
