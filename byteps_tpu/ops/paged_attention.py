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
  walks ``ceil(length[r] / T)`` chunks of ``T = ppb·bs`` keys, and a
  page is fetched (one DMA for K, one for V) only where it starts below
  the fill level. A padded row (length 1, scratch table) reads one
  page. Chunks are double buffered across rows: while chunk ``c`` is
  computed, chunk ``c+1`` — or the next row's first — is in flight.
* **A window layer's row starts where its window does.** With ``first``
  (one scalar-prefetched position a row) a row walks the chunks from the
  one holding ``first[r]`` on: pages wholly below it are not fetched (the
  cache has handed their blocks back; the table still has an entry a
  logical block, 0 where one was released), keys below it are masked like
  keys past the fill level. Without ``first`` the trace is the kernel as
  it was: the branch is taken in Python.
* **Garbage never reaches the output.** The gathered view zeroed every
  position at or past the fill level; here scores there take ``_NEG``
  and their ``p`` is forced to 0, and V's rows there are selected to 0
  before ``p·V``, so whatever a recycled block or a stale buffer holds
  (NaN, inf) cannot enter (0 × NaN).

A decode query is one row per head, which gives the MXU nothing to
tile head by head. So the heads go through it together: the caller's
``q (R, H, D)`` is laid out block-diagonally as ``(R, H, Hkv·D)`` (row
``h`` holds its query in its kv head's ``D`` columns and zeros
elsewhere), scores are ONE ``(H, Hkv·D) × (T, Hkv·D)ᵀ`` matmul per
chunk — the zeros pick each head's own keys, and the ``G = H/Hkv`` query
heads of a group ride their kv head's columns (GQA reads the narrow
pool once) — and ``p·V`` is one ``(H, T) × (T, Hkv·D)`` matmul whose
diagonal blocks are the output; the wrapper picks them out. The MXU
does ``Hkv`` times the needed FLOPs and is still far from the limit:
the chunk's time is its weight-tile loads, the same number as bytes/32
KB. Online softmax ``(m, l, acc)`` in VMEM with keys on lanes, f32
scores and accumulation, K and V in the pool's dtype (``p`` rounded to
it for ``p·V``, as the MXU rounds the twin's), output in ``q.dtype``:
the contract of ``ops/flash_decode.py``, and of ``_gather_view`` +
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

from byteps_tpu.ops.backend import interpret as _interpret
from byteps_tpu.ops.flash_attention import _NEG, _out_struct, _unify_vma

__all__ = ["paged_attention_decode", "unsupported_reason"]

_CHUNK_TOKENS = 128                  # keys per chunk: one MXU tile wide
_BUFFER_BUDGET = 8 * 1024 * 1024     # VMEM for the 2 slots of K and of V


def _pages_per_chunk(W: int, bs: int, row_bytes: int) -> int:
    """Pages fetched and computed per loop iteration: ``_CHUNK_TOKENS``
    keys, never more than the table is wide, the four chunk buffers
    inside their budget (0: not even one page fits)."""
    return min(W, max(1, _CHUNK_TOKENS // bs),
               _BUFFER_BUDGET // (4 * bs * row_bytes))


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
            windowed: bool = False, groups: int = 1):
    # a windowed trace prefetches one scalar array more: each row's first
    # live key. Without it nothing below differs from the kernel as it was
    first_ref = refs[0] if windowed else None
    (tab_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems,
     m_scr, l_scr, acc_scr) = refs[1:] if windowed else refs
    H, HD = acc_scr.shape
    T = bs * ppb
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
        rows = pl.ds(i * bs, bs)
        return (pltpu.make_async_copy(k_hbm.at[layer, blk],
                                      kbuf.at[slot, rows], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, blk],
                                      vbuf.at[slot, rows], sems.at[1, slot]))

    def dma(r, c, slot, wait: bool):
        # a page is live where it starts below the row's fill level;
        # start and wait walk the SAME predicate, so every DMA issued
        # is awaited and no dead page is ever fetched
        for i in range(ppb):
            live = (c * ppb + i) * bs < len_ref[r]
            if windowed:           # and it ends above the first live key
                live &= (c * ppb + i + 1) * bs > first_ref[r]

            @pl.when(live)
            def _(i=i):
                for cp in page_copies(r, c, slot, i):
                    cp.wait() if wait else cp.start()

    def chunk(r, c, slot, length, first):
        k = kbuf[slot]                                    # (T, HD)
        v = vbuf[slot]
        q = q_ref[r]                                      # (H, HD)
        ct = jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(
            q.astype(ct), k.astype(ct), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (H, T)
        at = c * T + jax.lax.broadcasted_iota(jnp.int32, (H, T), 1)
        live = at < length
        if windowed:
            live &= at >= first
        s = jnp.where(live, s, _NEG)
        m_prev = m_scr[...]                               # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        # rows at or past the fill level: a partly filled block's tail,
        # or a page this chunk never fetched — either may hold NaN/inf
        row_at = c * T + jax.lax.broadcasted_iota(jnp.int32, (T, HD), 0)
        rows_live = row_at < length
        if windowed:               # a page the window only partly covers,
            rows_live &= row_at >= first    # or one behind it never fetched
        v = jnp.where(rows_live, v, jnp.zeros((), v.dtype))
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (H, HD)
        m_scr[...] = m_new

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
            chunk(r, c, slot, length, first)
            return n + 1

        n = jax.lax.fori_loop(first_chunk(r), nchunks, step, n)
        l = l_scr[...]
        o_ref[r] = (acc_scr[...] / jnp.where(l > 0.0, l, 1.0)
                    ).astype(o_ref.dtype)
        return n

    jax.lax.fori_loop(0, R, row, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "groups"))
def _paged(qbd, k_pool, v_pool, tables, lengths, layer, scale: float,
           interpret: bool, first=None, groups: int = 1):
    """qbd: (R, H, Hkv·D) block-diagonal queries; pools (L, NB, bs,
    Hkv·D) → (R, H, Hkv·D), row ``h``'s output in its kv head's block.
    ``first (R,)``: each row's first live key (None: key 0, and the trace
    of the kernel as it was without a window). ``groups``: grid steps the
    ``R`` rows are walked in (it divides ``R``)."""
    R, H, HD = qbd.shape
    bs = k_pool.shape[2]
    W = tables.shape[1]
    ppb = _pages_per_chunk(W, bs, HD * k_pool.dtype.itemsize)
    windowed = first is not None
    R = R // groups                    # rows a grid step
    kern = functools.partial(_kernel, scale=scale, R=R, W=W, bs=bs, ppb=ppb)
    if windowed:
        kern = functools.partial(kern, windowed=True)
    if groups > 1:                     # one group: the trace as it was
        kern = functools.partial(kern, groups=groups)
    operands = _unify_vma(
        lengths.astype(jnp.int32),
        *((first.astype(jnp.int32),) if windowed else ()),
        tables.reshape(-1).astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), qbd, k_pool, v_pool)
    whole = pl.BlockSpec((R, H, HD), (lambda i, *_: (0, 0, 0))
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
                pltpu.SemaphoreType.DMA((2, 2)),      # (k|v, slot)
                pltpu.VMEM((H, 1), jnp.float32),      # m
                pltpu.VMEM((H, 1), jnp.float32),      # l
                pltpu.VMEM((H, HD), jnp.float32),     # acc
            ]),
        out_shape=_out_struct(qbd.shape, qbd.dtype, *operands),
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
    the kernel without any of that. Callers gate on
    :func:`unsupported_reason` / ``backend.use_pallas``."""
    nq = 1
    if q.ndim == 4:
        # a block of queries a row: its nq x H query rows ride the layout
        # the H heads have, query-major (row j is head j % H of query j // H)
        nq = q.shape[1]
        q = q.reshape(q.shape[0], -1, q.shape[-1])
    R, H, D = q.shape
    H //= nq
    bs, HD = k_pool.shape[2:]
    Hkv = HD // D
    if HD != Hkv * D or H % Hkv != 0:
        raise ValueError(f"q heads ({H}) x head_dim ({D}) do not fit a "
                         f"pool row of {HD}")
    why = unsupported_reason(bs, Hkv, D, k_pool.dtype)
    if why is not None:
        raise ValueError(f"paged_attention_decode: {why}; gate on "
                         "unsupported_reason()")
    # head h belongs to kv head h // G (group-major, as flash_decode)
    head = jnp.arange(nq * H)[:, None]
    if nq > 1:
        head = head % H
    own = (head // (H // Hkv)
           == jnp.arange(Hkv)[None, :])[None, :, :, None]  # (1, nq·H, Hkv, 1)
    qbd = jnp.where(own, q[:, :, None, :], jnp.zeros((), q.dtype))
    # q and o are whole in VMEM: nq times the rows go in as many grid steps
    groups = nq if R % nq == 0 else 1
    o = _paged(qbd.reshape(R, nq * H, HD), k_pool, v_pool, tables, lengths,
               layer, 1.0 / (D ** 0.5), _interpret(), first=first,
               groups=groups)
    # the diagonal blocks; a select, so an off-diagonal product (some
    # other head's V) never meets arithmetic
    o = jnp.where(own, o.reshape(R, nq * H, Hkv, D), jnp.zeros((), o.dtype))
    o = o.sum(axis=2)
    return o if nq == 1 else o.reshape(R, nq, H, D)
