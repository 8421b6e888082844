"""Row moves of the dropless expert layer — Pallas kernels over the live
prefix of its row buffer.

``parallel/moe.py::moe_ffn_dropless`` lays its (token, expert) pairs out in
a row buffer sized for the worst case. Under an expert share only a few
tiles at the front of it hold a pair (JoyAI's step: ~8.2 k of 135,168 rows),
and an XLA gather moves every row of the buffer whatever it holds. The
kernels here are driven by the row plan's integer maps and touch only rows
that hold a pair:

* ``moe_rows_to_buffer`` — ``xs[r] = x[row_pair[r] // k]``, times the
  pair's weight where one is given, for the rows of the live tiles (a
  padding row inside a live tile: zero). A tile past
  the live ones is not visited and NOT written: it holds whatever the
  allocation held. Nothing may read it, and nothing does — the grouped
  products visit live tiles only and zero their own dead rows
  (``ops/grouped_matmul.py::_live_rows``).
* ``moe_rows_to_tokens`` — ``out[t] = Σ_r w[t, r] · ys[rows[t, r]]`` over
  the ``count[t]`` pairs of token ``t`` that have a row, summed in f32 in
  ascending ``r``: the order the layer's XLA path sums ``j`` in, since the
  slots are a token's held pairs in that order. A pair with no row is not
  fetched.
* ``moe_rows_dweight`` — ``dw[t, r] = Σ_d ys[rows[t, r], d] · dout[t, d]``,
  its sibling: the same fetch, a product and a row sum after it.

**How a row is fetched.** A DMA cannot take one row out of a tiled 2-D
array (Mosaic: a slice of the second-minor axis is aligned to the tiling),
so a source is first *packed*: ``moe_rows_pack`` rewrites the tiles that
matter — all of ``x``, the live tiles of a buffer — as ``(rows, S, 128)``
32-bit words, a row one contiguous slab that a DMA addresses by its leading
index. A bf16 row's word ``c`` holds columns ``c`` (low half) and ``c + d /
2`` (high half), so that unpacking yields whole lane blocks; an f32 row's
words are its values. The packed array has one tile more than its source:
the tile after the packed ones is zero, and a padding row is fetched from
there.

Every entry point is jitted, so the unrolled layers of a program share one
trace and one lowering of each kernel and shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byteps_tpu.ops.backend import interpret as _interpret
from byteps_tpu.ops.flash_attention import _out_struct, _unify_vma
from byteps_tpu.ops.grouped_matmul import ROW_TILE

__all__ = ["moe_rows_pack", "moe_rows_to_buffer", "moe_rows_to_tokens",
           "moe_rows_dweight", "rows_supported", "ROWS"]

#: rows of a packed tile and of a tile of ``moe_rows_to_buffer``: the row
#: tile of a chunk's or a training step's buffer
ROWS = ROW_TILE
_LANES = 128
_U32, _F32, _I32 = jnp.uint32, jnp.float32, jnp.int32
_HIGH = 0xFFFF0000
# VMEM the fetched rows of a token tile may take (the tile follows from it)
_FETCH_BYTES = 4 * 1024 * 1024
# the calls state their limit: blocks of a 7,168-wide row are over Mosaic's
# scoped default of 16 MiB, and a v5e core has 128 MiB
_VMEM_LIMIT = 64 * 1024 * 1024


def _slabs(d: int, itemsize: int) -> int:
    """Lane blocks of 32-bit words a packed row of ``d`` values takes."""
    return d * itemsize // (4 * _LANES)


def rows_supported(tokens: int, d: int, dtype) -> bool:
    """Whether the kernels take rows of ``d`` values of ``dtype`` out of
    ``tokens`` tokens: bf16 or f32, a row of whole lane blocks of words,
    the tokens in whole tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    return (jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32))
            and d * itemsize % (4 * _LANES) == 0 and tokens % ROWS == 0)


def _token_tile(k: int, slabs: int) -> int:
    """Tokens a grid step of the token-side kernels holds: the largest
    power of two (8 .. ROWS, so it divides the tokens) whose ``k`` fetched
    rows a token fit ``_FETCH_BYTES`` (a slab is padded to 8 sublanes)."""
    per_token = k * (-(-slabs // 8) * 8) * _LANES * 4
    tile = ROWS
    while tile > 8 and tile * per_token > _FETCH_BYTES:
        tile //= 2
    return tile


def _bits(x):
    return jax.lax.bitcast_convert_type(x, _U32)


def _lanes_at(col):
    """The lane block that starts at column ``col`` (a multiple of 128,
    traced or not)."""
    return pl.ds(pl.multiple_of(col, _LANES), _LANES)


def _word_block(x_ref, s, slabs: int):
    """Word block ``s`` of the tile in ``x_ref``: ``(rows, 128)`` uint32."""
    if x_ref.dtype.itemsize == 4:
        return _bits(x_ref[:, _lanes_at(s * _LANES)])
    return ((_bits(x_ref[:, _lanes_at(s * _LANES)].astype(_F32)) >> 16)
            | (_bits(x_ref[:, _lanes_at((slabs + s) * _LANES)].astype(_F32))
               & _U32(_HIGH)))


def _value_blocks(words, s, slabs: int, itemsize: int):
    """``[(first column, (rows, 128) f32 values)]`` held by word block
    ``s``: one lane block of an f32 row, two of a bf16 row."""
    f32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=_F32)
    if itemsize == 4:
        return [(s * _LANES, f32(words))]
    return [(s * _LANES, f32(words << 16)),
            ((slabs + s) * _LANES, f32(words & _U32(_HIGH)))]


def _each_slab(slabs: int, body):
    """``body(s)`` for every word block of a row, as ONE traced loop: a
    7,168-wide row has 28 blocks, and unrolled they are what a program's
    lowering spends its time on (a serve cell lowers ~40 chunk programs)."""
    def step(s, carry):
        body(s)
        return carry

    jax.lax.fori_loop(0, slabs, step, 0)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=_VMEM_LIMIT)


# --------------------------------------------------------------------------
# pack: tiles of (rows, d) values -> (rows, S, 128) words, a zero tile after
# --------------------------------------------------------------------------
def _pack_kernel(n_ref, x_ref, p_ref, *, slabs: int):
    i = pl.program_id(0)

    @pl.when(i < n_ref[0])
    def _pack():
        def block(s):
            p_ref[:, pl.ds(s, 1), :] = _word_block(x_ref, s, slabs)[:, None, :]

        _each_slab(slabs, block)

    @pl.when(i == n_ref[0])
    def _zero():
        p_ref[...] = jnp.zeros_like(p_ref)


@jax.jit
def moe_rows_pack(x, n_tiles):
    """``x (rows, d)`` → ``(rows + ROWS, S, 128)`` uint32: the first
    ``n_tiles[0]`` tiles of ``ROWS`` rows packed, the tile after them zero,
    the rest not written."""
    rows, d = x.shape
    slabs = _slabs(d, x.dtype.itemsize)
    n_tiles, x = _unify_vma(n_tiles.astype(_I32), x)
    return pl.pallas_call(
        functools.partial(_pack_kernel, slabs=slabs),
        out_shape=_out_struct((rows + ROWS, slabs, _LANES), _U32, x, n_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec(
                (ROWS, d),
                lambda i, n: (jnp.maximum(jnp.minimum(i, n[0] - 1), 0), 0))],
            out_specs=pl.BlockSpec((ROWS, slabs, _LANES),
                                   lambda i, n: (i, 0, 0)),
            grid=(n_tiles[0] + 1,)),
        compiler_params=_params(),
        interpret=_interpret(),
        name="moe_rows_pack",
    )(n_tiles, x)


# --------------------------------------------------------------------------
# xs[r] = [w(pair r) *] x[row_pair[r] // k] over the live tiles
# --------------------------------------------------------------------------
def _to_buffer_kernel(n_live, pair_ref, *refs, k: int, zero_row: int,
                      slabs: int, weighted: bool):
    del n_live
    if weighted:
        w_tab, p_hbm, out_ref, buf, w_col, sem = refs
    else:
        p_hbm, out_ref, buf, sem = refs
    tm = out_ref.shape[0]
    lane = jax.lax.broadcasted_iota(_I32, (1, _LANES), 1)

    def start(r, carry):
        # a row that holds no pair names the pair past the last one: its
        # token is past the last token, and fetched from the zero row
        # (lax.div / lax.rem with a Python divisor: jnp's floor division
        # makes array constants, which under shard_map's check_vma need a
        # pvary that Mosaic does not lower; the pairs are not negative)
        pair = pair_ref[0, r]
        pltpu.make_async_copy(
            p_hbm.at[jnp.minimum(jax.lax.div(pair, k), zero_row)], buf.at[r],
            sem).start()
        if weighted:
            # the pair's weight out of the table in VMEM: its row of 128,
            # its lane picked by a compare and summed (one value and
            # zeros: exact), kept along the lanes of the row's sublane
            at = jnp.minimum(pair, zero_row * k - 1)
            mine = jnp.where(
                lane == jax.lax.rem(at, _LANES),
                w_tab[pl.ds(jax.lax.div(at, _LANES), 1), :], 0.0)
            w_col[pl.ds(r, 1), :] = jnp.broadcast_to(
                jnp.sum(mine, axis=1, keepdims=True), (1, _LANES))
        return carry

    jax.lax.fori_loop(0, tm, start, 0)

    def wait(r, carry):
        pltpu.make_async_copy(p_hbm.at[0], buf.at[r], sem).wait()
        return carry

    jax.lax.fori_loop(0, tm, wait, 0)

    def block(s):
        for col, vals in _value_blocks(buf[:, pl.ds(s, 1), :][:, 0, :], s,
                                       slabs, out_ref.dtype.itemsize):
            if weighted:
                vals = w_col[...] * vals
            out_ref[:, _lanes_at(col)] = vals.astype(out_ref.dtype)

    _each_slab(slabs, block)


@functools.partial(jax.jit, static_argnames=("k", "dtype"))
def _to_buffer(packed, row_pair, weight, n_live, k, dtype):
    zero_row = packed.shape[0] - ROWS
    slabs = packed.shape[1]
    d = slabs * _LANES * 4 // jnp.dtype(dtype).itemsize
    n_rows = row_pair.shape[0]
    weighted = weight is not None
    table = [weight.astype(_F32).reshape(-1, _LANES)] if weighted else []
    n_live, pairs, *table, packed = _unify_vma(
        n_live.astype(_I32), row_pair.astype(_I32).reshape(-1, 1, ROWS),
        *table, packed)
    return pl.pallas_call(
        functools.partial(_to_buffer_kernel, k=k, zero_row=zero_row,
                          slabs=slabs, weighted=weighted),
        out_shape=_out_struct((n_rows, d), dtype, packed, pairs, *table),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((None, 1, ROWS), lambda i, n: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                *[pl.BlockSpec(t.shape, lambda i, n: (0, 0)) for t in table],
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((ROWS, d), lambda i, n: (i, 0)),
            grid=(n_live[0],),
            scratch_shapes=[
                pltpu.VMEM((ROWS, slabs, _LANES), _U32),
                *[pltpu.VMEM((ROWS, _LANES), _F32)] * weighted,
                pltpu.SemaphoreType.DMA(())]),
        compiler_params=_params(),
        interpret=_interpret(),
        name="moe_rows_to_buffer",
    )(n_live, pairs, *table, packed)


def moe_rows_to_buffer(x, row_pair, k: int, weight, n_live):
    """``xs (n_rows, d)`` with ``xs[r] = x[row_pair[r] // k]`` on the first
    ``n_live[0]`` tiles of ``ROWS`` rows, zero where ``row_pair[r]`` is past
    the last of the ``tokens · k`` pairs; the tiles after them are not
    written. With ``weight (tokens, k)``: ``xs[r] = weight.flat[row_pair[r]]
    · x[..]``, an f32 product rounded to ``x.dtype``."""
    packed = moe_rows_pack(x, jnp.full((1,), x.shape[0] // ROWS, _I32))
    return _to_buffer(packed, row_pair, weight, n_live, k, x.dtype)


# --------------------------------------------------------------------------
# the token side: a token's held pairs fetched by slot, then summed or dotted
# --------------------------------------------------------------------------
def _fetch(cnt_ref, rows_ref, p_hbm, got, sem, k: int):
    """Start and await the DMA of every held pair's row of this token tile
    into ``got[slot, token]``; returns the most slots a token fills."""
    tile = got.shape[1]

    def token(t, carry):
        n, most = carry
        c = cnt_ref[0, t]

        def slot(r, inner):
            pltpu.make_async_copy(p_hbm.at[rows_ref[0, t * k + r]],
                                  got.at[r, t], sem).start()
            return inner

        jax.lax.fori_loop(0, c, slot, 0)
        return n + c, jnp.maximum(most, c)

    n, most = jax.lax.fori_loop(0, tile, token, (jnp.int32(0), jnp.int32(0)))

    def wait(_, carry):
        pltpu.make_async_copy(p_hbm.at[0], got.at[0, 0], sem).wait()
        return carry

    jax.lax.fori_loop(0, n, wait, 0)
    return most


def _slot_column(ref, r):
    """Column ``r`` (a traced slot) of a ``(tile, k)`` block as ``(tile,
    1)``: a compare along the lanes and a sum (one value and zeros: exact)."""
    slot = jax.lax.broadcasted_iota(_I32, ref.shape, 1)
    return jnp.sum(jnp.where(slot == r, ref[...], 0.0), axis=1, keepdims=True)


def _to_tokens_kernel(cnt_ref, rows_ref, cnt_col, w_ref, p_hbm, out_ref, got,
                      acc, sem, *, k: int, slabs: int):
    most = _fetch(cnt_ref, rows_ref, p_hbm, got, sem, k)
    acc[...] = jnp.zeros_like(acc)

    # a loop over the slots that any token of the tile fills, in ascending
    # order (the order of the sums); traced once, not once a slot
    def add(r, carry):
        filled = cnt_col[...] > r                          # (tile, 1)
        w_col = _slot_column(w_ref, r)
        # (the blocks unrolled: inside a traced loop XLA:CPU contracts the
        # product and the sum into one rounding, and the interpreted kernel
        # is held to the gathers bit for bit)
        for s in range(slabs):
            for col, vals in _value_blocks(got[r, :, s, :], s, slabs,
                                           out_ref.dtype.itemsize):
                acc[:, col:col + _LANES] += jnp.where(filled, w_col * vals,
                                                      0.0)
        return carry

    jax.lax.fori_loop(0, most, add, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def _dweight_kernel(cnt_ref, rows_ref, cnt_col, dout_ref, p_hbm, out_ref,
                    got, sem, *, k: int, slabs: int):
    most = _fetch(cnt_ref, rows_ref, p_hbm, got, sem, k)
    out_ref[...] = jnp.zeros_like(out_ref)
    lane = jax.lax.broadcasted_iota(_I32, out_ref.shape, 1)

    # (the blocks unrolled: the row is laid out whole and summed in one
    # reduction, the order of the gathers' ``jnp.sum``; the backward alone
    # runs this kernel)
    def dot(r, carry):
        blocks = dict(b for s in range(slabs) for b in _value_blocks(
            got[r, :, s, :], s, slabs, dout_ref.dtype.itemsize))
        row = jnp.concatenate([blocks[c] for c in sorted(blocks)], axis=1)
        dots = jnp.sum(row * dout_ref[...].astype(_F32), axis=-1,
                       keepdims=True)
        out_ref[...] = jnp.where((lane == r) & (cnt_col[...] > r), dots,
                                 out_ref[...])
        return carry

    jax.lax.fori_loop(0, most, dot, 0)


def _token_call(kernel, name: str, packed, count, rows, operand, out_dtype,
                d: int, accumulates: bool):
    """One call of a token-side kernel: ``count (T,)`` and ``rows (T, k)``
    in SMEM a tile at a time, ``count`` again as a VMEM column, ``operand
    (T, ·)`` a tile at a time, the packed rows left in HBM; an f32
    accumulator of the output's shape where the kernel ``accumulates``."""
    T, k = rows.shape
    slabs = packed.shape[1]
    tile = _token_tile(k, slabs)
    count, rows, operand, packed = _unify_vma(
        count.astype(_I32), rows.astype(_I32), operand, packed)
    return pl.pallas_call(
        functools.partial(kernel, k=k, slabs=slabs),
        out_shape=_out_struct((T, d), out_dtype, packed, rows, operand),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            in_specs=[
                pl.BlockSpec((None, 1, tile), lambda i: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((None, 1, tile * k), lambda i: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((tile, 1), lambda i: (i, 0)),
                pl.BlockSpec((tile, operand.shape[1]), lambda i: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tile, d), lambda i: (i, 0)),
            grid=(T // tile,),
            scratch_shapes=[pltpu.VMEM((k, tile, slabs, _LANES), _U32),
                            *[pltpu.VMEM((tile, d), _F32)] * accumulates,
                            pltpu.SemaphoreType.DMA(())]),
        compiler_params=_params(),
        interpret=_interpret(),
        name=name,
    )(count.reshape(-1, 1, tile), rows.reshape(-1, 1, tile * k),
      count.reshape(T, 1), operand, packed)


@jax.jit
def moe_rows_to_tokens(ys, count, rows, weight, n_live):
    """``out (T, d)`` with ``out[t] = Σ_{r < count[t]} weight[t, r] ·
    ys[rows[t, r]]``: f32 products summed in ascending ``r``, rounded to
    ``ys.dtype``. ``rows (T, k)`` name rows of the first ``n_live[0]``
    tiles of ``ys``; a slot at or past ``count[t]`` is not read."""
    return _token_call(
        _to_tokens_kernel, "moe_rows_to_tokens", moe_rows_pack(ys, n_live),
        count, rows, weight.astype(_F32), ys.dtype, ys.shape[1], True)


@jax.jit
def moe_rows_dweight(ys, count, rows, dout, n_live):
    """``dw (T, k)`` f32 with ``dw[t, r] = Σ_d ys[rows[t, r], d] · dout[t,
    d]`` for ``r < count[t]`` (f32 products, summed along the row), zero in
    the other slots."""
    return _token_call(
        _dweight_kernel, "moe_rows_dweight", moe_rows_pack(ys, n_live),
        count, rows, dout, _F32, rows.shape[1], False)
