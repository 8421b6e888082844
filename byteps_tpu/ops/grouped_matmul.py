"""Grouped matrix product — the Pallas kernels under dropless MoE routing.

``grouped_matmul(lhs (M, K), rhs (G, K, N), group_sizes (G,), tm)`` gives
``out (M, N)``: the rows of ``lhs`` are ``G`` consecutive groups, group
``g`` of ``group_sizes[g]`` rows, and each group's rows are multiplied by
its own matrix ``rhs[g]``. It is ``jax.lax.ragged_dot`` (the jnp twin
here, behind ``ops/backend.py`` as the flash kernels' twin is) under one
contract that makes the kernels plain:

* **Groups are tile-aligned.** Every ``group_sizes[g]`` is a multiple of
  the row tile ``tm`` (0 is allowed: an empty group), and ``M`` is too, so
  a row tile belongs to exactly one group and no kernel masks rows. The
  caller pads each group with zero rows (``parallel/moe.py`` lays its
  grouped token-expert pairs out so); zero rows give zero products and add
  nothing to a weight gradient. **The caller chooses ``tm``**, since the
  layout is its own: ``parallel/moe.py::dropless_row_tile`` derives it
  from the pairs its program holds, between :func:`min_row_tile` (a
  decode step's few rows an expert) and ``ROW_TILE`` (a chunk, a training
  step), and the kernels take the tile they are given.
* **Only tiles that hold rows are visited.** ``M`` is the caller's static
  worst case; ``sum(group_sizes) / tm`` tiles are live, and that number is
  the (dynamic) extent of the kernels' tile axis. Rows past the last
  group read as zero, as ``ragged_dot`` gives them.

Three kernels, each with a name of its own in a device trace:
``moe_gmm_fwd`` (``out = lhs · rhs[g]``), ``moe_gmm_dx`` (``dlhs = dout ·
rhs[g]ᵀ``, the same kernel contracting the other axis) and ``moe_gmm_dw``
(``drhs[g] = Σ_tiles lhs_tileᵀ · dout_tile``: the tile axis innermost, one
f32 accumulator carried while the group stays the same). All accumulate
in f32 and round once, to the operand type, at the store.

**Blocks are chosen from the shapes alone** (``_rows_blocks``,
``_dw_blocks``: pure functions of the row tile, the two widths and the
operand item size; no argument, environment variable or config field).
A block of a width is the whole axis or any multiple of 128 that divides
it, and a call takes the set with the fewest grid steps a row tile whose
pipeline — two buffers of each operand block, two of the output block, the
f32 accumulator — fits ``_VMEM_BUDGET``. An expert matrix that fits is
taken whole (Mellum2's 2304 x 896 and JoyAI's 2048 x 768 in bf16: one step
a row tile, the matrix not fetched again for the group's next tile); one
that does not (dots3's 5120 x 1536) splits its contraction axis first and
keeps the output axis as wide as the steps allow.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byteps_tpu.ops.backend import interpret as _interpret
from byteps_tpu.ops.backend import use_pallas
from byteps_tpu.ops.flash_attention import _out_struct, _unify_vma

__all__ = ["grouped_matmul", "grouped_matmul_jnp", "ROW_TILE",
           "min_row_tile"]

# Rows of a tile. The one block the kernels do not choose: the caller lays
# its groups out by it, so the caller picks it — ``parallel/moe.py::
# dropless_row_tile`` from the pairs its program holds, between the two
# numbers below. ``ROW_TILE`` (two MXU passes, 4 tiles of padding/MB) is the
# cap and the default of :func:`grouped_matmul`.
ROW_TILE = 256


def min_row_tile(itemsize: int) -> int:
    """The smallest row block Mosaic tiles for operands of ``itemsize``
    bytes: one packed tile of sublanes (8 rows of 32 bits — 8 for f32, 16
    for bf16)."""
    return 32 // itemsize


# What one call's pipeline may hold of Mosaic's 16 MiB of scoped VMEM on a
# v5e (its default): the rest is the compiler's own (the f32 product before
# it is stored, spills).
_VMEM_BUDGET = 14 * 1024 * 1024
_SCOPED_VMEM = 16 * 1024 * 1024


def _widths(n: int) -> List[int]:
    """The legal blocks of a last dimension of ``n``, widest first: the
    whole axis, then every multiple of 128 that divides it."""
    return [n] + [w for w in range(n - n % 128, 0, -128)
                  if w != n and n % w == 0]


def _set_bytes(a: int, b: int, out: int, itemsize: int) -> int:
    """VMEM the pipeline holds for operand blocks of ``a`` and ``b``
    elements and an output block of ``out``: two buffers of each, and the
    f32 accumulator."""
    return 2 * (a + b + out) * itemsize + 4 * out


def _rows_bytes(tm: int, tc: int, to: int, itemsize: int) -> int:
    return _set_bytes(tm * tc, tc * to, tm * to, itemsize)


def _dw_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    return _set_bytes(tm * tk, tm * tn, tk * tn, itemsize)


def _fewest_steps(cands) -> Tuple[int, int]:
    """Of candidates ``(grid steps a row tile, elements read again, blocks,
    bytes of the set)``: among the sets that fit ``_VMEM_BUDGET`` the one
    of the fewest steps, then of the fewest elements read again; where none
    fits, the smallest (the call then asks for the VMEM it needs)."""
    cands = list(cands)
    fit = [c[:3] for c in cands if c[3] <= _VMEM_BUDGET]
    return min(fit)[2] if fit else min(cands, key=lambda c: c[3])[2]


def _rows_blocks(tm: int, C: int, out_dim: int, itemsize: int
                 ) -> Tuple[int, int]:
    """``(tc, to)`` of ``_rows_call`` from the shapes alone. Whole axes
    where they fit: with ``tc == C`` an expert's block of ``rhs`` depends on
    the tile's group only, so the next row tile of the same group does not
    fetch it again and nothing is summed across grid steps; with ``to ==
    out_dim`` the row block of ``lhs`` is read once. Where both do not fit:
    the fewest grid steps, and of two such sets the wider output block (the
    contraction axis is split first: ``lhs`` is read ``out_dim // to``
    times)."""
    return _fewest_steps(
        ((C // tc) * (out_dim // to), out_dim // to, (tc, to),
         _rows_bytes(tm, tc, to, itemsize))
        for tc in _widths(C) for to in _widths(out_dim))


def _dw_blocks(tm: int, K: int, N: int, itemsize: int) -> Tuple[int, int]:
    """``(tk, tn)`` of ``_dw_call``: the fewest ``(k, n)`` walks over the
    live tiles, then the fewest elements read again (``lhs`` is read ``N //
    tn`` times and ``dout`` ``K // tk`` times)."""
    return _fewest_steps(
        ((K // tk) * (N // tn), K * (N // tn) + N * (K // tk), (tk, tn),
         _dw_bytes(tm, tk, tn, itemsize))
        for tk in _widths(K) for tn in _widths(N))


def _compiler_params(semantics: Tuple[str, ...], need: int):
    """A set of ``need`` bytes within the budget runs under Mosaic's
    default; a larger one (no set fit) asks for its bytes and the headroom
    the budget leaves under the default."""
    limit = None if need <= _VMEM_BUDGET else \
        need + _SCOPED_VMEM - _VMEM_BUDGET
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


def _tile_groups(group_sizes: jnp.ndarray, tm: int, n_tiles: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(group of each row tile, number of live tiles). Tiles past the
    live ones carry the last group and are never visited. Tile ``i`` lies
    in the group after those that end at or before it: one ``(n_tiles, G)``
    compare and a count (no search: ``parallel/moe.py::_row_plan`` has
    why)."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32)) // tm
    tiles = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_group = jnp.sum((ends[None, :] <= tiles[:, None]).astype(jnp.int32),
                         axis=1)
    return jnp.minimum(tile_group, group_sizes.shape[0] - 1), ends[-1:]


# --------------------------------------------------------------------------
# out[tile] = lhs[tile] · rhs[group(tile)]   (or · rhs[..]ᵀ)
# --------------------------------------------------------------------------
def _rows_kernel(tile_group, n_live, lhs_ref, rhs_ref, out_ref, acc,
                 *, n_k: int, transposed: bool):
    del tile_group, n_live
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...],
        (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _store():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def _rows_call(lhs, rhs, tile_group, n_live, tm: int, transposed: bool,
               name: str):
    """``lhs (M, C)`` times, per row tile, ``rhs[g] (C, O)`` — or, when
    ``transposed``, ``rhs[g] (O, C)`` contracted on its second axis."""
    M, C = lhs.shape
    out_dim = rhs.shape[1] if transposed else rhs.shape[2]
    tc, to = _rows_blocks(tm, C, out_dim, lhs.dtype.itemsize)
    n_k = C // tc
    if transposed:
        rhs_spec = pl.BlockSpec((None, to, tc),
                                lambda n, i, k, tg, nl: (tg[i], n, k))
    else:
        rhs_spec = pl.BlockSpec((None, tc, to),
                                lambda n, i, k, tg, nl: (tg[i], k, n))
    lhs, rhs, tile_group, n_live = _unify_vma(lhs, rhs, tile_group, n_live)
    return pl.pallas_call(
        functools.partial(_rows_kernel, n_k=n_k, transposed=transposed),
        out_shape=_out_struct((M, out_dim), lhs.dtype, lhs, rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[
                pl.BlockSpec((tm, tc), lambda n, i, k, tg, nl: (i, k)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((tm, to), lambda n, i, k, tg, nl: (i, n)),
            grid=(out_dim // to, n_live[0], n_k),
            scratch_shapes=[pltpu.VMEM((tm, to), jnp.float32)],
        ),
        compiler_params=_compiler_params(
            ("parallel", "arbitrary", "arbitrary"),
            _rows_bytes(tm, tc, to, lhs.dtype.itemsize)),
        interpret=_interpret(),
        name=name,
    )(tile_group, n_live, lhs, rhs)


# --------------------------------------------------------------------------
# drhs[g] = sum over the tiles of g of lhs[tile]ᵀ · dout[tile]
# --------------------------------------------------------------------------
def _dw_kernel(tile_group, n_live, lhs_ref, dout_ref, out_ref, acc):
    i = pl.program_id(2)
    g = tile_group[i]
    first = jnp.logical_or(i == 0, tile_group[jnp.maximum(i - 1, 0)] != g)
    last = jnp.logical_or(
        i == n_live[0] - 1,
        tile_group[jnp.minimum(i + 1, tile_group.shape[0] - 1)] != g)

    @pl.when(first)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _store():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def _dw_call(lhs, dout, tile_group, n_live, n_groups: int, tm: int):
    M, K = lhs.shape
    N = dout.shape[1]
    tk, tn = _dw_blocks(tm, K, N, lhs.dtype.itemsize)
    lhs, dout, tile_group, n_live = _unify_vma(lhs, dout, tile_group, n_live)
    return pl.pallas_call(
        _dw_kernel,
        out_shape=_out_struct((n_groups, K, N), lhs.dtype, lhs, dout),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda k, n, i, tg, nl: (i, k)),
                pl.BlockSpec((tm, tn), lambda k, n, i, tg, nl: (i, n)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda k, n, i, tg, nl: (tg[i], k, n)),
            grid=(K // tk, N // tn, n_live[0]),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"),
            _dw_bytes(tm, tk, tn, lhs.dtype.itemsize)),
        interpret=_interpret(),
        name="moe_gmm_dw",
    )(tile_group, n_live, lhs, dout)


# --------------------------------------------------------------------------
# the differentiable op
# --------------------------------------------------------------------------
def _live_rows(x, group_sizes):
    """Rows past the last group were never written: read them as zero."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    return jnp.where(rows < jnp.sum(group_sizes), x, jnp.zeros_like(x))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, tm: int):
    tg, nl = _tile_groups(group_sizes, tm, lhs.shape[0] // tm)
    return _live_rows(
        _rows_call(lhs, rhs, tg, nl, tm, False, "moe_gmm_fwd"), group_sizes)


def _gmm_fwd(lhs, rhs, group_sizes, tm):
    return _gmm(lhs, rhs, group_sizes, tm), (lhs, rhs, group_sizes)


def _gmm_bwd(tm, res, dout):
    lhs, rhs, group_sizes = res
    tg, nl = _tile_groups(group_sizes, tm, lhs.shape[0] // tm)
    dout = dout.astype(lhs.dtype)
    dlhs = _live_rows(
        _rows_call(dout, rhs, tg, nl, tm, True, "moe_gmm_dx"), group_sizes)
    drhs = _dw_call(lhs, dout, tg, nl, rhs.shape[0], tm)
    # a group with no tile was never stored to
    drhs = jnp.where((group_sizes > 0)[:, None, None], drhs,
                     jnp.zeros_like(drhs)).astype(rhs.dtype)
    # a cotangent carries its primal's varying axes (shard_map
    # check_vma): a replicated rhs used on varying rows gets its gradient
    # summed over the axes the rows vary on, as `head_dot`'s rule does
    try:
        extra = tuple(jax.typeof(drhs).vma - jax.typeof(rhs).vma)
    except (AttributeError, TypeError):
        extra = ()
    if extra:
        drhs = jax.lax.psum(drhs, extra)
    return dlhs, drhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul_jnp(lhs: jnp.ndarray, rhs: jnp.ndarray,
                       group_sizes: jnp.ndarray) -> jnp.ndarray:
    """The jnp twin and numerics golden: ``jax.lax.ragged_dot``, f32
    accumulation, output in ``lhs.dtype``, any group sizes."""
    return jax.lax.ragged_dot(
        lhs, rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32).astype(lhs.dtype)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray,
                   tm: int = ROW_TILE) -> jnp.ndarray:
    """``out[r] = lhs[r] · rhs[group of row r]`` for ``lhs (M, K)``, ``rhs
    (G, K, N)`` and tile-aligned ``group_sizes (G,)`` (module docstring);
    rows past the last group give zero. Differentiable in ``lhs`` and
    ``rhs``. Pallas on the TPU (or forced, interpreted), else the twin."""
    if lhs.shape[0] % tm != 0:
        raise ValueError(f"grouped_matmul: {lhs.shape[0]} rows do not tile "
                         f"by {tm}; pad the row buffer to the tile")
    if use_pallas():
        return _gmm(lhs, rhs.astype(lhs.dtype),
                    group_sizes.astype(jnp.int32), tm)
    return grouped_matmul_jnp(lhs, rhs, group_sizes)
