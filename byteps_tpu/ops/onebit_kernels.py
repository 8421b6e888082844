"""Onebit pack/unpack kernels (Pallas TPU + layout-identical jnp fallback).

Reference analog: the bit pack/unpack loops of
``byteps/common/compressor/impl/onebit.cc``. TPU-first layout: the flat
input is padded and viewed as ``(32, L)`` — bit-position k along the
*sublane* axis, word j along the *lane* axis — so packing is a 32-row
reduction over full 128-lane vectors and unpacking is a broadcast+shift,
both pure VPU ops with no cross-lane shuffles. (Packing 32 *consecutive*
elements per word, as the reference does on CPU, would need strided lane
gathers on TPU.) Wire format: element ``e`` (of the padded array) is bit
``e // L`` of word ``e % L``.

The fused ``onebit_unpack_sum`` is the aggregation-tier hot op — the
server's decompress→sum loop (``byteps/server/server.cc`` SumRecvBuff on
compressed pushes) done in one VMEM pass without materializing K dense
arrays.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BITS = 32


def _block(L: int) -> int:
    """Largest lane-multiple block size dividing L (L is always a multiple
    of 128, so this never falls through)."""
    for bl in (1024, 512, 256, 128):
        if L % bl == 0:
            return bl
    return L


from byteps_tpu.ops.backend import interpret as _interpret  # noqa: E402
from byteps_tpu.ops.backend import kernel_backend as _backend  # noqa: E402


def packed_words(n: int) -> int:
    """Words on the wire for n elements: ceil(n/32), lane-padded to 128."""
    m = -(-n // _BITS)
    return -(-m // _LANES) * _LANES


def _pad_len(n: int) -> int:
    return packed_words(n) * _BITS


# --- jnp fallback (same (32, L) layout) -------------------------------------
def _pack_jnp(x: jnp.ndarray) -> jnp.ndarray:
    L = packed_words(x.shape[0])
    xp = jnp.pad(x.astype(jnp.float32), (0, L * _BITS - x.shape[0]))
    bits = (xp.reshape(_BITS, L) >= 0).astype(jnp.uint32)
    shifts = jnp.arange(_BITS, dtype=jnp.uint32)[:, None]
    return (bits << shifts).sum(axis=0, dtype=jnp.uint32)


def _unpack_sum_jnp(words: jnp.ndarray, scales: jnp.ndarray,
                    n: int) -> jnp.ndarray:
    # words: (K, L) uint32, scales: (K,) f32 → Σ_k signs_k * scale_k, (n,)
    K, L = words.shape
    shifts = jnp.arange(_BITS, dtype=jnp.uint32)[None, :, None]
    bits = (words[:, None, :] >> shifts) & jnp.uint32(1)     # (K, 32, L)
    signs = bits.astype(jnp.float32) * 2.0 - 1.0
    acc = (signs * scales[:, None, None]).sum(axis=0)        # (32, L)
    return acc.reshape(-1)[:n]


# --- pallas kernels ----------------------------------------------------------
# Kernel arithmetic runs in int32 (Mosaic has no unsigned reductions);
# pack sums are exact bitwise under two's-complement wraparound (each word
# sums 32 distinct powers of two), and bit-k extraction `(w >> k) & 1`
# is shift-kind agnostic. uint32 lives only at the wire boundary.
def _pack_kernel(x_ref, out_ref):
    x = x_ref[...]                                           # (32, bl) f32
    bits = (x >= 0).astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    out_ref[...] = jnp.sum(bits << shifts, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pack_pallas(x2d: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    _, L = x2d.shape
    bl = _block(L)
    out = pl.pallas_call(
        _pack_kernel,
        grid=(L // bl,),
        in_specs=[pl.BlockSpec((_BITS, bl), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, bl), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, L), jnp.int32),
        interpret=interpret,
    )(x2d)
    return jax.lax.bitcast_convert_type(out[0], jnp.uint32)


# Above this K the unpack-sum switches from a fully unrolled body to a
# grid axis over K: unrolling is fastest for mesh-axis-sized K (one VMEM
# pass, no revisits) but its program size — and Mosaic compile time —
# grows linearly with K, which is unbounded at pod scale (K = worker
# count on the server decompress-sum path).
_UNROLL_K_MAX = 32


def _rows_unpack_acc(words_ref, scales_ref, rows: int, bl: int):
    """Σ_r signs(words[r]) · scale[r] over ``rows`` block rows — the one
    copy of the bit-unpack arithmetic both unpack-sum kernels share."""
    shifts = jax.lax.broadcasted_iota(jnp.int32, (_BITS, bl), 0)
    acc = jnp.zeros((_BITS, bl), jnp.float32)
    for r in range(rows):
        w = jnp.broadcast_to(words_ref[r:r + 1, :], (_BITS, bl))
        bits = (w >> shifts) & jnp.int32(1)
        signs = bits.astype(jnp.float32) * 2.0 - 1.0
        acc = acc + signs * scales_ref[r, 0]
    return acc


def _make_unpack_sum_kernel(K: int, bl: int):
    def kernel(words_ref, scales_ref, out_ref):
        out_ref[...] = _rows_unpack_acc(words_ref, scales_ref, K, bl)

    return kernel


_GRID_K_BLOCK = 8  # sublane-dim blocks must be divisible by 8 on TPU


def _make_unpack_sum_grid_kernel(bl: int):
    """K as the innermost grid axis in blocks of 8 rows: constant program
    size for any K; the output block is revisited across consecutive k
    steps (legal revisit order on TPU), accumulating in place. Padded rows
    carry scale 0 and contribute nothing."""

    def kernel(words_ref, scales_ref, out_ref):
        kb = pl.program_id(1)
        acc = _rows_unpack_acc(words_ref, scales_ref, _GRID_K_BLOCK, bl)

        @pl.when(kb == 0)
        def _init():
            out_ref[...] = acc

        @pl.when(kb > 0)
        def _accumulate():
            out_ref[...] = out_ref[...] + acc

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def _unpack_sum_pallas(words: jnp.ndarray, scales: jnp.ndarray,
                       interpret: bool = False) -> jnp.ndarray:
    K, L = words.shape
    bl = _block(L)
    words_i32 = jax.lax.bitcast_convert_type(words, jnp.int32)
    if K <= _UNROLL_K_MAX:
        return pl.pallas_call(
            _make_unpack_sum_kernel(K, bl),
            grid=(L // bl,),
            in_specs=[
                pl.BlockSpec((K, bl), lambda i: (0, i)),
                pl.BlockSpec((K, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((_BITS, bl), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((_BITS, L), jnp.float32),
            interpret=interpret,
        )(words_i32, scales.reshape(K, 1))
    kp = -(-K // _GRID_K_BLOCK) * _GRID_K_BLOCK
    if kp != K:
        # pod worker counts are usually 8-multiples, so this copy of the
        # (already 32x-compressed) payload is the uncommon case; padded
        # rows are zero-scaled in the kernel
        words_i32 = jnp.pad(words_i32, ((0, kp - K), (0, 0)))
        scales_p = jnp.pad(scales.reshape(K, 1), ((0, kp - K), (0, 0)))
    else:
        scales_p = scales.reshape(K, 1)
    return pl.pallas_call(
        _make_unpack_sum_grid_kernel(bl),
        grid=(L // bl, kp // _GRID_K_BLOCK),
        in_specs=[
            pl.BlockSpec((_GRID_K_BLOCK, bl), lambda j, k: (k, j)),
            pl.BlockSpec((_GRID_K_BLOCK, 1), lambda j, k: (k, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((_BITS, bl), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((_BITS, L), jnp.float32),
        interpret=interpret,
    )(words_i32, scales_p)


# --- public API --------------------------------------------------------------
def onebit_pack(x: jnp.ndarray,
                backend: Optional[str] = None) -> jnp.ndarray:
    """Flat f32 (n,) → (L,) uint32 sign words (L = packed_words(n))."""
    backend = backend or _backend()
    if backend == "jnp":
        return _pack_jnp(x)
    n = x.shape[0]
    L = packed_words(n)
    xp = jnp.pad(x.astype(jnp.float32), (0, L * _BITS - n))
    return _pack_pallas(xp.reshape(_BITS, L), interpret=_interpret())


def onebit_unpack_sum(words: jnp.ndarray, scales: jnp.ndarray, n: int,
                      backend: Optional[str] = None) -> jnp.ndarray:
    """(K, L) sign words + (K,) scales → Σ_k signs_k·scale_k as f32 (n,)."""
    backend = backend or _backend()
    if backend == "jnp":
        return _unpack_sum_jnp(words, scales, n)
    out = _unpack_sum_pallas(words, scales, interpret=_interpret())
    return out.reshape(-1)[:n]


def onebit_unpack(words: jnp.ndarray, scale: jnp.ndarray, n: int,
                  backend: Optional[str] = None) -> jnp.ndarray:
    """Single-payload decompress: (L,) words + scalar scale → (n,) f32."""
    return onebit_unpack_sum(words[None], scale.reshape(1), n, backend)
