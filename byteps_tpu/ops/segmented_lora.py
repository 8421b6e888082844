"""Segmented/gathered LoRA matmul — the batched heterogeneous-adapter
delta behind multi-tenant serving (Pallas TPU + jnp twin).

Reference shape: Punica's SGMV / S-LoRA's batched gather — R packed
decode rows each carry a per-row adapter *slot* into a device-resident
slab pool, and one fused op computes every row's low-rank delta
``(x_r @ A[slot_r]) @ B[slot_r]`` without materializing per-row weight
copies. Slot 0 is the pool's reserved all-zero slot (base-model rows,
padded batch rows): its delta is exactly 0.0, so heterogeneous batches
never branch.

Exactness contract: the jnp twin's per-row arithmetic is the packed
form of ``models/lora.lora_delta`` — same contraction order over the
input dim, same rank-bucket zero padding (a zero A column times a zero
B row adds exactly 0.0) — so a pooled tenant's greedy tokens stay
BIT-identical to a solo ``make_generate_fn`` run on its grafted params
(pinned in tests/test_serve_multitenant.py). The Pallas kernel is the
TPU fast path behind the shared ``ops/backend.py`` rule; it gathers
each row's A/B slabs by scalar-prefetched slot index so the weight DMA
overlaps the row's two thin matmuls.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from byteps_tpu.models.lora import _fence
from byteps_tpu.ops.backend import interpret, use_pallas

__all__ = ["segmented_lora_delta"]


def _delta_jnp(x: jnp.ndarray, a_slab: jnp.ndarray, b_slab: jnp.ndarray,
               slots: jnp.ndarray,
               tp_axis: Optional[str] = None,
               row_parallel: bool = False) -> jnp.ndarray:
    """(R, S, d_in) x (n_slots, d_in, rb) x (n_slots, rb, d_out) →
    (R, S, d_out): scan over rows, each body gathering its slot's slabs
    and running the SAME ``(1, S, d_in) @ (d_in, rb)`` / ``(1, S, rb) @
    (rb, d_out)`` dots the solo ``lora_delta`` emits on a grafted tree.
    A batched einsum (or a lax.scan) would be the obvious packed form,
    but XLA's accumulation is context-dependent — a gathered R-batched
    dot, a dot inside a scan-loop fusion, and R separate solo dots can
    each disagree by 1 ulp on some inputs. R is static at trace time,
    so the twin UNROLLS: each row emits its own standalone
    ``(1, S, d) @ (d, rb)`` / ``(1, S, rb) @ (rb, d_out)`` dot pair —
    HLO-identical to the solo path's ops — which is what makes the
    BIT-identical multi-tenant contract hold. The slabs are cast to
    ``x.dtype`` exactly like ``lora_delta`` casts the grafted leaves;
    the rank deltas are thin (R × targets × layers extra small dots is
    noise next to the step's base matmuls and a one-time trace cost the
    factory lru-cache amortizes)."""
    rows = []
    for i in range(x.shape[0]):
        sl = slots[i]
        a = jnp.take(a_slab, sl, axis=0).astype(x.dtype)
        b = jnp.take(b_slab, sl, axis=0).astype(x.dtype)
        # the same barrier fence lora_delta uses: each row's dot pair
        # becomes an isolated island with the solo path's exact HLO, so
        # XLA can neither merge the R rows into a batched dot nor fold
        # a row into a consumer fusion — either would change the
        # accumulation order and break bit-identity with the solo run
        xi, a, b = _fence((x[i:i + 1], a, b))
        u = xi @ a                                       # (1, S, rb)
        if row_parallel and tp_axis is not None:
            u = jax.lax.psum(u, tp_axis)
        rows.append(_fence(u @ b))
    return jnp.concatenate(rows, axis=0)


def _delta_pallas(x, a_slab, b_slab, slots):
    """One grid step per packed row; the row's A/B slabs are gathered
    by the scalar-prefetched slot index (the BlockSpec index maps read
    ``slots`` before the body runs, so the slab DMA is a plain block
    fetch — no in-kernel gather)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, S, d_in = x.shape
    _, _, rb = a_slab.shape
    d_out = b_slab.shape[-1]

    def kernel(slots_ref, x_ref, a_ref, b_ref, o_ref):
        xv = x_ref[0].astype(jnp.float32)          # (S, d_in)
        av = a_ref[0].astype(jnp.float32)          # (d_in, rb)
        bv = b_ref[0].astype(jnp.float32)          # (rb, d_out)
        u = jnp.dot(xv, av, preferred_element_type=jnp.float32)
        o_ref[0] = jnp.dot(
            u, bv, preferred_element_type=jnp.float32).astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, S, d_in), lambda r, slots: (r, 0, 0)),
            pl.BlockSpec((1, d_in, rb),
                         lambda r, slots: (slots[r], 0, 0)),
            pl.BlockSpec((1, rb, d_out),
                         lambda r, slots: (slots[r], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, d_out), lambda r, slots: (r, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, S, d_out), x.dtype),
        interpret=interpret(),
    )(slots, x, a_slab, b_slab)


def segmented_lora_delta(x: jnp.ndarray, a_slab: jnp.ndarray,
                         b_slab: jnp.ndarray, slots: jnp.ndarray,
                         row_parallel: bool = False,
                         tp_axis: Optional[str] = None) -> jnp.ndarray:
    """Per-row LoRA delta for a packed batch of heterogeneous adapters.

    x: ``(R, S, d_in)`` activations (S = 1 in the packed decode step);
    a_slab/b_slab: the pool's ``(n_slots, d_in, rank_bucket)`` /
    ``(n_slots, rank_bucket, d_out)`` slot arrays; slots: ``(R,)``
    int32 per-row slot indices. Returns ``(R, S, d_out)``.

    ``row_parallel`` mirrors ``lora_delta``'s tp contract for wo/w2:
    the thin ``(R, S, rank)`` intermediate is psum'd over ``tp_axis``
    before the second matmul — which also rules the Pallas fast path
    out for row-parallel targets (the psum must sit BETWEEN the two
    matmuls; the fused kernel has no collective seam), so those take
    the jnp twin on every backend.
    """
    if row_parallel and tp_axis is not None:
        return _delta_jnp(x, a_slab, b_slab, slots,
                          tp_axis=tp_axis, row_parallel=True)
    if use_pallas():
        return _delta_pallas(x, a_slab, b_slab, slots)
    return _delta_jnp(x, a_slab, b_slab, slots)
