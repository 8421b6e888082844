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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byteps_tpu.ops.backend import interpret as _interpret
from byteps_tpu.ops.backend import note_fallback, use_pallas

__all__ = ["index_scores", "index_scores_jnp", "unsupported_reason"]

_NEG = -1e30
_TQ, _TK = 128, 512


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
