"""The gated delta rule — a linear-attention layer's recurrent state, in the
three forms the serve tier and its tests need.

A value head keeps ``S (Dk, Dv)`` in f32. One token, with ``q``, ``k``
L2-normalised (``q`` scaled by ``Dk^-0.5``), ``beta`` in (0, 1) and ``g <= 0``
the log of the decay::

    S <- exp(g) * S;  u = beta * (v - S^T k);  S <- S + k u^T;  o = S^T q

* :func:`gdn_recurrent` — that, token by token under ``lax.scan``: the form
  the others are held to, never a timed path.
* :func:`gdn_decode` — one token a row of a packed decode step, the state
  read and written ONCE where it lies in the slot pool ``(layers, slots, H,
  Dk, Dv)``: a Pallas kernel whose slot indices are scalar-prefetched (as
  ``ops/paged_attention.py`` prefetches block tables) and whose output
  aliases the pool, ``HEADS_PER_STEP`` heads of one row a grid step. ``k``
  and ``q`` arrive with the key axis on sublanes (``(R, H/hb, Dk, hb)``), so
  ``S * k`` is a lane broadcast and ``S^T k`` a sublane reduction: no MXU
  pass rounds the f32 state, and nothing is transposed in the kernel. Off
  the Pallas backend the jnp twin gathers, updates and scatters the rows'
  states (``gdn.decode_kernel`` / ``gdn.decode_twin`` count which was
  traced).
* :func:`gdn_chunk_fwd` — a prefill chunk of one request in the chunked (WY
  / UT-transform) form over sub-chunks of ``sub`` tokens, plain XLA: inside
  a sub-chunk ``(I + tril(beta k k^T * decay, -1))^-1`` by six doublings of a
  nilpotent matrix (matmuls, no triangular solve), between sub-chunks a
  ``lax.scan`` that carries ``S``. Every product takes f32 operands at
  ``Precision.HIGHEST``: the state's error must stay far under what a bf16
  state shows (benchmark/controls/qwen3next_limits.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byteps_tpu.common.metrics import get_registry
from byteps_tpu.ops.backend import interpret as _interpret
from byteps_tpu.ops.backend import note_fallback, use_pallas
from byteps_tpu.ops.flash_attention import _unify_vma

__all__ = ["gdn_recurrent", "gdn_decode", "gdn_decode_jnp", "gdn_chunk_fwd",
           "decode_unsupported_reason", "HEADS_PER_STEP"]

HEADS_PER_STEP = 16        # 1 MB of state in and out a grid step at 128x128
_HI = jax.lax.Precision.HIGHEST


def gdn_recurrent(q, k, v, g, beta, S):
    """``q, k (T, H, Dk)``, ``v (T, H, Dv)``, ``g, beta (T, H)``, ``S (H, Dk,
    Dv)``, all f32 → ``(o (T, H, Dv), S)``: the rule, one token a step."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                             precision=_HI))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=_HI)

    S, o = jax.lax.scan(step, S, (q, k, v, g, beta))
    return o, S


# --------------------------------------------------------------------------
# a prefill chunk: the chunked form
# --------------------------------------------------------------------------
def _unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower-triangular ``A (..., c, c)``: with
    ``N = -A`` nilpotent, ``(I - N)^-1 = (I + N)(I + N^2)(I + N^4)...``."""
    c = A.shape[-1]
    eye = jnp.eye(c, dtype=A.dtype)
    N, inv, power = -A, eye - A, 1
    while 2 * power < c:
        N = jnp.matmul(N, N, precision=_HI)
        inv = jnp.matmul(inv, eye + N, precision=_HI)
        power *= 2
    return inv


def gdn_chunk_fwd(q, k, v, g, beta, S, sub: int = 64):
    """The rule over ``T`` tokens of one sequence, ``sub`` at a time (shapes
    as :func:`gdn_recurrent`). ``T`` is padded to whole sub-chunks with
    tokens that leave the state as it is (``k = 0``, ``beta = 0``, ``g =
    0``); their outputs are dropped."""
    T, H, Dk = q.shape
    pad = -T % sub
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, pad), (0, 0))) for a in (g, beta))
    n = (T + pad) // sub

    def heads_first(a):                   # (n*sub, H, ...) -> (n, H, sub, ...)
        return jnp.moveaxis(a.reshape((n, sub) + a.shape[1:]), 2, 1)

    q, k, v, g, beta = map(heads_first, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                            # (n, H, sub)
    kb, vb = k * beta[..., None], v * beta[..., None]
    # decay from token j to token i of a sub-chunk, i >= j; the exponent is
    # masked first: above the diagonal it is positive and may overflow
    low = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.where(low, jnp.exp(jnp.where(
        low, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    A = jnp.einsum("nhik,nhjk->nhij", kb, k, precision=_HI) * decay
    Tm = _unit_lower_inverse(jnp.where(jnp.tril(low, -1), A, 0.0))
    u = jnp.matmul(Tm, vb, precision=_HI)                 # (n, H, sub, Dv)
    w = jnp.matmul(Tm, kb * jnp.exp(G)[..., None], precision=_HI)
    qk = jnp.einsum("nhik,nhjk->nhij", q, k, precision=_HI) * decay
    q_in = q * jnp.exp(G)[..., None]
    k_out = k * jnp.exp(G[..., -1:] - G)[..., None]
    last = jnp.exp(G[..., -1])                            # (n, H)

    def step(S, x):
        u_i, w_i, qk_i, q_i, k_i, last_i = x
        v_new = u_i - jnp.matmul(w_i, S, precision=_HI)
        o = jnp.matmul(q_i, S, precision=_HI) \
            + jnp.matmul(qk_i, v_new, precision=_HI)
        S = S * last_i[:, None, None] + jnp.einsum(
            "hik,hiv->hkv", k_i, v_new, precision=_HI)
        return S, o

    S, o = jax.lax.scan(step, S, (u, w, qk, q_in, k_out, last))
    o = jnp.moveaxis(o, 1, 2).reshape(n * sub, H, -1)     # (T + pad, H, Dv)
    return o[:T], S


# --------------------------------------------------------------------------
# a packed decode step: the state updated where it lies
# --------------------------------------------------------------------------
def decode_unsupported_reason(H: int, Dk: int, Dv: int, dtype):
    """Why the kernel does not take a state pool of these shapes (None: it
    does): a head's state is DMA'd as whole f32 tiles."""
    if jnp.dtype(dtype) != jnp.float32:
        return "the state pool must be float32"
    if Dk % 8 != 0 or Dv % 128 != 0:
        return "a head's state must be whole (8, 128) tiles"
    if H % min(H, HEADS_PER_STEP) != 0:
        return f"heads must divide into blocks of {HEADS_PER_STEP}"
    return None


def gdn_decode_jnp(q, k, v, g, beta, pool, layer, slots):
    """The twin: ``q, k (R, H, Dk)``, ``v (R, H, Dv)``, ``g, beta (R, H)``
    f32; ``pool (L, N, H, Dk, Dv)`` f32; row ``r`` updates ``pool[layer,
    slots[r]]``. Returns ``(o (R, H, Dv), pool)``."""
    S = pool[layer, slots] * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.einsum("rhkv,rhk->rhv", S, k,
                                          precision=_HI))
    S = S + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("rhkv,rhk->rhv", S, q, precision=_HI)
    return o, pool.at[layer, slots].set(S)


def _decode_kernel(slot_ref, layer_ref, q_ref, k_ref, v_ref, dec_ref, b_ref,
                   s_ref, o_ref, s_out_ref):
    del slot_ref, layer_ref              # read by the index maps alone
    for j in range(s_ref.shape[0]):
        row = slice(j, j + 1)
        kc = k_ref[:, row]                                # (Dk, 1)
        S = s_ref[j] * dec_ref[row, :]                    # (Dk, Dv) * (1, Dv)
        u = b_ref[row, :] * (v_ref[row, :]
                             - jnp.sum(S * kc, axis=0, keepdims=True))
        S = S + kc * u
        o_ref[row, :] = jnp.sum(S * q_ref[:, row], axis=0, keepdims=True)
        s_out_ref[j] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode(q, k, v, dec, b, pool, layer, slots, interpret: bool):
    R, H, Dk = q.shape
    Dv = v.shape[-1]
    hb = min(H, HEADS_PER_STEP)
    nb = H // hb

    def on_sublanes(a):                   # (R, H, Dk) -> (R, nb, Dk, hb)
        return jnp.swapaxes(a.reshape(R, nb, hb, Dk), 2, 3)

    def on_lanes(a):                      # (R, H[, Dv]) -> (R, nb, hb, Dv)
        if a.ndim == 2:
            a = jnp.broadcast_to(a[..., None], (R, H, Dv))
        return a.reshape(R, nb, hb, Dv)

    operands = _unify_vma(
        slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        on_sublanes(q), on_sublanes(k), on_lanes(v), on_lanes(dec),
        on_lanes(b), pool)
    col = pl.BlockSpec((None, None, Dk, hb), lambda r, h, *_: (r, h, 0, 0))
    lane = pl.BlockSpec((None, None, hb, Dv), lambda r, h, *_: (r, h, 0, 0))
    state = pl.BlockSpec(
        (None, None, hb, Dk, Dv),
        lambda r, h, slot_ref, layer_ref: (layer_ref[0], slot_ref[r], h, 0, 0))
    o, pool = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R, nb),
            in_specs=[col, col, lane, lane, lane, state],
            out_specs=[lane, state]),
        out_shape=[jax.ShapeDtypeStruct((R, nb, hb, Dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 7 (the two prefetched scalars counted) is the pool
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gdn_decode",
    )(*operands)
    return o.reshape(R, H, Dv), pool


def gdn_decode(q, k, v, g, beta, pool, layer, slots):
    """One token a row, the state in place (module docstring; shapes as
    :func:`gdn_decode_jnp`). Live rows name distinct slots; rows that hold
    no request name the scratch slot, whose content nothing reads."""
    R, H, Dk = q.shape
    reg = get_registry()
    if use_pallas():
        why = decode_unsupported_reason(H, Dk, v.shape[-1], pool.dtype)
        if why is None:
            reg.counter("gdn.decode_kernel").inc()
            return _decode(q, k, v, jnp.exp(g), beta, pool, layer, slots,
                           _interpret())
        note_fallback("gdn_decode", (R, H, Dk, v.shape[-1]), why)
    reg.counter("gdn.decode_twin").inc()
    return gdn_decode_jnp(q, k, v, g, beta, pool, layer, slots)
