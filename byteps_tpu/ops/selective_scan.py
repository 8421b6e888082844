"""The selective scan of a Mamba-1 mixer — a layer's recurrent state, in the
three forms the serve tier and its tests need (as ``ops/ssd.py`` has the
Mamba-2 rule's, which this is NOT: there one scalar decays a whole head, here
the decay is per channel AND per state dimension, so neither ``ssd_decode``
nor the chunked SSD form can compute it).

A layer keeps ``S (N, Dn)`` in f32: ``N`` the state size on the second-minor
axis, ``Dn`` the inner width on lanes (the published ``(d_inner, d_state) =
(5120, 16)`` transposed: a minor axis of 16 would be padded to a lane tile,
eight times the bytes). One token, with the channels' input ``u (Dn,)``, their
step ``delta > 0`` (``softplus`` already applied), the decay rates ``A (N,
Dn) < 0``, the skip ``D (Dn,)``, and the token's ``B``, ``C (N,)``::

    S <- exp(delta * A) * S + B (delta * u)^T;   y = S^T C + D * u

* :func:`sscan_recurrent` — that, token by token under ``lax.scan``: the form
  the others are held to, never a timed path.
* :func:`sscan_decode` — one token a row of a packed decode step, the state
  read and written ONCE where it lies in the slot pool ``(layers, slots, N,
  Dn)``: a Pallas kernel (``sscan_decode`` in a device trace) whose slot
  indices are scalar-prefetched and whose output aliases the pool, one row's
  whole state a grid step. ``exp(delta * A)`` is made in the kernel (outside
  it would be a state-sized array a row); ``B`` and ``C`` arrive as columns
  ``(N, 2)``, so ``B (delta u)^T`` and ``S * C`` are lane broadcasts and ``S^T
  C`` a sublane reduction: no MXU pass rounds the f32 state. Off the Pallas
  backend the jnp twin gathers, updates and scatters the rows' states
  (``sscan.decode_kernel`` / ``sscan.decode_twin`` count which was traced).
* :func:`sscan_chunk_fwd` — a prefill chunk of one request, plain XLA, in two
  passes over sub-chunks of ``sub`` tokens. The rule has no matmul form (a
  decay per channel and state dimension), so the chunked form is the rule
  itself run on EVERY sub-chunk at once from a zero state (``sub`` sequential
  steps over arrays ``(T / sub, N, Dn)``, where token by token takes ``T``),
  then one pass over the sub-chunks that carries the true state: what a
  sub-chunk starts from decays into each of its tokens by ``exp(A *
  cumsum(delta))`` — at most 1, so nothing overflows — and is added to that
  token's output and to the sub-chunk's last state.
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

__all__ = ["sscan_recurrent", "sscan_decode", "sscan_decode_jnp",
           "sscan_chunk_fwd", "decode_unsupported_reason"]


def _step(S, u, delta, A, B, C):
    """One token of the rule on states ``S (..., N, Dn)``: ``u``, ``delta
    (..., Dn)``, ``B``, ``C (..., N)``. Returns ``(S, S^T C)``."""
    S = jnp.exp(delta[..., None, :] * A) * S \
        + B[..., :, None] * (delta * u)[..., None, :]
    return S, jnp.sum(S * C[..., :, None], axis=-2)


def sscan_recurrent(u, delta, A, B, C, D, S):
    """``u``, ``delta (T, Dn)``, ``A (N, Dn)``, ``B``, ``C (T, N)``, ``D
    (Dn,)``, ``S (N, Dn)``, all f32 → ``(y (T, Dn), S)``: the rule, one token
    a step."""
    def step(S, t):
        u_t, d_t, B_t, C_t = t
        S, y = _step(S, u_t, d_t, A, B_t, C_t)
        return S, y + D * u_t

    S, y = jax.lax.scan(step, S, (u, delta, B, C))
    return y, S


# --------------------------------------------------------------------------
# a prefill chunk: every sub-chunk at once, then the carry
# --------------------------------------------------------------------------
def sscan_chunk_fwd(u, delta, A, B, C, D, S, sub: int = 64):
    """The rule over ``T`` tokens of one sequence, ``sub`` at a time (shapes
    as :func:`sscan_recurrent`). ``T`` is padded to whole sub-chunks with
    tokens that leave the state as it is (``delta = 0``: no decay, no
    update); their outputs are dropped."""
    T, Dn = u.shape
    N = A.shape[0]
    pad = -T % sub
    if pad:
        u, delta, B, C = (jnp.pad(a, ((0, pad), (0, 0)))
                          for a in (u, delta, B, C))
    n = (T + pad) // sub

    def by_step(a):                       # (n*sub, w) -> (sub, n, w)
        return jnp.swapaxes(a.reshape(n, sub, a.shape[-1]), 0, 1)

    us, ds, Bs, Cs = by_step(u), by_step(delta), by_step(B), by_step(C)

    # pass 1: token j of every sub-chunk, from a zero state
    def local(Sl, t):
        u_t, d_t, B_t, C_t = t
        return _step(Sl, u_t, d_t, A, B_t, C_t)

    ends, y_loc = jax.lax.scan(
        local, jnp.zeros((n, N, Dn), jnp.float32), (us, ds, Bs, Cs))
    cum = jnp.cumsum(ds, axis=0)                              # (sub, n, Dn)

    # pass 2: the state a sub-chunk starts from, into its tokens and its end
    def carry(S, t):
        end_i, cum_i, C_i = t             # (N, Dn), (sub, Dn), (sub, N)
        y = jnp.sum(jnp.exp(cum_i[:, None, :] * A) * S
                    * C_i[:, :, None], axis=1)                # (sub, Dn)
        return jnp.exp(cum_i[-1] * A) * S + end_i, y

    S, y_in = jax.lax.scan(
        carry, S, (ends, jnp.swapaxes(cum, 0, 1), jnp.swapaxes(Cs, 0, 1)))
    y = (jnp.swapaxes(y_loc, 0, 1) + y_in).reshape(n * sub, Dn)
    return y[:T] + D * u[:T], S


# --------------------------------------------------------------------------
# a packed decode step: the state updated where it lies
# --------------------------------------------------------------------------
def decode_unsupported_reason(N: int, Dn: int, dtype):
    """Why the kernel does not take a state pool of these shapes (None: it
    does): a row's state is DMA'd as whole f32 tiles."""
    if jnp.dtype(dtype) != jnp.float32:
        return "the state pool must be float32"
    if N % 8 != 0 or Dn % 128 != 0:
        return "a layer's state must be whole (8, 128) tiles"
    return None


def sscan_decode_jnp(u, delta, A, B, C, D, pool, layer, slots):
    """The twin: ``u``, ``delta (R, Dn)``, ``A (N, Dn)``, ``B``, ``C (R, N)``,
    ``D (Dn,)`` f32; ``pool (L, slots, N, Dn)`` f32; row ``r`` updates
    ``pool[layer, slots[r]]``. Returns ``(y (R, Dn), pool)``."""
    S, y = _step(pool[layer, slots], u, delta, A, B, C)
    return y + D * u, pool.at[layer, slots].set(S)


def _decode_kernel(slot_ref, layer_ref, a_ref, dx_ref, bc_ref, s_ref,
                   y_ref, s_out_ref):
    del slot_ref, layer_ref              # read by the index maps alone
    delta, du = dx_ref[0:1, :], dx_ref[1:2, :]                # (1, Dn)
    # (N, Dn) * (N, Dn) + (N, 1) * (1, Dn)
    S = jnp.exp(delta * a_ref[...]) * s_ref[...] + bc_ref[:, 0:1] * du
    y_ref[...] = jnp.sum(S * bc_ref[:, 1:2], axis=0, keepdims=True)
    s_out_ref[...] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode(u, delta, A, B, C, pool, layer, slots, interpret: bool):
    R, Dn = u.shape
    N = A.shape[0]
    operands = _unify_vma(
        slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        A, jnp.stack([delta, delta * u], axis=1),             # (R, 2, Dn)
        jnp.stack([B, C], axis=2),                            # (R, N, 2)
        pool)
    state = pl.BlockSpec(
        (None, None, N, Dn),
        lambda r, slot_ref, layer_ref: (layer_ref[0], slot_ref[r], 0, 0))
    y, pool = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R,),
            in_specs=[pl.BlockSpec((N, Dn), lambda r, *_: (0, 0)),
                      pl.BlockSpec((None, 2, Dn), lambda r, *_: (r, 0, 0)),
                      pl.BlockSpec((None, N, 2), lambda r, *_: (r, 0, 0)),
                      state],
            out_specs=[pl.BlockSpec((None, 1, Dn), lambda r, *_: (r, 0, 0)),
                       state]),
        out_shape=[jax.ShapeDtypeStruct((R, 1, Dn), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 5 (the two prefetched scalars counted) is the pool
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="sscan_decode",
    )(*operands)
    return y[:, 0], pool


def sscan_decode(u, delta, A, B, C, D, pool, layer, slots):
    """One token a row, the state in place (module docstring; shapes as
    :func:`sscan_decode_jnp`). Live rows name distinct slots; rows that hold
    no request name the scratch slot, whose content nothing reads. The skip
    ``D * u`` is added to the kernel's output here: it touches no state."""
    R, Dn = u.shape
    N = A.shape[0]
    reg = get_registry()
    if use_pallas():
        why = decode_unsupported_reason(N, Dn, pool.dtype)
        if why is None:
            reg.counter("sscan.decode_kernel").inc()
            y, pool = _decode(u, delta, A, B, C, pool, layer, slots,
                              _interpret())
            return y + D * u, pool
        note_fallback("sscan_decode", (R, N, Dn), why)
    reg.counter("sscan.decode_twin").inc()
    return sscan_decode_jnp(u, delta, A, B, C, D, pool, layer, slots)
