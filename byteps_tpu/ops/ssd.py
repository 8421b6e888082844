"""The state-space duality (SSD) rule of a Mamba-2 mixer — a head's recurrent
state, in the three forms the serve tier and its tests need (as
``ops/gated_delta.py`` has the gated delta rule's).

A head keeps ``S (N, P)`` in f32: ``N`` the state size, ``P`` the head's
width. One token, with the head's input ``x (P,)``, its step ``dt > 0``
(``softplus`` already applied), its decay rate ``A < 0`` and skip ``D``
(scalars a head), and ``B``, ``C (N,)`` of the head's GROUP (``H / G`` heads
share them)::

    S <- exp(dt * A) * S + B (dt * x)^T;  y = S^T C + D * x

* :func:`ssd_recurrent` — that, token by token under ``lax.scan``: the form
  the others are held to, never a timed path.
* :func:`ssd_decode` — one token a row of a packed decode step, the state
  read and written ONCE where it lies in the slot pool ``(layers, slots, H,
  N, P)``: a Pallas kernel (``ssd_decode`` in a device trace) whose slot
  indices are scalar-prefetched and whose output aliases the pool,
  ``HEADS_PER_STEP`` heads of one row a grid step. ``B`` and ``C`` arrive with
  the state axis on sublanes, one column a group (``(R, N, 2G)``: a block
  whose index does not move between a row's grid steps, so it is fetched once
  a row), ``S * C`` is a lane broadcast and ``S^T C`` a sublane reduction: no
  MXU pass rounds the f32 state, and nothing is transposed in the kernel. Off
  the Pallas backend the jnp twin gathers, updates and scatters the rows'
  states (``ssd.decode_kernel`` / ``ssd.decode_twin`` count which was
  traced).
* :func:`ssd_chunk_fwd` — a prefill chunk of one request in the chunked
  (SSD) form over sub-chunks of ``sub`` tokens, plain XLA: inside a sub-chunk
  the masked, decayed ``C B^T`` (one a GROUP, shared by its heads) times ``dt
  * x``, between sub-chunks a ``lax.scan`` that carries ``S``. Every product
  takes f32 operands at ``Precision.HIGHEST``: the state's error must stay far
  under what a bf16 state shows (benchmark/controls/falconh1_limits.py).
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

__all__ = ["ssd_recurrent", "ssd_decode", "ssd_decode_jnp", "ssd_chunk_fwd",
           "decode_unsupported_reason", "HEADS_PER_STEP"]

HEADS_PER_STEP = 8         # 1 MB of state in and out a grid step at 256x128
_HI = jax.lax.Precision.HIGHEST


def _by_head(a, H: int, axis: int = -2):
    """A group's ``B`` or ``C`` once a head of the group: the ``G`` of
    ``axis`` (``(..., G, N)`` by default) repeated to ``H``."""
    return jnp.repeat(a, H // a.shape[axis], axis=axis)


def ssd_recurrent(x, dt, A, B, C, D, S):
    """``x (T, H, P)``, ``dt (T, H)``, ``A, D (H,)``, ``B, C (T, G, N)``, ``S
    (H, N, P)``, all f32 → ``(y (T, H, P), S)``: the rule, one token a
    step."""
    H = x.shape[1]

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        S = S * jnp.exp(dt_t * A)[:, None, None] \
            + _by_head(B_t, H)[:, :, None] * (dt_t[:, None] * x_t)[:, None, :]
        y = jnp.einsum("hnp,hn->hp", S, _by_head(C_t, H), precision=_HI)
        return S, y + D[:, None] * x_t

    S, y = jax.lax.scan(step, S, (x, dt, B, C))
    return y, S


# --------------------------------------------------------------------------
# a prefill chunk: the chunked form
# --------------------------------------------------------------------------
def ssd_chunk_fwd(x, dt, A, B, C, D, S, sub: int = 128):
    """The rule over ``T`` tokens of one sequence, ``sub`` at a time (shapes
    as :func:`ssd_recurrent`). ``T`` is padded to whole sub-chunks with
    tokens that leave the state as it is (``dt = 0``: no decay, no update);
    their outputs are dropped."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    pad = -T % sub
    if pad:
        x, B, C = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (x, B, C))
        dt = jnp.pad(dt, ((0, pad), (0, 0)))
    n = (T + pad) // sub

    def heads_first(a):                   # (n*sub, H, ...) -> (n, H, sub, ...)
        return jnp.moveaxis(a.reshape((n, sub) + a.shape[1:]), 2, 1)

    xd = heads_first(x * dt[..., None])                   # (n, H, sub, P)
    B, C = heads_first(B), heads_first(C)                 # (n, G, sub, N)
    cum = jnp.cumsum(heads_first(dt * A), axis=-1)        # (n, H, sub), <= 0
    # decay from token j to token i of a sub-chunk, i >= j; the exponent is
    # masked first: above the diagonal it is positive and may overflow
    low = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.where(low, jnp.exp(jnp.where(
        low, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
    cb = jnp.einsum("cgis,cgjs->cgij", C, B, precision=_HI)
    y_in = jnp.matmul(_by_head(cb, H, 1) * decay, xd, precision=_HI)

    def step(S, t):
        # what the state the sub-chunk starts from adds to each token's
        # output, and what each token adds to the state it ends with
        c_i, b_i, xd_i, cum_i = t     # (G, sub, N) x 2, (H, sub, P), (H, sub)
        c_in = _by_head(c_i, H, 0) * jnp.exp(cum_i)[..., None]
        b_out = _by_head(b_i, H, 0) \
            * jnp.exp(cum_i[:, -1:] - cum_i)[..., None]
        y = jnp.matmul(c_in, S, precision=_HI)            # (H, sub, P)
        S = S * jnp.exp(cum_i[:, -1])[:, None, None] + jnp.einsum(
            "hin,hip->hnp", b_out, xd_i, precision=_HI)
        return S, y

    S, y_st = jax.lax.scan(step, S, (C, B, xd, cum))
    y = jnp.moveaxis(y_in + y_st, 1, 2).reshape(n * sub, H, P)
    return y[:T] + D[:, None] * x[:T], S


# --------------------------------------------------------------------------
# a packed decode step: the state updated where it lies
# --------------------------------------------------------------------------
def _heads_per_step(H: int, G: int) -> int:
    return min(H // G, HEADS_PER_STEP)


def decode_unsupported_reason(H: int, G: int, N: int, P: int, dtype):
    """Why the kernel does not take a state pool of these shapes (None: it
    does): a head's state is DMA'd as whole f32 tiles, a grid step's heads
    are of one group."""
    if jnp.dtype(dtype) != jnp.float32:
        return "the state pool must be float32"
    if N % 8 != 0 or P % 128 != 0:
        return "a head's state must be whole (8, 128) tiles"
    if H % G != 0 or (H // G) % _heads_per_step(H, G) != 0:
        return (f"a group's heads must divide into blocks of "
                f"{HEADS_PER_STEP}")
    return None


def ssd_decode_jnp(x, dt, A, B, C, D, pool, layer, slots):
    """The twin: ``x (R, H, P)``, ``dt (R, H)``, ``A, D (H,)``, ``B, C (R, G,
    N)`` f32; ``pool (L, slots, H, N, P)`` f32; row ``r`` updates
    ``pool[layer, slots[r]]``. Returns ``(y (R, H, P), pool)``."""
    H = x.shape[1]
    S = pool[layer, slots] * jnp.exp(dt * A)[..., None, None] \
        + _by_head(B, H)[..., :, None] * (dt[..., None] * x)[..., None, :]
    y = jnp.einsum("rhnp,rhn->rhp", S, _by_head(C, H), precision=_HI)
    return y + D[:, None] * x, pool.at[layer, slots].set(S)


def _decode_kernel(slot_ref, layer_ref, bc_ref, xd_ref, dec_ref, s_ref,
                   y_ref, s_out_ref, *, groups: int, steps_a_group: int):
    del slot_ref, layer_ref              # read by the index maps alone
    # this step's group: its B and C columns picked by a select (a lane
    # offset that is data would be a dynamic lane slice)
    g = pl.program_id(1) // steps_a_group
    bcol, ccol = bc_ref[:, 0:1], bc_ref[:, groups:groups + 1]    # (N, 1)
    for i in range(1, groups):
        bcol = jnp.where(g == i, bc_ref[:, i:i + 1], bcol)
        ccol = jnp.where(g == i, bc_ref[:, groups + i:groups + i + 1], ccol)
    for j in range(s_ref.shape[0]):
        row = slice(j, j + 1)
        # (N, P) * (1, P) + (N, 1) * (1, P)
        S = s_ref[j] * dec_ref[row, :] + bcol * xd_ref[row, :]
        y_ref[row, :] = jnp.sum(S * ccol, axis=0, keepdims=True)
        s_out_ref[j] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode(xd, dec, B, C, pool, layer, slots, interpret: bool):
    R, H, P = xd.shape
    G, N = B.shape[1:]
    hb = _heads_per_step(H, G)
    nb = H // hb

    def on_lanes(a):                      # (R, H[, P]) -> (R, nb, hb, P)
        if a.ndim == 2:
            a = jnp.broadcast_to(a[..., None], (R, H, P))
        return a.reshape(R, nb, hb, P)

    # the state axis on sublanes, a column a group: B's, then C's
    bc = jnp.swapaxes(jnp.concatenate([B, C], axis=1), 1, 2)    # (R, N, 2G)
    operands = _unify_vma(
        slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        bc, on_lanes(xd), on_lanes(dec), pool)
    col = pl.BlockSpec((None, N, 2 * G), lambda r, h, *_: (r, 0, 0))
    lane = pl.BlockSpec((None, None, hb, P), lambda r, h, *_: (r, h, 0, 0))
    state = pl.BlockSpec(
        (None, None, hb, N, P),
        lambda r, h, slot_ref, layer_ref: (layer_ref[0], slot_ref[r], h, 0, 0))
    y, pool = pl.pallas_call(
        functools.partial(_decode_kernel, groups=G,
                          steps_a_group=H // G // hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R, nb),
            in_specs=[col, lane, lane, state],
            out_specs=[lane, state]),
        out_shape=[jax.ShapeDtypeStruct((R, nb, hb, P), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 5 (the two prefetched scalars counted) is the pool
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_decode",
    )(*operands)
    return y.reshape(R, H, P), pool


def ssd_decode(x, dt, A, B, C, D, pool, layer, slots):
    """One token a row, the state in place (module docstring; shapes as
    :func:`ssd_decode_jnp`). Live rows name distinct slots; rows that hold
    no request name the scratch slot, whose content nothing reads. The skip
    ``D * x`` is added to the kernel's output here: it touches no state."""
    R, H, P = x.shape
    G, N = B.shape[1:]
    reg = get_registry()
    if use_pallas():
        why = decode_unsupported_reason(H, G, N, P, pool.dtype)
        if why is None:
            reg.counter("ssd.decode_kernel").inc()
            y, pool = _decode(x * dt[..., None], jnp.exp(dt * A), B, C, pool,
                              layer, slots, _interpret())
            return y + D[:, None] * x, pool
        note_fallback("ssd_decode", (R, H, G, N, P), why)
    reg.counter("ssd.decode_twin").inc()
    return ssd_decode_jnp(x, dt, A, B, C, D, pool, layer, slots)
