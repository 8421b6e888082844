"""Ring transport kernels for the ``ici-compressed`` wire tier (Pallas
TPU + ``lax.ppermute`` jnp twins).

The staged compressed collective (`comm/ici.py`) moves payloads with one
monolithic ``all_to_all`` and one ``all_gather``: codec compute and wire
time serialize, and every hop pays the full-exchange latency. These
kernels replace the *transport* with a ring — ``n−1`` pipelined hops, one
segment-payload per link per hop, each hop's DMA overlapping the next
block's codec work — while the aggregation arithmetic stays byte-for-byte
the staged path's (that is what makes the ring tier pinnable BIT-exact
against it; see ``comm/ici.py`` tier notes).

Three primitives, each a Pallas TPU kernel (``make_async_remote_copy`` +
DMA semaphores, double-buffered — SNIPPETS [1] ring-permute idiom) with a
``lax.ppermute`` twin that runs everywhere:

* ``ring_collect``: per-device ``(n, ...)`` stack whose row ``j`` is the
  payload bound for owner ``j`` → ``(n, ...)`` stack on each device whose
  row ``w`` is worker ``w``'s payload for *this* owner —
  ``lax.all_to_all`` semantics over rotation hops (hop ``t`` moves row
  ``(d+t) mod n`` directly to device ``(d+t) mod n``; on hardware that is
  ``t`` neighbor hops, and all ``n−1`` hops are mutually independent so
  the DMAs pipeline).
* ``ring_allgather``: per-device block → ``(n, ...)`` owner-ordered stack
  (``lax.all_gather(tiled=False)`` semantics), same rotation.
* ``ring_presum``: the genuinely fused per-hop form for PRESUMMABLE
  payloads (seed-synced randomk: payloads sum positionally, so adding
  payloads IS compressing the running partial): a serial chain where each
  hop receives the neighbor's partial, adds the local contribution
  in-kernel while the next DMA is in flight, and forwards — compressed
  bytes on every hop, ``n−1`` single-payload hops total (the
  bandwidth-optimal ring reduce-scatter). Chain accumulation order is
  arrival order, NOT the staged stack order, so the ici tier routes only
  *stochastic* presummable codecs here (their pin is statistical);
  deterministic codecs take ``ring_collect`` + the staged sum to keep the
  bit-exact contract.

Backend selection follows ``ops/backend.py``: Pallas on TPU, jnp twin
elsewhere (``BYTEPS_KERNEL_BACKEND`` override; off-TPU the pallas path
runs in interpret mode, which the parity tests use — the interpreter's
DMA discharge rule performs real cross-device transfers). The kernels
want a lane-aligned plane (trailing-dim product % 128 == 0) and a 1-D
mesh axis (logical device id == axis index); anything else takes the
twin, per-leaf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from byteps_tpu.ops.backend import interpret as _interpret
from byteps_tpu.ops.backend import kernel_backend as _backend
from byteps_tpu.ops.backend import note_fallback as _note_fallback

_LANES = 128


_UNSUPPORTED = ("needs a lane-aligned plane (flat size % 128 == 0) and a "
                "ring axis spanning every device")


def kernels_supported(shape, n: int) -> bool:
    """Pallas path wants >1 device, a lane-aligned flat plane, AND the
    ring axis spanning every device in mesh order: the remote DMAs
    address ``DeviceIdType.LOGICAL`` ids computed as axis-index
    arithmetic, which only equals the logical device id on an
    effectively 1-D mesh (on a ('dp','mp') mesh, device (i, j) has
    logical id i·|mp|+j ≠ i — the DMA would land on the wrong chip).
    Anything else takes the ppermute twin, which addresses by axis name
    and is correct on any mesh."""
    flat = 1
    for s in shape:
        flat *= int(s)
    return (n > 1 and flat % _LANES == 0 and flat > 0
            and n == jax.device_count())


def _axis_my_id(axis: str):
    return jax.lax.axis_index(axis)


# --- jnp twins (the goldens and the CPU/off-TPU path) ------------------------
def _collect_jnp(x: jnp.ndarray, axis: str, n: int) -> jnp.ndarray:
    """all_to_all-equivalent rotation: out row w = worker w's row my_id.

    Hop ``t`` ppermutes row ``(d+t) mod n`` of every device ``d`` to
    device ``(d+t) mod n`` — a shift-``t`` rotation (``t`` neighbor hops
    on a physical ring). The hops carry ORIGINAL payload rows and are
    mutually independent, so XLA dispatches them concurrently; the
    assembled stack is bitwise the ``all_to_all`` result."""
    my = _axis_my_id(axis)
    own = jax.lax.dynamic_index_in_dim(x, my, 0, keepdims=True)
    out = jnp.zeros_like(x)
    out = jax.lax.dynamic_update_slice_in_dim(out, own, my, 0)
    for t in range(1, n):
        perm = [(s, (s + t) % n) for s in range(n)]
        dest = jax.lax.rem(my + t, n)
        send = jax.lax.dynamic_index_in_dim(x, dest, 0, keepdims=True)
        recv = jax.lax.ppermute(send, axis, perm)
        src = jax.lax.rem(my - t + n, n)
        out = jax.lax.dynamic_update_slice_in_dim(out, recv, src, 0)
    return out


def _allgather_jnp(x: jnp.ndarray, axis: str, n: int) -> jnp.ndarray:
    """Owner-ordered stack of every device's block (all_gather
    tiled=False semantics): hop ``t`` rotates the own block by ``t``."""
    my = _axis_my_id(axis)
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, x[None], my, 0)
    for t in range(1, n):
        perm = [(s, (s + t) % n) for s in range(n)]
        recv = jax.lax.ppermute(x, axis, perm)
        src = jax.lax.rem(my - t + n, n)
        out = jax.lax.dynamic_update_slice_in_dim(out, recv[None], src, 0)
    return out


def _presum_jnp(x: jnp.ndarray, axis: str, n: int) -> jnp.ndarray:
    """Serial partial-sum chain (the classic ring reduce-scatter): at hop
    ``t`` device ``d`` forwards the running partial for segment
    ``(d−t) mod n`` to its right neighbor, which adds its own
    contribution — per-hop positional accumulation in payload space.
    Device ``d`` ends with the complete sum of segment ``d``, accumulated
    in chain order ``p_{d+1}, p_{d+2}, …, p_{d−1}, p_d``."""
    my = _axis_my_id(axis)
    perm = [(s, (s + 1) % n) for s in range(n)]
    cur = jax.lax.dynamic_index_in_dim(
        x, jax.lax.rem(my + n - 1, n), 0, keepdims=False)
    for t in range(1, n):
        recv = jax.lax.ppermute(cur, axis, perm)
        mine = jax.lax.dynamic_index_in_dim(
            x, jax.lax.rem(my + n - 1 - t, n), 0, keepdims=False)
        cur = recv + mine
    return cur


# --- pallas kernels ----------------------------------------------------------
def _rotate_kernel(src_ref, dst_ref, local_sem, send_sems, recv_sems, *,
                   n: int, axis: str, gather: bool):
    """Shared rotation body: deliver to device ``(my+t) mod n`` the row it
    expects from me — row ``(my+t) mod n`` of my stack (collect) or my own
    block (gather) — written at remote row ``my`` (worker/owner order).
    Double-buffered on semaphore parity: hop ``t`` starts before hop
    ``t−1`` is waited, so two DMAs are always in flight."""
    my = jax.lax.axis_index(axis)
    # own row: a local DMA, overlapped with the remote hops
    own_src = src_ref if gather else src_ref.at[my]
    own_cp = pltpu.make_async_copy(own_src, dst_ref.at[my], local_sem)
    own_cp.start()
    ops = []
    for t in range(1, n):
        dest = jax.lax.rem(my + t, n)
        op = pltpu.make_async_remote_copy(
            src_ref=src_ref if gather else src_ref.at[dest],
            dst_ref=dst_ref.at[my],
            send_sem=send_sems.at[t % 2],
            recv_sem=recv_sems.at[t % 2],
            device_id=dest,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        op.start()
        ops.append(op)
        if len(ops) >= 2:
            ops[-2].wait()
    if ops:
        ops[-1].wait()
    own_cp.wait()


@functools.partial(jax.jit,
                   static_argnames=("n", "axis", "gather", "interpret"))
def _rotate_pallas(x: jnp.ndarray, n: int, axis: str, gather: bool,
                   interpret: bool = False) -> jnp.ndarray:
    out_shape = ((n,) + x.shape) if gather else x.shape
    return pl.pallas_call(
        functools.partial(_rotate_kernel, n=n, axis=axis, gather=gather),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA,          # local own-row copy
            pltpu.SemaphoreType.DMA((2,)),    # send, double-buffer parity
            pltpu.SemaphoreType.DMA((2,)),    # recv, double-buffer parity
        ],
        interpret=interpret,
    )(x)


def _presum_kernel(src_ref, out_ref, comm_ref, acc_ref, stage_ref,
                   local_sems, send_sems, recv_sems, *, n: int, axis: str):
    """Fused per-hop accumulate: while hop ``t``'s partial is on the wire
    (remote DMA out of ``comm_ref``), the next local contribution row
    DMAs HBM→VMEM; the add (the presummable codec's whole per-hop
    "decompress + accumulate + recompress", since payload sum == compress
    of the partial sum) runs the moment both land.

    Flow control: ring skew lets a fast upstream neighbor run up to
    ``n−1`` hops ahead of a slow device, so hop ``t``'s arrival gets its
    OWN landing slot (``comm_ref`` row ``t``) and its own recv semaphore
    (``recv_sems[t]``) — a counting parity pair could be satisfied by a
    later hop's arrival while the earlier slot is still unwritten.
    Slot 0 is the local send stage, reused only after ``send_sems[t]``
    confirms the previous send drained."""
    my = jax.lax.axis_index(axis)
    # seed the chain with the contribution for segment (my+n-1) mod n
    first = jax.lax.rem(my + n - 1, n)
    cp = pltpu.make_async_copy(src_ref.at[first], acc_ref, local_sems.at[0])
    cp.start()
    cp.wait()
    right = jax.lax.rem(my + 1, n)
    for t in range(1, n):
        # stage the partial for the wire (remote DMAs move HBM-resident
        # buffers; acc lives in VMEM for the adds)
        st = pltpu.make_async_copy(acc_ref, comm_ref.at[0], local_sems.at[0])
        st.start()
        st.wait()
        op = pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[0],
            dst_ref=comm_ref.at[t],
            send_sem=send_sems.at[t],
            recv_sem=recv_sems.at[t],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        op.start()
        # overlap: prefetch my contribution for the incoming segment
        mine = jax.lax.rem(my + n - 1 - t, n)
        pf = pltpu.make_async_copy(src_ref.at[mine], stage_ref,
                                   local_sems.at[1])
        pf.start()
        op.wait()
        # land the received partial in VMEM and accumulate
        ld = pltpu.make_async_copy(comm_ref.at[t], acc_ref, local_sems.at[0])
        ld.start()
        ld.wait()
        pf.wait()
        acc_ref[...] = acc_ref[...] + stage_ref[...]
    wr = pltpu.make_async_copy(acc_ref, out_ref, local_sems.at[0])
    wr.start()
    wr.wait()


@functools.partial(jax.jit, static_argnames=("n", "axis", "interpret"))
def _presum_pallas(x: jnp.ndarray, n: int, axis: str,
                   interpret: bool = False) -> jnp.ndarray:
    rowshape = x.shape[1:]
    out, _comm = pl.pallas_call(
        functools.partial(_presum_kernel, n=n, axis=axis),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            # wire buffers (send stage row 0, per-hop landing rows 1..n-1)
            # — outputs only because pallas scratch has no HBM space;
            # discarded
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(rowshape, x.dtype),
            jax.ShapeDtypeStruct((n,) + rowshape, x.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM(rowshape, x.dtype),      # accumulator
            pltpu.VMEM(rowshape, x.dtype),      # own-contribution stage
            pltpu.SemaphoreType.DMA((2,)),      # local copies
            pltpu.SemaphoreType.DMA((n,)),      # per-hop send
            pltpu.SemaphoreType.DMA((n,)),      # per-hop recv
        ],
        interpret=interpret,
    )(x)
    return out


# --- public API (called INSIDE shard_map over a 1-D ``axis``) ----------------
def ring_collect(x: jnp.ndarray, axis: str, n: int,
                 backend=None) -> jnp.ndarray:
    """(n, ...) owner-major rows → (n, ...) worker-major rows (all_to_all
    semantics): exact, moves bits only."""
    backend = backend or _backend()
    if n == 1:
        return x
    if backend == "jnp":
        return _collect_jnp(x, axis, n)
    if not kernels_supported(x.shape[1:], n):
        _note_fallback("ring_collect", x.shape, _UNSUPPORTED)
        return _collect_jnp(x, axis, n)
    return _rotate_pallas(x, n, axis, gather=False, interpret=_interpret())


def ring_allgather(x: jnp.ndarray, axis: str, n: int,
                   backend=None) -> jnp.ndarray:
    """per-device block → (n, ...) owner-ordered stack (all_gather
    tiled=False semantics): exact, moves bits only."""
    backend = backend or _backend()
    if n == 1:
        return x[None]
    if backend == "jnp":
        return _allgather_jnp(x, axis, n)
    if not kernels_supported(x.shape, n):
        _note_fallback("ring_allgather", x.shape, _UNSUPPORTED)
        return _allgather_jnp(x, axis, n)
    return _rotate_pallas(x, n, axis, gather=True, interpret=_interpret())


def ring_presum(x: jnp.ndarray, axis: str, n: int,
                backend=None) -> jnp.ndarray:
    """(n, ...) owner-major rows → this device's summed row (ring
    reduce-scatter with per-hop payload accumulation). Chain-ordered fp
    adds: positionally exact for presummable payloads, NOT bitwise equal
    to the staged stack sum — callers route stochastic codecs only."""
    backend = backend or _backend()
    if n == 1:
        return x[0]
    if backend == "jnp":
        return _presum_jnp(x, axis, n)
    if not kernels_supported(x.shape[1:], n):
        _note_fallback("ring_presum", x.shape, _UNSUPPORTED)
        return _presum_jnp(x, axis, n)
    return _presum_pallas(x, n, axis, interpret=_interpret())
