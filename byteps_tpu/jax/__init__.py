"""byteps_tpu.jax — the JAX framework adapter.

Mirrors the reference's per-framework adapter surface
(``byteps/torch/__init__.py`` is the model: ``init``, ``rank``/``size``,
``push_pull``, ``DistributedOptimizer``, ``broadcast_parameters``), as the
BASELINE north star's ``byteps/jax/`` package. Typical use::

    import byteps_tpu.jax as bps

    bps.init()
    tx = bps.DistributedOptimizer(
        optax.sgd(0.1),
        compression_params={"compressor": "onebit", "ef": "vanilla"},
    )
    # inside a shard_map'd per-device train step:
    #   updates, opt_state = tx.update(grads, opt_state, params)

Two aggregation paths (SURVEY §7 phase 2/3):

* **fused** — ``DistributedOptimizer`` / ``push_pull_inside`` used inside the
  user's jitted ``shard_map`` step: gradients are flattened, chunked to
  ``BYTEPS_PARTITION_BYTES``, and each chunk aggregated with a psum or the
  compressed collective, all in one XLA program. This is the
  peak-bandwidth path — XLA's scheduler overlaps chunk collectives.
* **eager** — ``push_pull``/``push_pull_async`` on stacked ``(N, ...)``
  arrays outside jit: each tensor is declared (priority = -declaration
  order), partitioned, and its chunks dispatched through the credit-limited
  priority scheduler, preserving the reference's dynamic inter-tensor
  reordering and giving per-stage chrome traces.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from byteps_tpu.common.config import Config, get_config
from byteps_tpu.common.stage_orders import (
    EAGER_STAGE_ORDER,
    HYBRID_STAGE_ORDER,
)
from byteps_tpu.common.logging import bps_check, get_logger
from byteps_tpu.common.partition import OwnerTable, TensorRegistry
from byteps_tpu.common.scheduler import (
    Handle,
    PartitionTask,
    PipelineScheduler,
    Stage,
)
from byteps_tpu.common.tracing import get_tracer
from byteps_tpu.comm.ici import (
    all_gather_flat,
    allreduce_flat,
    broadcast_flat,
    compressed_allreduce_flat,
    compressed_reduce_scatter_flat,
    reduce_scatter_flat,
)
from byteps_tpu.comm.mesh import device_mesh
from byteps_tpu.compression import from_params
from byteps_tpu.compression.error_feedback import CompressionSpec, momentum_step

from byteps_tpu.jax.optimizer import (  # noqa: F401,E402
    DistributedOptimizer,
    DistributedOptState,
    dp_state_specs,
    push_pull_inside,
)
from byteps_tpu.jax.tuned_step import AutoTunedStep  # noqa: F401,E402

log = get_logger("jax")


class _BytePSJaxState:
    def __init__(self) -> None:
        self.initialized = False
        self.cfg: Optional[Config] = None
        self.mesh = None
        self.registry: Optional[TensorRegistry] = None
        self.scheduler: Optional[PipelineScheduler] = None
        self.spec: Optional[CompressionSpec] = None
        self.versions: Dict[str, int] = {}
        # per-(name, part_idx) EF residual / momentum buffers, (N, plen)
        self.ef_state: Dict[Any, jnp.ndarray] = {}
        self.mom_state: Dict[Any, jnp.ndarray] = {}
        self.base_rng = None
        self.anon_counter = 0
        self.lock = threading.Lock()
        # Serializes ICI collective DISPATCH across stage pool threads:
        # XLA launches collective programs in dispatch order per device,
        # so two host threads dispatching (reduce-scatter from REDUCE,
        # all-gather from ALLGATHER) concurrently can enqueue them in
        # different orders on different devices — a rendezvous deadlock
        # (observed on the CPU backend, same hazard on TPU). Dispatch is
        # async; only the enqueue order is pinned.
        self.ici_lock = threading.Lock()
        self.tuner = None
        self.psworker = None        # DCN tier client (distributed mode)
        # sharded-wire hierarchical mode: one PSWorker per pod controller
        # (psworker aliases psworkers[0]); owners maps partition keys to
        # the controller whose NIC carries them
        self.psworkers: List[Any] = []
        self.owners: Optional[OwnerTable] = None
        self.owner_failovers = 0
        # scale-up elasticity: hooks fired with the live pod count after
        # join() adopts a membership change (shard remap, LR rescale)
        self.membership_hooks: List[Any] = []
        # bumped (under lock) by _fail_owner's EF/momentum reset; a
        # COMPRESS that read its state before the bump must not write the
        # stale residual back after it (see _compress_stage)
        self.failover_gen = 0
        self.inited_keys = set()   # {(owner, key)} successfully init'ed


_state = _BytePSJaxState()


def init(
    mesh=None,
    compression_params: Optional[Dict[str, Any]] = None,
    seed: int = 0,
) -> None:
    """Initialize the adapter (reference: ``byteps_init`` / ``BytePSGlobal::Init``).

    On multi-host TPU pods with ``BYTEPS_JAX_DISTRIBUTED=1`` this joins the
    global ``jax.distributed`` group (the launcher's ``_jd_boot`` already
    did, making this a no-op); ``mesh`` then spans all hosts' devices.
    """
    if _state.initialized:
        return
    cfg = get_config()
    from byteps_tpu.comm.distributed import maybe_init_distributed

    maybe_init_distributed(cfg)
    from byteps_tpu.comm.distributed import is_multiprocess

    if cfg.hybrid_sharded and is_multiprocess():
        # The sharded graph's COPYD2H/COPYH2D move per-device SEGMENTS of
        # the reduce-scattered array; in a multi-process global mesh those
        # segments span non-addressable devices and jax.device_get would
        # throw on every push_pull. The dataflow needs per-process
        # addressable-shard plumbing (future work) — until then the
        # classic graph (full allreduce, controller 0's NIC) is the
        # correct multi-process hybrid.
        log.warning(
            "BYTEPS_HYBRID_SHARDED is not yet supported in multi-process "
            "global-mesh mode; falling back to the unsharded hybrid graph")
        cfg = dataclasses.replace(cfg, hybrid_sharded=False)
    _state.cfg = cfg
    _state.mesh = mesh if mesh is not None else device_mesh()
    _state.registry = TensorRegistry()
    _state.spec = from_params(compression_params)
    _state.base_rng = jax.random.PRNGKey(seed)
    tracer = get_tracer()
    if cfg.is_distributed:
        # Hybrid two-tier pipeline (reference root-GPU queue list,
        # operations.cc GetPushQueueList: REDUCE → COPYD2H → COMPRESS →
        # PUSH → PULL → DECOMPRESS → COPYH2D; BROADCAST is implicit — the
        # H2D value is the replicated result). Intra-pod reduction rides
        # ICI uncompressed (the reference's NCCL tier is uncompressed too);
        # compression applies to the DCN wire, where the summation servers
        # decompress→fp32-sum→recompress (SURVEY §2.2/§3.3). Only this
        # controller pushes the pod-sum per partition, which is what makes
        # the hybrid topology bandwidth-optimal (SURVEY §5.8).
        # Sharded-wire hierarchical tier (BYTEPS_HYBRID_SHARDED, default
        # on): REDUCE becomes an ICI reduce-SCATTER, each partition is
        # owned by one of the pod's BYTEPS_POD_CONTROLLERS controllers
        # (rendezvous hash) whose own NIC carries it over DCN — per-NIC
        # wire bytes divide by the controller count instead of H−1 NICs
        # idling — and an ALLGATHER tail reassembles the global sums
        # across the pod. Each controller is modeled by its own PSWorker
        # (own connections, pacer NIC, fault plan); with 1 controller the
        # graph is the same wire as before plus the scatter/gather pair,
        # pinned bit-exact against the unsharded path.
        from byteps_tpu.server import PSWorker

        n_ctl = max(1, cfg.pod_controllers) if cfg.hybrid_sharded else 1
        _state.psworkers = [PSWorker() for _ in range(n_ctl)]
        _state.psworker = _state.psworkers[0]
        _state.owners = OwnerTable(n_ctl, salt=cfg.owner_salt)
        if cfg.trace_on:
            # measure server_clock − local_clock per server (kPing RTT/2)
            # so merge_traces can align EVERY server's rows, not just
            # server 0's — cross-host clocks can differ by seconds each
            try:
                tracer.metadata["server_clock_offsets"] = {
                    str(sidx): _state.psworker.clock_offset_ns(sidx)
                    for sidx in range(max(1, cfg.num_server))
                }
            except Exception as e:  # noqa: BLE001 - tracing is best-effort
                log.warning("clock-offset probe failed: %s", e)
        # The credit is acquired at COMPRESS and released at PUSH exit
        # (releases_credit wire scope): on a slow/throttled DCN the PULL
        # direction costs as much as PUSH, and a completion-scoped
        # credit would let draining pulls starve later pushes — with
        # wire scope, COMPRESS of chunk i+1 runs while chunk i is on the
        # wire (credit ≥ 2) and at most ``credit`` encoded payloads are
        # ever buffered ahead of the wire.
        # PUSH/PULL are stage-retryable (chaos hardening): a mid-flight
        # failover re-runs the stage against the new server placement
        # instead of failing the Handle (docs/robustness.md).
        stages = [
            Stage("REDUCE", _reduce_stage, pool_size=1),
            Stage("COPYD2H", _d2h_stage, pool_size=2),
            Stage("COMPRESS", _compress_stage, credited=True,
                  pool_size=2),
            # +1 attempt per extra controller: a total-DCN-outage
            # walk-down spends one stage attempt failing each owner over
            # before the last controller may degrade
            Stage("PUSH", _dcn_push_stage, credited=True, pool_size=4,
                  releases_credit=True, retryable=True,
                  max_attempts=2 + n_ctl),
            Stage("PULL", _dcn_pull_stage, pool_size=4,
                  retryable=True, max_attempts=2 + n_ctl),
            Stage("DECOMPRESS", _decompress_stage, pool_size=2),
            Stage("COPYH2D", _h2d_stage, pool_size=2),
        ]
        if cfg.hybrid_sharded:
            # the hierarchical tail: H2D placed the pulled global sums as
            # per-device segments; the ICI all-gather replicates them
            # (reference BROADCAST after COPYH2D)
            stages.append(Stage("ALLGATHER", _allgather_stage, pool_size=2))
        # pinned against the canonical order trace_analysis sorts by
        # (stage_orders.HYBRID_STAGE_ORDER): a stage added here without
        # updating the shared constant is a bug, not a silent drift
        bps_check(
            tuple(s.name for s in stages)
            == HYBRID_STAGE_ORDER[:len(stages)],
            "hybrid stage list drifted from HYBRID_STAGE_ORDER")
        _state.scheduler = PipelineScheduler(
            stages=stages,
            credit=cfg.scheduling_credit,
            tracer=tracer,
            credit_scope="owner" if n_ctl > 1 else "global",
            # bounded staleness (BYTEPS_STALENESS=K): PUSH of round r+K
            # no longer gates on round r's PULL — a pipelining caller
            # keeps K+1 rounds of one key in flight and the window
            # bounds the run-ahead (docs/robustness.md §bounded
            # staleness)
            rounds_window=cfg.staleness if cfg.staleness > 0 else None,
        )
    else:
        # Eager ICI pipeline: PUSHPULL issues the jitted chunk collective
        # (async dispatch; issue order = execution order on the device
        # stream), SYNC blocks until the chunk's result is ready and frees
        # the credit.
        stages = [
            Stage("PUSHPULL", _dispatch_stage, credited=True, pool_size=1),
            Stage("SYNC", _sync_stage, pool_size=4),
        ]
        bps_check(
            tuple(s.name for s in stages) == EAGER_STAGE_ORDER,
            "eager stage list drifted from EAGER_STAGE_ORDER")
        _state.scheduler = PipelineScheduler(
            stages=stages,
            credit=cfg.scheduling_credit,
            tracer=tracer,
        )
    if cfg.auto_tune and cfg.is_distributed:
        # Credit-ONLY tuner in hybrid mode: credit is a purely local knob
        # (it changes this worker's issue parallelism, never the keys or
        # partition sizes the servers see), so per-worker moves are safe.
        # The partition knob stays off — per-worker tuners would
        # repartition at different times, pushing mismatched partition
        # sizes under the same keys. With wire-scoped credits (above),
        # credit is exactly the knob that trades pipeline overlap against
        # wire contention on a slow DCN.
        from byteps_tpu.common.tuner import AutoTuner

        log.info(
            "BYTEPS_AUTO_TUNE in distributed mode: tuning credit only "
            "(partition moves are not coordinated across workers)"
        )
        _state.tuner = AutoTuner(
            apply=lambda pb, cr: _state.scheduler.set_credit(cr),
            partition_bytes=cfg.partition_bytes,
            credit=cfg.scheduling_credit,
            knobs=("credit",),
        )
    elif cfg.auto_tune and not cfg.is_distributed:
        # ByteScheduler auto-tuner (BYTEPS_AUTO_TUNE=1): online hill-climb
        # of (partition_bytes, credit) on the eager path. Single-controller
        # only — all devices see one scheduler, so moves are consistent.
        from byteps_tpu.common.tuner import AutoTuner

        def _apply_tuning(pb: int, cr: int) -> None:
            _state.registry.repartition(pb)
            _state.scheduler.set_credit(cr)
            # EF/momentum buffers are shaped per partition; a repartition
            # invalidates them (the residual restarts from zero — same
            # effect as the reference re-instantiating compressors on
            # partition change)
            _state.ef_state.clear()
            _state.mom_state.clear()

        _state.tuner = AutoTuner(
            apply=_apply_tuning,
            partition_bytes=cfg.partition_bytes,
            credit=cfg.scheduling_credit,
        )
    else:
        _state.tuner = None
    _state.initialized = True
    log.info(
        "byteps_tpu.jax initialized: mesh=%s devices=%d compression=%s",
        dict(_state.mesh.shape), size(), _state.spec.compressor.name,
    )


def shutdown() -> None:
    """Reference: ``byteps_shutdown``."""
    if _state.scheduler is not None:
        _state.scheduler.shutdown()
    if _state.psworker is not None:
        # one kShutdown round per pod (servers count pods, and all of a
        # pod's controller NICs share its worker id); extra NICs retire
        # (counters folded into the trace under a per-NIC tag)
        from byteps_tpu.server import retire_nic

        for rank, w in enumerate(_state.psworkers[1:], start=1):
            retire_nic(w, rank)
        _state.psworker.shutdown()
        _state.psworker = None
        _state.psworkers = []
        _state.owners = None
    tracer = get_tracer()
    if tracer.enabled:
        # after the pipeline stops so late stage events are included; runs
        # shorter than BYTEPS_TRACE_END_STEP still get their trace
        tracer.dump()
    _state.initialized = False
    _state.versions.clear()
    _state.ef_state.clear()
    _state.mom_state.clear()
    _state.inited_keys.clear()
    _state.membership_hooks.clear()


def _require_init() -> None:
    bps_check(_state.initialized, "call byteps_tpu.jax.init() first")


# --- topology queries (reference: byteps_rank/size/local_rank/local_size) ---
def rank() -> int:
    """This controller's worker id (0 on a single-host job)."""
    _require_init()
    return _state.cfg.worker_id


def pod_size() -> int:
    """Devices on this controller's dp axis (one pod / reference machine)."""
    _require_init()
    return _state.mesh.shape[_state.cfg.dp_axis]


def size() -> int:
    """Global data-parallel participant count (each TPU device is the
    analog of one reference GPU worker): pod devices × DMLC_NUM_WORKER
    pods. Matches the reference's size() = machines × local GPUs. In
    global-mesh mode the mesh already spans every host's devices, so
    pod_size() IS the global count."""
    _require_init()
    if _state.cfg.jax_distributed:
        return pod_size()
    return pod_size() * max(1, _state.cfg.num_worker)


def local_rank() -> int:
    _require_init()
    return _state.cfg.local_rank


def local_size() -> int:
    _require_init()
    return jax.local_device_count()


def mesh():
    _require_init()
    return _state.mesh


# --- eager push_pull path ---------------------------------------------------
def _global_rows(local_rows: np.ndarray, n: int) -> jax.Array:
    """Assemble per-process local-device rows into one (n, L) global array
    sharded over the dp axis (global-mesh mode: each controller holds only
    its own devices' rows)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(_state.mesh, P(_state.cfg.dp_axis))
    return jax.make_array_from_process_local_data(
        sh, np.asarray(local_rows), (n,) + local_rows.shape[1:]
    )


def _tensor_rng(name: str, version: int, seed: int = 0):
    # zlib.crc32 is stable across processes/runs, unlike salted hash() —
    # multi-host controllers must derive identical keys for the same tensor
    # (randomk index agreement).
    import zlib

    base = jax.random.fold_in(_state.base_rng, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    base = jax.random.fold_in(base, seed)
    return jax.random.fold_in(base, version)


def _dispatch_stage(task: PartitionTask):
    """Issue the chunk collective (returns an in-flight jax array).

    Applies the reference compression pipeline per partition: Nesterov
    momentum → error feedback → compress → exchange (the decorator order of
    the reference's momentum/EF wrappers around the base compressor).
    """
    x = task.context["x2d"]
    p = task.partition
    chunk = jax.lax.slice_in_dim(x, p.offset, p.offset + p.length, axis=1)
    spec = task.context["spec"]
    average = task.context["average"]
    if not spec.enabled:
        return allreduce_flat(
            chunk, _state.mesh, _state.cfg.dp_axis, average=average
        )
    rng = jax.random.fold_in(task.context["rng"], p.part_idx)
    skey = (task.name, p.part_idx)
    if spec.momentum:
        m = _state.mom_state.get(skey)
        if m is None:
            m = jnp.zeros_like(chunk, dtype=jnp.float32)
        chunk, m = momentum_step(chunk.astype(jnp.float32), m, spec.mu)
        _state.mom_state[skey] = m
    if spec.ef:
        e = _state.ef_state.get(skey)
        if e is None:
            e = jnp.zeros_like(chunk, dtype=jnp.float32)
        out, new_e = compressed_allreduce_flat(
            chunk, spec.compressor, _state.mesh, _state.cfg.dp_axis,
            average=average, rng=rng, two_way=spec.two_way, ef_residual=e,
        )
        _state.ef_state[skey] = new_e
        return out
    return compressed_allreduce_flat(
        chunk, spec.compressor, _state.mesh, _state.cfg.dp_axis,
        average=average, rng=rng, two_way=spec.two_way,
    )


def _sync_stage(task: PartitionTask):
    out = task.payload
    out.block_until_ready()
    return out


# --- hybrid (distributed) pipeline stages -----------------------------------
def _reduce_stage(task: PartitionTask):
    """Intra-pod ICI sum of this chunk (async dispatch; reference REDUCE).

    Sharded-wire mode reduce-SCATTERs instead: each device ends up
    holding its segment of the pod sum — half the ICI bytes of a full
    allreduce (the ALLGATHER tail pays the other half AFTER the DCN round
    trip, reassembling the *global* sums), and on a multi-host pod each
    controller then only d2h's its own segments.

    Under ``BYTEPS_ICI_TIER=ring`` (the ici-compressed wire tier) a
    compressed job's qualifying partitions ride the compressed ring
    collective instead of the raw psum: compressed bytes on the ICI
    links, pod sums approximated by the codec (Σ D(C(g)) in fp32 —
    stateless at this hop; the DCN tier's EF keeps recirculating its own
    wire error as before). The layout contract is unchanged — same
    padded ``(n·ceil(L/n),)`` scattered form (or replicated ``(L,)``
    unsharded), so COPYD2H/DECOMPRESS/ALLGATHER need no changes."""
    x = task.context["x2d"]
    p = task.partition
    chunk = jax.lax.slice_in_dim(x, p.offset, p.offset + p.length, axis=1)
    cfg = _state.cfg
    spec = task.context["spec"]
    ici_compressed = (
        cfg.ici_tier == "ring" and spec.enabled and pod_size() > 1
        and p.length * 4 >= cfg.min_compress_bytes
    )
    with _state.ici_lock:
        if ici_compressed:
            rng = jax.random.fold_in(task.context["rng"], p.part_idx)
            if cfg.hybrid_sharded:
                return compressed_reduce_scatter_flat(
                    chunk, spec.compressor, _state.mesh, cfg.dp_axis,
                    average=False, rng=rng, tier="ring")
            return compressed_allreduce_flat(
                chunk, spec.compressor, _state.mesh, cfg.dp_axis,
                average=False, rng=rng, two_way=spec.two_way, tier="ring")
        if cfg.hybrid_sharded:
            return reduce_scatter_flat(chunk, _state.mesh, cfg.dp_axis)
        return allreduce_flat(chunk, _state.mesh, cfg.dp_axis,
                              average=False)


def _d2h_stage(task: PartitionTask):
    """Device→host for the DCN wire (reference COPYD2H; pool threads give
    the double-buffering the reference gets from pinned shm).

    ``jax.device_get`` instead of ``np.asarray(..., dtype=np.float32)``:
    on a CPU-backed buffer the old spelling could cast-copy a second
    time; device_get hands back the transferred (or zero-copy host) f32
    buffer directly. The scattered REDUCE output may be padded to
    n·ceil(L/n) — trim to the partition. Contract (pinned in
    tests/test_sharded_hybrid.py): f32 and C-contiguous always; writable
    whenever EF/momentum are configured, so the COMPRESS stage's state
    arithmetic may mutate in place — a read-only zero-copy view is only
    ever returned on the stateless path."""
    out = jax.device_get(task.payload)
    out = out.reshape(-1)[: task.partition.length]
    spec = task.context["spec"]
    needs_write = spec.enabled and (spec.ef or spec.momentum)
    if (out.dtype != np.float32 or not out.flags.c_contiguous
            or (needs_write and not out.flags.writeable)):
        out = np.ascontiguousarray(out, dtype=np.float32)
        if needs_write and not out.flags.writeable:
            out = out.copy()
    return out


def _wire_seed(task: PartitionTask) -> int:
    """Deterministic per (tensor, version, partition) seed shared by the
    COMPRESS and DECOMPRESS stages on every pod — the reference's
    synchronized compressor PRNG (randomk index agreement, dithering).
    One definition for every path: compression/wire.py wire_seed (the
    host DcnCore derives the same seed at salt 0)."""
    from byteps_tpu.compression.wire import wire_seed

    return wire_seed(task.name, task.context["version"],
                     task.partition.part_idx,
                     salt=task.context["spec"].seed)


def _compress_stage(task: PartitionTask):
    """Host-side momentum → error-feedback → wire encode (reference
    COMPRESS stage, core_loops.cc RunCompressLoopOnce; the decorator order
    matches the reference's momentum/EF wrappers around the compressor)."""
    p = task.partition
    plan = task.context["plans"][p.part_idx]
    x = task.payload  # np fp32 pod-sum
    if plan is None:
        return x.view(np.uint8).ravel()
    spec = task.context["spec"]
    seed = _wire_seed(task)
    skey = (task.name, p.part_idx)
    # _fail_owner resets EF/momentum for partitions whose owner moved; a
    # compress that read its buffers BEFORE that reset must not write them
    # back after it (the stale residual would silently resurrect). Writes
    # are dropped if the generation moved between read and write-back —
    # losing one best-effort residual update beats racing the reset.
    gen = _state.failover_gen
    if spec.momentum:
        m = _state.mom_state.get(skey)
        if m is None:
            m = np.zeros_like(x)
        m_new = spec.mu * m + x
        x = x + spec.mu * m_new
        with _state.lock:
            if _state.failover_gen == gen:
                _state.mom_state[skey] = m_new
    if spec.ef:
        e = _state.ef_state.get(skey)
        if e is None:
            e = np.zeros_like(x)
        corrected = x + e
        payload = plan.codec.encode(corrected, seed)
        approx = plan.codec.decode(payload, x.size, seed)
        with _state.lock:
            if _state.failover_gen == gen:
                _state.ef_state[skey] = corrected - approx
        return payload
    return plan.codec.encode(x, seed)


def _owner_of(key: int) -> int:
    return _state.owners.owner(key) if _state.owners is not None else 0


def _stall_diag():
    """Handle.diag callback for the hybrid tier — the same assembly as
    DcnCore's (`dcn_adapter.stall_diag`), so StallError reports from the
    two pipelines carry identical diagnostics."""
    from byteps_tpu.common.dcn_adapter import stall_diag

    return stall_diag(_state.psworkers, _state.owners, _state.scheduler)


def _fail_owner(rank: int, cause: Optional[BaseException] = None) -> bool:
    """Jax-side owner failover (mirrors DcnCore.fail_owner; the shared
    fence → export → adopt → shrink critical section is
    :func:`byteps_tpu.server.hand_off_owner`), then reset EF/momentum
    state for every partition whose owner moved — per-owner compressor
    state does not migrate off a dead controller; the residual restarts
    from zero with the remap, exactly like a PR3 key remap."""
    from byteps_tpu.server import hand_off_owner

    with _state.lock:
        live = hand_off_owner(_state.psworkers, _state.owners, rank)
        if live is None:
            return False
        new_live = _state.owners.live()
        moved = set()
        for name, ctx in _state.registry.snapshot():
            for part in ctx.partitions:
                if _state.owners.owner_in(part.key, live) == rank:
                    moved.add((name, part.part_idx))
        for skey in moved:
            _state.ef_state.pop(skey, None)
            _state.mom_state.pop(skey, None)
        # invalidate write-backs from any COMPRESS that read its state
        # before this reset (see _compress_stage)
        _state.failover_gen += 1
        _state.owner_failovers += 1
    if rank != 0:
        # free the dead NIC (monitor thread, connections, pacer) — worker
        # 0 stays open, fenced: it carries the pod's kShutdown round. The
        # dead NIC's counters (the faults that killed it) fold into the
        # trace first — close() alone would drop them.
        from byteps_tpu.server import retire_nic

        retire_nic(_state.psworkers[rank], rank)
    get_tracer().instant("owner_failover", "FAULT",
                         {"owner": rank, "survivors": sorted(new_live),
                          "cause": type(cause).__name__ if cause else None})
    log.warning(
        "pod controller %d gave up its wire (%s); %d partition state "
        "buffer(s) reset, partitions remap to owners %s", rank,
        cause if cause is not None else "requested", len(moved),
        sorted(new_live))
    return True


def _owner_giveup(task: PartitionTask, owner: int, e: BaseException):
    """Retry-exhausted wire error through ``owner``'s NIC: fail it over
    and re-raise stage-retryably so the re-run lands on a survivor."""
    from byteps_tpu.common.dcn_adapter import (
        owner_wire_death,
        remap_dead_owner,
    )

    if len(_state.psworkers) > 1 and owner_wire_death(e):
        remap_dead_owner(task, owner, _state.owners, _fail_owner,
                         _owner_of, e, "wire dead")
    raise e


def _dcn_push_stage(task: PartitionTask):
    p = task.partition
    owner = _owner_of(p.key)
    worker = _state.psworkers[owner]
    if not worker.has_live_servers():
        # THIS NIC sees zero live servers — with sibling NICs alive that
        # is the OWNER's link dying (per-PSWorker health monitors ping
        # through their own connections), so fail the owner over before
        # degrading; a genuine total outage walks down to the last
        # controller, which degrades as before.
        from byteps_tpu.common.dcn_adapter import remap_dead_owner
        from byteps_tpu.server import NoLiveServersError

        if len(_state.psworkers) > 1:
            remap_dead_owner(
                task, owner, _state.owners, _fail_owner, _owner_of,
                NoLiveServersError(f"owner {owner} sees no live servers"),
                "lost all servers")
        # total DCN outage: the payload is already the pod's pure-ICI sum
        # (REDUCE stage), so degrade to it instead of failing the handle —
        # cross-pod aggregation is lost, intra-pod training continues
        # (docs/robustness.md; gated by BYTEPS_DEGRADED_OK)
        from byteps_tpu.common.dcn_adapter import degraded_fallback

        return degraded_fallback(
            worker, _state.cfg, task, log,
            "the pure-ICI (pod-local) allreduce")
    plan = task.context["plans"][p.part_idx]
    store_bytes = (
        plan.codec.store_elems(p.length) * 4 if plan is not None
        else p.length * 4
    )
    with _state.lock:
        needs_init = (owner, p.key) not in _state.inited_keys
    try:
        if needs_init:
            # marked inited only AFTER success: a failed init whose stage
            # retries must re-run it, not be skipped forever (every later
            # push would then hit an uninitialized server key); two racing
            # pushes both initing is harmless — server init is idempotent
            worker.init_key(p.key, store_bytes)
            with _state.lock:
                _state.inited_keys.add((owner, p.key))
        codec_id = plan.codec.codec_id if plan is not None else 0
        # pin the round BEFORE the wire attempt (see DcnCore._push_stage
        # for the full why): a stage retry — possibly via a surviving
        # owner after a failover — re-sends the SAME round, which the
        # server either sums (never arrived) or dedupes (ack lost)
        task.push_version = worker.mint_version(
            p.key, getattr(task, "push_version", None))
        version = worker.push_bytes(
            p.key, task.payload, codec_id,
            version=task.push_version)
    except BaseException as e:  # noqa: BLE001 - owner-death classify
        from byteps_tpu.server import WorkerEvictedError

        if isinstance(e, WorkerEvictedError):
            # rejoin adopted the server watermarks; the stage retry must
            # mint a FRESH round (a stale pin would be dedupe-dropped —
            # see DcnCore._push_stage)
            task.push_version = None
        _owner_giveup(task, owner, e)
    task.push_version = version
    return version


def _dcn_pull_stage(task: PartitionTask):
    from byteps_tpu.common.dcn_adapter import DegradedLocal

    p = task.partition
    if isinstance(task.payload, DegradedLocal):
        return task.payload.payload
    plan = task.context["plans"][p.part_idx]
    owner = _owner_of(p.key)
    worker = _state.psworkers[owner]
    try:
        if plan is None:
            out = worker.pull_bytes(p.key, p.length * 4, task.payload, 0)
        else:
            out = worker.pull_bytes(
                p.key, plan.pull_capacity(p.length), task.payload,
                plan.pull_codec_id,
            )
        # the round's OWN live count (from its response's epoch stamp):
        # the averaging divisor for THIS partition, even if the current
        # membership has already moved on
        task.round_live = worker.last_round_live()
        # the round the server actually SERVED (bounded staleness may
        # answer up to K rounds behind the requested one) — DECOMPRESS
        # keys its seed off it so the aggregate decodes with the round
        # it was built from
        task.served_round = worker.last_pull_round()
        return out
    except BaseException as e:  # noqa: BLE001 - owner-death classify
        _owner_giveup(task, owner, e)


def _decompress_stage(task: PartitionTask):
    """Wire decode of the pulled round result (reference DECOMPRESS stage)."""
    p = task.partition
    plan = task.context["plans"][p.part_idx]
    buf = task.payload
    if plan is None:
        return np.ascontiguousarray(buf).view(np.float32).copy()
    if getattr(task, "degraded", False):
        # degraded payload is the PUSH-side encoding (the pull wire
        # format never existed for this round)
        return plan.codec.decode(np.ascontiguousarray(buf), p.length,
                                 _wire_seed(task))
    # the served round may trail the requested one under bounded
    # staleness — pull_seed owns the served-round → seed contract
    from byteps_tpu.compression.wire import pull_seed

    seed = pull_seed(task.name, task.context["version"], p.part_idx,
                     served_round=getattr(task, "served_round", None),
                     staleness=_state.cfg.staleness,
                     salt=task.context["spec"].seed)
    return plan.decode_pull(np.ascontiguousarray(buf), p.length, seed)


def _live_size() -> int:
    """Global participant count under ELASTIC membership: pod devices ×
    live pods per the most recently adopted membership epoch. Equals
    ``size()`` while the membership is full; after an eviction the pull
    results are sums over the live set (the server's quorum scaling keeps
    them unbiased), so averaging must divide by the live count — every
    worker adopts the same epoch, so the rescale is consistent across the
    survivors."""
    if _state.cfg.jax_distributed or not _state.psworkers:
        return size()
    return pod_size() * max(1, min(w.live_pods()
                                   for w in _state.psworkers))


# -- scale-up elasticity (mid-stream join; docs/robustness.md §scale-up) -----
def on_membership_change(hook) -> None:
    """Register ``hook(live_pods)`` to run after this process adopts a
    membership change through :func:`join`. This is where the elastic
    data-shard reassignment (``byteps_tpu.data.ElasticShardMap.assign``
    over the live set) and the LR/batch rescale policy
    (:func:`linear_scale`) hang — the framework owns the protocol event,
    the hooks own the training-semantics response."""
    _require_init()
    _state.membership_hooks.append(hook)


def join() -> int:
    """Mid-stream scale-UP: admit this worker into a RUNNING job — the
    counterpart of the eviction/rejoin machinery. Runs the kJoin
    admission + kRounds watermark adoption on every live summation
    server for each controller NIC (all share the pod's worker id), so
    the pod enters at a round boundary: the membership epoch bumps
    (peers adopt it on their next op and rescale their averaging
    divisor), rounds open at admission close over their contributors,
    and this pod's first push continues the server's round sequence at
    the served-round frontier. Fires the registered
    :func:`on_membership_change` hooks with the adopted live pod count
    and returns it. On the collectives-only path (no PS tier) the hooks
    still fire — membership there is ``jax.distributed``'s problem, but
    shard/LR policies remain the caller's."""
    _require_init()
    if _state.psworkers:
        for w in _state.psworkers:
            w.join()
    live = _live_size()
    for hook in list(_state.membership_hooks):
        hook(live)
    return live


def linear_scale(base: float, base_live: int, live: int) -> float:
    """The standard linear LR/batch rescale policy for elastic
    membership (Goyal et al.'s linear scaling rule applied to the LIVE
    worker count): ``base`` was tuned at ``base_live`` participants, the
    job now has ``live`` — scale proportionally. Offered as the default
    :func:`on_membership_change` policy; jobs with warmup or LARS-style
    schedules plug their own."""
    return base * (live / max(1, base_live))


def _average_h2d(task: PartitionTask, out: jnp.ndarray) -> jnp.ndarray:
    if task.context["average"]:
        if getattr(task, "degraded", False):
            # pod average: an unbiased estimate of the global average
            # (the pods the fallback cannot reach would have contributed
            # pod-sums of the same expected scale)
            out = out / pod_size()
        else:
            # divisor = the pulled round's OWN live membership (its
            # response carried the epoch it closed under); fall back to
            # the currently adopted count for non-elastic paths
            live = getattr(task, "round_live", None)
            d = (pod_size() * max(1, live) if live is not None
                 else _live_size())
            out = out / d
    return out


def _h2d_stage(task: PartitionTask):
    """Host→device of the pulled global sum (reference COPYH2D).

    Sharded-wire mode places it as per-device SEGMENTS over the dp axis —
    each device receives ~1/n of the partition over PCIe — and the
    ALLGATHER tail stage replicates them over ICI (the reference's
    BROADCAST). Unsharded keeps the replicated put + averaging here."""
    if not _state.cfg.hybrid_sharded:
        return _average_h2d(task, jnp.asarray(task.payload))
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = pod_size()
    L = task.partition.length
    seg = -(-L // n)
    host = np.asarray(task.payload, dtype=np.float32)
    if seg * n != L:
        host = np.pad(host, (0, seg * n - L))
    sh = NamedSharding(_state.mesh, P(_state.cfg.dp_axis))
    return jax.device_put(host, sh)


def _allgather_stage(task: PartitionTask):
    """Sharded-wire tail: replicate the per-device segments across the
    pod (exact — a gather moves bits, never sums) and apply the
    averaging scale the unsharded graph applies at H2D."""
    with _state.ici_lock:  # pin collective dispatch order (see ici_lock)
        out = all_gather_flat(task.payload, _state.mesh,
                              _state.cfg.dp_axis,
                              length=task.partition.length)
    # averaging is elementwise — no collective, so dispatch it outside
    # the lock rather than serializing against REDUCE's dispatch
    return _average_h2d(task, out)


def push_pull_async(
    x: jnp.ndarray,
    average: bool = True,
    name: Optional[str] = None,
    priority: Optional[int] = None,
    compression_params: Optional[Dict[str, Any]] = None,
) -> Handle:
    """Asynchronously all-reduce a stacked per-device tensor.

    ``x`` has shape ``(pod_size(), ...)``, row d = local device d's value
    (the analog of one reference worker's GPU buffer), ideally sharded over
    the dp axis. In hybrid mode the result additionally sums across the
    ``DMLC_NUM_WORKER`` pods (``average=True`` divides by the global
    ``size()``). Returns a Handle; ``handle.wait()`` / :func:`synchronize`.

    Reference: ``byteps_push_pull`` / ``byteps_torch_push_pull_async``.

    In global-mesh mode (``BYTEPS_JAX_DISTRIBUTED``) across several
    controller processes, pass either the full global ``(size(), ...)``
    array or just THIS process's local-device rows
    ``(jax.local_device_count(), ...)`` — local rows are assembled into one
    dp-sharded global array before the collective.
    """
    _require_init()
    from byteps_tpu.comm.distributed import is_multiprocess

    n = pod_size()
    multiproc = is_multiprocess()
    if multiproc:
        n_local = jax.local_device_count()
        bps_check(
            x.ndim >= 1 and x.shape[0] in (n, n_local),
            f"expected leading axis {n} (global) or {n_local} (local "
            f"devices), got {x.shape}",
        )
    else:
        bps_check(x.ndim >= 1 and x.shape[0] == n,
                  f"expected leading axis {n} (= pod_size()), got {x.shape}")
    anonymous = name is None
    with _state.lock:
        if anonymous:
            name = f"byteps_push_pull.anon_{_state.anon_counter}"
            _state.anon_counter += 1
    inner_shape = x.shape[1:]
    L = int(np.prod(inner_shape)) if inner_shape else 1
    ctx = _state.registry.declare(name, (L,), np.dtype(x.dtype))
    with _state.lock:
        version = _state.versions.get(name, 0)
        _state.versions[name] = version + 1
    # auto step detection: the highest round number any tensor has reached
    # IS the training step — BYTEPS_TRACE_ON=1 alone records, no user code
    get_tracer().advance_to(version + 1)
    spec = (
        from_params(compression_params)
        if compression_params is not None
        else _state.spec
    )
    if anonymous and spec.enabled and (spec.ef or spec.momentum):
        # EF/momentum are per-tensor persistent state keyed by name; a fresh
        # anonymous name every call would never accumulate (EF silently off)
        # while leaking one gradient-sized buffer per call into the state
        # dicts. The reference requires named tensors for the same reason
        # (per-tensor compressor instances in BPSContext).
        import dataclasses as _dc

        if not getattr(push_pull_async, "_warned_anon_state", False):
            log.warning(
                "push_pull called without name= while %s is configured: "
                "error-feedback/momentum need a stable tensor name to "
                "persist state — disabled for anonymous tensors",
                spec.compressor.name,
            )
            push_pull_async._warned_anon_state = True  # type: ignore[attr-defined]
        spec = _dc.replace(spec, ef=False, momentum=False)
    plans = None
    if _state.cfg.is_distributed:
        # Hybrid mode compresses the DCN wire per partition (the server
        # decompresses, fp32-sums, recompresses). Partitions below
        # BYTEPS_MIN_COMPRESS_BYTES ride raw fp32 — tiny chunks expand
        # under onebit's word floor and aren't worth the codec time.
        from byteps_tpu.compression.wire import WirePlan, make_wire_codec

        codec = None
        if spec.enabled:
            try:
                codec = make_wire_codec(spec)
            except ValueError:
                # custom registry compressors without a DCN byte format
                # degrade to fp32 on the wire instead of crashing the job
                if not getattr(push_pull_async, "_warned_nowire", False):
                    log.warning(
                        "compressor '%s' has no DCN wire codec — hybrid "
                        "pushes for it ride fp32", spec.compressor.name,
                    )
                    push_pull_async._warned_nowire = True  # type: ignore[attr-defined]
        plans = [
            None
            if codec is None
            or p.length * 4 < _state.cfg.min_compress_bytes
            else WirePlan(codec, spec.two_way)
            for p in ctx.partitions
        ]
    # Skip compression for tiny tensors (reference: BYTEPS_MIN_COMPRESS_BYTES)
    elif spec.enabled and L * np.dtype(x.dtype).itemsize < _state.cfg.min_compress_bytes:
        spec = from_params(None)
    if multiproc and x.shape[0] != n:
        x2d = _global_rows(np.asarray(x).reshape(x.shape[0], L), n)
    else:
        x2d = x.reshape(n, L)
    handle = Handle(name, len(ctx.partitions))
    handle.inner_shape = inner_shape  # type: ignore[attr-defined]
    handle.dtype = x.dtype            # type: ignore[attr-defined]
    if _state.psworkers:
        handle.diag = _stall_diag  # StallError diagnostics (hybrid tier)
    shared = {
        "x2d": x2d,
        "spec": spec,
        "average": average,
        "version": version,
        "plans": plans,
        "rng": _tensor_rng(name, version, spec.seed),
    }
    tasks = []
    for p in ctx.partitions:
        overrides: Dict[str, Any] = {}
        if priority is not None:
            overrides["priority"] = priority  # override declaration order
        if _state.owners is not None:
            # owner label = placement at enqueue time (credit-pool
            # identity / trace attribution); stages re-resolve live
            overrides["owner"] = _state.owners.owner(p.key)
        if overrides:
            p = dataclasses.replace(p, **overrides)
        tasks.append(
            PartitionTask(partition=p, name=name, handle=handle,
                          context=shared, round=version)
        )
    if multiproc:
        # SPMD determinism: every controller must issue IDENTICAL
        # collectives in IDENTICAL order or the job deadlocks. The credit
        # scheduler's pop order is timing-dependent (credits free on
        # device-side completion), so in global-mesh mode chunks dispatch
        # inline in partition order — JAX's async dispatch still overlaps
        # their execution; only the issue order is pinned.
        handle.localize = True  # type: ignore[attr-defined]
        tracer = get_tracer()
        for t in tasks:
            with tracer.span(
                f"{name}.p{t.partition.part_idx}", "PUSHPULL",
                args={"key": t.partition.key,
                      "priority": t.partition.priority,
                      "length": t.partition.length},
            ):
                result = _dispatch_stage(t)
            handle._partition_done(t.partition.part_idx, result)
        return handle
    _state.scheduler.enqueue(tasks)
    return handle


def synchronize(handle: Handle, timeout: Optional[float] = 120.0) -> jnp.ndarray:
    """Wait for a handle and assemble the replicated result.

    Reference: ``synchronize()``/``wait_and_clear`` in byteps/torch.
    """
    results = handle.wait(timeout)
    parts = [results[i] for i in sorted(results)]
    if getattr(handle, "localize", False):
        # global-mesh mode: chunk results are mesh-wide replicated arrays;
        # hand the caller an ordinary process-local value (the Horovod-style
        # eager contract — usable in plain per-device computation, exactly
        # like the reference's in-place updated GPU tensor)
        flat_np = (np.asarray(parts[0]) if len(parts) == 1
                   else np.concatenate([np.asarray(p) for p in parts]))
        out = jnp.asarray(flat_np.reshape(handle.inner_shape))  # type: ignore[attr-defined]
        return out.astype(handle.dtype)     # type: ignore[attr-defined]
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    out = flat.reshape(handle.inner_shape)  # type: ignore[attr-defined]
    return out.astype(handle.dtype)         # type: ignore[attr-defined]


def push_pull(
    x: jnp.ndarray,
    average: bool = True,
    name: Optional[str] = None,
    priority: Optional[int] = None,
    compression_params: Optional[Dict[str, Any]] = None,
) -> jnp.ndarray:
    """Blocking push_pull (reference: ``push_pull(tensor, average, name)``)."""
    return synchronize(
        push_pull_async(x, average, name, priority, compression_params)
    )


def push_pull_tree(
    grads, average: bool = True, name_prefix: str = "grad",
) -> Any:
    """Eagerly aggregate a pytree of stacked (N, ...) gradients; tensors are
    declared in pytree order so earlier leaves get higher priority."""
    _require_init()
    leaves, treedef = jax.tree.flatten(grads)
    handles = [
        push_pull_async(leaf, average=average, name=f"{name_prefix}.{i}")
        for i, leaf in enumerate(leaves)
    ]
    outs = [synchronize(h) for h in handles]
    return jax.tree.unflatten(treedef, outs)


# --- broadcast (reference: broadcast_parameters / broadcast_optimizer_state) -
def broadcast_parameters(params, root_rank: int = 0):
    """Replicate global rank ``root_rank``'s row of stacked (n_pod, ...)
    leaves to everyone — returns the replicated pytree (functional, unlike
    the reference's in-place op). Implemented as zero-on-non-root + summed
    aggregation, the reference's own trick; in hybrid mode the sum crosses
    pods through the summation servers (rank = pod_id·pod_size + row)."""
    _require_init()
    n = pod_size()
    root_pod, root_row = divmod(root_rank, n)

    if _state.cfg.is_distributed:
        import zlib

        leaves, treedef = jax.tree.flatten(params)
        # Fixed key family per pytree signature: repeated broadcasts (the
        # periodic-broadcast workload) reuse the same tensor names — and so
        # the same server KeyStores and registry entries — instead of
        # minting a fresh c{N} family per call that grows server memory
        # without bound. Distinct structures (params vs optimizer state)
        # hash to distinct families; workers derive the signature from the
        # same pytree, so names agree across pods with no counter to align.
        sig_src = repr(treedef) + repr(
            [(tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves]
        )
        sig = zlib.crc32(sig_src.encode()) & 0xFFFFFFFF
        handles = []
        for i, leaf in enumerate(leaves):
            bps_check(leaf.shape[0] == n, f"leading axis must be {n}")
            if _state.cfg.worker_id == root_pod:
                mask = (jnp.arange(n) == root_row).reshape(
                    (n,) + (1,) * (leaf.ndim - 1))
                z = jnp.where(mask, leaf, jnp.zeros_like(leaf))
            else:
                z = jnp.zeros_like(leaf)
            # fp32 wire: int leaves survive exactly below 2^24; broadcasts
            # never ride a lossy codec (params must replicate bit-faithfully
            # even when gradient compression is configured globally)
            handles.append(push_pull_async(
                z, average=False,
                name=f"byteps_broadcast.s{sig:08x}.{i}",
                compression_params={}))
        outs = [synchronize(h) for h in handles]
        return jax.tree.unflatten(treedef, outs)

    from byteps_tpu.comm.distributed import is_multiprocess

    multiproc = is_multiprocess()

    def bcast(leaf):
        L = int(np.prod(leaf.shape[1:])) if leaf.ndim > 1 else 1
        if multiproc and leaf.shape[0] != n:
            flat2d = _global_rows(
                np.asarray(leaf).reshape(leaf.shape[0], L), n)
        else:
            bps_check(leaf.shape[0] == n, f"leading axis must be {n}")
            flat2d = leaf.reshape(n, L)
        # native dtype throughout: zero-plus-psum is exact for ints too,
        # and a float32 round-trip would corrupt int leaves > 2^24
        flat = broadcast_flat(
            flat2d, _state.mesh, root=root_rank, axis=_state.cfg.dp_axis,
        )
        if multiproc:  # hand back a process-local value (see synchronize)
            flat = jnp.asarray(np.asarray(flat))
        return flat.reshape(leaf.shape[1:])

    return jax.tree.map(bcast, params)


def broadcast_optimizer_state(opt_state, root_rank: int = 0):
    """Parity alias: optimizer states are pytrees too."""
    return broadcast_parameters(opt_state, root_rank)


def tuner():
    """The active AutoTuner (or None): call ``tuner().record_step(secs)``
    once per training step to drive online (partition, credit) tuning."""
    _require_init()
    return _state.tuner


def auto_tune_enabled() -> bool:
    """True when BYTEPS_AUTO_TUNE=1 — build your fused step through
    :class:`AutoTunedStep` (the train-step factories in
    ``byteps_tpu.models.train`` do this automatically)."""
    return get_config().auto_tune


def default_partition_bytes() -> int:
    """The configured BYTEPS_PARTITION_BYTES (tuner starting point)."""
    return get_config().partition_bytes


def declare_tensor(name: str, shape, dtype) -> None:
    """Pre-declare to fix priority order explicitly (reference:
    ``byteps_declare_tensor``)."""
    _require_init()
    L = int(np.prod(shape)) if len(tuple(shape)) else 1
    _state.registry.declare(name, (L,), np.dtype(dtype))
