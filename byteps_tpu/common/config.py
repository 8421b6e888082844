"""Typed runtime configuration fed by ``DMLC_*`` / ``BYTEPS_*`` env vars.

The reference configures everything through environment variables (SURVEY
§5.6; reference ``docs/env.md``, parsed in ``byteps/common/global.cc`` and
``ps-lite include/ps/internal/env.h``). We keep the same names so reference
user scripts and launch wrappers work unchanged, but back them with a typed
``Config`` object used everywhere internally.

Two namespaces:

* ``DMLC_*`` — cluster topology (role, counts, rendezvous address). Consumed
  by the launcher, the DCN parameter-server tier, and ``jax.distributed``
  initialization.
* ``BYTEPS_*`` — runtime tuning (partition bytes, scheduling credit, async
  mode, tracing, log level).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return int(v)


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return v.strip().lower() in ("1", "true", "on", "yes", "y")


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name, default)


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return float(v)


# Partition size default mirrors the reference's BYTEPS_PARTITION_BYTES
# default of 4096000 bytes (byteps/common/global.cc).
DEFAULT_PARTITION_BYTES = 4096000
# Reference BYTEPS_SCHEDULING_CREDIT default (byteps/common/scheduled_queue.cc).
DEFAULT_SCHEDULING_CREDIT = 4
# (The reference's BYTEPS_NCCL_GROUP_SIZE has no TPU analog: XLA's async
# dispatch overlaps chunk collectives on the device stream and the credit
# scheduler bounds in-flight partitions, which together subsume NCCL group
# batching — the knob is intentionally not exposed.)
DEFAULT_SERVER_ENGINE_THREADS = 4


@dataclasses.dataclass
class Config:
    """Process-wide runtime configuration (reference: ``BytePSGlobal``)."""

    # --- DMLC_* cluster topology -------------------------------------------
    role: str = "worker"  # scheduler | server | worker | joint
    num_worker: int = 1
    num_server: int = 0
    ps_root_uri: str = "127.0.0.1"
    ps_root_port: int = 9000
    worker_id: int = 0
    interface: str = ""
    # Global-mesh mode (BYTEPS_JAX_DISTRIBUTED=1): the DMLC_NUM_WORKER
    # worker processes join one jax.distributed group and device_mesh()
    # spans all hosts; aggregation is pure XLA collectives (ICI + DCN) and
    # the PS tier is bypassed. Default off = hybrid PS topology.
    jax_distributed: bool = False
    # Coordination-service address for global-mesh rendezvous, hosted by
    # WORKER 0 (reference analog: the ps-lite scheduler's address). The
    # defaults reuse DMLC_PS_ROOT_URI/PORT — correct when worker 0 lives at
    # that address (the common colocated layout; PS servers bind
    # port+1+i so there is no clash). Deployments whose DMLC_PS_ROOT_URI
    # points at a dedicated scheduler machine must set
    # BYTEPS_JAX_COORD_URI to worker 0's host instead — our scheduler role
    # is a no-op that binds nothing.
    jax_coord_uri: str = "127.0.0.1"
    jax_coord_port: int = 9000

    # --- BYTEPS_* runtime tuning -------------------------------------------
    local_rank: int = 0
    local_size: int = 1
    partition_bytes: int = DEFAULT_PARTITION_BYTES
    scheduling_credit: int = DEFAULT_SCHEDULING_CREDIT
    force_distributed: bool = False
    enable_async: bool = False
    enable_ipc: bool = False
    server_engine_threads: int = DEFAULT_SERVER_ENGINE_THREADS
    # Priority-ordered server engine (reference BYTEPS_SERVER_ENABLE_SCHEDULE
    # [C-LOW]): a contended engine sums/answers lower keys (earlier-declared,
    # higher-priority tensors) first, matching the worker scheduler's order.
    server_enable_schedule: bool = False
    # Server expires pulls waiting longer than this with an error so a dead
    # worker fails the job fast instead of hanging its peers (reference
    # analog: ps-lite heartbeat/resender timeouts). 0 disables.
    pull_timeout_ms: int = 60000
    log_level: str = "INFO"
    # compression: compress only partitions >= this many bytes (reference
    # BYTEPS_MIN_COMPRESS_BYTES semantics: tiny tensors aren't worth it).
    min_compress_bytes: int = 65536
    # Application-level DCN bandwidth emulation (no reference analog):
    # > 0 paces every PSWorker's wire payload bytes through per-direction
    # token buckets at this many megabits/s, so loopback behaves like a
    # slow cross-pod link (the regime gradient compression exists for).
    # 0 disables. See server/pacer.py and tests/test_throttled_dcn.py.
    dcn_throttle_mbps: float = 0.0
    # Sharded-wire hierarchical DCN tier (BytePS "use every link", OSDI'20
    # §hierarchical): the hybrid pipeline reduce-SCATTERs the pod instead
    # of allreducing, assigns each partition an owner controller
    # (rendezvous hash over the pod's controllers), and each owner
    # pushes/pulls only its ~1/controllers slice through its own NIC; an
    # all-gather tail reassembles before H2D. Results are bit-exact vs
    # the unsharded path (raw) / at wire-codec roundoff (compressed) —
    # pinned in tests/test_sharded_hybrid.py. Default on.
    hybrid_sharded: bool = True
    # Controller NICs the pod is modeled with (each its own PSWorker:
    # connections, pacer, fault plan). 1 = the classic single-pusher
    # wire. > 1 divides per-NIC DCN bytes by the count (byte counts in
    # tests/test_sharded_hybrid.py). Deliberately its own knob
    # (NOT BYTEPS_LOCAL_SIZE, which counts launcher-spawned processes).
    pod_controllers: int = 1
    # Salt of the partition→owner rendezvous hash (reshuffles placement
    # without renaming tensors; must agree across a pod's controllers).
    owner_salt: int = 0
    # Multi-slice mesh: > 1 adds a leading slice_ axis of this size to
    # make_mesh/factor_devices (real TPU pods via
    # create_hybrid_device_mesh, anywhere else emulated slice
    # boundaries). The Partitioner routes "batch" over (slice_, dp) and
    # the gradient path becomes hierarchical: per-slice ICI
    # reduce-scatter, (optionally compressed) DCN exchange over slice_,
    # ICI all-gather. See docs/architecture.md §partitioner.
    num_slices: int = 1
    # ZeRO-3 FSDP (parallel/zero3.py): params + optimizer moments live
    # as flat segments sharded over slice_ (or dp), all-gathered
    # just-in-time per layer. Launchers translate this into
    # make_gpt_train_step(zero_3=True).
    zero3: bool = False

    # --- robustness / chaos (docs/robustness.md) ---------------------------
    # Deterministic fault injection at the PSWorker wire boundary
    # (common/faults.py grammar); empty = off. Arming it also turns on
    # wire CRC so injected corruption is detected, not summed.
    fault_spec: str = ""
    fault_seed: int = 0
    # Worker-side retry engine: retryable wire errors (recv timeout, dead
    # socket, desync, CRC mismatch) are retried up to this many times per
    # op with exponential backoff (base below, x2 per attempt, capped at
    # 2 s) + seeded jitter. Replay-safe: a re-sent push carries the same
    # (worker, key, version) and the server dedupes it.
    retry_limit: int = 8
    retry_backoff_ms: int = 50
    # CRC32 on wire payloads (frame header crc field): pushes are verified
    # server-side before summing, pull responses worker-side. Off by
    # default (a software CRC pass per 4 MB partition is measurable);
    # forced on while fault injection is armed.
    wire_crc: bool = False
    # Health monitor: > 0 pings every server each interval from a
    # background thread; after `health_miss_limit` consecutive misses the
    # server is marked dead and its keys fail over to the survivors
    # (rendezvous hash over the live set). 0 disables.
    health_interval_ms: int = 0
    health_miss_limit: int = 3
    # With no live server left: True degrades push_pull to the pod-local
    # (pure-ICI) sum with a loud log + counters; False fails the handle.
    degraded_ok: bool = True
    # Elastic worker membership (docs/robustness.md): > 0 arms worker
    # LEASES on the summation servers — a worker silent past this many ms
    # (no push/pull/heartbeat) is EVICTED: the membership epoch bumps,
    # open rounds re-target the live worker set (partial sums scaled to
    # the survivors so the global average stays unbiased), stuck barriers
    # release, and the server can exit without the dead worker's goodbye.
    # Workers heartbeat through the health monitor's kPing (enable
    # BYTEPS_HEALTH_INTERVAL_MS well below the lease). 0 = fixed
    # membership (legacy: one dead worker stalls every peer).
    worker_lease_ms: int = 0
    # > 0 caps EVERY Handle.wait() at this many ms: a would-be infinite
    # wait (peer death with no lease, total stall) raises a diagnosable
    # StallError carrying per-stage/per-server counters instead of
    # blocking forever. 0 = only the caller's own timeout applies.
    handle_deadline_ms: int = 0
    # Bounded-staleness PS rounds (BYTEPS_STALENESS=K, docs/robustness.md
    # §bounded staleness): K > 0 lets the summation servers answer a pull
    # for round v from the newest CLOSED round >= v-K — and force-close a
    # straggler-held round over its contributors (quorum-scaled, exactly
    # like an eviction-shrunk round) — so one slow worker no longer sets
    # the global step time; the worker pipeline keeps K rounds of pushes
    # in flight (per-key scheduler window) while PULL consumes whatever
    # round the server serves, and responses stamp the SERVED round.
    # K=0 = today's synchronous tier, bit-identical; BYTEPS_ENABLE_ASYNC
    # is the K=inf limit and wins when both are set.
    staleness: int = 0
    # --- autoscaler policy defaults (common/autoscaler.py) -----------------
    # One ScalingPolicy class drives BOTH elasticity domains: train
    # worker admit/evict off the telemetry registry (goodput/worker
    # trend, server.staleness p99, rounds_ahead straggler spread) and
    # serve replica spawn/drain off queue depth + TTFT. These knobs are
    # the shared decision dynamics; the load thresholds themselves are
    # per-policy constructor arguments (their units differ per domain).
    # Relative dead band around each threshold — decisions fire only
    # OUTSIDE load*(1±hysteresis), so a load oscillating on a threshold
    # cannot flap the membership.
    autoscale_hysteresis: float = 0.1
    # Policy steps to HOLD after any admit/evict (lets the epoch bump,
    # shard remap, and goodput trend settle before the next decision).
    autoscale_cooldown: int = 3
    # Consecutive out-of-band samples required before acting ("sustained
    # goodput headroom", not one lucky step).
    autoscale_sustain: int = 2
    # Unit-count bounds the policy will never cross.
    autoscale_min: int = 1
    autoscale_max: int = 16
    # --- launcher supervisor (byteps_tpu/launcher.py Supervisor) -----------
    # Max automatic respawns per flapping child before the supervisor
    # gives up on it (ISSUE 20 bounded restart-with-backoff).
    supervisor_restart_limit: int = 3
    # Base respawn delay; doubles per consecutive restart of one child.
    supervisor_backoff_ms: int = 200
    # SIGTERM→SIGKILL escalation grace on retire/shutdown.
    supervisor_grace_ms: int = 2000
    # Supervisor poll cadence (child reap + proc-fault plan tick).
    supervisor_poll_ms: int = 50
    # --- socket NIC (common/socknic.py) ------------------------------------
    # Per-request recv deadline on SocketNicClient (real wire-death
    # classification: past this the request raises TimeoutError).
    socket_timeout_ms: int = 10000
    # Token-bucket shaping for socket NIC payloads (0 = unshaped). The
    # PR 1 DcnPacer, now pacing a real link.
    socket_mbps: float = 0.0
    # Listen-path port probes through server.any_port (the PR 4
    # ephemeral-port-squatter sidestep).
    socket_port_attempts: int = 16

    # --- telemetry plane (docs/observability.md) ---------------------------
    # Always-on metrics registry (common/metrics.py): counters, gauges,
    # fixed-bucket latency/size histograms threaded through every layer
    # (scheduler stages, per-NIC wire, pacer, ICI dispatch, faults,
    # train-step walltime). 0 swaps every handle for a no-op.
    metrics_on: bool = True
    # Flight recorder (common/flight_recorder.py): bounded ring of
    # per-step metric snapshots, dumped on StallError/PartitionFailure.
    # 0 disables the per-step ring (FAULT events still recorded).
    flight_recorder_steps: int = 64
    # Recent FAULT-class events (retries, failovers, evictions,
    # membership changes) kept for the post-mortem; 0 disables.
    flight_recorder_events: int = 128
    # When set: post-mortems are ALSO written as JSON files into this
    # directory (one per distinct failure reason per run); empty = the
    # post-mortem only rides the raised error object.
    flight_recorder_dir: str = ""

    # --- inference serving tier (docs/serving.md) --------------------------
    # KV block size (tokens per paged-cache block). Must divide the
    # model's max_seq for bit-tight packing vs the dense cache (the
    # scheduler validates); 16 suits both the tiny CI configs and the
    # flash kernels' tiling.
    serve_block_size: int = 16
    # Physical KV blocks in the preallocated pool. 0 = auto: enough for
    # max_batch full-length requests plus the reserved scratch block
    # (no oversubscription). Smaller pools oversubscribe and trigger
    # preemption with recompute-on-resume.
    serve_pool_blocks: int = 0
    # Decode-batch slots: how many requests one packed decode step
    # serves (the jitted step's static batch dimension).
    serve_max_batch: int = 8
    # Prefill chunk length in tokens: long prompts are fed through the
    # model this many tokens per scheduler iteration so a 2k-token
    # prompt can't starve the decode lane (Orca-style iteration-level
    # scheduling).
    serve_prefill_chunk: int = 32
    # int8-quantized KV pool (reuses generate.py's _QuantSlot absmax
    # machinery) — ~half the pool HBM of bf16, the knob that doubles
    # the servable batch/context per chip.
    serve_quant_cache: bool = False
    # Default spec_len for per-request speculative policies.
    serve_spec_len: int = 4
    # Radix prefix cache over the paged KV pool (docs/serving.md
    # §prefix cache): committed prefill blocks are published to a
    # content-addressed radix index with per-block refcounts; requests
    # sharing a prompt prefix map their leading table entries to the
    # SAME physical pages (copy-on-write at the divergence block) and
    # skip the shared prefill chunks. Default-on — outputs are pinned
    # bit-identical either way; 0 is the escape hatch.
    serve_prefix_cache: bool = True
    # Replica lease for the serve router (serve/router.py): a replica
    # silent past this many ms (no completed scheduler step) is evicted
    # — epoch bump, its in-flight requests re-queue to survivors.
    # Mirrors the PR 5 server-side worker-lease semantics.
    serve_replica_lease_ms: int = 1000
    # --- disaggregated prefill/decode (docs/serving.md §disaggregation) ----
    # Emulated per-replica KV-migration NIC rate in megabits/s: finished
    # prefill blocks stream to the decode target through a token-bucket
    # pacer at this rate (the PR 1 pacer philosophy — loopback behaves
    # like the wire tier migration actually crosses). 0 = unthrottled.
    serve_disagg_mbps: float = 0.0
    # Admission classification knee: inputs of at least this many tokens
    # route to the prefill tier (when one is armed); shorter prompts
    # prefill in place on their decode replica. Shrinks 4x under decode
    # pool pressure (<= 25% free) — the "prompt length x pool pressure"
    # rule.
    serve_disagg_prompt_threshold: int = 64
    # Migrate-don't-evict: a pool-pressure preemption victim's committed
    # KV blocks move to a sibling replica over the KV wire instead of
    # being freed and recomputed (needs >= 2 decode-capable replicas
    # behind a Router). 0 = classic evict + recompute-on-resume.
    serve_disagg_migrate: bool = True
    # KVCOMPRESS->KVPUSH credits per migration wire: how many encoded
    # blocks may sit between the codec and a throttled wire.
    serve_disagg_credit: int = 4
    # --- multi-tenant LoRA multiplexing (docs/serving.md §multi-tenant) ----
    # Device-resident adapter-pool slots (slot 0 is the reserved
    # all-zero base-model slot, so N slots serve N-1 concurrently-live
    # adapters; idle ones LRU-cache in place). 0 = no pool: the
    # scheduler serves the bare base model and rejects adapter-tagged
    # requests.
    serve_adapter_slots: int = 0
    # Rank bucket every pooled adapter is zero-padded to — mixed-rank
    # tenants share ONE compiled packed decode step (the padding adds
    # exactly 0.0 to the delta; docs/serving.md has the exactness
    # argument). Adapters with rank above the bucket are rejected at
    # registration.
    serve_adapter_rank_bucket: int = 8
    # Per-tenant KV-pool quota in blocks. 0 = off. A tenant's running
    # requests may hold at most this many blocks: growth past it
    # preempts the OFFENDER's own youngest run (never a sibling's),
    # and a single request that could never fit its tenant's quota is
    # rejected at submit — the noisy tenant hits its own wall.
    serve_tenant_quota_blocks: int = 0
    # Deficit-weighted fair queuing at admission: pick the
    # max-credit tenant's oldest eligible request instead of the
    # global head of queue. Single-tenant traffic reduces exactly to
    # the historical FIFO. Off = plain FIFO regardless of tenants.
    serve_fair_queue: bool = True

    # --- tracing (SURVEY §5.1) ---------------------------------------------
    trace_on: bool = False
    trace_dir: str = "./traces"
    trace_start_step: int = 1
    trace_end_step: int = 30
    trace_xprof: bool = False

    # --- auto-tuner (ByteScheduler, SURVEY §2.6) ---------------------------
    auto_tune: bool = False

    # --- TPU-specific knobs (no reference analog; documented in docs/env.md)
    # Name of the data-parallel mesh axis used by push_pull collectives.
    dp_axis: str = "dp"
    # Reduce dtype on the aggregation tier. The reference PS sums in fp32.
    reduce_dtype: str = "float32"
    # Wire transport of the compressed ICI collectives (comm/ici.py):
    # "staged" = one monolithic all_to_all + all_gather (codec and wire
    # serialize); "ring" = the ici-compressed tier — payloads ride n-1
    # ring hops (Pallas make_async_remote_copy kernels on TPU,
    # lax.ppermute twins elsewhere) with per-hop DMA/codec overlap,
    # pinned bit-exact vs staged for deterministic codecs. Under "ring"
    # the hybrid pipeline's REDUCE stage also rides the compressed wire
    # (compressed bytes on ICI) for qualifying partitions.
    ici_tier: str = "staged"

    @classmethod
    def from_env(cls) -> "Config":
        c = cls(
            role=_env_str("DMLC_ROLE", "worker"),
            num_worker=_env_int("DMLC_NUM_WORKER", 1),
            num_server=_env_int("DMLC_NUM_SERVER", 0),
            ps_root_uri=_env_str("DMLC_PS_ROOT_URI", "127.0.0.1"),
            ps_root_port=_env_int("DMLC_PS_ROOT_PORT", 9000),
            worker_id=_env_int("DMLC_WORKER_ID", 0),
            interface=_env_str("DMLC_INTERFACE", ""),
            jax_distributed=_env_bool("BYTEPS_JAX_DISTRIBUTED"),
            jax_coord_uri=_env_str(
                "BYTEPS_JAX_COORD_URI",
                _env_str("DMLC_PS_ROOT_URI", "127.0.0.1"),
            ),
            jax_coord_port=_env_int(
                "BYTEPS_JAX_COORD_PORT", _env_int("DMLC_PS_ROOT_PORT", 9000)
            ),
            local_rank=_env_int("BYTEPS_LOCAL_RANK", 0),
            local_size=_env_int("BYTEPS_LOCAL_SIZE", 1),
            partition_bytes=_env_int("BYTEPS_PARTITION_BYTES", DEFAULT_PARTITION_BYTES),
            scheduling_credit=_env_int("BYTEPS_SCHEDULING_CREDIT", DEFAULT_SCHEDULING_CREDIT),
            force_distributed=_env_bool("BYTEPS_FORCE_DISTRIBUTED"),
            enable_async=_env_bool("BYTEPS_ENABLE_ASYNC"),
            enable_ipc=_env_bool("BYTEPS_ENABLE_IPC"),
            server_engine_threads=_env_int("BYTEPS_SERVER_ENGINE_THREAD", DEFAULT_SERVER_ENGINE_THREADS),
            server_enable_schedule=_env_bool("BYTEPS_SERVER_ENABLE_SCHEDULE"),
            pull_timeout_ms=_env_int("BYTEPS_SERVER_PULL_TIMEOUT_MS", 60000),
            log_level=_env_str("BYTEPS_LOG_LEVEL", "INFO").upper(),
            min_compress_bytes=_env_int("BYTEPS_MIN_COMPRESS_BYTES", 65536),
            dcn_throttle_mbps=_env_float("BYTEPS_DCN_THROTTLE_MBPS", 0.0),
            hybrid_sharded=_env_bool("BYTEPS_HYBRID_SHARDED", True),
            pod_controllers=_env_int("BYTEPS_POD_CONTROLLERS", 1),
            owner_salt=_env_int("BYTEPS_OWNER_SALT", 0),
            num_slices=max(1, _env_int("BYTEPS_NUM_SLICES", 1)),
            zero3=_env_bool("BYTEPS_ZERO3"),
            fault_spec=_env_str("BYTEPS_FAULT_SPEC", ""),
            fault_seed=_env_int("BYTEPS_FAULT_SEED", 0),
            retry_limit=_env_int("BYTEPS_RETRY_LIMIT", 8),
            retry_backoff_ms=_env_int("BYTEPS_RETRY_BACKOFF_MS", 50),
            wire_crc=_env_bool("BYTEPS_WIRE_CRC"),
            health_interval_ms=_env_int("BYTEPS_HEALTH_INTERVAL_MS", 0),
            health_miss_limit=_env_int("BYTEPS_HEALTH_MISS_LIMIT", 3),
            degraded_ok=_env_bool("BYTEPS_DEGRADED_OK", True),
            worker_lease_ms=_env_int("BYTEPS_WORKER_LEASE_MS", 0),
            handle_deadline_ms=_env_int("BYTEPS_HANDLE_DEADLINE_MS", 0),
            staleness=max(0, _env_int("BYTEPS_STALENESS", 0)),
            autoscale_hysteresis=_env_float("BYTEPS_AUTOSCALE_HYSTERESIS",
                                            0.1),
            autoscale_cooldown=_env_int("BYTEPS_AUTOSCALE_COOLDOWN", 3),
            autoscale_sustain=_env_int("BYTEPS_AUTOSCALE_SUSTAIN", 2),
            autoscale_min=_env_int("BYTEPS_AUTOSCALE_MIN", 1),
            autoscale_max=_env_int("BYTEPS_AUTOSCALE_MAX", 16),
            supervisor_restart_limit=_env_int(
                "BYTEPS_SUPERVISOR_RESTART_LIMIT", 3),
            supervisor_backoff_ms=_env_int(
                "BYTEPS_SUPERVISOR_BACKOFF_MS", 200),
            supervisor_grace_ms=_env_int(
                "BYTEPS_SUPERVISOR_GRACE_MS", 2000),
            supervisor_poll_ms=_env_int("BYTEPS_SUPERVISOR_POLL_MS", 50),
            socket_timeout_ms=_env_int("BYTEPS_SOCKET_TIMEOUT_MS", 10000),
            socket_mbps=_env_float("BYTEPS_SOCKET_MBPS", 0.0),
            socket_port_attempts=_env_int("BYTEPS_SOCKET_PORT_ATTEMPTS",
                                          16),
            metrics_on=_env_bool("BYTEPS_METRICS_ON", True),
            flight_recorder_steps=_env_int("BYTEPS_FLIGHT_RECORDER_STEPS",
                                           64),
            flight_recorder_events=_env_int("BYTEPS_FLIGHT_RECORDER_EVENTS",
                                            128),
            flight_recorder_dir=_env_str("BYTEPS_FLIGHT_RECORDER_DIR", ""),
            serve_block_size=_env_int("BYTEPS_SERVE_BLOCK_SIZE", 16),
            serve_pool_blocks=_env_int("BYTEPS_SERVE_POOL_BLOCKS", 0),
            serve_max_batch=_env_int("BYTEPS_SERVE_MAX_BATCH", 8),
            serve_prefill_chunk=_env_int("BYTEPS_SERVE_PREFILL_CHUNK", 32),
            serve_quant_cache=_env_bool("BYTEPS_SERVE_QUANT_CACHE"),
            serve_spec_len=_env_int("BYTEPS_SERVE_SPEC_LEN", 4),
            serve_prefix_cache=_env_bool("BYTEPS_SERVE_PREFIX_CACHE",
                                         True),
            serve_replica_lease_ms=_env_int(
                "BYTEPS_SERVE_REPLICA_LEASE_MS", 1000),
            serve_disagg_mbps=_env_float("BYTEPS_SERVE_DISAGG_MBPS", 0.0),
            serve_disagg_prompt_threshold=_env_int(
                "BYTEPS_SERVE_DISAGG_PROMPT_THRESHOLD", 64),
            serve_disagg_migrate=_env_bool("BYTEPS_SERVE_DISAGG_MIGRATE",
                                           True),
            serve_disagg_credit=_env_int("BYTEPS_SERVE_DISAGG_CREDIT", 4),
            serve_adapter_slots=_env_int("BYTEPS_SERVE_ADAPTER_SLOTS", 0),
            serve_adapter_rank_bucket=_env_int(
                "BYTEPS_SERVE_ADAPTER_RANK_BUCKET", 8),
            serve_tenant_quota_blocks=_env_int(
                "BYTEPS_SERVE_TENANT_QUOTA_BLOCKS", 0),
            serve_fair_queue=_env_bool("BYTEPS_SERVE_FAIR_QUEUE", True),
            trace_on=_env_bool("BYTEPS_TRACE_ON"),
            trace_dir=_env_str("BYTEPS_TRACE_DIR", "./traces"),
            trace_start_step=_env_int("BYTEPS_TRACE_START_STEP", 1),
            trace_end_step=_env_int("BYTEPS_TRACE_END_STEP", 30),
            trace_xprof=_env_bool("BYTEPS_TRACE_XPROF"),
            auto_tune=_env_bool("BYTEPS_AUTO_TUNE"),
            dp_axis=_env_str("BYTEPS_DP_AXIS", "dp"),
            reduce_dtype=_env_str("BYTEPS_REDUCE_DTYPE", "float32"),
            ici_tier=_env_str("BYTEPS_ICI_TIER", "staged"),
        )
        return c

    def snapshot(self) -> dict:
        """JSON-safe dict of every resolved knob. Stamped into chrome-
        trace metadata (``TraceRecorder.dump``) and flight-recorder
        post-mortems so a recorded run carries the configuration that
        produced it — the what-if simulator (``byteps_tpu/sim``) replays
        a run from its artifacts alone, no out-of-band knowledge."""
        return dataclasses.asdict(self)

    @property
    def is_distributed(self) -> bool:
        """Multi-host via the DCN PS tier vs collectives-only.

        Mirrors the reference's distinction between the NCCL-only single
        machine fast path and the hybrid-PS distributed path
        (``byteps/common/operations.cc`` queue-list construction). In
        global-mesh mode (``BYTEPS_JAX_DISTRIBUTED``) multi-worker jobs are
        collectives-only: one mesh spans the hosts and psum crosses DCN,
        so the PS tier stays out of the picture.
        """
        if self.jax_distributed:
            return self.force_distributed
        return self.num_worker > 1 or self.force_distributed


_config: Optional[Config] = None


def get_config() -> Config:
    global _config
    if _config is None:
        _config = Config.from_env()
    return _config


def set_config(cfg: Config) -> None:
    global _config
    _config = cfg


def reset_config() -> None:
    """Drop the cached config (tests mutate env vars)."""
    global _config
    _config = None
