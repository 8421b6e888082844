"""byteps_tpu.server — the DCN-tier parameter server (summation service).

Reference analogs: ``byteps/server/server.{h,cc}`` (the service itself,
started by ``import byteps.server`` from the launcher) and the worker-side
``ps::KVWorker`` usage in ``byteps/common/core_loops.cc`` PUSH/PULL stages.

Topology: ``DMLC_NUM_SERVER`` summation servers listen on
``DMLC_PS_ROOT_PORT + 1 + server_id`` (all on ``DMLC_PS_ROOT_URI`` in the
localhost test topology; one per aggregator host in a real deployment).
Partition keys are assigned to servers by ``key % num_server`` — the
reference's key→server hash placement. There is no separate scheduler
process: ``jax.distributed`` (or the launcher) does rendezvous, which is the
TPU-native simplification of ps-lite's scheduler node (SURVEY §5.8).

Pushes and pulls carry a wire-codec id (``compression/wire.py`` formats):
the server decompresses each push into an fp32 accumulator and re-compresses
round results for compressed pulls — the reference server's
decompress→sum→recompress engine (SURVEY §2.2/§3.3).
"""

from __future__ import annotations

import itertools
import random
import threading
import time
import zlib
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from byteps_tpu.common.config import Config, get_config
from byteps_tpu.common.faults import (
    FaultPlan,
    InjectedConnectionError,
    InjectedTimeout,
    ServerDownError,
    WorkerKilledError,
    plan_from_env,
)
from byteps_tpu.common.flight_recorder import get_flight_recorder
from byteps_tpu.common.logging import get_logger
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.common.tracing import get_tracer
from byteps_tpu.server.native import (
    WIRE_RAW,
    NativeClient,
    WireCorruption,
    WorkerEvictedError,
    load_lib,
    reduce_sum_f32,
)
from byteps_tpu.server.pacer import DcnPacer, pacer_from_mbps

log = get_logger("server")

__all__ = [
    "start_server", "start_server_any_port", "stop_server",
    "serve_forever", "server_addresses",
    "PSWorker", "reduce_sum_f32", "DcnPacer", "FailedOverError",
    "NoLiveServersError", "WireCorruption", "WorkerEvictedError",
    "WorkerKilledError", "wire_crc32",
]


# Per-key rows the C++ summation server's own chrome trace emits
# (declared in the light stage_orders module so trace_analysis can
# learn the display order without importing the data plane).
from byteps_tpu.common.stage_orders import SERVER_STAGE_ORDER  # noqa: F401,E402

# Sequential id per PSWorker instance: each emulated NIC gets its own
# per-NIC metric series (wire.nic<N>.*) beside the process aggregates.
_NIC_SEQ = itertools.count()

# Per-server epochs of (epoch -> live count) divisor history retained in
# PSWorker._epoch_live: under churn every membership change adds an entry
# forever, so entries older than the newest adopted epoch minus this
# window are pruned (a response for a round >window epochs stale falls
# back to the currently adopted live count — by then the round snapshot
# itself has long been overwritten).
_EPOCH_LIVE_WINDOW = 64


def wire_crc32(buf) -> int:
    """CRC32 as carried in the frame header: 0 means 'unchecked', so the
    one-in-2^32 payload whose true CRC is 0 maps to 1 (the C++ side's
    wire_crc applies the identical adjustment)."""
    c = zlib.crc32(buf) & 0xFFFFFFFF
    return c if c != 0 else 1


class FailedOverError(RuntimeError):
    """The key's server placement changed (failover) while this op was in
    flight; its round numbering is gone. Not retryable at the wire level —
    the *stage* retry re-runs the op, which re-derives version and target
    against the post-failover topology."""


class NoLiveServersError(ConnectionError):
    """Every summation server is marked dead. Excluded from the WIRE retry
    budget (re-sending cannot help), but deliberately stage-retryable: the
    re-run of the PUSH stage takes the degraded pure-ICI branch when
    BYTEPS_DEGRADED_OK, else fails the handle."""


def hand_off_owner(workers, owners, rank: int):
    """The owner-failover handoff critical section — ONE definition shared
    by the jax hybrid pipeline and DcnCore (the caller holds its own pod
    lock around this). Fences the dying controller's worker so no round
    can be minted past the snapshot, hands its round counters / store
    sizes to every survivor, then shrinks the live set — in that order:
    fence-before-export closes the mint race, export-before-fail keeps a
    racing stage retry from minting a round at/below the server's replay
    watermark (the PR3 atomicity argument). Returns the PRE-fail live set
    (callers diff it to find which partitions moved), or None if ``rank``
    is already dead or the last controller."""
    live = owners.live()
    if rank not in live or len(live) <= 1:
        return None
    workers[rank].fence()
    versions, nbytes = workers[rank].export_rounds()
    for r in sorted(live - {rank}):
        workers[r].adopt_rounds(versions, nbytes)
    owners.fail(rank)
    return live


def retire_nic(worker, rank: int) -> None:
    """Free an EXTRA pod-controller NIC (owner failover or pod shutdown):
    fold its robustness counters into the trace first — tagged per-NIC,
    since every controller shares the pod's worker id — then close it
    (health monitor thread, connections, pacer). NIC 0 never retires this
    way: it alone carries the pod's single kShutdown round, so it goes
    through ``PSWorker.shutdown``."""
    worker.export_counters(f"worker{worker._worker_id}.nic{rank}")
    get_registry().counter("nic.retired").inc()
    worker.close()


def _is_retryable_wire_error(e: BaseException) -> bool:
    """Errors the worker retry engine may safely re-attempt: lost
    responses (rc=-7), desynchronized/killed sockets (rc=-6/-2/-3, the
    next attempt reconnects), detected corruption (CRC), and injected
    equivalents. Server-side kErr rejections (size/init mismatches, pull
    deadline expiry) are semantic failures a resend cannot fix."""
    if isinstance(e, (NoLiveServersError, FailedOverError)):
        return False
    if isinstance(e, (TimeoutError, ConnectionError, WireCorruption)):
        return True
    if isinstance(e, RuntimeError):
        s = str(e)
        return ("rc=-2" in s or "rc=-3" in s or "key mismatch" in s
                or "NativeClient is closed" in s)
    return False


def server_addresses(cfg: Optional[Config] = None) -> List[Tuple[str, int]]:
    cfg = cfg or get_config()
    num = max(1, cfg.num_server)
    return [(cfg.ps_root_uri, cfg.ps_root_port + 1 + i) for i in range(num)]


# server_id of the summation service running in THIS process, if any —
# lets PSWorker route that server's keys through the in-process fast path
# (BYTEPS_ENABLE_IPC) instead of TCP loopback.
_INPROC_SERVER_ID: Optional[int] = None


def start_server(
    port: Optional[int] = None,
    num_workers: Optional[int] = None,
    engine_threads: Optional[int] = None,
    async_mode: Optional[bool] = None,
    server_id: int = 0,
    pull_timeout_ms: Optional[int] = None,
    enable_schedule: Optional[bool] = None,
    lease_ms: Optional[int] = None,
    staleness: Optional[int] = None,
) -> int:
    """Start the native summation service in this process (non-blocking).

    ``lease_ms`` (default ``BYTEPS_WORKER_LEASE_MS``) > 0 arms elastic
    worker membership: a worker silent past the lease is evicted, the
    membership epoch bumps, open rounds re-target the live worker set,
    and stuck barriers release (docs/robustness.md §elastic membership).

    ``staleness`` (default ``BYTEPS_STALENESS``) > 0 arms BOUNDED-
    STALENESS rounds: a pull for round v is served from the newest
    CLOSED round >= v-K, a pull past the bound force-closes straggler-
    held rounds over their contributors (quorum-scaled), and responses
    stamp the served round — so one slow worker no longer sets the
    global step time (docs/robustness.md §bounded staleness). K=0 is
    bit-identical to the synchronous tier; ``BYTEPS_ENABLE_ASYNC`` is
    the K=inf limit and wins when both are set.
    """
    global _INPROC_SERVER_ID
    cfg = get_config()
    lib = load_lib()
    port = port if port is not None else cfg.ps_root_port + 1 + server_id
    rc = lib.bps_server_start(
        port,
        num_workers if num_workers is not None else cfg.num_worker,
        engine_threads if engine_threads is not None
        else cfg.server_engine_threads,
        1 if (async_mode if async_mode is not None else cfg.enable_async)
        else 0,
        pull_timeout_ms if pull_timeout_ms is not None
        else cfg.pull_timeout_ms,
        server_id,
        1 if (enable_schedule if enable_schedule is not None
              else cfg.server_enable_schedule) else 0,
        lease_ms if lease_ms is not None else cfg.worker_lease_ms,
        staleness if staleness is not None else cfg.staleness,
    )
    if rc != 0:
        raise RuntimeError(f"bps_server_start failed (rc={rc}, port={port})")
    _INPROC_SERVER_ID = server_id
    if cfg.trace_on:
        lib.bps_server_trace_enable(1)
    log.info("summation server listening on :%d", port)
    return port


def stop_server() -> None:
    global _INPROC_SERVER_ID
    load_lib().bps_server_stop()
    _INPROC_SERVER_ID = None


def any_port(bind, port: int, attempts: int = 16, stride: int = 1):
    """Probe ``attempts`` ports ``stride`` apart until ``bind(p)``
    succeeds, sidestepping ephemeral-port squatters: when the OS
    ip_local_port_range overlaps the chosen port (this image's starts at
    16000), any client socket can be sitting on it and the bind fails —
    rc=-2 from the native server, EADDRINUSE from a Python socket.
    Returns whatever ``bind`` returned for the port that stuck; any
    OTHER bind error propagates (a squatter is routine, a bad address
    is a bug). This is the one home of the PR 4 workaround — the native
    server path and the socket NIC listen path both delegate here."""
    import errno

    last: Optional[Exception] = None
    for i in range(attempts):
        p = port + i * stride
        try:
            return bind(p)
        except RuntimeError as e:
            if "rc=-2" not in str(e):
                raise
            last = e
        except OSError as e:
            if e.errno not in (errno.EADDRINUSE, errno.EACCES):
                raise
            last = e
    raise RuntimeError(
        f"no squatter-free port in {attempts} probes from {port}") from last


def start_server_any_port(port: int, attempts: int = 16, stride: int = 1,
                          **kw) -> int:
    """``start_server`` through the :func:`any_port` squatter sidestep;
    returns the port actually bound."""
    return any_port(lambda p: start_server(port=p, **kw), port,
                    attempts=attempts, stride=stride)


def dump_server_trace(path: str) -> int:
    """Write the server's chrome trace JSON; returns event count."""
    return load_lib().bps_server_trace_dump(path.encode())


def serve_forever(server_id: Optional[int] = None) -> None:
    """Launcher entry for the server role: start and block until all workers
    shut down (reference: ``import byteps.server`` → ``StartPS`` blocks)."""
    import os

    cfg = get_config()
    sid = (
        server_id if server_id is not None
        else int(os.environ.get("DMLC_SERVER_ID", "0"))
    )
    global _INPROC_SERVER_ID
    start_server(server_id=sid)
    load_lib().bps_server_wait()
    # the native server stopped (worker-driven shutdown); make sure no
    # later PSWorker(use_ipc=True) in this process routes into its leaked
    # store (the native Local* entries also refuse once stopped)
    _INPROC_SERVER_ID = None
    if cfg.trace_on:
        os.makedirs(cfg.trace_dir, exist_ok=True)
        path = os.path.join(cfg.trace_dir, f"trace_server{sid}.json")
        n = dump_server_trace(path)
        log.info("dumped %d server trace events to %s", n, path)
    log.info("summation server stopped")


class PSWorker:
    """Worker-side facade: key→server placement, per-key round tracking,
    connection-per-thread for pipelined push/pull, wire-byte accounting.

    Each OS thread (one per scheduler pool slot) gets its own serial
    connection to each server, so a pull blocked on a slow round never
    stalls another partition's push — the deadlock-freedom argument of the
    reference's separate PUSH/PULL core loops.

    With ``BYTEPS_ENABLE_IPC`` and a summation server running in THIS
    process (joint role), pushes/pulls for locally-owned keys skip TCP and
    access the store directly (the reference's colocated shared-memory
    fast path, ps-lite ``BYTEPS_ENABLE_IPC``).

    With ``BYTEPS_DCN_THROTTLE_MBPS`` > 0 (or ``throttle_mbps=``), this
    worker's payload bytes are paced through an emulated full-duplex NIC
    of that speed (``server/pacer.py``) — the bandwidth-throttled
    regime of the compression fast lane. The pacer is per-PSWorker, so
    several workers emulated in one process each get their own NIC.
    """

    def __init__(
        self,
        servers: Optional[Sequence[Tuple[str, int]]] = None,
        timeout_ms: int = 60000,
        recv_timeout_ms: int = 120000,
        worker_id: Optional[int] = None,
        use_ipc: Optional[bool] = None,
        throttle_mbps: Optional[float] = None,
        fault_plan: Optional[FaultPlan] = None,
        health_interval_ms: Optional[int] = None,
    ):
        """``health_interval_ms`` overrides BYTEPS_HEALTH_INTERVAL_MS for
        THIS worker (chaos tests arm a heartbeating survivor beside a
        monitor-less victim in one process; None = the config value)."""
        cfg = get_config()
        self._servers = list(servers) if servers else server_addresses()
        self._timeout = timeout_ms
        self._recv_timeout = recv_timeout_ms
        self._worker_id = (
            worker_id if worker_id is not None else cfg.worker_id
        )
        self._tls = threading.local()
        self._versions: Dict[int, int] = {}
        self._vlock = threading.Lock()
        self._fenced = False
        self._all_conns: List[NativeClient] = []
        self._conn_lock = threading.Lock()
        self._closed = False
        # wire accounting (compression tests / docs assert against these)
        self.bytes_pushed = 0
        self.bytes_pulled = 0
        self._ipc = (
            use_ipc if use_ipc is not None else cfg.enable_ipc
        ) and _INPROC_SERVER_ID is not None
        self.pacer: Optional[DcnPacer] = pacer_from_mbps(
            throttle_mbps if throttle_mbps is not None
            else cfg.dcn_throttle_mbps
        )
        # --- robustness state (docs/robustness.md) -------------------------
        self._plan = (fault_plan if fault_plan is not None
                      else plan_from_env(cfg, worker_id=self._worker_id))
        # CRC is forced on while CORRUPTION injection is armed:
        # corruption must be *detected* to be retryable instead of
        # silently summed. Every other kind needs no checksum — loss
        # kinds (timeout/kill/down) are caught by the rc/desync
        # classification and the version dedupe, latency ('slow') and
        # control ('join'/'hang') kinds touch no payload — so they do
        # not force the 2×-per-payload software CRC pass onto every
        # worker sharing the spec string (the churn/straggler legs
        # would otherwise measure CRC overhead, not elasticity).
        self._crc = bool(cfg.wire_crc) or (
            self._plan is not None
            and any(r.kind == "corrupt" for r in self._plan.rules))
        self._retry_limit = max(0, cfg.retry_limit)
        self._backoff_ms = max(1, cfg.retry_backoff_ms)
        # bounded staleness (BYTEPS_STALENESS): armed here so pull_bytes
        # can re-sync the mint counter off a serve-ahead response
        self._staleness = max(0, cfg.staleness)
        # seeded jitter: reproducible backoff schedules per worker
        self._retry_rng = random.Random(
            0xC0FFEE ^ (self._worker_id * 7919) ^ cfg.fault_seed)
        self._live: Set[int] = set(range(len(self._servers)))
        self._epoch = 0  # bumped per failover; in-flight ops self-abort
        self._key_nbytes: Dict[int, int] = {}  # for post-failover re-init
        # --- elastic worker membership (docs/robustness.md) ----------------
        # per-server membership epoch (low 16 bits, stamped on every
        # response) this worker has ADOPTED; a mismatch on any op
        # triggers a kMembers query + adoption
        self._epoch_seen: Dict[int, int] = {}
        # (server, epoch16) -> live worker count at that epoch: pull
        # responses carry the epoch their ROUND closed under, and the
        # averaging divisor must be THAT epoch's live count — a round
        # closed at full membership but delivered after an eviction must
        # still divide by the full count. Seeded with epoch 0 = the
        # configured membership.
        self._epoch_live: Dict[Tuple[int, int], int] = {
            (s, 0): max(1, cfg.num_worker)
            for s in range(len(self._servers))
        }
        # live worker (pod) count per the most recent adoption — what
        # averaging consumers divide by instead of the static
        # DMLC_NUM_WORKER once the membership shrinks/grows
        self._live_pods = max(1, cfg.num_worker)
        # injected self-death (worker:kill) / wedge window (worker:hang)
        self._self_killed = False
        self._wedged_until = 0.0
        # one-shot latch for the worker<N>:join fault rule: a join window
        # wider than one op must not re-run the admission handshake on
        # every subsequent wire attempt
        self._join_fired = False
        self.counters: Dict[str, int] = {
            "retries": 0, "timeouts": 0, "conn_errors": 0,
            "crc_errors": 0, "reinits": 0, "give_ups": 0,
            "failovers": 0, "ici_fallbacks": 0,
            "membership_events": 0, "rejoins": 0, "joins": 0,
        }
        self._counter_lock = threading.Lock()
        # --- always-on metrics registry (docs/observability.md) ------------
        # Every robustness count and wire byte ALSO lands in the
        # process-wide registry: the per-instance views above die with
        # the NIC (owner failover retires it), the registry totals do
        # not — which is what keeps per-run accounting complete.
        # Handles are resolved once here; _count mirrors lazily.
        self._nic_tag = f"nic{next(_NIC_SEQ)}"
        _reg = get_registry()
        self._m_counts: Dict[str, Tuple] = {}
        self._m_push_bytes = _reg.counter("wire.push_bytes")
        self._m_pull_bytes = _reg.counter("wire.pull_bytes")
        self._m_push_bytes_nic = _reg.counter(
            f"wire.{self._nic_tag}.push_bytes")
        self._m_pull_bytes_nic = _reg.counter(
            f"wire.{self._nic_tag}.pull_bytes")
        self._m_push_size = _reg.histogram("wire.push_size_bytes")
        # bounded-staleness observability (docs/observability.md):
        # requested − served per pull (how stale the aggregate this
        # worker consumed was), and how many rounds this worker's newest
        # minted push runs ahead of the round it last consumed. The
        # gauge is per-NIC (two NICs sharing one series would mask each
        # other last-writer-wins); the plain series is the most recent
        # pull in the process — the per-step flight-recorder view.
        self._m_staleness = _reg.histogram("server.staleness")
        self._m_rounds_ahead = _reg.gauge("psworker.rounds_ahead")
        self._m_rounds_ahead_nic = _reg.gauge(
            f"psworker.{self._nic_tag}.rounds_ahead")
        self._m_attempts = {
            op: (_reg.counter(f"wire.{op}_attempts"),
                 _reg.counter(f"wire.{self._nic_tag}.{op}_attempts"))
            for op in ("push", "pull", "init")
        }
        self._health: Optional[_HealthMonitor] = None
        hb_ms = (health_interval_ms if health_interval_ms is not None
                 else cfg.health_interval_ms)
        if hb_ms > 0 and len(self._servers) > 0:
            self._health = _HealthMonitor(
                self, interval_ms=hb_ms,
                miss_limit=max(1, cfg.health_miss_limit))
            self._health.start()

    # -- robustness helpers -------------------------------------------------
    def _count(self, name: str, n: int = 1) -> None:
        with self._counter_lock:
            self.counters[name] = self.counters.get(name, 0) + n
        m = self._m_counts.get(name)
        if m is None:
            _reg = get_registry()
            m = (_reg.counter(f"psworker.{name}"),
                 _reg.counter(f"psworker.{self._nic_tag}.{name}"))
            self._m_counts[name] = m
        m[0].inc(n)
        m[1].inc(n)

    def _trace_fault(self, event: str, **args) -> None:
        get_tracer().instant(event, "FAULT",
                             {"worker": self._worker_id, **args})

    def _kill_conn(self, sidx: int) -> None:
        """Drop this thread's connection to ``sidx`` (injected socket
        death); the next attempt reconnects through ``_conn``."""
        pool = getattr(self._tls, "conns", {})
        c = pool.get(sidx)
        if c is not None:
            self._evict(sidx, c)

    def _inject_pre(self, op: str, sidx: int):
        """Evaluate the fault plan for one wire attempt. 'kill'/'down'
        raise here (the request never leaves); 'timeout'/'corrupt' are
        returned for the caller to act on around the real op. Worker-scope
        rules simulate THIS process's death ('worker:kill' — sticky, every
        later op refuses) or wedge ('worker:hang' — ops block out the
        window, then report a lost response); both stop the lease
        heartbeat so the server's eviction fires as for a real crash."""
        if self._self_killed:
            raise WorkerKilledError(
                f"worker {self._worker_id} is dead (injected worker:kill); "
                f"{op} refused")
        rest = self._wedged_until - time.time()
        if rest > 0:
            time.sleep(rest)
            self._kill_conn(sidx)
            raise InjectedTimeout(
                f"injected: worker {self._worker_id} wedged through {op} "
                "(worker:hang window)")
        if self._plan is None:
            return None
        inj = self._plan.intercept(op, sidx)
        if inj is None:
            return None
        if inj.rule.scope == "worker":
            if inj.kind == "kill":
                self._self_killed = True
                self._trace_fault("worker_kill", op=op,
                                  step=self._plan.step)
                log.warning(
                    "worker %d killed by injection at plan step %d",
                    self._worker_id, self._plan.step)
                # a dead process's sockets die with it
                for s in list(getattr(self._tls, "conns", {})):
                    self._kill_conn(s)
                raise WorkerKilledError(
                    f"injected: worker {self._worker_id} killed during "
                    f"{op} (plan step {self._plan.step})")
            if inj.kind == "hang":
                self._wedged_until = (time.time()
                                      + inj.rule.latency_ms / 1e3)
                self._trace_fault("worker_hang", op=op,
                                  ms=inj.rule.latency_ms)
                time.sleep(inj.rule.latency_ms / 1e3)
                self._kill_conn(sidx)
                raise InjectedTimeout(
                    f"injected: worker {self._worker_id} wedged for "
                    f"{inj.rule.latency_ms} ms during {op}")
            if inj.kind == "join":
                # deterministic mid-stream admission (worker<N>:join@
                # step=A): run the kJoin handshake once, then let the
                # intercepted op proceed under the adopted membership —
                # the churn tests schedule joins this way
                if not self._join_fired:
                    self._join_fired = True
                    self.join()
                return None
            # other kinds under worker scope fall through to the generic
            # handling below (e.g. worker:timeout = lose own responses)
        if inj.kind == "down":
            self._kill_conn(sidx)
            raise ServerDownError(
                f"injected: server {sidx} down during {op} "
                f"(plan step {self._plan.step})")
        if inj.kind == "kill":
            self._kill_conn(sidx)
            raise InjectedConnectionError(
                f"injected: connection to server {sidx} killed before {op}")
        return inj

    def is_wedged(self) -> bool:
        """True while a worker:hang window is open (the health monitor
        stops heartbeating so the server lease can expire, exactly as a
        really-wedged process would go silent)."""
        return self._self_killed or self._wedged_until > time.time()

    def has_live_servers(self) -> bool:
        return bool(self._live)

    def live_servers(self) -> Set[int]:
        return set(self._live)

    def fail_over(self, sidx: int, barrier: bool = True) -> bool:
        """Mark server ``sidx`` dead and remap its keys to the survivors.

        All workers must take the same view of the live set before any
        pushes the new placement (their health monitors each call this;
        the worker barrier through the lowest surviving server aligns
        them). Key remap is rendezvous-hashed over the live set; the dead
        server's keys get fresh round counters (their stores — and the
        rounds in flight against them — are gone; in-flight ops for
        remapped keys abort with :class:`FailedOverError` and the stage
        retry re-runs them against the new placement). Returns False if
        the server was already dead."""
        with self._vlock:
            if sidx not in self._live:
                return False
            old_live = set(self._live)
            self._live.discard(sidx)
            self._epoch += 1
            # reset round numbering for every key whose placement changed,
            # atomically with the live-set shrink: a push racing this (a
            # stage retry landing on the survivor) must either see the old
            # placement (and abort FailedOverError) or a reset counter —
            # never mint a CONTINUATION version on the new server, which
            # would make all later fresh rounds look like replays to the
            # dedupe watermark
            for key in list(self._versions):
                if (self._server_for_live(key, old_live)
                        != self._server_for_live(key, self._live)):
                    del self._versions[key]
        self._count("failovers")
        self._trace_fault("failover", server=sidx,
                          survivors=sorted(self._live))
        log.warning("server %d marked dead; %s", sidx,
                    f"keys fail over to {sorted(self._live)}"
                    if self._live else "NO live servers remain "
                    "(degraded mode)")
        if barrier and self._live:
            try:
                self.barrier()
            except Exception as e:  # noqa: BLE001 - best-effort alignment
                log.warning("failover barrier failed: %s", e)
        return True

    def _server_for_live(self, key: int, live: Set[int]) -> int:
        """Deterministic placement agreed across workers: the home slot
        (key % n) when alive, else rendezvous hash over the survivors
        (zlib.crc32 is stable across processes, unlike salted hash())."""
        home = key % len(self._servers)
        if home in live or not live:
            return home  # no survivors: degraded path decides upstream
        return max(live,
                   key=lambda s: zlib.crc32(f"{key}:{s}".encode()))

    def server_for(self, key: int) -> int:
        with self._vlock:
            live = set(self._live)
        return self._server_for_live(key, live)

    # -- elastic worker membership (epoch adoption + rejoin) ----------------
    def live_pods(self) -> int:
        """Live WORKER (pod) count per the most recently adopted
        membership epoch — what averaging consumers divide by instead of
        the static DMLC_NUM_WORKER once a peer is evicted or rejoins."""
        with self._vlock:
            return max(1, self._live_pods)

    def _note_epoch(self, sidx: int) -> None:
        """Per-op membership-change detection: every server response
        stamps the current epoch (header reserved field); on a mismatch
        with the adopted one, query the live set and adopt it. Costs one
        ctypes read per op — no extra round trip until a change."""
        try:
            if self._is_local(sidx):
                e = int(load_lib().bps_server_epoch()) & 0xFFFF
            else:
                conn = getattr(self._tls, "conns", {}).get(sidx)
                if conn is None:
                    return
                e = conn.epoch()
        except Exception:  # noqa: BLE001 - detection is best-effort; the
            return         # next op retries it
        with self._vlock:
            seen = self._epoch_seen.get(sidx, 0)
        # adopt only a NEWER epoch (mod-2^16 window): a connection idle
        # across the bump still reports the old stamp on its last parsed
        # response, and adopting backwards would flap the live count
        if e != seen and ((e - seen) & 0xFFFF) < 0x8000:
            self._adopt_membership(sidx)

    def _adopt_membership(self, sidx: int) -> None:
        """Adopt a new membership epoch from server ``sidx`` (kMembers
        query): refresh the live pod count (pull results under the new
        epoch are sums over the LIVE set, so averaging must rescale
        consistently), record the query's own (epoch, live) pair in the
        divisor history, count the event, and land a MembershipEvent on
        the chrome trace's FAULT track. Failure leaves the old epoch
        adopted — the next op re-detects and retries."""
        try:
            if self._is_local(sidx):
                import ctypes

                lib = load_lib()
                ep = ctypes.c_uint64(0)
                live = ctypes.c_uint32(0)
                bitmap = (ctypes.c_uint8 * 1024)()
                n = lib.bps_server_members(
                    ctypes.byref(ep), ctypes.byref(live), bitmap, 1024)
                if n < 0:
                    return
                q_epoch = int(ep.value)
                live_count = int(live.value)
                bits = bytes(bitmap[: min(n, 1024)])
            else:
                q_epoch, live_count, bits = self._conn(sidx).members()
        except Exception as e:  # noqa: BLE001 - adoption retried next op
            log.debug("membership query on server %d failed: %s", sidx, e)
            return
        # the (epoch, live) pair must come from the QUERY's atomic view:
        # the trigger stamp `epoch16` may be older than the membership
        # the query answered for (another change landed in between), and
        # caching the new count under the old epoch would poison that
        # epoch's averaging divisor permanently
        q_epoch16 = q_epoch & 0xFFFF
        # plain bool: bits is a numpy array and an np.bool_ leaking into
        # the trace args breaks the chrome-trace JSON dump
        evicted_self = bool(self._worker_id < len(bits)
                            and bits[self._worker_id] == 0)
        with self._vlock:
            self._record_epoch_live(sidx, q_epoch16, int(live_count))
            seen = self._epoch_seen.get(sidx, 0)
            if (q_epoch16 == seen
                    or ((q_epoch16 - seen) & 0xFFFF) >= 0x8000):
                return  # another pool thread already adopted this epoch
            self._epoch_seen[sidx] = q_epoch16
            self._live_pods = max(1, int(live_count))
        self._count("membership_events")
        self._trace_fault("membership", server=sidx, epoch=q_epoch16,
                          live_pods=int(live_count),
                          evicted_self=evicted_self)
        log.warning(
            "membership epoch %d adopted from server %d: %d live "
            "worker(s)%s", q_epoch16, sidx, live_count,
            " — THIS worker is evicted (rejoin on next push)"
            if evicted_self else "")

    def _record_epoch_live(self, sidx: int, epoch16: int,
                           live: int) -> None:
        """Record the (epoch -> live count) divisor pair for ``sidx`` and
        PRUNE entries older than the recorded epoch minus
        ``_EPOCH_LIVE_WINDOW`` (mod-2^16 window, same arithmetic as the
        adoption ordering): under churn every membership change adds an
        entry forever, and a long-lived worker would otherwise grow this
        dict without bound. Caller holds ``_vlock``."""
        self._epoch_live[(sidx, epoch16 & 0xFFFF)] = max(1, int(live))
        # keep only entries within ±window of the recorded epoch: a
        # bare backward-window test would strand entries a large epoch
        # jump pushed onto the "future" half of the mod-2^16 ring —
        # they would then never age out (the unbounded growth this
        # prune exists to stop)
        stale = [
            k for k in self._epoch_live
            if k[0] == sidx
            and ((epoch16 - k[1]) & 0xFFFF) >= _EPOCH_LIVE_WINDOW
            and ((k[1] - epoch16) & 0xFFFF) >= _EPOCH_LIVE_WINDOW
        ]
        for k in stale:
            del self._epoch_live[k]

    def _live_at(self, sidx: int, epoch16: int) -> int:
        """Live worker count at ``epoch16`` on server ``sidx`` — the
        divisor for a round that CLOSED under that epoch. Unknown epochs
        (the round's close was the first sign of a membership change)
        adopt the current membership and retry the lookup; the final
        fallback is the currently adopted live count."""
        with self._vlock:
            v = self._epoch_live.get((sidx, epoch16))
        if v is not None:
            return v
        self._note_epoch(sidx)
        with self._vlock:
            return self._epoch_live.get((sidx, epoch16),
                                        max(1, self._live_pods))

    def last_round_live(self) -> Optional[int]:
        """Live worker count of the round the calling thread's most
        recent :meth:`pull_bytes` returned — what averaging consumers
        divide by for THAT round (``None`` before any pull). Thread-local,
        like the connections themselves."""
        return getattr(self._tls, "round_live", None)

    def last_pull_round(self) -> Optional[int]:
        """The round the calling thread's most recent :meth:`pull_bytes`
        was actually SERVED from (the response's round stamp). Under
        bounded staleness (``BYTEPS_STALENESS``) it may trail the
        requested round by up to K — requested − served is the pull's
        effective staleness. ``None`` before any pull; thread-local."""
        return getattr(self._tls, "round_served", None)

    def sync_rounds(self, sidx: int) -> None:
        """Adopt server ``sidx``'s per-key (round, nbytes) watermarks —
        the restart/rejoin half of the ``export_rounds``/``adopt_rounds``
        handshake: the server's store (and its (worker, key, version)
        replay-dedupe watermark) outlives this worker, so a fresh round
        counter would mint versions the dedupe silently drops — a
        permanent per-key stall. Max-merge via :meth:`adopt_rounds`;
        sizes seed the lazy re-init of inherited keys."""
        trips = self._conn(sidx).rounds()
        self.adopt_rounds(
            {int(k): int(v) for k, v, _ in trips},
            {int(k): int(nb) for k, _, nb in trips},
        )

    def rejoin(self) -> None:
        """Re-register with every live server after an eviction or a
        process restart: heartbeat with the worker id (the server
        re-admits and bumps the epoch), then adopt round watermarks so
        the next mint continues the server's round sequence. Invoked
        automatically when a push is refused with 'worker evicted'; also
        the public entry for a restarted process resuming from a
        checkpoint against a still-running server tier."""
        with self._vlock:
            live = sorted(self._live)
        for sidx in live:
            try:
                self.ping(sidx)        # heartbeat: re-admit + epoch bump
                self.sync_rounds(sidx)
                self._note_epoch(sidx)
            except Exception as e:  # noqa: BLE001 - a dead server cannot
                # block the rejoin against the live ones; its own
                # failover path owns it
                log.warning("rejoin against server %d failed: %s: %s",
                            sidx, type(e).__name__, e)
        self._count("rejoins")
        self._trace_fault("rejoin", servers=live)

    def join(self) -> int:
        """First-class mid-stream ADMISSION (kJoin) — the scale-UP
        counterpart of :meth:`rejoin`: register this worker id with
        every live server. A FRESH id (beyond ``DMLC_NUM_WORKER``) grows
        the server's membership table and per-key round vectors before
        the admission is published, so the join lands at a round
        boundary: the epoch bumps (stamped in every response — peers
        adopt it on their next op and rescale their averaging divisor),
        rounds open at admission close over their contributors
        (quorum-scaled), and this worker adopts round watermarks
        (``kRounds``) so its first mint continues at the served-round
        frontier — under ``BYTEPS_STALENESS`` that frontier never trails
        the force-close watermark. A previously evicted id re-admits the
        same way. Returns the number of servers that admitted us; raises
        :class:`NoLiveServersError` when none did (a joiner with no
        quorum cannot contribute)."""
        with self._vlock:
            live = sorted(self._live)
        joined = []
        for sidx in live:
            try:
                if self._is_local(sidx):
                    rc = int(load_lib().bps_server_join(self._worker_id))
                    if rc < 0:
                        raise RuntimeError(
                            f"local join failed (rc={rc})")
                else:
                    self._conn(sidx).join(self._worker_id)
                self.sync_rounds(sidx)
                self._note_epoch(sidx)
                joined.append(sidx)
            except Exception as e:  # noqa: BLE001 - mirror rejoin(): a
                # dead server must not block admission by the live
                # quorum; its own failover/recovery path owns it, and
                # its later recovery re-admits us via the eviction →
                # inline-rejoin handshake
                log.warning("join against server %d failed: %s: %s",
                            sidx, type(e).__name__, e)
        if not joined:
            raise NoLiveServersError(
                f"worker {self._worker_id} could not join any summation "
                "server")
        self._count("joins")
        self._trace_fault("join", servers=joined)
        log.info("worker %d joined mid-stream via server(s) %s",
                 self._worker_id, joined)
        return len(joined)

    # -- connection management ----------------------------------------------
    def _conn(self, sidx: int) -> NativeClient:
        pool = getattr(self._tls, "conns", None)
        if pool is None:
            pool = {}
            self._tls.conns = pool
        c = pool.get(sidx)
        if c is not None and c.is_dead():
            # a timeout/desync killed the socket (native side closes it so
            # no stale frame can be misread); evict so this thread's next
            # op reconnects instead of failing rc=-2 forever
            self._evict(sidx, c)
            c = None
        if c is None:
            if self._closed:
                raise RuntimeError("PSWorker is shut down")
            host, port = self._servers[sidx]
            c = NativeClient(host, port, self._timeout, self._recv_timeout)
            pool[sidx] = c
            with self._conn_lock:
                self._all_conns.append(c)
        return c

    def _evict(self, sidx: int, c: NativeClient) -> None:
        pool = getattr(self._tls, "conns", {})
        if pool.get(sidx) is c:
            del pool[sidx]
        with self._conn_lock:
            try:
                self._all_conns.remove(c)
            except ValueError:
                pass
        c.close()

    def _is_local(self, sidx: int) -> bool:
        return self._ipc and sidx == _INPROC_SERVER_ID

    # -- retry engine -------------------------------------------------------
    def _retry_loop(self, op: str, key: int, attempt_fn):
        """Drive ``attempt_fn(sidx) -> result`` under the per-op retry
        budget. Placement is re-resolved every attempt so a failover
        mid-retry lands on the survivor; an op whose key MOVED since the
        first attempt aborts with :class:`FailedOverError` (its round
        numbering died with the old server — the *stage* retry re-runs
        the whole op against the new placement, with a fresh version).

        Backoff: ``BYTEPS_RETRY_BACKOFF_MS`` × 2^attempt, capped at 2 s,
        with seeded jitter in [0.5, 1.0] — the standard exponential
        backoff + jitter that keeps a retry storm from re-synchronizing
        every worker onto the recovering server."""
        sidx0 = self.server_for(key)
        attempt = 0
        while True:
            with self._vlock:
                live = set(self._live)
                epoch = self._epoch
            if not live:
                raise NoLiveServersError(
                    f"{op} key {key}: every summation server is dead")
            sidx = self._server_for_live(key, live)
            if sidx != sidx0:
                raise FailedOverError(
                    f"{op} key {key}: placement moved {sidx0}->{sidx} "
                    f"(failover epoch {epoch}); round abandoned")
            m_att = self._m_attempts.get(op)
            if m_att is not None:
                m_att[0].inc()
                m_att[1].inc()
            try:
                result = attempt_fn(sidx)
                self._note_epoch(sidx)
                return result
            except BaseException as e:  # noqa: BLE001 - classified below
                self._note_epoch(sidx)
                if isinstance(e, WorkerEvictedError):
                    # the server refuses this worker until it rejoins:
                    # heartbeat re-admit + round-watermark adoption here,
                    # then escalate stage-retryably — the op's pinned
                    # round predates the adopted watermarks, so the stage
                    # re-run must mint afresh (push stages clear the pin
                    # on this error class)
                    log.warning(
                        "%s key %d refused: worker %d evicted; rejoining",
                        op, key, self._worker_id)
                    self.rejoin()
                    raise
                if (isinstance(e, RuntimeError) and "before init" in str(e)
                        and key in self._key_nbytes
                        and attempt < self._retry_limit):
                    # post-failover target has never seen this key:
                    # re-init from the recorded size and go again (init
                    # is idempotent server-side)
                    attempt += 1
                    self._count("reinits")
                    self._trace_fault("reinit", key=key, server=sidx)
                    self._conn(sidx).init_key(key, self._key_nbytes[key])
                    continue
                if not _is_retryable_wire_error(e):
                    raise
                if attempt >= self._retry_limit:
                    self._count("give_ups")
                    self._trace_fault("retry_exhausted", key=key, op=op,
                                      error=type(e).__name__)
                    raise
                attempt += 1
                if isinstance(e, TimeoutError):
                    self._count("timeouts")
                elif isinstance(e, WireCorruption):
                    self._count("crc_errors")
                else:
                    self._count("conn_errors")
                self._count("retries")
                self._trace_fault("retry", key=key, op=op, attempt=attempt,
                                  error=type(e).__name__)
                log.debug("%s key %d attempt %d failed (%s: %s); retrying",
                          op, key, attempt, type(e).__name__, e)
                backoff = min(self._backoff_ms * (2 ** (attempt - 1)), 2000)
                time.sleep(backoff * self._retry_rng.uniform(0.5, 1.0)
                           / 1e3)

    # -- owner-failover handoff (sharded-wire hierarchical mode) ------------
    def fence(self) -> None:
        """Refuse every future round mint on this worker. Set when its
        owner is declared dead, BEFORE ``export_rounds`` snapshots the
        counters: a push thread that resolved this owner pre-failover
        could otherwise mint a round AFTER the snapshot — invisible to
        the survivors' adopted counters, so the next round's re-mint of
        the same number would be dropped by the server's replay dedupe
        (silent stale gradient). The FailedOverError is stage-retryable:
        the re-run resolves ownership afresh and lands on a survivor."""
        with self._vlock:
            self._fenced = True

    def export_rounds(self) -> Tuple[Dict[int, int], Dict[int, int]]:
        """Snapshot (per-key round counters, per-key store sizes) — what a
        surviving controller adopts when this worker's owner dies."""
        with self._vlock:
            return dict(self._versions), dict(self._key_nbytes)

    def adopt_rounds(self, versions: Dict[int, int],
                     nbytes: Dict[int, int]) -> None:
        """Seed round counters/store sizes from a dead owner's worker.

        Owner failover differs from PR3's SERVER failover: the summation
        server — and its per-(worker, key) replay watermark — survives an
        owner death, so the surviving controller must CONTINUE the pod's
        round numbering (all of a pod's controllers push under the pod's
        worker_id). A fresh counter would mint versions at/below the
        server's watermark and every later round would be dropped as a
        replay. Adopting the max also keeps a round the dead owner had
        pushed-but-not-pulled replayable: the stage retry re-sends the
        pinned version through this worker and the dedupe recognizes it.
        """
        with self._vlock:
            for k, v in versions.items():
                if v > self._versions.get(k, 0):
                    self._versions[k] = v
            for k, nb in nbytes.items():
                self._key_nbytes.setdefault(k, nb)

    # -- data plane ---------------------------------------------------------
    def init_key(self, key: int, nbytes: int) -> None:
        with self._vlock:
            self._key_nbytes[key] = int(nbytes)
        sidx = self.server_for(key)
        if self._is_local(sidx):
            rc = load_lib().bps_local_init(key, nbytes)
            if rc != 0:
                raise RuntimeError(f"local init failed (rc={rc})")
            return

        def attempt(s):
            # 'init'/server-scoped rules only (down windows, init-ack
            # loss) — push/pull loss rules target the data plane proper
            inj = self._inject_pre("init", s)
            if inj is not None and inj.kind == "corrupt":
                inj = None  # nothing summable to corrupt in an init
            self._conn(s).init_key(key, nbytes)
            if inj is not None and inj.kind == "timeout":
                # the init WAS applied (and is idempotent); lose the ack
                # so the caller's retry/stage-retry path re-inits
                self._kill_conn(s)
                raise InjectedTimeout(
                    f"injected: init ack for key {key} lost (server {s})")

        self._retry_loop("init", key, attempt)

    def mint_version(self, key: int, pinned: Optional[int] = None) -> int:
        """Reserve the round number a push will carry, BEFORE the wire
        attempt — the push stages pin it on their task so a stage retry
        re-sends the SAME round even when the first attempt died before
        ``push_bytes`` could return it. That pin is what keeps the
        server's per-key round sequence gapless across an owner failover:
        the counter increments at mint time, so a push that never reached
        the server still consumed its round number, and a survivor that
        adopted this worker's counters would otherwise mint one PAST the
        round the server is still waiting for — a permanent stall (the
        server can't complete round v without v's push, and the pull for
        v+1 waits on v). Re-sending the pinned round is safe in both
        failure modes: never-applied → the server sums it as round v;
        applied-but-ack-lost → the (worker, key, version) dedupe drops
        it. A pin that exceeds the current counter (it predates a server
        failover's counter reset) is discarded and a fresh round minted,
        exactly like ``push_bytes``'s own rule."""
        with self._vlock:
            if self._fenced:
                raise FailedOverError(
                    f"owner worker fenced (failed over); re-resolve the "
                    f"owner for key {key}")
            cur = self._versions.get(key, 0)
            if pinned is None or pinned > cur:
                pinned = cur + 1
                self._versions[key] = pinned
            return pinned

    def push_bytes(self, key: int, buf: np.ndarray,
                   codec: int = WIRE_RAW,
                   version: Optional[int] = None) -> int:
        """Push codec-encoded bytes; returns the round number the matching
        pull must wait for. Retryable wire failures re-send the SAME
        (worker, key, version) — the server dedupes a replay whose
        original landed (the version-safe replay contract), so a lost
        *response* cannot double-sum the round.

        ``version`` pins the round across HIGHER-level retries (the
        scheduler's stage retry passes the version its first try minted):
        a push whose wire budget was exhausted AFTER the server applied it
        must re-send the same version, not mint a fresh one that the
        dedupe cannot recognize. A pinned version from before a failover
        (the per-key counter was reset, so it exceeds the counter) is
        discarded and a fresh round minted against the new placement."""
        with self._vlock:
            cur = self._versions.get(key, 0)
            if version is None or version > cur:
                version = cur + 1
                self._versions[key] = version
        b = np.ascontiguousarray(buf)
        crc = wire_crc32(b) if self._crc and not self._is_local(
            self.server_for(key)) else 0

        def attempt(sidx):
            if self.pacer is not None:
                # book the payload's transmission time on the emulated NIC
                # BEFORE the wire op (every re-send pays wire time again,
                # as it would on a real NIC); applies to the IPC path too:
                # colocated deployments being modeled still cross a NIC
                self.pacer.throttle_send(int(b.nbytes))
            if self._is_local(sidx):
                rc = load_lib().bps_local_push2(
                    self._worker_id, key, codec, version,
                    b.ctypes.data, b.nbytes,
                )
                if rc == -11:
                    raise WorkerEvictedError(
                        f"local push of key {key} rejected: worker "
                        f"{self._worker_id} evicted; rejoin required")
                if rc != 0:
                    raise RuntimeError(f"local push failed (rc={rc})")
                return
            inj = self._inject_pre("push", sidx)
            send = b
            if inj is not None and inj.kind == "corrupt":
                # CRC was computed on the pristine payload: the flipped
                # byte is detected server-side and NEVER summed
                send = b.copy()
                FaultPlan.corrupt(send.view(np.uint8).reshape(-1),
                                  inj.corrupt_at)
            self._conn(sidx).push(key, send, codec, self._worker_id,
                                  version, crc)
            if inj is not None and inj.kind == "timeout":
                # the push WAS applied; lose the ack (models a lost
                # response) — the retry's re-send exercises the dedupe
                self._kill_conn(sidx)
                raise InjectedTimeout(
                    f"injected: push ack for key {key} lost "
                    f"(server {sidx})")

        self._retry_loop("push", key, attempt)
        with self._vlock:
            self.bytes_pushed += int(b.nbytes)
        self._m_push_bytes.inc(int(b.nbytes))
        self._m_push_bytes_nic.inc(int(b.nbytes))
        self._m_push_size.observe(int(b.nbytes))
        return version

    def pull_bytes(self, key: int, capacity: int, version: int,
                   codec: int = WIRE_RAW) -> np.ndarray:
        """Pull the round result as codec-encoded bytes. Pull retries are
        naturally idempotent (the round snapshot is immutable)."""

        def attempt(sidx):
            out = np.empty(capacity, np.uint8)
            if self._is_local(sidx):
                import ctypes

                ep = ctypes.c_uint64(0)
                served = ctypes.c_uint64(0)
                got = load_lib().bps_local_pull3(
                    key, codec, version, self._recv_timeout,
                    out.ctypes.data, out.nbytes, ctypes.byref(ep),
                    ctypes.byref(served),
                )
                if got < 0:
                    raise RuntimeError(f"local pull failed (rc={got})")
                if self.pacer is not None:
                    self.pacer.throttle_recv(int(got))
                # same divisor contract as the TCP header stamp: the
                # epoch the returned ROUND closed under
                self._tls.round_live = self._live_at(
                    sidx, int(ep.value) & 0xFFFF)
                self._tls.round_served = int(served.value)
                return out, int(got)
            inj = self._inject_pre("pull", sidx)
            conn = self._conn(sidx)
            if self._crc:
                got, resp_crc = conn.pull(key, out, version, codec,
                                          want_crc=True,
                                          worker_id=self._worker_id)
            else:
                got, resp_crc = conn.pull(
                    key, out, version, codec,
                    worker_id=self._worker_id), 0
            if self.pacer is not None:
                # book the response's transmission time per ATTEMPT
                # (downstream direction): a lost/corrupted response still
                # crossed the emulated NIC, exactly like a re-sent push
                self.pacer.throttle_recv(int(got))
            if inj is not None:
                if inj.kind == "timeout":
                    self._kill_conn(sidx)
                    raise InjectedTimeout(
                        f"injected: pull response for key {key} lost "
                        f"(server {sidx})")
                if inj.kind == "corrupt" and got > 0:
                    FaultPlan.corrupt(out[:got], inj.corrupt_at)
            if resp_crc and wire_crc32(out[:got]) != resp_crc:
                raise WireCorruption(
                    f"pull response for key {key} failed CRC "
                    f"(server {sidx}); retrying")
            # the response header carries the epoch this ROUND closed
            # under — resolve the round's own live count (divisor
            # authority for averaging; the current epoch may be newer)
            self._tls.round_live = self._live_at(sidx,
                                                 conn.last_pull_epoch())
            self._tls.round_served = conn.last_pull_round()
            return out, int(got)

        out, got = self._retry_loop("pull", key, attempt)
        with self._vlock:
            self.bytes_pulled += got
        self._m_pull_bytes.inc(got)
        self._m_pull_bytes_nic.inc(got)
        # bounded-staleness telemetry: requested − served = how stale the
        # consumed aggregate was (0 on the strict-sync tier), and minted −
        # served = how far this worker's pipeline runs ahead of the round
        # it just consumed (≈ K when the window is full)
        served = getattr(self._tls, "round_served", None)
        if served is not None and version > 0:
            self._m_staleness.observe(max(0, int(version) - int(served)))
            with self._vlock:
                # Serve-AHEAD re-sync (staleness only): a straggler whose
                # rounds were force-closed past it gets served a NEWER
                # round than it asked for. Its mint counter must adopt
                # that round — its next push then targets the OPEN round
                # and rejoins the quorum, instead of minting ever-late
                # versions the server consumes silently forever (a
                # transient slowdown would otherwise exclude the worker
                # for the rest of the job). Max-merge, same contract as
                # adopt_rounds; in strict sync served == requested ≤ the
                # counter, so this is structurally a no-op there.
                if (self._staleness > 0
                        and int(served) > self._versions.get(key, 0)):
                    self._versions[key] = int(served)
                minted = self._versions.get(key, int(version))
            ahead = max(0, int(minted) - int(served))
            self._m_rounds_ahead.set(ahead)
            self._m_rounds_ahead_nic.set(ahead)
        return out[:got]

    def push(self, key: int, data: np.ndarray) -> int:
        """Push this worker's fp32 partition (raw wire)."""
        data = np.ascontiguousarray(data, dtype=np.float32)
        return self.push_bytes(key, data.view(np.uint8).ravel(), WIRE_RAW)

    def pull(self, key: int, nelems: int, version: int) -> np.ndarray:
        buf = self.pull_bytes(key, nelems * 4, version, WIRE_RAW)
        # view, not copy: pull_bytes allocated the buffer for this call, so
        # the caller owns it — the copy was a full extra pass per partition
        return buf.view(np.float32)

    def push_pull(self, key: int, data: np.ndarray) -> np.ndarray:
        v = self.push(key, data)
        return self.pull(key, data.size, v)

    def barrier(self) -> None:
        """Global worker barrier through the lowest LIVE server (server 0
        while healthy — reference: ps-lite Postoffice::Barrier via the
        scheduler; after a failover the survivors host it). Carries the
        worker id: a barrier wait can outlast a short membership lease,
        and the arrival itself refreshes it."""
        with self._vlock:
            sidx = min(self._live) if self._live else 0
        self._conn(sidx).barrier(self._worker_id)

    def ping(self, sidx: int = 0) -> Tuple[int, int]:
        """(server CLOCK_REALTIME ns, rtt ns) for clock alignment of merged
        worker/server traces (SURVEY §5.1 dPRO clock-offset capability).
        Also the health monitor's probe — injected down windows fail it —
        and, carrying the worker id, the membership lease HEARTBEAT (an
        evicted worker's ping re-admits it)."""
        self._inject_pre("ping", sidx)
        return self._conn(sidx).ping(self._worker_id)

    def clock_offset_ns(self, sidx: int = 0) -> int:
        """Estimated server_clock − local_clock in ns (RTT/2 method)."""
        import time

        server_ns, rtt = self.ping(sidx)
        return server_ns + rtt // 2 - time.time_ns()

    def close(self) -> None:
        """Drop every connection WITHOUT the kShutdown round. For the
        extra per-controller NICs of a sharded pod (DcnCore
        ``pod_controllers``): servers count shutdowns against
        DMLC_NUM_WORKER and all of a pod's controllers share the pod's
        worker id, so exactly one of them — worker 0's ``shutdown()`` —
        may say goodbye."""
        if self._closed:
            return
        self._closed = True
        if self._health is not None:
            self._health.stop(join=True)
        with self._conn_lock:
            conns = list(self._all_conns)
            self._all_conns.clear()
        for c in conns:
            c.close()
        self._tls.conns = {}

    def shutdown(self) -> None:
        """Tell every server this worker is done (server exits once all
        workers said so), then drop connections."""
        if self._closed:
            return
        self._closed = True
        if self._health is not None:
            # join (bounded by the monitor's short probe timeouts) BEFORE
            # tearing down: the monitor owns its probe connections, but a
            # fail_over it triggers mid-shutdown would race the teardown
            self._health.stop(join=True)
        self.export_counters()
        # one shutdown per server (not per connection): servers count
        # shutdowns against DMLC_NUM_WORKER. Use this thread's pool
        # (creating connections as needed), then close EVERY connection
        # ever created — snapshot taken after the shutdown round so none
        # created during it escape.
        pool = getattr(self._tls, "conns", {})
        for sidx in range(len(self._servers)):
            try:
                c = pool.get(sidx)
                if c is not None and c.is_dead():
                    c = None  # killed socket cannot carry the kShutdown —
                    # send it on a fresh connection or the server's
                    # shutdown count never completes and serve_forever hangs
                if c is None:
                    host, port = self._servers[sidx]
                    c = NativeClient(host, port, 2000, self._recv_timeout)
                    with self._conn_lock:
                        self._all_conns.append(c)
                # identified goodbye: the membership layer marks this
                # worker DEPARTED, so the server can exit even if a PEER
                # died without one (departed + evicted covers everyone)
                c.shutdown(self._worker_id)
            except Exception as e:  # noqa: BLE001 - server may already be
                # gone (it stops itself once every worker said shutdown,
                # and a chaos run may have killed it outright) — expected
                # enough not to warn, but never silent: the index says
                # WHICH server missed its shutdown count
                log.debug("shutdown of server %d failed: %s: %s",
                          sidx, type(e).__name__, e)
        with self._conn_lock:
            conns = list(self._all_conns)
            self._all_conns.clear()
        for c in conns:
            c.close()
        self._tls.conns = {}

    def get_counters(self) -> Dict[str, int]:
        """Robustness counters (+ per-kind injected counts when a fault
        plan is armed, + the health monitor's last-probe age and
        per-server miss counts so a stall report shows WHY failover did
        or did not fire) — what the chaos smokes assert on."""
        with self._counter_lock:
            out = dict(self.counters)
        out["live_pods"] = self.live_pods()
        if self._plan is not None:
            for k, v in self._plan.counters().items():
                out[f"injected_{k}"] = v
        if self._health is not None:
            out.update(self._health.debug_counters())
        return out

    def export_counters(self, tag: Optional[str] = None) -> None:
        """Fold the robustness counters into the chrome-trace metadata so
        a retry storm / failover is visible beside the dPRO timeline.
        Extra pod-controller NICs share the pod's worker id, so callers
        closing them pass a ``worker<id>.nic<rank>`` tag — the plain
        ``worker<id>`` key belongs to NIC 0's ``shutdown()``."""
        counters = self.get_counters()
        if any(counters.values()):
            get_tracer().metadata.setdefault("robustness", {})[
                tag or f"worker{self._worker_id}"] = counters
            # the flight recorder keeps the final per-NIC snapshot too:
            # after retire_nic closes this worker, the snapshot (incl.
            # injected_* and health-probe state, which have no
            # per-increment registry mirror) outlives the instance
            get_flight_recorder().record_event(
                "counters_export",
                {"tag": tag or f"worker{self._worker_id}",
                 "nic": self._nic_tag, "counters": counters})


class _HealthMonitor:
    """Marks servers dead after K consecutive missed heartbeats.

    Built on the kPing probe, but on the monitor's OWN connections with
    SHORT connect/recv timeouts (scaled to the probe interval): they are
    never shared with — or torn down by — the data plane, so a probe
    mid-flight during ``PSWorker.shutdown`` cannot race a freed native
    client, and a really-hung server costs one bounded probe, not the
    data plane's long recv timeout. ``miss_limit`` consecutive failures
    trigger :meth:`PSWorker.fail_over`. The reference analog is ps-lite's
    scheduler heartbeat (SURVEY §5.3); every worker monitors
    independently and the failover barrier aligns their live-set views.
    Injected ``server<N>`` fault windows fail the probe through the
    worker's plan (``_inject_pre('ping', ...)``).
    """

    def __init__(self, worker: "PSWorker", interval_ms: int,
                 miss_limit: int):
        self._worker = worker
        self._interval = max(1, interval_ms) / 1e3
        # probe timeout: generous vs the interval, small vs the data
        # plane's recv timeout
        self._probe_ms = max(500, 4 * interval_ms)
        self._miss_limit = miss_limit
        self._misses: Dict[int, int] = {}
        # debuggability (stall reports): per-server CUMULATIVE miss count
        # and the monotonic time of the last finished probe attempt.
        # _dbg_lock guards these against debug_counters() readers — a
        # stall report must never crash on "dict changed during
        # iteration" while the monitor records its first miss.
        self._total_misses: Dict[int, int] = {}
        self._last_probe: Dict[int, float] = {}
        self._dbg_lock = threading.Lock()
        self._m_misses = get_registry().counter("health.misses")
        self._conns: Dict[int, NativeClient] = {}
        self._stop_ev = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="bps-health", daemon=True)

    def debug_counters(self) -> Dict[str, int]:
        """Folded into PSWorker.get_counters(): per-server consecutive +
        cumulative miss counts and the age of the newest probe — a stall
        report then shows whether the monitor was even looking, and how
        close each server sat to the miss limit."""
        now = time.monotonic()
        out: Dict[str, int] = {}
        with self._dbg_lock:
            for sidx, n in sorted(self._misses.items()):
                out[f"health_consec_miss_s{sidx}"] = n
            for sidx, n in sorted(self._total_misses.items()):
                out[f"health_misses_s{sidx}"] = n
            if self._last_probe:
                age = now - max(self._last_probe.values())
                out["health_last_probe_age_ms"] = int(age * 1e3)
        return out

    def start(self) -> None:
        self._thread.start()

    def stop(self, join: bool = False) -> None:
        self._stop_ev.set()
        if join and self._thread.is_alive():
            # bounded: one probe + one bounded failover barrier, both on
            # probe timeouts (never the data plane's long recv timeout)
            self._thread.join(timeout=2 * self._probe_ms / 1e3 + 5.0)

    def _probe(self, sidx: int) -> None:
        self._worker._inject_pre("ping", sidx)
        c = self._conns.get(sidx)
        if c is None or c.is_dead():
            if c is not None:
                c.close()
            host, port = self._worker._servers[sidx]
            c = NativeClient(host, port, self._probe_ms, self._probe_ms)
            self._conns[sidx] = c
        # the probe doubles as this worker's membership lease HEARTBEAT
        # (and re-admits it after an eviction, e.g. a worker:hang window
        # that outlasted the lease)
        c.ping(self._worker._worker_id)

    def _run(self) -> None:
        try:
            while not self._stop_ev.wait(self._interval):
                if self._worker.is_wedged():
                    # a dead/wedged process heartbeats nothing: going
                    # silent here is exactly what lets the server lease
                    # evict this worker on schedule
                    continue
                for sidx in sorted(self._worker.live_servers()):
                    if self._stop_ev.is_set():
                        return
                    try:
                        self._probe(sidx)
                        with self._dbg_lock:
                            self._last_probe[sidx] = time.monotonic()
                            self._misses[sidx] = 0
                    except WorkerKilledError:
                        return  # injected process death: no more probes
                    except Exception as e:  # noqa: BLE001 - miss
                        self._m_misses.inc()
                        with self._dbg_lock:
                            self._last_probe[sidx] = time.monotonic()
                            n = self._misses.get(sidx, 0) + 1
                            self._misses[sidx] = n
                            self._total_misses[sidx] = (
                                self._total_misses.get(sidx, 0) + 1)
                        log.debug(
                            "heartbeat miss %d/%d for server %d (%s)",
                            n, self._miss_limit, sidx, e)
                        if n >= self._miss_limit:
                            self._fail_over(sidx)
        finally:
            for c in self._conns.values():
                c.close()

    def _fail_over(self, sidx: int) -> None:
        """Failover with a BOUNDED alignment barrier: the data-plane
        barrier waits on the worker's long recv timeout, which would hold
        this thread (and block a joining shutdown) for tens of seconds —
        use a dedicated probe-timeout connection instead, and accept that
        a laggard peer degrades the barrier to best-effort (fail_over's
        own barrier handling is best-effort already)."""
        if not self._worker.fail_over(sidx, barrier=False):
            return
        live = self._worker.live_servers()
        if not live:
            return
        try:
            host, port = self._worker._servers[min(live)]
            c = NativeClient(host, port, self._probe_ms, self._probe_ms)
            try:
                c.barrier()
            finally:
                c.close()
        except Exception as e:  # noqa: BLE001 - best-effort alignment
            log.warning("failover barrier (monitor) failed: %s", e)
