"""Multi-replica request routing with lease/epoch replica liveness.

The PR 5 elastic-membership layer taught this repo one lesson worth
repeating at the serving tier: **death is detected by silence, never by
exception identity**. The summation servers there lease every worker —
one silent past the lease is evicted, the membership epoch bumps, and
open work re-targets the live set. :class:`Router` mirrors exactly
those semantics over serve replicas:

* every completed ``Scheduler.step()`` is the replica's lease renewal
  (the serve analog of the push/pull/kPing heartbeat);
* a replica silent past ``serve_replica_lease_ms`` — crashed, wedged,
  or deterministically killed by a ``worker:kill`` fault rule — is
  EVICTED: the routing epoch bumps (stamped on every completed
  result), and its in-flight requests re-queue to the survivors;
* re-queued requests keep their committed tokens and recompute their
  KV on the survivor (the scheduler's recompute-on-resume path), so a
  greedy request's final output is bit-identical to an undisturbed run
  — failover moves work, never content (pinned in tests/test_serve.py
  under the deterministic ``worker:kill`` fault scope).

Dispatch is least-loaded over the live set. The router is
single-threaded by design (one ``run()`` loop steps every replica
round-robin): replica parallelism in a real deployment is process- or
host-level, and this in-process form is what the tests and the chaos
pins drive deterministically.

The router also closes the scale-UP loop (docs/robustness.md §scale-up
elasticity): construct it with a
:class:`~byteps_tpu.common.autoscaler.ScalingPolicy` and a ``spawn``
callback and it runs one policy tick per step — the SAME policy class
that drives train-worker admission observes per-replica queue depth +
TTFT-SLO pressure, spawns replicas on ``admit`` and drains the
least-loaded one on ``evict``; every decision (the lease sweep's
evictions included) flows through the shared ``autoscaler.decisions``
event path, so train and serve share one elasticity story.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from byteps_tpu.common.autoscaler import (
    ScalingPolicy,
    record_decision,
    serve_sample,
)
from byteps_tpu.common.config import get_config
from byteps_tpu.common.faults import WorkerKilledError
from byteps_tpu.common.flight_recorder import get_flight_recorder
from byteps_tpu.common.logging import get_logger
from byteps_tpu.common.metrics import get_registry
from byteps_tpu.serve.scheduler import Request, Scheduler

log = get_logger("serve.router")


class NoLiveReplicasError(RuntimeError):
    """Every replica is dead or evicted — nothing can serve."""


class Router:
    """Lease/epoch routing over a set of :class:`Scheduler` replicas."""

    def __init__(self, replicas: List[Scheduler],
                 lease_ms: Optional[int] = None,
                 clock=time.monotonic,
                 policy: Optional[ScalingPolicy] = None,
                 spawn: Optional[Callable[[], Scheduler]] = None,
                 ttft_slo_ms: Optional[float] = None,
                 prefill_replicas: Optional[List[Scheduler]] = None,
                 wire_mbps: Optional[float] = None,
                 wire_credit: Optional[int] = None,
                 prompt_threshold: Optional[int] = None,
                 migrate_preempt: Optional[bool] = None,
                 kv_target_wrap: Optional[Callable[[Scheduler], Any]]
                 = None):
        """``policy``/``spawn`` arm replica AUTOSCALING: the same
        :class:`~byteps_tpu.common.autoscaler.ScalingPolicy` class that
        drives train-worker admit/evict observes per-replica queue depth
        (+ TTFT-SLO pressure when ``ttft_slo_ms`` is set, off the
        ``serve.ttft_ms`` histogram, WINDOWED per tick — see
        :meth:`_autoscale`) once per :meth:`step`; an ``admit`` spawns a
        replica via ``spawn()``, an ``evict`` DRAINS the least-loaded
        one (its unfinished requests re-queue to the survivors — the
        lease-eviction mechanics, minus the death). A policy without a
        ``spawn`` callback — or one allowed to evict the last replica —
        would RECORD decisions the router cannot execute (phantom
        admits in the post-mortem, cooldowns armed for nothing), so
        both are rejected up front.

        ``prefill_replicas`` arms DISAGGREGATION (docs/serving.md
        §disaggregation): dedicated ``role="prefill"`` replicas whose
        finished KV blocks stream to a decode target over per-replica
        :class:`~byteps_tpu.serve.kv_wire.KVWire` NICs (token-bucket
        paced at ``wire_mbps`` ≡ ``BYTEPS_SERVE_DISAGG_MBPS``).
        Admission classifies on prompt length × decode-pool pressure:
        inputs of ``prompt_threshold``+ tokens (the knee shrinks 4×
        when the decode pools run ≤25% free) route to the prefill tier
        and MIGRATE to their decode target as their blocks commit;
        shorter prompts prefill in place on a decode replica (one cheap
        chunk beats a migration round-trip). ``migrate_preempt``
        additionally turns pool-pressure preemption into
        migrate-don't-evict wherever ≥2 decode replicas live: the
        victim's committed blocks MOVE to the roomiest sibling instead
        of being freed and recomputed.

        ``kv_target_wrap`` swaps the migration wire's DELIVERY surface:
        the wrap maps a resolved decode Scheduler to whatever should
        receive its ``ingest_block`` calls — e.g. a
        :class:`~byteps_tpu.serve.kv_socket.SocketKVTarget` so the
        block bytes cross a real TCP link. Only the resolve callback
        handed to :class:`~byteps_tpu.serve.kv_wire.KVWire` is wrapped;
        the router's own adoption bookkeeping (``staged_blocks``/
        ``pop_staged``/``submit_migrated``) still talks to the local
        scheduler object."""
        if not replicas:
            raise ValueError("router needs at least one replica")
        if policy is not None:
            if spawn is None:
                raise ValueError(
                    "a Router policy needs a spawn callback: the policy "
                    "records every decision it makes, and an admit the "
                    "router cannot execute would be a phantom event")
            if policy.min_units < 1:
                raise ValueError(
                    "Router policy min_units must be >= 1: the router "
                    "cannot drain its last replica")
        c = get_config()
        self.replicas = list(replicas) + list(prefill_replicas or [])
        self._prefill_ids = set(range(len(replicas), len(self.replicas)))
        for i in self._prefill_ids:
            if self.replicas[i].role != "prefill":
                raise ValueError(
                    f"prefill_replicas[{i - len(replicas)}] has role "
                    f"{self.replicas[i].role!r} — construct it with "
                    "Scheduler(..., role='prefill')")
        self.lease_ms = lease_ms if lease_ms is not None \
            else c.serve_replica_lease_ms
        self._clock = clock
        now = clock()
        self._beat: Dict[int, float] = {i: now
                                        for i in range(len(self.replicas))}
        self._live = set(range(len(self.replicas)))
        # -- disaggregation / migration plane -------------------------------
        self._wire_mbps = wire_mbps if wire_mbps is not None \
            else c.serve_disagg_mbps
        self._wire_credit = wire_credit if wire_credit is not None \
            else c.serve_disagg_credit
        self._prompt_threshold = prompt_threshold \
            if prompt_threshold is not None \
            else c.serve_disagg_prompt_threshold
        self._migrate_preempt = migrate_preempt \
            if migrate_preempt is not None else c.serve_disagg_migrate
        # rid -> decode-target index; re-resolved (remapped) when the
        # target dies — read by KVWire PUSH threads, hence the lock
        self._mig_lock = threading.Lock()
        self._assignment: Dict[Any, int] = {}
        # rid -> in-flight migration: ticket, source index, whether the
        # source still PINS the blocks (prefill handoff) or already
        # freed them (migrate-out), full payload store, per-block wire
        # handles. The payload store is the retransmit source: a dead
        # target mid-migration costs a re-send, never the request.
        self._migrations: Dict[Any, Dict[str, Any]] = {}
        self._stream_store: Dict[Any, Dict[int, Any]] = {}
        self._stream_handles: Dict[Any, Dict[int, Any]] = {}
        self._stream_src: Dict[Any, int] = {}
        self._wires: Dict[int, Any] = {}
        self._kv_target_wrap = kv_target_wrap
        if self._prefill_ids or (self._migrate_preempt
                                 and len(self.replicas) > 1):
            # every migration-capable replica must share one pool
            # layout — the wire codec frames the pool's own bytes, so a
            # mismatch is a construction error, not a retryable one.
            # Duck-typed test stubs without a pool sit the check (and
            # the migrate hooks) out.
            keys = {i: self._codec_key(self.replicas[i])
                    for i in range(len(self.replicas))
                    if hasattr(self.replicas[i], "cache")}
            if len(set(keys.values())) > 1:
                raise ValueError(
                    "migration needs every replica on one pool layout "
                    f"(block_size, kv shape, dtype, quant); got {keys}")
        for i in self._prefill_ids:
            self.replicas[i].stream_blocks = self._make_stream_cb(i)
        if self._migrate_preempt:
            for i in range(len(self.replicas)):
                if i not in self._prefill_ids \
                        and hasattr(self.replicas[i], "cache"):
                    self.replicas[i].migrate_out = self._migrate_out
        self.epoch = 0
        self.results: Dict[Any, Dict[str, Any]] = {}
        self._policy = policy
        self._spawn = spawn
        self._ttft_slo_ms = ttft_slo_ms
        # (count, sum) of serve.ttft_ms at the previous autoscale tick:
        # SLO pressure is computed over the DELTA, not the process-
        # lifetime histogram — a cold-start spike must stop inflating
        # the load signal as soon as fresh traffic is healthy
        self._ttft_mark = (0, 0.0)
        _reg = get_registry()
        self._m_dispatch = _reg.counter("serve.router.dispatched")
        self._m_evict = _reg.counter("serve.router.evictions")
        self._m_requeued = _reg.counter("serve.router.requeued")
        self._m_mig_done = _reg.counter("serve.migration.adopted")
        self._m_mig_fallback = _reg.counter(
            "serve.migration.fallback_recompute")
        self._m_mig_retarget = _reg.counter("serve.migration.retargets")
        self._g_epoch = _reg.gauge("serve.router.epoch")
        self._g_live = _reg.gauge("serve.router.live_replicas")
        self._h_ttft = _reg.histogram("serve.ttft_ms")
        self._g_live.set(len(self._live))

    # -- dispatch -----------------------------------------------------------
    def live_replicas(self) -> List[int]:
        return sorted(self._live)

    def _live_decode(self) -> List[int]:
        return [i for i in sorted(self._live)
                if i not in self._prefill_ids]

    def _live_prefill(self) -> List[int]:
        return [i for i in sorted(self._live) if i in self._prefill_ids]

    def _effective_threshold(self) -> int:
        """Prompt-length classification knee, scaled by decode-pool
        pressure: when the decode tier runs low on (free + reclaimable)
        blocks, even shorter prompts are worth shipping to the prefill
        tier — their prefill would otherwise land ON the pressured
        pools and force preemptions there."""
        thr = self._prompt_threshold
        dec = self._live_decode()
        if not dec:
            return thr
        frac = min((self.replicas[i].cache.free_blocks
                    + self.replicas[i].cache.reclaimable_blocks())
                   / max(1, self.replicas[i].cache.pool_blocks - 1)
                   for i in dec)
        return max(1, thr // 4) if frac <= 0.25 else thr

    def _pick_decode_for(self, req: Request, dec: List[int]) -> int:
        """Least-loaded live decode replica, with ADAPTER AFFINITY for
        tenanted requests: replicas whose adapter pool already holds
        the request's adapter RESIDENT win first (admission's acquire
        is then a residency hit — no host->device slab load on the
        critical path), then replicas that at least have it registered
        (reloadable from their host registry); plain least-loaded
        otherwise. Ties always break by load then index."""
        # getattr: router duck-types requests/replicas (stub schedulers
        # in the autoscaling tests predate the adapter surface)
        adapter = getattr(req, "adapter", None)
        if adapter is not None:
            def _pool(i):
                return getattr(self.replicas[i], "adapter_pool", None)
            warm = [i for i in dec
                    if _pool(i) is not None
                    and _pool(i).resident(adapter)]
            if warm:
                return min(warm,
                           key=lambda i: (self.replicas[i].load, i))
            able = [i for i in dec
                    if _pool(i) is not None
                    and _pool(i).registered(adapter)]
            if able:
                return min(able,
                           key=lambda i: (self.replicas[i].load, i))
        return min(dec, key=lambda i: (self.replicas[i].load, i))

    def submit(self, req: Request,
               resume_tokens: Optional[List[int]] = None) -> int:
        """Route to the least-loaded live replica; returns its index.
        With the prefill tier armed, admissions classify on prompt
        length × decode-pool pressure: long inputs go to a prefill
        replica (their decode target reserved now, streamed to as
        blocks commit), short ones prefill in place on a decode
        replica. With every prefill replica dead the tier degrades to
        colocated routing — decode replicas can always prefill.
        Adapter-tagged requests add pool affinity (see
        :meth:`_pick_decode_for`); they only classify to a prefill
        replica that can graft their adapter."""
        dec = self._live_decode()
        if not dec:
            raise NoLiveReplicasError(
                "no live decode-capable replica to route to")
        pre = self._live_prefill()
        if pre and getattr(req, "adapter", None) is not None:
            pre = [i for i in pre
                   if (getattr(self.replicas[i], "adapter_pool", None)
                       is not None
                       and self.replicas[i].adapter_pool.registered(
                           req.adapter))]
        if pre:
            n_in = (np.asarray(req.prompt).size
                    + len(resume_tokens or ()))
            if n_in >= self._effective_threshold():
                target = min(pre,
                             key=lambda i: (self.replicas[i].load, i))
                self.replicas[target].submit(
                    req, resume_tokens=resume_tokens)
                # decode target reserved only AFTER the prefill replica
                # accepted the request — a rejected submit must not
                # leave a phantom pending assignment skewing future
                # target picks
                with self._mig_lock:
                    self._assignment[req.rid] = \
                        self._pick_decode_locked(dec)
                self._m_dispatch.inc()
                return target
        target = self._pick_decode_for(req, dec)
        self.replicas[target].submit(req, resume_tokens=resume_tokens)
        self._m_dispatch.inc()
        return target

    # -- liveness -----------------------------------------------------------
    def step(self) -> bool:
        """Step every live replica once (its completed step renews the
        lease), then sweep expired leases. Returns True when any
        replica made progress."""
        progress = False
        completed = []
        for i in sorted(self._live):
            sched = self.replicas[i]
            try:
                if sched.step():
                    progress = True
                completed.append(i)
            except WorkerKilledError:
                # a dead replica renews nothing — eviction happens by
                # silence in sweep(), exactly like a real crash (the
                # PR 5 lease philosophy: no exception-identity paths)
                pass
        # renew every completed step at the SAME post-round timestamp:
        # this harness steps replicas serially, so a sibling's slow step
        # (first-call jit compile) must not age a healthy replica's
        # lease — a replica that completed its step this round is alive
        # NOW. Only true silence (kill/crash/wedge) accumulates.
        now = self._clock()
        for i in completed:
            self._beat[i] = now
        self._collect()
        self.sweep()
        if self._migrations or self._prefill_ids:
            if self._pump_migrations():
                progress = True
        self._autoscale()
        return progress

    def sweep(self) -> None:
        """Evict replicas silent past the lease: epoch bump + re-queue
        of their entire unfinished load onto the survivors. A dead
        PREFILL replica's load re-classifies through ``submit`` (a
        surviving prefill sibling, else colocated on the decode tier);
        handoffs it was mid-migration on are cancelled — their runs
        ride the drain — while migrate-OUT transfers it sourced keep
        going (the payload store and wire outlive the source's lease)."""
        now = self._clock()
        expired = [i for i in sorted(self._live)
                   if (now - self._beat[i]) * 1e3 > self.lease_ms]
        for i in expired:
            self._live.discard(i)
            self._cancel_sourced_migrations(i)
            self.epoch += 1
            self._m_evict.inc()
            self._g_epoch.set(self.epoch)
            self._g_live.set(len(self._live))
            incomplete = self.replicas[i].drain_incomplete()
            get_flight_recorder().record_event(
                "serve.replica_evicted",
                {"replica": i, "epoch": self.epoch,
                 "requeued": len(incomplete)})
            # the ONE shared decision path (common/autoscaler.py): lease
            # evictions and policy decisions land in the same counters/
            # FAULT instants, so a post-mortem shows WHY a replica left
            record_decision(
                "serve", "evict",
                f"lease-expired ({self.lease_ms} ms silent)",
                target=i, live=len(self._live))
            log.warning(
                "serve router: replica %d lease expired (epoch -> %d), "
                "re-queueing %d request(s)", i, self.epoch,
                len(incomplete))
            for req, emitted in incomplete:
                if not self._live:
                    raise NoLiveReplicasError(
                        f"replica {i} died holding {len(incomplete)} "
                        "request(s) and no survivor remains")
                self.submit(req, resume_tokens=emitted)
                self._m_requeued.inc()

    # -- replica autoscaling (common/autoscaler.py) --------------------------
    def add_replica(self, sched: Scheduler) -> int:
        """Bring a freshly spawned replica into the routing set (the
        serve-side JOIN: epoch bump so results stamp the new topology,
        lease seeded now). Returns its index."""
        self.replicas.append(sched)
        i = len(self.replicas) - 1
        if (self._migrate_preempt and hasattr(sched, "cache")
                and getattr(sched, "role", "both") != "prefill"):
            sched.migrate_out = self._migrate_out
        self._beat[i] = self._clock()
        self._live.add(i)
        self.epoch += 1
        self._g_epoch.set(self.epoch)
        self._g_live.set(len(self._live))
        log.info("serve router: replica %d admitted (epoch -> %d)", i,
                 self.epoch)
        return i

    def drain_replica(self, i: int) -> int:
        """Voluntarily retire replica ``i``: remove it from the live set
        (epoch bump) and re-queue its unfinished requests onto the
        survivors — the lease-eviction mechanics without the death, so
        drained requests keep their committed tokens (recompute-on-
        resume). Returns how many requests moved. The CALLER records the
        decision (policy evictions already did via ``observe``)."""
        if i not in self._live:
            raise ValueError(f"replica {i} is not live")
        if len(self._live) <= 1:
            raise NoLiveReplicasError(
                f"cannot drain replica {i}: it is the last live replica")
        if (i not in self._prefill_ids
                and len(self._live_decode()) <= 1):
            raise NoLiveReplicasError(
                f"cannot drain replica {i}: it is the last live "
                "decode-capable replica")
        self._live.discard(i)
        self._cancel_sourced_migrations(i)
        self.epoch += 1
        self._g_epoch.set(self.epoch)
        self._g_live.set(len(self._live))
        incomplete = self.replicas[i].drain_incomplete()
        for req, emitted in incomplete:
            self.submit(req, resume_tokens=emitted)
            self._m_requeued.inc()
        log.info(
            "serve router: replica %d drained (epoch -> %d), "
            "%d request(s) re-queued", i, self.epoch, len(incomplete))
        return len(incomplete)

    def _autoscale(self) -> None:
        """One policy tick per router step: observe per-replica queue
        depth (+ TTFT-SLO pressure over the ticks' DELTA of the
        ``serve.ttft_ms`` histogram — the registry histogram is
        process-cumulative, and a lifetime p99 would carry a cold-start
        spike forever; the windowed mean resets with the traffic) and
        execute the decision."""
        if self._policy is None:
            return
        depth = sum(self.replicas[i].load for i in self._live)
        snap = self._h_ttft.snapshot()
        count = int(snap.get("count", 0))
        total = float(snap.get("sum", 0.0))
        dc = count - self._ttft_mark[0]
        ds = total - self._ttft_mark[1]
        self._ttft_mark = (count, total)
        ttft_ms = ds / dc if dc > 0 else 0.0
        d = self._policy.observe(serve_sample(
            live=len(self._live), queue_depth=depth,
            ttft_p99_ms=ttft_ms,
            ttft_slo_ms=self._ttft_slo_ms))
        if d.action == "admit":
            self.add_replica(self._spawn())
        elif d.action == "evict":
            # drain the LEAST-loaded live DECODE replica (cheapest to
            # move; the prefill tier is not the policy's to shrink);
            # ties break toward the newest index
            dec = self._live_decode()
            if len(dec) > 1:
                target = min(sorted(dec, reverse=True),
                             key=lambda i: self.replicas[i].load)
                self.drain_replica(target)

    # -- KV migration plane (serve/kv_wire.py, docs/serving.md) -------------
    @staticmethod
    def _codec_key(sched: Scheduler):
        st = sched.cache.state
        return (sched.cache.block_size, sched.cache.quant,
                st.k.shape[0], st.k.shape[2:], str(st.k.dtype))

    def _wire_for(self, i: int):
        """The source replica's outbound migration NIC (lazy: colocated
        routers never build one)."""
        w = self._wires.get(i)
        if w is None:
            from byteps_tpu.serve.kv_wire import KVWire

            resolve = self._resolve_target
            if self._kv_target_wrap is not None:
                # wrap ONLY the wire's delivery surface — adoption
                # bookkeeping elsewhere still needs the local object
                wrap = self._kv_target_wrap

                def resolve(rid, _r=self._resolve_target, _w=wrap):
                    t = _r(rid)
                    return None if t is None else _w(t)

            w = KVWire(self.replicas[i].kv_codec, resolve,
                       mbps=self._wire_mbps, credit=self._wire_credit)
            self._wires[i] = w
        return w

    def _pick_decode_locked(self, dec: List[int]) -> int:
        """Least-loaded live decode replica, counting PENDING migration
        assignments as load — a decode replica's `.load` only moves at
        adoption, so without this every concurrent migration would pile
        onto one target. Callers hold ``_mig_lock``."""
        pending: Dict[int, int] = {}
        for t in self._assignment.values():
            pending[t] = pending.get(t, 0) + 1
        return min(dec, key=lambda i: (self.replicas[i].load
                                       + pending.get(i, 0), i))

    def _resolve_target(self, rid):
        """The CURRENT decode target for a migrating rid — called by
        KVWire PUSH threads per delivery attempt, so a dead target is a
        remap (the stage retry lands on the live sibling), never a
        loss. Returns None when no decode-capable replica lives (the
        push retries until the autoscaler/operator brings one back or
        the retry budget trips — the payload store re-sends either
        way), and for rids with no ACTIVE migration/stream: a straggler
        push task whose migration was cancelled (dead source) or whose
        request already completed must die quietly, not resurrect an
        assignment and stage orphan payloads nobody will reclaim."""
        with self._mig_lock:
            t = self._assignment.get(rid)
            if t is not None and t in self._live \
                    and t not in self._prefill_ids:
                return self.replicas[t]
            if (t is None and rid not in self._migrations
                    and rid not in self._stream_src):
                return None
            dec = self._live_decode()
            if not dec:
                return None
            nt = self._pick_decode_locked(dec)
            if t is not None:
                self._m_mig_retarget.inc()
                get_flight_recorder().record_event(
                    "serve.migration.retarget",
                    {"rid": str(rid), "from": t, "to": nt})
            self._assignment[rid] = nt
            return self.replicas[nt]

    def _make_stream_cb(self, i: int):
        """Prefill replica ``i``'s block-commit hook: every newly full
        block goes onto the wire NOW (overlapping the next chunk's
        compute) and into the payload store (the retransmit source
        until adoption)."""
        def cb(sched, run, payloads):
            rid = run.req.rid
            wire = self._wire_for(i)
            store = self._stream_store.setdefault(rid, {})
            handles = self._stream_handles.setdefault(rid, {})
            self._stream_src[rid] = i
            for bi, p in payloads.items():
                store[bi] = p
                handles[bi] = wire.send_block(rid, bi, p)
        return cb

    def _migrate_out(self, sched: Scheduler, run) -> bool:
        """Migrate-don't-evict: scheduler ``sched`` is about to preempt
        ``run`` — move its committed blocks to the roomiest live
        sibling instead, when one can hold them. Returns False (the
        classic evict proceeds) when no sibling fits or the wire is
        not armed."""
        src = self.replicas.index(sched)
        need = sched.cache.blocks_for(run.cache_len + 1)
        with self._mig_lock:
            sibs = [i for i in self._live_decode()
                    if i != src and self.replicas[i].cache.free_blocks
                    + self.replicas[i].cache.reclaimable_blocks()
                    >= need]
            if not sibs:
                return False
            target = max(sibs,
                         key=lambda i: self.replicas[i].cache.free_blocks
                         - self.replicas[i].load)
            rid = run.req.rid
            self._assignment[rid] = target
        ticket = sched.extract_for_migration(rid)
        wire = self._wire_for(src)
        handles = {bi: wire.send_block(rid, bi, p)
                   for bi, p in ticket.payloads.items()}
        self._migrations[rid] = {
            "ticket": ticket, "source": src, "src_holds": False,
            "payloads": dict(ticket.payloads), "handles": handles}
        get_flight_recorder().record_event(
            "serve.migration.start",
            {"rid": str(rid), "kind": "preempt", "from": src,
             "to": target, "blocks": ticket.n_blocks})
        return True

    def _begin_handoff(self, src: int, ticket) -> None:
        rid = ticket.req.rid
        payloads = self._stream_store.pop(rid, {})
        payloads.update(ticket.payloads)
        handles = self._stream_handles.pop(rid, {})
        self._stream_src.pop(rid, None)
        wire = self._wire_for(src)
        for bi, p in ticket.payloads.items():
            handles[bi] = wire.send_block(rid, bi, p)
        self._migrations[rid] = {
            "ticket": ticket, "source": src, "src_holds": True,
            "payloads": payloads, "handles": handles}
        get_flight_recorder().record_event(
            "serve.migration.start",
            {"rid": str(rid), "kind": "handoff", "from": src,
             "blocks": ticket.n_blocks})

    def _cancel_sourced_migrations(self, i: int) -> None:
        """Source replica ``i`` left the live set: its HANDOFF
        migrations cancel (the parked runs ride its drain and
        re-classify — recompute, the pre-migration behavior), while
        migrate-OUT transfers keep going: their blocks were already
        extracted, and the payload store + wire outlive the source."""
        gone = [r for r, m in self._migrations.items()
                if m["source"] == i and m["src_holds"]]
        # mid-prefill streams from the dead source cancel the same way
        # (their runs re-classify through the drain, recompute clean)
        gone += [r for r, s in self._stream_src.items()
                 if s == i and r not in gone]
        for rid in gone:
            self._migrations.pop(rid, None)
            self._stream_store.pop(rid, None)
            self._stream_handles.pop(rid, None)
            self._stream_src.pop(rid, None)
            with self._mig_lock:
                t = self._assignment.pop(rid, None)
            if t is not None and t < len(self.replicas):
                self.replicas[t].drop_staged(rid)

    def _pump_migrations(self) -> bool:
        """One migration tick: collect fresh prefill handoffs, then
        push every pending migration forward (re-send what failed or
        landed on a since-dead target; adopt once the target staged the
        full block set). Returns True when anything moved."""
        progress = False
        for i in self._live_prefill():
            for ticket in self.replicas[i].pop_handoffs():
                self._begin_handoff(i, ticket)
                progress = True
        for rid in list(self._migrations):
            if self._advance_migration(rid):
                progress = True
        return progress

    def _advance_migration(self, rid) -> bool:
        m = self._migrations[rid]
        ticket = m["ticket"]
        target = self._resolve_target(rid)
        if target is None:
            return False          # no decode tier right now; keep waiting
        wire = self._wire_for(m["source"])
        waiting = False
        for bi in range(ticket.n_blocks):
            h = m["handles"].get(bi)
            if h is not None and h.failed():
                cause = getattr(h.error(), "cause", None)
                if cause is not None and not getattr(
                        cause, "retryable", True):
                    # layout mismatch or similar construction bug:
                    # re-sending the same bytes can never fix it —
                    # surface it instead of looping on the wire
                    raise RuntimeError(
                        f"KV migration for {rid!r} failed terminally: "
                        f"{cause}") from cause
                # retry budget exhausted (e.g. every attempt hit a dead
                # target before the remap): re-send from the payload
                # store as a fresh task
                wire.abandon(1)
                h = None
            if h is None:
                m["handles"][bi] = wire.send_block(rid, bi,
                                                   m["payloads"][bi])
                waiting = True
            elif not h.done():
                waiting = True
        if waiting:
            return False
        staged = target.staged_blocks(rid)
        missing = [bi for bi in range(ticket.n_blocks)
                   if bi not in staged]
        if missing:
            # delivered to a target that died before adoption — the
            # payload store re-sends to the current one
            for bi in missing:
                m["handles"][bi] = wire.send_block(rid, bi,
                                                   m["payloads"][bi])
            return True
        ok = target.submit_migrated(ticket, target.pop_staged(rid))
        if ok:
            self._m_mig_done.inc()
            if m["src_holds"]:
                self.replicas[m["source"]].finish_handoff(rid)
            get_flight_recorder().record_event(
                "serve.migration.adopted",
                {"rid": str(rid), "blocks": ticket.n_blocks})
        else:
            # the target cannot hold it even after preemption: fall
            # back to recompute-on-resume — slower, never wrong
            self._m_mig_fallback.inc()
            get_flight_recorder().record_event(
                "serve.migration.fallback",
                {"rid": str(rid), "blocks": ticket.n_blocks})
            if m["src_holds"]:
                self.replicas[m["source"]].finish_handoff(rid)
            target.submit(ticket.req, resume_tokens=ticket.emitted)
        del self._migrations[rid]
        with self._mig_lock:
            self._assignment.pop(rid, None)
        return True

    def close(self) -> None:
        """Tear down the migration wires (their stage pools own
        threads); idempotent, and a colocated router has nothing to
        do."""
        for w in self._wires.values():
            w.shutdown()
        self._wires.clear()

    def _collect(self) -> None:
        """DRAIN newly completed results up to the router (stamped with
        the epoch they completed under, like PR 5's response headers).
        Popping — not copying — keeps each replica's results dict and
        this loop sized by new completions, not lifetime traffic."""
        for i, sched in enumerate(self.replicas):
            while sched.results:
                rid, res = sched.results.popitem()
                res = dict(res)
                res["epoch"] = self.epoch
                res["replica"] = i
                self.results[rid] = res
                if self._prefill_ids or self._migrations:
                    # a cancelled/retargeted migration can strand
                    # staged host payloads for this rid — reclaim them
                    # now that the request is done
                    with self._mig_lock:
                        self._assignment.pop(rid, None)
                    for other in self.replicas:
                        other.drop_staged(rid)

    # -- convenience --------------------------------------------------------
    def finished(self, rids) -> bool:
        return all(r in self.results for r in rids)

    def run(self, requests: List[Request],
            max_idle_iters: int = 10000) -> Dict[Any, Dict[str, Any]]:
        """Dispatch ``requests`` (arrival-ordered) and drive the replica
        set until every one completes. Requests whose ``arrival_s`` is
        in the future are held back and dispatched on time — continuous
        admission, not a batch."""
        pending = sorted(requests, key=lambda r: r.arrival_s)
        rids = [r.rid for r in requests]
        idle = 0
        idle_since = None
        while not self.finished(rids):
            now = self._clock()
            while pending and pending[0].arrival_s <= now:
                self.submit(pending.pop(0))
            if self.step():
                idle = 0
                idle_since = None
            else:
                idle += 1
                if idle_since is None:
                    idle_since = self._clock()
                # idle wall time is what expires a dead replica's lease
                # — spinning without sleeping would burn the iteration
                # budget before the silence gets long enough to matter.
                # The per-step sleep is capped at 50 ms (a huge lease
                # must not turn one idle step — e.g. waiting on an
                # in-flight KV migration — into a multi-second stall);
                # the no-progress abort is therefore WALL-CLOCK gated
                # past twice the lease, so a dead replica always gets
                # evicted before the loop gives up, whatever the lease
                time.sleep(min(0.05, max(1e-4, self.lease_ms / 20e3)))
                if (idle > max_idle_iters
                        and (self._clock() - idle_since) * 1e3
                        > 2 * self.lease_ms):
                    raise RuntimeError(
                        "router made no progress with "
                        f"{len(rids) - len(self.results)} request(s) "
                        "outstanding")
        return self.results
