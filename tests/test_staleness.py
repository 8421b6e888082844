"""Bounded-staleness PS rounds (BYTEPS_STALENESS, docs/robustness.md
§bounded staleness) — plus the BYTEPS_ENABLE_ASYNC pins it brackets.

Tier-1: the served-round/force-close golden (a choreographed 2-worker
ladder: stale serves are stamped with the round they came from, a pull
past the bound closes the straggler-held round quorum-SCALED over its
contributors, and the straggler's late push is consumed silently); the
K=0 ≡ synchronous-tier bit-identity pin (the ROADMAP item 3 equivalence
requirement); the scheduler's per-key rounds window (round r+K+1 holds
until round r finishes, sibling keys unaffected); the DcnCore straggler
SMOKE (K=1, ``worker1:slow`` — every round completes at the fast
worker's pace, served-round staleness is observed in the registry, zero
credit leak); the async-mode bounds/liveness validation regression (the
server.cc satellite bugfix, TCP path); the 2-worker ASYNC convergence
pin (async = the K=inf limit — it never had a dedicated test); and the
K∈{1,4} vs K=0 small-model loss-curve envelope (staleness converges
into a bounded neighborhood, K=0 converges exactly).

Goodput under a straggler (K≥1 tracking the median worker, K=0 the
slowest) is not measured: no benchmark cell runs the DCN tier; the
straggler smoke above holds the behaviour by counts.
"""

import threading
import time
from collections import deque

import numpy as np
import pytest

from byteps_tpu.common.metrics import get_registry
from byteps_tpu.server import (
    PSWorker,
    WorkerEvictedError,
    start_server,
    stop_server,
)
from byteps_tpu.server.native import NativeClient

BASE_PORT = 25600


@pytest.fixture(autouse=True)
def _cleanup_server():
    yield
    stop_server()


# ---- served-round stamps + force-close quorum scaling (golden) --------------
def test_staleness_serves_stale_stamps_round_and_force_closes(monkeypatch):
    """The K=1 ladder, choreographed: (a) the first round is a REAL
    quorum sum (v <= K never forces — the ladder's base is never served
    zeros); (b) a pull within the bound is served the newest CLOSED
    round and STAMPED with it; (c) a pull past the bound FORCE-closes
    the straggler-held round over its contributors, scaled by
    live/contributors so the global average stays unbiased; (d) the
    straggler's late push is consumed silently — watermark advanced,
    payload dropped, no error — and its next pull serves it the newest
    round to catch up from; (e) a serve-ahead pull re-syncs the
    straggler's mint counter so it REJOINS the quorum once it recovers."""
    from byteps_tpu.common import config as config_mod

    monkeypatch.setenv("BYTEPS_STALENESS", "1")  # arm the worker side too
    config_mod.reset_config()
    port = BASE_PORT + 1
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=False, staleness=1)
    servers = [("127.0.0.1", port)]
    rng = np.random.default_rng(5)
    x0 = rng.standard_normal(64).astype(np.float32)
    x1 = rng.standard_normal(64).astype(np.float32)
    w0 = PSWorker(servers=servers, worker_id=0, health_interval_ms=0)
    w1 = PSWorker(servers=servers, worker_id=1, health_interval_ms=0)
    try:
        w0.init_key(0, 256)
        w1.init_key(0, 256)
        # (a) round 1 needs the full quorum: both push, then the pull is
        # the closed round itself (staleness 0)
        v = w0.push(0, x0)
        w1.push(0, x1)
        np.testing.assert_array_equal(w0.pull(0, 64, v), x0 + x1)
        assert w0.last_pull_round() == 1

        # (b) round 2: the straggler has not pushed; w0's pull of round
        # 2 is WITHIN the bound, so it is served round 1 — stale by one,
        # stamped with the round it actually came from
        v = w0.push(0, x0)
        assert v == 2
        np.testing.assert_array_equal(w0.pull(0, 64, v), x0 + x1)
        assert w0.last_pull_round() == 1

        # (c) round 3: the pull is past the bound (3 - 1 = 2 > newest
        # closed 1) — it force-closes round 2 over its one contributor,
        # scaled live/contributors = 2/1, and is served that round
        v = w0.push(0, x0)
        assert v == 3
        np.testing.assert_array_equal(w0.pull(0, 64, v), x0 + x0)
        assert w0.last_pull_round() == 2

        # (d) the straggler's round-2 push arrives AFTER round 2 closed:
        # consumed silently (no error, no rejoin storm), and its pull is
        # served the newest closed round to catch up from
        v1 = w1.push(0, x1)
        assert v1 == 2
        out = w1.pull(0, 64, v1)
        np.testing.assert_array_equal(out, x0 + x0)
        assert w1.last_pull_round() == 2

        # (e) RECOVERY: the fast worker laps the straggler further
        # (rounds 4 and 5 force-closed over w0 alone), opening a GAP
        # between the straggler's mint counter (2) and the server round
        # (5). The straggler's serve-AHEAD pull re-syncs its counter to
        # the served round, so its NEXT push targets the OPEN round and
        # rejoins the quorum — a transiently slow worker must not stay
        # excluded forever (its late pushes silently consumed) once it
        # recovers.
        for _ in range(2):
            v = w0.push(0, x0)
            w0.pull(0, 64, v)
        assert w0.last_pull_round() == v - 1 == 4
        v1 = w1.push(0, x1)          # mints 3 — late, consumed silently
        assert v1 == 3
        w1.pull(0, 64, v1)           # served round 4 (> requested 3):
        assert w1.last_pull_round() == 4  # ... counter adopts it
        v1 = w1.push(0, x1)          # re-synced: targets OPEN round 5
        assert v1 == 5               # (w0's deferred round-5 push is
        # already there, so this completes the quorum — round 5 closes
        # NATURALLY, unscaled, once the async apply lands; poll a
        # serve-within-bound pull, which never forces round 5 itself)
        deadline = time.time() + 10
        out = None
        while time.time() < deadline:
            out = w0.pull(0, 64, 5)
            if w0.last_pull_round() == 5:
                break
            time.sleep(0.01)
        assert w0.last_pull_round() == 5
        np.testing.assert_array_equal(out, x0 + x1)

        # telemetry: requested − served landed in the registry histogram
        h = get_registry().snapshot()["histograms"]["server.staleness"]
        assert h["count"] >= 4 and h["max"] >= 1.0, h
    finally:
        for w in (w0, w1):
            w.close()
        stop_server()


def test_staleness_k0_bit_identical_to_sync():
    """The ROADMAP item 3 equivalence pin: a server started with
    BYTEPS_STALENESS=0 runs the IDENTICAL code path as the synchronous
    tier — multi-round 2-worker sums are bit-identical, every pull is
    served exactly the requested round, and the staleness histogram
    never observes a nonzero value."""
    rng = np.random.default_rng(11)
    rounds = [(rng.standard_normal(96).astype(np.float32),
               rng.standard_normal(96).astype(np.float32))
              for _ in range(4)]

    def run(port, staleness):
        start_server(port=port, num_workers=2, engine_threads=2,
                     async_mode=False, staleness=staleness)
        servers = [("127.0.0.1", port)]
        w0 = PSWorker(servers=servers, worker_id=0, health_interval_ms=0)
        w1 = PSWorker(servers=servers, worker_id=1, health_interval_ms=0)
        outs = []
        try:
            w0.init_key(0, 384)
            w1.init_key(0, 384)
            for x0, x1 in rounds:
                v = w0.push(0, x0)
                w1.push(0, x1)
                outs.append(w0.pull(0, 96, v).copy())
                assert w0.last_pull_round() == v  # served == requested
        finally:
            for w in (w0, w1):
                w.close()
            stop_server()
        return outs

    sync = run(BASE_PORT + 3, staleness=None)   # the plain sync tier
    k0 = run(BASE_PORT + 5, staleness=0)        # K=0 bounded staleness
    for a, b in zip(sync, k0):
        np.testing.assert_array_equal(a, b)
    h = get_registry().snapshot()["histograms"]["server.staleness"]
    assert h["count"] >= 8 and h["max"] == 0.0, h


# ---- scheduler per-key rounds window ----------------------------------------
def test_scheduler_rounds_window_gates_per_key():
    """The worker-side half of the bound: with ``rounds_window=K`` a
    task whose round is more than K ahead of its key's oldest
    in-flight round HOLDS at its queue — and a round-blocked head is
    skipped, so a sibling key's task behind it still issues."""
    from byteps_tpu.common.partition import Partition
    from byteps_tpu.common.scheduler import (
        Handle,
        PartitionTask,
        PipelineScheduler,
        Stage,
    )

    started = []
    release = {0: threading.Event(), 1: threading.Event(),
               2: threading.Event(), 3: threading.Event()}

    def run(task):
        started.append((task.partition.key, task.round))
        release[task.round].wait(10)
        return task.round

    sched = PipelineScheduler(
        [Stage("RUN", run, pool_size=4)], credit=8, rounds_window=1)

    def mk(key, rnd):
        h = Handle(f"k{key}r{rnd}", 1)
        return h, PartitionTask(
            partition=Partition(key=key, tensor_id=key, part_idx=0,
                                offset=0, length=1, priority=0),
            name=f"k{key}", handle=h, round=rnd)

    try:
        handles = {}
        tasks = []
        for rnd in (0, 1, 2):      # key 7: rounds 0..2
            h, t = mk(7, rnd)
            handles[(7, rnd)] = h
            tasks.append(t)
        h, t = mk(9, 3)            # sibling key behind the blocked head
        handles[(9, 3)] = h
        tasks.append(t)
        sched.enqueue(tasks)
        deadline = time.time() + 5
        while time.time() < deadline and len(started) < 3:
            time.sleep(0.01)
        # rounds 0 and 1 of key 7 issue (window 1 = two rounds in
        # flight); round 2 must HOLD, while key 9 — enqueued after the
        # blocked task — flows freely
        assert sorted(started) == [(7, 0), (7, 1), (9, 3)], started
        release[0].set()           # retire round 0 -> round 2 unblocks
        handles[(7, 0)].wait(10)
        deadline = time.time() + 5
        while time.time() < deadline and (7, 2) not in started:
            time.sleep(0.01)
        assert (7, 2) in started, started
        for ev in release.values():
            ev.set()
        for h in handles.values():
            h.wait(10)
        # zero credit leak with the window armed
        assert sched.credit_pools() == {0: 8}
    finally:
        for ev in release.values():
            ev.set()
        sched.shutdown()


# ---- DcnCore straggler smoke (tier-1 acceptance) ----------------------------
def test_staleness_smoke_straggler_k1_dcncore(monkeypatch):
    """THE tier-1 staleness smoke: 2 DcnCore workers, K=1, worker 1 a
    deterministic straggler (``worker1:slow`` — every one of its wire
    attempts pays 120 ms). The fast worker pipelines K+1 rounds of
    pushes (the scheduler window) and completes EVERY round without
    waiting out the straggler; served-round stamps show real staleness
    in the registry, and the credit pool drains back to full (zero
    leak)."""
    from byteps_tpu.common import config as config_mod
    from byteps_tpu.common.dcn_adapter import DcnCore

    monkeypatch.setenv("DMLC_NUM_WORKER", "2")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("BYTEPS_STALENESS", "1")
    monkeypatch.setenv("BYTEPS_FAULT_SPEC", "worker1:slow@ms=120")
    monkeypatch.setenv("BYTEPS_FAULT_SEED", "0")
    config_mod.reset_config()
    port = BASE_PORT + 7
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=False)
    servers = [("127.0.0.1", port)]
    rng = np.random.default_rng(2)
    flat0 = rng.standard_normal(65536).astype(np.float32)
    flat1 = rng.standard_normal(65536).astype(np.float32)
    rounds = 5
    window = 1  # = K: keep K+1 handles in flight
    errs = []
    fast_done = []
    pools = {}

    def fast_body():
        core = DcnCore(servers=servers, worker_id=0)
        try:
            pend = deque()
            for _ in range(rounds):
                pend.append(core.push_pull_async(flat0, name="st"))
                while len(pend) > window:
                    out = DcnCore.assemble(pend.popleft(), timeout=120.0)
                    fast_done.append(out.size)
            while pend:
                fast_done.append(
                    DcnCore.assemble(pend.popleft(), timeout=120.0).size)
            core.scheduler.drain(timeout=30.0)
            pools.update(core.scheduler.credit_pools())
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errs.append(e)
        finally:
            core.shutdown()

    def straggler_body():
        core = DcnCore(servers=servers, worker_id=1)
        try:
            for _ in range(rounds):
                DcnCore.assemble(
                    core.push_pull_async(flat1, name="st"), timeout=120.0)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)
        finally:
            core.shutdown()

    ts = [threading.Thread(target=fast_body),
          threading.Thread(target=straggler_body)]
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
            assert not t.is_alive(), "staleness smoke wedged"
        if errs:
            raise errs[0]
    finally:
        stop_server()
        config_mod.reset_config()
    assert len(fast_done) == rounds and all(n == 65536 for n in fast_done)
    # the fast worker really consumed stale rounds (served < requested)
    h = get_registry().snapshot()["histograms"]["server.staleness"]
    assert h["count"] >= rounds and h["max"] >= 1.0, h
    # zero credit leak with the rounds window + pipelined driver
    assert pools == {0: config_mod.get_config().scheduling_credit}, pools


# ---- BYTEPS_ENABLE_ASYNC: the K=inf limit -----------------------------------
def test_async_push_validates_bounds_and_liveness(monkeypatch):
    """Satellite bugfix regression (server.cc): async mode used to skip
    the worker-bounds check, the liveness check, and (with them) any
    chance of kMembers telling the truth — an out-of-range or evicted
    worker id silently summed into the free-running aggregate. Now, via
    the TCP path: out-of-range ids are rejected, pushes refresh the
    lease, an evicted worker's push is refused until its heartbeat
    re-admits it, and the live bitmap tracks all of it."""
    from byteps_tpu.common import config as config_mod

    monkeypatch.setenv("DMLC_NUM_WORKER", "2")
    config_mod.reset_config()
    port = BASE_PORT + 9
    # seconds, not 300 ms: after the eviction the ping, the push and the
    # members() call below must all land inside ONE lease, on a host that
    # runs five other test workers
    lease_ms = 3000
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=True, lease_ms=lease_ms)
    x = np.arange(16, dtype=np.float32)
    buf = x.view(np.uint8).ravel()
    c = NativeClient("127.0.0.1", port, 5000, 10000)
    try:
        c.init_key(0, 64)
        # out-of-range worker id: rejected, never summed
        with pytest.raises(RuntimeError, match="out of range"):
            c.push(0, buf, 0, worker_id=7, version=1)
        c.push(0, buf, 0, worker_id=1, version=1)
        got = np.empty(64, np.uint8)
        n = c.pull(0, got, 1, worker_id=1)
        np.testing.assert_array_equal(got[:n].view(np.float32), x)

        # both workers go silent past the lease: evicted, bitmap shrinks
        deadline = time.time() + 30
        while time.time() < deadline:
            epoch, live, bits = c.members()
            if live == 0:
                break
            time.sleep(0.05)
        assert live == 0 and not bits.any(), (epoch, live, bits)

        # an evicted worker's async push is REFUSED (it used to sum
        # silently) until the kPing heartbeat re-admits it
        with pytest.raises(WorkerEvictedError):
            c.push(0, buf, 0, worker_id=1, version=2)
        c.ping(worker_id=1)
        c.push(0, buf, 0, worker_id=1, version=2)
        epoch, live, bits = c.members()
        assert live == 1 and bits[1] == 1 and bits[0] == 0, (live, bits)
        n = c.pull(0, got, 1, worker_id=1)
        np.testing.assert_array_equal(got[:n].view(np.float32), x + x)
    finally:
        c.close()
        stop_server()
        config_mod.reset_config()


def test_async_two_worker_converges_small_model():
    """BYTEPS_ENABLE_ASYNC pinned as the K→inf limit on a small model —
    it never had a dedicated convergence test. Reference async
    semantics: the store IS the parameter vector (zero-initialized);
    workers push −lr·grad deltas at their own pace and pull the current
    params, no per-round barrier anywhere. Two free-running workers on
    a shared quadratic must still drive the loss down ~monotonically."""
    port = BASE_PORT + 11
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=True)
    servers = [("127.0.0.1", port)]
    dim = 32
    rng = np.random.default_rng(3)
    w_true = rng.standard_normal(dim).astype(np.float32)
    lr = np.float32(0.05)
    steps = 60
    errs = []
    final = {}

    def body(wid):
        w = PSWorker(servers=servers, worker_id=wid, health_interval_ms=0)
        try:
            w.init_key(0, dim * 4)
            params = np.zeros(dim, np.float32)
            for _ in range(steps):
                grad = 2.0 * (params - w_true)
                v = w.push(0, (-lr * grad).astype(np.float32))
                params = w.pull(0, dim, v).copy()
            final[wid] = params
        except BaseException as e:  # noqa: BLE001
            errs.append(e)
        finally:
            w.close()

    ts = [threading.Thread(target=body, args=(i,)) for i in range(2)]
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
            assert not t.is_alive(), "async worker wedged"
        if errs:
            raise errs[0]
    finally:
        stop_server()
    loss0 = float(np.sum(w_true ** 2))  # loss at the zero init
    for wid, params in final.items():
        loss = float(np.sum((params - w_true) ** 2))
        assert loss < 0.05 * loss0, (wid, loss, loss0)


# ---- K ladder convergence envelope ------------------------------------------
def test_staleness_envelope_k1_k4_vs_k0():
    """Small-model loss-curve envelope for the K ladder under a
    deterministic straggler: worker gradients are the true gradient
    plus worker-specific offsets that CANCEL across the pair, so K=0
    (every round a full quorum) converges to the optimum exactly, while
    K≥1 rounds that close over the fast worker alone carry a bounded
    bias (offset/2) — the textbook SSP trade. The envelope pins both:
    K=0 lands ~at the optimum, K∈{1,4} land inside the bias
    neighborhood, far below the initial loss."""
    from byteps_tpu.common.faults import FaultPlan, parse_fault_spec

    dim = 16
    rng = np.random.default_rng(9)
    w_true = rng.standard_normal(dim).astype(np.float32)
    d = 0.2 * rng.standard_normal(dim).astype(np.float32)  # ±offset pair
    lr = np.float32(0.1)
    rounds = 40
    loss0 = float(np.sum(w_true ** 2))
    finals = {}
    for i, K in enumerate((0, 1, 4)):
        port = BASE_PORT + 13 + 2 * i
        start_server(port=port, num_workers=2, engine_threads=2,
                     async_mode=False, staleness=K)
        servers = [("127.0.0.1", port)]
        errs = []
        curve = []

        def body(wid, plan=None, record=False):
            w = PSWorker(servers=servers, worker_id=wid,
                         health_interval_ms=0, fault_plan=plan)
            try:
                w.init_key(0, dim * 4)
                params = np.zeros(dim, np.float32)
                off = d if wid == 0 else -d
                for _ in range(rounds):
                    grad = 2.0 * (params - w_true) + off
                    v = w.push(0, grad.astype(np.float32))
                    avg = w.pull(0, dim, v) / np.float32(2.0)
                    params = params - lr * avg
                    if record:
                        curve.append(
                            float(np.sum((params - w_true) ** 2)))
            except BaseException as e:  # noqa: BLE001
                errs.append(e)
            finally:
                w.close()

        plan = FaultPlan(parse_fault_spec("worker1:slow@ms=6"),
                         seed=0, worker_id=1)
        ts = [threading.Thread(target=body, args=(0, None, True)),
              threading.Thread(target=body, args=(1, plan))]
        try:
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120)
                assert not t.is_alive(), f"K={K} leg wedged"
            if errs:
                raise errs[0]
        finally:
            stop_server()
        finals[K] = curve[-1]
        # the curve's tail beats its head by a lot (it converged, not
        # wandered)
        assert curve[-1] < 0.05 * max(curve[0], 1e-9), (K, curve[:3],
                                                        curve[-3:])
    # K=0 is exact sync: both offsets cancel every round -> ~optimum
    assert finals[0] < 1e-4 * loss0, finals
    # K>=1 rounds may close over the fast worker alone: bounded bias
    # (offset/2 per such round) -> inside the bias neighborhood
    bias_floor = float(np.sum((d / 2.0) ** 2))  # ||d/2||^2
    for K in (1, 4):
        assert finals[K] < max(4.0 * bias_floor, 1e-3 * loss0), (
            K, finals, bias_floor)
