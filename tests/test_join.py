"""Scale-up elasticity: mid-stream worker JOIN (kJoin) + the shared
autoscaler policy (docs/robustness.md §scale-up elasticity).

Tier-1 pins: the kJoin admission protocol end to end (fresh-id
membership growth, epoch bump, round-boundary semantics, unbiased
divisors), the BIT-safety acceptance criterion (a K=0 run with a join is
bit-identical to a clean run started at the post-join membership from
the join round onward), composition with bounded staleness (a joiner
starts at the served-round frontier, never below the force-close
watermark), the fault grammar's deterministic ``worker<N>:join`` rule,
rejoin against a partially-live server set, the bounded
``_epoch_live`` divisor history, the elastic data-shard map invariants
(no example dropped or double-visited within an epoch window), and the
``ScalingPolicy`` decision dynamics shared by train-worker admission and
serve-replica scaling (``serve/router.py``).
"""

import dataclasses
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from byteps_tpu.common import config as config_mod
from byteps_tpu.common.faults import (
    FaultPlan,
    churn_events,
    parse_fault_spec,
    rules_to_spec,
)
from byteps_tpu.server import (
    NoLiveServersError,
    PSWorker,
    WorkerEvictedError,
    start_server,
    stop_server,
)
from byteps_tpu.server.native import NativeClient, load_lib

BASE_PORT = 25300
# the lease of the tests below that wait for no eviction: it only arms
# admission, and with no heartbeat (health_interval_ms=0) a worker that
# sits out longer than the lease while its peers work is evicted. Half a
# second was a whole test's budget on a host running five other workers
_LEASE_MS = 10_000


@pytest.fixture(autouse=True)
def _cleanup_server():
    yield
    stop_server()
    config_mod.reset_config()


def _fresh_registry():
    from byteps_tpu.common.metrics import get_registry, reset_registry

    reset_registry()
    return get_registry()


# ---- kJoin protocol (tentpole) ----------------------------------------------
def test_kjoin_admits_fresh_worker_and_grows_membership(monkeypatch):
    """A FRESH worker id beyond DMLC_NUM_WORKER joins a running job: the
    membership table grows, the epoch bumps (peers adopt it on their
    next op), the joiner adopts round watermarks, and the next round
    sums — and divides by — the grown live set."""
    monkeypatch.setenv("DMLC_NUM_WORKER", "2")
    config_mod.reset_config()
    port = BASE_PORT + 1
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=False, lease_ms=_LEASE_MS)
    servers = [("127.0.0.1", port)]
    x = [np.full(16, float(i + 1), np.float32) for i in range(3)]
    w0 = PSWorker(servers=servers, worker_id=0, health_interval_ms=0)
    w1 = PSWorker(servers=servers, worker_id=1, health_interval_ms=0)
    try:
        w0.init_key(0, 64)
        w1.init_key(0, 64)
        for _ in range(2):
            v = w0.push(0, x[0])
            w1.push(0, x[1])
            np.testing.assert_array_equal(w0.pull(0, 16, v), x[0] + x[1])
        assert w0.last_round_live() == 2

        w2 = PSWorker(servers=servers, worker_id=2, health_interval_ms=0)
        assert w2.join() == 1
        assert w2.get_counters()["joins"] == 1
        # watermark adopted: the next mint continues the round sequence
        versions, nbytes = w2.export_rounds()
        assert versions == {0: 2} and nbytes == {0: 64}
        # the server grew: membership now reports 3 live of 3 slots
        ep, live, bits = w2._conn(0).members()
        assert live == 3 and bits.tolist() == [1, 1, 1] and ep >= 1

        # the next round sums all three, and the divisor authority is
        # the grown live count on EVERY member's view
        v = w0.push(0, x[0])
        w1.push(0, x[1])
        w2.push(0, x[2])
        np.testing.assert_array_equal(
            w0.pull(0, 16, v), x[0] + x[1] + x[2])
        assert w0.last_round_live() == 3
        np.testing.assert_array_equal(
            w2.pull(0, 16, v), x[0] + x[1] + x[2])
        assert w2.last_round_live() == 3
        w2.close()
    finally:
        for w in (w0, w1):
            w.close()


def test_kjoin_closes_open_round_over_contributors(monkeypatch):
    """A round OPEN at admission closes over whoever contributed
    (quorum-scaled, the eviction arithmetic generalized upward): the
    joiner is only expected from its adopted watermark onward."""
    monkeypatch.setenv("DMLC_NUM_WORKER", "2")
    config_mod.reset_config()
    port = BASE_PORT + 2
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=False, lease_ms=_LEASE_MS)
    servers = [("127.0.0.1", port)]
    x0 = np.linspace(0, 1, 16, dtype=np.float32)
    x1 = np.linspace(2, 3, 16, dtype=np.float32)
    w0 = PSWorker(servers=servers, worker_id=0, health_interval_ms=0)
    w1 = PSWorker(servers=servers, worker_id=1, health_interval_ms=0)
    try:
        w0.init_key(0, 64)
        w1.init_key(0, 64)
        # round 1 OPEN: only w0 contributed when w2 joins
        v = w0.push(0, x0)
        w2 = PSWorker(servers=servers, worker_id=2, health_interval_ms=0)
        w2.join()
        # joiner adopted watermark 0 (no closed round yet — the zero
        # watermark leaves the fresh counter as-is): it is expected in
        # round 1 now — the round closes once w1 AND w2 contribute,
        # with all three summed (arrived == live, no scale)
        assert w2.export_rounds()[0].get(0, 0) == 0
        w1.push(0, x1)
        w2.push(0, x0)
        np.testing.assert_array_equal(w0.pull(0, 16, v),
                                      (x0 + x1) + x0)
        assert w0.last_round_live() == 3
        w2.close()
    finally:
        for w in (w0, w1):
            w.close()


def test_join_bit_identical_post_join_rounds(monkeypatch):
    """ACCEPTANCE: a K=0 run with a mid-stream join is BIT-identical to
    a clean run started at the post-join membership, from the join round
    onward (same push order ⇒ same fp32 sum order ⇒ same bytes)."""
    rng = np.random.default_rng(17)
    x = [rng.standard_normal(64).astype(np.float32) for _ in range(3)]

    def run(port, n_workers, joiner, rounds):
        monkeypatch.setenv("DMLC_NUM_WORKER", str(n_workers))
        config_mod.reset_config()
        start_server(port=port, num_workers=n_workers, engine_threads=2,
                     async_mode=False, lease_ms=_LEASE_MS)
        servers = [("127.0.0.1", port)]
        ws = [PSWorker(servers=servers, worker_id=i,
                       health_interval_ms=0) for i in range(n_workers)]
        pulls = []
        try:
            for w in ws:
                w.init_key(0, 256)
            for _ in range(2):  # pre-join rounds (churn run only)
                if joiner:
                    v = ws[0].push(0, x[0])
                    ws[1].push(0, x[1])
                    ws[0].pull(0, 64, v)
            if joiner:
                w2 = PSWorker(servers=servers, worker_id=2,
                              health_interval_ms=0)
                w2.join()
                ws.append(w2)
            for _ in range(rounds):
                v = None
                for i, w in enumerate(ws):
                    vi = w.push(0, x[i])
                    v = vi if v is None else v
                pulls.append(ws[0].pull(0, 64, v).tobytes())
                assert ws[0].last_round_live() == 3
        finally:
            for w in ws:
                w.close()
            stop_server()
            config_mod.reset_config()
        return pulls

    churn = run(BASE_PORT + 3, 2, joiner=True, rounds=3)
    clean = run(BASE_PORT + 4, 3, joiner=False, rounds=3)
    assert churn == clean  # byte-for-byte, from the join round onward


def test_join_composes_with_staleness(monkeypatch):
    """Under BYTEPS_STALENESS=K a joiner starts at the SERVED-round
    frontier — which never trails the force-close watermark — so its
    first push lands in the open round, not a force-closed one."""
    monkeypatch.setenv("DMLC_NUM_WORKER", "2")
    config_mod.reset_config()
    port = BASE_PORT + 5
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=False, lease_ms=_LEASE_MS, staleness=2)
    servers = [("127.0.0.1", port)]
    rng = np.random.default_rng(23)
    x0 = rng.standard_normal(16).astype(np.float32)
    x1 = rng.standard_normal(16).astype(np.float32)
    x2 = rng.standard_normal(16).astype(np.float32)
    w0 = PSWorker(servers=servers, worker_id=0, health_interval_ms=0)
    w1 = PSWorker(servers=servers, worker_id=1, health_interval_ms=0)
    try:
        w0.init_key(0, 64)
        w1.init_key(0, 64)
        # w0 runs ahead (pushes rounds 1..3); w1 contributes round 1 only
        for _ in range(3):
            w0.push(0, x0)
        w1.push(0, x1)
        # round 1 closes naturally; w0's pull for round 4 FORCE-closes
        # the straggler-held round 2 over its contributor (w0 alone,
        # quorum-scaled ×2) — the force-close watermark is now 2
        np.testing.assert_array_equal(w0.pull(0, 16, 1), x0 + x1)
        out = w0.pull(0, 16, 4)
        assert w0.last_pull_round() == 2
        np.testing.assert_array_equal(out, x0 * np.float32(2.0))

        # the joiner adopts the served-round frontier (== force-close
        # watermark here), never below it
        w2 = PSWorker(servers=servers, worker_id=2, health_interval_ms=0)
        w2.join()
        assert w2.export_rounds()[0] == {0: 2}
        # its first push mints round 3 — the OPEN round (w0's deferred
        # push of round 3 already sits in it); the straggler's late
        # round-2 push is consumed silently, its round-3 push closes the
        # round over the full grown membership, unscaled
        w2.push(0, x2)
        w1.push(0, x1)  # late round 2: consumed silently (no error)
        w1.push(0, x1)  # round 3
        np.testing.assert_array_equal(w2.pull(0, 16, 3),
                                      (x0 + x2) + x1)
        assert w2.last_round_live() == 3
        w2.close()
    finally:
        for w in (w0, w1):
            w.close()


def test_kjoin_rejects_out_of_range_and_fixed_membership():
    """Structured admission errors: an id beyond the growth ceiling is
    refused; under FIXED membership (lease disabled) a configured id
    acks idempotently but a fresh id cannot be grown."""
    port = BASE_PORT + 6
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=False, lease_ms=0)
    c = NativeClient("127.0.0.1", port)
    try:
        assert c.join(0) == 0   # configured id under fixed membership
        with pytest.raises(RuntimeError, match="fixed membership"):
            c.join(5)
        with pytest.raises(RuntimeError, match="out of range"):
            c.join(4000)
        with pytest.raises(RuntimeError, match="worker id"):
            c.join(-1)
    finally:
        c.close()
    # IPC surface: same contract against the in-process server
    lib = load_lib()
    assert lib.bps_server_join(0) == 0
    assert lib.bps_server_join(5) == -2
    assert lib.bps_server_join(4000) == -1


# ---- satellite: bounded divisor history ------------------------------------
def test_epoch_live_divisor_history_bounded():
    """Under churn every membership epoch adds an (epoch -> live)
    divisor entry; a 100-epoch churn must hold the dict size constant
    (pruned to the window), including across the mod-2^16 wrap."""
    from byteps_tpu.server import _EPOCH_LIVE_WINDOW

    w = PSWorker(servers=[("127.0.0.1", 1)], worker_id=0,
                 health_interval_ms=0)
    try:
        with w._vlock:
            for e in range(1, 101):
                w._record_epoch_live(0, e, 2 + e % 3)
        entries = [k for k in w._epoch_live if k[0] == 0]
        assert len(entries) <= _EPOCH_LIVE_WINDOW
        # the newest window survives, the tail is gone
        assert (0, 100) in w._epoch_live
        assert (0, 1) not in w._epoch_live
        # wraparound: epochs just past 0xFFFF prune the now-distant
        # mid-ring entries but keep the recent pre-wrap ones (the prune
        # is a ±window around the newest epoch, so nothing can strand
        # on the "future" half of the mod-2^16 ring)
        with w._vlock:
            for e in range(0xFFF0, 0x10000):
                w._record_epoch_live(0, e, 2)
            for e in range(0, 8):
                w._record_epoch_live(0, e, 3)
        entries = [k for k in w._epoch_live if k[0] == 0]
        assert len(entries) <= 2 * _EPOCH_LIVE_WINDOW
        assert (0, 0xFFF0) in w._epoch_live  # within window across wrap
        assert (0, 100) not in w._epoch_live
    finally:
        w.close()


# ---- satellite: rejoin against a partially-live server set ------------------
def test_rejoin_with_partially_live_server_set(monkeypatch):
    """A restarted worker rejoining while one server is unreachable is
    admitted by the live quorum (per-server warn-and-continue) and
    completes rounds; the dead server's later recovery re-admits it via
    the eviction → inline-rejoin handshake WITHOUT a round gap (its next
    mint continues that server's watermark)."""
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    config_mod.reset_config()
    port0 = BASE_PORT + 8
    port1 = port0 + 1
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "from byteps_tpu.server import start_server;"
         "from byteps_tpu.server.native import load_lib;"
         "start_server(port=%d, num_workers=1, engine_threads=2,"
         "async_mode=False, lease_ms=400);"
         "load_lib().bps_server_wait()" % port1],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "DMLC_NUM_WORKER": "1",
             "PYTHONPATH": os.path.dirname(
                 os.path.dirname(os.path.abspath(__file__)))},
    )
    servers = [("127.0.0.1", port0), ("127.0.0.1", port1)]
    lib = load_lib()
    rng = np.random.default_rng(29)
    xa, xb, xc = (rng.standard_normal(16).astype(np.float32)
                  for _ in range(3))
    w = w2 = None
    try:
        # Server 1 (the python child) first, and server 0 only once it
        # listens (the connect retries every 50 ms): a server's leases
        # run from ITS start, and on this image the child needs ~450 ms
        # to import and bind — longer than the 400 ms lease. Started the
        # other way round, server 0 had evicted the heartbeat-less
        # worker before its first push in 19 runs of 20 (the A0
        # failure). The lease is not widened: the test's own eviction
        # waits depend on it.
        NativeClient("127.0.0.1", port1).close()
        start_server(port=port0, num_workers=1, engine_threads=2,
                     async_mode=False, lease_ms=400)
        w = PSWorker(servers=servers, worker_id=0, health_interval_ms=0)
        w.init_key(0, 64)   # key 0 -> server 0
        w.init_key(1, 64)   # key 1 -> server 1
        v0 = w.push(0, xa)
        v1 = w.push(1, xb)
        np.testing.assert_array_equal(w.pull(0, 16, v0), xa)
        np.testing.assert_array_equal(w.pull(1, 16, v1), xb)
        # the worker "crashes" (silent close); both leases evict it
        w.close()
        probe = NativeClient("127.0.0.1", port1)
        deadline = time.time() + 10
        while time.time() < deadline and (
                lib.bps_server_epoch() == 0 or probe.members()[0] == 0):
            time.sleep(0.05)
        assert lib.bps_server_epoch() >= 1
        assert probe.members()[0] >= 1
        probe.close()

        # restart: server 1 sits behind an injected down window for the
        # first rejoin attempt — rejoin() warns and continues, the live
        # quorum (server 0) re-admits
        plan = FaultPlan(parse_fault_spec("server1:down@op=1..2"),
                         seed=0, worker_id=0)
        w2 = PSWorker(servers=servers, worker_id=0, fault_plan=plan,
                      health_interval_ms=0)
        w2.rejoin()   # ping s0 (step 1, clean) + ping s1 (step 2, DOWN)
        assert w2.get_counters()["rejoins"] == 1
        versions, _ = w2.export_rounds()
        assert versions.get(0) == 1 and 1 not in versions
        # rounds complete against the live quorum, continuing server
        # 0's sequence without a gap
        v = w2.push(0, xc)
        assert v == v0 + 1
        np.testing.assert_array_equal(w2.pull(0, 16, v), xc)

        # server 1 "recovers" (the down window expired). Its lease had
        # evicted this worker, so the first push is refused and the
        # inline rejoin adopts ITS watermark too — the re-push mints
        # exactly watermark+1: no round gap
        with pytest.raises(WorkerEvictedError):
            w2.push(1, xc)
        versions, _ = w2.export_rounds()
        assert versions.get(1) == v1
        v = w2.push(1, xc)
        assert v == v1 + 1
        np.testing.assert_array_equal(w2.pull(1, 16, v), xc)
    finally:
        for worker in (w, w2):
            if worker is not None:
                try:
                    worker.close()
                except Exception:
                    pass
        if proc.poll() is None:
            proc.kill()


# ---- satellite: fault grammar join scope ------------------------------------
def test_fault_grammar_join_round_trip_and_errors():
    """``worker<N>:join@step=A`` parses, renders back (to_spec round
    trip), surfaces structured errors naming the grammar, and
    churn_events() reads the schedule back for orchestration."""
    for form in ("worker2:join@step=12", "worker0:join@step=3..5",
                 "worker1:join@step=7.."):
        rules = parse_fault_spec(form)
        assert parse_fault_spec(rules_to_spec(rules)) == rules, form
    (r,) = parse_fault_spec("worker2:join@step=12")
    assert (r.scope, r.worker, r.kind, r.window) == ("worker", 2,
                                                     "join", (12, 12))
    for bad, hint in [
        ("pull:join@step=1", "worker"),     # worker-scope-only kind
        ("worker2:join", "step="),          # deterministic: needs step
        ("worker2:join@p=0.5", "step="),    # probabilistic join is a bug
    ]:
        with pytest.raises(ValueError) as ei:
            parse_fault_spec(bad)
        msg = str(ei.value)
        assert "bad BYTEPS_FAULT_SPEC rule" in msg and hint in msg, (
            bad, msg)
    spec = ("worker2:join@step=1;worker3:join@step=1;"
            "worker1:kill@step=9..")
    assert churn_events(parse_fault_spec(spec)) == [
        (1, 2, "join"), (1, 3, "join"), (9, 1, "kill")]


def test_fault_grammar_join_fires_once(monkeypatch):
    """A ``worker<N>:join`` rule runs the kJoin handshake exactly ONCE
    (one-shot latch) even with an open window, before the intercepted op
    proceeds — the deterministic mid-stream join the churn leg uses."""
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    config_mod.reset_config()
    port = BASE_PORT + 10
    start_server(port=port, num_workers=1, engine_threads=2,
                 async_mode=False, lease_ms=_LEASE_MS)
    servers = [("127.0.0.1", port)]
    x = np.full(16, 2.0, np.float32)
    w0 = PSWorker(servers=servers, worker_id=0, health_interval_ms=0)
    w1 = None
    try:
        w0.init_key(0, 64)
        v = w0.push(0, x)
        np.testing.assert_array_equal(w0.pull(0, 16, v), x)
        # fresh id 1 with an OPEN join window: first wire attempt (the
        # init) triggers the admission, later ops do not re-join
        plan = FaultPlan(parse_fault_spec("worker1:join@step=1.."),
                         seed=0, worker_id=1)
        w1 = PSWorker(servers=servers, worker_id=1, fault_plan=plan,
                      health_interval_ms=0)
        w1.init_key(0, 64)
        for _ in range(3):
            v0 = w0.push(0, x)
            w1.push(0, x)
            np.testing.assert_array_equal(w0.pull(0, 16, v0), x + x)
        assert w1.get_counters()["joins"] == 1
        assert w1.get_counters()["injected_join"] >= 1
    finally:
        for worker in (w0, w1):
            if worker is not None:
                worker.close()


# ---- satellite: elastic data-shard map --------------------------------------
def test_elastic_shard_map_no_drop_no_double_visit():
    from byteps_tpu.data.elastic import (
        ElasticShardMap,
        live_ids_from_bitmap,
    )

    m = ElasticShardMap(101, seed=3)
    full = m.assign([0, 1])
    got = np.sort(np.concatenate([full[0], full[1]]))
    np.testing.assert_array_equal(got, np.arange(101))
    assert not set(full[0]) & set(full[1])

    # consume 37, then the membership changes mid-epoch (join + evict):
    # only the UNVISITED remainder re-splits — the visited prefix is
    # never handed out again
    m.advance(37)
    visited = set(m._order[:37].tolist())
    remap = m.assign([0, 2, 3])
    pieces = [set(remap[w].tolist()) for w in (0, 2, 3)]
    assert not (pieces[0] | pieces[1] | pieces[2]) & visited
    assert sorted(pieces[0] | pieces[1] | pieces[2]) == sorted(
        set(range(101)) - visited)
    assert not pieces[0] & pieces[1] and not pieces[1] & pieces[2]

    # pure function of (seed, epoch, cursor, live): a second replica
    # computes the identical map with no coordination
    m2 = ElasticShardMap(101, seed=3)
    m2.advance(37)
    for w in (0, 2, 3):
        np.testing.assert_array_equal(remap[w], m2.assign([0, 2, 3])[w])

    # a new epoch window reshuffles deterministically and rewinds
    m.next_epoch()
    assert m.remaining == 101
    assert not np.array_equal(m._order, m2._order)

    assert live_ids_from_bitmap([1, 0, 1, 1]) == [0, 2, 3]
    with pytest.raises(ValueError):
        m.assign([])
    with pytest.raises(ValueError, match="not in the live set"):
        m.shard_for(9, [0, 1])


# ---- autoscaler policy (shared train/serve) ---------------------------------
def test_scaling_policy_deterministic_trace():
    """ACCEPTANCE: deterministic decision trace on a recorded sample
    sequence — admit on sustained headroom, evict on straggler
    detection, hold inside the hysteresis band / cooldown / bounds."""
    from byteps_tpu.common.autoscaler import Sample, ScalingPolicy

    _fresh_registry()
    pol = ScalingPolicy(scale_up_load=1.0, scale_down_load=0.3,
                        straggler_limit=4.0, hysteresis=0.1, cooldown=2,
                        sustain=2, min_units=1, max_units=4,
                        domain="train")
    S = Sample
    recorded = [
        S(live=2, load=0.9),                  # in hysteresis band
        S(live=2, load=1.2),                  # headroom streak 1
        S(live=2, load=1.15),                 # streak 2 -> admit
        S(live=3, load=1.2),                  # cooldown
        S(live=3, load=1.2),                  # cooldown
        S(live=3, load=1.2),                  # streak sustained -> admit
        S(live=4, load=1.2),                  # cooldown
        S(live=4, load=1.2),                  # cooldown
        S(live=4, load=1.2),                  # at max_units -> hold
        S(live=4, load=0.9, straggler=6.0),   # straggler streak 1
        S(live=4, load=0.9, straggler=5.5),   # streak 2 -> evict
        S(live=3, load=0.2),                  # cooldown
        S(live=3, load=0.2),                  # cooldown
        S(live=3, load=0.2),                  # idle sustained -> evict
        S(live=2, load=0.9),                  # cooldown
    ]
    actions = [pol.observe(s).action for s in recorded]
    assert actions == [
        "hold", "hold", "admit", "hold", "hold", "admit", "hold",
        "hold", "hold", "hold", "evict", "hold", "hold", "evict",
        "hold",
    ]
    reasons = [d.reason for d in pol.trace]
    assert "sustained load headroom" in reasons[2]
    assert "at max_units" in reasons[8]
    assert "straggler detected" in reasons[10]
    assert "sustained idle" in reasons[13]
    # replaying the same recording reproduces the trace exactly
    pol2 = ScalingPolicy(scale_up_load=1.0, scale_down_load=0.3,
                         straggler_limit=4.0, hysteresis=0.1,
                         cooldown=2, sustain=2, min_units=1,
                         max_units=4, domain="train")
    assert [pol2.observe(s).action for s in recorded] == actions

    with pytest.raises(ValueError, match="inverted band"):
        ScalingPolicy(scale_up_load=0.3, scale_down_load=0.9)


def test_train_sample_reads_metrics_snapshot():
    """The train sampler distills goodput trend + staleness p99 +
    rounds_ahead spread straight from ``metrics_snapshot()``."""
    import byteps_tpu
    from byteps_tpu.common.autoscaler import train_sample

    reg = _fresh_registry()
    reg.gauge("psworker.nic0.rounds_ahead").set(0)
    reg.gauge("psworker.nic1.rounds_ahead").set(5)
    for v in (0, 0, 1, 3):
        reg.histogram("server.staleness").observe(v)
    s = train_sample(byteps_tpu.metrics_snapshot(), live=3,
                     goodput_per_worker=0.9, baseline_per_worker=1.0)
    assert s.live == 3
    assert s.load == pytest.approx(0.9)
    assert s.straggler >= 5.0  # the nic spread dominates here
    _fresh_registry()


def test_record_decision_shared_event_path():
    """Satellite: every decision source lands in the ONE shared path —
    ``autoscaler.decisions`` counter + flight-recorder FAULT event — so
    post-mortems show WHY a worker/replica was admitted or evicted."""
    from byteps_tpu.common.autoscaler import record_decision
    from byteps_tpu.common.flight_recorder import get_flight_recorder

    reg = _fresh_registry()
    before = reg.counter("autoscaler.decisions").value()
    record_decision("train", "admit", "test join", target=7, live=3)
    assert reg.counter("autoscaler.decisions").value() == before + 1
    assert reg.counter("autoscaler.train.admit").value() == 1
    events = [e for e in get_flight_recorder().events()
              if e.get("event") == "autoscaler.decision"]
    assert events and events[-1]["args"]["target"] == 7
    _fresh_registry()


# ---- serve router: replica scaling reuses the policy class ------------------
class _StubReplica:
    """Minimal Scheduler stand-in: a queue the router can load-balance,
    step, drain, and collect results from."""

    def __init__(self):
        self.queue = []
        self.results = {}

    @property
    def load(self):
        return len(self.queue)

    def submit(self, req, resume_tokens=None):
        self.queue.append(req)

    def step(self):
        if not self.queue:
            return False
        req = self.queue.pop(0)
        self.results[req.rid] = {"text": "ok"}
        return True

    def drain_incomplete(self):
        out = [(r, []) for r in self.queue]
        self.queue.clear()
        return out


@dataclasses.dataclass
class _Req:
    rid: int
    arrival_s: float = 0.0


def test_router_replica_autoscaling_reuses_policy_class():
    """ACCEPTANCE: the serve router's replica scaling is driven by the
    SAME ScalingPolicy class — queue-depth pressure spawns replicas
    (admit), sustained idleness drains them back to min (evict), and
    every decision flows through the shared event path."""
    from byteps_tpu.common.autoscaler import ScalingPolicy
    from byteps_tpu.serve.router import Router

    reg = _fresh_registry()
    pol = ScalingPolicy(scale_up_load=3.0, scale_down_load=0.5,
                        hysteresis=0.0, cooldown=0, sustain=1,
                        min_units=1, max_units=3, domain="serve")
    router = Router([_StubReplica()], lease_ms=10_000_000,
                    policy=pol, spawn=_StubReplica)
    for i in range(12):
        router.submit(_Req(rid=i))
    assert router.live_replicas() == [0]
    router.step()   # load 12/replica >= 3 -> admit
    assert len(router.live_replicas()) == 2
    router.step()   # still saturated -> admit up to max_units
    assert len(router.live_replicas()) == 3
    # drain the queue; sustained idleness evicts back to min_units
    for _ in range(40):
        router.step()
        if router.live_replicas() == [0] and len(router.results) == 12:
            break
    assert len(router.results) == 12
    assert len(router.live_replicas()) == 1
    assert reg.counter("autoscaler.serve.admit").value() == 2
    assert reg.counter("autoscaler.serve.evict").value() >= 2
    assert reg.counter("autoscaler.decisions").value() >= 4
    _fresh_registry()


def test_router_lease_eviction_uses_shared_decision_path():
    """The router's LEASE eviction (death by silence) records through
    the same autoscaler.decisions path as policy evictions."""
    from byteps_tpu.serve.router import Router

    from byteps_tpu.common.faults import WorkerKilledError

    def _killed():
        raise WorkerKilledError("injected replica death")

    reg = _fresh_registry()
    now = [0.0]
    alive = _StubReplica()
    dead = _StubReplica()
    dead.step = _killed  # dead replica: its step never completes, so
    # its lease is never renewed (death by silence, PR 5 philosophy)

    # both replicas beat at t=0; only steps renew — fake clock advances
    router = Router([alive, dead], lease_ms=1000, clock=lambda: now[0])
    router.submit(_Req(rid=0))
    before = reg.counter("autoscaler.decisions").value()
    now[0] = 0.5
    router.step()
    assert len(router.live_replicas()) == 2  # inside the lease
    # the completed step above renewed BOTH beats (serial-harness rule);
    # from here only `alive` completes steps, so `dead` ages out
    for t in (1.2, 2.0):
        now[0] = t
        router.step()
    assert router.live_replicas() == [0]
    assert reg.counter("autoscaler.serve.evict").value() == 1
    assert reg.counter("autoscaler.decisions").value() == before + 1
    _fresh_registry()


# ---- jax adapter: join + membership hooks -----------------------------------
def test_jax_join_fires_membership_hooks():
    """byteps_tpu.jax.join(): the membership hooks (shard remap, LR
    rescale) fire with the adopted live count; linear_scale is the
    default rescale policy."""
    import byteps_tpu.jax as bps

    bps.init()
    try:
        seen = []
        bps.on_membership_change(seen.append)
        live = bps.join()
        assert seen == [live] and live >= 1
        assert bps.linear_scale(0.1, 2, 4) == pytest.approx(0.2)
        assert bps.linear_scale(0.1, 2, 1) == pytest.approx(0.05)
    finally:
        bps.shutdown()
