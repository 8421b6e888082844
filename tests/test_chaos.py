"""Chaos-hardened DCN data plane (docs/robustness.md).

Tier-1 part (runs every CI pass): fault-spec grammar (incl. the
structured-error + round-trip pins), cross-process plan determinism, the
csrc replay-dedupe golden test, CRC corruption detection, the
dead-socket shutdown branch, the chaos SMOKE — a fixed-seed DcnCore
push_pull run under two injected fault kinds that must converge to the
clean values with retry counters > 0 and zero credit leak — and the
ELASTIC MEMBERSHIP pins: the lease/eviction/quorum-scaling golden test,
the worker-death chaos smoke (2 workers, one killed mid-run; the
survivor completes with post-eviction sums bit-identical to a 1-worker
clean run), the Handle deadline (StallError), and the TSAN race smoke
when a toolchain is present.

Slow tier: the acceptance sweep (5% timeouts + a 15-step server-down
window, bit-identical sums vs the clean run), health-monitor failover
onto the surviving server, graceful pure-local degradation when every
server is dead, and the eviction→rejoin round-trip. Goodput against
fault rate has no measurement: no benchmark cell runs the DCN tier.
"""

import dataclasses
import logging
import time

import numpy as np
import pytest

from byteps_tpu.common.faults import (
    FaultPlan,
    FaultRule,
    parse_fault_spec,
    rules_to_spec,
)
from byteps_tpu.server import (
    PSWorker,
    WorkerEvictedError,
    start_server,
    stop_server,
    wire_crc32,
)
from byteps_tpu.server.native import NativeClient, WireCorruption, load_lib

BASE_PORT = 25100


@pytest.fixture(autouse=True)
def _cleanup_server():
    yield
    stop_server()


# ---- fault-spec grammar (pure unit tier) ------------------------------------
def test_parse_fault_spec_grammar():
    rules = parse_fault_spec(
        "push:timeout@p=0.05;server1:down@step=40..55;pull:corrupt@p=0.01;"
        "all:slow@p=0.5,ms=10;server0:down;push:kill@op=7")
    assert rules[0] == FaultRule(scope="push", kind="timeout", p=0.05)
    assert rules[1].server == 1 and rules[1].window == (40, 55)
    assert rules[2].kind == "corrupt" and rules[2].p == 0.01
    assert rules[3].latency_ms == 10 and rules[3].p == 0.5
    assert rules[4].window == (0, None)  # bare rule = always
    assert rules[5].window == (7, 7)     # single-op window
    # open-ended window
    (r,) = parse_fault_spec("server2:down@step=100..")
    assert r.window == (100, None)
    for bad in ("push:explode", "push:timeout@q=1", "flux:timeout",
                "push:timeout@p=x"):
        with pytest.raises(ValueError, match="bad BYTEPS_FAULT_SPEC"):
            parse_fault_spec(bad)


def test_parse_fault_spec_structured_errors():
    """Satellite: a malformed server index must surface as the structured
    'bad BYTEPS_FAULT_SPEC rule' error NAMING the grammar — not a bare
    ``invalid literal for int()`` — and so must every cond-value typo."""
    for bad, hint in [
        ("serverX:down", "server<N>"),
        ("server:down", "server<N>"),
        ("server1x:down", "server<N>"),
        ("worker1x:slow", "worker<N>"),
        ("push:timeout@p=x", "float"),
        ("push:kill@op=x", "int"),
        ("server1:down@step=1..y", "int"),
        ("all:slow@ms=fast", "int"),
        ("pull:hang", "worker"),   # hang is a worker-scope-only kind
        ("pull:join@step=1", "worker"),  # join is worker-scope-only too
        ("worker2:join", "step="),       # joins are a schedule: step=
        ("worker2:join@p=0.5", "step="),  # ...never a probability
    ]:
        with pytest.raises(ValueError) as ei:
            parse_fault_spec(bad)
        msg = str(ei.value)
        assert "bad BYTEPS_FAULT_SPEC rule" in msg, (bad, msg)
        assert hint in msg, (bad, msg)
        assert "invalid literal" not in msg, (bad, msg)


def test_fault_spec_round_trip_every_documented_form():
    """Satellite: parse → render (``rules_to_spec``) → parse reproduces
    every documented rule form exactly."""
    forms = [
        "push:timeout@p=0.05",
        "pull:corrupt@p=0.01",
        "server1:down@step=40..55",
        "server1:down",
        "server2:down@step=100..",
        "all:slow@p=0.5,ms=20",
        "init:kill@op=1",
        "push:kill@op=7",
        "worker:kill@step=8..",
        "worker:hang@step=3,ms=250",
        "worker:hang@step=3",  # default hang latency
        # per-worker straggler targeting (worker<N> scope): the bounded-
        # staleness smoke's slow worker, plus kill/hang variants
        "worker1:slow@ms=80",
        "worker0:kill@step=8..",
        "worker2:hang@step=3,ms=250",
        # deterministic mid-stream joins (scale-up elasticity): the
        # schedule forms of tests/test_join.py
        "worker2:join@step=12",
        "worker0:join@step=3..5",
        "worker4:join@step=7..",
    ]
    for form in forms:
        rules = parse_fault_spec(form)
        rendered = rules_to_spec(rules)
        assert parse_fault_spec(rendered) == rules, (form, rendered)
    # and the full multi-rule spec round-trips as a whole
    spec = ";".join(forms)
    rules = parse_fault_spec(spec)
    assert parse_fault_spec(rules_to_spec(rules)) == rules


def test_worker_scoped_rule_targets_one_worker():
    """Satellite: ``worker<N>`` restricts a worker-scope rule to the plan
    whose worker_id is N — the same BYTEPS_FAULT_SPEC string is handed to
    every worker, and exactly one of them becomes the deterministic
    straggler (slow fires per intercepted wire attempt) or victim."""
    (r,) = parse_fault_spec("worker1:slow@ms=1")
    assert r.scope == "worker" and r.worker == 1 and r.kind == "slow"
    target = FaultPlan([r], seed=0, worker_id=1)
    other = FaultPlan([r], seed=0, worker_id=0)
    for _ in range(4):
        target.intercept("push", 0)
        other.intercept("push", 0)
    assert target.counters()["slow"] == 4
    assert other.counters()["slow"] == 0
    # kill variant: only the targeted worker's plan returns the injection
    (k,) = parse_fault_spec("worker0:kill@op=1")
    assert (FaultPlan([k], seed=0, worker_id=0)
            .intercept("push", 0) is not None)
    assert (FaultPlan([k], seed=0, worker_id=1)
            .intercept("push", 0) is None)


def test_fault_plan_bit_identical_across_processes():
    """Satellite: same spec + seed + worker id ⇒ bit-identical injection
    schedule across two FRESH processes (the chaos smokes assume this;
    in-process determinism alone would miss hash-seed / env leakage)."""
    import os
    import subprocess
    import sys

    code = (
        "import json\n"
        "from byteps_tpu.common.faults import FaultPlan, parse_fault_spec\n"
        "plan = FaultPlan(parse_fault_spec("
        "'push:timeout@p=0.3;pull:corrupt@p=0.2;server0:down@op=50..60'),"
        " seed=11, worker_id=3)\n"
        "sched = []\n"
        "for i in range(300):\n"
        "    inj = plan.intercept('push' if i % 2 == 0 else 'pull', i % 2)\n"
        "    sched.append(None if inj is None else"
        " [inj.kind, inj.corrupt_at])\n"
        "print(json.dumps([sched, plan.counters()], sort_keys=True))\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outs = []
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, timeout=120,
            env={**os.environ, "PYTHONPATH": repo,
                 "PYTHONHASHSEED": "random"},
        )
        assert r.returncode == 0, r.stderr.decode()
        outs.append(r.stdout)
    assert outs[0] == outs[1]
    assert b"timeout" in outs[0]  # sanity: the schedule actually fired


def test_fault_plan_deterministic_from_seed():
    spec = "push:timeout@p=0.3;pull:corrupt@p=0.2"
    a = FaultPlan(parse_fault_spec(spec), seed=7, worker_id=1)
    b = FaultPlan(parse_fault_spec(spec), seed=7, worker_id=1)
    seq_a = [(a.intercept("push", 0) or None) and "t" for _ in range(200)]
    seq_b = [(b.intercept("push", 0) or None) and "t" for _ in range(200)]
    assert seq_a == seq_b
    assert a.counters() == b.counters()
    # a different worker id draws a different (but still seeded) schedule
    c = FaultPlan(parse_fault_spec(spec), seed=7, worker_id=2)
    [c.intercept("push", 0) for _ in range(200)]
    assert c.counters() != {}  # sanity: counters populated


def test_fault_plan_window_ticks_per_op():
    (r,) = parse_fault_spec("server1:down@step=3..4")
    plan = FaultPlan([r], seed=0)
    hits = [plan.intercept("push", 1) is not None for _ in range(6)]
    # ops 3 and 4 (1-indexed) fall in the window — including retries,
    # which is what lets a transient window expire under pure retry
    assert hits == [False, False, True, True, False, False]
    # ops against another server never match
    plan2 = FaultPlan([r], seed=0)
    assert all(plan2.intercept("push", 0) is None for _ in range(6))


# ---- csrc golden: version-safe replay dedupe --------------------------------
def _serve(port, num_workers=1, **kw):
    start_server(port=port, num_workers=num_workers, engine_threads=2,
                 async_mode=False, **kw)
    return [("127.0.0.1", port)]


def test_push_replay_dedupe_golden():
    """A re-sent push carrying the same (worker, key, version) — the retry
    engine's replay after a lost ack — must be summed exactly once."""
    port = BASE_PORT + 1
    _serve(port, num_workers=2)
    c0 = NativeClient("127.0.0.1", port)
    c1 = NativeClient("127.0.0.1", port)
    n = 64
    rng = np.random.default_rng(5)
    x0 = rng.standard_normal(n).astype(np.float32)
    x1 = rng.standard_normal(n).astype(np.float32)
    c0.init_key(0, n * 4)
    b0 = x0.view(np.uint8).ravel()
    b1 = x1.view(np.uint8).ravel()
    # round 1: worker 0's push arrives THREE times (two replays)
    for _ in range(3):
        c0.push(0, b0, 0, worker_id=0, version=1, crc=wire_crc32(b0))
    c1.push(0, b1, 0, worker_id=1, version=1, crc=wire_crc32(b1))
    out = np.empty(n * 4, np.uint8)
    got = c0.pull(0, out, 1, 0)
    np.testing.assert_array_equal(out[:got].view(np.float32), x0 + x1)

    # round 2 pipelined while round 2 is still open for worker 1: worker
    # 0's v2 goes to the DEFERRED queue — its replay must dedupe there too
    for _ in range(2):
        c0.push(0, b0, 0, worker_id=0, version=2, crc=wire_crc32(b0))
    c1.push(0, b1, 0, worker_id=1, version=2, crc=wire_crc32(b1))
    got = c0.pull(0, out, 2, 0)
    np.testing.assert_array_equal(out[:got].view(np.float32), x0 + x1)

    # unversioned pushes (version=0, the legacy wire) never dedupe:
    # round 3 takes worker 0's push once and worker 1's once as before
    c0.push(0, b0, 0, worker_id=0, version=3, crc=wire_crc32(b0))
    c1.push(0, b1, 0, worker_id=1, version=3, crc=wire_crc32(b1))
    got = c0.pull(0, out, 3, 0)
    np.testing.assert_array_equal(out[:got].view(np.float32), x0 + x1)
    c0.shutdown()
    c1.shutdown()
    c0.close()
    c1.close()


def test_push_crc_mismatch_rejected_and_not_summed():
    """A corrupted-but-checksummed push is rejected (retryable
    WireCorruption), and the round sum proves it was never applied."""
    port = BASE_PORT + 2
    _serve(port, num_workers=1)
    c = NativeClient("127.0.0.1", port)
    n = 32
    x = np.arange(n, dtype=np.float32)
    b = x.view(np.uint8).ravel()
    c.init_key(0, n * 4)
    crc = wire_crc32(b)
    bad = b.copy()
    bad[5] ^= 0xFF
    with pytest.raises(WireCorruption, match="crc mismatch"):
        c.push(0, bad, 0, worker_id=0, version=1, crc=crc)
    # the pristine re-send (same version) completes the round correctly
    c.push(0, b, 0, worker_id=0, version=1, crc=crc)
    out = np.empty(n * 4, np.uint8)
    got = c.pull(0, out, 1, 0)
    np.testing.assert_array_equal(out[:got].view(np.float32), x)
    # checksummed pull: the returned crc verifies round-trip
    got2, rcrc = c.pull(0, out, 1, 0, want_crc=True)
    assert rcrc == wire_crc32(out[:got2])
    c.shutdown()
    c.close()


# ---- PSWorker retry engine --------------------------------------------------
def test_worker_retries_injected_timeouts_and_corruption(monkeypatch):
    """Direct PSWorker loop under injected push-ack loss (the op WAS
    applied — replay dedupe keeps sums exact) and pull corruption
    (detected by the response CRC)."""
    monkeypatch.setenv("BYTEPS_RETRY_LIMIT", "6")
    monkeypatch.setenv("BYTEPS_RETRY_BACKOFF_MS", "2")
    monkeypatch.setenv(
        "BYTEPS_FAULT_SPEC", "push:timeout@p=0.25;pull:corrupt@p=0.25")
    monkeypatch.setenv("BYTEPS_FAULT_SEED", "3")
    port = BASE_PORT + 3
    servers = _serve(port, num_workers=1)
    w = PSWorker(servers=servers, worker_id=0)
    x = np.linspace(-1, 1, 256, dtype=np.float32)
    w.init_key(1, x.nbytes)
    for _ in range(25):
        np.testing.assert_array_equal(w.push_pull(1, x), x)
    counters = w.get_counters()
    assert counters["retries"] > 0, counters
    assert counters["injected_timeout"] > 0, counters
    assert counters["injected_corrupt"] > 0, counters
    assert counters["crc_errors"] > 0, counters
    assert counters["give_ups"] == 0, counters
    w.shutdown()


def test_shutdown_dead_socket_branch_and_debug_log(monkeypatch):
    """Satellite: PSWorker.shutdown() must send kShutdown on a FRESH
    connection when the pooled one is dead (or the server's exit count
    never completes), and the server-already-gone branch logs at debug
    WITH the server index instead of swallowing bare."""
    monkeypatch.setenv("BYTEPS_RETRY_LIMIT", "0")  # fail fast to kill conn
    port = BASE_PORT + 4
    servers = _serve(port, num_workers=1)
    w = PSWorker(servers=servers, worker_id=0, recv_timeout_ms=300)
    x = np.ones(8, np.float32)
    w.init_key(2, x.nbytes)
    w.push_pull(2, x)
    # pull a round that will never exist -> socket-level recv timeout
    # kills the connection (and retry_limit=0 surfaces it immediately)
    with pytest.raises(TimeoutError):
        w.pull(2, 8, version=99)
    assert w._tls.conns[2 % 1].is_dead()
    w.shutdown()  # dead pooled conn -> kShutdown rides a fresh connection
    lib = load_lib()
    deadline = time.time() + 5
    while time.time() < deadline and lib.bps_local_init(3, 32) != -10:
        time.sleep(0.05)
    assert lib.bps_local_init(3, 32) == -10  # server counted the shutdown

    # server gone: a second worker's shutdown logs the failure at debug
    # (the byteps_tpu root logger has propagate=False, so attach a
    # handler directly instead of relying on caplog's root handler)
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    srv_log = logging.getLogger("byteps_tpu.server")
    cap = _Capture(level=logging.DEBUG)
    old_level = srv_log.level
    srv_log.addHandler(cap)
    srv_log.setLevel(logging.DEBUG)
    try:
        w2 = PSWorker(servers=servers, worker_id=0, timeout_ms=500)
        w2.shutdown()
    finally:
        srv_log.removeHandler(cap)
        srv_log.setLevel(old_level)
    assert any("shutdown of server 0 failed" in m for m in records), records


# ---- tier-1 chaos smoke (full DcnCore pipeline) -----------------------------
def test_chaos_smoke_dcncore_converges_with_retries(monkeypatch):
    """THE tier-1 chaos smoke: fixed seed, two fault kinds (push-ack loss
    + pull corruption) through the full COMPRESS/PUSH/PULL/DECOMPRESS
    pipeline. Asserts (a) every round's push_pull values converge to the
    clean expectation, (b) retry counters fired, (c) no credit leaked."""
    from byteps_tpu.common import config as config_mod
    from byteps_tpu.common.dcn_adapter import DcnCore

    monkeypatch.setenv("BYTEPS_RETRY_LIMIT", "6")
    monkeypatch.setenv("BYTEPS_RETRY_BACKOFF_MS", "2")
    monkeypatch.setenv(
        "BYTEPS_FAULT_SPEC", "push:timeout@p=0.2;pull:corrupt@p=0.2")
    monkeypatch.setenv("BYTEPS_FAULT_SEED", "1")
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    config_mod.reset_config()
    port = BASE_PORT + 5
    _serve(port, num_workers=1)
    core = DcnCore(servers=[("127.0.0.1", port)])
    try:
        rng = np.random.default_rng(0)
        flat = rng.standard_normal(16384).astype(np.float32)
        for _ in range(20):
            h = core.push_pull_async(flat, name="chaos_smoke")
            out = DcnCore.assemble(h, timeout=60.0)
            # one worker: the round sum IS the pushed vector, bit-exact
            np.testing.assert_array_equal(out, flat)
        counters = core.worker.get_counters()
        assert counters["retries"] > 0, counters
        assert counters["injected_timeout"] > 0, counters
        assert counters["injected_corrupt"] > 0, counters
        assert counters["give_ups"] == 0, counters
        # no credit leaked across all those retries
        sched = core.scheduler
        assert sched._credits == sched._credit_total
    finally:
        core.shutdown()


# ---- acceptance: transient server-down window (slow tier) -------------------
@pytest.mark.slow
def test_bit_identical_sums_under_timeouts_and_down_window(monkeypatch):
    """Acceptance criterion: 5% injected recv timeouts plus one 15-step
    server-down window; a 2-worker multi-round push_pull workload must
    complete with BIT-IDENTICAL sums to the clean run (replay dedupe +
    retry/backoff outlasting the window), with retry counters fired."""
    import threading

    rng = np.random.default_rng(11)
    keys = [0, 1]
    rounds = 30
    n = 512
    data = {w: {k: rng.standard_normal(n).astype(np.float32)
                for k in keys} for w in range(2)}

    def run(port, spec):
        monkeypatch.setenv("BYTEPS_RETRY_LIMIT", "30")
        monkeypatch.setenv("BYTEPS_RETRY_BACKOFF_MS", "1")
        monkeypatch.setenv("BYTEPS_FAULT_SPEC", spec)
        monkeypatch.setenv("BYTEPS_FAULT_SEED", "2")
        from byteps_tpu.common import config as config_mod

        config_mod.reset_config()
        servers = _serve(port, num_workers=2)
        results = {}
        counters = {}

        def body(widx):
            w = PSWorker(servers=servers, worker_id=widx)
            for k in keys:
                w.init_key(k, n * 4)
            w.barrier()
            out = []
            for _ in range(rounds):
                vs = [w.push(k, data[widx][k]) for k in keys]
                out.append([w.pull(k, n, v).copy()
                            for k, v in zip(keys, vs)])
            results[widx] = out
            counters[widx] = w.get_counters()
            w.shutdown()

        ts = [threading.Thread(target=body, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
            assert not t.is_alive(), "worker hung under chaos"
        stop_server()
        return results, counters

    clean, _ = run(BASE_PORT + 6, "")
    chaos, counters = run(
        BASE_PORT + 7,
        "push:timeout@p=0.05;server0:down@step=40..55")
    # the chaos run saw faults and healed
    total = {k: sum(c[k] for c in counters.values())
             for k in counters[0]}
    assert total["retries"] > 0, total
    assert total["injected_timeout"] + total["injected_down"] > 0, total
    # ...and every round of every worker matches the clean run BIT-exactly
    for widx in range(2):
        for r in range(rounds):
            for ki, k in enumerate(keys):
                np.testing.assert_array_equal(
                    chaos[widx][r][ki], clean[widx][r][ki],
                    err_msg=f"worker {widx} round {r} key {k}")


# ---- failover + graceful degradation (slow tier) ----------------------------
@pytest.mark.slow
def test_health_monitor_failover_to_survivor(monkeypatch):
    """An open-ended down window on server 1 trips the ping health monitor
    after K misses; server 1's keys fail over (rendezvous over the live
    set) to server 0 and push_pull keeps working with fresh rounds."""
    import os
    import subprocess
    import sys

    p0, p1 = BASE_PORT + 8, BASE_PORT + 9
    _serve(p0, num_workers=1)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "from byteps_tpu.server import start_server;"
         "from byteps_tpu.server.native import load_lib;"
         "start_server(port=%d, num_workers=1, engine_threads=1,"
         "async_mode=False); load_lib().bps_server_wait()" % p1],
        env={**os.environ, "PYTHONPATH": repo},
    )
    try:
        monkeypatch.setenv("BYTEPS_RETRY_LIMIT", "2")
        monkeypatch.setenv("BYTEPS_RETRY_BACKOFF_MS", "1")
        # server 1 goes down from plan-op 30 onward, forever
        monkeypatch.setenv("BYTEPS_FAULT_SPEC", "server1:down@op=30..")
        monkeypatch.setenv("BYTEPS_HEALTH_INTERVAL_MS", "50")
        monkeypatch.setenv("BYTEPS_HEALTH_MISS_LIMIT", "3")
        from byteps_tpu.common import config as config_mod

        config_mod.reset_config()
        servers = [("127.0.0.1", p0), ("127.0.0.1", p1)]
        w = PSWorker(servers=servers, worker_id=0)
        x = np.arange(64, dtype=np.float32)
        for k in (0, 1):  # key 0 -> server 0, key 1 -> server 1
            w.init_key(k, x.nbytes)
            np.testing.assert_array_equal(w.push_pull(k, x), x)
        assert w.server_for(1) == 1
        # monitor pings tick the plan past op 30 -> server 1 "dies";
        # K misses at 50 ms intervals mark it dead
        deadline = time.time() + 15
        while time.time() < deadline and 1 in w.live_servers():
            time.sleep(0.05)
        assert w.live_servers() == {0}, "health monitor never failed over"
        assert w.server_for(1) == 0  # remapped to the survivor
        # new rounds work against the survivor (fresh round numbering,
        # lazy re-init from the recorded key size)
        for _ in range(3):
            np.testing.assert_array_equal(w.push_pull(1, x), x)
        counters = w.get_counters()
        assert counters["failovers"] == 1, counters
        assert counters["reinits"] >= 1, counters
        w.shutdown()
    finally:
        if proc.poll() is None:
            proc.kill()


@pytest.mark.slow
def test_degraded_local_fallback_when_all_servers_dead(monkeypatch):
    """With NO live servers and BYTEPS_DEGRADED_OK (default), DcnCore
    degrades push_pull to the local contribution instead of failing the
    handle; with it off, the handle fails loudly."""
    from byteps_tpu.common import config as config_mod
    from byteps_tpu.common.dcn_adapter import DcnCore
    from byteps_tpu.common.scheduler import PartitionFailure

    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    config_mod.reset_config()
    port = BASE_PORT + 10
    _serve(port, num_workers=1)
    core = DcnCore(servers=[("127.0.0.1", port)])
    try:
        flat = np.linspace(0, 1, 4096, dtype=np.float32)
        h = core.push_pull_async(flat, name="pre")
        np.testing.assert_array_equal(DcnCore.assemble(h, 30.0), flat)
        core.worker.fail_over(0, barrier=False)  # the only server "dies"
        assert not core.worker.has_live_servers()
        h = core.push_pull_async(flat, name="post")
        out = DcnCore.assemble(h, 30.0)
        np.testing.assert_array_equal(out, flat)  # local contribution
        assert core.worker.get_counters()["ici_fallbacks"] >= 1
    finally:
        core.shutdown()
        stop_server()

    # strict mode: degraded_ok=False fails the handle instead
    cfg = dataclasses.replace(config_mod.Config.from_env(),
                              degraded_ok=False, num_worker=1)
    config_mod.set_config(cfg)
    port = BASE_PORT + 11
    _serve(port, num_workers=1)
    core = DcnCore(servers=[("127.0.0.1", port)])
    try:
        flat = np.linspace(0, 1, 4096, dtype=np.float32)
        core.worker.fail_over(0, barrier=False)
        h = core.push_pull_async(flat, name="strict")
        with pytest.raises(PartitionFailure, match="no live summation"):
            DcnCore.assemble(h, 30.0)
    finally:
        core.shutdown()


# ---- elastic worker membership (leases, epochs, quorum sums) ----------------
def test_lease_eviction_quorum_scaling_and_rejoin_golden():
    """Golden pin of the csrc membership layer end to end: (a) a worker
    that contributed to the open round and then went silent is evicted
    after BYTEPS_WORKER_LEASE_MS and the round closes QUORUM-SCALED
    (sum × live/contributors — the global average stays unbiased);
    (b) survivor-only rounds are bit-identical to a 1-worker clean run
    (no scaling multiply on clean rounds); (c) the survivor adopts the
    bumped epoch from the response headers (one membership event, live
    count 1); (d) a restarted worker's first push is REFUSED with
    'worker evicted', auto-rejoins (heartbeat re-admit + kRounds
    watermark adoption), and the next rounds sum both workers again;
    (e) the server exits once every worker departed or was evicted."""
    port = BASE_PORT + 12
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=False, lease_ms=400)
    servers = [("127.0.0.1", port)]
    lib = load_lib()
    rng = np.random.default_rng(7)
    x0 = rng.standard_normal(64).astype(np.float32)
    x1 = rng.standard_normal(64).astype(np.float32)

    w0 = PSWorker(servers=servers, worker_id=0, health_interval_ms=50)
    w1 = PSWorker(servers=servers, worker_id=1, health_interval_ms=0)
    try:
        w0.init_key(0, 256)
        w1.init_key(0, 256)
        v0 = w0.push(0, x0)
        w1.push(0, x1)
        np.testing.assert_array_equal(w0.pull(0, 64, v0), x0 + x1)

        # w1 contributes the next round, then "dies" (silent)
        w1.push(0, x1)
        w1.close()
        deadline = time.time() + 10
        while time.time() < deadline and lib.bps_server_epoch() == 0:
            time.sleep(0.05)
        assert lib.bps_server_epoch() == 1, "lease eviction never fired"

        # the open round closes scaled to the survivors: (x0+x1) · 1/2
        v0 = w0.push(0, x0)
        np.testing.assert_array_equal(
            w0.pull(0, 64, v0), (x0 + x1) * np.float32(0.5))

        # surviving epoch: bit-identical to a 1-worker clean run, and the
        # round's OWN live count (from the response's epoch stamp) is the
        # survivor membership
        for _ in range(3):
            v0 = w0.push(0, x0)
            np.testing.assert_array_equal(w0.pull(0, 64, v0), x0)
        assert w0.last_round_live() == 1
        c = w0.get_counters()
        assert c["membership_events"] == 1, c
        assert c["live_pods"] == 1, c
        assert w0.live_pods() == 1

        # restarted worker 1 (fresh process state): push refused, inline
        # rejoin (ping re-admit + sync_rounds), stage-level re-mint works
        w1b = PSWorker(servers=servers, worker_id=1, health_interval_ms=0)
        with pytest.raises(WorkerEvictedError):
            w1b.push(0, x1)
        cb = w1b.get_counters()
        assert cb["rejoins"] == 1, cb
        # watermarks adopted: the next mint continues the server sequence
        versions, nbytes = w1b.export_rounds()
        assert versions.get(0, 0) >= 5 and nbytes.get(0) == 256, (versions,
                                                                  nbytes)
        w1b.push(0, x1)
        v0 = w0.push(0, x0)
        np.testing.assert_array_equal(w0.pull(0, 64, v0), x0 + x1)
        assert w0.live_pods() == 2  # rejoin epoch adopted

        # teardown: one departed (w0's goodbye) + one evicted is enough
        # for the server to exit — kill w1b silently again first
        w1b.close()
        deadline = time.time() + 10
        while time.time() < deadline and lib.bps_server_epoch() < 3:
            time.sleep(0.05)
        w0.shutdown()
        deadline = time.time() + 10
        while time.time() < deadline and lib.bps_local_init(9, 32) != -10:
            time.sleep(0.05)
        assert lib.bps_local_init(9, 32) == -10, (
            "server must exit without the evicted worker's goodbye")
    finally:
        for w in (w0, w1):
            try:
                w.close()
            except Exception:
                pass
        stop_server()


def test_round_epoch_stamp_and_stale_round_guard(monkeypatch):
    """Two review-hardening pins on the membership layer. (a) A round
    that CLOSED under the old membership but is PULLED after an eviction
    is stamped with its round-close epoch, so the puller's averaging
    divisor is the OLD live count — not the shrunken current one (a
    2-worker sum divided by 1 would double that step's gradient).
    (b) A worker evicted mid-round whose heartbeat already re-admitted
    it (monitor rejoin after a wedge) may re-send the round it was
    evicted out of; that round closed WITHOUT it, so the push is REFUSED
    as stale ('worker evicted mid-round') instead of crediting a stale
    gradient to the currently open round."""
    from byteps_tpu.common import config as config_mod

    monkeypatch.setenv("DMLC_NUM_WORKER", "2")
    config_mod.reset_config()  # epoch-0 live seed = configured membership
    port = BASE_PORT + 18
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=False, lease_ms=500)
    servers = [("127.0.0.1", port)]
    lib = load_lib()
    x0 = np.linspace(0, 1, 64, dtype=np.float32)
    x1 = np.linspace(2, 3, 64, dtype=np.float32)
    w0 = PSWorker(servers=servers, worker_id=0, health_interval_ms=50)
    w1 = PSWorker(servers=servers, worker_id=1, health_interval_ms=0)
    try:
        w0.init_key(0, 256)
        w1.init_key(0, 256)
        # round 1 closes at FULL membership; nobody pulls it yet
        v0 = w0.push(0, x0)
        w1.push(0, x1)
        # worker 1 dies; wait out the eviction (epoch bumps)
        w1.close()
        deadline = time.time() + 10
        while time.time() < deadline and lib.bps_server_epoch() == 0:
            time.sleep(0.05)
        assert lib.bps_server_epoch() == 1
        # (a) the delayed pull of the pre-eviction round: full sum AND
        # the pre-eviction live count as its divisor authority
        np.testing.assert_array_equal(w0.pull(0, 64, v0), x0 + x1)
        assert w0.last_round_live() == 2, (
            "round closed at full membership must carry live=2 even "
            "when pulled after the eviction")

        # (b) re-admit worker 1 via a bare heartbeat (no rejoin), then
        # re-send the round it missed: round 2 closes without it first
        v0 = w0.push(0, x0)
        np.testing.assert_array_equal(w0.pull(0, 64, v0), x0)
        w1c = PSWorker(servers=servers, worker_id=1, health_interval_ms=0)
        w1c.ping(0)  # heartbeat re-admits (epoch 2) — but NO round sync
        # recreate the wedged worker's pre-eviction state: it had MINTED
        # round 2 before going silent (counter = 2, push never landed)
        w1c.adopt_rounds({0: 2}, {0: 256})
        with pytest.raises(WorkerEvictedError, match="stale round"):
            # version 2 = the round that closed without worker 1
            # (> its applied watermark 1, <= the key's closed-round 2)
            w1c.push_bytes(0, x1.view(np.uint8).ravel(), 0, version=2)
        # the refusal triggered the inline rejoin: watermarks adopted,
        # and a FRESH push now joins the open round correctly
        versions, _ = w1c.export_rounds()
        assert versions.get(0) == 2, versions
        w1c.push(0, x1)
        v0 = w0.push(0, x0)
        np.testing.assert_array_equal(w0.pull(0, 64, v0), x0 + x1)
        assert w0.last_round_live() == 2
        w1c.close()
    finally:
        for w in (w0, w1):
            try:
                w.close()
            except Exception:
                pass
        stop_server()
        config_mod.reset_config()


def test_worker_death_chaos_smoke_survivor_completes(monkeypatch):
    """THE tier-1 worker-death smoke (acceptance criterion): 2 DcnCore
    workers, ``worker:kill`` fires on worker 1 mid-run (its 4th-round
    push never leaves). The survivor's training run COMPLETES — no hang:
    the lease eviction re-targets the stalled round — with (a) pre-kill
    rounds summing both workers, (b) every surviving-epoch round
    BIT-IDENTICAL to a 1-worker clean run (= the pushed vector itself,
    raw wire), (c) exactly one eviction + epoch bump in the counters,
    (d) zero credit leak, and (e) the victim's handle failing with
    WorkerKilledError instead of wedging its thread."""
    import threading

    from byteps_tpu.common import config as config_mod
    from byteps_tpu.common.dcn_adapter import DcnCore
    from byteps_tpu.common.faults import WorkerKilledError
    from byteps_tpu.common.scheduler import PartitionFailure

    monkeypatch.setenv("DMLC_NUM_WORKER", "2")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    config_mod.reset_config()
    port = BASE_PORT + 14
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=False, lease_ms=400)
    servers = [("127.0.0.1", port)]
    lib = load_lib()
    rng = np.random.default_rng(3)
    x0 = rng.standard_normal(4096).astype(np.float32)
    x1 = rng.standard_normal(4096).astype(np.float32)
    kill_round = 3   # victim dies on its round-4 push:
    # plan ops = init(1) + {push,pull} per round → round-4 push = op 8
    total_rounds = 8
    cores = {}
    results = {0: [], 1: []}
    errors = {}
    barrier = threading.Barrier(2, timeout=60)

    def body(widx, flat, spec):
        core = DcnCore(servers=servers, worker_id=widx,
                       fault_specs=[spec] if spec else None,
                       health_interval_ms=50 if widx == 0 else 0)
        cores[widx] = core
        barrier.wait()
        for r in range(total_rounds):
            h = core.push_pull_async(flat, name="wd")
            try:
                results[widx].append(DcnCore.assemble(h, timeout=60.0))
            except PartitionFailure as e:
                errors[widx] = e
                return

    ts = [
        threading.Thread(target=body, args=(0, x0, None)),
        threading.Thread(target=body, args=(1, x1, "worker:kill@step=8..")),
    ]
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
            assert not t.is_alive(), "worker hung under worker death"

        # victim: died on round 4's push, handle failed diagnosably
        assert len(results[1]) == kill_round
        assert isinstance(errors[1].cause, WorkerKilledError), errors[1]

        # survivor completed ALL rounds: pre-kill rounds sum both
        # workers, surviving-epoch rounds are bit-identical to the
        # 1-worker clean run (raw wire single push = memcpy of x0)
        assert len(results[0]) == total_rounds and 0 not in errors
        for r in range(kill_round):
            np.testing.assert_array_equal(results[0][r], x0 + x1,
                                          err_msg=f"round {r}")
        for r in range(kill_round, total_rounds):
            np.testing.assert_array_equal(results[0][r], x0,
                                          err_msg=f"round {r}")

        # exactly one eviction + epoch bump, seen and adopted
        assert lib.bps_server_epoch() == 1
        c = cores[0].worker.get_counters()
        assert c["membership_events"] == 1, c
        assert c["live_pods"] == 1, c
        assert cores[0].live_size() == 1

        # zero credit leak across the stall + eviction
        sched = cores[0].scheduler
        assert sched._credits == sched._credit_total
    finally:
        try:
            if 1 in cores:
                # victim "process death": no goodbye, just drop sockets
                cores[1].scheduler.shutdown()
                for w in cores[1].workers:
                    w.close()
            if 0 in cores:
                cores[0].shutdown()
        finally:
            stop_server()
            config_mod.reset_config()


def test_handle_deadline_caps_every_wait(monkeypatch):
    """Acceptance: no configuration can make Handle.wait() block past
    BYTEPS_HANDLE_DEADLINE_MS — timeout=None and any larger explicit
    timeout are capped, and the expiry is a diagnosable StallError
    carrying the attached per-stage/per-server counters."""
    from byteps_tpu.common import config as config_mod
    from byteps_tpu.common.scheduler import Handle, StallError

    monkeypatch.setenv("BYTEPS_HANDLE_DEADLINE_MS", "300")
    config_mod.reset_config()
    try:
        h = Handle("stalled", 2)
        h._partition_done(0, "done-part")
        h.diag = lambda: {"retries": 7, "live_servers": [0],
                          "health_last_probe_age_ms": 12}
        t0 = time.time()
        with pytest.raises(StallError) as ei:
            h.wait(None)  # would block FOREVER without the deadline
        assert time.time() - t0 < 5.0
        e = ei.value
        assert isinstance(e, TimeoutError)  # existing callers still catch
        assert e.deadline_capped
        assert e.done_parts == [0] and e.total_parts == 2
        # the stall report shows WHY failover/retry did or didn't fire
        assert "retries" in str(e) and "health_last_probe_age_ms" in str(e)
        # an explicit timeout larger than the cap is still capped
        t0 = time.time()
        with pytest.raises(StallError):
            h.wait(60.0)
        assert time.time() - t0 < 5.0
        # a failing diag callback must not mask the stall
        h.diag = lambda: 1 / 0
        with pytest.raises(StallError, match="diag_error"):
            h.wait(None)
    finally:
        monkeypatch.delenv("BYTEPS_HANDLE_DEADLINE_MS", raising=False)
        config_mod.reset_config()


def test_race_smoke_tsan():
    """Satellite: the csrc TSAN race smoke as a buildable one-shot
    (scripts/race_smoke.sh), run from tier-1 when a TSAN toolchain is
    present — server-side concurrency changes (this PR adds lease state
    beside the per-key slot mutexes) stay race-clean."""
    import os
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        ["bash", os.path.join(repo, "scripts", "race_smoke.sh")],
        capture_output=True, timeout=570,
    )
    if r.returncode == 77:
        pytest.skip("no ThreadSanitizer toolchain in this image")
    assert r.returncode == 0, (r.stdout.decode()[-2000:],
                               r.stderr.decode()[-2000:])
    assert b"race_smoke: OK" in r.stdout


@pytest.mark.slow
def test_worker_hang_wedge_then_rejoin(monkeypatch):
    """``worker:hang``: the worker wedges (ops block, heartbeats stop),
    the server lease evicts it, peers keep summing over the live set;
    when the window expires the worker's monitor heartbeat re-admits it
    and it resumes with adopted rounds."""
    from byteps_tpu.common import config as config_mod

    config_mod.reset_config()
    port = BASE_PORT + 16
    start_server(port=port, num_workers=2, engine_threads=2,
                 async_mode=False, lease_ms=300)
    servers = [("127.0.0.1", port)]
    lib = load_lib()
    x0 = np.linspace(-1, 1, 64, dtype=np.float32)
    x1 = np.linspace(1, 2, 64, dtype=np.float32)
    from byteps_tpu.common.faults import FaultPlan

    # w1 wedges for 1.2 s on its plan-op 5 (round-2 push)
    plan = FaultPlan(parse_fault_spec("worker:hang@step=4,ms=1200"),
                     seed=0, worker_id=1)
    w0 = PSWorker(servers=servers, worker_id=0, health_interval_ms=50)
    w1 = PSWorker(servers=servers, worker_id=1, health_interval_ms=50,
                  fault_plan=plan)
    try:
        w0.init_key(0, 256)  # w0 op: init
        w1.init_key(0, 256)  # w1 op 1 (+ping ops from its monitor)
        v0 = w0.push(0, x0)
        w1.push(0, x1)
        np.testing.assert_array_equal(w0.pull(0, 64, v0), x0 + x1)

        # w1's next push hits the hang window (whichever op ticks 4th,
        # monitor pings included — the window is per plan op), wedging
        # it past the lease: w0's rounds continue over the live set
        import threading

        def wedged():
            try:
                w1.push(0, x1)
            except Exception:
                pass

        t = threading.Thread(target=wedged)
        t.start()
        deadline = time.time() + 10
        while time.time() < deadline and lib.bps_server_epoch() == 0:
            time.sleep(0.05)
        assert lib.bps_server_epoch() >= 1, "wedged worker never evicted"
        v0 = w0.push(0, x0)
        out = w0.pull(0, 64, v0)
        # w1 MAY have contributed its round-2 push before wedging;
        # either way the round closes over the live set
        assert out.shape == (64,)
        t.join(timeout=30)
        assert not t.is_alive()

        # after the window the monitor's heartbeat re-admits w1
        deadline = time.time() + 15
        while time.time() < deadline and lib.bps_server_epoch() < 2:
            time.sleep(0.05)
        assert lib.bps_server_epoch() >= 2, "unwedged worker never rejoined"
    finally:
        for w in (w0, w1):
            try:
                w.close()
            except Exception:
                pass
        stop_server()
        config_mod.reset_config()


def test_mixed_degraded_handle_scales_per_partition(monkeypatch):
    """A handle can be MIXED: partition 0 aggregated globally before the
    last server died, partition 1 degraded to the local contribution.
    Averaging adapters must scale slice-by-slice — global slices divide
    by size(), degraded slices stay local."""
    torch = pytest.importorskip("torch")
    import dataclasses as dc

    import byteps_tpu.torch as bt
    from byteps_tpu.common.config import Config
    from byteps_tpu.common.scheduler import Handle

    monkeypatch.setattr(bt._state, "initialized", True)
    monkeypatch.setattr(bt._state, "cfg", dc.replace(Config(), num_worker=4))
    h = Handle("t", 2)
    h._partition_done(0, np.full(4, 8.0, np.float32))  # 4-worker global sum
    h._partition_done(1, np.full(4, 3.0, np.float32))  # degraded local value
    h.average = True
    h.degraded_parts = {1: (4, 4)}  # part 1 covers elements [4, 8)
    h.tensor = torch.zeros(8)
    out = bt.synchronize(h)
    np.testing.assert_array_equal(
        out.numpy(), np.array([2, 2, 2, 2, 3, 3, 3, 3], np.float32))
