"""Always-on telemetry plane (docs/observability.md).

Tier-1: registry semantics (counters/gauges/histogram percentiles,
BYTEPS_METRICS_ON=0 no-op gate), the PINNED hot-path overhead budget
(per-op bound + the metrics share of a real DcnCore round < 2%),
counter totals surviving ``retire_nic`` + owner failover, the flight
recorder's per-step ring + FAULT events, and THE acceptance smoke: a
stalled DcnCore handle raises a StallError whose diag carries per-NIC
wire counters + credit pools and whose flight-recorder post-mortem
carries per-step stage dwell p50/p99 and the recent FAULT events.
"""

import collections
import time

import numpy as np
import pytest

from byteps_tpu.common.flight_recorder import (
    get_flight_recorder,
    reset_flight_recorder,
)
from byteps_tpu.common.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    reset_registry,
)
from byteps_tpu.server import PSWorker, retire_nic, start_server, stop_server

BASE_PORT = 26200


@pytest.fixture(autouse=True)
def _cleanup_server():
    yield
    stop_server()


def _serve(port, num_workers=1, **kw):
    start_server(port=port, num_workers=num_workers, engine_threads=2,
                 async_mode=False, **kw)
    return [("127.0.0.1", port)]


# ---- registry semantics -----------------------------------------------------
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("a")
    c.inc()
    c.inc(4)
    assert c.value() == 5
    assert reg.counter("a") is c  # cached handle

    g = reg.gauge("g")
    g.set(3)
    g.set(1)
    assert g.value() == 1 and g.max() == 3

    h = reg.histogram("h")
    for v in (10, 10, 10, 10, 10, 10, 10, 10, 10, 1000):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 10 and s["min"] == 10 and s["max"] == 1000
    # p50 lands in the 10s bucket, p99 near the 1000 outlier — a 1-2-5
    # ladder is coarse, so assert the order of magnitude, not exactness
    assert s["p50"] <= 20
    assert s["p99"] >= 500
    assert s["sum"] == pytest.approx(1090)


def test_registry_snapshot_and_prefix_filter():
    reg = MetricsRegistry()
    reg.counter("x.one").inc(2)
    reg.counter("y.two").inc(3)
    reg.histogram("x.h").observe(7)
    snap = reg.snapshot()
    assert snap["counters"] == {"x.one": 2, "y.two": 3}
    only_x = reg.snapshot(prefix="x.")
    assert set(only_x["counters"]) == {"x.one"}
    assert set(only_x["histograms"]) == {"x.h"}


def test_metrics_off_gate(monkeypatch):
    monkeypatch.setenv("BYTEPS_METRICS_ON", "0")
    reset_registry()
    reg = get_registry()
    c = reg.counter("nope")
    c.inc(100)
    h = reg.histogram("nope.h")
    h.observe(5)
    assert c.value() == 0 and h.snapshot() == {"count": 0}
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_series_cap_drops_not_grows():
    from byteps_tpu.common import metrics as m

    reg = MetricsRegistry()
    for i in range(m._MAX_SERIES + 10):
        reg.counter(f"c{i}")
    assert reg.dropped_series == 10
    # dropped names return the shared no-op, not a crash
    reg.counter("c999999").inc()


# ---- overhead budget pin (satellite) ---------------------------------------
def test_metrics_hot_path_per_op_budget():
    """The registry's whole design contract is near-zero hot-path cost:
    pin counter inc and histogram observe under a generous per-op bound
    (typical is ~1 µs; the bound absorbs loaded CI hosts). If this
    fails, someone made the hot path allocate or take a global lock."""
    reg = MetricsRegistry()
    c = reg.counter("bench.c")
    h = reg.histogram("bench.h")
    N = 20000
    t0 = time.perf_counter()
    for _ in range(N):
        c.inc()
    per_inc = (time.perf_counter() - t0) / N
    t0 = time.perf_counter()
    for _ in range(N):
        h.observe(123.0)
    per_obs = (time.perf_counter() - t0) / N
    assert per_inc < 25e-6, f"counter inc {per_inc*1e6:.2f}us/op"
    assert per_obs < 50e-6, f"histogram observe {per_obs*1e6:.2f}us/op"


def test_span_hot_path_per_span_budget():
    """The always-on span ring shares the registry's contract: pin one
    span (two clock reads, a profiler annotation outside any session, a
    ring append; typical is ~1.5 us) under the same generous bound as a
    histogram observe, and the serve scheduler's entries per iteration at
    the numbers the cost estimate in docs/observability.md multiplies by:
    structural spans, and the spans it emits from its own stamps (the
    issue's four parts and the device steps its two reads end: a clock
    read and a ring append each, no annotation). If this fails, someone
    made the span path build a dict, take a lock or log."""
    import jax  # noqa: F401 — the annotation is live once jax is loaded

    from byteps_tpu.common.tracing import TraceRecorder, get_tracer
    from byteps_tpu.models import GPTConfig, gpt_init
    from byteps_tpu.serve import Request, Scheduler

    rec = TraceRecorder(enabled=False)
    N = 20000
    t0 = time.perf_counter()
    for i in range(N):
        with rec.span("bench.span", "S", (i,)):
            pass
    per_span = (time.perf_counter() - t0) / N
    assert per_span < 50e-6, f"span {per_span*1e6:.2f}us"

    cfg = GPTConfig.tiny()
    sched = Scheduler(gpt_init(jax.random.PRNGKey(0), cfg), cfg,
                      max_batch=2, prefill_chunk=8, block_size=4)
    rng = np.random.default_rng(0)
    sched.serve([Request(rid=i, max_new=6,
                         prompt=rng.integers(0, cfg.vocab_size, 10)
                         .astype(np.int32)) for i in range(4)])
    ring = get_tracer().spans()
    by_id = {e[3]: e for e in ring}
    per_iteration = collections.Counter()
    emitted = collections.Counter()
    for e in ring:
        if e[0] == "serve.iteration":
            per_iteration[e[3]] += 1
        elif e[0].startswith("serve.issue."):
            emitted[by_id[e[4]][4]] += 1    # under a dispatch span
        elif e[0].startswith("serve.device_step."):
            emitted[e[4]] += 1
        elif not e[0].startswith("serve.request."):
            per_iteration[e[4]] += 1
    # 8, and one more in an iteration whose prefill lane walks the prefix
    # index again before its chunk (serve.prefix.match, PR 55)
    assert max(per_iteration.values()) <= 9
    assert max(emitted.values()) <= 6 and set(emitted) <= set(per_iteration)
    assert len(ring) / len(per_iteration) <= 16


def test_metrics_overhead_under_two_percent_of_dcn_round(monkeypatch):
    """Registry-on vs registry-off DcnCore budget: count the metric ops
    one full push_pull round actually performs (instrumented classes),
    price them at the measured per-op cost, and assert the product is
    < 2% of the measured round time. Counting × pricing instead of a
    raw A/B wall-clock diff keeps the assertion deterministic on noisy
    CI hosts while still bounding the same quantity; the registry-OFF
    leg additionally proves the no-op gate works end to end."""
    from byteps_tpu.common import config as config_mod
    from byteps_tpu.common import metrics as m
    from byteps_tpu.common.dcn_adapter import DcnCore

    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    config_mod.reset_config()
    reset_registry()
    port = BASE_PORT
    servers = _serve(port)
    core = DcnCore(servers=servers)
    flat = np.random.default_rng(0).standard_normal(262144).astype(
        np.float32)
    try:
        # warm up (init, connection setup, first-trace costs)
        DcnCore.assemble(core.push_pull_async(flat, name="warm"))

        ops = [0]
        orig = (m.Counter.inc, m.Gauge.set, m.Histogram.observe)

        def counting(fn):
            def wrapped(self, *a, **k):
                ops[0] += 1
                return fn(self, *a, **k)
            return wrapped

        m.Counter.inc = counting(orig[0])
        m.Gauge.set = counting(orig[1])
        m.Histogram.observe = counting(orig[2])
        try:
            t0 = time.perf_counter()
            DcnCore.assemble(core.push_pull_async(flat, name="warm"))
            round_s = time.perf_counter() - t0
        finally:
            m.Counter.inc, m.Gauge.set, m.Histogram.observe = orig

        # price the ops at the measured (unwrapped) per-op cost
        c = MetricsRegistry().counter("price")
        N = 20000
        t0 = time.perf_counter()
        for _ in range(N):
            c.inc()
        per_op = (time.perf_counter() - t0) / N
        overhead = ops[0] * per_op
        assert ops[0] > 0  # the round really was instrumented
        assert overhead < 0.02 * round_s, (
            f"{ops[0]} metric ops x {per_op*1e6:.2f}us = "
            f"{overhead*1e3:.3f}ms on a {round_s*1e3:.1f}ms round")
    finally:
        core.shutdown()

    # registry-OFF leg: the same pipeline runs with every handle a no-op
    # (fresh server: the shutdown above was this 1-worker tier's goodbye,
    # so the first server has exited)
    monkeypatch.setenv("BYTEPS_METRICS_ON", "0")
    config_mod.reset_config()
    reset_registry()
    stop_server()  # release the in-process native server slot
    servers = _serve(port + 1)
    core2 = DcnCore(servers=servers)
    try:
        out = DcnCore.assemble(core2.push_pull_async(flat, name="off"))
        np.testing.assert_array_equal(out, flat)
        assert get_registry().snapshot()["counters"] == {}
    finally:
        core2.shutdown()


# ---- counter totals survive NIC retirement + failover (satellite) ----------
def test_counters_survive_retire_nic_and_owner_failover():
    """The per-PSWorker counter dicts die with their NIC; the registry
    totals must not. Two NICs count retries, one retires (the owner
    failover teardown path), the other keeps counting through the
    fence/export/adopt handoff — the registry total covers all of it,
    and the flight recorder holds the dead NIC's final snapshot."""
    from byteps_tpu.common.partition import OwnerTable
    from byteps_tpu.server import hand_off_owner

    servers = [("127.0.0.1", BASE_PORT + 7)]  # never contacted
    w0 = PSWorker(servers=servers, worker_id=0)
    w1 = PSWorker(servers=servers, worker_id=0)
    reg = get_registry()
    w0._count("retries")
    w1._count("retries", 2)
    assert reg.counter("psworker.retries").value() == 3

    owners = OwnerTable(2)
    live = hand_off_owner([w0, w1], owners, 1)  # fence+export+adopt+shrink
    assert live == {0, 1} and owners.live() == {0}
    retire_nic(w1, 1)  # export + close the dead NIC
    assert reg.counter("nic.retired").value() == 1
    # the dead NIC's final snapshot survives in the flight recorder
    evs = [e for e in get_flight_recorder().events()
           if e["event"] == "counters_export"]
    assert evs and evs[-1]["args"]["counters"]["retries"] == 2

    # the survivor keeps accumulating into the SAME totals
    w0._count("retries", 5)
    assert reg.counter("psworker.retries").value() == 8
    w0.close()


# ---- flight recorder --------------------------------------------------------
def test_flight_recorder_ring_and_events(monkeypatch):
    monkeypatch.setenv("BYTEPS_FLIGHT_RECORDER_STEPS", "4")
    reset_flight_recorder()
    fr = get_flight_recorder()
    reg = get_registry()
    reg.counter("c").inc()
    for s in range(1, 8):
        fr.on_step(s)
    steps = fr.steps()
    assert len(steps) == 4  # bounded ring
    assert [e["step"] for e in steps] == [4, 5, 6, 7]
    assert steps[-1]["counters"]["c"] == 1
    assert steps[-1]["step_ms"] is not None
    # step walltime became a first-class metric
    assert reg.histogram("train.step_ms").count() == 6
    fr.record_event("retry", {"key": np.int64(3)})  # sanitized at record
    evs = fr.events()
    assert evs[-1]["event"] == "retry" and evs[-1]["args"]["key"] == 3
    pm = fr.post_mortem(reason="test")
    assert pm["steps"] == steps and pm["fault_events"] == evs
    import json

    json.dumps(pm)  # the whole post-mortem must be JSON-safe


def test_flight_recorder_concurrent_ticks_stay_ordered(monkeypatch):
    """Step advance is serialized end to end: concurrent tickers (jax
    host-callback trace markers racing the post-dispatch tick) must not
    interleave snapshots — ring entries stay strictly step-ordered and
    no tick is swallowed by a racing read-then-advance."""
    import threading

    monkeypatch.setenv("BYTEPS_FLIGHT_RECORDER_STEPS", "4096")
    reset_flight_recorder()
    fr = get_flight_recorder()
    N, T = 200, 4

    def ticker():
        for _ in range(N):
            fr.tick()

    threads = [threading.Thread(target=ticker) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    steps = [e["step"] for e in fr.steps()]
    assert steps == sorted(set(steps)), "ring entries out of order"
    assert fr.summary()["step"] == N * T  # no tick swallowed
    assert len(steps) == N * T


def test_flight_recorder_file_dump_once_per_reason(tmp_path, monkeypatch):
    monkeypatch.setenv("BYTEPS_FLIGHT_RECORDER_DIR", str(tmp_path))
    reset_flight_recorder()
    fr = get_flight_recorder()
    fr.post_mortem(reason="stall")
    fr.post_mortem(reason="stall")  # second dump suppressed
    dumps = list(tmp_path.glob("flight_stall_*.json"))
    assert len(dumps) == 1
    import json

    doc = json.loads(dumps[0].read_text())
    assert doc["reason"] == "stall" and "metrics" in doc


def test_partition_failure_carries_post_mortem():
    from byteps_tpu.common.partition import make_partitions
    from byteps_tpu.common.scheduler import (
        Handle,
        PartitionFailure,
        PartitionTask,
        PipelineScheduler,
        Stage,
    )

    def boom(task):
        raise ValueError("kaput")

    sched = PipelineScheduler([Stage("BOOM", boom)], credit=1)
    h = Handle("t", 1)
    [p] = make_partitions(0, 4, itemsize=4, partition_bytes=64)
    sched.enqueue([PartitionTask(partition=p, name="t", handle=h)])
    with pytest.raises(PartitionFailure) as ei:
        h.wait(10.0)
    pm = ei.value.post_mortem
    assert pm is not None and pm["reason"] == "partition_failure"
    assert any(e["event"] == "partition_failure"
               for e in pm["fault_events"])
    sched.shutdown()


def test_train_step_tick_is_always_on():
    """The fused train-step factories tick the flight recorder per
    dispatched step WITHOUT BYTEPS_TRACE_ON (the in-program trace
    marker stays gated; this host-side tick is ~free), so train.step_ms
    records for every run."""
    from byteps_tpu.models.train import _finalize_step

    step = _finalize_step(lambda pb: (lambda x: x + 1), None, None)
    for x in range(3):
        assert step(x) == x + 1
    assert get_flight_recorder().summary()["step"] == 3
    assert get_registry().histogram("train.step_ms").count() == 2
    # ticks are RELATIVE: a recorder already ahead (eager rounds, a
    # previous model in the process) must not swallow them
    get_flight_recorder().on_step(50)
    step(0)
    assert get_flight_recorder().summary()["step"] == 51


def test_metrics_snapshot_public_api():
    import byteps_tpu

    get_registry().counter("x").inc()
    snap = byteps_tpu.metrics_snapshot()
    assert snap["metrics"]["counters"]["x"] == 1
    assert "flight_recorder" in snap


# ---- THE acceptance smoke: StallError post-mortem ---------------------------
def test_stallerror_dumps_flight_recorder_post_mortem(monkeypatch):
    """Chaos smoke (tier-1): a DcnCore run with one injected CRC
    corruption (FAULT events + retry counters) followed by a push big
    enough to stall on the emulated 8 Mbps NIC. The StallError must
    carry (a) diag: per-NIC wire counters + credit pools, and (b) the
    flight-recorder post-mortem: per-step stage dwell/run p50/p99 and
    the recent FAULT events — the acceptance criterion of the
    telemetry-plane PR."""
    from byteps_tpu.common import config as config_mod
    from byteps_tpu.common.dcn_adapter import DcnCore
    from byteps_tpu.common.scheduler import StallError

    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    # op ticks per intercepted wire attempt: round 1 is init(1) push(2)
    # pull(3) — corrupt exactly the first pull; CRC detects, the retry
    # engine re-pulls (op 4) clean. Deterministic, seeded.
    monkeypatch.setenv("BYTEPS_FAULT_SPEC", "pull:corrupt@op=3..3")
    monkeypatch.setenv("BYTEPS_FAULT_SEED", "1")
    monkeypatch.setenv("BYTEPS_RETRY_LIMIT", "4")
    monkeypatch.setenv("BYTEPS_RETRY_BACKOFF_MS", "2")
    # emulated 8 Mbps NIC: the 4 MB stall payload books ~4 s of wire
    # time; the 32 KB warmups ride the 64 KB burst almost free
    monkeypatch.setenv("BYTEPS_DCN_THROTTLE_MBPS", "8")
    config_mod.reset_config()
    reset_registry()
    reset_flight_recorder()
    port = BASE_PORT + 11
    servers = _serve(port)
    core = DcnCore(servers=servers)
    try:
        rng = np.random.default_rng(0)
        warm = rng.standard_normal(8192).astype(np.float32)
        for _ in range(3):  # steps 1..3: populate the per-step ring
            out = DcnCore.assemble(core.push_pull_async(warm, name="warm"))
            np.testing.assert_array_equal(out, warm)
        assert core.worker.get_counters()["crc_errors"] == 1

        big = rng.standard_normal(1 << 19).astype(np.float32)  # 2 MB
        h = core.push_pull_async(big, name="stall_me")
        with pytest.raises(StallError) as ei:
            DcnCore.assemble(h, timeout=0.4)
        e = ei.value

        # (a) live diag: per-NIC wire counters + credit pools
        assert e.diag is not None
        assert e.diag["workers"]["nic0"]["retries"] >= 1
        assert e.diag["workers"]["nic0"]["crc_errors"] == 1
        assert e.diag["wire_bytes"]["nic0"]["pushed"] > 0
        assert e.diag["credit_pools"] is not None
        assert "PUSH" in e.diag["stage_busy"]

        # (b) flight-recorder post-mortem: per-step ring with stage
        # dwell/run percentiles + the injected FAULT events
        pm = e.post_mortem
        assert pm is not None and pm["reason"] == "stall"
        assert len(pm["steps"]) >= 3
        last = pm["steps"][-1]
        assert last["stages"]["PUSH"]["run_p50_us"] is not None
        assert last["stages"]["PUSH"]["dwell_p50_us"] is not None
        assert last["stages"]["PUSH"]["run_p99_us"] >= \
            last["stages"]["PUSH"]["run_p50_us"]
        names = [ev["event"] for ev in pm["fault_events"]]
        assert "retry" in names  # the CRC retry landed in the ring
        # per-NIC wire totals visible in the registry view too
        assert pm["metrics"]["counters"]["wire.push_bytes"] > 0
        import json

        json.dumps(pm)  # post-mortem is JSON-safe end to end

        # drain the stalled round (the push finishes its booked wire
        # time and the pipeline completes) so no stage thread outlives
        # this test and logs into a closed pytest capture stream
        out = DcnCore.assemble(h, timeout=60.0)
        np.testing.assert_array_equal(out, big)
    finally:
        core.shutdown()
