"""Chrome-trace recorder (SURVEY §5.1; reference docs/timeline.md)."""

import json
import os

from byteps_tpu.common.tracing import TraceRecorder


def test_disabled_recorder_collects_nothing(tmp_path):
    rec = TraceRecorder(enabled=False, trace_dir=str(tmp_path))
    rec.step()
    with rec.span("t0.p0", "PUSH"):
        pass
    assert rec.dump() is None


def test_records_and_dumps_chrome_format(tmp_path):
    rec = TraceRecorder(enabled=True, trace_dir=str(tmp_path), start_step=1, end_step=2, rank=3)
    rec.step()  # step 1 -> active
    with rec.span("grad.p0", "PUSH", args={"key": 7}):
        pass
    rec.instant("credit_exhausted", "SCHED")
    path = rec.dump()
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert len(evs) == 2
    x = [e for e in evs if e["ph"] == "X"][0]
    assert x["name"] == "grad.p0"
    assert x["tid"] == "PUSH"
    assert x["pid"] == 3
    assert x["args"]["key"] == 7
    assert x["dur"] >= 0


def test_step_window_gating(tmp_path):
    rec = TraceRecorder(enabled=True, trace_dir=str(tmp_path), start_step=2, end_step=2)
    rec.step()  # step 1: inactive
    with rec.span("a", "S"):
        pass
    rec.step()  # step 2: active
    with rec.span("b", "S"):
        pass
    rec.step()  # step 3 -> past end, auto-dumps
    assert rec._dumped
    names = [e["name"] for e in rec._events]
    assert names == ["b"]


def test_xprof_window_capture(tmp_path):
    """BYTEPS_TRACE_XPROF: a jax.profiler capture opens at the window
    start and closes past the end (or at dump), landing device-trace
    files under trace_dir/xprof_rank{r}; chrome events still record."""
    import os

    rec = TraceRecorder(enabled=True, trace_dir=str(tmp_path),
                        start_step=1, end_step=2, rank=0, xprof=True)
    import jax
    import jax.numpy as jnp

    rec.step()                       # enters the window -> capture starts
    assert rec._xprof_running
    jnp.ones((8, 8)) @ jnp.ones((8, 8))  # something for the device trace
    with rec.span("grad.p0", "PUSH"):
        pass
    rec.step()                       # step 2, still inside
    rec.step()                       # step 3 -> capture stops + dump
    assert not rec._xprof_running
    xdir = os.path.join(str(tmp_path), "xprof_rank0")
    assert os.path.isdir(xdir) and any(os.scandir(xdir))
    data = json.load(open(os.path.join(str(tmp_path), "trace_rank0.json")))
    assert data["traceEvents"]


def test_xprof_failure_degrades_to_chrome_only(tmp_path, monkeypatch):
    rec = TraceRecorder(enabled=True, trace_dir=str(tmp_path),
                        start_step=1, end_step=2, rank=0, xprof=True)
    import jax

    def boom(*a, **k):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    rec.step()
    assert not rec._xprof_running and not rec.xprof  # disabled, no crash
    with rec.span("grad.p0", "PUSH"):
        pass
    rec.step()
    rec.step()
    assert json.load(open(
        os.path.join(str(tmp_path), "trace_rank0.json")))["traceEvents"]


def test_trace_args_json_safe_over_numpy_scalar_types(tmp_path):
    """Property test (telemetry-plane satellite): ANY event arg built
    from a numpy scalar type must survive the chrome-trace JSON dump —
    the np.bool_ that broke the dump once (PR 5 fixed one call site) is
    now scrubbed centrally in the recorder, for every call site."""
    import numpy as np

    scalars = [
        np.bool_(True), np.int8(-3), np.int16(9), np.int32(-5),
        np.int64(7), np.uint8(2), np.uint16(4), np.uint32(6),
        np.uint64(8), np.float16(1.5), np.float32(2.5), np.float64(3.5),
        np.complex64(1 + 2j), np.complex128(3 - 4j),
        np.bytes_(b"x"), np.str_("s"),
        np.array(True), np.array(11), np.arange(3),
        np.zeros((100,)), np.float64("nan"), np.float64("inf"),
    ]
    rec = TraceRecorder(enabled=True, trace_dir=str(tmp_path),
                        start_step=1, end_step=999, rank=0)
    rec.step()
    for i, s in enumerate(scalars):
        rec.instant(f"e{i}", "FAULT", {"v": s, "nested": {"list": [s]}})
        rec.complete_event(f"x{i}", "PUSH", 0.0, 1.0, {"v": s})
    rec.metadata["robustness"] = {"w0": {"flag": np.bool_(False),
                                         "n": np.int64(12)}}
    path = rec.dump()
    doc = json.load(open(path))  # strict JSON round-trip, no np leakage
    by_name = {e["name"]: e for e in doc["traceEvents"]
               if e["name"].startswith("e")}
    assert by_name["e0"]["args"]["v"] is True
    assert by_name["e4"]["args"]["v"] == 7
    assert by_name["e10"]["args"]["v"] == 2.5
    assert by_name["e18"]["args"]["v"] == [0, 1, 2]
    assert "ndarray" in by_name["e19"]["args"]["v"]  # big array: descriptor
    assert doc["metadata"]["robustness"]["w0"] == {"flag": False, "n": 12}
    # the FAULT instants also landed in the always-on flight recorder,
    # sanitized the same way
    from byteps_tpu.common.flight_recorder import get_flight_recorder

    evs = get_flight_recorder().events()
    assert any(e["event"] == "e0" and e["args"]["v"] is True for e in evs)


def test_fault_instants_feed_flight_recorder_even_when_trace_off():
    """The chrome trace is opt-in; the flight recorder is not. A FAULT
    instant recorded with tracing DISABLED must still reach the ring."""
    from byteps_tpu.common.flight_recorder import get_flight_recorder

    rec = TraceRecorder(enabled=False)
    rec.instant("failover", "FAULT", {"server": 1})
    rec.instant("not_a_fault", "PUSH", {})
    assert rec._events == []  # nothing traced
    evs = get_flight_recorder().events()
    assert [e["event"] for e in evs] == ["failover"]
    assert evs[0]["args"] == {"server": 1}


# ---- the always-on span ring (docs/observability.md §spans) -----------------
def test_ring_records_with_trace_off_and_carries_id_and_parent():
    import time

    rec = TraceRecorder(enabled=False)
    before = time.monotonic()
    with rec.span("outer", "S", (7,)) as outer:
        with rec.span("inner", "S"):
            pass
        with rec.span("inner", "S", {"k": 1}):
            pass
    sid = rec.emit("stamped", "S", before, 0.25, ("r1", "resumed"))
    after = time.monotonic()
    assert rec.dump() is None and rec._events == []     # nothing traced
    inner1, inner2, out, stamped = rec.spans()
    assert out[0] == "outer" and out[3] == outer.sid and out[4] == 0
    assert out[5] == (7,) and inner2[5] == {"k": 1}
    assert inner1[4] == inner2[4] == out[3]             # their parent
    assert len({inner1[3], inner2[3], out[3], sid}) == 4
    assert stamped == ("stamped", before, 0.25, sid, 0, ("r1", "resumed"))
    # the ring's clock is time.monotonic(): a reader cuts it to a window
    # with its own stamps
    assert rec.clock is time.monotonic
    assert before <= out[1] <= inner1[1] <= inner2[1] <= after
    assert inner2[1] + inner2[2] <= out[1] + out[2] <= after
    assert rec.spans(since=inner2[1]) == [inner2]
    assert rec.spans(since=after) == []
    # a sibling opened after the parent closed has no parent
    with rec.span("later", "S"):
        pass
    assert rec.spans()[-1][4] == 0


def test_ring_is_bounded():
    from byteps_tpu.common import tracing

    rec = TraceRecorder(enabled=False)
    for i in range(tracing.RING_SPANS + 10):
        rec.emit("e", "S", 0.0, 0.0, (i,))
    ring = rec.spans()
    assert len(ring) == tracing.RING_SPANS
    assert ring[0][5] == (10,) and ring[-1][5] == (tracing.RING_SPANS + 9,)


def test_metrics_off_stills_the_ring(monkeypatch):
    from byteps_tpu.common import config as config_mod

    monkeypatch.setenv("BYTEPS_METRICS_ON", "0")
    config_mod.reset_config()
    rec = TraceRecorder(enabled=False)
    with rec.span("a", "S"):
        pass
    rec.complete_event("b", "S", 0.0, 1.0)
    assert rec.spans() == []


def test_ring_and_chrome_dump_agree_inside_the_step_window(tmp_path):
    rec = TraceRecorder(enabled=True, trace_dir=str(tmp_path),
                        start_step=1, end_step=2)
    rec.step()
    with rec.span("it", "SERVE", (3, "resumed")):
        pass
    rec.complete_event("x", "PUSH", rec._now_us(), 5.0, {"key": 1})
    it, x = rec.spans()
    doc = json.load(open(rec.dump()))
    ev = {e["name"]: e for e in doc["traceEvents"]}
    assert ev["it"]["args"] == {"args": [3, "resumed"]}
    assert ev["x"]["args"] == {"key": 1} and ev["x"]["dur"] == 5.0
    # one constant takes the ring's clock to the chrome trace's
    for entry, name in ((it, "it"), (x, "x")):
        assert abs(rec._epoch_minus_mono_us + entry[1] * 1e6
                   - ev[name]["ts"]) < 1.0
    assert abs(x[2] - 5e-6) < 1e-12


def test_post_mortem_carries_the_last_spans():
    from byteps_tpu.common.flight_recorder import get_flight_recorder
    from byteps_tpu.common.tracing import get_tracer

    with get_tracer().span("serve.iteration", "SERVE", (1,)):
        pass
    pm = get_flight_recorder().post_mortem(reason="test", dump=False)
    assert pm["spans"][-1][0] == "serve.iteration"
    assert pm["spans"][-1][5] == [1]
    json.dumps(pm)


def test_program_spans_sit_on_the_profilers_host_plane(tmp_path):
    """Under a real jax.profiler session (CPU backend) a program span's
    name is on the xplane's host plane, and the profiler's clock is the
    ring's plus one constant: pairs agree within 50 us."""
    import glob
    import statistics
    import time

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    rec = TraceRecorder(enabled=False)
    x = jnp.ones((64, 64))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(12):
            with rec.span("prog.work", "S", (i,)):
                (x @ x).block_until_ready()
            time.sleep(0.002 * (i % 3))      # uneven gaps
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    starts = sorted(
        ev.start_ns for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name == "prog.work")
    ring = [e for e in rec.spans() if e[0] == "prog.work"]
    assert len(starts) == len(ring) == 12
    offsets = [s - e[1] * 1e9 for s, e in zip(starts, ring)]
    mid = statistics.median(offsets)
    assert sum(abs(o - mid) <= 50e3 for o in offsets) >= 11, offsets
