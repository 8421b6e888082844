"""The three readers of the program's own spans and kernel names, on
hand-made rings and traces whose answers can be worked out on paper, and on
the recorded trace (recorded_trace.json, from before the kernels had
names)."""

import json
import os
import types

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import (program_idle_ms, program_span_ms,
                               trace_named_ms_per_step)

HERE = os.path.dirname(os.path.abspath(__file__))


def _e(name, start, dur, args=None, sid=0, parent=0):
    return (name, start, dur, sid, parent, args)


# ---- program_span_ms ------------------------------------------------------
ITERATIONS = [
    _e("serve.admit", 10.00, 0.001), _e("serve.commit", 10.05, 0.002),
    _e("serve.iteration", 10.00, 0.06, (1,)),
    _e("serve.admit", 10.10, 0.003), _e("serve.decode_pack", 10.11, 0.004),
    _e("serve.iteration", 10.10, 0.06, (2,)),
    # starts inside the window and ends outside it: not counted
    _e("serve.admit", 10.95, 0.002), _e("serve.iteration", 10.95, 0.06, (3,)),
]


def test_span_ms_per_span_and_own_mean():
    host = ["serve.admit", "serve.decode_pack", "serve.commit"]
    assert program_span_ms.mean_ms(
        ITERATIONS, 10.0, 11.0, host, per_span="serve.iteration") == \
        pytest.approx((1 + 2 + 3 + 4 + 2) / 2)       # the last admit is inside
    assert program_span_ms.mean_ms(
        ITERATIONS, 10.0, 11.0, ["serve.admit"]) == pytest.approx(2.0)
    # a shorter window keeps whole spans only
    assert program_span_ms.mean_ms(
        ITERATIONS, 10.0, 10.12, host, per_span="serve.iteration") == \
        pytest.approx((1 + 2 + 3 + 4) / 1)
    assert program_span_ms.mean_ms(
        ITERATIONS, 10.0, 11.0, ["train.dispatch"]) is None
    assert program_span_ms.mean_ms(
        ITERATIONS, 20.0, 21.0, host, per_span="serve.iteration") is None


REQUESTS = [
    _e("serve.request.queued", 9.9, 0.5, ("a",)),      # origin before window
    _e("serve.request.queued", 10.2, 0.1, ("b",)),
    _e("serve.request.prefill", 10.3, 0.4, ("b",)),
    _e("serve.request.queued", 10.3, 0.3, ("c",)),
    _e("serve.request.prefill", 10.6, 0.2, ("c",)),
    _e("serve.request.decode", 10.7, 0.2, ("b", "preempted")),
    _e("serve.request.queued", 10.9, 0.05, ("b", "resumed")),
    _e("serve.request.prefill", 10.95, 0.2, ("b", "resumed")),
    _e("serve.request.prefill", 10.4, 0.5, ("a",)),
    # due inside the window, first token after it (in a traced run: held
    # by the profiler's stop_trace): left out
    _e("serve.request.queued", 10.9, 0.05, ("d",)),
    _e("serve.request.prefill", 10.95, 20.0, ("d",)),
    _e("serve.request.queued", 10.95, 0.02, ("e",)),   # never admitted since
    _e("serve.iteration", 10.0, 0.06, (1,)),
    _e("serve.admit", 10.0, 0.001),
]
PHASES = ["serve.request.queued", "serve.request.prefill"]


def test_span_ms_per_request_follows_the_requests_whose_wait_is_inside():
    q = program_span_ms.mean_ms(REQUESTS, 10.0, 11.0,
                                ["serve.request.queued"], per_request=PHASES)
    p = program_span_ms.mean_ms(REQUESTS, 10.0, 11.0,
                                ["serve.request.prefill"],
                                per_request=PHASES)
    # b and c; a's origin is outside; b's resumed phases are tagged
    assert q == pytest.approx(1e3 * (0.1 + 0.3) / 2)
    assert p == pytest.approx(1e3 * (0.4 + 0.2) / 2)
    both = program_span_ms.mean_ms(REQUESTS, 10.0, 11.0, PHASES,
                                   per_request=PHASES)
    assert both == pytest.approx(q + p)
    assert program_span_ms.mean_ms(
        REQUESTS, 30.0, 31.0, ["serve.request.queued"],
        per_request=PHASES) is None


def test_span_readers_return_none_without_a_ring(monkeypatch):
    run = types.SimpleNamespace(t_process=0.0, setup_s=1.0, reduced=None)
    monkeypatch.setattr(program_span_ms, "ring", lambda: None)
    assert program_span_ms.read(run, {"elapsed_s": 5.0},
                                ["train.dispatch"]) is None
    assert program_idle_ms.read(run, {}, ["train.dispatch"], "train.dispatch",
                                "train.dispatch", "train.step") is None
    # a program from before the ring: its tracer has no ``spans``
    monkeypatch.undo()
    from byteps_tpu.common import tracing

    monkeypatch.setattr(tracing, "get_tracer", lambda: object())
    assert program_span_ms.ring() is None


# ---- program_idle_ms ----------------------------------------------------
OFFSET = -123_456_789_000.0        # trace clock = program clock + OFFSET
STARTS = [50.000, 50.061, 50.119, 50.185, 50.240, 50.307, 50.366]   # s


def _idle_case(offset=OFFSET, lag_ns=(2e3, 3e3, 2e3, 9e3, 2e3, 3e3, 2e3)):
    """Seven iterations of ~60 ms; the trace holds the middle five. In
    each the device idles for the first 4 ms, of which the program's
    ``serve.admit`` covers the first 1 ms and ``serve.decode_pack`` the
    third; the rest of the iteration the device is busy."""
    entries, spans, events = [], [], []
    for i, s in enumerate(STARTS):
        entries += [_e("serve.admit", s, 0.001),
                    _e("serve.decode_pack", s + 0.002, 0.001),
                    _e("serve.iteration", s, 0.055, (i,))]
        if 1 <= i <= 5:
            t = s * 1e9 + offset
            spans.append(["serve.step", t - lag_ns[i], 56e6])
            events.append(["copy.1", "data formatting", t + 4e6, 50e6])
    w0 = STARTS[1] * 1e9 + offset - 1e6
    w1 = STARTS[5] * 1e9 + offset + 58e6
    spans.append([tr.WINDOW_SPAN, w0, w1 - w0])
    spans.sort(key=lambda s: s[1])
    return entries, tr.Reduced({"devices": {"0": events}, "spans": spans}, 1)


def test_idle_ms_recovers_a_planted_offset():
    entries, reduced = _idle_case()
    found = program_idle_ms.align(
        [e[1] * 1e9 for e in entries if e[0] == "serve.iteration"],
        [s for n, s, _ in reduced.trace["spans"] if n == "serve.step"])
    assert found[0] == pytest.approx(OFFSET - 2e3, abs=1.5e3)
    assert found[1] <= 5e3 and found[2] == 5
    notes = {}
    got = program_idle_ms.idle_ms(
        entries, reduced, ["serve.admit", "serve.decode_pack"],
        "serve.iteration", "serve.iteration", "serve.step", notes)
    # 2 ms of the 4 ms idle lie under the two spans, in each of 5 iterations
    assert got == pytest.approx(2.0, abs=0.01)
    assert notes["offset_seen"]["pairs"] == 5
    only_admit = program_idle_ms.idle_ms(
        entries, reduced, ["serve.admit"], "serve.iteration",
        "serve.iteration", "serve.step")
    assert only_admit == pytest.approx(1.0, abs=0.01)


def test_idle_ms_is_none_when_the_sequences_do_not_align():
    # the annotations lag the program's spans by amounts that differ by
    # hundreds of microseconds: no constant relates the clocks
    entries, reduced = _idle_case(
        lag_ns=(0, 0, 400e3, 900e3, 100e3, 600e3, 0))
    notes = {}
    assert program_idle_ms.idle_ms(
        entries, reduced, ["serve.admit"], "serve.iteration",
        "serve.iteration", "serve.step", notes) is None
    assert notes["offset_seen"]["spread_ns"] > program_idle_ms.MAX_SPREAD_NS
    # too few anchors on either side
    assert program_idle_ms.align([1.0, 2.0], [1.0, 2.0]) is None
    assert program_idle_ms.align([1.0, 2.0, 3.0], [1.0, 2.0, 3.0, 4.0]) is None
    entries, reduced = _idle_case()
    assert program_idle_ms.idle_ms(
        [e for e in entries if e[0] != "serve.iteration"], reduced,
        ["serve.admit"], "serve.iteration", "serve.iteration",
        "serve.step") is None


# ---- trace_named_ms_per_step ----------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return tr.Reduced(json.load(f), chips=1)


def test_named_events_of_the_recorded_trace(recorded):
    r = recorded
    # today's name of the forward kernel: seven events, and none of the
    # 149 ``custom-call.N`` (XLA's own, ~0 ms) that share its category
    n, ns = trace_named_ms_per_step.named_ns(
        r.first, r.w0, r.w1, ["jvp_jit__fwd__"])
    fwd = [e for e in r.first if e[0].startswith("jvp_jit__fwd__")]
    assert n == len(fwd) == 7
    assert ns == pytest.approx(sum(
        min(e[2] + e[3], r.w1) - max(e[2], r.w0) for e in fwd))
    assert 0 < ns < r.seconds(["custom-call"]) * 1e9 + 1
    assert sum(e[0].startswith("custom-call") for e in r.first) == 149
    assert trace_named_ms_per_step.named_ns(
        r.first, r.w0, r.w1, ["flash_fwd", "flash_bwd_dq"]) == (0, 0.0)
    # the piece is 40 ms and holds no whole step: nothing to divide by
    run = types.SimpleNamespace(reduced=r)
    assert r.count("train.step") == 0
    assert trace_named_ms_per_step.read(
        run, {}, ["jvp_jit__fwd__"], "train.step") is None
    assert trace_named_ms_per_step.read(
        types.SimpleNamespace(reduced=None), {}, ["x"], "train.step") is None


def test_named_ms_per_step_on_a_hand_made_trace():
    trace = {
        "devices": {"0": [
            ["flash_fwd.3", "custom-call", 100.0, 2e6],
            ["flash_bwd_dq.1", "custom-call", 3e6, 3e6],
            ["flash_bwd_dkv", "custom-call", 7e6, 4e6],
            ["custom-call.7", "custom-call", 12e6, 1e6],
            ["flash_fwd.30", "custom-call", 19e6, 2e6],      # clipped to 1e6
        ]},
        "spans": [[tr.WINDOW_SPAN, 0.0, 20e6], ["train.step", 1.0, 9e6],
                  ["train.step", 10e6, 9e6]],
    }
    run = types.SimpleNamespace(reduced=tr.Reduced(trace, 1))
    assert trace_named_ms_per_step.read(
        run, {}, ["flash_fwd"], "train.step") == pytest.approx(3.0 / 2)
    assert trace_named_ms_per_step.read(
        run, {}, ["flash_bwd_dq", "flash_bwd_dkv"], "train.step") == \
        pytest.approx(7.0 / 2)
    assert trace_named_ms_per_step.read(
        run, {}, ["flash_decode"], "train.step") is None
