"""The two readers of the scheduler's device steps
(``serve.device_step.*``), on hand-made rings and traces whose answers can
be worked out on paper, and on the recorded trace's device events with
made-up steps laid over them."""

import json
import os
import types

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import (program_idle_ms, program_span_share,
                               trace_ms_in_device_steps as in_steps)

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = "serve.device_step."
ALL = [STEP + k for k in ("decode", "chunk", "chunk_decode", "unseen")]


def _e(name, start, dur, args=None, sid=0, parent=0):
    return (name, start, dur, sid, parent, args)


# ---- program_span_share ---------------------------------------------------
RING = [
    _e("serve.iteration", 9.0, 0.01, (0,)),              # the ring's oldest
    _e(STEP + "unseen", 9.98, 0.03, ("decode", 8, 4)),   # straddles the open
    _e(STEP + "decode", 10.01, 0.02, (8, 4)),
    _e(STEP + "chunk", 10.03, 0.04, (32, 4)),
    _e(STEP + "decode", 10.07, 0.02, (8, 4)),
    _e(STEP + "chunk_decode", 10.09, 0.05, (32, 4, 8, 4)),
    _e(STEP + "unseen", 10.14, 0.05, ("chunk_decode", 32, 4, 8, 4)),
    _e(STEP + "unseen", 10.19, 0.02, ("decode", 7, 4)),
    _e("serve.iteration", 10.2, 0.01, (1,)),
    _e(STEP + "decode", 10.99, 0.02, (8, 4)),            # ends after the close
]


def test_share_counts_named_and_tagged_spans_inside_the_window():
    held = dict(spans=[STEP + "chunk", STEP + "chunk_decode",
                       STEP + "unseen:chunk", STEP + "unseen:chunk_decode"],
                of=ALL)
    notes = {}
    # six steps lie wholly inside; a chunk, a chunk_decode and one unseen
    # that says it held a chunk
    assert program_span_share.share_pct(
        RING, 10.0, 11.0, notes=notes, **held) == pytest.approx(50.0)
    assert program_span_share.share_pct(
        RING, 10.0, 11.0, [STEP + "unseen"], ALL) == pytest.approx(100 / 3)
    seen = notes["spans_in_window"]
    assert seen[STEP + "decode"] == (2, pytest.approx(0.04))
    assert seen[STEP + "unseen:chunk_decode"] == (1, pytest.approx(0.05))
    assert seen[STEP + "unseen:decode"] == (1, pytest.approx(0.02))
    assert seen["window_s"] == pytest.approx(1.0)
    # the oldest entry starts a second before the window opened: the ring
    # holds the window
    assert notes["ring"] == {"entries": len(RING),
                             "oldest_before_window_s": pytest.approx(1.0)}
    assert program_span_share.share_pct(RING, 20.0, 21.0, **held) is None
    assert program_span_share.share_pct(
        [e for e in RING if e[0] == "serve.iteration"], 10.0, 11.0,
        **held) is None


def test_share_reader_returns_none_without_a_ring(monkeypatch):
    from benchmark.readers import program_span_ms

    run = types.SimpleNamespace(t_process=0.0, setup_s=10.0)
    monkeypatch.setattr(program_span_ms, "ring", lambda: None)
    assert program_span_share.read(
        run, {"elapsed_s": 1.0}, [STEP + "unseen"], ALL) is None
    monkeypatch.setattr(program_span_ms, "ring", lambda: RING)
    observed = {"elapsed_s": 1.0}
    assert program_span_share.read(
        run, observed, [STEP + "unseen"], ALL) == pytest.approx(100 / 3)
    assert observed["notes"]["ring"]["entries"] == len(RING)
    assert program_span_share.read(run, {}, [STEP + "unseen"], ALL) is None


# ---- trace_ms_in_device_steps ---------------------------------------------
OFFSET = -123_456_789_000.0        # trace clock = program clock + OFFSET
KINDS = ["unseen", "decode", "chunk_decode", "decode", "chunk", "decode",
         "chunk_decode"]
STARTS = [50.000, 50.020, 50.040, 50.100, 50.120, 50.150, 50.170, 50.230]


def _case(lag_ns=(2e3, 3e3, 2e3, 9e3, 2e3, 3e3, 2e3, 3e3), skip=()):
    """Seven iterations, each read ending one device step at the
    iteration's start (the step that began at the iteration before). In
    every step the device runs a 4 ms ``fusion.7`` (loop fusion) from 1 ms
    in and a ``moe_gmm_fwd`` from 6 ms in to 1 ms PAST the step's end: the
    stamp cuts it. A chunk_decode step also holds a 30 ms ``flash_fwd``."""
    entries, spans, events = [], [], []
    for i, s in enumerate(STARTS):
        entries.append(_e("serve.iteration", s, 0.015, (i,)))
        spans.append(["serve.step", s * 1e9 + OFFSET - lag_ns[i], 16e6])
    for i, kind in enumerate(KINDS):
        a, b = STARTS[i], STARTS[i + 1]
        if kind not in skip:
            entries.append(_e(
                STEP + kind, a, b - a,
                ("decode", 8, 4) if kind == "unseen" else (8, 4)))
        t = a * 1e9 + OFFSET
        events += [["fusion.7", "loop fusion", t + 1e6, 4e6],
                   [f"moe_gmm_fwd.{i}", "custom-call", t + 6e6,
                    (b - a) * 1e9 - 5e6]]
        if kind == "chunk_decode":
            events.append(["flash_fwd.2", "custom-call", t + 8e6, 30e6])
    w0 = STARTS[0] * 1e9 + OFFSET - 5e6
    w1 = STARTS[-1] * 1e9 + OFFSET + 20e6
    spans.append([tr.WINDOW_SPAN, w0, w1 - w0])
    spans.sort(key=lambda s: s[1])
    return entries, tr.Reduced({"devices": {"0": events}, "spans": spans}, 1)


ANCHORS = ("serve.iteration", "serve.step")


def test_ms_in_steps_cuts_at_the_stamps():
    entries, reduced = _case()
    notes = {}
    got = in_steps.ms_in_steps(entries, reduced, "decode", *ANCHORS,
                               categories=["loop fusion"], notes=notes)
    assert got == pytest.approx(4.0, abs=0.01)
    # a decode step is 20 ms: its own product from 6 ms in (14 ms) and the
    # last millisecond of the product of the step before it
    assert in_steps.ms_in_steps(
        entries, reduced, "decode", *ANCHORS,
        names=["moe_gmm_fwd"]) == pytest.approx(15.0, abs=0.01)
    assert in_steps.ms_in_steps(
        entries, reduced, "chunk_decode", *ANCHORS,
        names=["moe_gmm_fwd"]) == pytest.approx(55.0, abs=0.01)
    assert in_steps.ms_in_steps(
        entries, reduced, "chunk_decode", *ANCHORS,
        names=["flash_fwd"]) == pytest.approx(30.0, abs=0.01)
    # busy: the union, so the flash kernel under the product adds nothing
    assert in_steps.ms_in_steps(
        entries, reduced, "chunk_decode", *ANCHORS) == \
        pytest.approx(4.0 + 55.0, abs=0.01)
    by_kind = notes["device_steps.decode.loop fusion"]
    assert by_kind["steps"] == {"unseen": 1, "decode": 3, "chunk_decode": 2,
                                "chunk": 1}
    assert by_kind["ms"]["chunk"] == pytest.approx(4.0, abs=0.01)
    assert sum(by_kind["ms"].values()) + by_kind["outside_ms"] == \
        pytest.approx(7 * 4.0)
    clock = notes["device_step_clock"]
    assert clock["offset_ns"] == pytest.approx(OFFSET - 2e3, abs=1.5e3)
    assert clock["pairs"] == 8 and clock["stamps"] == 7
    # every stamp falls inside a running product: the last event that ended
    # before it is the step's fusion, 14-54 ms earlier; only the last
    # product ends (1 ms) past the last stamp, so no stamp is idle
    assert clock["idle_stamps"] == 0 and clock["lag_idle_ns_median"] is None
    assert clock["lag_ns_median"] == pytest.approx(15e6, abs=1e4)


def test_ms_in_steps_is_none_when_clocks_or_steps_are_missing():
    entries, reduced = _case(lag_ns=(0, 0, 400e3, 900e3, 100e3, 600e3, 0, 0))
    notes = {}
    assert in_steps.ms_in_steps(entries, reduced, "decode", *ANCHORS,
                                categories=["loop fusion"],
                                notes=notes) is None
    assert notes["device_step_clock"]["spread_ns"] > \
        program_idle_ms.MAX_SPREAD_NS
    # a slice with no step of the kind; a program that emits none
    entries, reduced = _case(skip=("chunk",))
    assert in_steps.ms_in_steps(entries, reduced, "chunk", *ANCHORS) is None
    assert in_steps.ms_in_steps(entries, reduced, "decode", *ANCHORS) > 0
    parent = [e for e in entries if not e[0].startswith(STEP)]
    assert in_steps.ms_in_steps(parent, reduced, "decode", *ANCHORS) is None
    # no operation of the window is among those asked for
    assert in_steps.ms_in_steps(entries, reduced, "decode", *ANCHORS,
                                names=["paged_attn_decode"]) is None
    # no anchor: the clocks cannot be laid on each other
    assert in_steps.ms_in_steps(
        [e for e in entries if e[0] != "serve.iteration"], reduced,
        "decode", *ANCHORS) is None
    run = types.SimpleNamespace(reduced=None)
    assert in_steps.read(run, {}, "decode", *ANCHORS) is None


def test_lag_where_the_device_idles_at_a_stamp():
    """The device finishes 40 us before each stamp and starts again 10 us
    after it: the lag is read off the step's own last event."""
    entries, spans, events = [], [], []
    for i in range(6):
        s = 70.0 + 0.010 * i
        entries += [_e("serve.iteration", s, 0.009, (i,)),
                    _e(STEP + "decode", s, 0.010, (8, 4)) if i < 5 else
                    _e(STEP + "unseen", s, 0.010, ("decode", 8, 4))]
        t = s * 1e9 + OFFSET
        spans.append(["serve.step", t - 2e3, 9.5e6])
        events.append(["fusion.1", "loop fusion", t + 10e3, 10e6 - 50e3])
    spans.append([tr.WINDOW_SPAN, 70.0 * 1e9 + OFFSET - 1e6, 80e6])
    spans.sort(key=lambda s: s[1])
    reduced = tr.Reduced({"devices": {"0": events}, "spans": spans}, 1)
    notes = {}
    got = in_steps.ms_in_steps(entries, reduced, "decode", *ANCHORS,
                               notes=notes)
    assert got == pytest.approx(10.0 - 0.05, abs=0.005)
    clock = notes["device_step_clock"]
    # the unseen step's end may be a read that found its result ready
    assert clock["stamps"] == 6 and clock["idle_stamps"] == 5
    assert clock["lag_idle_ns_median"] == pytest.approx(40e3 - 2e3, abs=1e3)
    assert clock["lag_ns_median"] == clock["lag_idle_ns_median"]


def test_steps_laid_over_the_recorded_trace_add_up_to_its_window():
    """Made-up steps over the recorded trace's real device events: what
    falls inside the kinds and outside every step adds up to what
    ``trace_reduce`` counts in the window, and a step cut at a known stamp
    holds exactly the clipped events."""
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    w0, w1 = tr.window(rec)
    cuts = [w0 + 2e6 + 6e6 * i for i in range(7)]          # trace clock
    entries, spans = [], [[tr.WINDOW_SPAN, w0, w1 - w0]]
    for i, c in enumerate(cuts):
        entries.append(_e("serve.iteration", (c - OFFSET) / 1e9, 0.005, (i,)))
        spans.append(["serve.step", c - 3e3, 5.5e6])
    for i, (a, b) in enumerate(zip(cuts, cuts[1:])):
        entries.append(_e(STEP + ("decode" if i % 2 else "chunk_decode"),
                          (a - OFFSET) / 1e9, (b - a) / 1e9, (8, 4)))
    spans.sort(key=lambda s: s[1])
    reduced = tr.Reduced({"devices": rec["devices"], "spans": spans}, 1)
    cats = ["convolution fusion"]
    notes = {}
    got = in_steps.ms_in_steps(entries, reduced, "decode", *ANCHORS,
                               categories=cats, notes=notes)
    by_kind = notes["device_steps.decode.convolution fusion"]
    assert by_kind["steps"] == {"decode": 3, "chunk_decode": 3}
    assert sum(by_kind["ms"].values()) + by_kind["outside_ms"] == \
        pytest.approx(reduced.seconds(cats) * 1e3, rel=1e-6)
    off = notes["device_step_clock"]["offset_ns"]
    a, b = cuts[1] + off - OFFSET, cuts[2] + off - OFFSET  # the first decode
    assert tr.matching_ns(reduced.first, a, b, cats) > 0
    decode = [(cuts[i] + off - OFFSET, cuts[i + 1] + off - OFFSET)
              for i in (1, 3, 5)]
    assert got == pytest.approx(sum(
        tr.matching_ns(reduced.first, a, b, cats) for a, b in decode)
        / 1e6 / 3, rel=1e-6)
    busy = in_steps.ms_in_steps(entries, reduced, "chunk_decode", *ANCHORS)
    assert busy == pytest.approx(sum(
        tr.busy_ns(reduced.first, cuts[i] + off - OFFSET,
                   cuts[i + 1] + off - OFFSET) for i in (0, 2, 4))
        / 1e6 / 3, rel=1e-6)
