"""``trace_scope_ms_in_steps``: the reader's arithmetic on hand-made rings,
traces and scope tables whose answers can be worked out on paper, and on
the recorded trace's device events with a made-up table laid over them."""

import json
import os
import types

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import program_span_ms
from benchmark.readers import trace_scope_ms_in_steps as sc

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = "serve.device_step."
OFFSET = 5e9                      # trace clock = ring clock + 5 s
ANCHORS = dict(anchor="serve.iteration", trace_anchor="serve.step")
MS = 1e6


def _e(name, start_ns, dur_ns, args=None):
    return (name, (start_ns - OFFSET) / 1e9, dur_ns / 1e9, 0, 0, args)


def _run(events, spans, chips=1, devices=None):
    devs = devices or {"0": events}
    trace = {"devices": devs, "spans": sorted(spans, key=lambda s: s[1])}
    return types.SimpleNamespace(reduced=tr.Reduced(trace, chips))


def _table(scopes, order=(), mixed=None):
    return {"module": "jit_x", "signature": "", "scopes": dict(scopes),
            "order": list(order), "mixed": dict(mixed or {})}


@pytest.fixture
def program(monkeypatch):
    """Stand-ins for the program's side: its ring and ``program_scopes``.
    ``program(ring, tables)`` installs them and returns the list the asked
    ``only`` sets are appended to."""
    from byteps_tpu.common import tracing

    def install(ring, tables):
        asked = []

        def program_scopes(only=None):
            asked.append(sorted(only))
            return {k: t for k, t in tables.items()
                    if k in only or k.split("[")[0] in only}
        monkeypatch.setattr(program_span_ms, "ring", lambda: ring)
        monkeypatch.setattr(tracing, "program_scopes", program_scopes,
                            raising=False)
        return asked
    return install


@pytest.mark.parametrize("scope, prefixes, inside", [
    ("block/attn", ["block/attn"], True),
    ("block/attn/paged/attention", ["block/attn"], True),
    ("block/attnx", ["block/attn"], False),
    ("block/mlp/block/moe/moe/route", ["block/mlp", "block/moe"], True),
    ("readout_ce.bwd_vocab", ["readout_ce"], True),
    ("readout_ce/readout_ce.bwd_rows", ["readout_ce"], True),
    ("readout", ["readout_ce"], False),
    ("block/ssm/ssd/decode", ["block/"], True),
    ("blocks", ["block/"], False),
    ("", ["block/"], False),
    ("embed", ["block/", "readout_ce", "embed"], True),
])
def test_matches(scope, prefixes, inside):
    assert sc.matches(scope, prefixes) is inside


# three decode steps of 10 ms at 100, 110, 120 ms on the trace's clock, the
# same five operations in each; a while spans two of them
def _decode_trace(extra=()):
    events, spans, ring = [], [[tr.WINDOW_SPAN, 90 * MS, 60 * MS]], []
    for i in range(4):
        t = (100 + 10 * i) * MS
        ring.append(_e("serve.iteration", t, 5 * MS, (i,)))
        spans.append(["serve.step", t - 3e3, 6 * MS])
    for i in range(3):
        t = (100 + 10 * i) * MS
        ring.append(_e(STEP + "decode", t, 10 * MS, (8, 4)))
        events += [
            ["slice_bitcast_fusion", "loop fusion", t + 0.1 * MS, 0.2 * MS],
            ["fusion.1", "loop fusion", t + 0.5 * MS, 1.0 * MS],
            ["while.3", tr.CONTROL_FLOW, t + 2.0 * MS, 4.0 * MS],
            ["paged_attn_decode.7", "custom-call", t + 2.0 * MS, 2.5 * MS],
            ["fusion.2", "convolution fusion", t + 4.5 * MS, 1.5 * MS],
            ["fusion.9", "convolution fusion", t + 7.0 * MS, 2.0 * MS],
            ["iota_reduce_fusion", "loop fusion", t + 9.2 * MS, 0.3 * MS],
        ] + [list(e[:2]) + [t + e[2], e[3]] for e in extra]
    return events, spans, ring


TABLES = {
    "serve.decode[W=4]": _table({
        "fusion.1": "embed", "while.3": "",
        "paged_attn_decode.7": "block/attn/paged/attention",
        "fusion.2": "block/mlp/block/moe/moe/experts",
        "fusion.9": "readout", "slice_bitcast_fusion": "block/attn"},
        mixed={"fusion.1": ["block/attn", "embed"]}),
    "serve.decode[W=8]": _table({"fusion.1": "block/attn"}),
    "serve.take[0]": _table({"slice_bitcast_fusion": ""}),
    "serve.pick[0]": _table({"iota_reduce_fusion": ""}),
    "serve.prefill[C=32,W=4,readout=1]": _table({"fusion.1": "block/mlp"}),
}


def test_decode_steps_by_scope(program):
    events, spans, ring = _decode_trace()
    asked = program(ring, TABLES)
    run, observed = _run(events, spans), {}
    mixer = sc.read(run, observed, scopes=["block/attn"], step="decode",
                    **ANCHORS)
    ffn = sc.read(run, observed, scopes=["block/mlp", "block/moe"],
                  step="decode", **ANCHORS)
    outside = sc.read(run, observed, outside=["block/"], step="decode",
                      **ANCHORS)
    assert mixer == pytest.approx(2.5 + 0.2)      # the take's slice lost
    assert ffn == pytest.approx(1.5)
    assert outside == pytest.approx(1.0 + 2.0 + 0.3)
    note = observed["notes"]["device_scopes.decode"]
    # the container is left out; the scopes add up to the leaves' time
    assert note["busy_ms"] == pytest.approx(7.5)
    assert sum(note["ms_per_step"].values()) == pytest.approx(7.5)
    assert mixer + ffn + outside == pytest.approx(note["busy_ms"])
    assert note["steps"] == 3 and note["left_out"] == 0
    assert note["unmatched_ms"] == 0
    # a name in the big table and in a small one under another scope: the
    # big one wins, and the note says how much time that is
    assert note["ambiguous_ms"] == pytest.approx(0.2)
    assert note["top"][0] == [
        "block/attn/paged/attention:paged_attn_decode.7",
        pytest.approx(2.5)]
    assert note["ms_per_step"][""] == pytest.approx(0.3)
    # a fusion across two regions says whose time its scope's is
    assert note["across_regions_ms"] == {
        "block/attn|embed": pytest.approx(1.0)}
    # one reading a run: the tables were asked for once, and only those
    # of the programs the slice's steps ran
    assert asked == [["serve.decode[W=4]", "serve.pick", "serve.take"]]


def test_an_unplaced_twentieth_of_the_time_prints_no_number(program):
    # 0.3 ms of 7.8 a step is in no table: under 5%, a number
    events, spans, ring = _decode_trace(
        extra=[["fusion.77", "loop fusion", 6.2 * MS, 0.3 * MS]])
    program(ring, TABLES)
    observed = {}
    assert sc.read(_run(events, spans), observed, outside=["block/"],
                   step="decode", **ANCHORS) == pytest.approx(3.3)
    note = observed["notes"]["device_scopes.decode"]
    assert note["unmatched_ms"] == pytest.approx(0.3)
    assert note["busy_ms"] == pytest.approx(7.8)
    # 0.5 ms of 8.0: over, None, and the note still says why
    events, spans, ring = _decode_trace(
        extra=[["fusion.77", "loop fusion", 6.2 * MS, 0.5 * MS]])
    program(ring, TABLES)
    observed = {}
    assert sc.read(_run(events, spans), observed, outside=["block/"],
                   step="decode", **ANCHORS) is None
    assert observed["notes"]["device_scopes.decode"]["unmatched_ms"] == \
        pytest.approx(0.5)


def test_a_step_whose_program_has_no_table_is_left_out(program):
    events, spans, ring = _decode_trace()
    ring = [e if e[0] != STEP + "decode" or i % 2 else
            e[:5] + ((8, 16),) for i, e in enumerate(ring)]
    program(ring, TABLES)
    observed = {}
    sc.read(_run(events, spans), observed, scopes=["block/attn"],
            step="decode", **ANCHORS)
    note = observed["notes"]["device_scopes.decode"]
    assert note["steps"] + note["left_out"] == 3 and note["left_out"] >= 1


def test_none_without_the_programs_side(program, monkeypatch):
    from byteps_tpu.common import tracing

    events, spans, ring = _decode_trace()
    kw = dict(scopes=["block/attn"], step="decode", **ANCHORS)
    program(ring, TABLES)
    assert sc.read(_run(events, spans), {}, step="chunk", scopes=["x"],
                   **ANCHORS) is None               # no step of the kind
    assert sc.read(types.SimpleNamespace(reduced=None), {}, **kw) is None
    program(None, TABLES)
    assert sc.read(_run(events, spans), {}, **kw) is None     # no ring
    program(ring, TABLES)
    monkeypatch.delattr(tracing, "program_scopes")  # the parent commit
    observed = {}
    assert sc.read(_run(events, spans), observed, **kw) is None
    assert not [k for k in observed.get("notes", {})
                if k.startswith("device_scopes")]
    # clocks that do not align: anchors 2 ms apart on one side only
    program([e if e[0] != "serve.iteration" else
             e[:1] + (e[1] + 0.002 * e[5][0] ** 2,) + e[2:] for e in ring],
            TABLES)
    assert sc.read(_run(events, spans), {}, **kw) is None
    with pytest.raises(ValueError):
        sc.read(_run(events, spans), {}, step="decode", **ANCHORS)


def test_a_chunk_decode_step_is_split_where_the_decode_program_starts(
        program):
    """Two large programs whose names collide in one step: the decode
    program's first eight top-level instructions in sequence mark where its
    events start; a step without that sequence is left out and counted."""
    order = [f"d{i}" for i in range(8)] + ["fusion.1", "tail"]
    chunk_names = ["fusion.1", "fusion.2", "d0", "fusion.1"]   # d0 collides
    tables = {
        "serve.prefill[C=32,W=4,readout=0]": _table(
            {"fusion.1": "block/attn", "fusion.2": "block/mlp",
             "d0": "embed"}),
        "serve.decode[W=4]": _table(
            dict({n: "block/mlp" for n in order}, **{"fusion.1": "readout"}),
            order),
        "serve.take[0]": _table({"take": ""}),
    }
    events, spans, ring = [], [[tr.WINDOW_SPAN, 90 * MS, 60 * MS]], []
    for i in range(4):
        t = (100 + 10 * i) * MS
        ring.append(_e("serve.iteration", t, 5 * MS, (i,)))
        spans.append(["serve.step", t - 3e3, 6 * MS])
    for i in range(3):
        t = (100 + 10 * i) * MS
        ring.append(_e(STEP + "chunk_decode", t, 10 * MS, (32, 4, 8, 4)))
        names = chunk_names + ["take"] + (
            order if i < 2 else order[:5] + order[6:])   # the third: no d5
        for j, n in enumerate(names):
            events.append([n, "loop fusion", t + j * 0.5 * MS, 0.4 * MS])
    asked = program(ring, tables)
    observed = {}
    assert sc.read(_run(events, spans), observed, scopes=["block/attn"],
                   step="chunk_decode", **ANCHORS) == pytest.approx(0.8)
    notes = observed["notes"]
    chunk = notes["device_scopes.chunk_decode.chunk"]
    decode = notes["device_scopes.chunk_decode.decode"]
    assert chunk["steps"] == decode["steps"] == 2
    assert chunk["left_out"] == decode["left_out"] == 1
    # the chunk's fusion.1 is attention's, the decode step's the readout's
    assert chunk["ms_per_step"] == {
        "": pytest.approx(0.4), "block/attn": pytest.approx(0.8),
        "block/mlp": pytest.approx(0.4), "embed": pytest.approx(0.4)}
    assert decode["ms_per_step"] == {
        "block/mlp": pytest.approx(9 * 0.4), "readout": pytest.approx(0.4)}
    assert asked == [["serve.decode[W=4]", "serve.pick",
                      "serve.prefill[C=32,W=4,readout=0]", "serve.take"]]
    # a split that leaves a side's time in no table is no split: with the
    # chunk program's table a stranger's, every step is left out
    tables["serve.prefill[C=32,W=4,readout=0]"] = _table({"other": "x"})
    program(ring, tables)
    observed = {}
    assert sc.read(_run(events, spans), observed, scopes=["block/attn"],
                   step="chunk_decode", **ANCHORS) is None
    assert observed["notes"]["device_scopes.chunk_decode.chunk"][
        "left_out"] == 3


def test_train_steps_by_scope_and_idle_before_scope(program):
    table = _table({"fusion.1": "block/attn", "fusion.2": "block/mlp",
                    "all-reduce.1": "grad_aggregate",
                    "fusion.3": "optimizer_update", "fusion.4": "",
                    "fusion.5": "readout_ce/readout_ce.bwd_vocab"})
    other = _table({"fusion.4": "embed"})
    asked = program([], {"train.step[0]": table, "train.step[1]": other,
                         "serve.decode[W=4]": _table({"fusion.1": "x"})})
    events, spans = [], [[tr.WINDOW_SPAN, 95 * MS, 50 * MS]]
    for i in range(3):                  # the third ends after the window
        t = (100 + 20 * i) * MS
        spans.append(["train.step", t, 18 * MS])
        events += [["fusion.1", "convolution fusion", t + 1 * MS, 4 * MS],
                   ["fusion.5", "convolution fusion", t + 5 * MS, 2 * MS],
                   ["fusion.2", "convolution fusion", t + 7 * MS, 3 * MS],
                   ["all-reduce.1", "all-reduce", t + 12 * MS, 2 * MS],
                   ["fusion.3", "loop fusion", t + 14 * MS, 1 * MS],
                   ["fusion.4", "loop fusion", t + 15.5 * MS, 0.5 * MS]]
    kw = dict(step_span="train.step")
    run = _run(events, spans, chips=2, devices={"0": events, "1": events})
    observed = {}
    assert sc.read(run, observed, scopes=["block/attn"], **kw) == \
        pytest.approx(4.0)
    assert sc.read(run, observed, scopes=["readout_ce"], **kw) == \
        pytest.approx(2.0)
    assert sc.read(run, observed, scopes=["grad_aggregate",
                                          "optimizer_update"], **kw) == \
        pytest.approx(3.0)
    assert sc.read(run, observed, outside=[
        "block/", "readout_ce", "grad_aggregate", "optimizer_update",
        "embed"], **kw) == pytest.approx(0.5)
    note = observed["notes"]["device_scopes.train.step"]
    assert note["steps"] == 2 and note["busy_ms"] == pytest.approx(12.5)
    # two executables of the step name fusion.4 differently: the first wins
    assert note["ambiguous_ms"] == pytest.approx(0.5)
    assert note["idle_before_scope_ms_per_step"] == {
        "": pytest.approx(0.5), "block/attn": pytest.approx(1.0),
        "grad_aggregate": pytest.approx(2.0)}
    assert asked == [["train.step"]]
    # one chip: no idle note
    observed = {}
    sc.read(_run(events, spans), observed, scopes=["block/attn"], **kw)
    assert "idle_before_scope_ms_per_step" not in \
        observed["notes"]["device_scopes.train.step"]


def test_scopes_laid_over_the_recorded_trace_add_up_to_its_busy_time(
        program):
    """The recorded trace's real device events under a made-up table (an
    event's category as its scope) and made-up decode steps: the scopes add
    up to the leaves' time inside the steps, which is the union
    ``trace_ms_in_device_steps`` counts there."""
    from benchmark.readers import trace_ms_in_device_steps as in_steps

    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    w0, w1 = tr.window(rec)
    cuts = [w0 + 2e6 + 6e6 * i for i in range(7)]
    ring, spans = [], [[tr.WINDOW_SPAN, w0, w1 - w0]]
    for i, c in enumerate(cuts):
        ring.append(_e("serve.iteration", c, 5e6, (i,)))
        spans.append(["serve.step", c - 3e3, 5.5e6])
    for a, b in zip(cuts, cuts[1:]):
        ring.append(_e(STEP + "decode", a, b - a, (8, 4)))
    table = _table({name: cat for name, cat, _, _ in rec["devices"]["0"]})
    program(ring, {"serve.decode[W=4]": table})
    run, observed = _run(rec["devices"]["0"], spans), {}
    total = sc.read(run, observed, outside=["nothing"], step="decode",
                    **ANCHORS)
    note = observed["notes"]["device_scopes.decode"]
    assert note["steps"] == 6 and note["unmatched_ms"] == 0
    assert total == pytest.approx(note["busy_ms"], rel=1e-9)
    busy = in_steps.ms_in_steps(ring, run.reduced, "decode",
                                ANCHORS["anchor"], ANCHORS["trace_anchor"])
    # summed leaves against the union of everything (containers too): the
    # same time, as ops of one core do not overlap outside a container
    assert total == pytest.approx(busy, rel=0.02)
    conv = sc.read(run, observed, scopes=["convolution fusion"],
                   step="decode", **ANCHORS)
    assert conv == pytest.approx(in_steps.ms_in_steps(
        ring, run.reduced, "decode", ANCHORS["anchor"],
        ANCHORS["trace_anchor"], categories=["convolution fusion"]),
        rel=1e-6)
