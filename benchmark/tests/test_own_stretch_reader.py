"""``trace_scope_ms_in_own_stretch``: a final chunk between two decode steps,
the next decode step's first operations inside the chunk's stamps — the
reader cuts them off where ``trace_scope_ms_in_steps`` prints no number."""

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import trace_scope_ms_in_own_stretch as own
from benchmark.readers import trace_scope_ms_in_steps as sc
from benchmark.tests.test_scope_reader import (   # noqa: F401  (fixture)
    ANCHORS, MS, STEP, _e, _run, _table, program)

TABLES = {
    "serve.prefill[C=32,W=4,readout=1]": _table({
        "fusion.1": "block/mlp", "fusion.2": "block/attn/cross",
        "fusion.3": "block/ssm/gmu", "fusion.4": "readout"}),
    "serve.pick_last[0]": _table({"reduce.1": ""}),
    "serve.decode[W=4]": _table({
        "fusion.1": "embed", "sscan_decode.9": "block/ssm/sscan/decode",
        "paged_attn_decode.7": "block/attn/window"}),
    "serve.take[0]": _table({"gather.1": ""}),
    "serve.pick[0]": _table({"reduce.2": ""}),
}


def _trace(spill_ms, stray_ms=0.0, next_w=4):
    """decode, chunk, decode, chunk, decode: steps of 10 ms from 100 ms on;
    each chunk's stamps hold ``spill_ms`` of the decode step behind it (its
    take, an embed whose name the chunk program has too, its first kernel)
    and, at the head, the last pick of the step before."""
    events, spans, ring = [], [[tr.WINDOW_SPAN, 90 * MS, 70 * MS]], []
    for i in range(6):
        t = (100 + 10 * i) * MS
        ring.append(_e("serve.iteration", t, 5 * MS, (i,)))
        spans.append(["serve.step", t - 3e3, 6 * MS])
    for i in range(5):
        t = (100 + 10 * i) * MS
        if i % 2 == 0:
            ring.append(_e(STEP + "decode", t, 10 * MS,
                           (8, next_w if i == 2 else 4)))
            events += [["sscan_decode.9", "custom-call", t + 1 * MS, 3 * MS],
                       ["paged_attn_decode.7", "custom-call", t + 4 * MS,
                        5 * MS]]
            continue
        ring.append(_e(STEP + "chunk", t, 10 * MS, (32, 4)))
        events += [
            ["reduce.2", "loop fusion", t + 0.0 * MS, 0.1 * MS],  # pick
            ["fusion.1", "convolution fusion", t + 0.2 * MS, 5.0 * MS],
            ["fusion.2", "convolution fusion", t + 5.2 * MS, 0.5 * MS],
            ["fusion.3", "convolution fusion", t + 5.7 * MS, 0.25 * MS],
            ["fusion.4", "convolution fusion", t + 6.0 * MS, 1.0 * MS],
            ["reduce.1", "loop fusion", t + 7.0 * MS, 0.05 * MS],
            ["fusion.77", "loop fusion", t + 7.1 * MS, stray_ms * MS],
            # the next decode step, before the host woke
            ["fusion.1", "loop fusion", t + 8.0 * MS, 0.1 * MS],  # its embed
            ["gather.1", "loop fusion", t + 8.2 * MS, 0.1 * MS],  # its take
            ["sscan_decode.9", "custom-call", t + 8.4 * MS, spill_ms * MS],
        ]
    return [e for e in events if e[3] > 0], spans, ring


KW = dict(scopes=["block/attn/cross", "block/ssm/gmu"], step="chunk",
          **ANCHORS)


def test_the_neighbours_operations_are_cut_off(program):
    events, spans, ring = _trace(spill_ms=1.0)
    program(ring, TABLES)
    # over a twentieth of the chunk's busy time is the decode program's:
    # the accepted reader prints no number
    observed = {}
    assert sc.read(_run(events, spans), observed, **KW) is None
    assert observed["notes"]["device_scopes.chunk"]["unmatched_ms"] == \
        pytest.approx(0.1 + 0.1 + 1.0)
    observed = {}
    run = _run(events, spans)
    assert own.read(run, observed, **KW) == pytest.approx(0.75)
    assert own.read(run, observed, scopes=["readout"], step="chunk",
                    **ANCHORS) == pytest.approx(1.0)
    note = observed["notes"]["device_scopes_own.chunk"]
    assert note["steps"] == 2 and note["left_out"] == 0
    assert note["unmatched_ms"] == 0
    # the name both programs have stays, up to the first the neighbour
    # alone has: 0.1 ms of its embed read as the chunk's MLP
    assert note["ms_per_step"]["block/mlp"] == pytest.approx(5.1)
    assert note["busy_ms"] == pytest.approx(6.8 + 0.1)
    # cut: the pick before, the take and the kernel behind
    assert note["cut_ms"] == pytest.approx(0.1 + 0.1 + 1.0)
    assert note["cut_steps"] == 2


def test_what_no_program_of_the_slice_names_still_prints_no_number(program):
    events, spans, ring = _trace(spill_ms=1.0, stray_ms=0.3)
    program(ring, TABLES)
    observed = {}
    assert own.read(_run(events, spans), observed, **KW) == \
        pytest.approx(0.75)                         # 0.3 of 7.2: under
    assert observed["notes"]["device_scopes_own.chunk"]["unmatched_ms"] \
        == pytest.approx(0.3)
    events, spans, ring = _trace(spill_ms=1.0, stray_ms=0.5)
    program(ring, TABLES)
    observed = {}
    assert own.read(_run(events, spans), observed, **KW) is None
    assert observed["notes"]["device_scopes_own.chunk"]["unmatched_ms"] \
        == pytest.approx(0.5)


def test_a_neighbour_outside_the_window_is_asked_for_too(program):
    """A chunk's neighbours run decode programs no step inside the window
    ran: their tables are asked for, by the steps beside the chunk."""
    events, spans, ring = _trace(spill_ms=1.0, next_w=16)
    spans[0] = [tr.WINDOW_SPAN, 105 * MS, 20 * MS]   # the first chunk alone
    tables = dict(TABLES)
    tables["serve.decode[W=16]"] = tables["serve.decode[W=4]"]
    asked = program(ring, tables)
    observed = {}
    assert own.read(_run(events, spans), observed, **KW) == \
        pytest.approx(0.75)
    assert observed["notes"]["device_scopes_own.chunk"]["steps"] == 1
    assert asked == [["serve.decode[W=16]", "serve.decode[W=4]",
                      "serve.pick", "serve.pick_last",
                      "serve.prefill[C=32,W=4,readout=1]", "serve.take"]]


def test_none_without_the_programs_side(program, monkeypatch):
    from byteps_tpu.common import tracing

    events, spans, ring = _trace(spill_ms=1.0)
    program(ring, TABLES)
    assert own.read(_run(events, spans), {}, scopes=["x"], step="unseen",
                    **ANCHORS) is None               # no step of the kind
    program(None, TABLES)
    assert own.read(_run(events, spans), {}, **KW) is None       # no ring
    program(ring, TABLES)
    monkeypatch.delattr(tracing, "program_scopes")   # a tree before it
    observed = {}
    assert own.read(_run(events, spans), observed, **KW) is None
    assert not observed.get("notes")
    with pytest.raises(ValueError):
        own.read(_run(events, spans), {}, step="chunk", **ANCHORS)
    with pytest.raises(ValueError):
        own.read(_run(events, spans), {}, scopes=["x"],
                 step="chunk_decode", **ANCHORS)
