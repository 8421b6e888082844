"""Device steps on the ring (docs/observability.md §Spans, docs/serving.md
§Scheduler iteration): every read of a decode step's tokens or of a final
chunk's first token ends one ``serve.device_step.*`` span, of the kind and
with the args of what the scheduler issued between the two programs read;
and the host's issue by its parts, inside the two dispatch spans."""

import time

import jax
import numpy as np
import pytest

from byteps_tpu.common.tracing import (RING_SPANS, TraceRecorder, get_tracer,
                                       reset_tracer)
from byteps_tpu.models import GPTConfig, gpt_init
from byteps_tpu.serve import Request, Scheduler

STEP = "serve.device_step."
PARTS = ("serve.issue.take", "serve.issue.decode_call", "serve.issue.pick",
         "serve.issue.chunk_call")
CFG = GPTConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return gpt_init(jax.random.PRNGKey(0), CFG)


def _sched(params, **kw):
    return Scheduler(params, CFG, **dict(
        dict(max_batch=2, prefill_chunk=8, block_size=4), **kw))


def _reqs(lens, new=6):
    rng = np.random.default_rng(0)
    return [Request(rid=i, max_new=new,
                    prompt=rng.integers(0, CFG.vocab_size, n)
                    .astype(np.int32)) for i, n in enumerate(lens)]


def _steps(ring):
    """(kind, args, seen) of each device step, in the order read."""
    out = []
    for e in ring:
        if e[0] == STEP + "unseen":
            out.append((e[5][0], e[5][1:], False))
        elif e[0].startswith(STEP):
            out.append((e[0][len(STEP):], e[5], True))
    return out


def _issued(ring):
    """What the two dispatch spans say was issued, grouped as the device
    ran it: (kind, args) a program whose result the host reads."""
    out, ahead = [], None
    for e in sorted((e for e in ring if e[0].endswith("_dispatch")),
                    key=lambda e: e[1]):
        if e[0] == "serve.prefill_dispatch":
            _, C, W, final = e[5][:4]
            if final:
                out.append(("chunk", (C, W)))
                ahead = None
            else:
                ahead = (C, W)
        elif ahead is None:
            out.append(("decode", e[5]))
        else:
            out.append(("chunk_decode", ahead + e[5]))
            ahead = None
    return out


@pytest.fixture(scope="module")
def served(params):
    reset_tracer()
    sched = _sched(params)
    res = sched.serve(_reqs([10, 18, 26, 34]))
    return res, get_tracer().spans()


def test_every_read_ends_one_device_step_of_the_kind_issued(served):
    _, ring = served
    steps = _steps(ring)
    reads = [e for e in ring
             if e[0] in ("serve.decode_sync", "serve.prefill_sync")]
    assert len(steps) == len(reads) > 20
    assert [(k, a) for k, a, _ in steps] == _issued(ring)
    assert {k for k, _, _ in steps} == {"decode", "chunk", "chunk_decode"}
    # a step ends inside the read that learnt of it
    ends = sorted(e[1] + e[2] for e in ring if e[0].startswith(STEP))
    for end, r in zip(ends, sorted(reads, key=lambda e: e[1])):
        assert r[1] <= end <= r[1] + r[2]


def test_device_steps_are_back_to_back(served):
    """No two overlap; a gap before one means nothing was queued when the
    step before it ended (the host was issuing: the step starts at the
    launch of its first program and is ``unseen``)."""
    _, ring = served
    steps = sorted((e for e in ring if e[0].startswith(STEP)),
                   key=lambda e: e[1])
    launches = {e[1] for e in ring
                if e[0] in ("serve.issue.take", "serve.issue.chunk_call")}
    gaps = 0
    for a, b in zip(steps, steps[1:]):
        gap = b[1] - (a[1] + a[2])
        assert gap >= 0
        if gap > 0:
            gaps += 1
            assert b[0] == STEP + "unseen" and b[1] in launches
    assert gaps < len(steps) - 1
    # the first step of a run has no read before it
    assert steps[0][0] == STEP + "unseen"


def test_device_steps_hang_under_the_iteration_that_read_them(served):
    _, ring = served
    by_id = {e[3]: e for e in ring}
    for e in ring:
        if e[0].startswith(STEP):
            it = by_id[e[4]]
            assert it[0] == "serve.iteration"
            assert it[1] <= e[1] + e[2] <= it[1] + it[2]


def test_issue_parts_lie_inside_their_dispatch_spans(served):
    _, ring = served
    by_id = {e[3]: e for e in ring}
    inside = {}
    for e in ring:
        if e[0] in PARTS:
            d = by_id[e[4]]
            assert d[0] == ("serve.prefill_dispatch"
                            if e[0] == "serve.issue.chunk_call"
                            else "serve.decode_dispatch")
            assert d[1] <= e[1] and e[1] + e[2] <= d[1] + d[2]
            inside.setdefault(d[3], []).append(e)
    for sid, parts in inside.items():
        assert sum(p[2] for p in parts) <= by_id[sid][2]
        assert [p[0] for p in parts] in ([PARTS[3]], list(PARTS[:3]))
        # take, the decode call and the pick follow each other with nothing
        # between them
        for p, q in zip(parts, parts[1:]):
            assert q[1] == pytest.approx(p[1] + p[2], abs=1e-9)
    n = {name: sum(e[0] == name for e in ring) for name in
         PARTS + ("serve.prefill_dispatch", "serve.decode_dispatch")}
    assert n["serve.issue.chunk_call"] == n["serve.prefill_dispatch"] > 0
    assert (n["serve.issue.take"] == n["serve.issue.decode_call"]
            == n["serve.issue.pick"] == n["serve.decode_dispatch"] > 0)


def test_a_step_read_without_waiting_is_unseen(params, monkeypatch):
    """Hold the host back after every decode step is issued: each result is
    ready when it is read, so no step's time is any kind's mean."""
    sched = _sched(params)
    issue = sched._issue_decode

    def slow(tr):
        out = issue(tr)
        time.sleep(0.03)
        return out

    monkeypatch.setattr(sched, "_issue_decode", slow)
    sched.serve(_reqs([10, 12], new=4))
    steps = _steps(get_tracer().spans())
    assert len(steps) >= 6 and not any(seen for _, _, seen in steps)
    assert {k for k, _, _ in steps} >= {"decode", "chunk"}


class _Result:
    """What ``_wait`` is handed, with the device's side made up."""

    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        return np.zeros(1, np.int32)


def test_the_rule_of_wait(params):
    """Seen: the host waited in this read and in the one before it, and the
    step was launched before that one returned. Otherwise unseen, from the
    launch where that is later; an unseen step's end is a start like any
    other if the host waited for it."""
    sched = _sched(params)
    tr = get_tracer()
    t = tr.clock()
    sched._wait(tr, _Result(False), ("decode", t, (2, 8)))       # the first
    t1 = sched._done[0]
    sched._wait(tr, _Result(False), ("chunk_decode", t, (8, 8, 2, 8)))
    t2 = sched._done[0]
    sched._wait(tr, _Result(True), ("chunk", t, (3, 8)))         # was ready
    sched._wait(tr, _Result(False), ("decode", t, (2, 8)))       # after it
    t4 = sched._done[0]
    sched._wait(tr, _Result(False), ("decode", t, (1, 8)))
    late = tr.clock() + 1e-3                 # launched onto an idle device
    sched._wait(tr, _Result(False), ("decode", late, (1, 8)))
    t6 = sched._done[0]
    sched._wait(tr, _Result(False), ("chunk", t, (5, 16)))
    got = [e for e in tr.spans() if e[0].startswith(STEP)]
    assert [(e[0][len(STEP):], e[5]) for e in got] == [
        ("unseen", ("decode", 2, 8)),
        ("chunk_decode", (8, 8, 2, 8)),
        ("unseen", ("chunk", 3, 8)),
        ("unseen", ("decode", 2, 8)),
        ("decode", (1, 8)),
        ("unseen", ("decode", 1, 8)),
        ("chunk", (5, 16))]
    assert got[1][1] == t1 and got[1][1] + got[1][2] == pytest.approx(t2)
    assert got[4][1] == t4
    assert got[5][1] == late                 # from the launch, not the read
    assert got[6][1] == t6
    assert all(e[4] == 0 for e in got)       # read outside any iteration


def test_chunks_with_no_decode_row_between_them_are_one_unseen_step(params):
    """A lone request's non-final chunks are read by nobody: the final
    chunk's read ends a step that held all three, which is counted and is
    no kind's time."""
    sched = _sched(params)
    sched.serve(_reqs([20], new=3))
    steps = _steps(get_tracer().spans())
    assert steps[0] == ("chunk", (4, 8), False)
    assert [k for k, _, _ in steps[1:]] == ["decode", "decode"]


def test_tokens_do_not_depend_on_the_ring(params, monkeypatch, served):
    """``BYTEPS_METRICS_ON=0`` stills the ring; what is served is the same
    tokens, and nothing is recorded."""
    from byteps_tpu.common import config as config_mod

    monkeypatch.setenv("BYTEPS_METRICS_ON", "0")
    config_mod.reset_config()
    reset_tracer()
    res = _sched(params).serve(_reqs([10, 18, 26, 34]))
    assert get_tracer().spans() == []
    for rid, r in served[0].items():
        np.testing.assert_array_equal(res[rid]["emitted"], r["emitted"])


def test_emit_takes_a_parent_and_the_ring_holds_a_saturated_window():
    rec = TraceRecorder(enabled=False)
    with rec.span("outer", "S") as outer:
        child = rec.emit("part", "S", 1.0, 0.5, parent=outer.sid)
        orphan = rec.emit("phase", "S", 1.0, 0.5, ("r",))
    part, phase, _ = rec.spans()
    assert part[3:5] == (child, outer.sid) and phase[3:5] == (orphan, 0)
    # ~2,700 entries a second of a 50 s window (tracing.py, at RING_SPANS)
    assert rec._ring.maxlen == RING_SPANS >= 2700 * 50
    N = 20000
    t0 = time.perf_counter()
    for i in range(N):
        rec.emit("bench.emit", "S", 0.0, 0.0, (i, 8), outer.sid)
    per_emit = (time.perf_counter() - t0) / N
    assert per_emit < 50e-6, f"emit {per_emit * 1e6:.2f}us"
