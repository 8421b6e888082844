"""The serve driver's loop (``drivers.serve.drive``) against a stub
scheduler that admits ``cap`` waiting requests a step and finishes them in
that step: hundreds of times the rate the mix was sized for."""

import collections
import contextlib
import time

import pytest

from benchmark import harness, traffic_gen
from benchmark.drivers import serve

HERE = harness.HERE


class StubRun:
    """What ``drive`` asks of ``harness.Run``."""

    def __init__(self, seconds):
        self.seconds = seconds
        self.spans = collections.Counter()
        self.closed_at = None

    @contextlib.contextmanager
    def span(self, name):
        self.spans[name] += 1
        yield

    def open_window(self):
        return time.monotonic()

    def close_window(self):
        self.closed_at = time.monotonic()

    def trace_due(self, t0, now):
        return False


class StubScheduler:
    def __init__(self, cap):
        self.cap, self.waiting, self.results = cap, collections.deque(), {}
        self.seen_empty = False
        self.committed = 0

    def submit(self, r, base):
        self.waiting.append(r)

    @property
    def finished(self):
        return not self.waiting

    def step(self):
        self.seen_empty |= not self.waiting
        time.sleep(5e-4)
        for _ in range(min(self.cap, len(self.waiting))):
            r = self.waiting.popleft()
            self.results[r.rid] = r
            self.committed += r.max_new
        return True


def _spec(**arrivals):
    """The saturated mix with a short ramp and these ``arrivals``."""
    t = harness.load_json(HERE, "traffic", "chat-backlog-sat.json")
    spec = harness.merged(
        harness.load_json(HERE, "traffic", t["multiset"] + ".json"), t)
    return dict(spec, ramp_seconds=0.05,
                arrivals=dict(arrivals, kind="backlog"))


def _drive(spec, seconds=0.25, cap=4):
    h, sched = StubRun(seconds), StubScheduler(cap)
    backlog = traffic_gen.Backlog(spec, 11, seconds, 50257, 1024)
    t0 = time.monotonic()
    seen = serve.drive(
        h, sched, spec, sched.submit, backlog.initial, backlog,
        waiting=lambda: len(sched.waiting), tokens=lambda: sched.committed,
        reading=lambda now: {"t": now})
    return h, sched, backlog, seen, time.monotonic() - t0


def test_the_queue_is_kept_full_to_the_end_of_the_window():
    spec = _spec(requests_per_second_of_run=100.0, queued_min=16)
    h, sched, backlog, seen, took = _drive(spec)
    n0 = len(backlog.initial)
    assert n0 == 30                      # gone after 8 steps of 4
    assert not sched.seen_empty and seen["refills"] > 10
    # read before each top-up: within one step's admissions of queued_min
    assert 16 - 4 <= seen["queued_min"] < 16
    # whole cycles, continuing the rids; the run ends when the window does
    made = seen["reqs"]
    assert [r.rid for r in made] == list(range(len(made)))
    assert (len(made) - n0) % spec["cycle"] == 0
    assert seen["end"]["t"] - seen["start"]["t"] == pytest.approx(0.25,
                                                                  abs=0.02)
    assert took < 0.25 + 0.05 + 0.1 and sched.waiting   # drain is false
    assert h.closed_at is not None
    assert h.spans["serve.submit"] == 1 + seen["refills"]
    assert seen["refill_ms_total"] > 0
    # the reference check draws from every completed request, and most of
    # those were topped up: each is among the requests the loop returns
    by_rid = {r.rid: r for r in made}
    assert set(sched.results) <= set(by_rid)
    assert max(sched.results) >= n0
    # committed tokens by tenth of the window: ten slices, none empty
    assert len(seen["tokens_by_slice"]) == serve.SLICES
    assert min(seen["tokens_by_slice"]) > 0
    assert sum(seen["tokens_by_slice"]) <= sched.committed


def test_a_backlog_that_is_deep_enough_is_never_topped_up():
    spec = _spec(requests_per_second_of_run=1e4, queued_min=16)
    h, sched, backlog, seen, _ = _drive(spec)
    assert seen["refills"] == 0 and seen["refill_ms_total"] == 0.0
    assert seen["reqs"] == backlog.initial
    assert seen["queued_min"] >= 16 and h.spans["serve.submit"] == 1


def test_without_queued_min_the_queue_runs_dry_and_the_run_says_so():
    spec = _spec(requests_per_second_of_run=100.0)
    h, sched, backlog, seen, _ = _drive(spec)
    assert seen["refills"] == 0 and seen["queued_min"] == 0
    assert sched.seen_empty and seen["tokens_by_slice"][-1] == 0
