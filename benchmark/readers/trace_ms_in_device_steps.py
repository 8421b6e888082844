"""Device milliseconds of some operations INSIDE the device steps of one
kind, per such step, on the first device of the traced slice.

A device step is a span the serve scheduler emits when it learns the step is
done (``serve.device_step.<kind>``: a decode step alone, a final chunk
alone, a non-final chunk and the decode step behind it; ``unseen`` where the
host did not wait at one of its ends): from the return of one read the host
blocked in to the return of the next, on ``time.monotonic()``. The spans are
laid on the trace's clock by the one constant ``program_idle_ms.align``
finds (the program's ``anchor`` span against the benchmark's
``trace_anchor`` annotation), so which program a device operation belongs to
comes from what the scheduler issued, not from an event's index.

The operations are those whose category is in ``categories``, else those
whose short name less its ``.N`` suffix is in ``names``, else all of them
(the union of their intervals: busy time). The result is their time inside
the steps of ``kind`` that lie wholly inside the traced window, over the
number of those steps.

**The cut.** A step's interval is its two stamps, moved by that constant and
by nothing else; an operation that straddles a stamp is split there. The
host wakes a little after the device finishes, so a stamp lies some tens of
microseconds inside the NEXT step's first operations: each step gives that
much of its head to the step before it and takes as much from the one after.
Between steps of one kind that cancels; between kinds it is the lag over the
step's length. The lag is recorded, not corrected for (a correction would be
a guess): ``device_step_clock`` in ``observed["notes"]`` holds the offset
and its spread, ``lag_ns_median`` (each stamp of the slice against the end
of the last device event that ended before it: under a busy device that
event belongs to the next step already, so this is a floor) and
``lag_idle_ns_median`` over the ``idle_stamps``: the ends of seen steps at
which the device ran nothing (there the event is the step's own last: the
lag itself).
``device_steps.<kind>.<what>`` holds, for every kind, the steps in the slice
and the milliseconds inside them, and ``outside_ms`` (inside no step), so
that the kinds add up to the window.

``None`` where there is no ring or no trace, the clocks did not align
(``program_idle_ms.MAX_SPREAD_NS``), the slice holds no step of the kind, or
no operation of the window is among those asked for.
"""

import bisect
import statistics

from benchmark import trace_reduce
from benchmark.readers import program_idle_ms, program_span_ms
from benchmark.readers.trace_named_ms_per_step import _SUFFIX

PREFIX = "serve.device_step."


def steps_on_trace(entries, off, w0, w1):
    """The device steps wholly inside the window, on the trace's clock, in
    order: ``[(start_ns, end_ns, kind), ...]``."""
    out = []
    for e in entries:
        if e[0].startswith(PREFIX):
            a = e[1] * 1e9 + off
            b = a + e[2] * 1e9
            if a >= w0 and b <= w1:
                out.append((a, b, e[0][len(PREFIX):]))
    return sorted(out)


def selected(events, w0, w1, categories=None, names=None):
    """``[(start_ns, end_ns), ...]`` of the operations asked for, clipped
    to the window, in order."""
    if categories is not None:
        cats = set(categories)
        picked = [e for e in events if e[1] in cats]
    elif names is not None:
        names = set(names)
        picked = [e for e in events if _SUFFIX.sub("", e[0]) in names]
    else:
        return [tuple(i) for i in trace_reduce.busy_intervals(events, w0, w1)]
    return sorted((a, b) for _, _, a, b in
                  trace_reduce._clipped(picked, w0, w1))


def ns_by_kind(intervals, steps):
    """({kind: ns of the intervals inside the steps of that kind}, ns inside
    no step). ``steps`` in order and disjoint."""
    starts = [s[0] for s in steps]
    tot, outside = {}, 0.0
    for a, b in intervals:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(steps) and steps[i][0] < b:
            lo, hi = max(a, steps[i][0]), min(b, steps[i][1])
            if hi > lo:
                tot[steps[i][2]] = tot.get(steps[i][2], 0.0) + hi - lo
                covered += hi - lo
            i += 1
        outside += b - a - covered
    return tot, outside


def lags(events, steps, w0, w1):
    """(every stamp's distance from the end of the last device event that
    ended at or before it, the same for the stamps the host waited for at
    which the device ran nothing), in nanoseconds. An ``unseen`` step's end
    may be a read that found its result ready, any time after the device
    went idle: it says nothing of how fast the host wakes."""
    ends = sorted(e[2] + e[3] for e in events)
    busy = trace_reduce.busy_intervals(events, w0, w1)
    busy_starts = [a for a, _ in busy]
    all_, idle = [], []
    for _, stamp, kind in steps:
        i = bisect.bisect_right(ends, stamp) - 1
        if i < 0:
            continue
        all_.append(stamp - ends[i])
        j = bisect.bisect_right(busy_starts, stamp) - 1
        if kind != "unseen" and j >= 0 and busy[j][1] < stamp:
            idle.append(stamp - busy[j][1])
    return all_, idle


def ms_in_steps(entries, reduced, kind, anchor, trace_anchor,
                categories=None, names=None, notes=None):
    """The arithmetic of ``read``: ring entries and a
    ``trace_reduce.Reduced``."""
    notes = {} if notes is None else notes
    found = program_idle_ms.align(
        [e[1] * 1e9 for e in entries if e[0] == anchor],
        [s for n, s, _ in reduced.trace["spans"] if n == trace_anchor])
    clock = notes["device_step_clock"] = found and {
        "offset_ns": found[0], "spread_ns": found[1], "pairs": found[2]}
    if found is None or found[1] > program_idle_ms.MAX_SPREAD_NS:
        return None
    w0, w1 = reduced.w0, reduced.w1
    steps = steps_on_trace(entries, found[0], w0, w1)
    all_, idle = lags(reduced.first, steps, w0, w1)
    clock.update(
        stamps=len(all_), idle_stamps=len(idle),
        lag_ns_median=statistics.median(all_) if all_ else None,
        lag_idle_ns_median=statistics.median(idle) if idle else None)
    n = {}
    for _, _, k in steps:
        n[k] = n.get(k, 0) + 1
    intervals = selected(reduced.first, w0, w1, categories, names)
    if not n.get(kind) or not intervals:
        return None
    tot, outside = ns_by_kind(intervals, steps)
    what = ",".join(categories or names or ["busy"])
    notes[f"device_steps.{kind}.{what}"] = {
        "steps": n, "ms": {k: v / 1e6 for k, v in tot.items()},
        "outside_ms": outside / 1e6}
    return tot.get(kind, 0.0) / 1e6 / n[kind]


def read(run, observed, kind, anchor, trace_anchor, categories=None,
         names=None):
    entries = program_span_ms.ring()
    if not entries or run.reduced is None:
        return None
    return ms_in_steps(entries, run.reduced, kind, anchor, trace_anchor,
                       categories, names, observed.setdefault("notes", {}))
