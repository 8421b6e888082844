"""Milliseconds the device sat idle while the program was inside some of
its own spans, per ``per_span`` span, on the traced part of the window.

The device's busy intervals come from the reduced trace (first device);
the program's spans from its ring (``program_span_ms.ring``), which is on
``time.monotonic()`` while the trace is on the profiler's clock. The two
differ by one constant per profiler session. It is found from an anchor
both sides have: the benchmark's ``trace_anchor`` annotation (around each
call into the program) starts a few microseconds before the program's
``anchor`` span inside that call. The trace's anchor starts are slid along
the program's as sequences, the alignment of least residual is taken, and
its median offset used. Where the matched pairs' offsets spread (first to
third quartile) by more than ``MAX_SPREAD_NS`` the clocks were not
aligned and the reader returns ``None``: the metric is left out, never
guessed. ``offset_seen`` in ``observed["notes"]`` records what it saw.
"""

import statistics

from benchmark import trace_reduce
from benchmark.readers import program_span_ms

MAX_SPREAD_NS = 50e3
MIN_PAIRS = 3


def align(program_ns, trace_ns):
    """(offset, spread, pairs): ``trace = program + offset`` for the
    alignment of the whole ``trace_ns`` sequence against a run of
    ``program_ns`` (both ascending) with the least summed distance from
    its median offset; None with fewer than ``MIN_PAIRS`` trace anchors or
    fewer program anchors than that."""
    m = len(trace_ns)
    if m < MIN_PAIRS or len(program_ns) < m:
        return None
    best = None
    for s in range(len(program_ns) - m + 1):
        offs = [b - p for b, p in zip(trace_ns, program_ns[s:s + m])]
        med = statistics.median(offs)
        resid = sum(abs(o - med) for o in offs)
        if best is None or resid < best[0]:
            best = (resid, med, offs)
    q = statistics.quantiles(best[2], n=4)
    return best[1], q[2] - q[0], m


def idle_ms(entries, reduced, spans, per_span, anchor, trace_anchor,
            notes=None):
    """The arithmetic of ``read``: ring entries and a
    ``trace_reduce.Reduced``."""
    found = align(
        [e[1] * 1e9 for e in entries if e[0] == anchor],
        [s for n, s, _ in reduced.trace["spans"] if n == trace_anchor])
    if notes is not None:
        notes["offset_seen"] = found and {
            "offset_ns": found[0], "spread_ns": found[1], "pairs": found[2]}
    if found is None or found[1] > MAX_SPREAD_NS:
        return None
    off, w0, w1 = found[0], reduced.w0, reduced.w1
    names = set(spans) | {per_span}
    mapped = [[e[0], e[1] * 1e9 + off, e[2] * 1e9] for e in entries
              if e[0] in names]
    n = sum(1 for name, s, d in mapped
            if name == per_span and s >= w0 and s + d <= w1)
    if n == 0:
        return None
    gaps = trace_reduce.idle_gaps_by_span(
        reduced.first, [m for m in mapped if m[0] in spans], w0, w1,
        k=len(spans) + 1)
    return 1e3 * sum(sec for name, sec in gaps if name in spans) / n


def read(run, observed, spans, per_span, anchor, trace_anchor):
    entries = program_span_ms.ring()
    if not entries or run.reduced is None:
        return None
    return idle_ms(entries, run.reduced, spans, per_span, anchor,
                   trace_anchor, observed.setdefault("notes", {}))
