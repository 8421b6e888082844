"""Device milliseconds of the operations with some names per step, on the
first device: the summed durations, inside the traced window, of the events
whose short name less its ``.N`` suffix is one of ``names`` (a Pallas
kernel's ``name=`` is its HLO instruction's name, so its event's), divided
by the number of ``step_span`` host spans wholly inside the window. ``None``
where no event has such a name or the window holds no step."""

import re

_SUFFIX = re.compile(r"\.\d+$")


def named_ns(events, w0, w1, names):
    """(events, nanoseconds) of the named operations inside the window."""
    names, n, ns = set(names), 0, 0.0
    for name, _, start, dur in events:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a and _SUFFIX.sub("", name) in names:
            n, ns = n + 1, ns + b - a
    return n, ns


def read(run, observed, names, step_span):
    r = run.reduced
    if r is None:
        return None
    steps = r.count(step_span)
    n, ns = named_ns(r.first, r.w0, r.w1, names)
    if steps == 0 or n == 0:
        return None
    return ns / 1e6 / steps
