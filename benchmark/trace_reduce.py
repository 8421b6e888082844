"""From a profiler trace to the numbers per-layer metrics read.

``load`` reads the ``*.xplane.pb`` the JAX profiler wrote with
``jax.profiler.ProfileData`` (nothing but JAX) into a plain dict; everything
after that is pure Python on that dict, which is also the form the small
recorded trace under ``benchmark/tests/`` is kept in. Category names are
those of XLA's ``hlo_category`` statistic ("convolution fusion" is MXU work,
"custom-call" a Pallas kernel, "data formatting" a copy ...), kept here and
nowhere in the program since PR 41. On the installed JAX a device event
carries its whole HLO instruction as its name and no category, so
``categorise`` works the category out from the instruction's opcode and
fusion kind, and the event keeps the instruction's short name.

The "XLA Ops" line nests: a ``while`` spans the operations of its body. The
busy time is a union and unaffected; sums by category count leaves, and
``control flow`` (the containers) is left out of the list of longest ops.

The dict::

    {"devices": {"0": [[name, category, start_ns, dur_ns], ...], ...},
     "spans":   [[name, start_ns, dur_ns], ...]}     # host annotations

``devices`` holds the events of each device plane's "XLA Ops" line;
``spans`` the benchmark's own ``jax.profiler.TraceAnnotation`` spans, on the
same clock.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
NO_SPAN = "_no_span_"
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")
CONTROL_FLOW = "control flow"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HLO = re.compile(r"^%?(\S+) = .*?\s([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r", kind=k(\w+)")
_FUSION = {"Output": "convolution fusion", "Loop": "loop fusion",
           "Input": "input fusion", "Custom": "custom fusion"}
_FORMATTING = {"copy", "copy-start", "copy-done", "transpose", "reshape",
               "bitcast", "slice", "dynamic-slice", "dynamic-update-slice",
               "concatenate", "pad", "broadcast", "reverse", "slice-start",
               "slice-done"}


def categorise(name: str, stats=()) -> Tuple[str, str]:
    """(short name, category) of one device event. ``name`` is either a
    whole HLO instruction (``%fusion.12 = bf16[..] fusion(..), kind=kOutput,
    ..``) or already short, with the category among ``stats``."""
    m = _HLO.match(name)
    if not m:
        return name, next((str(v) for k, v in stats
                           if k == "hlo_category"), "")
    short, op = m.group(1), m.group(2)
    if op == "fusion":
        k = _KIND.search(name)
        return short, _FUSION.get(k.group(1) if k else "", "fusion")
    if op in ("convolution", "dot"):
        return short, "convolution"
    if op in _FORMATTING:
        return short, "data formatting"
    if op in ("while", "conditional", "call"):
        return short, CONTROL_FLOW
    for c in COLLECTIVES:
        if op.startswith(c):
            return short, c
    return short, op


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, span_names: Iterable[str]) -> Dict:
    """The dict above from one xplane file. ``span_names``: the host
    annotations to keep (the benchmark's own)."""
    from jax.profiler import ProfileData

    keep = set(span_names) | {WINDOW_SPAN}
    devices: Dict[str, List] = {}
    spans: List = []
    host_ops: List = []
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = []
                for ev in line.events:
                    short, cat = categorise(ev.name, ev.stats)
                    evs.append([short, cat, float(ev.start_ns),
                                float(ev.duration_ns)])
                devices[m.group(1)] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in keep:
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)])
                    elif any(k == "hlo_op" for k, _ in ev.stats):
                        host_ops.append([ev.name, "", float(ev.start_ns),
                                         float(ev.duration_ns)])
    if not devices and host_ops:
        # a rehearsal on the CPU backend: its XLA ops sit on host threads.
        # Good for exercising this file, never for a number.
        devices["0"] = host_ops
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def window(trace: Dict) -> Tuple[float, float]:
    """The traced window in the trace's clock: the ``bench.window`` span
    where it was recorded, else first device event to last."""
    for name, start, dur in trace["spans"]:
        if name == WINDOW_SPAN:
            return start, start + dur
    evs = [e for d in trace["devices"].values() for e in d]
    if not evs:
        raise ValueError("a trace with no bench.window span and no device "
                         "event: nothing says what was traced")
    return (min(e[2] for e in evs), max(e[2] + e[3] for e in evs))


def _clipped(events: Sequence, w0: float, w1: float):
    for name, cat, start, dur in events:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a:
            yield name, cat, a, b


def busy_intervals(events: Sequence, w0: float, w1: float) -> List[List[float]]:
    """Union of the device events' intervals inside the window."""
    out: List[List[float]] = []
    for _, _, a, b in sorted(_clipped(events, w0, w1), key=lambda e: e[2]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(events: Sequence, w0: float, w1: float) -> float:
    return sum(b - a for a, b in busy_intervals(events, w0, w1))


def category_ns(events: Sequence, w0: float, w1: float) -> Dict[str, float]:
    """Device time by ``hlo_category`` inside the window (summed durations;
    ops of one core do not overlap)."""
    out: Dict[str, float] = collections.defaultdict(float)
    for _, cat, a, b in _clipped(events, w0, w1):
        out[cat] += b - a
    return dict(out)


def matching_ns(events: Sequence, w0: float, w1: float,
                categories: Sequence[str]) -> float:
    """Device time of the events whose category is one of ``categories``."""
    cats = set(categories)
    return sum(b - a for _, cat, a, b in _clipped(events, w0, w1)
               if cat in cats)


def top_ops(events: Sequence, w0: float, w1: float, k: int = 10):
    """The ``k`` operations with most device time, [[name, seconds], ...],
    under the names the trace prints."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for name, cat, a, b in _clipped(events, w0, w1):
        if cat != CONTROL_FLOW:
            tot[name] += b - a
    return [[n, ns / 1e9] for n, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps_by_span(events: Sequence, spans: Sequence, w0: float,
                      w1: float, k: int = 10):
    """The device's idle time inside the window, summed by the host span
    that covers each instant of it (``_no_span_`` where none does):
    [[span, seconds], ...], longest first."""
    busy = busy_intervals(events, w0, w1)
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    spans = sorted((s, s + d, n) for n, s, d in spans if n != WINDOW_SPAN)
    starts = [s[0] for s in spans]
    tot: Dict[str, float] = collections.defaultdict(float)
    for a, b in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(spans) and spans[i][0] < b:
            lo, hi = max(a, spans[i][0]), min(b, spans[i][1])
            if hi > lo:
                tot[spans[i][2]] += hi - lo
                covered += hi - lo
            i += 1
        if b - a > covered:
            tot[NO_SPAN] += b - a - covered
    return [[n, ns / 1e9] for n, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def spans_inside(trace: Dict, name: str, w0: float, w1: float) -> int:
    """How many host spans of that name lie wholly inside the window."""
    return sum(1 for n, s, d in trace["spans"]
               if n == name and s >= w0 and s + d <= w1)


class Reduced:
    """What readers are handed: one trace, reduced once."""

    def __init__(self, trace: Dict, chips: int):
        self.trace = trace
        self.w0, self.w1 = window(trace)
        # a device that ran nothing while the profiler was on leaves a
        # plane with no "XLA Ops" line, or no plane: no event, busy 0
        ids = sorted(trace["devices"], key=int)[:chips]
        self.device_ids = ids
        self.first = trace["devices"][ids[0]] if ids else []   # device 0
        self.window_s = (self.w1 - self.w0) / 1e9
        self.busy_s = sum(busy_ns(trace["devices"][i], self.w0, self.w1)
                          for i in ids) / max(len(ids), 1) / 1e9
        self.busy0_s = busy_ns(self.first, self.w0, self.w1) / 1e9

    def seconds(self, categories) -> float:
        return matching_ns(self.first, self.w0, self.w1, categories) / 1e9

    def count(self, span: str) -> int:
        return spans_inside(self.trace, span, self.w0, self.w1)

    def breakdown(self) -> Dict:
        return {"device_ops": top_ops(self.first, self.w0, self.w1),
                "idle_gaps": idle_gaps_by_span(
                    self.first, self.trace["spans"], self.w0, self.w1)}
