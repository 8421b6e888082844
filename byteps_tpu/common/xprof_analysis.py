"""Device-trace (xprof) attribution for bench workloads.

``jax.profiler`` captures fire per-kernel events on the DEVICE timeline
with hardware timestamps (reference analog: the role BytePS' per-stage
chrome traces + server timelines play for its pipeline, SURVEY §5.1 —
here the device side, which the reference reads out of nvprof instead).
Those timestamps are device time, independent of the host clock.

Primary data source: the ``*.xplane.pb`` protobuf the profiler writes
(parsed with tensorflow's bundled xplane proto), whose "XLA Ops" line
carries ``hlo_category`` per op — XLA's own MXU-vs-VPU-vs-copy verdict
("convolution fusion" = MXU work, "loop fusion" = elementwise/VPU,
"custom-call" = Pallas kernels, ...). The gzipped chrome trace next to
it has the same events but fusion names only; it remains the fallback
when no tensorflow is importable.

CLI::

    python -m byteps_tpu.common.xprof_analysis TRACE_DIR [--module NAME]

where TRACE_DIR is what ``jax.profiler.start_trace`` received (e.g.
``$BYTEPS_TRACE_DIR/xprof_rank0`` from ``BYTEPS_TRACE_XPROF=1``, or
``bench.py --mode profile``'s output dir).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class KernelStat:
    name: str            # HLO instruction (result shape included)
    category: str        # hlo_category (xplane) or name-pattern bucket
    count: int
    total_us: float


@dataclasses.dataclass
class StepProfile:
    """Aggregated attribution over the captured module executions."""

    module: str                       # jit_<name>
    n_steps: int
    step_us: float                    # MEAN device span per execution —
                                      # the same denominator as every
                                      # per-step kernel/category number
                                      # (totals / n), so percentages sum
                                      # to <= 100% and gap_us is exact
    kernels: List[KernelStat]         # sorted by total_us desc
    category_us: Dict[str, float]     # per-step, summed by category
    gap_us: float                     # per-step device idle inside spans

    def table(self, top: int = 20) -> str:
        lines = [
            f"module {self.module}: {self.n_steps} executions, "
            f"{self.step_us / 1e3:.3f} ms/step on-device",
            f"{'hlo category':<26}{'ms/step':>10}{'% of step':>11}",
        ]
        for c, us in sorted(self.category_us.items(), key=lambda kv: -kv[1]):
            lines.append(
                f"{c:<26}{us/1e3:>10.3f}{100*us/self.step_us:>10.1f}%")
        lines.append(f"{'gap (in-step idle)':<26}{self.gap_us/1e3:>10.3f}"
                     f"{100*self.gap_us/self.step_us:>10.1f}%")
        lines.append("")
        lines.append(f"{'op (top by time)':<56}{'category':<22}{'count':>6}"
                     f"{'ms/step':>9}{'%':>7}")
        for k in self.kernels[:top]:
            per_step = k.total_us / self.n_steps
            lines.append(
                f"{k.name[:55]:<56}{k.category[:21]:<22}{k.count:>6}"
                f"{per_step/1e3:>9.3f}{100*per_step/self.step_us:>6.1f}%")
        return "\n".join(lines)


def _profile_run_dir(trace_dir: str) -> str:
    runs = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*")))
    if not runs:
        raise FileNotFoundError(
            f"no plugins/profile/* run under {trace_dir!r} — was the "
            "capture stopped?")
    return runs[-1]


# ---------------------------------------------------------------------------
# primary path: xplane.pb (hlo_category per op)
# ---------------------------------------------------------------------------

def _load_xplane(trace_dir: str):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa: PLC0415

    files = sorted(glob.glob(
        os.path.join(_profile_run_dir(trace_dir), "*.xplane.pb")))
    if not files:
        raise FileNotFoundError("no *.xplane.pb in the profile run dir")
    xs = xplane_pb2.XSpace()
    with open(files[-1], "rb") as f:
        xs.ParseFromString(f.read())
    for plane in xs.planes:
        if "/device:" in plane.name and any(
                l.name == "XLA Ops" for l in plane.lines):
            return plane
    raise RuntimeError(
        f"no device plane with an 'XLA Ops' line in {files[-1]!r} "
        f"(planes: {[p.name for p in xs.planes]})")


def attribute_xplane(trace_dir: str,
                     module: Optional[str] = None) -> StepProfile:
    plane = _load_xplane(trace_dir)
    smd = {k: v.name for k, v in plane.stat_metadata.items()}
    emd = plane.event_metadata

    def line(name):
        for l in plane.lines:
            if l.name == name:
                return l
        return None

    mod_line, ops_line = line("XLA Modules"), line("XLA Ops")
    if mod_line is None or ops_line is None:
        raise RuntimeError(
            "device plane lacks an 'XLA Modules'/'XLA Ops' line — "
            "falling back to the chrome trace")
    # dominant module = most total device time (the train step, not the
    # little fence/_reduce_sum programs the timing machinery also runs)
    by_mod = collections.defaultdict(list)
    for ev in mod_line.events:
        nm = emd[ev.metadata_id].name
        if module is None or module in nm:
            by_mod[nm].append(ev)
    if not by_mod:
        raise RuntimeError(f"no XLA module matching {module!r}")
    mod_name, mod_events = max(
        by_mod.items(), key=lambda kv: sum(e.duration_ps for e in kv[1]))
    spans = sorted((e.offset_ps, e.offset_ps + e.duration_ps)
                   for e in mod_events)
    n = len(mod_events)
    step_us = sum(e.duration_ps for e in mod_events) / n / 1e6

    def in_module(off):
        import bisect
        i = bisect.bisect_right(spans, (off, float("inf"))) - 1
        return i >= 0 and spans[i][0] <= off < spans[i][1]

    agg: Dict[str, KernelStat] = {}
    busy_ps = 0
    for ev in ops_line.events:
        if not in_module(ev.offset_ps):
            continue
        md = emd[ev.metadata_id]
        cat = "?"
        for st in list(ev.stats) + list(md.stats):
            if smd.get(st.metadata_id) == "hlo_category":
                cat = st.str_value or cat
                break
        st_ = agg.get(md.name)
        if st_ is None:
            agg[md.name] = KernelStat(md.name, cat, 1, ev.duration_ps / 1e6)
        else:
            st_.count += 1
            st_.total_us += ev.duration_ps / 1e6
        busy_ps += ev.duration_ps
    kernels = sorted(agg.values(), key=lambda k: -k.total_us)
    category_us: Dict[str, float] = collections.defaultdict(float)
    for k in kernels:
        category_us[k.category] += k.total_us / n
    gap = max(0.0, step_us - busy_ps / 1e6 / n)
    return StepProfile(module=mod_name, n_steps=n, step_us=step_us,
                       kernels=kernels, category_us=dict(category_us),
                       gap_us=gap)


# ---------------------------------------------------------------------------
# fallback path: chrome trace json (fusion names only)
# ---------------------------------------------------------------------------

_BUCKETS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("collective", ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")),
    ("convolution fusion", ("convolution", "dot", "gemm")),
    ("copy", ("copy", "transpose", "bitcast")),
    ("custom-call", ("custom-call", "jvp_jit", "pallas")),
    ("scatter/gather/sort", ("scatter", "gather", "sort", "top-k")),
)


def _bucket_of(name: str) -> str:
    nl = name.lower()
    for bucket, pats in _BUCKETS:
        for p in pats:
            if p in nl:
                return bucket
    return "loop fusion"


_MODULE_RE = re.compile(r"^jit_\w+\(\d+\)$|^jit_\w+$|^pjit_\w+")


def attribute_trace_json(trace_dir: str,
                         module: Optional[str] = None) -> StepProfile:
    files = sorted(glob.glob(
        os.path.join(_profile_run_dir(trace_dir), "*.trace.json.gz")))
    if not files:
        raise FileNotFoundError("no *.trace.json.gz in the profile run dir")
    with gzip.open(files[-1], "rt") as f:
        trace = json.load(f)
    evs = trace.get("traceEvents", [])
    lanes = {e["pid"]: e.get("args", {}).get("name", "")
             for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    dev_pids = {p for p, nm in lanes.items() if "/device:" in nm.lower()}
    dev = [e for e in evs
           if e.get("ph") == "X" and e.get("pid") in dev_pids
           and "dur" in e and "ts" in e]
    spans = [e for e in dev if _MODULE_RE.match(e["name"])
             and (module is None or module in e["name"])]
    if not spans:
        raise RuntimeError("no jit_* module spans on the device lane")
    by_mod = collections.defaultdict(list)
    for e in spans:
        by_mod[e["name"]].append(e)
    mod_name, mod_spans = max(
        by_mod.items(), key=lambda kv: sum(e["dur"] for e in kv[1]))
    mod_spans.sort(key=lambda e: e["ts"])
    n = len(mod_spans)
    step_us = sum(e["dur"] for e in mod_spans) / n
    agg: Dict[str, KernelStat] = {}
    busy = 0.0
    for s in mod_spans:
        t0, t1 = s["ts"], s["ts"] + s["dur"]
        for e in dev:
            if (e is s or _MODULE_RE.match(e["name"])
                    or not (t0 <= e["ts"] and e["ts"] + e["dur"] <= t1)):
                continue
            st = agg.get(e["name"])
            if st is None:
                agg[e["name"]] = KernelStat(
                    e["name"], _bucket_of(e["name"]), 1, e["dur"])
            else:
                st.count += 1
                st.total_us += e["dur"]
            busy += e["dur"]
    kernels = sorted(agg.values(), key=lambda k: -k.total_us)
    category_us: Dict[str, float] = collections.defaultdict(float)
    for k in kernels:
        category_us[k.category] += k.total_us / n
    return StepProfile(module=mod_name, n_steps=n, step_us=step_us,
                       kernels=kernels, category_us=dict(category_us),
                       gap_us=max(0.0, step_us - busy / n))


def attribute(trace_dir: str, module: Optional[str] = None) -> StepProfile:
    """xplane (hlo_category) when tensorflow is importable and the
    capture carries a usable device plane, else the chrome-trace
    fallback with name-pattern buckets (same run dir, fusion names
    only). Raises only when both sources fail."""
    try:
        return attribute_xplane(trace_dir, module=module)
    except (ImportError, FileNotFoundError, RuntimeError):
        return attribute_trace_json(trace_dir, module=module)


def profile_fn(fn, trace_dir: str, steps: int = 8, warmup: int = 1,
               module: Optional[str] = None) -> StepProfile:
    """Capture ``steps`` calls of ``fn`` (which must block until its
    step's work is done, e.g. via a fence) and attribute the trace.
    ``warmup`` calls run outside the window (compile + cache warm)."""
    import jax

    for _ in range(max(1, warmup)):
        fn()
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(steps):
            fn()
    finally:
        jax.profiler.stop_trace()
    return attribute(trace_dir, module=module)


def main(argv: Optional[List[str]] = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--module", default=None,
                    help="jit_* module name substring (default: dominant)")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args(argv)
    prof = attribute(args.trace_dir, module=args.module)
    print(prof.table(top=args.top))


if __name__ == "__main__":
    main()
