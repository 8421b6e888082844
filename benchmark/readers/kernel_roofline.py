"""Roofline share of one of a serving cell's kernels: the least time the chip
could take for the work the program itself counted in the TRACED iterations
(every counter and every histogram's sum, between the driver's reading at the
start of the trace and the window's end, the late series flushed at both), as
the function ``work`` of the module the configuration's file names under
``flops`` (``benchmark/flops_<model>.py``) turns it into operations and bytes,
over the device time of the events named ``names`` in the traced window.
``None`` where the program keeps no such series, the driver marked no trace
start, the configuration names no module or its module has no such function,
or the trace holds no such event."""

import importlib

from benchmark import flops
from benchmark.readers.trace_named_ms_per_step import named_ns


def read(run, observed, names, work):
    r = run.reduced
    c, h = observed.get("counters"), observed.get("histograms")
    module = run.config.get("flops")
    if r is None or not c or not h or "trace_start" not in c \
            or "trace_start" not in h or not module:
        return None
    count = getattr(importlib.import_module(f"benchmark.{module}"), work,
                    None)
    if count is None:
        return None
    done = {k: c["end"][k] - c["trace_start"][k] for k in c["end"]}
    done.update({k: v.get("sum", 0.0) - h["trace_start"][k].get("sum", 0.0)
                 for k, v in h["end"].items()})
    n, ns = named_ns(r.first, r.w0, r.w1, names)
    try:
        need = count(done, run.config.get("gpt_config", {}))
    except KeyError:
        return None
    if n == 0 or max(need["flops"], need["bytes"]) <= 0:
        return None
    share = flops.roofline_pct(need["flops"], need["bytes"], ns / 1e9,
                               flops.peaks(run.device["kind"]))
    observed.setdefault("notes", {})[f"{work}_roofline_bound"] = \
        share["bound"]
    return share["pct"]
