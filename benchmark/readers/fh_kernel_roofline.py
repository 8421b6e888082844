"""Roofline share of one of the Falcon-H1 serving cell's kernels: the least
time the chip could take for the work the program itself counted in the TRACED
iterations (every counter and every histogram's sum, between the driver's
reading at the start of the trace and the window's end, the late series
flushed at both), as ``benchmark/flops_falconh1.py``'s function ``work`` turns
it into operations and bytes, over the device time of the events named
``names`` in the traced window. ``None`` where the program keeps no such
series, the driver marked no trace start, the configuration is another
model's, or the trace holds no such event."""

from benchmark import flops, flops_falconh1
from benchmark.readers.trace_named_ms_per_step import named_ns


def read(run, observed, names, work):
    r = run.reduced
    c, h = observed.get("counters"), observed.get("histograms")
    g = run.config.get("gpt_config", {})
    if r is None or not c or not h or "trace_start" not in c \
            or "trace_start" not in h or "ssm_heads" not in g:
        return None
    done = {k: c["end"][k] - c["trace_start"][k] for k in c["end"]}
    done.update({k: v.get("sum", 0.0) - h["trace_start"][k].get("sum", 0.0)
                 for k, v in h["end"].items()})
    n, ns = named_ns(r.first, r.w0, r.w1, names)
    try:
        need = getattr(flops_falconh1, work)(done, g)
    except KeyError:
        return None
    if n == 0 or max(need["flops"], need["bytes"]) <= 0:
        return None
    share = flops.roofline_pct(need["flops"], need["bytes"], ns / 1e9,
                               flops.peaks(run.device["kind"]))
    observed.setdefault("notes", {})[f"fh_{work}_roofline_bound"] = \
        share["bound"]
    return share["pct"]
