"""Roofline share of one of the dots3 serving cell's kernels: the least time
the chip could take for the work the program itself counted in the TRACED
iterations (``work_counter`` between the driver's reading at the start of
the trace and the window's end, the late series flushed at both), as
``benchmark/flops_dots3.py``'s function ``work`` turns it into operations
and bytes, over the device time of the events named ``names`` in the traced
window. ``None`` where the program keeps no such counter, the driver marked
no trace start, or the trace holds no such event."""

from benchmark import flops, flops_dots3
from benchmark.readers.trace_named_ms_per_step import named_ns


def read(run, observed, names, work, work_counter, tokens_counter):
    r = run.reduced
    c = observed.get("counters")
    if r is None or not c or "trace_start" not in c \
            or work_counter not in c["end"]:
        return None
    done = c["end"][work_counter] - c["trace_start"][work_counter]
    tokens = c["end"][tokens_counter] - c["trace_start"][tokens_counter]
    n, ns = named_ns(r.first, r.w0, r.w1, names)
    g = run.config.get("gpt_config", {})
    if done <= 0 or n == 0 or "index_n_heads" not in g:
        return None
    queries = tokens * sum(k == "full" for k in g["layer_types"])
    need = getattr(flops_dots3, work)(done, queries, g)
    share = flops.roofline_pct(need["flops"], need["bytes"], ns / 1e9,
                               flops.peaks(run.device["kind"]))
    observed.setdefault("notes", {})[f"dots_{work}_roofline_bound"] = \
        share["bound"]
    return share["pct"]
