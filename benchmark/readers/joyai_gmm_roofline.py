"""Roofline share of the grouped-product kernels in a JoyAI-LLM-Flash train
step: the least time the chip could take for the routed experts' products
of the pairs the program counted in the TRACED steps (``pairs_histogram``,
the mean a step between the driver's snapshot at the start of the trace
and the window's end: the steps whose kernels the trace timed — a step's
pairs swing from 0 to three times the window's mean, so the window's mean
would be another quantity; ``benchmark/flops_joyai.py``), over the device
time per step of the events named ``names``. ``None`` where the program
keeps no such counter, or the trace holds no such event or no step."""

from benchmark import flops, flops_joyai, metrics
from benchmark.readers.trace_named_ms_per_step import named_ns


def read(run, observed, names, step_span, pairs_histogram):
    r = run.reduced
    h = observed.get("histograms")
    if r is None or not h or pairs_histogram not in h["start"]:
        return None
    pairs = metrics.histogram_window_mean(
        h["end"][pairs_histogram],
        h.get("trace_start", h["start"])[pairs_histogram])
    # the steps the pairs were counted over, where the driver marked the
    # start of the trace: pairs and kernel time of the same steps
    steps = (h["end"][pairs_histogram]["count"]
             - h["trace_start"][pairs_histogram]["count"]
             if "trace_start" in h else r.count(step_span))
    n, ns = named_ns(r.first, r.w0, r.w1, names)
    g = run.config.get("gpt_config", {})
    if not pairs or steps == 0 or n == 0 or "experts_held" not in g:
        return None
    need = flops_joyai.moe_grouped_products_train(
        pairs, g["d_model"], g["d_ff_expert"], g["experts_held"],
        g["n_layers"] - g["first_k_dense"] + g["n_mtp"])
    share = flops.roofline_pct(need["flops"], need["bytes"],
                               ns / 1e9 / steps,
                               flops.peaks(run.device["kind"]))
    observed.setdefault("notes", {})["joy_moe_gmm_roofline_bound"] = \
        share["bound"]
    return share["pct"]
