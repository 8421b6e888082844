"""Roofline share of the attention kernels of a train step: the least time
the chip could take for causal attention forward and backward at the cell's
shapes (``benchmark/flops.py``; FLOPs and bytes as the algorithm needs them,
no recomputation) over the device time of the kernels per step."""

from benchmark import flops


def read(run, observed, step_span, categories):
    r = run.reduced
    if r is None:
        return None
    steps = r.count(step_span)
    seconds = r.seconds(categories)
    if steps == 0 or seconds <= 0:
        return None
    g = run.config["gpt_config"]
    # one chip's rows: the trace is read on the first device
    need = flops.causal_attention_train(
        observed["batch"] // run.chips, observed["seq"], g["d_model"],
        g["n_layers"])
    share = flops.roofline_pct(need["flops"], need["bytes"],
                               seconds / steps,
                               flops.peaks(run.device["kind"]))
    observed.setdefault("notes", {})["attention_roofline_bound"] = \
        share["bound"]
    return share["pct"]
