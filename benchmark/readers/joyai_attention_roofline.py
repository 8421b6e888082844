"""Roofline share of the flash kernels in a JoyAI-LLM-Flash train step: the
least time the chip could take for causal attention, forward and backward,
at the cell's shapes and the configuration's two head widths
(``benchmark/flops_joyai.py``), over the device time per step of the events
named ``names``. ``None`` where the trace holds no such event or no step."""

from benchmark import flops, flops_joyai
from benchmark.readers.trace_named_ms_per_step import named_ns


def read(run, observed, names, step_span):
    r = run.reduced
    if r is None:
        return None
    steps = r.count(step_span)
    n, ns = named_ns(r.first, r.w0, r.w1, names)
    g = run.config.get("gpt_config", {})
    if steps == 0 or n == 0 or "qk_nope_dim" not in g:
        return None
    need = flops_joyai.mla_attention_train(
        observed["batch"] // run.chips, observed["seq"], g["n_heads"],
        g["qk_nope_dim"] + g["qk_rope_dim"], g["v_head_dim"],
        g["n_layers"] + g["n_mtp"])
    share = flops.roofline_pct(need["flops"], need["bytes"],
                               ns / 1e9 / steps,
                               flops.peaks(run.device["kind"]))
    observed.setdefault("notes", {})["joy_flash_roofline_bound"] = \
        share["bound"]
    return share["pct"]
