"""The rule that picks a cell's per-layer metrics, and that BENCHMARK.json
says the same."""

import os

from benchmark import harness

M = [
    {"name": "a", "driver": "any", "moves": "setup_s"},
    {"name": "b", "driver": "train", "moves": "train_tokens_per_s"},
    {"name": "c", "driver": "train", "moves": "train_tokens_per_s",
     "min_chips": 2},
    {"name": "d", "driver": "serve", "moves": "ttft_mean_ms"},
    {"name": "e", "driver": "serve", "moves": "serve_tokens_per_s"},
]


def _names(driver, reports, chips):
    return [m["name"] for m in
            harness.layer_metrics_for(driver, reports, chips, M)]


def test_rule():
    assert _names("train", ["train_tokens_per_s", "setup_s"], 1) == ["a", "b"]
    assert _names("train", ["train_tokens_per_s", "setup_s"], 4) == \
        ["a", "b", "c"]
    assert _names("serve", ["ttft_mean_ms", "itl_p95_ms", "setup_s"], 1) == \
        ["a", "d"]
    assert _names("serve", ["serve_tokens_per_s", "setup_s"], 1) == ["a", "e"]
    assert _names("serve", ["serve_tokens_per_s"], 1) == ["e"]


def test_benchmark_json_lists_what_the_rule_picks():
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    assert bm["paths"] == ["benchmark"]
    cells = [w["name"] for w in bm["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bm["end_to_end"]}
    listed = {m["name"]: m for m in bm["per_layer"]}
    picked = {}
    for w in bm["workloads"]:
        t = harness.load_json(harness.HERE, "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            harness.HERE, "drivers", t["driver"] + ".py"))
        # the cell reports exactly the end-to-end metrics its mix names
        assert sorted(t["reports"]) == sorted(
            n for n, ws in e2e.items() if w["name"] in ws)
        for m in harness.layer_metrics_for(t["driver"], t["reports"],
                                           w["chips"]):
            assert os.path.exists(os.path.join(
                harness.HERE, "readers", m["reader"] + ".py"))
            picked.setdefault(m["name"], []).append(w["name"])
            for k in ("unit", "layer", "moves"):
                assert listed[m["name"]][k] == m[k]
    assert sorted(picked) == sorted(listed)
    for name, ws in picked.items():
        assert listed[name].get("workloads", cells) == ws
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(
        1, len(cells) // 4)


def test_the_list_fits_and_device_time_is_cut_along_steps_of_one_kind():
    """The contract's cap, and the rule for a serve cell whose iterations
    are of more than one kind: a device time is cut along the device steps
    of ONE kind (``trace_ms_in_device_steps``), never divided by the
    ``serve.step`` spans of the traced slice, whose mix of kinds it would
    follow. A cell is under the rule once one of its metrics is cut so
    (the rooflines carry no step count)."""
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    assert len(bm["per_layer"]) <= 128
    cut = set()
    for w in bm["workloads"]:
        t = harness.load_json(harness.HERE, "traffic", w["traffic"] + ".json")
        ms = harness.layer_metrics_for(t["driver"], t["reports"], w["chips"])
        if any(m["reader"] == "trace_ms_in_device_steps" for m in ms):
            cut.add(t["driver"])
            assert [m["name"] for m in ms if m.get("params", {}).get(
                "step_span") == "serve.step"] == []
    assert {"serve_dots3", "serve_mellum2", "serve_qwen3next"} <= cut
