"""The rule that picks a cell's per-layer metrics, that BENCHMARK.json says
the same, that no two metric files say one thing, and that a new cell joins
the shared entries from files of its own."""

import copy
import glob
import os

from benchmark import harness

M = [
    {"name": "a", "set": "any", "moves": "setup_s"},
    {"name": "b", "set": "train_steps", "moves": "train_tokens_per_s"},
    {"name": "c", "set": "collective", "moves": "train_tokens_per_s",
     "min_chips": 2},
    {"name": "d", "set": "chat", "moves": "ttft_mean_ms"},
    {"name": "e", "set": "serve_steps", "moves": "serve_tokens_per_s"},
    {"name": "f", "set": "paged_attn", "moves": "serve_tokens_per_s"},
]


def _names(sets, reports, chips):
    return [m["name"] for m in
            harness.layer_metrics_for(sets, reports, chips, M)]


def test_rule():
    train = ["train_steps", "collective"]
    assert _names(train, ["train_tokens_per_s", "setup_s"], 1) == ["a", "b"]
    assert _names(train, ["train_tokens_per_s", "setup_s"], 4) == \
        ["a", "b", "c"]
    assert _names(["train_steps"], ["train_tokens_per_s", "setup_s"], 4) == \
        ["a", "b"]
    assert _names(["chat"], ["ttft_mean_ms", "itl_p95_ms", "setup_s"], 1) == \
        ["a", "d"]
    # a set is named by the cell; what it does not name it does not report
    assert _names(["serve_steps"], ["serve_tokens_per_s", "setup_s"], 1) == \
        ["a", "e"]
    assert _names(["serve_steps", "paged_attn"], ["serve_tokens_per_s"],
                  1) == ["e", "f"]
    # nor a metric whose end-to-end metric its mix does not report
    assert _names(["serve_steps", "chat"], ["serve_tokens_per_s"], 1) == ["e"]
    assert _names([], ["serve_tokens_per_s", "setup_s"], 1) == ["a"]


def _metric_files():
    return [harness.load_json(p) for p in sorted(glob.glob(
        os.path.join(harness.HERE, "layer_metrics", "*.json")))]


def _traffic(name):
    return harness.load_json(harness.HERE, "traffic", name + ".json")


def listing(bm, metrics, traffic=_traffic):
    """``per_layer`` as the rule gives it for ``bm``'s cells: an entry a
    metric that some cell reports, with those cells in ``workloads``'s
    order."""
    picked = {}
    for w in bm["workloads"]:
        t = traffic(w["traffic"])
        for m in harness.layer_metrics_for(t["metric_sets"], t["reports"],
                                           w["chips"], metrics):
            picked.setdefault(m["name"], (m, []))[1].append(w["name"])
    return {name: {**{k: m[k] for k in ("name", "unit", "better", "source",
                                        "layer", "moves")},
                   "workloads": cells}
            for name, (m, cells) in picked.items()}


def test_benchmark_json_lists_what_the_rule_picks():
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    assert bm["paths"] == ["benchmark"]
    cells = [w["name"] for w in bm["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bm["end_to_end"]}
    metrics = _metric_files()
    sets = {m["set"] for m in metrics}
    for w in bm["workloads"]:
        t = _traffic(w["traffic"])
        assert os.path.exists(os.path.join(
            harness.HERE, "drivers", t["driver"] + ".py"))
        # the cell reports exactly the end-to-end metrics its mix names
        assert sorted(t["reports"]) == sorted(
            n for n, ws in e2e.items() if w["name"] in ws)
        # and names only sets that some metric file is in
        assert set(t["metric_sets"]) <= sets, w["name"]
    for m in metrics:
        assert os.path.exists(os.path.join(
            harness.HERE, "readers", m["reader"] + ".py"))
    assert {m["name"]: m for m in bm["per_layer"]} == listing(bm, metrics)
    assert len(bm["per_layer"]) == len({m["name"] for m in bm["per_layer"]})
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(
        1, len(cells) // 4)


def test_no_two_metric_files_say_one_thing():
    """A meaning has ONE entry, shared by the cells that report it: two
    files equal in everything but ``name`` and ``set`` are one metric under
    two names (the parent's layout held 50 such copies)."""
    seen = {}
    for m in _metric_files():
        key = repr(sorted((k, repr(v)) for k, v in m.items()
                          if k not in ("name", "set")))
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]


def test_a_new_cell_joins_the_shared_entries_from_files_of_its_own():
    """What a ``model_config`` PR brings: a configuration, a traffic file
    that names two shared sets and one of its own, one metric file in that
    set. The rule then picks the shared entries for the new cell, and the
    BENCHMARK.json that lists it differs from the committed one by appended
    entries and appended names alone: no file the benchmark has is edited."""
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    mine = {"name": "newfam_scan_roofline", "unit": "%", "layer": "kernels",
            "set": "newfam", "moves": "serve_tokens_per_s",
            "better": "higher", "source": "device_trace",
            "reader": "kernel_roofline",
            "params": {"names": ["newfam_scan"], "work": "scan"}}
    mix = {"name": "newfam-backlog-sat", "driver": "serve_newfam",
           "metric_sets": ["serve_steps", "paged_attn", "newfam"],
           "reports": ["serve_tokens_per_s", "setup_s"]}
    new = copy.deepcopy(bm)
    new["configs"].append({"name": "newfam-l4"})
    new["workloads"].append({"name": "newfam-serve-sat", "chips": 1,
                             "config": "newfam-l4",
                             "traffic": "newfam-backlog-sat"})
    for m in new["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("newfam-serve-sat")
    metrics = _metric_files()
    want = listing(new, metrics + [mine],
                   lambda n: mix if n == mix["name"] else _traffic(n))
    joined = sorted(n for n, m in want.items()
                    if "newfam-serve-sat" in m["workloads"])
    assert joined == sorted(
        ["compiles_in_window", "newfam_scan_roofline", "paged_attn_roofline"]
        + [m["name"] for m in metrics if m["set"] == "serve_steps"])
    assert {"serve_decode_step_ms", "serve_requests_per_s"} <= set(joined)
    # the committed list, entry by entry: the same, or one name appended
    for old in bm["per_layer"]:
        got = want.pop(old["name"])
        if old["name"] == "compiles_in_window" or old["name"] in joined:
            assert got["workloads"] == \
                old["workloads"] + ["newfam-serve-sat"]
            got = dict(got, workloads=old["workloads"])
        assert got == old
    assert list(want) == ["newfam_scan_roofline"]       # appended


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
        t = _traffic(w["traffic"])
        ms = harness.layer_metrics_for(t["metric_sets"], t["reports"],
                                       w["chips"])
        if any(m["reader"] == "trace_ms_in_device_steps" for m in ms):
            cut.add(t["driver"])
            assert [m["name"] for m in ms if m.get("params", {}).get(
                "step_span") == "serve.step"] == []
    assert {"serve_dots3", "serve_mellum2", "serve_qwen3next"} <= cut
