"""``benchmark/flops_sdar.py`` on shapes small enough to count by hand, the
reader that feeds it (``readers/kernel_roofline.py``) on a made-up trace —
what it divides, and that it returns nothing (and does not raise) where the
program keeps no such series: the parent of the PR that added it — and the
metrics the rule gives the cell."""

import glob
import json
import os

from benchmark import flops_sdar as fs
from benchmark import harness
from benchmark.readers import kernel_roofline

G = {"d_model": 8, "d_ff_expert": 4, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "n_layers": 3, "block_length": 4}
PUB = dict(G, d_model=2048, d_ff_expert=768, n_heads=32, n_kv_heads=4,
           head_dim=128, n_layers=6)


def test_paged_attention_counts_a_key_once_for_the_block_of_queries():
    # 16 keys: q.k and p.v of 2 wide for 4 query heads of 4 queries, 2 FLOPs
    # each; k and v rows of 2 heads x 2, bf16, read once for all 4 queries
    need = fs.paged_attention({"serve.kv.decode_keys_read.full": 16}, G)
    assert need == {"flops": 16 * (2 * 2 * 4 * 2) * 4,
                    "bytes": 16 * (2 * 2 * 2 * 2)}
    # at the published widths: 2,048 B and 16,384 x 4 FLOPs a key
    need = fs.paged_attention({"serve.kv.decode_keys_read.full": 1}, PUB)
    assert need == {"flops": 16384 * 4, "bytes": 2048}


def test_prefill_attention_counts_visible_pairs_and_q_o_once():
    # a chunk of 8 queries at position 4, blocks of 4: the first four see 8
    # keys each, the next four 12, on each of 3 layers
    done = {"serve.attn.prefill_pairs.full": 3 * (4 * 8 + 4 * 12),
            "serve.prefill_tokens": 8}
    need = fs.prefill_attention(done, G)
    assert need["flops"] == 240 * (2 * 2 * 4 * 2)
    # 8 queries x 3 layers: q read and o written, 4 heads x 2, bf16
    assert need["bytes"] == 24 * 2 * (4 * 2) * 2
    assert fs.prefill_attention(
        {"serve.attn.prefill_pairs.full": 1, "serve.prefill_tokens": 0},
        PUB)["flops"] == 16384


def test_expert_products_count_pairs_and_each_hit_experts_weights_once():
    # two programs: 5 pairs over 2 + 3 + 1 = 6 (layer, expert) hits (a mean of
    # 2.0 a layer over 3 layers) and 4 pairs over 3 hits (a mean of 1.0)
    done = {"moe.pairs_here": 9, "moe.experts_hit": 3.0}
    need = fs.expert_products(done, G)
    assert need["flops"] == 9 * 3 * 2 * 8 * 4
    assert need["bytes"] == (9 * 3 * 8 * 4 + 9 * 2 * 8) * 2
    assert fs.expert_products({"moe.pairs_here": 1, "moe.experts_hit": 0.0},
                              PUB)["flops"] == 9437184


class _Reduced:
    w0, w1 = 0.0, 1e9
    # (name, category, start ns, duration ns)
    first = [("paged_attn_decode.3", "custom-call", 10.0, 1e6),
             ("paged_attn_decode", "custom-call", 2e6, 1e6),
             ("fusion.1", "loop fusion", 5e6, 1e6)]


class _Run:
    reduced = _Reduced()
    device = {"kind": "TPU v5 lite"}
    config = {"flops": "flops_sdar",
              "gpt_config": PUB}


def _observed(keys):
    return {"counters": {"trace_start": {"serve.kv.decode_keys_read.full": 0},
                         "end": {"serve.kv.decode_keys_read.full": keys}},
            "histograms": {"trace_start": {}, "end": {}}}


def test_reader_divides_the_roofline_time_by_the_named_events_time():
    # 819,000 keys x 2,048 B = 1.677 GB: 2.048 ms at 819 GB/s, over 2 ms
    # (their 53.7 GFLOP take 0.27 ms at 197 TFLOP/s: bytes bind)
    observed = _observed(819000)
    pct = kernel_roofline.read(_Run(), observed, ["paged_attn_decode"],
                               "paged_attention")
    assert abs(pct - 102.4) < 1e-6
    assert observed["notes"]["paged_attention_roofline_bound"] == "bytes"


def test_reader_returns_nothing_where_there_is_nothing_to_read():
    run, names = _Run(), ["paged_attn_decode"]
    read = kernel_roofline.read
    # no counters at all (a program without them); no trace start marked
    assert read(run, {}, names, "paged_attention") is None
    assert read(run, {"counters": {"end": {}}, "histograms": {"end": {}}},
                names, "paged_attention") is None
    # a program whose registry lacks the series: the driver reads zeros
    assert read(run, _observed(0), names, "paged_attention") is None
    # no such event in the trace; another model's configuration; no trace
    assert read(run, _observed(5), ["moe_gmm_fwd"], "paged_attention") is None
    other = _Run()
    other.config = {"gpt_config": {"d_model": 8, "d_ff_expert": 4}}
    assert read(other, _observed(5), names, "paged_attention") is None
    other = _Run()
    other.reduced = None
    assert read(other, _observed(5), names, "paged_attention") is None
    # the work function's own counters missing
    assert read(run, _observed(5), names, "expert_products") is None


def test_every_metric_the_cell_reports_is_listed_for_it():
    """The cell's mix names the sets its program emits; the rule gives it 19
    metrics, four of them in a set of its own (``block_diffusion``, which no
    other cell names), each moves the one end-to-end metric the cell
    reports, and BENCHMARK.json lists each for this cell."""
    t = harness.load_json(harness.HERE, "traffic",
                          "chat-blockgen-backlog-sat.json")
    assert t["driver"] == "serve_sdar"
    assert json.dumps(t).count("PLACEHOLDER") == 0
    files = [m for m in harness.layer_metrics_for(
        t["metric_sets"], t["reports"], 1) if m["set"] != "any"]
    assert len(files) == 19
    own = [m["name"] for m in files if m["set"] == "block_diffusion"]
    assert own == sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(harness.HERE, "layer_metrics", "sd_*.json")))
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    listed = {m["name"]: m for m in bm["per_layer"]}
    for m in files:
        assert m["moves"] == "serve_tokens_per_s", m["name"]
        cells = listed[m["name"]]["workloads"]
        assert "sdar-serve-blockgen-sat" in cells
        assert (cells == ["sdar-serve-blockgen-sat"]) == (m["name"] in own)
