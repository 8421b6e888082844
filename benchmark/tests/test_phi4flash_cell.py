"""The Phi-4-mini-flash cell's own files: ``flops_phi4flash.py`` on shapes
small enough to count by hand and at the published ones, the multiset as the
issue asked for it, the configuration's count, that the reader finds nothing
to read (and does not raise) where the program keeps no such series — the
parent of the PR that added the cell — and the driver's comparison on pools
made by hand."""

import numpy as np

from benchmark import flops_phi4flash as fp
from benchmark import harness, traffic_gen
from benchmark.drivers import serve_phi4flash as driver
from benchmark.readers import kernel_roofline

G = {"n_layers": 8, "n_heads": 8, "n_kv_heads": 4, "d_head": 8,
     "ssm_state": 8, "d_inner": 64}
PUB = harness.load_json(harness.ROOT, "benchmark", "configs",
                        "phi-4-mini-flash-reasoning.json")


def test_scan_decode_counts_the_state_once_each_way():
    need = fp.selective_scan_decode({"serve.sscan.decode_rows": 3}, G)
    assert need == {"flops": 3 * 7 * 8 * 64,
                    "bytes": 3 * (2 * 8 * 64 + 3 * 64 + 2 * 8) * 4}
    # at the published sizes: 717 KB a row and layer, memory-bound by far
    one = fp.selective_scan_decode({"serve.sscan.decode_rows": 1},
                                   PUB["gpt_config"])
    assert one["bytes"] == 2 * 327680 + 3 * 20480 + 128
    assert one["flops"] / one["bytes"] < 1.0


def test_paged_attention_counts_the_shared_pages_once_a_reading_layer():
    # the program's counter already holds a row's length times the layers
    # that read the full layer's pages: the function adds the two kinds
    done = {"serve.kv.decode_keys_read.full": 100,
            "serve.kv.decode_keys_read.window": 20}
    need = fp.paged_attention(done, G)
    # a head meets ONE 8-wide component of its pair and 16 values
    assert need == {"flops": 120 * 8 * (2 * 8 + 2 * 16),
                    "bytes": 120 * 2 * 4 * 8 * 2}
    one = fp.paged_attention({"serve.kv.decode_keys_read.full": 1,
                              "serve.kv.decode_keys_read.window": 0},
                             PUB["gpt_config"])
    assert one == {"flops": 40 * 384, "bytes": 5120}


def test_prefill_attention_counts_window_queries_alone_in_its_bytes():
    done = {"serve.attn.prefill_pairs.full": 10,
            "serve.attn.prefill_pairs.window": 90,
            "serve.prefill_tokens": 7}
    need = fp.prefill_attention(done, G)
    assert need == {"flops": 100 * 8 * 48, "bytes": 7 * 2 * 3 * 8 * 8 * 2}
    pub = fp.prefill_attention(dict(done, **{"serve.prefill_tokens": 1}),
                               PUB["gpt_config"])
    assert pub["bytes"] == 8 * 3 * 40 * 64 * 2        # eight window layers


class _Run:
    reduced = type("R", (), {"first": [], "w0": 0.0, "w1": 1e9})()
    device = {"kind": "TPU v5 lite"}
    config = {"flops": "flops_phi4flash", "gpt_config": G}


def test_the_reader_returns_nothing_where_the_program_counts_nothing():
    assert kernel_roofline.read(_Run(), {}, ["sscan_decode"],
                                "selective_scan_decode") is None
    counters = {"start": {}, "end": {"x": 1}, "trace_start": {"x": 0}}
    hists = {"start": {}, "end": {"y": {"sum": 1.0}},
             "trace_start": {"y": {"sum": 0.0}}}
    assert kernel_roofline.read(
        _Run(), {"counters": counters, "histograms": hists},
        ["sscan_decode"], "selective_scan_decode") is None


def test_the_multiset_is_what_the_issue_asked_for():
    mix = harness.load_json(harness.HERE, "traffic",
                            "reason-longgen-backlog-sat.json")
    spec = harness.merged(harness.load_json(
        harness.HERE, "traffic", mix["multiset"] + ".json"), mix)
    cycle = traffic_gen.chat_cycle(spec)
    prompts, outputs = zip(*cycle)
    assert len(cycle) == 32
    assert min(prompts) == 256 and max(prompts) == 4096
    assert 1100 <= np.mean(prompts) <= 1300
    assert sum(p > 2048 for p in prompts) >= 6
    assert min(outputs) == 512 and max(outputs) == 3072
    assert 1300 <= np.mean(outputs) <= 1500
    sv = PUB["assumed"]["serve"]
    assert all(p + o > 512 for p, o in cycle)          # window blocks return
    assert max(p + o for p, o in cycle) <= PUB["gpt_config"]["max_seq"]
    assert (sv["max_batch"], sv["prefill_chunk"]) == (96, 2048)
    assert mix["arrivals"]["queued_min"] == 2 * sv["max_batch"]
    assert mix["drain"] is False
    # every program the window can need is served alone before it
    warm = driver.warmup_shapes(spec, sv["block_size"], sv["prefill_chunk"])
    assert {p for p, _ in warm} >= set(prompts)


def test_the_configuration_is_the_published_one():
    assert PUB["reduced"] == [] and PUB["parameters"] == 3852562944
    for key, value in PUB["source_config"].items():
        assert PUB[key] == value, key
    g = PUB["gpt_config"]
    assert (g["d_model"], g["n_layers"], g["n_heads"], g["n_kv_heads"],
            g["d_ff"], g["vocab_size"], g["window"]) == \
        (2560, 32, 40, 20, 10240, 200064, 512)
    assert g["d_inner"] == 2 * g["d_model"] and g["dt_rank"] == 160


def test_pool_errors_reads_each_pool_against_its_layers():
    """The driver's comparison on a pool made by hand from the reference's
    own values: every error 0, and a slot's tail one token stale is seen."""
    cfg = driver.Phi4FlashConfig.tiny()
    kinds = driver.layer_kinds(cfg)
    rng = np.random.default_rng(0)
    n, lo = 20, 20 - (cfg.window - 1)
    layers, S, tails, wk, wv = [], [], [], [], []
    for kind in kinds:
        if kind == "mamba":
            layers.append({"S": rng.normal(size=(cfg.d_inner, cfg.ssm_state)),
                           "tail": rng.normal(size=(3, cfg.d_inner))})
            S.append(layers[-1]["S"])
            tails.append(layers[-1]["tail"])
        elif kind in ("window", "full"):
            layers.append({"k": rng.normal(size=(24, 32)),
                           "v": rng.normal(size=(24, 32))})
            if kind == "window":
                wk.append(layers[-1]["k"][lo:n])
                wv.append(layers[-1]["v"][lo:n])
            else:
                full = layers[-1]
        else:
            layers.append({})
    taken = {"cached": n, "live_from": lo, "S": np.stack(S),
             "tail": np.stack(tails), "wk": np.stack(wk), "wv": np.stack(wv),
             "k": full["k"][:n], "v": full["v"][:n]}
    errs = driver.pool_errors(cfg, taken, layers)
    assert max(errs[k] for k in ("state_err", "deep_state_err", "tail_err",
                                 "window_row_err", "full_row_err")) == 0.0
    assert len(errs["state_errs_by_layer"]) == 3
    assert len(errs["window_row_errs_by_layer"]) == 2
    assert driver.pool_errors(cfg, taken, layers, 1)["tail_err"] > 0.5
    taken["wk"][1, 0] += 1.0
    assert driver.pool_errors(cfg, taken, layers)["window_row_err"] > 0.01
