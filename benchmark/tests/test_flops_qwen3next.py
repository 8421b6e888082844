"""``benchmark/flops_qwen3next.py`` on shapes small enough to count by hand,
and the reader that feeds it (``readers/kernel_roofline.py``) on a made-up
trace: what it divides, and that it returns nothing (and does not raise) where
the program keeps no such series — the parent of the PR that added it."""

import pytest

from benchmark import flops_qwen3next as fq
from benchmark.readers import kernel_roofline

G = {"d_model": 8, "d_ff_expert": 4, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "n_layers": 3, "linear_value_heads": 2,
     "linear_key_dim": 3, "linear_value_dim": 5}
PUB = dict(G, n_heads=16, n_kv_heads=2, head_dim=256, linear_value_heads=32,
           linear_key_dim=128, linear_value_dim=128, d_model=2048,
           d_ff_expert=512, n_layers=8)


def test_gdn_decode_counts_each_state_read_once_and_written_once():
    # 6 (row, layer) pairs x 2 heads of 3 x 5 f32: the state twice, q and k
    # of 3, v and o of 5; 7 FLOPs an element of the state
    need = fq.gdn_decode({"serve.gdn.decode_rows": 6}, G)
    assert need == {"flops": 12 * 7 * 15,
                    "bytes": 12 * (2 * 15 + 2 * 3 + 2 * 5) * 4}
    # at the published sizes: 2 x 65,536 B of state + 2,048 B of vectors a
    # row, head and layer
    need = fq.gdn_decode({"serve.gdn.decode_rows": 1}, PUB)
    assert need["bytes"] == 32 * (2 * 65536 + 2048)
    assert need["flops"] == 32 * 7 * 128 * 128


def test_paged_attention_counts_keys_read_once_for_the_group():
    need = fq.paged_attention({"serve.kv.decode_keys_read.full": 16}, G)
    assert need == {"flops": 16 * (2 * 2 * 4 * 2),
                    "bytes": 16 * (2 * 2 * 2 * 2)}
    # at the published widths: 2,048 B and 16,384 FLOPs a key
    need = fq.paged_attention({"serve.kv.decode_keys_read.full": 1}, PUB)
    assert need == {"flops": 2 * 2 * 16 * 256, "bytes": 2048}


def test_expert_products_count_pairs_and_each_hit_experts_weights_once():
    done = {"moe.pairs_here": 9, "moe.experts_hit": 3.0}
    need = fq.expert_products(done, G)
    assert need["flops"] == 9 * 3 * 2 * 8 * 4
    assert need["bytes"] == (9 * 3 * 8 * 4 + 9 * 2 * 8) * 2


class _Reduced:
    w0, w1 = 0.0, 1e9
    # (name, category, start ns, duration ns)
    first = [("gdn_decode.3", "custom-call", 10.0, 1e6),
             ("gdn_decode", "custom-call", 2e6, 1e6),
             ("fusion.1", "loop fusion", 5e6, 1e6)]


class _Run:
    reduced = _Reduced()
    device = {"kind": "TPU v5 lite"}
    config = {"flops": "flops_qwen3next",
              "gpt_config": PUB}


def _observed(rows):
    return {"counters": {"trace_start": {"serve.gdn.decode_rows": 0},
                         "end": {"serve.gdn.decode_rows": rows}},
            "histograms": {"trace_start": {}, "end": {}}}


def test_reader_divides_the_roofline_time_by_the_named_events_time():
    # 192 (row, layer) pairs x 32 heads x 133,120 B = 817.9 MB: 0.9986 ms at
    # 819 GB/s, over the 2 ms of the two events named gdn_decode
    observed = _observed(192)
    pct = kernel_roofline.read(_Run(), observed, ["gdn_decode"],
                               "gdn_decode")
    assert pct == pytest.approx(100 * 192 * 32 * 133120 / 819e9 / 2e-3)
    assert observed["notes"]["gdn_decode_roofline_bound"] == "bytes"


def test_reader_returns_nothing_where_there_is_nothing_to_read():
    run, names = _Run(), ["gdn_decode"]
    read = kernel_roofline.read
    assert read(run, {}, names, "gdn_decode") is None
    assert read(run, {"counters": {"end": {}}, "histograms": {"end": {}}},
                names, "gdn_decode") is None
    # a program whose registry lacks the series: the driver reads zeros
    assert read(run, _observed(0), names, "gdn_decode") is None
    # no such event in the trace; another model's configuration; no trace
    assert read(run, _observed(5), ["moe_gmm_fwd"], "gdn_decode") is None
    other = _Run()
    other.config = {"gpt_config": {"d_model": 8}}
    assert read(other, _observed(5), names, "gdn_decode") is None
    other = _Run()
    other.reduced = None
    assert read(other, _observed(5), names, "gdn_decode") is None
    # the work function's own counters missing
    assert read(run, _observed(5), names, "expert_products") is None
