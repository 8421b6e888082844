"""``benchmark/flops_mellum2.py`` on shapes small enough to count by hand, and
the reader that feeds it (``readers/kernel_roofline.py``) on a made-up
trace: what it divides, and that it returns nothing (and does not raise) where
the program keeps no such series — the parent of the PR that added it."""

from benchmark import flops_mellum2 as fm
from benchmark.readers import kernel_roofline

G = {"d_model": 8, "d_ff_expert": 4, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "n_layers": 3}


def test_paged_attention_counts_keys_read_once_for_the_group():
    # 10 + 6 keys: q.k and p.v of 2 wide for 4 query heads, 2 FLOPs each;
    # k and v rows of 2 heads x 2, bf16
    need = fm.paged_attention({"serve.kv.decode_keys_read.full": 10,
                               "serve.kv.decode_keys_read.window": 6}, G)
    assert need == {"flops": 16 * (2 * 2 * 4 * 2),
                    "bytes": 16 * (2 * 2 * 2 * 2)}
    # at the published widths: 2,048 B and 16,384 FLOPs a key
    pub = dict(G, n_heads=32, n_kv_heads=4, head_dim=128)
    need = fm.paged_attention({"serve.kv.decode_keys_read.full": 1,
                               "serve.kv.decode_keys_read.window": 0}, pub)
    assert need == {"flops": 2 * 2 * 32 * 128, "bytes": 2048}


def test_prefill_attention_counts_visible_pairs_and_q_o_once():
    # a chunk of 3 queries at position 2 on one full layer sees 3 + 4 + 5
    # keys, on each of two sliding layers with a window of 2 it sees 2 each
    done = {"serve.attn.prefill_pairs.full": 12,
            "serve.attn.prefill_pairs.window": 2 * 6,
            "serve.prefill_tokens": 3}
    need = fm.prefill_attention(done, G)
    assert need["flops"] == 24 * (2 * 2 * 4 * 2)
    # 3 queries x 3 layers: q read and o written, 4 heads x 2, bf16
    assert need["bytes"] == 9 * 2 * (4 * 2) * 2


def test_expert_products_count_pairs_and_each_hit_experts_weights_once():
    # two programs: 5 pairs over 2 + 3 + 1 = 6 (layer, expert) hits (a mean of
    # 2.0 a layer over 3 layers) and 4 pairs over 3 hits (a mean of 1.0)
    done = {"moe.pairs_here": 9, "moe.experts_hit": 3.0}
    need = fm.expert_products(done, G)
    assert need["flops"] == 9 * 3 * 2 * 8 * 4
    assert need["bytes"] == (9 * 3 * 8 * 4 + 9 * 2 * 8) * 2


class _Reduced:
    w0, w1 = 0.0, 1e9
    # (name, category, start ns, duration ns)
    first = [("paged_attn_decode.3", "custom-call", 10.0, 1e6),
             ("paged_attn_decode", "custom-call", 2e6, 1e6),
             ("fusion.1", "loop fusion", 5e6, 1e6)]


class _Run:
    reduced = _Reduced()
    device = {"kind": "TPU v5 lite"}
    config = {"flops": "flops_mellum2",
              "gpt_config": dict(G, n_heads=32, n_kv_heads=4, head_dim=128)}


def _observed(keys):
    zero = {"serve.kv.decode_keys_read.full": 0,
            "serve.kv.decode_keys_read.window": 0}
    return {"counters": {"trace_start": zero, "end": {
        "serve.kv.decode_keys_read.full": keys,
        "serve.kv.decode_keys_read.window": 0}},
        "histograms": {"trace_start": {}, "end": {}}}


def test_reader_divides_the_roofline_time_by_the_named_events_time():
    # 819,000 keys x 2,048 B = 1.677 GB: 2.048 ms at 819 GB/s, over 2 ms
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
    other.config = {"gpt_config": {"d_model": 8}}      # names no module
    assert read(other, _observed(5), names, "paged_attention") is None
    # a configuration whose module has no such function (Falcon-H1 has no
    # experts): nothing to read, where the parent's reader was another file
    other.config = dict(_Run.config, flops="flops_falconh1")
    assert read(other, _observed(5), names, "expert_products") is None
    assert read(other, _observed(5), names, "paged_attention") is not None
    other = _Run()
    other.reduced = None
    assert read(other, _observed(5), names, "paged_attention") is None
    # the work function's own counters missing
    assert read(run, _observed(5), names, "expert_products") is None
