"""``benchmark/flops_falconh1.py`` on shapes small enough to count by hand and
at the published sizes, and the reader that feeds it
(``readers/kernel_roofline.py``) on a made-up trace: what it divides, and
that it returns nothing (and does not raise) where the program keeps no such
series — the parent of the PR that added it."""

import pytest

from benchmark import flops_falconh1 as ff
from benchmark.readers import kernel_roofline

G = {"n_heads": 4, "n_kv_heads": 2, "head_dim": 2, "n_layers": 3,
     "ssm_heads": 4, "ssm_groups": 2, "ssm_state": 3, "ssm_head_dim": 5}
PUB = dict(G, n_heads=20, n_kv_heads=4, head_dim=128, n_layers=4,
           ssm_heads=32, ssm_groups=2, ssm_state=256, ssm_head_dim=128)


def test_ssd_decode_counts_each_state_once_each_way_and_b_c_once_a_group():
    # 6 (row, layer) pairs x 4 heads of 3 x 5 f32: the state twice, x and y
    # of 5, dt, A and D; B and C of 3 for each of the 2 groups of a pair;
    # 5 FLOPs an element of the state
    need = ff.ssd_decode({"serve.ssd.decode_rows": 6}, G)
    assert need == {"flops": 24 * 5 * 15,
                    "bytes": (24 * (2 * 15 + 2 * 5 + 3) + 6 * 2 * 2 * 3) * 4}
    # at the published sizes, a row and layer: 32 heads x (2 x 131,072 B of
    # state + 1,024 B of x and y + 12 B of scalars) + 2 groups x 2,048 B of
    # B and C = 8,425,856 B, and 32 x 5 x 32,768 FLOPs
    need = ff.ssd_decode({"serve.ssd.decode_rows": 1}, PUB)
    assert need["bytes"] == 32 * (2 * 131072 + 1024 + 12) + 2 * 2048 \
        == 8425856
    assert need["flops"] == 32 * 5 * 256 * 128 == 5242880
    # bound by bytes on a v5e by far: 6.4e-7 FLOPs a byte of its balance
    assert need["flops"] / 197e12 < need["bytes"] / 819e9 / 100


def test_paged_attention_counts_keys_read_once_for_the_group():
    need = ff.paged_attention({"serve.kv.decode_keys_read.full": 16}, G)
    assert need == {"flops": 16 * (2 * 2 * 4 * 2),
                    "bytes": 16 * (2 * 2 * 2 * 2)}
    # at the published widths: 2,048 B and 10,240 FLOPs a key and layer
    need = ff.paged_attention({"serve.kv.decode_keys_read.full": 1}, PUB)
    assert need == {"flops": 2 * 2 * 20 * 128, "bytes": 2048}


class _Reduced:
    w0, w1 = 0.0, 1e9
    # (name, category, start ns, duration ns)
    first = [("ssd_decode.3", "custom-call", 10.0, 1e6),
             ("ssd_decode", "custom-call", 2e6, 1e6),
             ("fusion.1", "loop fusion", 5e6, 1e6)]


class _Run:
    reduced = _Reduced()
    device = {"kind": "TPU v5 lite"}
    config = {"flops": "flops_falconh1",
              "gpt_config": PUB}


def _observed(rows):
    return {"counters": {"trace_start": {"serve.ssd.decode_rows": 0},
                         "end": {"serve.ssd.decode_rows": rows}},
            "histograms": {"trace_start": {}, "end": {}}}


def test_reader_divides_the_roofline_time_by_the_named_events_time():
    # 128 (row, layer) pairs x 8,425,856 B = 1.0785 GB: 1.317 ms at 819
    # GB/s, over the 2 ms of the two events named ssd_decode
    observed = _observed(128)
    pct = kernel_roofline.read(_Run(), observed, ["ssd_decode"],
                               "ssd_decode")
    assert pct == pytest.approx(100 * 128 * 8425856 / 819e9 / 2e-3)
    assert observed["notes"]["ssd_decode_roofline_bound"] == "bytes"


def test_reader_returns_nothing_where_there_is_nothing_to_read():
    run, names = _Run(), ["ssd_decode"]
    read = kernel_roofline.read
    assert read(run, {}, names, "ssd_decode") is None
    assert read(run, {"counters": {"end": {}}, "histograms": {"end": {}}},
                names, "ssd_decode") is None
    # a program whose registry lacks the series: the driver reads zeros
    assert read(run, _observed(0), names, "ssd_decode") is None
    # no such event in the trace; another model's configuration; no trace
    assert read(run, _observed(5), ["moe_gmm_fwd"], "ssd_decode") is None
    other = _Run()
    other.config = {"gpt_config": {"d_model": 8}}
    assert read(other, _observed(5), names, "ssd_decode") is None
    other = _Run()
    other.reduced = None
    assert read(other, _observed(5), names, "ssd_decode") is None
    # the work function's own counter missing
    assert read(run, _observed(5), names, "paged_attention") is None
