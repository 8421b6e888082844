"""The trace reduction on one small recorded trace (recorded_trace.json:
40 ms of gpt2m-train on the v5e, around a step boundary) and on a hand-made
one whose answers can be worked out on paper."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


HAND = {
    "devices": {"0": [
        ["fusion.1", "convolution fusion", 100.0, 50.0],     # 100..150
        ["while.1", tr.CONTROL_FLOW, 140.0, 60.0],           # 140..200 nests
        ["copy.1", "data formatting", 150.0, 30.0],          # 150..180
        ["custom-call.1", "custom-call", 300.0, 100.0],      # 300..400
        ["copy.1", "data formatting", 390.0, 60.0],          # 390..450 clipped
    ]},
    "spans": [["bench.window", 0.0, 420.0],
              ["train.wait_input", 0.0, 90.0],
              ["train.step", 95.0, 200.0],                   # 95..295
              ["train.step", 400.0, 100.0]],                 # not inside
}


def test_hand_made_trace():
    r = tr.Reduced(HAND, chips=1)
    assert (r.w0, r.w1) == (0.0, 420.0)
    # busy: 100..200 and 300..420
    assert r.busy_s == pytest.approx(220e-9)
    assert r.seconds(["data formatting"]) == pytest.approx((30 + 30) * 1e-9)
    assert r.seconds(["convolution fusion", "custom-call"]) == \
        pytest.approx(150e-9)
    assert r.count("train.step") == 1
    b = r.breakdown()
    assert b["device_ops"][0] == ["custom-call.1", pytest.approx(100e-9)]
    assert "while.1" not in [n for n, _ in b["device_ops"]]
    # idle: 0..100 (90 wait_input, 5 nothing, 5 step) and 200..300 (95 step,
    # 5 nothing)
    assert dict(b["idle_gaps"]) == {
        "train.step": pytest.approx(100e-9),
        "train.wait_input": pytest.approx(90e-9),
        tr.NO_SPAN: pytest.approx(10e-9)}


def test_recorded_trace(recorded):
    r = tr.Reduced(recorded, chips=1)
    assert r.window_s == pytest.approx(0.040)
    cats = tr.category_ns(r.first, r.w0, r.w1)
    leaves = sum(v for k, v in cats.items() if k != tr.CONTROL_FLOW)
    # one core: leaf operations do not overlap, so they add up to the busy
    # time, except the little a control-flow container does by itself
    assert leaves <= r.busy_s * 1e9 * 1.0001
    assert leaves >= r.busy_s * 1e9 * 0.98
    idle = sum(s for _, s in r.breakdown()["idle_gaps"])
    assert idle + r.busy_s == pytest.approx(r.window_s, rel=1e-6)
    # the stretch holds the optimizer's end of one step and the forward
    # pass of the next: MXU work, the flash kernels, copies
    assert cats["convolution fusion"] > cats["custom-call"] > 0
    # ... and the gap between the two steps, which the host spends in
    # train.step (the return from one call and the dispatch of the next)
    assert r.busy_s == pytest.approx(0.035787264)
    gaps = dict(r.breakdown()["idle_gaps"])
    assert gaps["train.step"] > 10 * gaps.get("train.wait_input", 0.0)
    assert r.count("train.wait_input") == 1


def test_categorise_reads_the_instruction(recorded):
    for cat, name in recorded["raw_names"].items():
        short, got = tr.categorise(name)
        assert got == cat and " " not in short and not short.startswith("%")
    assert tr.categorise("fusion.7", [("hlo_category", "loop fusion")]) == \
        ("fusion.7", "loop fusion")
    assert tr.categorise(
        "%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8]{0} %x), "
        "replica_groups={}")[1] == "all-reduce"
    assert tr.categorise(
        "%f.1 = (f32[2]{0}, f32[2]{0}) fusion(f32[2]{0:T(8,128)} %p), "
        "kind=kOutput, calls=%c") == ("f.1", "convolution fusion")
