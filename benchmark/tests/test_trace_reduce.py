"""The trace reduction on one small recorded trace (recorded_trace.json:
40 ms of gpt2m-train on the v5e, around a step boundary) and on a hand-made
one whose answers can be worked out on paper."""

import json
import os
import types

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


# ---- a device that ran nothing while the profiler was on -------------------
IDLE_SPANS = [["bench.window", 1000.0, 4000.0],
              ["serve.step", 1100.0, 900.0],                 # 1100..2000
              ["idle.wait_arrival", 2000.0, 2500.0],         # 2000..4500
              ["serve.step", 4500.0, 1000.0]]                # past the end
IDLE = {
    "no device plane": {"devices": {}, "spans": IDLE_SPANS},
    "a plane with no event": {"devices": {"0": []}, "spans": IDLE_SPANS},
    "every event outside the window": {"devices": {"0": [
        ["copy.1", "data formatting", 100.0, 800.0],         # before
        ["fusion.1", "convolution fusion", 5000.0, 50.0],    # at its end
    ]}, "spans": IDLE_SPANS},
}


@pytest.mark.parametrize("shape", sorted(IDLE))
def test_an_idle_device_reduces_to_busy_zero(shape):
    from benchmark.readers import (attention_roofline, program_idle_ms,
                                   trace_ms_per_step,
                                   trace_named_ms_per_step)

    r = tr.Reduced(IDLE[shape], chips=1)
    assert (r.w0, r.w1) == (1000.0, 5000.0)
    assert r.window_s == pytest.approx(4000e-9)
    assert r.busy_s == 0 and r.busy0_s == 0
    assert r.seconds(["data formatting", "convolution fusion"]) == 0
    assert r.count("serve.step") == 1
    b = r.breakdown()
    assert b["device_ops"] == []
    # the whole window is one gap, by the host span that covers it
    assert dict(b["idle_gaps"]) == {
        "idle.wait_arrival": pytest.approx(2500e-9),
        "serve.step": pytest.approx((900 + 500) * 1e-9),
        tr.NO_SPAN: pytest.approx(100e-9)}
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(r.window_s)
    json.dumps(b)
    # kernels and kinds of operation that did not run are left out: a
    # slice with step spans and no such operation reads nothing, not 0.0
    run = types.SimpleNamespace(reduced=r, chips=1)
    assert trace_named_ms_per_step.read(
        run, {}, ["flash_fwd"], "serve.step") is None
    assert attention_roofline.read(
        run, {}, "serve.step", ["custom-call"]) is None
    assert trace_ms_per_step.read(run, {}, "serve.step",
                                  ["all-reduce"]) is None
    assert program_idle_ms.idle_ms(
        [], r, ["serve.admit"], "serve.iteration", "serve.iteration",
        "serve.step") is None


def test_four_chips_of_which_none_ran_anything():
    r = tr.Reduced({"devices": {str(i): [] for i in range(4)},
                    "spans": IDLE_SPANS}, chips=4)
    assert r.busy_s == 0 and r.device_ids == ["0", "1", "2", "3"]


def test_a_trace_nobody_can_place_is_still_an_error(tmp_path):
    # no bench.window span and no device event: nothing says what was traced
    with pytest.raises(ValueError):
        tr.Reduced({"devices": {}, "spans": [["serve.step", 0.0, 1.0]]}, 1)
    # the profiler wrote no file at all: a broken run, not an idle one
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path))


def test_a_traced_run_of_an_idle_device_still_prints_its_line(monkeypatch,
                                                              tmp_path):
    from benchmark import harness

    args = types.SimpleNamespace(seed=1, seconds=50.0, trace=1,
                                 rehearse=False)
    traffic = harness.load_json(harness.HERE, "traffic",
                                "chat-backlog-sat.json")
    run = harness.Run(args, {"name": "gpt2l-serve-batch-sat", "chips": 1},
                      {}, traffic, {}, 0.0)
    run.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    run._trace_dir = str(tmp_path)
    monkeypatch.setattr(tr, "find_xplane", lambda d: "x")
    monkeypatch.setattr(tr, "load",
                        lambda path, names: IDLE["no device plane"])
    monkeypatch.delenv("BENCH_KEEP_TRACE", raising=False)
    run.reduce_trace(["serve.step"])
    line = run.result_line({
        "correct": True, "attempted": 3, "failed": 0, "end_to_end": {},
        "queued_min_in_window": 130, "requests_completed": 3,
        "elapsed_s": 50.0})
    assert line["device"]["busy_s"] == 0
    assert line["device"]["window_s"] == pytest.approx(4000e-9)
    assert line["breakdown"]["device_ops"] == []
    assert line["metrics"]["serve_requests_per_s"] == {"value": 0.06,
                                                       "unit": "req/s"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # a time nothing was spent on is left out, by name and by kind alike
    assert "sat_paged_attn_ms_per_iteration" not in line["metrics"]
    assert "sat_weight_path_ms_per_iteration" not in line["metrics"]
    json.dumps(line)
