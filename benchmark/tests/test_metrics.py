"""The metric arithmetic on hand-made inputs."""

import pytest

from benchmark import metrics


def test_percentile_is_numpys_linear_one():
    v = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert metrics.percentile(v, 50) == 3.0
    assert metrics.percentile(v, 95) == pytest.approx(4.8)
    assert metrics.stat(v, "mean") == 3.0
    assert metrics.stat(v, "p95") == pytest.approx(4.8)


def test_chat_metrics_over_the_measured_requests_only():
    results = {
        0: {"ttft_s": 9.0, "token_s": [0.0, 9.0]},          # ramp: ignored
        1: {"ttft_s": 0.5, "token_s": [10.5, 10.6, 10.8]},
        2: {"ttft_s": 1.5, "token_s": [12.0, 12.1]},
        # 3 never finished: failed
    }
    m = metrics.chat_metrics(results, [1, 2, 3])
    assert (m["attempted"], m["failed"]) == (3, 1)
    assert m["ttft_mean_ms"] == pytest.approx(1000.0)
    assert sorted(m["itl_ms"]) == pytest.approx([100.0, 100.0, 200.0])
    assert m["itl_p95_ms"] == pytest.approx(190.0)


def test_window_counting():
    # 40 generated tokens between two readings 4 s apart
    assert metrics.window_rate(140, 100, 14.0, 10.0) == 10.0
    start = {"count": 10, "sum": 50.0}
    end = {"count": 30, "sum": 200.0}
    assert metrics.histogram_window_mean(end, start) == 7.5
    assert metrics.histogram_window_mean(start, start) is None
    assert metrics.histogram_window_mean(end, {"count": 0}) == \
        pytest.approx(200.0 / 30)
    # 10 steps of 8192 tokens in 2.5 s on 4 chips
    assert metrics.train_tokens_per_s(10, 8192, 1.0, 3.5, 4) == 8192.0
    with pytest.raises(ValueError):
        metrics.window_rate(1, 0, 1.0, 1.0)
