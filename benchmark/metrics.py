"""Metric arithmetic: from what a driver observed to the numbers reported.

Kept here, under ``paths``, so that no later PR can change how a number is
made. Every function is pure; ``benchmark/tests/test_metrics.py`` holds
them to hand-made inputs.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default), of a non-empty sequence."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def stat(values: Sequence[float], name: str) -> float:
    if name == "mean":
        return sum(values) / len(values)
    if name == "median":
        return percentile(values, 50)
    if name.startswith("p"):
        return percentile(values, float(name[1:]))
    raise ValueError(f"unknown statistic {name!r}")


def inter_token_gaps_ms(token_times: Iterable[Sequence[float]]) -> List[float]:
    """All gaps between consecutive tokens of one request, over the
    requests given (each a sequence of commit times in seconds)."""
    gaps: List[float] = []
    for ts in token_times:
        gaps.extend((b - a) * 1e3 for a, b in zip(ts[:-1], ts[1:]))
    return gaps


def chat_metrics(results: Dict[int, Dict], measured: Iterable[int]) -> Dict:
    """End-to-end numbers of an open-loop run. ``results[rid]`` carries the
    scheduler's own ``ttft_s`` (timed from when the request was due, so a
    late generator cannot shorten it) and ``token_s``; ``measured`` are the
    requests due inside the window. A measured request with no result
    failed."""
    measured = list(measured)
    done = [r for r in measured if r in results
            and results[r]["ttft_s"] is not None]
    ttft = [results[r]["ttft_s"] * 1e3 for r in done]
    gaps = inter_token_gaps_ms(results[r]["token_s"] for r in done)
    return {"attempted": len(measured), "failed": len(measured) - len(done),
            "ttft_ms": ttft, "itl_ms": gaps,
            "ttft_mean_ms": stat(ttft, "mean") if ttft else None,
            "itl_p95_ms": stat(gaps, "p95") if gaps else None}


def window_rate(count_end: float, count_start: float, t_end: float,
                t_start: float) -> float:
    """Events per second between two readings of a counter: all the work
    of the window over all its time."""
    if t_end <= t_start:
        raise ValueError("window of no length")
    return (count_end - count_start) / (t_end - t_start)


def histogram_window_mean(end: Dict, start: Dict):
    """Mean of what a registry histogram observed between two snapshots
    (``count`` and ``sum`` of each); None when it observed nothing."""
    n = end.get("count", 0) - start.get("count", 0)
    if n <= 0:
        return None
    return (end.get("sum", 0.0) - start.get("sum", 0.0)) / n


def train_tokens_per_s(steps: int, tokens_per_step: int, t_start: float,
                       t_end: float, chips: int) -> float:
    """Tokens of the steps completed inside the window (the last ended by
    block_until_ready at ``t_end``) per second and chip."""
    return window_rate(steps * tokens_per_step, 0, t_end, t_start) / chips
