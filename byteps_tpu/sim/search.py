"""Solve the config space in simulation instead of sweeping it live.

Three consumers (ROADMAP item 3's payoff points):

* :func:`rank_configs` — sweep/hill-climb a ``SimConfig`` grid and
  return the ranked list (best first; ``make_proposer`` walks it);
* :func:`make_proposer` — the :class:`~byteps_tpu.common.tuner.AutoTuner`
  ``proposer=`` hook: after the tuner's warmup window it asks the
  simulator for the next candidate instead of walking blind
  coordinate-descent neighbors, and converges the moment the ranked
  list is exhausted (strictly fewer live evaluations than the grid
  walk — pinned in tests/test_sim.py);
* :func:`goodput_estimator` — the
  :class:`~byteps_tpu.common.autoscaler.ScalingPolicy` ``estimator=``
  hook: an admit/evict decision predicts its own payoff (simulated
  per-worker goodput at live±1) before spending capacity.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from byteps_tpu.common.logging import get_logger
from byteps_tpu.sim.engine import SimConfig
from byteps_tpu.sim.extract import (
    CostModel,
    predict_step_s,
    recorded_sim_config,
)

log = get_logger("sim.search")


def rank_configs(
    model: CostModel,
    base: Optional[SimConfig] = None,
    partition_bytes: Optional[Sequence[int]] = None,
    credits: Optional[Sequence[int]] = None,
    codecs: Optional[Sequence[str]] = None,
    staleness: Optional[Sequence[int]] = None,
    throttle_mbps: Optional[Sequence[float]] = None,
    pod_controllers: Optional[Sequence[int]] = None,
    limit: Optional[int] = None,
) -> List[Tuple[SimConfig, float]]:
    """Exhaustive predicted sweep over the cross product of the given
    axes (unspecified axes stay at ``base``); returns
    ``[(SimConfig, predicted_step_s)]`` fastest-first. The whole point
    of the simulator is that a 6-axis product that would take hours of
    wall-clock to measure runs in milliseconds of arithmetic — sweep
    breadth is limited by ``limit`` only for log hygiene."""
    if base is None:
        # the ONE recorded-config -> SimConfig mapping (extract owns it)
        base = recorded_sim_config(model.recorded)
    axes = {
        "partition_bytes": partition_bytes,
        "credit": credits,
        "codec": codecs,
        "staleness": staleness,
        "throttle_mbps": throttle_mbps,
        "pod_controllers": pod_controllers,
    }
    axes = {k: list(v) for k, v in axes.items() if v is not None}
    if not axes:
        return [(base, predict_step_s(model, base))]
    names = list(axes)
    out: List[Tuple[SimConfig, float]] = []
    for combo in itertools.product(*(axes[n] for n in names)):
        cfg = dataclasses.replace(base, **dict(zip(names, combo)))
        out.append((cfg, predict_step_s(model, cfg)))
    out.sort(key=lambda cv: cv[1])
    return out[:limit] if limit else out


def make_proposer(
    model: CostModel,
    base: Optional[SimConfig] = None,
    partition_grid: Optional[Sequence[int]] = None,
    credit_grid: Optional[Sequence[int]] = None,
    top_n: int = 4,
) -> Callable[[Tuple[int, int], Optional[float], Dict[Tuple[int, int],
                                                      float]],
              Optional[Tuple[int, int]]]:
    """Build an :class:`~byteps_tpu.common.tuner.AutoTuner` ``proposer``:
    rank the (partition_bytes, credit) grid in simulation ONCE, then
    hand the tuner the predicted-fastest candidates it has not yet
    measured, best first. Returning ``None`` (list exhausted) converges
    the tuner on its measured best — the live evaluations are spent
    CONFIRMING the simulator's shortlist, not exploring neighbors."""
    from byteps_tpu.common.tuner import CREDIT_GRID, PARTITION_GRID

    pgrid = list(partition_grid if partition_grid is not None
                 else PARTITION_GRID)
    cgrid = list(credit_grid if credit_grid is not None else CREDIT_GRID)
    ranked = rank_configs(model, base=base, partition_bytes=pgrid,
                          credits=cgrid)
    shortlist: List[Tuple[int, int]] = [
        (cfg.partition_bytes, cfg.credit) for cfg, _ in ranked[:top_n]]
    log.info("sim proposer: shortlist %s (of %d simulated)",
             [(pb >> 10, cr) for pb, cr in shortlist], len(ranked))

    def proposer(current, best_time, measured):
        for cand in shortlist:
            if cand not in measured:
                return cand
        return None

    return proposer


def goodput_estimator(
    model: CostModel,
    base: Optional[SimConfig] = None,
    rounds: int = 3,
) -> Callable[[int], float]:
    """Build a :class:`~byteps_tpu.common.autoscaler.ScalingPolicy`
    ``estimator``: ``estimator(n_workers) -> predicted aggregate
    goodput`` (rounds/s × workers, i.e. useful work per wall-second).
    An admit is worth its capacity only when goodput(live+1) beats
    goodput(live) — round-close barriers and server contention make
    that genuinely sublinear, which is exactly what the replay engine
    models. Memoized: the policy calls it at live and live±1 every
    decision."""
    if base is None:
        base = recorded_sim_config(model.recorded, rounds=rounds)
    cache: Dict[int, float] = {}

    def estimator(n_workers: int) -> float:
        n = max(1, int(n_workers))
        if n not in cache:
            cfg = dataclasses.replace(base, num_workers=n, rounds=rounds)
            step = predict_step_s(model, cfg)
            cache[n] = n / step if step > 0 else 0.0
        return cache[n]

    return estimator
