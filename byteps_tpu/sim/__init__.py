"""Trace-driven what-if simulator (dPRO, MLSys'22; ROADMAP item 3).

The joapolarbear fork exists to FEED its traces to dPRO, which replays
them to predict distributed-training performance under hypothetical
configurations — finishing the online-search story ByteScheduler started
with live coordinate descent. This package is that replay tier for the
TPU build: one recorded run (chrome trace + flight-recorder dump + the
run's resolved config, all of which now stamp themselves with
``Config.snapshot()``) is lifted into a calibrated cost model
(:mod:`~byteps_tpu.sim.extract`), replayed under any
:class:`~byteps_tpu.sim.engine.SimConfig` by a discrete-event engine
that re-expresses the scheduler's credit gates, per-owner pools,
rounds window, and the server's quorum/force-close round semantics as
event rules (:mod:`~byteps_tpu.sim.engine`), and searched
(:mod:`~byteps_tpu.sim.search`) so the AutoTuner and ScalingPolicy can
SOLVE for a config instead of sweeping it live.

Validation: tests/test_sim.py holds determinism and the event rules
against the real scheduler and pacer; no prediction is held against a
measured run since PR 50 (docs/whatif.md; ROADMAP.md C9).
"""

from byteps_tpu.sim.engine import SimConfig, SimResult, simulate
from byteps_tpu.sim.extract import (
    CostModel,
    calibrate_codecs,
    cost_model_from_events,
    cost_model_from_flight_dump,
)
from byteps_tpu.sim.search import (
    goodput_estimator,
    make_proposer,
    rank_configs,
)

__all__ = [
    "SimConfig", "SimResult", "simulate",
    "CostModel", "calibrate_codecs", "cost_model_from_events",
    "cost_model_from_flight_dump",
    "rank_configs", "make_proposer", "goodput_estimator",
]
