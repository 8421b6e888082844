"""Trace-driven what-if simulator (byteps_tpu/sim, docs/whatif.md).

Tier-1 pins the subsystem's contracts:

* determinism — same trace + same SimConfig (+ seed) → bit-identical
  prediction;
* event-rule fidelity — the sim's credit gate / priority order /
  rounds-window rules agree with the REAL ``PipelineScheduler`` on
  small choreographed schedules, and the sim's wire timing is the REAL
  ``TokenBucket`` arithmetic (driven on a virtual clock);
* calibration — extraction recovers tensor structure and service fits
  from a synthetic trace, round-trips through JSON, and degrades to a
  flight-recorder dump;
* the payoff hooks — AutoTuner's ``proposer`` converges within
  ``min_gain`` of the grid-walk optimum in strictly fewer live
  evaluations, and ScalingPolicy's ``estimator`` vetoes an admit whose
  simulated payoff is sublinear (recording the prediction);
* the satellites — ``Config.snapshot()`` stamped into trace metadata
  and flight dumps, ``--whatif-export``, flight dumps as
  ``load_events`` input.

Predictions are not held against a live run here: the one validation
sweep went with the pre-chip harness (ROADMAP.md C9).
"""

import json
import subprocess
import sys
import threading

import numpy as np
import pytest

from byteps_tpu.sim.engine import SimConfig, _Bucket, simulate
from byteps_tpu.sim.extract import (
    CostModel,
    cost_model_from_events,
    cost_model_from_flight_dump,
    predict_step_s,
)

# a tiny deterministic codec table: calibration-free tests must not pay
# (or depend on) the native micro-bench
_TABLE = {
    "_sum": {"us_per_byte": 1e-4},
    "raw": {"encode_us_per_byte": 1e-6, "decode_us_per_byte": 1e-4,
            "sdecode_us_per_byte": 1e-4, "sencode_us_per_byte": 2e-4},
    "onebit": {"encode_us_per_byte": 3e-4, "decode_us_per_byte": 1e-3,
               "sdecode_us_per_byte": 1.5e-3, "sencode_us_per_byte": 1e-3},
    "topk": {"encode_us_per_byte": 5e-4, "decode_us_per_byte": 7e-5,
             "sdecode_us_per_byte": 2e-5, "sencode_us_per_byte": 2e-3},
    "fp16": {"encode_us_per_byte": 5e-4, "decode_us_per_byte": 4e-4,
             "sdecode_us_per_byte": 4e-4, "sencode_us_per_byte": 1.7e-3},
}


def _model(nelems=4 * (1 << 20), throttle=200.0, codec="raw",
           slack_us=0.0):
    return CostModel(
        pipeline="dcn",
        tensors=[(0, "g", nelems)],
        stage_fits={"COMPRESS": (50.0, 0.0), "DECOMPRESS": (60.0, 0.0)},
        overheads={"PUSH": 200.0, "PULL": 100.0, "PULL_REQ": 20.0},
        codec_table=_TABLE,
        recorded={"codec": codec, "partition_bytes": 4096000,
                  "scheduling_credit": 4, "dcn_throttle_mbps": throttle,
                  "staleness": 0, "pod_controllers": 1, "owner_salt": 0,
                  "num_worker": 1},
        round_slack_us=slack_us,
    )


# ---- determinism -------------------------------------------------------------
def test_simulation_is_deterministic():
    """ACCEPTANCE: same model + same SimConfig + same seed →
    bit-identical prediction (exact float equality, not approx)."""
    m = _model()
    cfg = SimConfig(codec="onebit", throttle_mbps=64.0, rounds=3,
                    seed=7, jitter=0.05)
    a = simulate(m, cfg)
    b = simulate(m, cfg)
    assert a.step_time_s == b.step_time_s
    assert a.round_times_s == b.round_times_s
    assert a.issues == b.issues
    # a different seed moves jittered service times but stays close
    c = simulate(m, SimConfig(codec="onebit", throttle_mbps=64.0,
                              rounds=3, seed=8, jitter=0.05))
    assert c.step_time_s != a.step_time_s
    assert abs(c.step_time_s - a.step_time_s) < 0.2 * a.step_time_s


def test_cost_model_json_round_trip():
    m = _model()
    m2 = CostModel.from_dict(json.loads(json.dumps(m.to_dict())))
    cfg = SimConfig(codec="topk", throttle_mbps=800.0, rounds=3)
    assert predict_step_s(m, cfg) == predict_step_s(m2, cfg)


# ---- event rules vs the real scheduler --------------------------------------
def _run_real_scheduler(credit, rounds=1, parts=4, rounds_window=None):
    """Choreograph the REAL PipelineScheduler: DCN stage names, pool
    size 1 everywhere, instant stage fns that record issue order and
    credit occupancy."""
    from byteps_tpu.common.partition import Partition
    from byteps_tpu.common.scheduler import (
        Handle,
        PartitionTask,
        PipelineScheduler,
        Stage,
    )

    issued = []   # (stage, key, round)
    lock = threading.Lock()
    in_credit = [0]
    max_credit = [0]

    def fn(name, entering_credit=False, leaving_credit=False):
        def run(task):
            with lock:
                if entering_credit:
                    in_credit[0] += 1
                    max_credit[0] = max(max_credit[0], in_credit[0])
                issued.append((name, task.partition.key, task.round))
                if leaving_credit:
                    in_credit[0] -= 1
            return task.payload
        return run

    stages = [
        Stage("COMPRESS", fn("COMPRESS", entering_credit=True),
              credited=True, pool_size=1),
        Stage("PUSH", fn("PUSH", leaving_credit=True), credited=True,
              pool_size=1, releases_credit=True),
        Stage("PULL", fn("PULL"), pool_size=1),
        Stage("DECOMPRESS", fn("DECOMPRESS"), pool_size=1),
    ]
    sched = PipelineScheduler(stages, credit=credit,
                              rounds_window=rounds_window)
    try:
        for rnd in range(rounds):
            handle = Handle(f"g{rnd}", parts)
            tasks = [
                PartitionTask(
                    partition=Partition(key=k, tensor_id=0, part_idx=k,
                                        offset=0, length=1024,
                                        priority=0),
                    name=f"g{rnd}", handle=handle, round=rnd)
                # enqueue in REVERSE key order: priority order must win
                for k in reversed(range(parts))
            ]
            sched.enqueue(tasks)
            handle.wait(timeout=30)
    finally:
        sched.shutdown()
    return issued, max_credit[0]


def _sim_issues(credit, rounds=1, parts=4, staleness=0):
    m = CostModel(
        pipeline="dcn",
        tensors=[(0, "g", parts * 1024)],
        stage_fits={}, overheads={}, codec_table=_TABLE,
        recorded={"codec": "raw", "partition_bytes": 4096,
                  "scheduling_credit": credit, "dcn_throttle_mbps": 0.0,
                  "staleness": staleness, "pod_controllers": 1,
                  "owner_salt": 0, "num_worker": 1},
    )
    res = simulate(m, SimConfig(partition_bytes=4096, credit=credit,
                                codec="raw", rounds=rounds,
                                staleness=staleness))
    return [(st, key, rnd) for (_t, st, key, rnd, _w) in res.issues]


def test_sim_agrees_with_real_scheduler_on_toy_schedule():
    """ACCEPTANCE: the event rules agree with the production scheduler
    on a choreographed run — per-stage issue order is priority order
    (ties by key) in BOTH, and the credit high-water mark never exceeds
    the budget in the real run (the rule the sim enforces by
    construction)."""
    for credit in (1, 2, 4):
        real, real_max_credit = _run_real_scheduler(credit=credit)
        sim = _sim_issues(credit=credit)
        for st in ("COMPRESS", "PUSH", "PULL", "DECOMPRESS"):
            real_order = [k for (s, k, _r) in real if s == st]
            sim_order = [k for (s, k, _r) in sim if s == st]
            assert real_order == sorted(real_order), (st, credit, real)
            assert sim_order == real_order, (st, credit)
        assert real_max_credit <= credit


def test_sim_rounds_window_matches_real_scheduler():
    """Bounded staleness event rule: with rounds_window=K, a key may
    have at most K+1 rounds in flight — pinned on the REAL scheduler
    and asserted identically in the sim's issue trace."""
    def max_run_ahead(issued):
        finished = {}   # round -> done parts
        ahead = 0
        open_rounds = set()
        for (st, _k, rnd) in issued:
            if st == "COMPRESS":
                open_rounds.add(rnd)
            if st == "DECOMPRESS":
                finished[rnd] = finished.get(rnd, 0) + 1
                if finished[rnd] == 1:  # parts=1 per round below
                    open_rounds.discard(rnd)
            if open_rounds:
                ahead = max(ahead, max(open_rounds) - min(open_rounds))
        return ahead

    real, _ = _run_real_scheduler(credit=8, rounds=4, parts=1,
                                  rounds_window=1)
    sim = _sim_issues(credit=8, rounds=4, parts=1, staleness=1)
    assert max_run_ahead(real) <= 1
    assert max_run_ahead(sim) <= 1
    # every round still ran, in order, in both
    assert [r for (s, _k, r) in real if s == "PUSH"] == [0, 1, 2, 3]
    assert [r for (s, _k, r) in sim if s == "PUSH"] == [0, 1, 2, 3]


def test_sim_bucket_is_the_real_pacer_arithmetic(monkeypatch):
    """The sim's wire timing IS TokenBucket's deficit arithmetic: drive
    the REAL pacer bucket on a virtual clock and compare completion
    times charge by charge."""
    from byteps_tpu.server import pacer as pacer_mod

    clock = [0.0]
    monkeypatch.setattr(pacer_mod.time, "monotonic", lambda: clock[0])
    real = pacer_mod.TokenBucket(rate_bytes_per_s=1e6)
    sim = _Bucket(1e6)
    charges = [(0.0, 500 << 10), (0.1, 64 << 10), (0.1, 4 << 20),
               (2.5, 100), (2.5, 1 << 20), (10.0, 64 << 10)]
    for t, nbytes in charges:
        clock[0] = t
        slept = real.throttle(nbytes)   # time.sleep is a real no-op? no:
        # TokenBucket sleeps wall-clock; neutralize by asserting the
        # RETURNED sleep (the arithmetic) instead of elapsed time
        assert sim.charge(t, nbytes) == pytest.approx(t + slept, abs=1e-9)


def test_staleness_hides_straggler_in_sim():
    """K-ladder what-if as a first-class event rule: two workers, one
    3× slower on compute — K=0 barriers every round on the straggler,
    K=2 lets the fast worker run ahead and the server force-close, so
    the simulated step time strictly improves."""
    m = _model(throttle=64.0)
    base = dict(partition_bytes=4096000, credit=4, codec="raw",
                throttle_mbps=64.0, num_workers=2, rounds=6,
                worker_speed=(1.0, 3.0))
    sync = simulate(m, SimConfig(staleness=0, **base))
    stale = simulate(m, SimConfig(staleness=2, **base))
    assert stale.makespan_s < sync.makespan_s
    # and on a healthy pair, K=0 and K=2 are nearly identical (the
    # window only matters when someone is behind)
    healthy = dict(base, worker_speed=(1.0, 1.0))
    h0 = simulate(m, SimConfig(staleness=0, **healthy))
    h2 = simulate(m, SimConfig(staleness=2, **healthy))
    assert h2.makespan_s <= h0.makespan_s * 1.05


def test_owner_salt_and_controllers_change_placement_not_totals():
    """Sharded-wire what-ifs: controller count divides per-NIC wire
    time (faster rounds), and the owner salt reshuffles placement
    deterministically."""
    m = _model()
    one = simulate(m, SimConfig(codec="raw", throttle_mbps=64.0,
                                rounds=2, pod_controllers=1))
    four = simulate(m, SimConfig(codec="raw", throttle_mbps=64.0,
                                 rounds=2, pod_controllers=4))
    assert four.step_time_s < one.step_time_s / 2
    a = simulate(m, SimConfig(codec="raw", throttle_mbps=64.0, rounds=1,
                              pod_controllers=4, owner_salt=0))
    b = simulate(m, SimConfig(codec="raw", throttle_mbps=64.0, rounds=1,
                              pod_controllers=4, owner_salt=3))
    assert a.tasks == b.tasks


# ---- extraction --------------------------------------------------------------
def _synthetic_trace(parts=4, rounds=3, length=1024000, push_ms=5.0):
    """A DCN-shaped chrome trace with known service times."""
    events = []
    t = 0.0
    for rnd in range(rounds):
        for p in range(parts):
            for stage, dur in (("COMPRESS", 1000.0), ("PUSH", push_ms * 1e3),
                               ("PULL", 2000.0), ("DECOMPRESS", 1500.0)):
                events.append({
                    "name": f"g.p{p}", "cat": "byteps", "ph": "X",
                    "ts": t, "dur": dur, "pid": 0, "tid": stage,
                    "args": {"key": p, "priority": 0, "length": length},
                })
                t += dur
    return events


def test_extract_recovers_structure_and_fits():
    ev = _synthetic_trace()
    m = cost_model_from_events(
        ev, config={"codec": "raw", "partition_bytes": 4096000,
                    "dcn_throttle_mbps": 0.0, "num_worker": 1},
        codec_table=_TABLE)
    # tensor structure: 4 partitions x 1024000 elems
    assert m.tensors == [(0, "g", 4 * 1024000)]
    layout = m.partition_layout(4096000)
    assert [row[2] for row in layout] == [1024000] * 4
    # a different partition size re-partitions with make_partitions math
    assert len(m.partition_layout(1024000)) == 16
    # compute-stage fits keep the measured intercepts
    a, _b = m.stage_fits["COMPRESS"]
    assert a == pytest.approx(1000.0, rel=0.1)
    # the model predicts SOMETHING finite and positive for a what-if
    pred = predict_step_s(m, SimConfig(partition_bytes=1 << 20,
                                       credit=2, codec="onebit",
                                       throttle_mbps=100.0, rounds=2))
    assert 0 < pred < 60


def test_extract_requires_partition_spans():
    with pytest.raises(ValueError, match="no partition spans"):
        cost_model_from_events(
            [{"ph": "X", "ts": 0, "dur": 1, "tid": "PUSH", "pid": 0,
              "args": {}}],
            config={}, codec_table=_TABLE)


def test_flight_dump_is_a_degraded_extraction_input(tmp_path):
    """Satellite: a flight-recorder post-mortem (per-step stage p50s +
    wire counters + the stamped config) extracts into a coarse cost
    model, and load_events accepts the dump file directly."""
    from byteps_tpu.common.trace_analysis import load_events

    dump = {
        "reason": "test", "step": 3,
        "steps": [
            {"step": i, "t_s": 0.5 * i, "step_ms": 500.0,
             "stages": {
                 "COMPRESS": {"run_p50_us": 900.0},
                 "PUSH": {"run_p50_us": 4000.0},
                 "PULL": {"run_p50_us": 2000.0},
                 "DECOMPRESS": {"run_p50_us": 1200.0}},
             "counters": {}, "gauges": {}}
            for i in range(1, 4)
        ],
        "fault_events": [],
        "metrics": {"counters": {"wire.push_bytes": 3 * 4096000.0}},
        "config": {"partition_bytes": 1 << 20, "scheduling_credit": 2,
                   "dcn_throttle_mbps": 200.0},
    }
    m = cost_model_from_flight_dump(dump, codec_table=_TABLE)
    assert m.recorded["partition_bytes"] == 1 << 20
    assert m.tensors[0][2] == pytest.approx(1024000, rel=0.01)
    assert 0 < predict_step_s(
        m, SimConfig(codec="raw", throttle_mbps=200.0, rounds=2)) < 60

    p = tmp_path / "flight_test.json"
    p.write_text(json.dumps(dump))
    evs = load_events(str(p))
    stages = {e["tid"] for e in evs}
    assert stages == {"COMPRESS", "PUSH", "PULL", "DECOMPRESS"}
    assert all(e["ph"] == "X" for e in evs)


# ---- config snapshot satellites ---------------------------------------------
def test_trace_dump_carries_config_snapshot(tmp_path):
    from byteps_tpu.common.tracing import TraceRecorder

    rec = TraceRecorder(enabled=True, trace_dir=str(tmp_path),
                        start_step=1, end_step=5, rank=0)
    rec.advance_to(1)
    rec.complete_event("g.p0", "PUSH", 0.0, 10.0, {"length": 4})
    path = rec.dump()
    doc = json.load(open(path))
    cfg = doc["metadata"]["config"]
    assert "partition_bytes" in cfg and "scheduling_credit" in cfg
    assert "dcn_throttle_mbps" in cfg and "staleness" in cfg


def test_flight_post_mortem_carries_config_snapshot():
    from byteps_tpu.common.flight_recorder import FlightRecorder

    fr = FlightRecorder(max_steps=4, max_events=4)
    fr.on_step(1)
    pm = fr.post_mortem(reason="test", dump=False)
    assert "config" in pm and "partition_bytes" in pm["config"]


def test_whatif_export_cli(tmp_path):
    """Satellite: one command turns a recorded trace into the
    simulator's calibration input."""
    from byteps_tpu.common.tracing import TraceRecorder

    rec = TraceRecorder(enabled=True, trace_dir=str(tmp_path),
                        start_step=1, end_step=50, rank=0)
    rec.advance_to(1)
    for ev in _synthetic_trace(parts=2, rounds=2):
        rec.complete_event(ev["name"], ev["tid"], ev["ts"], ev["dur"],
                           ev["args"])
    trace_path = rec.dump()
    out = tmp_path / "model.json"
    res = subprocess.run(
        [sys.executable, "-m", "byteps_tpu.common.trace_analysis",
         trace_path, "--whatif-export", str(out)],
        capture_output=True, text=True)
    assert res.returncode == 0, res.stderr
    assert "calibrated cost model" in res.stdout
    m = CostModel.from_dict(json.load(open(out)))
    assert m.tensors[0][2] == 2 * 1024000
    assert 0 < predict_step_s(
        m, SimConfig(codec="raw", throttle_mbps=100.0, rounds=2)) < 60


# ---- the payoff hooks --------------------------------------------------------
def test_tuner_proposer_beats_grid_walk():
    """ACCEPTANCE pin: with the simulator itself as ground truth, the
    proposer-guided AutoTuner reaches a config within min_gain of the
    grid-walk optimum in STRICTLY fewer live evaluations."""
    from byteps_tpu.common.tuner import AutoTuner
    from byteps_tpu.sim.search import make_proposer

    m = _model(throttle=200.0)
    applied = {}

    def apply(pb, cr):
        applied["cfg"] = (pb, cr)

    def live_cost():
        pb, cr = applied["cfg"]
        return predict_step_s(m, SimConfig(
            partition_bytes=pb, credit=cr, codec="raw",
            throttle_mbps=200.0, rounds=2))

    def drive(tuner, budget=600):
        rounds = 0
        while not tuner.converged and rounds < budget:
            tuner.record_step(live_cost())
            rounds += 1
        assert tuner.converged
        return rounds

    grid = AutoTuner(apply, interval=2, warmup=0, min_gain=0.02)
    grid_rounds = drive(grid)
    grid_best_t = predict_step_s(m, SimConfig(
        partition_bytes=grid.best[0], credit=grid.best[1], codec="raw",
        throttle_mbps=200.0, rounds=2))

    prop = AutoTuner(apply, interval=2, warmup=0, min_gain=0.02,
                     proposer=make_proposer(m, top_n=4))
    prop_rounds = drive(prop)
    prop_best_t = predict_step_s(m, SimConfig(
        partition_bytes=prop.best[0], credit=prop.best[1], codec="raw",
        throttle_mbps=200.0, rounds=2))

    assert prop_rounds < grid_rounds, (prop_rounds, grid_rounds)
    assert prop_best_t <= grid_best_t * 1.02, (prop.best, grid.best)


def test_tuner_proposer_exhaustion_converges_on_best():
    from byteps_tpu.common.tuner import AutoTuner

    seen = []
    shortlist = [(1 << 20, 8), (2 << 20, 4)]

    def proposer(best, best_time, measured):
        for cand in shortlist:
            if cand not in measured:
                return cand
        return None

    tuner = AutoTuner(lambda pb, cr: seen.append((pb, cr)), interval=2,
                      warmup=0, min_gain=0.01, proposer=proposer)
    costs = {(4 << 20, 4): 1.0, (1 << 20, 8): 0.5, (2 << 20, 4): 0.8}
    while not tuner.converged:
        tuner.record_step(costs[seen[-1]])
    assert tuner.best == (1 << 20, 8)
    assert seen[-1] == (1 << 20, 8)          # converged best re-applied
    assert set(costs) == set(tuner.measured)


def test_scaling_policy_estimator_vetoes_non_paying_admit():
    """Satellite (ROADMAP item 4 remainder): an admit consults the
    estimator, a sublinear predicted payoff degrades it to a hold that
    RECORDS the prediction, and a paying payoff admits (prediction
    attached to the decision)."""
    from byteps_tpu.common.autoscaler import Sample, ScalingPolicy
    from byteps_tpu.common.flight_recorder import (
        get_flight_recorder,
        reset_flight_recorder,
    )
    from byteps_tpu.common.metrics import reset_registry

    reset_registry()
    reset_flight_recorder()

    def saturating(n):
        return {1: 1.0, 2: 1.9, 3: 1.95, 4: 1.96}.get(n, 2.0)

    pol = ScalingPolicy(scale_up_load=1.0, scale_down_load=0.1,
                        hysteresis=0.1, cooldown=2, sustain=1,
                        min_units=1, max_units=8, domain="train",
                        estimator=saturating)
    d = pol.observe(Sample(live=1, load=2.0))     # 1 -> 2 pays off
    assert d.action == "admit"
    assert d.predicted is not None and d.predicted["pays_off"]
    pol.observe(Sample(live=2, load=2.0))         # cooldown
    pol.observe(Sample(live=2, load=2.0))         # cooldown
    d = pol.observe(Sample(live=2, load=2.0))     # 2 -> 3 adds < 10% of
    assert d.action == "hold"                     # an avg worker's share
    assert "estimator veto" in d.reason
    assert d.predicted["goodput_target"] == pytest.approx(1.95)
    vetoes = [e for e in get_flight_recorder().events()
              if e.get("event") == "autoscaler.decision"
              and "veto" in e["args"].get("reason", "")]
    assert vetoes and vetoes[-1]["args"]["predicted"]["target"] == 3
    # a veto arms the cooldown + resets streaks (it is a consequential
    # decision): the next ticks are plain cooldown holds, NOT more ring
    # events — a sustained veto state records once per cooldown window
    # instead of drowning the bounded event ring
    n_events = len(get_flight_recorder().events())
    for _ in range(2):
        d2 = pol.observe(Sample(live=2, load=2.0))
        assert d2.action == "hold" and "veto" not in d2.reason
    assert len(get_flight_recorder().events()) == n_events
    # ...and perfect linear scaling is never vetoed, at any live count
    pol2 = ScalingPolicy(scale_up_load=1.0, scale_down_load=0.1,
                         hysteresis=0.1, cooldown=0, sustain=1,
                         min_units=1, max_units=64, domain="train",
                         estimator=lambda n: float(n))
    d3 = pol2.observe(Sample(live=40, load=2.0))
    assert d3.action == "admit" and d3.predicted["pays_off"]
    reset_registry()
    reset_flight_recorder()


def test_goodput_estimator_from_model_is_sublinear_under_contention():
    """The sim-backed estimator: aggregate goodput grows with workers
    but sublinearly once the serialized server saturates."""
    from byteps_tpu.sim.search import goodput_estimator

    m = _model(throttle=64.0, codec="onebit")
    est = goodput_estimator(
        m, base=SimConfig(partition_bytes=4096000, credit=4,
                          codec="onebit", throttle_mbps=64.0))
    g1, g2, g8 = est(1), est(2), est(8)
    assert g2 > g1                    # a second worker still pays
    assert g8 < 8 * g1                # ...but never linearly
    assert est(2) == g2               # memoized
