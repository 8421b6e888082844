"""Launcher supervisor (byteps_tpu/launcher.py): real OS-process
membership under the elastic control plane.

The acceptance bars, from ISSUE 20's tentpole (a):

* the supervisor executes REAL ScalingPolicy decisions — an ``admit``
  spawns a child process that joins mid-stream via kJoin (epoch bump,
  live count grows), an ``evict`` retires one (SIGTERM → exit WITHOUT
  the goodbye → server lease-evicts the id, epoch bump) — in a tier-1
  smoke, with structured exit reasons visible in
  ``metrics_snapshot()`` / flight-recorder events;
* ``proc:``-scoped fault rules are executed as real signals by the
  supervision tick (``proc:kill@step=N`` → SIGKILL), with the same
  grammar round-trip + structured-error contract as ``worker<N>:``;
* flapping children get bounded restart-with-backoff, then a
  ``supervisor.giveup`` instead of a hot loop;
* crash-resume: a SIGKILLed child respawns, restores from its
  ``Checkpointer`` dir, ``rejoin()``s, and lands on final params
  BIT-identical to an uninterrupted run (slow test).
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from byteps_tpu import metrics_snapshot
from byteps_tpu.common import config as config_mod
from byteps_tpu.common.autoscaler import Sample, ScalingPolicy
from byteps_tpu.common.faults import (
    FaultPlan,
    parse_fault_spec,
    rules_to_spec,
)
from byteps_tpu.common.flight_recorder import (
    get_flight_recorder,
    reset_flight_recorder,
)
from byteps_tpu.common.metrics import get_registry, reset_registry
from byteps_tpu.launcher import Supervisor
from byteps_tpu.server import PSWorker, start_server, stop_server

BASE_PORT = 25900
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every child here is a short python snippet or the --child-worker
# driver; anything that outlives this is a supervisor teardown bug
_T = 60  # hard cap (s) on any single wait in this module


@pytest.fixture(autouse=True)
def _fresh():
    reset_registry()
    reset_flight_recorder()
    yield
    stop_server()
    config_mod.reset_config()


def _counters():
    return get_registry().snapshot()["counters"]


def _child_argv(code: str):
    return [sys.executable, "-c", code]


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    return env


# ---- proc: fault grammar ----------------------------------------------------
def test_proc_grammar_round_trips():
    spec = "proc:kill@step=3;proc1:restart@p=0.5"
    rules = parse_fault_spec(spec)
    assert [(r.scope, r.kind, r.worker) for r in rules] == [
        ("proc", "kill", None), ("proc", "restart", 1)]
    assert parse_fault_spec(rules_to_spec(rules)) == rules


@pytest.mark.parametrize("bad,hint", [
    # proc is a process, not a wire: only supervisor actions apply
    ("proc:timeout@op=1", "kill|restart"),
    ("proc:corrupt@p=0.1", "kill|restart"),
    # restart is the supervisor's verb; emulated scopes can't take it
    ("worker:restart@p=0.1", "supervisor action"),
    ("replica1:restart", "supervisor action"),
    ("procx:kill", "bad proc index"),
])
def test_proc_grammar_structured_errors(bad, hint):
    with pytest.raises(ValueError) as ei:
        parse_fault_spec(bad)
    msg = str(ei.value)
    assert msg.startswith("bad BYTEPS_FAULT_SPEC rule")
    assert hint in msg
    assert "invalid literal" not in msg  # structured, not a traceback


def test_proc_rules_fire_only_on_proc_ticks():
    # a proc rule never triggers from wire ops — the supervision tick
    # (op="proc") is its only clock
    plan = FaultPlan(parse_fault_spec("proc:kill@step=1"), seed=0)
    assert plan.intercept("push", 0) is None
    plan = FaultPlan(parse_fault_spec("proc:kill@step=1"), seed=0)
    inj = plan.intercept("proc", -1)
    assert inj is not None and inj.kind == "kill"
    assert plan.counters()["kill"] == 1


def test_proc_index_filters_by_wid():
    rules = parse_fault_spec("proc1:kill@step=1")
    assert FaultPlan(rules, seed=0, worker_id=0).intercept(
        "proc", -1) is None
    inj = FaultPlan(rules, seed=0, worker_id=1).intercept("proc", -1)
    assert inj is not None and inj.kind == "kill"


# ---- exit-reason classification --------------------------------------------
def test_supervisor_classifies_exit_reasons():
    sup = Supervisor(grace_ms=2000)
    sup.spawn(argv=_child_argv("raise SystemExit(0)"))
    sup.spawn(argv=_child_argv("raise SystemExit(5)"))
    sup.spawn(argv=_child_argv(
        "import os, signal; os.kill(os.getpid(), signal.SIGKILL)"))
    try:
        assert sup.wait_all(timeout_s=_T, poll_ms=20)
    finally:
        sup.shutdown()
    assert sup.exit_reasons == {
        0: ["clean"], 1: ["error:rc=5"], 2: ["signal:SIGKILL"]}
    snap = _counters()
    assert snap["supervisor.spawns"] == 3
    assert snap["supervisor.exits"] == 3
    assert snap["supervisor.exit.clean"] == 1
    assert snap["supervisor.exit.error"] == 1
    assert snap["supervisor.exit.signal"] == 1
    events = [e for e in get_flight_recorder().events()
              if e["event"] == "supervisor.exit"]
    assert sorted(e["args"]["reason"] for e in events) == [
        "clean", "error:rc=5", "signal:SIGKILL"]
    assert all(e["args"]["pid"] > 0 for e in events)


def test_restart_backoff_then_giveup():
    """A crash-looping child restarts with doubling backoff, then is
    given up past the limit — never a hot respawn loop."""
    sup = Supervisor(restart_limit=2, backoff_ms=30)
    sup.spawn(argv=_child_argv("raise SystemExit(1)"), auto_restart=True)
    try:
        assert sup.wait_all(timeout_s=_T, poll_ms=20)
    finally:
        sup.shutdown()
    # original + 2 restarts, all crashing, then the giveup
    assert sup.exit_reasons[0] == ["error:rc=1"] * 3
    snap = _counters()
    assert snap["supervisor.restarts"] == 2
    assert snap["supervisor.giveups"] == 1
    assert sup.live() == []
    names = [e["event"] for e in get_flight_recorder().events()]
    assert names.count("supervisor.restart") == 2
    assert names.count("supervisor.giveup") == 1


def test_proc_kill_fault_is_a_real_sigkill():
    """proc:kill@step=3 — the third supervision tick delivers a REAL
    SIGKILL to the child's pid; the exit record says so."""
    sup = Supervisor(fault_spec="proc:kill@step=3")
    sup.spawn(argv=_child_argv("import time; time.sleep(60)"))
    pid = sup.child(0).pid
    try:
        assert sup.wait_all(timeout_s=_T, poll_ms=20)
    finally:
        sup.shutdown()
    assert sup.exit_reasons[0] == ["signal:SIGKILL"]
    with pytest.raises(ProcessLookupError):
        os.kill(pid, 0)  # really dead, not emulated


def test_proc_restart_fault_respawns():
    """proc:restart@step=2 — SIGKILL + respawn. The respawned child
    carries BYTEPS_SUPERVISOR_RESTARTS=1 and runs to completion."""
    sup = Supervisor(fault_spec="proc:restart@step=2", backoff_ms=20)
    sup.spawn(argv=_child_argv(
        "import os, sys, time\n"
        "if os.environ.get('BYTEPS_SUPERVISOR_RESTARTS') == '0':\n"
        "    time.sleep(60)\n"  # first life: wait for the injected kill
        "sys.exit(0)\n"))
    try:
        # a tick of a second: the respawn carries a fresh plan, so its
        # python must start and exit inside two ticks or be killed again
        assert sup.wait_all(timeout_s=_T, poll_ms=1000)
    finally:
        sup.shutdown()
    assert sup.exit_reasons[0] == ["signal:SIGKILL", "clean"]
    assert _counters()["supervisor.restarts"] == 1


def test_retire_escalates_sigterm_to_sigkill():
    """A child that ignores SIGTERM past the grace window is SIGKILLed
    by the tick — retire always converges."""
    sup = Supervisor(grace_ms=300)
    sup.spawn(argv=_child_argv(
        "import signal, time\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "time.sleep(60)\n"))
    time.sleep(0.3)  # let the child install its SIG_IGN first
    sup.retire(0)
    try:
        assert sup.wait_all(timeout_s=_T, poll_ms=20)
    finally:
        sup.shutdown()
    assert sup.exit_reasons[0] == ["signal:SIGKILL"]
    assert _counters()["supervisor.retired"] == 1
    exit_ev = [e for e in get_flight_recorder().events()
               if e["event"] == "supervisor.exit"][0]
    assert exit_ev["args"]["retired"] is True


# ---- the tier-1 acceptance smoke: policy admit → kJoin, evict → lease -------
def test_policy_admit_and_evict_against_real_processes():
    """ScalingPolicy decides, the Supervisor executes against REAL
    processes: admit spawns a child that kJoins (server live-count 2,
    epoch bump), evict retires it (clean exit, NO goodbye → lease
    eviction, epoch bump again) — with exit reasons and decision events
    visible in metrics_snapshot()."""
    port = BASE_PORT
    start_server(port=port, num_workers=1, engine_threads=2,
                 async_mode=False, lease_ms=800)
    w0 = PSWorker(servers=[("127.0.0.1", port)], worker_id=0,
                  health_interval_ms=150)
    sup = Supervisor(first_wid=1, base_env={
        "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
        "BYTEPS_CHILD_SERVERS": f"127.0.0.1:{port}",
        "BYTEPS_CHILD_ROUNDS": "0",  # idle probe: hold a lease only
    })
    policy = ScalingPolicy(scale_up_load=1.0, scale_down_load=0.2,
                           cooldown=0, sustain=1, min_units=1,
                           max_units=2, domain="proc")

    def members():
        ep, live, _bits = w0._conn(0).members()
        return ep, live

    try:
        # heavy load → admit → a real child process kJoins mid-stream
        d = policy.observe(Sample(live=1, load=2.0))
        assert d.action == "admit"
        wid = sup.execute(d)
        assert wid == 1
        deadline = time.monotonic() + _T
        while time.monotonic() < deadline:
            sup.poll()
            ep, live = members()
            if live == 2:
                break
            time.sleep(0.1)
        assert live == 2, "admitted child never joined"
        epoch_after_join = ep
        assert epoch_after_join >= 1  # fresh-id admission bumped it

        # idle → evict → retire: SIGTERM, clean exit WITHOUT goodbye,
        # the server lease-evicts the id and bumps the epoch
        d = policy.observe(Sample(live=2, load=0.05))
        assert d.action == "evict"
        assert sup.execute(d) == wid
        deadline = time.monotonic() + _T
        while time.monotonic() < deadline:
            sup.poll()
            w0.ping(0)  # keep the parent's own lease warm
            ep, live = members()
            if live == 1 and ep > epoch_after_join:
                break
            time.sleep(0.1)
        assert live == 1, "evicted child still holds membership"
        assert ep == epoch_after_join + 1  # exactly one lease eviction
        assert sup.wait_all(timeout_s=_T, poll_ms=20)
    finally:
        sup.shutdown()
        w0.shutdown()
    # the structured story is visible from the outside
    assert sup.exit_reasons[wid] == ["clean"]
    snap = metrics_snapshot()
    c = snap["metrics"]["counters"]
    assert c["autoscaler.decisions"] == 2  # once per decision, no dup
    assert c["autoscaler.proc.admit"] == 1
    assert c["autoscaler.proc.evict"] == 1
    assert c["supervisor.spawns"] == 1
    assert c["supervisor.retired"] == 1
    assert c["supervisor.exit.clean"] == 1
    names = [e["event"] for e in get_flight_recorder().events()]
    assert names.count("autoscaler.decision") == 2
    assert names.count("supervisor.execute") == 2
    assert "supervisor.spawn" in names
    assert "supervisor.exit" in names


# ---- crash-resume through the supervisor (slow: child imports orbax) --------
@pytest.mark.slow
def test_crash_resume_bit_identical_to_uninterrupted(tmp_path):
    """SIGKILL a checkpointing child mid-run; the supervisor respawns
    it, the driver restores + rejoin()s, and the FINAL accumulated
    state is bit-identical to a never-killed run."""
    rounds = 6

    def run(port, ckpt, out, kill_at=None):
        start_server(port=port, num_workers=1, engine_threads=2,
                     async_mode=False, lease_ms=2000)
        sup = Supervisor(backoff_ms=50, base_env={
            "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
            "BYTEPS_CHILD_SERVERS": f"127.0.0.1:{port}",
            "BYTEPS_CHILD_ROUNDS": str(rounds),
            "BYTEPS_CHILD_PIN": "1",
            "BYTEPS_CHILD_CKPT": str(ckpt),
            "BYTEPS_CHILD_OUT": str(out),
            "BYTEPS_CHILD_ROUND_DELAY_MS": "150",
        })
        sup.spawn(auto_restart=True)
        try:
            if kill_at is not None:
                progress = str(out) + ".progress"
                deadline = time.monotonic() + _T
                while time.monotonic() < deadline:
                    done = (open(progress).read().splitlines()
                            if os.path.exists(progress) else [])
                    if len(done) > kill_at:
                        break
                    time.sleep(0.05)
                else:
                    pytest.fail("child never reached the kill round")
                sup.kill(0, signal.SIGKILL)
            assert sup.wait_all(timeout_s=3 * _T, poll_ms=50)
        finally:
            sup.shutdown()
            stop_server()
        return json.loads(open(out).read()), dict(sup.exit_reasons)

    clean, _ = run(BASE_PORT + 4, tmp_path / "ck_clean",
                   tmp_path / "clean.json")
    crashed, reasons = run(BASE_PORT + 6, tmp_path / "ck_crash",
                           tmp_path / "crash.json", kill_at=2)
    assert reasons[0][0] == "signal:SIGKILL"
    assert reasons[0][-1] == "clean"
    assert crashed["restarts"] >= 1
    assert crashed["resumed_from"] >= 1  # really restored, not a redo
    assert len(clean["rounds"]) == rounds
    # the whole point: death + restore + rejoin costs NOTHING in bits
    assert crashed["state_crc"] == clean["state_crc"]
    assert crashed["state_sum"] == clean["state_sum"]
