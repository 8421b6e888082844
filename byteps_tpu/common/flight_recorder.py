"""Per-step flight recorder: the always-on post-mortem ring buffer.

A stall report used to show only the moment of death: ``StallError``
carried the *current* per-NIC counters and credit pools, but nothing
about the steps leading up to it — was PUSH p99 creeping for 40 rounds,
or did one FAULT event kill the job cold? This module keeps a bounded
ring of per-step metric snapshots (stage dwell/run percentiles, wire
totals, credit occupancy, step walltime) plus the most recent
FAULT-class events, and hands the whole thing out as a **post-mortem**
that rides every ``StallError`` / ``PartitionFailure`` (attached
centrally in ``common/scheduler.py``) and is exposed to bench/tests as
``byteps_tpu.metrics_snapshot()``.

Feeding it costs nothing extra at the producer sites:

* **steps** — the tracer's step advance (``TraceRecorder.advance_to`` /
  ``fused_step`` / ``step``) already fires on every push_pull round and
  every fused train step, on every path (jax eager, jax hybrid,
  DcnCore, torch/tf adapters); the recorder hooks it. Each tick also
  observes ``train.step_ms`` in the registry — train-step walltime is a
  first-class metric, not a bench-only number.
* **events** — every FAULT-track chrome-trace instant (retries,
  failovers, evictions, membership changes, injected faults) is
  forwarded by the tracer REGARDLESS of whether tracing is enabled;
  the flight recorder is the always-on consumer the trace file is the
  opt-in one.

Knobs: ``BYTEPS_FLIGHT_RECORDER_STEPS`` (ring size, 0 disables the
per-step ring), ``BYTEPS_FLIGHT_RECORDER_EVENTS`` (event ring),
``BYTEPS_FLIGHT_RECORDER_DIR`` (also write post-mortems as JSON files,
one per distinct failure reason per run). See docs/observability.md.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from byteps_tpu.common.logging import get_logger
from byteps_tpu.common.metrics import get_registry, json_safe

log = get_logger("flight_recorder")

# µs-scaled buckets would waste the low end on a step-walltime series;
# step times are ms-scale, so give train.step_ms the default ladder
# (1 ms .. 1e8 ms covers everything a real run produces).
_STEP_MS_HIST = "train.step_ms"


class FlightRecorder:
    """Bounded per-step snapshot ring + recent FAULT events."""

    def __init__(self, max_steps: int = 64, max_events: int = 128,
                 dump_dir: str = "") -> None:
        self.max_steps = max(0, max_steps)
        self.max_events = max(0, max_events)
        self._steps: deque = deque(maxlen=max(1, self.max_steps))
        self._events: deque = deque(maxlen=max(1, self.max_events))
        self._dump_dir = dump_dir
        self._lock = threading.Lock()
        # serializes the WHOLE step advance (guard + snapshot + ring
        # append): two concurrent advancers — e.g. a jax host-callback
        # trace marker and the post-dispatch tick — must not interleave
        # their snapshots, or the ring gets out-of-order entries whose
        # counters were sampled from the wrong step. RLock: tick() holds
        # it across its read-then-advance so a racing ticker cannot
        # swallow a step.
        self._step_serial = threading.RLock()
        self._step = 0
        self._last_step_t: Optional[float] = None
        self._t0 = time.time()
        # one post-mortem FILE per distinct reason per run: a shutdown
        # storm failing hundreds of handles must not write hundreds of
        # identical dumps
        self._dumped_reasons: set = set()
        # burst coalescing: per-reason (monotonic time, dict) of the
        # last built post-mortem — a storm failing hundreds of handles
        # in one instant shares ONE dict instead of assembling (and
        # retaining) hundreds of near-identical snapshots
        self._pm_cache: Dict[str, Any] = {}

    # -- producers -----------------------------------------------------------
    def record_event(self, name: str, args: Optional[Dict[str, Any]] = None,
                     ) -> None:
        """A FAULT-class event (fed by the tracer's FAULT-track instants;
        also callable directly). Args are sanitized at record time so a
        numpy scalar can never poison a later JSON dump."""
        if self.max_events <= 0:  # BYTEPS_FLIGHT_RECORDER_EVENTS=0
            return
        ev = {
            "t_s": round(time.time() - self._t0, 6),
            "step": self._step,
            "event": str(name),
            "args": json_safe(args or {}),
        }
        with self._lock:
            self._events.append(ev)

    def on_step(self, step_no: int) -> None:
        """Step boundary (tracer step advance). Snapshots the registry's
        headline series into the ring and observes the step walltime.
        Idempotent per step number; skipped steps collapse into one
        entry (the walltime then covers the skipped span). Serialized
        end to end under ``_step_serial`` so concurrent advancers
        append in step order with step-consistent snapshots."""
        with self._step_serial:
            self._on_step_serialized(step_no)

    def _on_step_serialized(self, step_no: int) -> None:
        now = time.perf_counter()
        with self._lock:
            if step_no <= self._step:
                return
            self._step = step_no
            last = self._last_step_t
            self._last_step_t = now
        step_ms = None if last is None else (now - last) * 1e3
        reg = get_registry()
        if step_ms is not None:
            reg.histogram(_STEP_MS_HIST).observe(step_ms)
        if self.max_steps <= 0:
            return
        # per-step cost must not grow with the process's total
        # histogram count: scalars for everything, percentile scans
        # only for the stage histograms (full snapshot is post_mortem's
        # job, once, at failure time)
        scalars = reg.snapshot_scalars()
        stage_hists = reg.snapshot(prefix="scheduler.stage.")
        # per-step stage view: cumulative dwell/run percentiles at this
        # step (the stall question is "what moved?" — diffing
        # consecutive entries answers it)
        stages: Dict[str, Any] = {}
        for k in stage_hists["histograms"]:
            if not k.endswith(".run_us"):
                continue
            st = k[len("scheduler.stage."):-len(".run_us")]
            stages[st] = {
                "dwell_p50_us": _p(stage_hists,
                                   f"scheduler.stage.{st}.dwell_us", "p50"),
                "dwell_p99_us": _p(stage_hists,
                                   f"scheduler.stage.{st}.dwell_us", "p99"),
                "run_p50_us": _p(stage_hists,
                                 f"scheduler.stage.{st}.run_us", "p50"),
                "run_p99_us": _p(stage_hists,
                                 f"scheduler.stage.{st}.run_us", "p99"),
            }
        entry = {
            "step": step_no,
            "t_s": round(time.time() - self._t0, 6),
            "step_ms": None if step_ms is None else round(step_ms, 3),
            "stages": stages,
            "counters": scalars["counters"],
            "gauges": scalars["gauges"],
        }
        with self._lock:
            self._steps.append(entry)

    def tick(self) -> None:
        """Advance ONE step relative to the recorder's current step —
        for producers with a private notion of "a step happened" (the
        fused train-step wrappers) that cannot know the process-wide
        step number: an absolute ``on_step(local_count)`` from a fresh
        1-based counter would be silently dropped whenever the recorder
        already advanced past it (eager rounds before training, a
        second model in the same process). The read-then-advance holds
        ``_step_serial`` so a racing advancer cannot swallow the tick
        (and its train.step_ms sample)."""
        with self._step_serial:
            with self._lock:
                nxt = self._step + 1
            self._on_step_serialized(nxt)

    # -- consumers -----------------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def steps(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._steps)

    def post_mortem(self, reason: str = "manual",
                    extra: Optional[Dict[str, Any]] = None,
                    dump: bool = True,
                    coalesce_s: float = 0.5) -> Dict[str, Any]:
        """The full flight dump: the step ring, the FAULT-event ring, and
        the registry's current snapshot. Attached to StallError /
        PartitionFailure; also written to BYTEPS_FLIGHT_RECORDER_DIR
        (once per reason) when configured and ``dump``. Extra-less calls
        for the same reason within ``coalesce_s`` share ONE dict — a
        shutdown storm failing hundreds of handles must not assemble
        hundreds of near-identical snapshots."""
        now = time.monotonic()
        if extra is None:
            with self._lock:
                cached = self._pm_cache.get(reason)
            if cached is not None and now - cached[0] < coalesce_s:
                return cached[1]
        with self._lock:
            steps = list(self._steps)
            events = list(self._events)
            step = self._step
        pm: Dict[str, Any] = {
            "reason": reason,
            "step": step,
            "steps": steps,
            "fault_events": events,
            "metrics": get_registry().snapshot(),
            # what the program was doing last: the tail of the tracer's
            # always-on span ring (name, start_s, dur_s, id, parent,
            # args; seconds on time.monotonic())
            "spans": _last_spans(),
            # the run's resolved knobs ride every dump: a post-mortem is
            # a valid (degraded) what-if simulator input on its own
            # (sim/extract.cost_model_from_flight_dump)
            "config": _config_snapshot(),
        }
        if extra:
            pm["extra"] = json_safe(extra)
        else:
            with self._lock:
                self._pm_cache[reason] = (now, pm)
        if dump:
            self.maybe_dump(reason, pm)
        return pm

    def summary(self) -> Dict[str, Any]:
        """Light view for metrics_snapshot(): counts, not payloads."""
        with self._lock:
            return {
                "step": self._step,
                "ring_steps": len(self._steps),
                "fault_events": len(self._events),
            }

    def maybe_dump(self, reason: str, pm: Dict[str, Any]) -> Optional[str]:
        """Write ``pm`` as a JSON file into BYTEPS_FLIGHT_RECORDER_DIR
        (no-op when unset; once per reason per run). Public so callers
        that must signal waiters BEFORE touching the disk (scheduler's
        partition-failure path) can split build and dump."""
        if not self._dump_dir:
            return None
        with self._lock:
            if reason in self._dumped_reasons:
                return None
            self._dumped_reasons.add(reason)
        try:
            os.makedirs(self._dump_dir, exist_ok=True)
            path = os.path.join(
                self._dump_dir,
                f"flight_{reason}_{os.getpid()}.json")
            with open(path, "w") as f:
                json.dump(pm, f, indent=1)
            log.warning("flight-recorder post-mortem (%s) written to %s",
                        reason, path)
            return path
        except Exception as e:  # noqa: BLE001 - a post-mortem writer
            # must never add a second failure on top of the first
            log.warning("flight-recorder dump failed: %s", e)
            return None


def _last_spans(n: int = 256) -> List[Any]:
    # imported here: the tracer imports this module for its FAULT instants
    from byteps_tpu.common.tracing import get_tracer

    return json_safe(get_tracer().spans()[-n:])


def _config_snapshot() -> Dict[str, Any]:
    """The resolved Config as a JSON-safe dict; never lets a config
    problem break a post-mortem (telemetry must not add a second
    failure)."""
    try:
        from byteps_tpu.common.config import get_config

        return get_config().snapshot()
    except Exception:  # noqa: BLE001
        return {}


def _p(snap: Dict[str, Any], name: str, stat: str) -> Optional[float]:
    h = snap["histograms"].get(name)
    if not h or not h.get("count"):
        return None
    v = h.get(stat)
    return None if v is None else round(v, 1)


_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def get_flight_recorder() -> FlightRecorder:
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                from byteps_tpu.common.config import get_config

                cfg = get_config()
                _recorder = FlightRecorder(
                    max_steps=cfg.flight_recorder_steps,
                    max_events=cfg.flight_recorder_events,
                    dump_dir=cfg.flight_recorder_dir,
                )
    return _recorder


def reset_flight_recorder() -> None:
    """Drop the cached recorder (test isolation, like reset_registry)."""
    global _recorder
    with _recorder_lock:
        _recorder = None
