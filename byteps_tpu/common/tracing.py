"""Chrome trace-event recorder (SURVEY §5.1).

The reference collects per-tensor, per-queue-stage timestamps in its core
loops and dumps Chrome trace-event JSON per worker, controlled by
``BYTEPS_TRACE_ON`` / ``BYTEPS_TRACE_DIR`` / ``BYTEPS_TRACE_START_STEP`` /
``BYTEPS_TRACE_END_STEP`` (reference ``docs/timeline.md``; the joapolarbear
fork exists largely to feed these traces to dPRO). We reproduce the same
schema: one ``X`` (complete) event per partition per pipeline stage, with
``pid`` = worker rank, ``tid`` = stage name, and args carrying key/partition
metadata, so dPRO-style per-stage attribution works on the TPU build.

Device-side work is additionally coverable by ``jax.profiler`` XLA traces;
this recorder is the framework-level (scheduler/transport) view.

One span primitive, three consumers (docs/observability.md §spans):

- an always-on, bounded in-memory **ring** (``spans()``), whatever
  ``BYTEPS_TRACE_ON`` and the step window say — what a benchmark reader or
  a post-mortem cuts to a window. An entry is the tuple ``(name, start_s,
  dur_s, span_id, parent_id, args)``; times are seconds on
  ``time.monotonic()``'s clock (``TraceRecorder.clock``), so a reader
  compares them with its own ``time.monotonic()`` stamps directly.
  ``BYTEPS_METRICS_ON=0`` stills it, like the registry;
- a ``jax.profiler.TraceAnnotation`` for the span's lifetime, so that under
  any profiler session the span sits on the host line of the same xplane as
  the device ops (the profiler's clock is this clock plus one constant per
  session);
- the step-windowed chrome-trace dump, as before.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from byteps_tpu.common.config import get_config
from byteps_tpu.common.flight_recorder import get_flight_recorder
from byteps_tpu.common.logging import get_logger
from byteps_tpu.common.metrics import json_safe

log = get_logger("tracing")

# the saturated serving cell makes ~214 iterations a second x ~12 entries
# (8 structural spans, the issue's parts, the device steps) + three phases
# for each of 27 requests a second: ~2,700 entries a second, 135k in a 50 s
# window; an entry is one small tuple
RING_SPANS = 262144


class TraceRecorder:
    """Collects chrome trace events; thread-safe; dumps per-worker JSON."""

    def __init__(
        self,
        enabled: bool = False,
        trace_dir: str = "./traces",
        start_step: int = 1,
        end_step: int = 30,
        rank: int = 0,
        xprof: bool = False,
    ) -> None:
        self.enabled = enabled
        self.trace_dir = trace_dir
        self.start_step = start_step
        self.end_step = end_step
        self.rank = rank
        # BYTEPS_TRACE_XPROF=1: capture a jax.profiler (XLA/xprof) trace
        # over the SAME [start_step, end_step] window as the chrome
        # trace — device-side kernel/fusion attribution beside the
        # framework's stage spans (view with tensorboard or xprof)
        self.xprof = xprof and enabled
        self._xprof_running = False
        self.metadata: Dict[str, Any] = {}
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._step = 0
        # Chrome timestamps are ABSOLUTE epoch microseconds, advanced by
        # the monotonic clock (immune to wall-clock steps mid-run): the
        # server trace records CLOCK_REALTIME, so worker and server events
        # land on one timeline without post-hoc shifting (same host;
        # cross-host uses the recorded ping clock offset — see
        # merge_traces). One constant takes the ring's clock there.
        self._epoch_minus_mono_us = (time.time_ns()
                                     - time.monotonic_ns()) / 1e3
        self._dumped = False
        self._ring: Optional[collections.deque] = (
            collections.deque(maxlen=RING_SPANS)
            if get_config().metrics_on else None)
        self._ids = itertools.count(1)
        self._open = threading.local()     # .sid: the thread's open span

    # -- step lifecycle -----------------------------------------------------
    def step(self) -> None:
        """Advance the step counter; auto-dump once past end_step."""
        self._step += 1
        # ALWAYS-ON step boundary: the flight recorder snapshots the
        # metrics registry per step regardless of trace_on — step
        # advancement is the one signal every aggregation path already
        # drives (docs/observability.md)
        get_flight_recorder().on_step(self._step)
        self._maybe_xprof()
        if self.enabled and self._step > self.end_step:
            self.dump()

    def advance_to(self, step_no: int) -> None:
        """Idempotent step advance: the production paths drive this
        automatically (eager: a tensor's round/version number; fused: the
        optimizer's count via jax.debug.callback), so ``BYTEPS_TRACE_ON=1``
        alone records — no manual ``step()`` calls in user code."""
        dump = False
        with self._lock:
            if step_no <= self._step:
                return
            self._step = step_no
            dump = self.enabled and self._step > self.end_step
        get_flight_recorder().on_step(step_no)
        self._maybe_xprof()
        if dump:
            self.dump()

    def _maybe_xprof(self) -> None:
        """Start/stop the jax.profiler capture at the window edges.
        Failures degrade to a warning — the chrome trace still records."""
        if not self.xprof:
            return
        entering = (not self._xprof_running
                    and self.start_step <= self._step <= self.end_step)
        leaving = self._xprof_running and self._step > self.end_step
        if not entering and not leaving:
            return
        try:
            import jax

            if entering:
                d = os.path.join(self.trace_dir, f"xprof_rank{self.rank}")
                os.makedirs(d, exist_ok=True)
                jax.profiler.start_trace(d)
                self._xprof_running = True
                log.info("xprof capture started -> %s", d)
            else:
                jax.profiler.stop_trace()
                self._xprof_running = False
                log.info("xprof capture stopped")
        except Exception as e:  # noqa: BLE001 — profiler support varies
            self.xprof = False
            self._xprof_running = False
            log.warning("xprof capture unavailable: %s", e)

    def fused_step(self, count: int, args: Optional[Dict[str, Any]] = None) -> None:
        """Per-execution marker fired from inside a jitted train step
        (``jax.debug.callback`` in ``DistributedOptimizer.update``); `count`
        is the optimizer's pre-increment step counter. Idempotent across
        the per-shard duplicate callbacks shard_map can produce."""
        step_no = int(count) + 1
        emit = False
        with self._lock:
            if step_no > self._step:
                self._step = step_no
                emit = True
        if emit:
            get_flight_recorder().on_step(step_no)
            self._maybe_xprof()
            self.instant(f"step{step_no}", "FUSED_PUSHPULL", args)
            if self.enabled and self._step > self.end_step:
                self.dump()

    @property
    def active(self) -> bool:
        return (
            self.enabled
            and self.start_step <= self._step <= self.end_step
        )

    #: the ring's clock: entries compare with ``time.monotonic()`` stamps
    clock = staticmethod(time.monotonic)

    def _now_us(self) -> float:
        return self._epoch_minus_mono_us + time.monotonic_ns() / 1e3

    # -- event emission -----------------------------------------------------
    def emit(self, name: str, stage: str, start_s: float, dur_s: float,
             args: Any = None, parent: int = 0) -> int:
        """One finished span whose ends the caller stamped itself on
        ``clock`` (the serve scheduler's per-request phases, from the
        stamps its results are computed from), under the span whose id
        ``parent`` is. Returns the span's id."""
        sid = next(self._ids)
        self._record(name, stage, start_s, dur_s, sid, parent, args)
        return sid

    def _record(self, name, stage, start_s, dur_s, sid, parent, args):
        if self._ring is not None:
            self._ring.append((name, start_s, dur_s, sid, parent, args))
        if self.active:
            self._chrome_x(name, stage,
                           self._epoch_minus_mono_us + start_s * 1e6,
                           dur_s * 1e6, args)

    def complete_event(
        self,
        name: str,
        stage: str,
        start_us: float,
        dur_us: float,
        args: Optional[Dict[str, Any]] = None,
    ) -> None:
        """``emit`` in the chrome trace's units (epoch microseconds)."""
        if self._ring is not None:
            self._ring.append((
                name, (start_us - self._epoch_minus_mono_us) / 1e6,
                dur_us / 1e6, next(self._ids), 0, args))
        if self.active:
            self._chrome_x(name, stage, start_us, dur_us, args)

    def _chrome_x(self, name, stage, start_us, dur_us, args) -> None:
        if args is not None and not isinstance(args, dict):
            args = {"args": args}
        ev = {
            "name": name,
            "cat": "byteps",
            "ph": "X",
            "ts": start_us,
            "dur": dur_us,
            "pid": self.rank,
            "tid": stage,
            # sanitize at the producer boundary: ONE rule for every call
            # site (np.bool_/np-scalar args broke the JSON dump once —
            # see metrics.json_safe)
            "args": json_safe(args or {}),
        }
        with self._lock:
            self._events.append(ev)

    def span(self, name: str, stage: str, args: Any = None):
        """Context manager recording one span: ring, profiler annotation
        and (inside the step window) chrome event. Spans opened inside
        it on the same thread carry its id as their parent. ``args`` is
        kept as given on the ring (a small tuple is cheapest) and made
        JSON-safe only on the chrome path."""
        return _Span(self, name, stage, args)

    def spans(self, since: Optional[float] = None) -> List[tuple]:
        """A copy of the ring, oldest first: ``(name, start_s, dur_s,
        span_id, parent_id, args)``; with ``since``, the entries that
        start at or after that ``clock`` time."""
        ring = list(self._ring) if self._ring is not None else []
        if since is not None:
            ring = [e for e in ring if e[1] >= since]
        return ring

    def instant(self, name: str, stage: str, args: Optional[Dict[str, Any]] = None) -> None:
        if stage == "FAULT":
            # every FAULT-track instant (retries, failovers, evictions,
            # membership, injections) also lands in the ALWAYS-ON flight
            # recorder — the chrome trace is the opt-in consumer, the
            # post-mortem ring the unconditional one
            get_flight_recorder().record_event(name, args)
        if not self.active:
            return
        ev = {
            "name": name,
            "cat": "byteps",
            "ph": "i",
            "ts": self._now_us(),
            "s": "t",
            "pid": self.rank,
            "tid": stage,
            "args": json_safe(args or {}),
        }
        with self._lock:
            self._events.append(ev)

    # -- output -------------------------------------------------------------
    def dump(self, path: Optional[str] = None) -> Optional[str]:
        if self._xprof_running:
            # run ended inside the window — close the device capture
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                log.warning("xprof stop at dump failed: %s", e)
            self._xprof_running = False
        if self._dumped or not self._events:
            return None
        self._dumped = True
        if path is None:
            os.makedirs(self.trace_dir, exist_ok=True)
            path = os.path.join(self.trace_dir, f"trace_rank{self.rank}.json")
        with self._lock:
            doc = {
                "traceEvents": self._events,
                "displayTimeUnit": "ms",
                "metadata": json_safe({
                    "rank": self.rank,
                    "framework": "byteps_tpu",
                    "clock": "epoch_us",
                    # the run's resolved knobs: a dumped trace is
                    # replayable by the what-if simulator without
                    # out-of-band knowledge (sim/extract.py)
                    "config": get_config().snapshot(),
                    **self.metadata,
                }),
            }
        with open(path, "w") as f:
            json.dump(doc, f)
        log.info("dumped %d trace events to %s", len(self._events), path)
        return path


_TraceAnnotation = None


def _resolve_annotation():
    """``jax.profiler.TraceAnnotation`` once the process has imported jax
    (without jax there is no profiler session to be seen in, and this
    module stays importable by a process that never loads it)."""
    global _TraceAnnotation
    prof = getattr(sys.modules.get("jax"), "profiler", None)
    _TraceAnnotation = getattr(prof, "TraceAnnotation", None)
    return _TraceAnnotation


class _Span:
    __slots__ = ("rec", "name", "stage", "args", "t0", "sid", "parent",
                 "_ann")

    def __init__(self, rec: TraceRecorder, name: str, stage: str, args):
        self.rec = rec
        self.name = name
        self.stage = stage
        self.args = args

    def __enter__(self):
        rec = self.rec
        open_ = rec._open
        self.parent = getattr(open_, "sid", 0)
        self.sid = open_.sid = next(rec._ids)
        ann = _TraceAnnotation or _resolve_annotation()
        if ann is not None:
            self._ann = ann(self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        rec = self.rec
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        rec._open.sid = self.parent
        rec._record(self.name, self.stage, self.t0, t1 - self.t0,
                    self.sid, self.parent, self.args)
        return False


_tracer: Optional[TraceRecorder] = None


def get_tracer() -> TraceRecorder:
    global _tracer
    if _tracer is None:
        cfg = get_config()
        _tracer = TraceRecorder(
            enabled=cfg.trace_on,
            trace_dir=cfg.trace_dir,
            start_step=cfg.trace_start_step,
            end_step=cfg.trace_end_step,
            rank=cfg.worker_id,
            xprof=cfg.trace_xprof,
        )
    return _tracer


def reset_tracer() -> None:
    global _tracer
    _tracer = None


def merge_traces(out_path: str, in_paths: List[str]) -> int:
    """Merge per-role chrome traces onto ONE aligned timeline.

    Worker traces carry absolute epoch-us timestamps; server traces carry
    CLOCK_REALTIME us (the same clock on the same host). For a server on a
    DIFFERENT host, the worker that pinged it recorded
    ``server_clock_offset_ns`` (= server_clock − worker_clock, kPing RTT/2
    method — SURVEY §5.1, the dPRO cross-worker alignment capability) in
    its own metadata; server events are shifted by −offset onto the
    workers' clock here. Returns the merged event count.
    """
    docs = [json.load(open(p)) for p in in_paths]
    # per-server offsets (server_clock − worker_clock, ns) from the first
    # worker that probed them; every server's rows get their OWN shift
    offsets_ns: Dict[str, float] = {}
    for d in docs:
        md = d.get("metadata", {})
        if md.get("role") != "server" and md.get("server_clock_offsets"):
            offsets_ns = {
                str(k): float(v)
                for k, v in md["server_clock_offsets"].items()
            }
            break
    events: List[Dict[str, Any]] = []
    for d in docs:
        md = d.get("metadata", {})
        is_server = md.get("role") == "server"
        offset_us = (
            offsets_ns.get(str(md.get("server_id", 0)), 0.0) / 1e3
            if is_server else 0.0
        )
        for ev in d.get("traceEvents", []):
            if is_server and offset_us:
                ev = {**ev, "ts": ev["ts"] - offset_us}
            events.append(ev)
    events.sort(key=lambda e: e.get("ts", 0))
    with open(out_path, "w") as f:
        json.dump(
            {
                "traceEvents": events,
                "displayTimeUnit": "ms",
                "metadata": {"merged_from": [os.path.basename(p) for p in in_paths]},
            },
            f,
        )
    return len(events)


def _merge_main(argv: List[str]) -> int:
    """CLI: python -m byteps_tpu.common.tracing merged.json trace1.json ..."""
    if len(argv) < 3:
        print("usage: python -m byteps_tpu.common.tracing OUT.json IN.json "
              "[IN.json ...]")
        return 2
    n = merge_traces(argv[1], argv[2:])
    print(f"merged {n} events from {len(argv) - 2} traces into {argv[1]}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_merge_main(sys.argv))
