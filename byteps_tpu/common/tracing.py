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
import functools
import itertools
import json
import os
import re
import sys
import threading
import time
import weakref
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


# -- regions on the device (docs/observability.md §Regions on the device) ---
#
# The models and the serve steps mark their regions with ``jax.named_scope``.
# The names end up in ``metadata={op_name="..."}`` of every instruction of a
# compiled program, and a device event of a profiler trace is named after its
# instruction. ``scope_table`` is the join's left side: instruction -> scope.

# path components of an ``op_name`` that are JAX's own wrappers, not a region
_WRAPPER_WORDS = frozenset((
    "shard_map", "checkpoint", "remat", "remat2", "rematted_computation",
    "while", "cond", "body", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "custom_lin", "pjit",
    "xla_call", "scan", "named_call"))
_BRANCH = re.compile(r"^branch_\d+_fun$")
# wrappers whose parentheses hold a FUNCTION's name; the others (jvp,
# transpose, vmap, ...) hold the scopes open when the transformation began
_NAMES_A_FUNCTION = frozenset(("jit", "pjit", "xla_call", "named_call"))
_HLO_INSTR = re.compile(
    r"^\s+(?:ROOT )?%?([^\s=]+) = .*?\s([a-z][a-z0-9\-]*)\(")
_HLO_COMP = re.compile(r"^(ENTRY )?%?([^\s(]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")
_APPLIES = re.compile(r"\bto_apply=%?([^\s,}]+)")
_NO_EVENT = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                       "bitcast"))
# what names a computation, not an operand, in an instruction's line
_NAMES_A_COMPUTATION = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?[^\s,}]+|"
    r"\b(?:branch_computations|called_computations)=\{[^}]*\}")
_OPERAND = re.compile(r"%([^\s,(){}]+)")


def _split_path(path: str) -> List[str]:
    """``path`` cut at the slashes outside any parentheses."""
    parts, depth, start = [], 0, 0
    for i, c in enumerate(path):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth == 0:
            parts.append(path[start:i])
            start = i + 1
    parts.append(path[start:])
    return parts


def _regions(path: str) -> List[str]:
    out: List[str] = []
    for part in _split_path(path):
        head, paren, inner = part.partition("(")
        if paren and part.endswith(")"):
            if head not in _NAMES_A_FUNCTION:
                out.extend(_regions(inner[:-1]))
        elif part and part not in _WRAPPER_WORDS and "->" not in part \
                and "<" not in part and not _BRANCH.match(part):
            out.append(part)
    return out


@functools.lru_cache(maxsize=65536)
def scope_of(op_name: str) -> str:
    """The region of the source an instruction belongs to, from its
    ``op_name``: the ``/``-joined run of path components that are not
    ``jit(..)``, ``jvp(..)``, ``transpose(..)``, ``vmap(..)``,
    ``shard_map``, ``checkpoint`` / ``remat``, ``while`` / ``cond`` /
    ``body`` wrappers (the scopes INSIDE a ``jvp(..)`` or ``transpose(..)``
    are kept: the forward and the backward of a region land on one scope),
    an ``einsum``'s own ``ab,bc->ac``, a Python qualified name
    (``f.<locals>.g``) or the trailing primitive name.
    ``jit(step)/transpose(jvp(block/attn))/paged/attention/dot_general`` is
    ``block/attn/paged/attention``; ``""`` where nothing is left. JAX names
    a few components after the Python function a lowering rule ran in
    (``moe/plan/_row_plan``) or prints a region twice (the transpose of a
    ``jax.checkpoint``: ``block/mlp/block/mlp``): they are kept as they
    come, under the region that was open."""
    return "/".join(_regions("/".join(_split_path(op_name)[:-1])))


def _common_scope(scopes) -> str:
    """The scope all of ``scopes`` agree on, else their longest common
    prefix of whole components."""
    return "/".join(os.path.commonprefix([s.split("/") for s in scopes]))


def _parse_hlo(hlo_text: str):
    """(module name, {computation: [(instruction, opcode, scope or None,
    called fused computation or None, operands)]}, entry computation,
    computations that are fused or applied)."""
    module, comps, entry, inner = "", {}, None, set()
    cur = None
    for line in hlo_text.splitlines():
        if cur is None:
            if line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
                continue
            m = _HLO_COMP.match(line)
            if m:
                cur = comps[m.group(2)] = []
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        name, op = m.group(1), m.group(2)
        meta = _OP_NAME.search(line)
        calls = None
        if op == "fusion":
            c = _CALLS.search(line)
            calls = c and c.group(1)
            if calls:
                inner.add(calls)
        elif op != "call":
            a = _APPLIES.search(line)
            if a:
                inner.add(a.group(1))
        operands = _OPERAND.findall(_NAMES_A_COMPUTATION.sub(
            "", line[m.end():].split(", metadata=")[0]))
        cur.append((name, op, scope_of(meta.group(1)) if meta else None,
                    calls, operands))
    return module, comps, entry, inner


def _scopes_and_order(hlo_text: str) -> Dict[str, Any]:
    """``{"module": the module's name, "scopes": {instruction: scope},
    "order": [the entry computation's instructions in schedule order],
    "mixed": {fusion: the distinct scopes of its fused instructions} for
    the fusions across regions}`` of one optimized HLO text."""
    module, comps, entry, inner = _parse_hlo(hlo_text)
    table: Dict[str, str] = {}
    mixed: Dict[str, List[str]] = {}
    for cname, instrs in comps.items():
        if cname in inner:
            continue
        own: Dict[str, Optional[str]] = {}
        users: Dict[str, List[str]] = {}
        for name, op, scope, calls, operands in instrs:
            if calls in comps:
                fused = sorted({i[2] for i in comps[calls] if i[2]})
                if fused:
                    scope = _common_scope(fused)
                if len(fused) > 1:
                    mixed[name] = fused
            own[name] = scope
            for o in operands:
                users.setdefault(o, []).append(name)

        def resolve(name):
            # an instruction the compiler made (a weight's prefetch, a
            # copy, an async pair: no metadata) belongs where its result
            # goes; instructions come in schedule order, users later
            if own[name] is None:
                own[name] = ""                       # a cycle cannot be
                scopes = [resolve(u) for u in users.get(name, ())
                          if u in own]
                own[name] = _common_scope(scopes) if scopes else ""
            return own[name]

        for name in reversed([i[0] for i in instrs]):
            table[name] = resolve(name)
    order = [i[0] for i in comps.get(entry, ()) if i[1] not in _NO_EVENT]
    return {"module": module, "scopes": table, "order": order,
            "mixed": mixed}


def scope_table(hlo_text: str) -> Dict[str, str]:
    """``{instruction short name: scope}`` of one compiled program, from its
    optimized HLO text (``jax.stages.Compiled.as_text()``): every
    instruction of the entry computation, of ``while`` bodies and
    conditions, of branches and of called computations — a device trace's
    "XLA Ops" line nests their events under the container's — with
    :func:`scope_of` its ``op_name``. A fusion takes the scope all its fused
    instructions that carry one agree on, else their longest common prefix
    of whole components (its own where none carries one): an instruction of
    no region fused into a region's goes with it, one fused across two
    regions goes to what they share. An instruction WITHOUT metadata —
    the compiler's own: a weight's prefetch (``copy-start`` / ``copy-done``),
    an async pair, a layout copy — takes the scope the instructions that
    use its result agree on (their common prefix; through chains of such
    instructions): the time a region waits for its operands is the
    region's. ``""`` is a legal answer: an instruction in no region, or
    one that feeds several; nothing is guessed.
    The instructions INSIDE fused and applied computations produce no event
    and are left out."""
    return _scopes_and_order(hlo_text)["scopes"]


# every traced program still alive, and the newest few kept alive: a train
# step its caller let go of can still be asked about after the run, and a
# process that builds steps by the hundred (the tests) does not keep them
_programs: "weakref.WeakSet" = weakref.WeakSet()
_recent: collections.deque = collections.deque(maxlen=16)


class _TracedProgram:
    """What :func:`traced_program` returns."""

    __slots__ = ("name", "_jitted", "_size", "_n", "_key", "_statics",
                 "signatures", "_tables", "__weakref__")

    def __init__(self, name, jitted, key, statics):
        self.name = name
        self._jitted = jitted
        self._size = jitted._cache_size
        self._n = self._size()
        self._key = key
        self._statics = frozenset(statics)
        #: {label: (args, kwargs) as ShapeDtypeStructs}, one an executable
        self.signatures: Dict[str, tuple] = {}
        self._tables: Dict[str, Dict[str, Any]] = {}     # program_scopes'
        _programs.add(self)
        _recent.append(self)

    def __call__(self, *args, **kwargs):
        out = self._jitted(*args, **kwargs)
        if self._size() != self._n:
            self._note(args, kwargs)
        return out

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def _note(self, args, kwargs) -> None:
        """The jit wrapper holds another executable than at the last look:
        keep this call's abstract signature (shapes, dtypes, weak types,
        the sharding of a committed array; a donated array still says all
        of these; ``statics`` as given) under its label."""
        import jax

        self._n = self._size()
        leaves = jax.tree_util.tree_leaves((args, kwargs))
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return                       # traced into a larger program

        def abstract(x):
            aval = jax.api_util.shaped_abstractify(x)
            return jax.ShapeDtypeStruct(
                aval.shape, aval.dtype, weak_type=aval.weak_type,
                sharding=x.sharding if getattr(x, "_committed", False)
                else None)

        try:
            sig = (jax.tree_util.tree_map(abstract, args),
                   {k: v if k in self._statics
                    else jax.tree_util.tree_map(abstract, v)
                    for k, v in kwargs.items()})
            label = str(len(self.signatures)) if self._key is None \
                else self._key(*args, **kwargs)
        except (TypeError, ValueError, AttributeError, IndexError) as e:
            # an argument with no abstract value (a static passed by
            # position): the call itself succeeded and must not fail here
            log.debug("traced_program %s: signature not kept: %s",
                      self.name, e)
            return
        self.signatures.setdefault(label, sig)


def traced_program(name: str, jitted, key=None, statics=()):
    """``jitted`` (a ``jax.jit`` wrapper) behind a thin callable that
    remembers WHAT IT COMPILED: after each call it compares the wrapper's
    executable count (``jitted._cache_size()``) with the count it last saw
    and, only when that moved, keeps the call's abstract signature —
    nothing is lowered, no text is held. :func:`program_scopes` turns the
    signatures into scope tables when someone asks. A call costs one C++
    method call and one comparison more than the jitted function's own
    (tests/test_scope_table.py pins it under a microsecond); every other
    attribute (``lower``, ``_cache_size`` ...) is the jitted function's.

    ``key(*args, **kwargs) -> str`` labels an executable in the words the
    program's spans use for it (a decode step's table width ``W=8``); by
    default executables are numbered. ``statics``: the keyword arguments
    that are ``static_argnames`` of the jit, kept as given. A callable
    that is no jit wrapper (it counts no executables) comes back as it is."""
    if not hasattr(jitted, "_cache_size"):
        return jitted
    return _TracedProgram(name, jitted, key, statics)


def program_scopes(only=None) -> Dict[str, Dict[str, Any]]:
    """The scope tables of the programs this process has run through
    :func:`traced_program`: ``{"<name>[<label>]": {"module": the HLO
    module's name, "signature": the executable's label, "scopes":
    {instruction: scope}, "order": [the entry computation's instructions
    in schedule order], "mixed": {fusion: [the distinct scopes of its fused
    instructions]} for the fusions that lie across regions (their scope is
    what those share, often ``""``)}}``. ``only``: program names
    (``serve.decode``), full keys (``serve.decode[W=8]``) or the wrappers
    themselves, to restrict the work to.

    Built when asked and memoised; the package itself never asks. Each
    table comes from ``jitted.lower(*signature).compile().as_text()``: the
    text of the executable the process LOADED for that signature — a hit in
    JAX's caches, no compile, where the persistent compile cache is on —
    and so certain to name its instructions as the device events do, which
    neither ``lower()``'s pre-optimization text nor a dump directory of
    another compile is. The persistent cache leaves metadata out of its
    key: on a hit the scopes are those of the tree that COMPILED the entry,
    so after renaming a scope in the source clear the cache
    (``.jax_cache``) before looking for the new name."""
    want = None if only is None else set(only)
    out: Dict[str, Dict[str, Any]] = {}
    for prog in sorted(_programs, key=lambda p: p.name):
        for label, (args, kwargs) in list(prog.signatures.items()):
            full = f"{prog.name}[{label}]"
            if want is not None and not {prog, prog.name, full} & want:
                continue
            if label not in prog._tables:
                text = prog._jitted.lower(*args, **kwargs).compile().as_text()
                prog._tables[label] = dict(_scopes_and_order(text),
                                           signature=label)
            while full in out:           # two wrappers under one name
                full += "'"
            out[full] = prog._tables[label]
    return out


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
