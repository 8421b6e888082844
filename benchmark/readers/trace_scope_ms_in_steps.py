"""Device milliseconds of some REGIONS OF THE PROGRAM'S SOURCE inside the
steps of one kind, per such step, on the first device of the traced slice.

A device event is named after the HLO instruction it ran; the program says
which region of its source every instruction of the executables it runs
belongs to (``byteps_tpu.common.tracing.program_scopes``: the
``jax.named_scope`` names — ``block/attn``, ``block/moe/moe/route``,
``readout_ce`` ... — from the loaded executables' own text). This reader
joins the two: the LEAF events of device 0 inside the steps (containers —
``control flow`` — left out, as ``trace_reduce.top_ops`` leaves them), each
looked up by its short name in the tables of the programs that kind of step
issues, summed by scope.

``step``: a device step's kind (``serve.device_step.<kind>``), laid on the
trace's clock exactly as ``trace_ms_in_device_steps`` lays it (the same
constant from ``anchor`` / ``trace_anchor``, the same cut at the stamps, the
same ``None``s). A ``decode`` step runs ``serve.decode[W=<its table
width>]``, ``serve.take`` and ``serve.pick``; a ``chunk`` step (a final chunk
alone) ``serve.prefill[C=..,W=..,readout=1]`` and ``serve.pick_last``: the
labels are the step's own span args, so a slice asks for the tables of the
two or three programs it ran and no other. Or ``step_span``: a host
annotation of the trace (``train.step``), whose every span wholly inside the
window is a step of the program of that name, all its executables.

``scopes``: prefixes of whole components (``block/attn`` takes
``block/attn/paged/attention``; ``readout_ce`` takes ``readout_ce.bwd_vocab``
too); or ``outside``: prefixes whose complement among the events that were
placed is meant (the scope ``""`` — an instruction in no region — is
outside everything). The result is the milliseconds under them per step.

``observed["notes"]["device_scopes.<kind>"]`` holds what was seen, per step:
``ms_per_step`` by every scope (``""`` included), ``top`` (the fifteen
longest ``scope:instruction``), ``busy_ms`` (the union of the leaf events),
``unmatched_ms`` (events whose name no table of the step's programs has;
``unmatched_top``: the five longest of them),
``ambiguous_ms`` (names two tables have under different scopes: the small
``take`` / ``pick`` modules against the big one; the big one wins and this
bounds the error), ``across_regions_ms`` (the ten longest sets of regions
that fusions of the big program lie across, ``a|b``: such a fusion's scope
is what its regions share, often ``""``, and this says whose time that is),
``asked_s`` (what ``program_scopes`` took), and the steps ``left_out``
because the program named no table for them. On more than one
chip a ``step_span`` note adds ``idle_before_scope_ms_per_step``: device 0's
idle time inside the steps by the scope of the operation that ends each gap.

``chunk_decode`` steps hold two large programs whose instruction names
collide. They are split where the decode program's first ``SPLIT_NAMES``
top-level instructions occur in sequence (its events are the step's tail),
each part read from its own tables, and noted as
``device_scopes.chunk_decode.chunk`` / ``.decode``; a step in which that
sequence is not found, or whose split leaves more than ``MAX_UNMATCHED`` of
either side's time in no table, is left out and counted. A note only: no
metric asks for that kind.

``None`` where there is no ring, no trace, no ``program_scopes`` (a tree
from before it), no step of the kind in the slice, the clocks do not align,
or ``unmatched_ms`` exceeds ``MAX_UNMATCHED`` of the steps' busy time: a
stale or missing table prints no number.
"""

import bisect
import time

from benchmark import trace_reduce
from benchmark.readers import program_idle_ms, program_span_ms
from benchmark.readers.trace_ms_in_device_steps import PREFIX

MAX_UNMATCHED = 0.05
TOP = 15
SPLIT_NAMES = 8
_CACHE = "_scope_reading"       # on the Run: one reading a kind a run


def matches(scope, prefixes):
    """Whether ``scope`` lies under one of ``prefixes``: equal, or longer by
    a whole component (``/``) or a ``.`` suffix; a prefix that ends in ``/``
    is taken as written."""
    for p in prefixes:
        if scope == p or (p.endswith("/") and scope.startswith(p)) or (
                scope.startswith(p) and scope[len(p)] in "/."):
            return True
    return False


def leaves(events, w0, w1):
    """The leaf events clipped to the window, in order:
    ``[(start, end, name), ...]``."""
    return sorted((a, b, name) for name, cat, a, b in
                  trace_reduce._clipped(events, w0, w1)
                  if cat != trace_reduce.CONTROL_FLOW)


def inside(evs, starts, a, b):
    """The events of ``evs`` (sorted, with their ``starts``) cut to
    ``[a, b)``."""
    i = max(0, bisect.bisect_left(starts, a) - 1)
    out = []
    while i < len(evs) and evs[i][0] < b:
        lo, hi = max(evs[i][0], a), min(evs[i][1], b)
        if hi > lo:
            out.append((lo, hi, evs[i][2]))
        i += 1
    return out


def merged_table(tables):
    """``({name: scope}, names under two different scopes)`` of several
    tables; the first to name an instruction wins."""
    out, clash = {}, set()
    for t in tables:
        for name, scope in t["scopes"].items():
            if out.setdefault(name, scope) != scope:
                clash.add(name)
    return out, clash


class Tally:
    """Nanoseconds by scope and by ``scope:instruction`` over some steps."""

    def __init__(self):
        self.steps = 0
        self.left_out = 0
        self.by_scope, self.by_op, self.across, self.lost = {}, {}, {}, {}
        self.busy = self.unmatched = self.ambiguous = 0.0

    def add(self, evs, main, small, clash, mixed=()):
        """One step's events against its big program's table ``main`` (and
        its fusions across regions, ``mixed``) and the merged small ones;
        ``clash``: names that merged tables hold under two scopes."""
        self.steps += 1
        for a, b, name in evs:
            d = b - a
            self.busy += d
            scope = main.get(name)
            if name in mixed:
                regions = "|".join(mixed[name][:4]) + (
                    f"|+{len(mixed[name]) - 4}" if len(mixed[name]) > 4
                    else "")
                self.across[regions] = self.across.get(regions, 0.0) + d
            if scope is None:
                scope = small.get(name)
                if scope is None:
                    self.unmatched += d
                    self.lost[name] = self.lost.get(name, 0.0) + d
                    continue
            if name in clash or small.get(name, scope) != scope:
                self.ambiguous += d
            self.by_scope[scope] = self.by_scope.get(scope, 0.0) + d
            op = f"{scope}:{name}"
            self.by_op[op] = self.by_op.get(op, 0.0) + d

    def note(self, asked_s):
        n = max(self.steps, 1) * 1e6
        return {"steps": self.steps, "left_out": self.left_out,
                "ms_per_step": {s: v / n for s, v in
                                sorted(self.by_scope.items())},
                "top": [[op, v / n] for op, v in sorted(
                    self.by_op.items(), key=lambda kv: -kv[1])[:TOP]],
                "across_regions_ms": {k: v / n for k, v in sorted(
                    self.across.items(), key=lambda kv: -kv[1])[:10]},
                "unmatched_top": [[k, v / n] for k, v in sorted(
                    self.lost.items(), key=lambda kv: -kv[1])[:5]],
                "busy_ms": self.busy / n, "unmatched_ms": self.unmatched / n,
                "ambiguous_ms": self.ambiguous / n, "asked_s": asked_s}

    def ok(self):
        return self.steps > 0 and self.unmatched <= MAX_UNMATCHED * self.busy


def device_steps(entries, off, w0, w1):
    """``trace_ms_in_device_steps.steps_on_trace`` with each step's args:
    ``[(start_ns, end_ns, kind, args), ...]``."""
    out = []
    for e in entries:
        if e[0].startswith(PREFIX):
            a = e[1] * 1e9 + off
            b = a + e[2] * 1e9
            if a >= w0 and b <= w1:
                out.append((a, b, e[0][len(PREFIX):], tuple(e[5] or ())))
    return sorted(out)


def programs_of(kind, args):
    """(the big programs a step of ``kind`` ran, in issue order; the small
    ones beside them), by ``program_scopes`` key."""
    if kind == "decode":
        return [f"serve.decode[W={args[1]}]"], ["serve.take", "serve.pick"]
    if kind == "chunk":
        return ([f"serve.prefill[C={args[0]},W={args[1]},readout=1]"],
                ["serve.pick_last"])
    if kind == "chunk_decode":
        return ([f"serve.prefill[C={args[0]},W={args[1]},readout=0]",
                 f"serve.decode[W={args[3]}]"], ["serve.take", "serve.pick"])
    return [], []


def split_at(evs, order):
    """The index in ``evs`` at which the program whose top-level schedule
    is ``order`` starts: the LAST place its first ``SPLIT_NAMES``
    instructions occur in sequence (other events — the bodies of its loops
    — may lie between them). None where they do not."""
    first = order[:SPLIT_NAMES]
    if len(first) < SPLIT_NAMES:
        return None
    names = [n for _, _, n in evs]
    for start in range(len(names) - 1, -1, -1):
        if names[start] != first[0]:
            continue
        i, k = start, 0
        while i < len(names) and k < len(first):
            if names[i] == first[k]:
                k += 1
            i += 1
        if k == len(first):
            return start
    return None


def tally_steps(steps, evs, tables, kind):
    """(Tally of the steps of ``kind``; for ``chunk_decode`` a pair: the
    chunk's part and the decode step's)."""
    starts = [e[0] for e in evs]
    split = kind == "chunk_decode"
    out = (Tally(), Tally()) if split else (Tally(),)

    def named(key):
        return [t for k, t in tables.items()
                if k == key or k.split("[")[0] == key]

    for a, b, k, args in steps:
        if k != kind:
            continue
        big, small = programs_of(kind, args)
        mains = [tables.get(key) for key in big]
        if not mains or any(m is None for m in mains):
            for t in out:
                t.left_out += 1
            continue
        small_t, clash = merged_table(
            [t for key in small for t in named(key)])
        mine = inside(evs, starts, a, b)
        if not split:
            out[0].add(mine, mains[0]["scopes"], small_t, clash,
                       mains[0].get("mixed", ()))
            continue
        at = split_at(mine, mains[1]["order"])
        # the take of the decode step behind the chunk is issued before the
        # decode program: small modules are looked up on both sides
        parts = [] if at is None else [(mine[:at], mains[0]),
                                        (mine[at:], mains[1])]
        if not parts or any(unplaced(evs_, m["scopes"], small_t)
                            for evs_, m in parts):
            for t in out:            # no split, or one that places too
                t.left_out += 1      # little of either side: not guessed
            continue
        for t, (evs_, m) in zip(out, parts):
            t.add(evs_, m["scopes"], small_t, clash, m.get("mixed", ()))
    return out


def unplaced(evs, main, small):
    """Whether more than ``MAX_UNMATCHED`` of the events' time is in
    neither table."""
    lost = sum(b - a for a, b, n in evs if n not in main and n not in small)
    return lost > MAX_UNMATCHED * sum(b - a for a, b, _ in evs)


def idle_before_scope(evs, steps, table):
    """Device 0's idle nanoseconds inside ``steps`` by the scope of the
    event that ends each gap."""
    starts = [e[0] for e in evs]
    out = {}
    for a, b in steps:
        t = a
        for lo, hi, name in inside(evs, starts, a, b):
            if lo > t:
                s = table.get(name, "?")
                out[s] = out.get(s, 0.0) + lo - t
            t = max(t, hi)
    return out


def _ask(only, notes):
    from byteps_tpu.common import tracing

    ask = getattr(tracing, "program_scopes", None)
    if ask is None:
        return None, 0.0
    t0 = time.monotonic()
    try:
        tables = ask(only=sorted(only))
    except Exception as e:                       # noqa: BLE001
        # a table that cannot be had prints no number, and says why
        notes["device_scopes.error"] = f"{type(e).__name__}: {e}"[:300]
        return None, time.monotonic() - t0
    return tables, time.monotonic() - t0


def read_serve(run, notes, kind, anchor, trace_anchor):
    """{scope: ms per step} of the ``kind`` steps, or None."""
    entries, r = program_span_ms.ring(), run.reduced
    if not entries or r is None:
        return None
    found = program_idle_ms.align(
        [e[1] * 1e9 for e in entries if e[0] == anchor],
        [s for n, s, _ in r.trace["spans"] if n == trace_anchor])
    if found is None or found[1] > program_idle_ms.MAX_SPREAD_NS:
        return None
    steps = device_steps(entries, found[0], r.w0, r.w1)
    # every kind the slice holds is noted once, with the first metric
    kinds = sorted({k for _, _, k, _ in steps
                    if k in ("decode", "chunk", "chunk_decode")} | {kind})
    only = set()
    for _, _, k, args in steps:
        if k in kinds:
            big, small = programs_of(k, args)
            only.update(big + small)
    if not only:
        return None
    tables, asked_s = _ask(only, notes)
    if tables is None:
        return None
    evs = leaves(r.first, r.w0, r.w1)
    result = None
    for k in kinds:
        tallies = tally_steps(steps, evs, tables, k)
        parts = ("",) if len(tallies) == 1 else (".chunk", ".decode")
        for part, t in zip(parts, tallies):
            if t.steps or t.left_out:
                notes[f"device_scopes.{k}{part}"] = t.note(asked_s)
        if k == kind and tallies[0].ok():
            n = tallies[0].steps * 1e6
            result = {s: v / n for s, v in tallies[0].by_scope.items()}
    return result


def read_span(run, notes, step_span):
    """{scope: ms per step} of the ``step_span`` steps, or None."""
    r = run.reduced
    if r is None:
        return None
    steps = [(s, s + d) for n, s, d in r.trace["spans"]
             if n == step_span and s >= r.w0 and s + d <= r.w1]
    if not steps:
        return None
    tables, asked_s = _ask({step_span}, notes)
    if not tables:
        return None
    table, clash = merged_table(tables.values())
    mixed = {}
    for tb in tables.values():
        for name, regions in tb.get("mixed", {}).items():
            mixed.setdefault(name, regions)
    evs = leaves(r.first, r.w0, r.w1)
    starts = [e[0] for e in evs]
    t = Tally()
    for a, b in steps:
        t.add(inside(evs, starts, a, b), table, {}, clash, mixed)
    note = notes[f"device_scopes.{step_span}"] = t.note(asked_s)
    if len(r.device_ids) > 1:
        note["idle_before_scope_ms_per_step"] = {
            s: v / (t.steps * 1e6) for s, v in sorted(
                idle_before_scope(evs, steps, table).items())}
    if not t.ok():
        return None
    return {s: v / (t.steps * 1e6) for s, v in t.by_scope.items()}


def read(run, observed, scopes=None, outside=None, step=None, anchor=None,
         trace_anchor=None, step_span=None):
    if (scopes is None) == (outside is None):
        raise ValueError("one of scopes and outside")
    notes = observed.setdefault("notes", {})
    cache = run.__dict__.setdefault(_CACHE, {})
    key = step or step_span
    if key not in cache:
        cache[key] = read_serve(run, notes, step, anchor, trace_anchor) \
            if step is not None else read_span(run, notes, step_span)
    by_scope = cache[key]
    if by_scope is None:
        return None
    if scopes is not None:
        return sum(v for s, v in by_scope.items() if matches(s, scopes))
    return sum(v for s, v in by_scope.items() if not matches(s, outside))
