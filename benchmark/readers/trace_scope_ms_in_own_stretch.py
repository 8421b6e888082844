"""``trace_scope_ms_in_steps`` for the steps of a kind whose NEIGHBOURS ARE
OTHER PROGRAMS: device milliseconds of some regions of the program's source
inside such steps, per step, with the neighbours' operations cut off.

A device step's interval is the host's two stamps
(``trace_ms_in_device_steps``: the host wakes a little after the device
finishes, so a stamp lies inside the next step's first operations). Between
steps of one kind the lag cancels. A final chunk alone (``chunk``) sits
between two decode steps: the operations the next decode step ran before the
host woke are in the chunk's interval and in no table of the chunk's
programs, and ``trace_scope_ms_in_steps`` prints no number once they pass
its twentieth of the busy time — at a lag of 1–3 ms against a 55-ms chunk,
in one traced run of some.

The device runs what it is handed in issue order, one program at a time. So
an operation that only ANOTHER program of the slice names (in a table of the
programs the slice's steps ran, in none of this step's) marks the edge of
this step's own programs: the step is cut to its **own stretch**, the
longest run of its events (by their summed time) that holds no such
operation, and read as ``trace_scope_ms_in_steps`` reads a step — the same
tables, the same ``matches``, the same ``MAX_UNMATCHED`` on what is left
(now: names NO table of the slice has). A name both programs have stays in
the stretch up to the first that only the neighbour has; the note bounds it.

``observed["notes"]["device_scopes_own.<kind>"]`` holds the tally's note
(as ``device_scopes.<kind>``) and ``cut_ms``: the time per step of the
events cut off, ``cut_steps``: the steps that lost any.

Parameters and ``None``s as ``trace_scope_ms_in_steps`` with ``step``;
``chunk_decode`` (two large programs in one step) is not this reader's.
"""

from benchmark.readers import program_idle_ms, program_span_ms
from benchmark.readers import trace_scope_ms_in_steps as sc

_CACHE = "_own_stretch_reading"      # on the Run: one reading a kind a run


def own_stretch(evs, own, foreign):
    """The longest run of ``evs`` (by summed time) without an event whose
    name is in ``foreign`` and not in ``own``; ``(run, ns cut off)``."""
    best, best_ns, cur, cur_ns = [], 0.0, [], 0.0
    for e in evs:
        if e[2] in foreign and e[2] not in own:
            cur, cur_ns = [], 0.0
            continue
        cur.append(e)
        cur_ns += e[1] - e[0]
        if cur_ns > best_ns:
            best, best_ns = cur, cur_ns
    return best, sum(b - a for a, b, _ in evs) - best_ns


def read_steps(run, notes, kind, anchor, trace_anchor):
    """{scope: ms per step} of the ``kind`` steps' own stretches, or None."""
    entries, r = program_span_ms.ring(), run.reduced
    if not entries or r is None:
        return None
    found = program_idle_ms.align(
        [e[1] * 1e9 for e in entries if e[0] == anchor],
        [s for n, s, _ in r.trace["spans"] if n == trace_anchor])
    if found is None or found[1] > program_idle_ms.MAX_SPREAD_NS:
        return None
    steps = sc.device_steps(entries, found[0], r.w0, r.w1)
    if not any(k == kind for _, _, k, _ in steps):
        return None
    # the programs the slice ran, and those of the steps next to a step of
    # the kind where the window's edge leaves that neighbour out
    every = sc.device_steps(entries, found[0], float("-inf"), float("inf"))
    near = [n for i, s in enumerate(every) if s[2] == kind and s in steps
            for n in every[max(i - 1, 0):i + 2]]
    only = set()
    for _, _, k, args in steps + near:
        big, small = sc.programs_of(k, args)
        only.update(big + small)
    tables, asked_s = sc._ask(only, notes)
    if not tables:
        return None
    evs = sc.leaves(r.first, r.w0, r.w1)
    starts = [e[0] for e in evs]
    t, cut_ns, cut_steps = sc.Tally(), 0.0, 0
    for a, b, k, args in steps:
        if k != kind:
            continue
        big, small = sc.programs_of(k, args)
        if len(big) != 1 or tables.get(big[0]) is None:
            t.left_out += 1
            continue
        main = tables[big[0]]
        mine = [tb for key, tb in tables.items()
                if key.split("[")[0] in small]
        small_t, clash = sc.merged_table(mine)
        own = set(main["scopes"]) | set(small_t)
        foreign = set()
        for key, tb in tables.items():
            if tb is not main and not any(tb is m for m in mine):
                foreign.update(tb["scopes"])
        stretch, cut = own_stretch(sc.inside(evs, starts, a, b), own,
                                   foreign)
        cut_ns += cut
        cut_steps += cut > 0
        t.add(stretch, main["scopes"], small_t, clash,
              main.get("mixed", ()))
    if not (t.steps or t.left_out):
        return None
    note = notes[f"device_scopes_own.{kind}"] = t.note(asked_s)
    note["cut_ms"] = cut_ns / (max(t.steps, 1) * 1e6)
    note["cut_steps"] = cut_steps
    if not t.ok():
        return None
    return {s: v / (t.steps * 1e6) for s, v in t.by_scope.items()}


def read(run, observed, scopes=None, outside=None, step=None, anchor=None,
         trace_anchor=None):
    if (scopes is None) == (outside is None):
        raise ValueError("one of scopes and outside")
    if step is None or step == "chunk_decode":
        raise ValueError("a step of one large program")
    notes = observed.setdefault("notes", {})
    cache = run.__dict__.setdefault(_CACHE, {})
    if step not in cache:
        cache[step] = read_steps(run, notes, step, anchor, trace_anchor)
    by_scope = cache[step]
    if by_scope is None:
        return None
    if scopes is not None:
        return sum(v for s, v in by_scope.items() if sc.matches(s, scopes))
    return sum(v for s, v in by_scope.items()
               if not sc.matches(s, outside))
