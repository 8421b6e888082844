"""How many of the program's spans are of some kinds, in percent of those of
other kinds, from the always-on ring (``program_span_ms.ring``) cut to the
measured window (a span counts when it lies wholly inside it).

A span whose first argument is a string says its kind there and is known by
two names: its own, and ``name:kind``. The spans counted are those one of
whose names is in ``spans``; they are divided by the number of spans named
in ``of``. ``None`` where the program keeps no ring or the window holds no
span named in ``of``.

``observed["notes"]`` gets what the share stands on: ``spans_in_window``
(count and summed seconds of each span named in ``of``, under its longer
name, and the window's seconds) and ``ring`` (its entries, and by how many
seconds its oldest entry starts before the window opened: negative where
the ring no longer holds the window it is read over).
"""

from benchmark.readers import program_span_ms


def _kind(e):
    return f"{e[0]}:{e[5][0]}" if e[5] and isinstance(e[5][0], str) else e[0]


def share_pct(entries, t0, t1, spans, of, notes=None):
    """The arithmetic of ``read`` on a list of ring entries."""
    spans, of = set(spans), set(of)
    inside = [e for e in entries
              if e[0] in of and e[1] >= t0 and e[1] + e[2] <= t1]
    if notes is not None:
        seen = {}
        for e in inside:
            n, s = seen.get(_kind(e), (0, 0.0))
            seen[_kind(e)] = (n + 1, s + e[2])
        notes["spans_in_window"] = dict(seen, window_s=t1 - t0)
        notes["ring"] = {"entries": len(entries),
                         "oldest_before_window_s": t0 - entries[0][1]}
    if not inside:
        return None
    mine = sum(1 for e in inside if e[0] in spans or _kind(e) in spans)
    return 100.0 * mine / len(inside)


def read(run, observed, spans, of):
    entries = program_span_ms.ring()
    w = program_span_ms.window(run, observed)
    if not entries or w is None:
        return None
    return share_pct(entries, w[0], w[1], spans, of,
                     observed.setdefault("notes", {}))
