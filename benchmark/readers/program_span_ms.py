"""Milliseconds the program spent in some of its own spans, from the
always-on ring of ``byteps_tpu.common.tracing`` (entries ``(name, start_s,
dur_s, span_id, parent_id, args)``, seconds on ``time.monotonic()``, the
clock ``run.t_process`` is read from), cut to the measured window.

Everywhere a span counts when it lies wholly inside the window. Without
``per_request``: the spans named in ``spans``, summed and divided by the
number of ``per_span`` spans (their own mean when ``per_span`` is absent).
With ``per_request`` (the span names of a request's phases, in order): a
request counts when the first of those phases starts inside the window and
the last ends inside it, and the result is the spans named in ``spans`` of
those requests, summed, over their number. Spans are matched to requests
by ``args[0]``, and only untagged ones count (a phase cut short by a
preemption, or repeated after one, carries a tag). A request still waiting
when the window closes is left out: in a traced run the profiler's
``stop_trace`` holds the host for many seconds right there, and a mean
that took it in would measure the profiler. ``None`` where the program
keeps no ring or it holds no such span.
"""


def ring():
    """The program's spans, oldest first, or None where it keeps none."""
    from byteps_tpu.common import tracing

    spans = getattr(tracing.get_tracer(), "spans", None)
    return spans() if spans is not None else None


def window(run, observed):
    """The measured window on the ring's clock, or None."""
    if run.setup_s is None or not observed.get("elapsed_s"):
        return None
    t0 = run.t_process + run.setup_s
    return t0, t0 + observed["elapsed_s"]


def mean_ms(entries, t0, t1, spans, per_span=None, per_request=None):
    """The arithmetic of ``read`` on a list of ring entries."""
    spans = set(spans)
    if per_request is not None:
        first, last = per_request[0], per_request[-1]
        phases = [e for e in entries if e[5] and len(e[5]) == 1
                  and (e[0] in spans or e[0] in (first, last))]
        ends = {e[5][0]: e[1] + e[2] for e in phases if e[0] == last}
        rids = {e[5][0] for e in phases if e[0] == first and e[1] >= t0
                and ends.get(e[5][0], t1 + 1) <= t1}
        mine = [e[2] for e in phases if e[0] in spans and e[5][0] in rids]
        n = len(rids)
    else:
        inside = [e for e in entries if e[1] >= t0 and e[1] + e[2] <= t1]
        mine = [e[2] for e in inside if e[0] in spans]
        n = len(mine) if per_span is None else \
            sum(1 for e in inside if e[0] == per_span)
    return 1e3 * sum(mine) / n if mine and n else None


def read(run, observed, spans, per_span=None, per_request=None):
    entries, w = ring(), window(run, observed)
    if not entries or w is None:
        return None
    return mean_ms(entries, w[0], w[1], spans, per_span, per_request)
