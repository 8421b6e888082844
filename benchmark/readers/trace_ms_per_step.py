"""Device milliseconds of some kinds of operation per step, on the first
device: their summed durations over the traced window, divided by the
number of ``step_span`` host spans that lie wholly inside it. ``None`` where
the window holds no step, or no operation of those kinds: a time that was
not spent is left out, not reported as 0."""


def read(run, observed, step_span, categories):
    r = run.reduced
    if r is None:
        return None
    steps, seconds = r.count(step_span), r.seconds(categories)
    if steps == 0 or seconds <= 0:
        return None
    return 1e3 * seconds / steps
