"""Device milliseconds of some kinds of operation per step, on the first
device: their summed durations over the traced window, divided by the
number of ``step_span`` host spans that lie wholly inside it."""


def read(run, observed, step_span, categories):
    r = run.reduced
    if r is None:
        return None
    steps = r.count(step_span)
    if steps == 0:
        return None
    return 1e3 * r.seconds(categories) / steps
