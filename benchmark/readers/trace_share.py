"""Device time of some kinds of operation over device busy time, in
percent, on the first device of the traced window. Kinds are the
categories of ``trace_reduce.categorise``."""


def read(run, observed, categories):
    r = run.reduced
    if r is None or r.busy0_s <= 0:
        return None
    return 100.0 * r.seconds(categories) / r.busy0_s
