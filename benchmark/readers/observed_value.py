"""A number the driver or the harness observed, as it is (a count)."""


def read(run, observed, key):
    return observed.get(key)
