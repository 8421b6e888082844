"""A count the driver observed inside the window, per second of it."""


def read(run, observed, count):
    if observed.get(count) is None or not observed.get("elapsed_s"):
        return None
    return observed[count] / observed["elapsed_s"]
