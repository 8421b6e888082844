"""A statistic (mean, median, pNN) of a series the driver observed."""

from benchmark import metrics


def read(run, observed, series, stat):
    values = observed.get(series)
    return metrics.stat(values, stat) if values else None
