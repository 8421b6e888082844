"""Mean of what one of the program's registry histograms observed between
the window's two ends (count and sum read at each)."""

from benchmark import metrics


def read(run, observed, histogram):
    h = observed.get("histograms")
    if not h or histogram not in h["start"]:
        return None
    return metrics.histogram_window_mean(h["end"][histogram],
                                         h["start"][histogram])
