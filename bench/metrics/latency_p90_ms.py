"""90th percentile latency of every answered request of the window, timed from
the due time (host clock)."""

from benchlib.stats import percentile


def read(run):
    lat = [s.latency_ms for s in run.answered]
    return percentile(lat, 90) if lat else None
