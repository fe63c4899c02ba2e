"""Median latency of every answered request of the window. Closed loop: from the
call to its answer; open loop: from the due time to the answer (host clock)."""

from benchlib.stats import percentile


def read(run):
    lat = [s.latency_ms for s in run.answered]
    return percentile(lat, 50) if lat else None
