"""90th percentile of the time answered requests spent in JoinSession's
submission queue (``SessionResult.queue_us``); asynchronous submits only."""

from benchlib.stats import percentile


def read(run):
    q = [r.queue_us / 1e3 for s in run.answered if s.due is not None for r in s.sessions]
    return percentile(q, 90) if q else None
