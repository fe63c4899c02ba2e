"""Host<->device bytes per answered query, in MB: the host operands every launch
ships and the outputs every sync pulls back (``h2d_bytes + d2h_bytes`` of each
``DataplaneJoinResult``, counted from shapes). Each executor run counts once."""

from benchlib.engine import executor_runs


def read(run):
    n = len(run.answered)
    runs = executor_runs(run)
    if not n or not runs or not all(hasattr(e, "h2d_bytes") for e in runs):
        return None
    return sum(e.h2d_bytes + e.d2h_bytes for e in runs) / n / 1e6
