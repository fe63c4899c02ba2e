"""Bytes the route rounds hand to ``all_to_all`` per answered query, in MB: the
send buffers of every launch, summed over the chips (``a2a_bytes`` of each
``DataplaneJoinResult``, counted from shapes). Each executor run counts once."""

from benchlib.engine import executor_runs


def read(run):
    n = len(run.answered)
    runs = executor_runs(run)
    if not n or not runs or not all(hasattr(e, "a2a_bytes") for e in runs):
        return None
    return sum(e.a2a_bytes for e in runs) / n / 1e6
