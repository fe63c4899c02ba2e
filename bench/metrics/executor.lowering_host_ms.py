"""Host time per answered query of the executor's lowering rules outside their
op rounds (blocking, unblocking, key packing, host filters), plus the assembly
of rows and counts at the end of a run: ``executor.op`` spans less their
``executor.round`` children, plus ``executor.assemble``
(``DataplaneJoinResult.lowering_us``). Each executor run counts once."""

from benchlib.engine import executor_runs


def read(run):
    n = len(run.answered)
    runs = executor_runs(run)
    if not n or not runs or not all(hasattr(e, "lowering_us") for e in runs):
        return None
    return sum(e.lowering_us for e in runs) / n / 1e3
