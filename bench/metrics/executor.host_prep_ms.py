"""Host time the DataplaneExecutor spends building dispatches (stacking and
staging host blocks, ``phase_us["host_prep"]``), per answered query."""


def read(run):
    n = len(run.answered)
    if not n:
        return None
    return sum(b["phase_us"].get("host_prep", 0.0) for b in run.batches()) / n / 1e3
