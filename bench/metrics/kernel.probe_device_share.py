"""Share of the device's busy time spent in the merge-join probe kernels
(``merge_join_counts`` and the pair expansion ``merge_join_pairs``), from the op
events of the trace, which name each kernel by its jitted wrapper."""

PROBE = r"merge_join_(counts|pairs)"


def read(run):
    if run.trace is None:
        return None
    spent = run.trace.op_time_s(PROBE)
    return 100.0 * spent / run.trace.busy_s if spent > 0 else None
