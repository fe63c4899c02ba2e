"""The executor runs behind a window's answers, for the readers of counters that
exist once per run (``bench/metrics/executor.*.py``)."""


def executor_runs(run) -> list:
    """The ``DataplaneJoinResult`` of every executor run of the window, once
    each: coalesced requests share one run and each of their results carries
    its counters (the key is ``RunRecord.batches``')."""
    seen = {}
    for r in run.session_results():
        eng = r.result
        key = (tuple(sorted(eng.phase_us.items())), tuple(sorted(eng.round_us.items())))
        seen.setdefault(key, eng)
    return list(seen.values())
