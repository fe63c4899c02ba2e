"""Device time of the collectives (the route rounds' all_to_all and the like)
per answered query, in ms: ``TraceSummary.collective_s``, the collective ops'
time averaged over the chips, over the answered queries of the traced window."""


def read(run):
    n = len(run.answered)
    if run.trace is None or not n:
        return None
    return run.trace.collective_s * 1e3 / n
