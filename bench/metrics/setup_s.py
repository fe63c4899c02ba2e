"""Process start until the window opens (host clock): data, session, cold query,
warm-up, and in a run with an empty compile cache, compilation."""


def read(run):
    return run.setup_s
