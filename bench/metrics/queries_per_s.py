"""Queries answered in the window over the window's length (host clock)."""


def read(run):
    return len(run.answered) / run.window_s
