"""The process's first submit of the cell's query, call to answer (host clock):
plan, executables from the compile cache, the counting pass and the emit."""


def read(run):
    return run.cold.latency_ms
