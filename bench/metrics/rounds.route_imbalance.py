"""How unevenly a routing round leaves rows over the devices: the largest
device's rows over the mean (``device_rows`` of each ``DataplaneJoinResult``),
at the routing round where that ratio is largest, as the median over the
window's executor runs. 1 is an even spread."""

from statistics import median

from benchlib.engine import executor_runs


def read(run):
    worst = []
    for e in executor_runs(run):
        ratios = [max(rows) * len(rows) / sum(rows)
                  for rows in getattr(e, "device_rows", {}).values() if sum(rows)]
        if ratios:
            worst.append(max(ratios))
    return median(worst) if worst else None
