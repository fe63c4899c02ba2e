"""Host wall time per answered query of the routing rounds: residual routing,
the size broadcast, the grid route and the HyperCube share route, with their
counting passes (``round_us``)."""

ROUTE = ("step1", "step3-sizes", "step3-route", "hc-route")


def read(run):
    n = len(run.answered)
    if not n:
        return None
    total = sum(v for b in run.batches() for k, v in b["round_us"].items()
                if k.split("/")[0] in ROUTE)
    return total / n / 1e3
