"""Host wall time per answered query of the op rounds that join: LocalJoin and
CellJoin (``output``), the semijoins of the paper's step 2 and the Yannakakis
sweeps, with their counting passes (``round_us``, each round ending in its
blocking overflow read)."""

JOIN = ("output", "step2-unary", "step2-bx", "step2-by", "step2-fused", "yan-up", "yan-down")


def read(run):
    n = len(run.answered)
    if not n:
        return None
    total = sum(v for b in run.batches() for k, v in b["round_us"].items()
                if k.split("/")[0] in JOIN)
    return total / n / 1e3
