"""End-to-end subgraph enumeration on the MPC join engine.

``enumerate_subgraphs`` runs the full pipeline — compile the pattern against
the graph, execute the Theorem 6.2 join on the chosen backend, then apply the
two row-level corrections the reduction owes (injectivity filter, automorphic
canonical dedup) — and returns every occurrence exactly once.

Backends mirror the engine's executors:

  * ``"simulator"`` — :func:`repro.mpc.engine.mpc_join`: shared-input Scatter,
    the 3-round distributed histogram, exact load metering;
  * ``"dataplane"`` — ``compile_plan`` + :class:`DataplaneExecutor` (stage-
    batched by default; pass ``executor=DataplaneExecutor(batch_stages=False)``
    for the per-stage schedule).

Passing ``session=`` (a :class:`repro.mpc.service.JoinSession`) routes the
join through the persistent service instead: repeated patterns over the same
graph hit the session's plan cache and warm executor
(``JoinSession.submit_pattern`` is the method-form of the same path).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..core.hypergraph import fractional_edge_cover
from ..core.planner import heavy_parameter
from ..core.taxonomy import compute_stats
from ..obs import span
from .compile import CompiledPattern, compile_pattern
from .graphs import Graph
from .patterns import Pattern, automorphisms, canonical_rows


@dataclass
class EnumerationResult:
    """Occurrences (each exactly once) + the engine run behind them.

    ``occurrences``: (count, k) int64, row = G-vertices bound to pattern
    vertices 0..k-1, canonicalized (lex-min automorphic image) and sorted.
    ``embeddings``: raw Join(Q) rows before injectivity/dedup — the
    homomorphism count the engine actually materialized.
    ``host_us``: host time of the graph layer around the join — the pattern's
    compilation and the post-processing (the ``graph.compile_pattern`` and
    ``graph.postprocess`` spans)."""

    pattern: Pattern
    backend: str
    occurrences: np.ndarray
    count: int
    embeddings: int
    compiled: CompiledPattern
    engine: object
    host_us: float = 0.0


def postprocess_rows(compiled: CompiledPattern, rows: np.ndarray) -> np.ndarray:
    """Join rows → exactly-once occurrence set.

    Injectivity: drop rows collapsing two pattern vertices (skipped when the
    orientation already separates every pair).  Dedup: canonicalize through
    Aut(P) and unique — when the orientation is complete this is a no-op on
    the row *set* but still normalizes each row to its canonical image (the
    oriented row order follows the degree order, not the value order)."""
    k = compiled.pattern.n_vertices
    rows = np.asarray(rows, dtype=np.int64).reshape(-1, k)
    if rows.shape[0] and compiled.orientation.needs_injectivity:
        keep = np.ones(rows.shape[0], dtype=bool)
        for i in range(k):
            for j in range(i + 1, k):
                keep &= rows[:, i] != rows[:, j]
        rows = rows[keep]
    canon = canonical_rows(rows, automorphisms(compiled.pattern))
    if canon.shape[0] == 0:
        return canon.reshape(0, k)
    return np.unique(canon, axis=0)


def enumerate_subgraphs(
    graph: Graph,
    pattern: Pattern,
    p: int = 8,
    backend: str = "simulator",
    lam: Optional[int] = None,
    orientation: str = "degree",
    executor=None,
    seed: int = 0,
    fuse_semijoin: bool = False,
    session=None,
) -> EnumerationResult:
    """Enumerate every occurrence of ``pattern`` in ``graph`` via the join.

    Args:
        graph: the data graph (its edge set becomes the shared physical table).
        pattern: the pattern to enumerate (≤ 8 vertices).
        p: the plan's machine count (the dataplane maps it onto however many
            devices the mesh has).
        backend: ``"simulator"`` or ``"dataplane"`` (ignored when ``session``
            is given — the session's backend is used).
        lam: heavy parameter; defaults to the paper's λ = Θ(p^{1/(2ρ)}).
        orientation: vertex order behind the oriented table (``"degree"``/``"id"``).
        executor: inject a configured :class:`DataplaneExecutor` (one-shot
            dataplane path only).
        seed: shared-randomness seed (one-shot simulator path only).
        fuse_semijoin: enable the beyond-paper semi-join fusion rewrite.
        session: a :class:`repro.mpc.service.JoinSession` to submit through —
            the persistent-service path with cross-query plan/compile reuse.

    Returns:
        An :class:`EnumerationResult`: exactly-once ``occurrences`` plus the
        engine run behind them.
    """
    with span("graph.enumerate", pattern=pattern.name):
        with span("graph.compile_pattern") as compile_span:
            compiled = compile_pattern(graph, pattern, orientation)
        q = compiled.query
        if session is not None:
            p, backend = session.p, session.backend    # the session's plans rule
        if lam is None:
            rho_val = float(fractional_edge_cover(q.hypergraph)[0])
            lam = heavy_parameter(p, rho_val)

        if session is not None:
            res = session.submit(q, lam=lam, fuse_semijoin=fuse_semijoin).result
        elif backend == "simulator":
            from ..mpc.engine import mpc_join

            res = mpc_join(q, p=p, seed=seed, lam=lam, fuse_semijoin=fuse_semijoin)
        elif backend == "dataplane":
            from ..mpc.executors import DataplaneExecutor
            from ..mpc.program import compile_plan, fuse_semijoin_pass

            stats = compute_stats(q, lam)
            program = compile_plan(q, stats, p)
            if fuse_semijoin:
                program = fuse_semijoin_pass(program)
            ex = executor if executor is not None else DataplaneExecutor()
            res = ex.run(program)
        else:
            raise ValueError(f"unknown backend {backend!r}")

        with span("graph.postprocess") as post_span:
            occ = postprocess_rows(compiled, res.rows)
            post_span.set(rows=int(occ.shape[0]))
        return EnumerationResult(
            pattern=pattern,
            backend=backend,
            occurrences=occ,
            count=int(occ.shape[0]),
            embeddings=int(res.count),
            compiled=compiled,
            engine=res,
            host_us=compile_span.us + post_span.us,
        )
