#!/usr/bin/env python3
"""Smoke run of the join engine's main path on a TPU.

Drives ``JoinSession(backend="dataplane")`` -> ``DataplaneExecutor`` -> the
shard_map route/join steps -> the Pallas kernels through the entry points a
user calls, at ``JoinSession(p=16)``, and checks every answer against an oracle
that does not use the engine:

  a. triangle counting (``submit_pattern``) on a Zipf graph of 2^19 edges over
     2^18 vertices (skew 0.9), submitted cold and then warm; the count must
     equal a scipy.sparse count over the degree-oriented adjacency, and the
     warm submit must compile nothing and retry nothing. 2^20 edges, the
     scale of SNAP's com-DBLP and com-Amazon, is halved once: on one chip
     every LocalJoin level probes whole device blocks (when the size was
     chosen the probe swept all tile pairs, O(N·M)), and compiling the 2^20
     executables for a v5e takes ~450 s on an 8-core host (mostly XLA's
     sorts of multi-million-row blocks);
  b. binary queries with planted hubs (``hub_triangle_query``, and
     ``hub_star_query`` whose hub stage is a pure cartesian-product grid) and
     an acyclic 3-ary star (``general_query("star3")``); rows must be
     byte-equal to ``reference_join``;
  c. a few ``submit_async`` queries of one shape over distinct data; results
     must equal serial submits, with no failed or degraded request.

Usage (one process; it holds the chip for its whole run):

    python3 chip_smoke.py              # one chip: phases a, b and c
    python3 chip_smoke.py --chips 4    # phase a only, on a mesh over four chips

It exits non-zero, and prints no result line, when JAX finds no TPU, on any
mismatch and on any failure. A passing run's last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Compiled executables go to JAX's persistent cache (``JAX_COMPILATION_CACHE_DIR``
if set, else ``.jax_cache/`` at the repository root).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SEED = 0
P_VIRTUAL = 16
N_VERTICES = 2**18
N_EDGES = 2**19
SKEW = 0.9


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def oracle_triangles(edges: np.ndarray, n_vertices: int) -> int:
    """Triangles of the simple undirected graph on ``edges``: orient every edge
    from the lower to the higher (degree, id) rank, then sum((L @ L) * L)."""
    import scipy.sparse as sp

    e = np.sort(np.asarray(edges, np.int64), axis=1)
    e = np.unique(e[e[:, 0] != e[:, 1]], axis=0)
    deg = np.bincount(e.ravel(), minlength=n_vertices)
    rank = np.empty(n_vertices, np.int64)
    rank[np.lexsort((np.arange(n_vertices), deg))] = np.arange(n_vertices)
    lo = np.where(rank[e[:, 0]] < rank[e[:, 1]], e[:, 0], e[:, 1])
    hi = np.where(rank[e[:, 0]] < rank[e[:, 1]], e[:, 1], e[:, 0])
    ones = np.ones(len(e), np.int64)
    lmat = sp.csr_matrix((ones, (lo, hi)), shape=(n_vertices, n_vertices))
    return int((lmat @ lmat).multiply(lmat).sum())


def sorted_rows(rows) -> np.ndarray:
    rows = np.asarray(rows, np.int64)
    return rows[np.lexsort(rows.T[::-1])] if rows.size else rows


def rows_equal(got, want) -> bool:
    g, w = sorted_rows(got), sorted_rows(want)
    return g.shape == w.shape and g.tobytes() == w.tobytes()


def kernel_executables(cache) -> int:
    """How many cached executables contain a Pallas TPU kernel."""
    return sum("tpu_custom_call" in exe.as_text() for exe in cache.values())


def phase_triangles(mesh, n_vertices: int, n_edges: int) -> None:
    from repro.graph import triangle, zipf_graph
    from repro.mpc.executors import EXECUTABLE_CACHE, DataplaneExecutor
    from repro.mpc.service import JoinSession

    t0 = time.perf_counter()
    graph = zipf_graph(np.random.default_rng(SEED), n_vertices, n_edges, skew=SKEW)
    say("a.graph", f"vertices={graph.n_vertices} edges={graph.n_edges} skew={SKEW} "
        f"seed={SEED} build_s={time.perf_counter() - t0:.3f}")
    t0 = time.perf_counter()
    want = oracle_triangles(graph.edges, graph.n_vertices)
    say("a.oracle", f"triangles={want} scipy_s={time.perf_counter() - t0:.3f}")

    session = JoinSession(p=P_VIRTUAL, executor=DataplaneExecutor(mesh=mesh))
    try:
        misses0 = EXECUTABLE_CACHE.misses
        for label in ("cold", "warm"):
            t0 = time.perf_counter()
            res = session.submit_pattern(triangle(), graph)
            wall_ms = (time.perf_counter() - t0) * 1e3
            eng = res.engine
            window = session.stats.cold_us if label == "cold" else session.stats.warm_us
            say(f"a.{label}", f"triangles={res.count} embeddings={res.embeddings} "
                f"session_ms={window[-1] / 1e3:.3f} wall_ms={wall_ms:.3f} "
                f"jit_misses={eng.jit_cache_misses} retries={len(eng.retry_log)} "
                f"dispatches={eng.dispatches}")
            say(f"a.{label}.phase_ms", {k: round(v / 1e3, 3) for k, v in eng.phase_us.items()})
            say(f"a.{label}.round_ms", {k: round(v / 1e3, 3) for k, v in eng.round_us.items()})
            say(f"a.{label}.device_rows", eng.device_rows)
            check(res.count == want, f"{label} triangle count {res.count} != oracle {want}")
            if label == "warm":
                check(eng.jit_cache_misses == 0,
                      f"warm submit compiled {eng.jit_cache_misses} executables")
                check(not eng.retry_log, f"warm submit retried: {eng.retry_log}")
        for rnd, rows in eng.device_rows.items():
            check(len(rows) == mesh.size and all(r > 0 for r in rows),
                  f"round {rnd} leaves a device without rows: {rows}")
        with_kernels = kernel_executables(EXECUTABLE_CACHE)
        say("a.executables", f"compiled={EXECUTABLE_CACHE.misses - misses0} "
            f"with_kernels={with_kernels}")
        check(with_kernels > 0, "no compiled executable contains a Pallas kernel")
    finally:
        session.close()


def phase_queries(mesh) -> None:
    from repro.core.query import general_query, hub_star_query, hub_triangle_query, reference_join
    from repro.mpc.executors import DataplaneExecutor
    from repro.core.taxonomy import compute_stats
    from repro.mpc.program import compile_plan
    from repro.mpc.service import JoinSession

    cases = [
        ("triangle-hub", hub_triangle_query(n=300, hub_n=80, dom_size=40, hub=10_000), 16),
        ("star-hub-cp", hub_star_query(n=90, hub_n=40, dom_size=25), 10),
        ("star3", general_query("star3", n=240, dom_size=20, skew=0.8, seed=11), 8),
    ]
    session = JoinSession(p=P_VIRTUAL, executor=DataplaneExecutor(mesh=mesh))
    try:
        iso = 0
        for name, query, lam in cases:
            program = compile_plan(query, compute_stats(query, lam), P_VIRTUAL)
            n_iso = sum(1 for st in program.stages if getattr(st.plan, "isolated", None))
            iso += n_iso
            want = reference_join(query).data
            res = session.submit(query, lam=lam)
            say(f"b.{name}", f"rows={res.count} oracle_rows={len(want)} "
                f"isolated_stages={n_iso} session_ms={res.total_us / 1e3:.3f} "
                f"jit_misses={res.jit_cache_misses} retries={res.retries}")
            check(rows_equal(res.rows, want), f"{name}: rows differ from reference_join")
        check(iso > 0, "no query of phase b has an isolated (cartesian-product) stage")
    finally:
        session.close()


def phase_async(mesh) -> None:
    from repro.core.query import hub_triangle_query
    from repro.mpc.executors import DataplaneExecutor
    from repro.mpc.service import JoinSession

    queries = [
        hub_triangle_query(n=300, hub_n=80, dom_size=40, hub=10_000, seed=seed)
        for seed in (21, 22, 23, 24)
    ]
    session = JoinSession(p=P_VIRTUAL, executor=DataplaneExecutor(mesh=mesh))
    try:
        futures = [session.submit_async(q, lam=16) for q in queries]
        got = [f.result(timeout=600) for f in futures]
        serial = [session.submit(q, lam=16) for q in queries]
        for i, (a, s) in enumerate(zip(got, serial)):
            check(rows_equal(a.rows, s.rows), f"async query {i}: rows differ from serial submit")
        st = session.stats
        say("c.async", f"queries={len(queries)} rows={[a.count for a in got]} "
            f"e2e_ms={[round(a.e2e_us / 1e3, 3) for a in got]} "
            f"coalesced_batches={st.coalesced_batches} failed={st.failed} "
            f"degraded_fallbacks={st.degraded_fallbacks}")
        check(st.failed == 0 and st.degraded_fallbacks == 0,
              f"failed={st.failed} degraded_fallbacks={st.degraded_fallbacks}")
    finally:
        session.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-c on one chip; 4: phase a on a mesh over four chips")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repository's src/repro is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked for, {len(devices)} found",
              file=sys.stderr)
        return 1

    from repro.mpc.executors import enable_compile_cache

    say("compile_cache", enable_compile_cache())
    used = devices[: args.chips]
    mesh = Mesh(np.array(used), ("join",))
    say("device", f"platform={platform} kind={used[0].device_kind} count={len(used)}")
    try:
        t0 = time.perf_counter()
        phase_triangles(mesh, N_VERTICES, N_EDGES)
        say("a.seconds", f"{time.perf_counter() - t0:.3f}")
        if args.chips == 1:
            t0 = time.perf_counter()
            phase_queries(mesh)
            say("b.seconds", f"{time.perf_counter() - t0:.3f}")
            t0 = time.perf_counter()
            phase_async(mesh)
            say("c.seconds", f"{time.perf_counter() - t0:.3f}")
        for d in used:
            stats = d.memory_stats() or {}
            say(f"peak_bytes_in_use.{d.id}", stats.get("peak_bytes_in_use", "not reported"))
    except Exception as e:  # any failure of any phase fails the smoke
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": used[0].device_kind, "count": len(used),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
