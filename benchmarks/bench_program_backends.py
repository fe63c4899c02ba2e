"""One compiled program, two backends: simulator-metered load vs dataplane wall-clock.

The round-program IR makes the comparison apples-to-apples: `compile_plan`
fixes the stages and routes once; the SimulatorExecutor reports the exact MPC
load (the paper's cost metric), the DataplaneExecutor executes the same stages
as stage-batched shard_map collectives (one fused dispatch per geometry
bucket) and reports wall-clock: cold (first run, pays AOT compilation of one
executable per bucket) and warm (best of 3 repeat runs — the learned-caps
steady state).  `dataplane_dispatches` / `dataplane_buckets` /
`dataplane_jit_misses` / `ir_signatures` expose the scheduler: compile count
tracks geometry buckets, never stage count.  The case list deliberately
spans the per-op lowering surface: skew-free binary, light-subquery triangle,
and the CP-grid-heavy shapes (isolated attributes, 2-D isolated grids,
disconnected light subqueries) the dataplane formerly rejected.

Every run also appends a machine-readable snapshot to
``BENCH_program_backends.json`` at the repo root so the perf trajectory
accumulates across PRs.

Run standalone with 8 fake host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python -m benchmarks.run --only program_backends

(inside the harness the device count is whatever the process booted with;
a 1-device mesh is valid, just not a communication benchmark)."""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from repro.core.query import (
    JoinQuery,
    Relation,
    disconnected_query,
    hub_star_query,
    hub_triangle_query,
    random_query,
    reference_join,
)
from repro.core.taxonomy import compute_stats
from repro.mpc.executors import DataplaneExecutor, SimulatorExecutor
from repro.mpc.program import compile_plan

import os

# Overridable so CI can accumulate same-machine snapshots in a scratch file
# (base ref then head ref) instead of appending to the committed history.
RESULTS_PATH = Path(
    os.environ.get(
        "BENCH_RESULTS_PATH",
        Path(__file__).resolve().parents[1] / "BENCH_program_backends.json",
    )
)


def binary_join(n_a: int, n_b: int, dom: int, seed: int = 0) -> JoinQuery:
    rng = np.random.default_rng(seed)
    a = np.unique(rng.integers(0, dom, size=(n_a, 2)), axis=0)
    b = np.unique(rng.integers(0, dom, size=(n_b, 2)), axis=0)
    return JoinQuery.make(
        [Relation.make(("A", "B"), a), Relation.make(("B", "C"), b)]
    )


def cases():
    return [
        ("binary", binary_join(1200, 1500, 60), 2),
        ("triangle-hub", hub_triangle_query(n=300, hub_n=80, dom_size=40, hub=10_000), 16),
        ("star-hub-cp", hub_star_query(n=90, hub_n=40, dom_size=25), 10),
        ("cycle4-2d-cp", random_query(
            np.random.default_rng(7), "cycle", 4, tuples_per_rel=120,
            dom_size=10, skew=2.5,
        ), 24),
        ("disconnected-cp", disconnected_query(120, dom_size=14, skew=1.8), 8),
    ]


def run(report):
    import jax

    p_sim = 8
    n_dev = len(jax.devices())
    records = []
    for name, q, lam in cases():
        stats = compute_stats(q, lam)
        t0 = time.time()
        program = compile_plan(q, stats, p_sim)
        compile_us = (time.time() - t0) * 1e6
        n_iso = sum(1 for st in program.stages if st.plan.isolated)
        oracle_n = len(reference_join(q))
        report(
            f"program_backends/{name}/compile", compile_us,
            f"stages={len(program.stages)} iso_stages={n_iso} emits={len(program.emit)}",
        )

        t0 = time.time()
        sim_res = SimulatorExecutor(p=p_sim).run(program, materialize=False)
        sim_us = (time.time() - t0) * 1e6
        assert sim_res.count == oracle_n, (sim_res.count, oracle_n)
        report(
            f"program_backends/{name}/simulator", sim_us,
            f"p={p_sim} load={sim_res.sim.parallel_total_load} out={sim_res.count}",
        )

        ex = DataplaneExecutor()
        t0 = time.time()
        dp_res = ex.run(program)           # first run pays jit compilation
        cold_us = (time.time() - t0) * 1e6
        assert dp_res.count == oracle_n, (name, dp_res.count, oracle_n)
        warm_samples = []
        for _ in range(3):                 # best-of-3 damps scheduler noise
            t0 = time.time()
            warm_res = ex.run(program, materialize=False)
            warm_samples.append((time.time() - t0) * 1e6)
        warm_us = min(warm_samples)
        n_buckets = sum(len(v) for v in dp_res.bucket_stage_counts.values())
        report(
            f"program_backends/{name}/dataplane", warm_us,
            f"devices={n_dev} cold_us={cold_us:.0f} out={dp_res.count} "
            f"retries={dp_res.retries} dispatches={dp_res.dispatches} "
            f"buckets={n_buckets} jit_misses={dp_res.jit_cache_misses}",
        )
        records.append(
            {
                "case": name,
                "lam": lam,
                "stages": len(program.stages),
                "iso_stages": n_iso,
                "count": int(dp_res.count),
                "compile_us": round(compile_us, 1),
                "sim_load": int(sim_res.sim.parallel_total_load),
                "sim_us": round(sim_us, 1),
                "dataplane_cold_us": round(cold_us, 1),
                "dataplane_warm_us": round(warm_us, 1),
                "dataplane_retries": int(dp_res.retries),
                "dataplane_dispatches": int(dp_res.dispatches),
                "dataplane_buckets": int(n_buckets),
                "dataplane_jit_misses": int(dp_res.jit_cache_misses),
                "dataplane_warm_retries": int(warm_res.retries),
                "ir_signatures": len(program.bucket_histogram()),
            }
        )

    snapshot = {
        "bench": "program_backends",
        "p_sim": p_sim,
        "device_count": n_dev,
        "cases": records,
    }
    history = []
    if RESULTS_PATH.exists():
        try:
            history = json.loads(RESULTS_PATH.read_text())
            if not isinstance(history, list):
                history = [history]
        except (json.JSONDecodeError, OSError):
            history = []
    history.append(snapshot)
    RESULTS_PATH.write_text(json.dumps(history, indent=2) + "\n")
    report(
        "program_backends/json", 0.0,
        f"snapshot {len(history)} appended to {RESULTS_PATH.name}",
    )


if __name__ == "__main__":
    from repro.mpc.executors import enable_compile_cache

    enable_compile_cache()
    run(lambda name, us, derived="": print(f"{name},{us:.1f},{derived}"))
