"""Cold-vs-warm latency of the general (arbitrary-arity) join route.

The general-route claim (docs/design/12-general-joins.md): a k-ary acyclic
query compiles once into a Yannakakis RoundProgram (GYO join tree, up/down
semijoin sweeps, share route, cell join) and then serves warm repeats from
the plan LRU + executable cache exactly like the binary pipeline — steady
state is the stage-batched dispatch cost with zero retries and zero jit
misses.  This bench meters the canonical acyclic families plus the binary
triangle forced down the generalized-HyperCube (cyclic) route:

  * ``star3``     — 3-ary fact + three binary dimensions (smallest k≥3 tree);
  * ``snowflake`` — star3 with one dimension normalized a level deeper
                    (a depth-2 sweep: the down pass must re-reduce chains);
  * ``path4``     — arity-2/3 relations chained in a path;
  * ``triangle-general`` — the cyclic share route (no tree, pure BKS shares).

Each case does one cold submit through a fresh :class:`JoinSession` (pays
``compile_plan`` — GYO + LP shares — plus AOT jit), then best-of-3 warm
repeats on the same session; every count is oracle-checked against
``reference_join``.  Snapshots append to ``BENCH_acyclic.json`` in the shape
``compare_bench.py --bench acyclic`` gates (warm time, >25%).

Run standalone with 8 fake host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python -m benchmarks.run --only acyclic
"""

from __future__ import annotations

import json
import os
from pathlib import Path

from repro.core.query import general_query, reference_join
from repro.mpc.service import JoinSession

RESULTS_PATH = Path(
    os.environ.get(
        "BENCH_ACYCLIC_RESULTS_PATH",
        Path(__file__).resolve().parents[1] / "BENCH_acyclic.json",
    )
)

WARM_REPEATS = 3


def cases():
    return [
        ("star3", general_query("star3", n=240, dom_size=20, skew=0.8, seed=11), 8),
        ("snowflake", general_query("snowflake", n=200, dom_size=18, skew=0.8, seed=12), 8),
        ("path4", general_query("path4", n=200, dom_size=16, skew=0.5, seed=13), 8),
        ("triangle-general", general_query("triangle", n=260, dom_size=24, skew=1.2, seed=14), 8),
    ]


def run(report):
    import jax

    n_dev = len(jax.devices())
    records = []
    for name, q, lam in cases():
        oracle_n = len(reference_join(q))
        session = JoinSession(p=8, backend="dataplane")
        try:
            cold = session.submit(q, lam=lam, materialize=False)
            assert cold.count == oracle_n, (name, cold.count, oracle_n)
            warm = None
            warm_samples = []
            for _ in range(WARM_REPEATS):
                warm = session.submit(q, lam=lam, materialize=False)
                warm_samples.append(warm.total_us)
                assert warm.plan_cache_hit
                assert warm.count == oracle_n
            warm_us = min(warm_samples)
        finally:
            session.close()
        report(
            f"acyclic/{name}", warm_us,
            f"cold_us={cold.total_us:.0f} rows={oracle_n} "
            f"compile_us={cold.compile_us:.0f} "
            f"jit_misses_warm={warm.jit_cache_misses} "
            f"warm_retries={warm.retries}",
        )
        records.append(
            {
                "case": name,
                "lam": lam,
                "count": int(cold.count),
                "dataplane_cold_us": round(cold.total_us, 1),
                "dataplane_warm_us": round(warm_us, 1),
                "dataplane_retries": int(warm.retries),
                "compile_us": round(cold.compile_us, 1),
                "jit_misses_cold": int(cold.jit_cache_misses),
                "jit_misses_warm": int(warm.jit_cache_misses),
            }
        )

    snapshot = {
        "bench": "acyclic",
        "p_sim": 8,
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
        "acyclic/json", 0.0,
        f"snapshot {len(history)} appended to {RESULTS_PATH.name}",
    )


if __name__ == "__main__":
    from repro.mpc.executors import enable_compile_cache

    enable_compile_cache()
    run(lambda name, us, derived="": print(f"{name},{us:.1f},{derived}"))
