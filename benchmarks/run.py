"""Benchmark harness — one module per paper table/claim + the roofline table.

    PYTHONPATH=src python -m benchmarks.run [--only substring]

Prints ``name,us_per_call,derived`` CSV rows (the scaffold contract)."""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run benches whose name contains this")
    args = ap.parse_args()

    from . import (
        bench_acyclic,
        bench_cartesian,
        bench_hypercube,
        bench_isolated_cp,
        bench_kernels,
        bench_lambda,
        bench_load_vs_p,
        bench_oneround_baseline,
        bench_program_backends,
        bench_roofline,
        bench_service,
        bench_subgraph,
    )

    modules = [
        ("load_vs_p", bench_load_vs_p),          # Theorem 6.2 (headline claim)
        ("oneround", bench_oneround_baseline),   # ψ vs ρ comparison (Sec. 1.2)
        ("icp", bench_isolated_cp),              # Theorem 5.1/5.4
        ("cartesian", bench_cartesian),          # Lemma 3.1
        ("hypercube", bench_hypercube),          # Lemma 3.3
        ("lambda", bench_lambda),                # λ-constant ablation (Sec. 6)
        ("kernels", bench_kernels),              # Pallas kernels
        ("program_backends", bench_program_backends),  # IR: sim load vs device wall-clock
        ("subgraph", bench_subgraph),            # Sec. 1.4 corollary workload
        ("service", bench_service),              # JoinSession cold vs warm
        ("acyclic", bench_acyclic),              # general k-ary route cold vs warm
        ("roofline", bench_roofline),            # §Roofline table from dry-run
    ]

    rows = []

    def report(name: str, us: float, derived: str = "") -> None:
        rows.append((name, us, derived))
        print(f"{name},{us:.1f},{derived}", flush=True)

    print("name,us_per_call,derived")
    failed = []
    for name, mod in modules:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            mod.run(report)
        except Exception as e:  # keep the harness running; surface at the end
            failed.append((name, e))
            traceback.print_exc()
        print(f"# [{name}] {time.time() - t0:.1f}s", flush=True)

    if failed:
        print(f"# FAILED: {[n for n, _ in failed]}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.mpc.executors import enable_compile_cache

    enable_compile_cache()
    main()
