"""Cold-vs-warm submit latency and mixed-workload throughput of the join service.

The service claim (docs/design/09-service.md): a warm repeat of any cached
query through :class:`~repro.mpc.service.JoinSession` skips the planner LPs
(plan LRU), every XLA trace+compile (executable cache), and every overflow
retry (learned caps) — steady-state latency is the stage-batched dispatch
cost alone.  This bench meters exactly that:

  * per-shape cases (``triangle-hub``, ``star-hub-cp``, ``pattern-triangle``):
    one cold submit (pays compile_plan + AOT jit), then best-of-3 warm
    repeats through the same session — ``dataplane_cold_us`` vs
    ``dataplane_warm_us`` is the figure the service exists for;
  * ``mixed-workload``: three query shapes round-robin through ONE session —
    round 1 is the cold sweep, rounds 2–3 are steady state; reports the mean
    warm per-query latency AND the measured closed-loop throughput
    (``qps_warm`` = completed queries over wall clock; the old
    per-query-latency derivation rides along as ``qps_warm_derived`` for
    comparison).  This is the serving regime: many shapes interleaved, every
    one warm after its first visit.
  * ``mixed-coalesced``: the same three shapes under *concurrent* load — a
    closed loop of ``CLIENTS`` outstanding ``submit_async`` requests per
    wave, drained through the coalescing queue (identical submissions share
    one execution; same-signature distinct queries stack into fused
    dispatches).  Records offered concurrency, measured qps, e2e p50/p99,
    steady-state jit misses (must be 0) and retries (must be 0) — the
    cross-query scheduler's acceptance figure (≥10x the serial mixed qps).
  * ``stacked-distinct``: ``STACK_CLIENTS`` permutation-distinct triangle
    queries (same plan key, different tables — dedup can't help) coalesced
    into one scheduler pass vs submitted serially: isolates the pure
    stage-stacking win of fusing same-bucket dispatches.

Every run appends a snapshot to ``BENCH_service.json`` (same shape as the
other BENCH histories, so ``compare_bench.py --bench service`` gates warm
regressions — and, for cases carrying ``qps_warm``, qps drops — in CI).

Run standalone with 8 fake host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python -m benchmarks.run --only service
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

import numpy as np

from repro.core.query import (
    disconnected_query,
    hub_star_query,
    hub_triangle_query,
    reference_join,
)
from repro.mpc.service import JoinSession

RESULTS_PATH = Path(
    os.environ.get(
        "BENCH_SERVICE_RESULTS_PATH",
        Path(__file__).resolve().parents[1] / "BENCH_service.json",
    )
)

WARM_REPEATS = 3
#: outstanding submit_async requests per wave of the closed-loop case.
CLIENTS = 16
#: measured steady-state waves (after the warm-up waves).
WAVES = 4
#: distinct-data queries in the stacking case.
STACK_CLIENTS = 8


def shape_cases():
    return [
        ("triangle-hub", hub_triangle_query(n=300, hub_n=80, dom_size=40, hub=10_000), 16),
        ("star-hub-cp", hub_star_query(n=90, hub_n=40, dom_size=25), 10),
    ]


def _run_shape(session, q, lam, oracle_n):
    # materialize=False on BOTH sides so cold-vs-warm isolates the service
    # caches, not the device->host row pull (counts still oracle-checked)
    cold = session.submit(q, lam=lam, materialize=False)
    assert cold.count == oracle_n, (cold.count, oracle_n)
    warm_samples = []
    warm = None
    for _ in range(WARM_REPEATS):
        warm = session.submit(q, lam=lam, materialize=False)
        warm_samples.append(warm.total_us)
        assert warm.plan_cache_hit
    return cold, warm, min(warm_samples)


def run(report):
    import jax

    n_dev = len(jax.devices())
    records = []

    # -- per-shape cold vs warm ----------------------------------------------
    for name, q, lam in shape_cases():
        session = JoinSession(p=8, backend="dataplane")
        oracle_n = len(reference_join(q))
        cold, warm, warm_us = _run_shape(session, q, lam, oracle_n)
        report(
            f"service/{name}", warm_us,
            f"cold_us={cold.total_us:.0f} jit_misses_cold={cold.jit_cache_misses} "
            f"jit_misses_warm={warm.jit_cache_misses} warm_retries={warm.retries} "
            f"compile_us={cold.compile_us:.0f}",
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

    # -- session-backed subgraph enumeration ---------------------------------
    from repro.graph import triangle, zipf_graph

    g = zipf_graph(np.random.default_rng(0), n_vertices=800, n_edges=3200, skew=1.0)
    session = JoinSession(p=8, backend="dataplane")
    t0 = time.perf_counter()
    first = session.submit_pattern(triangle(), g)
    cold_us = (time.perf_counter() - t0) * 1e6
    warm_samples = []
    for _ in range(WARM_REPEATS):
        t0 = time.perf_counter()
        rep = session.submit_pattern(triangle(), g)
        warm_samples.append((time.perf_counter() - t0) * 1e6)
        assert rep.count == first.count
    warm_us = min(warm_samples)
    warm_engine = rep.engine
    report(
        "service/pattern-triangle", warm_us,
        f"cold_us={cold_us:.0f} triangles={first.count} "
        f"plan_hits={session.stats.plan_hits} "
        f"jit_misses_warm={warm_engine.jit_cache_misses}",
    )
    records.append(
        {
            "case": "pattern-triangle",
            "lam": None,
            "count": int(first.count),
            "dataplane_cold_us": round(cold_us, 1),
            "dataplane_warm_us": round(warm_us, 1),
            "dataplane_retries": int(warm_engine.retries),
            "jit_misses_cold": None,
            "jit_misses_warm": int(warm_engine.jit_cache_misses),
        }
    )

    # -- mixed workload: three shapes round-robin through one session --------
    shapes = [(n, q, lam) for n, q, lam in shape_cases()] + [
        ("disconnected", disconnected_query(120, dom_size=14, skew=1.8), 8)
    ]
    session = JoinSession(p=8, backend="dataplane")
    t0 = time.perf_counter()
    for _, q, lam in shapes:                       # round 1: cold sweep
        session.submit(q, lam=lam, materialize=False)
    cold_round_us = (time.perf_counter() - t0) * 1e6
    warm_lat, warm_retries = [], 0
    t_loop = time.perf_counter()
    for _ in range(2):                             # rounds 2-3: steady state
        for _, q, lam in shapes:
            r = session.submit(q, lam=lam, materialize=False)
            assert r.plan_cache_hit
            warm_lat.append(r.total_us)
            warm_retries += r.retries
    loop_wall = time.perf_counter() - t_loop
    mean_warm_us = sum(warm_lat) / len(warm_lat)
    # the headline qps is measured closed-loop: completed queries over wall
    # clock — the old per-query-latency derivation under-counts inter-submit
    # overhead (λ/stats/bookkeeping outside total_us) and is kept only for
    # comparison against the pre-measurement history
    qps = len(warm_lat) / loop_wall if loop_wall else 0.0
    qps_derived = 1e6 / mean_warm_us if mean_warm_us else 0.0
    report(
        "service/mixed-workload", mean_warm_us,
        f"cold_round_us={cold_round_us:.0f} shapes={len(shapes)} "
        f"qps_warm={qps:.1f} (derived {qps_derived:.1f}) "
        f"jit_misses_total={session.stats.jit_misses} "
        f"plan_hits={session.stats.plan_hits}",
    )
    records.append(
        {
            "case": "mixed-workload",
            "lam": None,
            "count": None,
            "dataplane_cold_us": round(cold_round_us, 1),
            "dataplane_warm_us": round(mean_warm_us, 1),
            "dataplane_retries": int(warm_retries),
            "qps_warm": round(qps, 2),
            "qps_warm_derived": round(qps_derived, 2),
            "jit_misses_total": int(session.stats.jit_misses),
        }
    )
    serial_mixed_qps = qps

    # -- mixed workload under concurrent load through the coalescing queue ---
    # Closed loop: CLIENTS outstanding submit_async requests per wave,
    # round-robin over the same three shapes.  The drainer coalesces each
    # wave into one scheduler batch: identical submissions share one
    # execution, the rest stack into fused dispatches.  Two warm-up waves
    # compile the stacked-signature executables; the measured waves must run
    # with zero jit misses and zero retries (steady state).
    session = JoinSession(p=8, backend="dataplane")
    wave = [shapes[i % len(shapes)] for i in range(CLIENTS)]
    for _ in range(2):                              # cold + signature warm-up
        futs = [
            session.submit_async(q, lam=lam, materialize=False)
            for _, q, lam in wave
        ]
        for f in futs:
            f.result()
    jit0, ret0 = session.stats.jit_misses, session.stats.retries
    batch_sizes = []
    t0 = time.perf_counter()
    for _ in range(WAVES):
        futs = [
            session.submit_async(q, lam=lam, materialize=False)
            for _, q, lam in wave
        ]
        batch_sizes.extend(f.result().batch_size for f in futs)
    wall = time.perf_counter() - t0
    n_done = WAVES * CLIENTS
    qps_coal = n_done / wall if wall else 0.0
    jit_steady = session.stats.jit_misses - jit0
    ret_steady = session.stats.retries - ret0
    p50 = session.stats.percentile(50, window="e2e")
    p99 = session.stats.percentile(99, window="e2e")
    session.close()
    report(
        "service/mixed-coalesced", wall * 1e6 / n_done,
        f"clients={CLIENTS} qps_warm={qps_coal:.1f} "
        f"speedup_vs_serial={qps_coal / serial_mixed_qps:.1f}x "
        f"e2e_p50_us={p50:.0f} p99_us={p99:.0f} "
        f"jit_misses_steady={jit_steady} retries_steady={ret_steady} "
        f"deduped={session.stats.deduped} "
        f"max_batch={session.stats.max_coalesced_batch}",
    )
    records.append(
        {
            "case": "mixed-coalesced",
            "lam": None,
            "count": None,
            "clients": CLIENTS,
            "queries": n_done,
            "dataplane_cold_us": round(cold_round_us, 1),
            "dataplane_warm_us": round(wall * 1e6 / n_done, 1),
            "dataplane_retries": int(ret_steady),
            "qps_warm": round(qps_coal, 2),
            "qps_serial_baseline": round(serial_mixed_qps, 2),
            "e2e_p50_us": round(p50, 1),
            "e2e_p99_us": round(p99, 1),
            "jit_misses_steady": int(jit_steady),
            "deduped": int(session.stats.deduped),
            "max_coalesced_batch": int(session.stats.max_coalesced_batch),
            "mean_coalesced_batch": round(
                sum(batch_sizes) / len(batch_sizes), 1
            ) if batch_sizes else 0,
        }
    )

    # -- pure stacking: distinct-data same-plan queries, dedup can't help ----
    rng = np.random.default_rng(7)
    base = hub_triangle_query(n=300, hub_n=80, dom_size=40, hub=10_000)
    from repro.core.query import JoinQuery, Relation

    def permuted(q, seed):
        r = np.random.default_rng(seed)
        rels = []
        for rel in q.relations:
            d = rel.data.copy()
            r.shuffle(d)
            rels.append(Relation(scheme=rel.scheme, data=d, table=None))
        return JoinQuery(rels)

    distinct = [permuted(base, int(rng.integers(1 << 30))) for _ in range(STACK_CLIENTS)]
    session = JoinSession(p=8, backend="dataplane")
    for q in distinct:                              # cold sweep (serial caches)
        session.submit(q, lam=16, materialize=False)
    session.submit_coalesced(distinct, lam=16, materialize=False)  # stacked sigs
    t0 = time.perf_counter()
    for q in distinct:
        session.submit(q, lam=16, materialize=False)
    serial_wall = time.perf_counter() - t0
    jit0, ret0 = session.stats.jit_misses, session.stats.retries
    t0 = time.perf_counter()
    session.submit_coalesced(distinct, lam=16, materialize=False)
    coal_wall = time.perf_counter() - t0
    qps_stack = len(distinct) / coal_wall if coal_wall else 0.0
    qps_stack_serial = len(distinct) / serial_wall if serial_wall else 0.0
    report(
        "service/stacked-distinct", coal_wall * 1e6 / len(distinct),
        f"queries={len(distinct)} qps_warm={qps_stack:.1f} "
        f"serial_qps={qps_stack_serial:.1f} "
        f"jit_misses_steady={session.stats.jit_misses - jit0} "
        f"retries_steady={session.stats.retries - ret0}",
    )
    records.append(
        {
            "case": "stacked-distinct",
            "lam": 16,
            "count": None,
            "queries": len(distinct),
            "dataplane_cold_us": round(serial_wall * 1e6, 1),
            "dataplane_warm_us": round(coal_wall * 1e6 / len(distinct), 1),
            "dataplane_retries": int(session.stats.retries - ret0),
            "qps_warm": round(qps_stack, 2),
            "qps_serial_baseline": round(qps_stack_serial, 2),
            "jit_misses_steady": int(session.stats.jit_misses - jit0),
        }
    )

    # -- degraded-mode throughput under injected dispatch failures -----------
    # Seeded FaultPlan injects dispatch exceptions at 1% / 5% of dispatch
    # events; failed submits surface as typed JoinServiceErrors and quarantine
    # their plan + learned-caps entries (docs/design/10-robustness.md).  The
    # figures: closed-loop qps of the *surviving* queries while the plan is
    # live (degraded-mode throughput carries the qps gate), plus the latency
    # of the first clean submit after the plan drains (recovery cost: re-plan
    # + count-pass re-derivation, zero overflow retries).
    from repro.mpc.faults import FaultPlan, FaultRule, JoinServiceError

    for rate in (0.01, 0.05):
        label = f"faults-{int(rate * 100)}pct"
        session = JoinSession(p=8, backend="dataplane")
        for _, q, lam in shapes:                    # clean warm-up sweep
            session.submit(q, lam=lam, materialize=False)
        session.fault_plan = FaultPlan(
            [FaultRule(site="dispatch", rate=rate)], seed=20260808
        )
        ok = failed = 0
        t0 = time.perf_counter()
        for _ in range(WAVES):
            for _, q, lam in shapes:
                try:
                    session.submit(q, lam=lam, materialize=False)
                    ok += 1
                except JoinServiceError:
                    failed += 1
        wall = time.perf_counter() - t0
        qps_fault = ok / wall if wall else 0.0
        injected = session.fault_plan.total_injected
        session.fault_plan = None                   # plan drained: recover
        t0 = time.perf_counter()
        rec = session.submit(shapes[0][1], lam=shapes[0][2], materialize=False)
        recovery_us = (time.perf_counter() - t0) * 1e6
        assert rec.retries == 0, rec.retries        # quarantine left no debris
        session.close()
        report(
            f"service/{label}", wall * 1e6 / max(ok, 1),
            f"rate={rate:.0%} survivors={ok} failed={failed} "
            f"injected={injected} qps_degraded={qps_fault:.1f} "
            f"recovery_us={recovery_us:.0f} "
            f"plans_quarantined={session.stats.quarantined_plans}",
        )
        records.append(
            {
                "case": label,
                "lam": None,
                "count": None,
                "fault_rate": rate,
                "queries": ok + failed,
                "survivors": ok,
                "failed": failed,
                "injected": int(injected),
                "dataplane_cold_us": None,
                "dataplane_warm_us": round(wall * 1e6 / max(ok, 1), 1),
                "dataplane_retries": 0,
                "qps_warm": round(qps_fault, 2),
                "recovery_us": round(recovery_us, 1),
                "plans_quarantined": int(session.stats.quarantined_plans),
            }
        )

    snapshot = {
        "bench": "service",
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
        "service/json", 0.0,
        f"snapshot {len(history)} appended to {RESULTS_PATH.name}",
    )


if __name__ == "__main__":
    from repro.mpc.executors import enable_compile_cache

    enable_compile_cache()
    run(lambda name, us, derived="": print(f"{name},{us:.1f},{derived}"))
