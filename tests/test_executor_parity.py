"""Executor parity: DataplaneExecutor ≡ SimulatorExecutor on every compiled program.

The acceptance bar of the per-op dataplane lowering: for any program
`compile_plan` emits — including stages with isolated attributes (Lemma 3.1
CP grid), multi-dimensional isolated sets, and disconnected light subqueries —
the device backend must reproduce the simulator's join count, per-H counts
(including the zero entries of stages that ran but produced nothing), and the
sorted result-row multiset.  Inputs are seeded Zipf-skewed so heavy values
actually exist and the taxonomy fans out into many (H, η) stages.

Also covers the overflow-retry contract: output overflow scales only the
output capacity (routing buffers untouched), and slot retries re-randomize
the routing salts (fresh randomness per attempt).
"""

from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.query import (
    JoinQuery,
    Relation,
    disconnected_query,
    hub_star_query,
    random_query,
    reference_join,
)
from repro.core.taxonomy import compute_stats
from repro.mpc.cartesian import CartesianGrid
from repro.mpc.executors import DataplaneExecutor, SimulatorExecutor, _WorkItem, _salt
from repro.mpc.hypercube import HyperCubeGrid
from repro.mpc.program import compile_plan, fuse_semijoin_pass


def rows_key(rows):
    return sorted(map(tuple, rows.tolist()))


def assert_parity(q: JoinQuery, lam: int, p: int = 8, fused: bool = False):
    """Compile once, run every backend and schedule, compare all + oracle.

    The dataplane runs twice — stage-batched and per-stage (``batch_stages``
    off) — and the two schedules must agree on results *and* retry-log
    semantics: capacities are a function of the round's work items, never of
    the bucketing, so overflow behavior is schedule-independent."""
    stats = compute_stats(q, lam)
    program = compile_plan(q, stats, p)
    if fused:
        program = fuse_semijoin_pass(program)
    sim = SimulatorExecutor(p=p).run(program)
    dp = DataplaneExecutor(batch_stages=True).run(program)
    dp_u = DataplaneExecutor(batch_stages=False).run(program)
    oracle = reference_join(q)
    assert sim.count == len(oracle), "simulator must match the oracle"
    assert dp.count == sim.count, (dp.count, sim.count)
    assert dp.per_h_counts == sim.per_h_counts, (dp.per_h_counts, sim.per_h_counts)
    assert rows_key(dp.rows) == rows_key(sim.rows)
    # batched ≡ unbatched: identical results and identical retry semantics
    assert dp_u.count == dp.count
    assert dp_u.per_h_counts == dp.per_h_counts
    assert rows_key(dp_u.rows) == rows_key(dp.rows)
    assert dp_u.retries == dp.retries
    assert dp_u.retry_log == dp.retry_log
    # the batched schedule must actually batch: never more fused dispatches
    # than the per-stage schedule issues
    assert dp.dispatches <= dp_u.dispatches
    return program, sim, dp


# ---------------------------------------------------------------------------
# Randomized seeded parity across query families (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_parity_triangle_zipf_isolated_stages():
    """Skewed triangle: H with two heavy attrs leaves the third attribute
    isolated (CP grid with hc_size = 1), alongside cyclic light stages."""
    q = random_query(
        np.random.default_rng(2), "clique", 3, tuples_per_rel=200, dom_size=30,
        skew=2.0,
    )
    program, _, _ = assert_parity(q, lam=16)
    assert any(st.plan.isolated for st in program.stages), (
        "triangle taxonomy must exercise isolated attributes"
    )


def test_parity_four_cycle_2d_isolated_grid():
    """Skewed 4-cycle: H = two opposite attributes isolates the other two —
    a genuinely multi-dimensional Lemma 3.1 grid."""
    q = random_query(
        np.random.default_rng(7), "cycle", 4, tuples_per_rel=120, dom_size=10,
        skew=2.5,
    )
    program, _, _ = assert_parity(q, lam=24)
    assert any(len(st.plan.isolated) >= 2 for st in program.stages), (
        "4-cycle taxonomy must exercise a >=2-dimensional CP grid"
    )


def test_parity_hub_star_isolated_only():
    """Planted heavy hub on a star: under H = {hub} every leaf is isolated and
    no light edges survive — the pure-CP-grid stage the dataplane formerly
    rejected with DataplaneUnsupported."""
    q = hub_star_query(n=48, hub_n=24, dom_size=25)
    program, _, _ = assert_parity(q, lam=10)
    assert any(
        st.plan.isolated and not st.plan.light_edges for st in program.stages
    ), "hub star must produce a light-edge-free CP-grid stage"


def test_parity_disconnected_light_subquery():
    """Two skewed components (A,B) ⋈ (C,D): the H = ∅ light subquery is
    disconnected (the second former DataplaneUnsupported escape hatch), and
    heavy values produce stages mixing an isolated attribute with a light
    component."""
    q = disconnected_query(90, dom_size=12, skew=1.8)
    program, _, _ = assert_parity(q, lam=8)
    h_empty = [st for st in program.stages if st.hkey == ()]
    assert h_empty and len(h_empty[0].plan.light_edges) == 2, (
        "H=∅ stage must carry the disconnected light subquery"
    )


def test_parity_fused_program():
    """The fused semi-join rewrite changes the op list, not the executor: the
    per-op dispatch lowers SemiJoin[fused-*] through the same rule."""
    q = random_query(
        np.random.default_rng(4), "star", 4, tuples_per_rel=150, dom_size=12,
        skew=1.5,
    )
    program, _, _ = assert_parity(q, lam=3, fused=True)
    assert program.fused


# ---------------------------------------------------------------------------
# Packed int32 composite keys: eligibility + checked fallback
# ---------------------------------------------------------------------------


def _join_packed_flags(ex):
    """Packed-key decisions recorded in the executor's learned-caps keys:
    one flag per composite-key LocalJoin bucket (dup_pairs non-empty)."""
    return [
        key[4]
        for (_, _, key, _) in ex._learned_caps
        if key and key[0] == "join" and len(key) == 5 and key[3]
    ]


def _run_both_schedules(q, lam, p=8):
    stats = compute_stats(q, lam)
    program = compile_plan(q, stats, p)
    ex = DataplaneExecutor(batch_stages=True)
    res = ex.run(program)
    ex_u = DataplaneExecutor(batch_stages=False)
    res_u = ex_u.run(program)
    oracle = reference_join(q)
    assert res.count == len(oracle) == res_u.count
    assert rows_key(res.rows) == rows_key(oracle.data) == rows_key(res_u.rows)
    return ex, ex_u


def test_key_compression_packs_small_domains():
    """Cyclic (triangle) query with small vertex ids: every composite-key
    join bucket passes the int32 eligibility check and takes the packed path."""
    q = random_query(
        np.random.default_rng(2), "clique", 3, tuples_per_rel=200, dom_size=30,
        skew=2.0,
    )
    ex, ex_u = _run_both_schedules(q, lam=16)
    for e in (ex, ex_u):
        flags = _join_packed_flags(e)
        assert flags, "triangle chains must produce composite-key joins"
        assert all(flags), "small domains must take the packed int32 path"


def test_key_compression_int32_overflow_takes_ranked_fallback():
    """Adversarial key space: vertex ids shifted by 5·10^7 keep every value
    int32-safe, but (max_cell+1)·(max_dup+1) exceeds 2^31, so packing would
    collide — the eligibility check must reject it and the ranked
    (lexicographic dense-rank) fallback must produce the identical result on
    both schedules."""
    q = random_query(
        np.random.default_rng(2), "clique", 3, tuples_per_rel=200, dom_size=30,
        skew=2.0,
    )
    shift = 50_000_000
    q_big = JoinQuery.make(
        [Relation.make(r.scheme, r.data + shift) for r in q.relations]
    )
    ex, ex_u = _run_both_schedules(q_big, lam=16)
    for e in (ex, ex_u):
        flags = _join_packed_flags(e)
        assert flags, "triangle chains must produce composite-key joins"
        assert not any(flags), (
            "key space over 2^31 must take the ranked fallback"
        )


# ---------------------------------------------------------------------------
# Overflow-retry contract (satellites: split channels + fresh randomness)
# ---------------------------------------------------------------------------


def test_output_only_overflow_scales_cap_out_not_routing():
    """A high-fanout join forces the LocalJoin output estimate to overflow
    while every routing buffer fits: the retry must scale only cap_out.  Runs
    on a 1-device mesh so routing-slot overflow is impossible by construction
    — any retry the log records is a pure output-capacity retry.  Uses
    ``exact_caps=False``: the legacy estimate+retry path this test exercises
    (the default count-then-emit path sizes caps exactly and never retries)."""
    import jax

    a = np.stack(
        [np.repeat(np.arange(100), 2), np.tile(np.arange(2), 100)], axis=1
    )
    b = np.stack(
        [np.tile(np.arange(2), 100), 1000 + np.repeat(np.arange(100), 2)], axis=1
    )
    q = JoinQuery.make(
        [Relation.make(("A", "B"), a), Relation.make(("B", "C"), b)]
    )
    stats = compute_stats(q, lam=2)   # threshold m/2: no heavy values
    program = compile_plan(q, stats, p=8)
    mesh = jax.make_mesh((1,), ("join",))
    ex = DataplaneExecutor(mesh=mesh, exact_caps=False)
    res = ex.run(program)
    oracle = reference_join(q)
    assert res.count == len(oracle) == 20_000
    assert sorted(map(tuple, res.rows.tolist())) == sorted(
        map(tuple, oracle.data.tolist())
    )
    assert res.retries >= 1, "the output estimate must have been exceeded"
    assert all(kind == "out" for _, _, kind in res.retry_log), res.retry_log
    assert any(rnd == "output" for _, rnd, _ in res.retry_log), res.retry_log


def _bare_scheduler(batch=True):
    """A DataplaneExecutor shell with only the scheduler state — no devices
    (the fake mesh tag just keys the executable-cache signatures)."""
    from collections import OrderedDict, defaultdict

    from repro.mpc.executors import ExecutableCache

    ex = DataplaneExecutor.__new__(DataplaneExecutor)
    ex.max_retries = 4
    ex.batch_stages = batch
    ex.mesh, ex.axis_name = "fake-mesh", "join"
    ex.compiled_cache = ExecutableCache()
    ex._retries, ex._retry_log = 0, []
    ex._qi_retries, ex._qi_retry_log = defaultdict(int), defaultdict(list)
    ex._dispatches, ex._jit_hits, ex._jit_misses = 0, 0, 0
    ex._bucket_log, ex._learned_caps = {}, OrderedDict()
    ex._caps_hits, ex._caps_misses, ex._caps_evictions = 0, 0, 0
    ex.caps_hits, ex.caps_misses, ex.caps_evictions = 0, 0, 0
    ex._phase_us, ex._round_us = {}, {}
    return ex


class _FakeFn:
    """Stands in for a jitted primitive.  Like a real compiled executable its
    output is a pure function of its call args (the scheduler caches by
    signature, so a bucket may execute an executable compiled for an earlier
    same-signature bucket): each arg is (trip, retries) for one stage and the
    overflow tensor trips that stage's channel on the first run only (a real
    retry runs at grown caps / fresh salts, which is what clears the trip)."""

    def lower(self, *args):
        return self

    def compile(self):
        return self._impl

    @staticmethod
    def _impl(*args):
        ovf = np.zeros((len(args), 1, 2), np.int64)
        for j, (trip, retries) in enumerate(args):
            if retries == 0 and trip:
                ovf[j, 0, 0 if trip == "slot" else 1] = 1
        return ovf


def _item(i, caps, trip=None):
    """trip: None | "slot" | "out" — which channel overflows on the first run."""
    return _WorkItem(
        state=SimpleNamespace(skey=("H", i), qi=0),
        key=("k",),
        caps=dict(caps),
        payload={"i": i, "trip": trip},
        group=("g", i),
    )


def _fake_dispatch(log):
    def dispatch(bucket):
        log.append([(it.payload["i"], dict(it.caps), it.attempt) for it in bucket])
        args = tuple((it.payload["trip"] or "", it.retries) for it in bucket)

        def post(outs):
            return (lambda: [it.payload["i"] for it in bucket]), outs

        return _FakeFn(), args, post

    return dispatch


def test_scheduler_doubles_only_the_tripped_channel():
    """Per-channel retry: an output overflow doubles only 'out' and keeps the
    attempt-0 salts (row order must not depend on capacity history); a slot
    overflow doubles only 'slot' and advances to fresh attempt salts."""
    for trip, doubled, attempt in (("out", {"slot": 16, "out": 128}, 0),
                                   ("slot", {"slot": 32, "out": 64}, 1)):
        ex = _bare_scheduler()
        log = []
        items = [_item(0, {"slot": 16, "out": 64}, trip=trip)]
        out = ex._run_buckets("rnd", items, _fake_dispatch(log))
        assert out[0].result == 0
        assert log[0][0] == (0, {"slot": 16, "out": 64}, 0)
        assert log[1][0] == (0, doubled, attempt), (trip, log)
        assert ex._retry_log == [(("H", 0), "rnd", trip)]
        assert ex._retries == 1


def test_scheduler_mixed_channel_overflow_in_one_bucket():
    """Mixed channels inside one fused bucket: each item doubles exactly its
    own tripped channel, untouched items never re-run, and the retry log
    carries one entry per overflowed group."""
    ex = _bare_scheduler()
    log = []
    caps = {"slot": 16, "out": 64}
    items = [
        _item(0, caps, trip="slot"),
        _item(1, caps, trip="out"),
        _item(2, caps, trip=None),
    ]
    ex._run_buckets("rnd", items, _fake_dispatch(log))
    assert log[0] == [
        (0, {"slot": 16, "out": 64}, 0),
        (1, {"slot": 16, "out": 64}, 0),
        (2, {"slot": 16, "out": 64}, 0),
    ]
    # retry round: only the two overflowed items, each with its own channel
    # doubled — and (caps now differing) in separate buckets; the slot item
    # re-salts (attempt 1) while the out item keeps its attempt-0 salts
    retried = sorted((b[0] for b in log[1:]), key=lambda t: t[0])
    assert retried == [
        (0, {"slot": 32, "out": 64}, 1),
        (1, {"slot": 16, "out": 128}, 0),
    ]
    assert ex._retry_log == [
        (("H", 0), "rnd", "slot"),
        (("H", 1), "rnd", "out"),
    ]
    assert items[2].attempt == 0            # clean item never re-ran
    assert ex._retries == 2


def test_scheduler_batched_and_unbatched_retry_identically():
    """The same item set produces the same caps trajectory and retry log
    under both schedules (capacities are item-set functions, not bucket
    functions)."""
    logs = {}
    for batch in (True, False):
        ex = _bare_scheduler(batch=batch)
        log = []
        caps = {"slot": 16, "out": 64}
        items = [_item(0, caps, trip="slot"), _item(1, caps, trip="out")]
        ex._run_buckets("rnd", items, _fake_dispatch(log))
        logs[batch] = (ex._retry_log, [it.caps for it in items], ex._retries)
    assert logs[True] == logs[False]


def test_salt_is_wide_and_attempt_threaded():
    """The routing salt spans the full 31-bit range (beyond the old 2^20) and
    a retry draws a fresh value — the paper's per-attempt randomness."""
    salts = {_salt("stage", i) for i in range(2000)}
    assert max(salts) >= 1 << 20, "salt range must exceed the old 2^20 cap"
    assert len(salts) == 2000
    assert _salt("k", attempt=0) != _salt("k", attempt=1)
    # stability: same key + attempt ⇒ same salt on every host
    assert _salt("k", 3, attempt=2) == _salt("k", 3, attempt=2)


# ---------------------------------------------------------------------------
# Device grid math ≡ host grid math (the geometry the route relies on)
# ---------------------------------------------------------------------------


def test_grid_coordinate_functions_match_numpy():
    import jax.numpy as jnp

    g = CartesianGrid([50, 30, 7], 16)
    ids = np.arange(87, dtype=np.int64)
    for li in range(g.t_prime):
        want = g.cells_for_ids(li, ids)
        got = np.asarray(g.cells_for_ids_dev(li, jnp.asarray(ids, jnp.int32)))
        assert np.array_equal(want, got)

    hc = HyperCubeGrid(("A", "B", "C"), {"A": 3, "B": 2, "C": 4})
    fixed = {"A": np.array([0, 1, 2, 0, 2]), "C": np.array([3, 2, 1, 0, 3])}
    want = hc.cells_for(fixed)
    got = np.asarray(
        hc.cells_for_dev({k: jnp.asarray(v, jnp.int32) for k, v in fixed.items()})
    )
    assert np.array_equal(want, got)


# ---------------------------------------------------------------------------
# Scheduler observability (satellite: compile count is O(#buckets))
# ---------------------------------------------------------------------------


def test_compile_count_scales_with_buckets_not_stages():
    """The stage-batched scheduler compiles one executable per geometry
    bucket: the jit-miss count is bounded by the bucket count (itself far
    below the work-item count), and a repeat run compiles nothing."""
    q = disconnected_query(90, dom_size=12, skew=1.8)
    stats = compute_stats(q, lam=8)
    program = compile_plan(q, stats, 8)
    ex = DataplaneExecutor()
    res = ex.run(program)
    n_buckets = sum(len(v) for v in res.bucket_stage_counts.values())
    n_items = sum(sum(v) for v in res.bucket_stage_counts.values())
    assert res.dispatches == n_buckets
    assert n_buckets < n_items, "batching must actually group stages"
    assert res.jit_cache_misses <= n_buckets
    assert res.jit_cache_hits + res.jit_cache_misses == res.dispatches
    # Steady state: learned caps converge within one repeat run (a run-1
    # partial-bucket retry may force run 2 to compile the merged-caps
    # variant once), after which nothing compiles and nothing retries.
    ex.run(program, materialize=False)
    res3 = ex.run(program, materialize=False)
    assert res3.jit_cache_misses == 0
    assert res3.retries == 0
    assert res3.jit_cache_hits == res3.dispatches
    # the IR-level signature histogram bounds the bucket structure: far
    # fewer distinct signatures than stages
    hist = program.bucket_histogram()
    assert sum(hist.values()) == len(program.stages)
    assert len(hist) < len(program.stages)


@pytest.mark.parametrize("from_env", [False, True], ids=["default-dir", "env-dir"])
def test_enable_compile_cache_directory(tmp_path, from_env):
    """Entry points keep JAX's persistent cache in JAX_COMPILATION_CACHE_DIR
    when it is set (and set no other directory), else at the fixed
    <repo>/.jax_cache, caching every executable however fast it compiled."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    code = (
        "import jax\n"
        "from repro.mpc.executors import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [str(repo / "src"), env.get("PYTHONPATH")]))
    want = str(repo / ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    path, configured, min_secs = out.stdout.split("\n")[:3]
    assert path == configured == want
    assert float(min_secs) == 0
