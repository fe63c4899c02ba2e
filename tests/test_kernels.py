"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel body + BlockSpec schedule on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional test extra; only the property test needs it
    HAVE_HYPOTHESIS = False

from repro.kernels.ops import (
    fold64,
    hash_partition,
    hash_partition_pack,
    merge_join_counts,
    merge_join_pairs,
    ssd_chunk,
)
from repro.kernels import ref as kref
from repro.models.mamba import ssd_reference


# ---------------------------------------------------------------------------
# merge_join
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,m", [
    (256, 1024), (512, 2048), (300, 1500), (256, 999),
    # several 1024-key blocks on each side, neither a multiple of the block
    (2049, 1025), (1500, 3100),
])
@pytest.mark.parametrize("dom", [50, 10_000])
def test_merge_join_counts_matches_searchsorted(n, m, dom):
    rng = np.random.default_rng(n + m + dom)
    a = np.sort(rng.integers(0, dom, n).astype(np.int32))
    b = np.sort(rng.integers(0, dom, m).astype(np.int32))
    lo, up = merge_join_counts(jnp.asarray(a), jnp.asarray(b))
    lo_ref, up_ref = kref.merge_join_counts_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo_ref))
    np.testing.assert_array_equal(np.asarray(up), np.asarray(up_ref))


if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        seed=st.integers(0, 1_000),
        n=st.integers(1, 2500),
        m=st.integers(1, 3000),
        dom=st.integers(1, 500),
    )
    def test_merge_join_property(seed, n, m, dom):
        rng = np.random.default_rng(seed)
        a = np.sort(rng.integers(0, dom, n).astype(np.int32))
        b = np.sort(rng.integers(0, dom, m).astype(np.int32))
        lo, up = merge_join_counts(jnp.asarray(a), jnp.asarray(b))
        lo, up = np.asarray(lo), np.asarray(up)
        # counts == true multiplicity
        want = np.array([np.sum(b == x) for x in a])
        np.testing.assert_array_equal(up - lo, want)
        # ranges actually index matches
        for i in range(0, n, max(1, n // 10)):
            assert np.all(b[lo[i] : up[i]] == a[i])

else:

    @pytest.mark.skip(reason="property test needs the optional hypothesis extra")
    def test_merge_join_property():
        pass


def _pairs_fixture(seed, n, m, dom, cap_out):
    """Sorted sides → (lower, starts, total, expected pair list) for the
    pair-emission kernel, built the exact way local_sorted_join builds them."""
    rng = np.random.default_rng(seed)
    a = np.sort(rng.integers(0, dom, n).astype(np.int32))
    b = np.sort(rng.integers(0, dom, m).astype(np.int32))
    lower = np.searchsorted(b, a, side="left").astype(np.int32)
    upper = np.searchsorted(b, a, side="right").astype(np.int32)
    counts = upper - lower
    starts = (np.cumsum(counts) - counts).astype(np.int32)
    total = int(counts.sum())
    exp_a = np.concatenate([np.full(c, i, np.int32) for i, c in enumerate(counts)]) \
        if total else np.zeros(0, np.int32)
    exp_b = np.concatenate(
        [lower[i] + np.arange(c, dtype=np.int32) for i, c in enumerate(counts)]
    ) if total else np.zeros(0, np.int32)
    return lower, starts, total, exp_a[:cap_out], exp_b[:cap_out]


@pytest.mark.parametrize("n,m,dom,cap_out", [
    (256, 1024, 50, 1 << 13),
    (300, 1500, 40, 1 << 12),
    (512, 2048, 10_000, 1 << 10),
    (1, 7, 3, 64),
    (1100, 2500, 60, 3000),     # 2 key blocks × 3 output blocks, ragged
])
def test_merge_join_pairs_matches_ref_and_expansion(n, m, dom, cap_out):
    lower, starts, total, exp_a, exp_b = _pairs_fixture(n + m + dom, n, m, dom, cap_out)
    out_k = merge_join_pairs(
        jnp.asarray(lower), jnp.asarray(starts), cap_out, use_pallas=True
    )
    out_r = merge_join_pairs(
        jnp.asarray(lower), jnp.asarray(starts), cap_out, use_pallas=False
    )
    # kernel ≡ jnp reference on the full padded range (pads alias the last key
    # in both paths), and both enumerate exactly the true pair list up front
    for k, r in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
    v = min(total, cap_out)
    np.testing.assert_array_equal(np.asarray(out_k[0])[:v], exp_a[:v])
    np.testing.assert_array_equal(np.asarray(out_k[1])[:v], exp_b[:v])


if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        seed=st.integers(0, 1_000),
        n=st.integers(1, 700),
        m=st.integers(1, 2000),
        dom=st.integers(1, 300),
        cap_log=st.integers(4, 12),
    )
    def test_merge_join_pairs_property(seed, n, m, dom, cap_log):
        cap_out = 1 << cap_log
        lower, starts, total, exp_a, exp_b = _pairs_fixture(seed, n, m, dom, cap_out)
        a_idx, b_idx = merge_join_pairs(
            jnp.asarray(lower), jnp.asarray(starts), cap_out, use_pallas=True
        )
        a_ref, b_ref = merge_join_pairs(
            jnp.asarray(lower), jnp.asarray(starts), cap_out, use_pallas=False
        )
        np.testing.assert_array_equal(np.asarray(a_idx), np.asarray(a_ref))
        np.testing.assert_array_equal(np.asarray(b_idx), np.asarray(b_ref))
        v = min(total, cap_out)
        np.testing.assert_array_equal(np.asarray(a_idx)[:v], exp_a[:v])
        np.testing.assert_array_equal(np.asarray(b_idx)[:v], exp_b[:v])

else:

    @pytest.mark.skip(reason="property test needs the optional hypothesis extra")
    def test_merge_join_pairs_property():
        pass


def test_merge_join_total_pairs_vs_join():
    """Σ counts == |A ⋈ B| on the shared key."""
    rng = np.random.default_rng(7)
    a = np.sort(rng.integers(0, 40, 512).astype(np.int32))
    b = np.sort(rng.integers(0, 40, 2048).astype(np.int32))
    lo, up = merge_join_counts(jnp.asarray(a), jnp.asarray(b))
    total = int(np.sum(np.asarray(up) - np.asarray(lo)))
    brute = sum(int(np.sum(b == x)) for x in a)
    assert total == brute


# ---------------------------------------------------------------------------
# merge-path schedule: skewed, sentinel and disjoint inputs
# ---------------------------------------------------------------------------

BIG = np.iinfo(np.int32).max


def _sorted(*parts):
    return np.sort(np.concatenate(parts).astype(np.int32))


def _merge_path_cases():
    """(name, A (P, n), B (P, m)): sorted rows, one problem unless batched; each
    side 17 or more blocks, so that the kernels run the merge-path schedule
    and not the dense sweep of small probes."""
    rng = np.random.default_rng(17)
    r = lambda lo, hi, k: rng.integers(lo, hi, k)
    one = lambda a, b: (np.asarray(a)[None], np.asarray(b)[None])
    zipf = lambda k, dom: np.minimum(rng.zipf(1.3, k), dom)
    batch_a = np.stack([_sorted(r(0, 9000, 17000)), _sorted(zipf(17000, 60)),
                        _sorted(r(0, 9000, 11000), np.full(6000, BIG))])
    batch_b = np.stack([_sorted(r(0, 9000, 18500)), _sorted(zipf(18500, 60)),
                        _sorted(r(4000, 5000, 14000), np.full(4500, BIG))])
    return [
        ("all-keys-equal", *one(np.full(17 * 1024, 7), np.full(19 * 1024, 7))),
        # key 50 fills 5 whole A tiles and 7 whole B blocks, with keys either side
        ("heavy-key", *one(_sorted(r(0, 50, 6 * 1024), np.full(5 * 1024, 50), r(51, 99, 6500)),
                           _sorted(r(0, 50, 6 * 1024), np.full(7 * 1024, 50), r(51, 99, 6000)))),
        ("a-all-sentinel", *one(np.full(17 * 1024, BIG), _sorted(r(0, 500, 19000)))),
        ("b-all-sentinel", *one(_sorted(r(0, 500, 17000)), np.full(18 * 1024, BIG))),
        ("a-below-b", *one(_sorted(r(0, 1000, 17000)), _sorted(r(2000, 3000, 19000)))),
        ("a-above-b", *one(_sorted(r(2000, 3000, 17000)), _sorted(r(0, 1000, 19000)))),
        ("interleaved", *one(2 * np.arange(17 * 1024), 2 * np.arange(18 * 1024) + 1)),
        ("ragged", *one(_sorted(r(0, 3000, 17 * 1024 + 1)),
                        _sorted(r(0, 3000, 19 * 1024 - 6), np.full(5, BIG)))),
        ("batch-of-three", batch_a, batch_b),
    ]


MERGE_PATH_CASES = _merge_path_cases()


def _padded(x, fill):
    return np.pad(x, ((0, 0), (0, -x.shape[1] % 1024)), constant_values=fill)


def _for_each_problem(fn, *xs):
    if xs[0].shape[0] == 1:
        return [np.asarray(o)[None] for o in fn(*(jnp.asarray(x[0]) for x in xs))]
    return [np.asarray(o) for o in jax.vmap(fn)(*(jnp.asarray(x) for x in xs))]


@pytest.mark.parametrize("name,a,b", MERGE_PATH_CASES, ids=[c[0] for c in MERGE_PATH_CASES])
def test_merge_path_kernels_match_ref(name, a, b):
    """Both kernels are bit-identical to the jnp reference on skewed, sentinel,
    disjoint and batched inputs, and no schedule has more steps than its static
    bound, which is below the dense sweep's."""
    from repro.kernels import merge_join as mj

    counts = lambda up: _for_each_problem(
        lambda x, y: merge_join_counts(x, y, use_pallas=up), a, b)
    (lo, up), (lo_r, up_r) = counts(True), counts(False)
    np.testing.assert_array_equal(lo, lo_r)
    np.testing.assert_array_equal(up, up_r)

    # the pair list of the probe, built as local_sorted_join builds it
    n_match = np.where(a < BIG, up - lo, 0)
    starts = (np.cumsum(n_match, axis=1) - n_match).astype(np.int32)
    cap_out = 20 * 1024 - 500
    pairs = lambda use: _for_each_problem(
        lambda lw, st: merge_join_pairs(lw, st, cap_out, use_pallas=use), lo, starts)
    for k, r in zip(pairs(True), pairs(False)):
        np.testing.assert_array_equal(k, r)

    a_p, b_p = _padded(a, BIG), _padded(b, BIG)
    na, nb, nt = a_p.shape[1] // 1024, b_p.shape[1] // 1024, -(-cap_out // 1024)
    _, active = mj.counts_schedule(jnp.asarray(a_p), jnp.asarray(b_p))
    assert mj.counts_steps(na, nb) < na * nb
    assert np.all(np.asarray(active) <= mj.counts_steps(na, nb)), (active, na, nb)
    _, active = mj.pairs_schedule(jnp.asarray(_padded(starts, BIG)), nt * 1024)
    assert mj.pairs_steps(nt, na) < nt * na
    assert np.all(np.asarray(active) <= mj.pairs_steps(nt, na)), (active, nt, na)


def test_problems_beyond_smem_split_across_calls(monkeypatch):
    """A batch whose schedules outgrow SMEM_STEPS runs in several kernel calls,
    with the same answers."""
    from repro.kernels import merge_join as mj

    (_, a, b), = [c for c in MERGE_PATH_CASES if c[0] == "batch-of-three"]
    steps = mj.counts_steps(-(-a.shape[1] // 1024), -(-b.shape[1] // 1024))
    monkeypatch.setattr(mj, "SMEM_STEPS", 2 * steps)          # two problems a call
    counts = lambda up: jax.vmap(lambda x, y: merge_join_counts(x, y, use_pallas=up))(
        jnp.asarray(a), jnp.asarray(b))
    for k, r in zip(counts(True), counts(False)):
        np.testing.assert_array_equal(np.asarray(k), np.asarray(r))


def test_one_executable_per_shape():
    """Keys of one shape and different distributions run one executable of each
    kernel: no key value reaches a grid extent or a static argument."""
    compiles = []

    def on_compile(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kwargs.get("fun_name"))

    rng = np.random.default_rng(3)
    inputs = [(np.sort(rng.integers(0, 10**6, 17003)), np.sort(rng.integers(0, 10**6, 19007))),
              (np.full(17003, 4), np.sort(rng.integers(0, 9, 19007))),
              (np.sort(rng.integers(0, 50, 17003)), np.full(19007, BIG))]
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        for a, b in inputs:
            lo, up = merge_join_counts(jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32))
            n_match = np.asarray(up) - np.asarray(lo)
            starts = jnp.asarray(np.cumsum(n_match) - n_match, jnp.int32)
            jax.block_until_ready(merge_join_pairs(lo, starts, 20001))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert compiles.count("jit(merge_join_counts)") == 1, compiles
    assert compiles.count("jit(merge_join_pairs)") == 1, compiles


def test_probe_schedule_span_at_trace_time(monkeypatch):
    """Tracing a probe of a new shape opens one kernel.probe_schedule span with
    the static schedule length; calling it again traces nothing."""
    from repro import obs

    opened = []
    inner = obs.span

    def span(name, **args):
        opened.append((name, args))
        return inner(name, **args)

    monkeypatch.setattr(obs, "span", span)
    a = jnp.zeros((16 * 1024 + 5,), jnp.int32)
    b = jnp.zeros((17 * 1024 + 7,), jnp.int32)
    for _ in range(2):
        merge_join_counts(a, b)
    assert opened == [("kernel.probe_schedule",
                       dict(problems=1, n=17 * 1024, m=18 * 1024, steps=68, dense_steps=306))]
    opened.clear()
    merge_join_pairs(a, a, 16 * 1024)
    assert opened == [("kernel.probe_schedule",
                       dict(problems=1, n=17 * 1024, cap_out=16 * 1024, steps=32, dense_steps=272))]


# ---------------------------------------------------------------------------
# hash_partition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1024, 4096, 1000, 2500])
@pytest.mark.parametrize("parts", [1, 4, 16, 8, 64, 256])
def test_hash_partition_matches_ref(n, parts):
    rng = np.random.default_rng(n * parts)
    keys = rng.integers(-(2**62), 2**62, n).astype(np.int64)
    part, hist = hash_partition(jnp.asarray(keys), parts)
    part_ref, hist_ref = kref.hash_partition_ref(fold64(jnp.asarray(keys)), parts, tile=1)
    np.testing.assert_array_equal(np.asarray(part), np.asarray(part_ref).reshape(-1))
    np.testing.assert_array_equal(
        np.asarray(hist), np.bincount(np.asarray(part), minlength=parts)
    )
    assert int(np.asarray(hist).sum()) == n


def _pack_check(keys, count, parts):
    """Semantic contract of the fused pack: rows before ``count`` carry their
    hash partition and a stable in-partition rank; rows at or past ``count``
    are ghosted to partition id ``parts``."""
    part, slot, send = hash_partition_pack(jnp.asarray(keys), count, parts)
    part, slot, send = np.asarray(part), np.asarray(slot), np.asarray(send)
    ref_part, _ = hash_partition(jnp.asarray(keys), parts)
    ref_part = np.asarray(ref_part)
    n = len(keys)
    assert np.all(part[count:] == parts)
    np.testing.assert_array_equal(part[:count], ref_part[:count])
    for pid in range(parts):
        ranks = slot[:count][part[:count] == pid]
        np.testing.assert_array_equal(np.sort(ranks), np.arange(len(ranks)))
        assert send[pid] == len(ranks)
    assert int(send.sum()) == int(count)
    return part, slot, send


@pytest.mark.parametrize("n,parts", [
    (1024, 8), (4096, 64), (1000, 16),
    # ragged multi-tile lists: the running base crosses tile boundaries
    (2500, 1), (3000, 4), (5000, 16),
])
@pytest.mark.parametrize("frac", [1.0, 0.7])
def test_hash_partition_pack_matches_ref(n, parts, frac):
    rng = np.random.default_rng(n * parts)
    keys = rng.integers(-(2**62), 2**62, n).astype(np.int64)
    count = int(n * frac)
    out_k = hash_partition_pack(jnp.asarray(keys), count, parts, use_pallas=True)
    out_r = hash_partition_pack(jnp.asarray(keys), count, parts, use_pallas=False)
    for k, r in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
    _pack_check(keys, count, parts)


if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        seed=st.integers(0, 1_000),
        n=st.integers(1, 2000),
        parts=st.sampled_from([1, 2, 4, 8, 16, 32, 128]),
        frac=st.floats(0.0, 1.0),
    )
    def test_hash_partition_pack_property(seed, n, parts, frac):
        rng = np.random.default_rng(seed)
        keys = rng.integers(-(2**62), 2**62, n).astype(np.int64)
        count = int(n * frac)
        out_k = hash_partition_pack(jnp.asarray(keys), count, parts, use_pallas=True)
        out_r = hash_partition_pack(jnp.asarray(keys), count, parts, use_pallas=False)
        for k, r in zip(out_k, out_r):
            np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
        _pack_check(keys, count, parts)

else:

    @pytest.mark.skip(reason="property test needs the optional hypothesis extra")
    def test_hash_partition_pack_property():
        pass


def test_hash_partition_balanced():
    """2-universal-ish mix: no partition should be grossly overloaded on uniform keys."""
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 2**62, 1 << 14).astype(np.int64)
    _, hist = hash_partition(jnp.asarray(keys), 16)
    h = np.asarray(hist)
    assert h.max() < 2.0 * h.mean()


# ---------------------------------------------------------------------------
# stage batching and platform choice
# ---------------------------------------------------------------------------


def _sorted_batch(rng, shape, dom):
    return jnp.asarray(np.sort(rng.integers(0, dom, shape), axis=-1).astype(np.int32))


def _batched_cases():
    rng = np.random.default_rng(5)
    a, b = _sorted_batch(rng, (3, 1500), 60), _sorted_batch(rng, (3, 2100), 60)
    counts = rng.integers(0, 3, (3, 1100)).astype(np.int32)
    starts = jnp.asarray(np.cumsum(counts, axis=1) - counts)
    lower = jnp.asarray(rng.integers(0, 100, (3, 1100)).astype(np.int32))
    keys = jnp.asarray(rng.integers(0, 2**30, (3, 2500)).astype(np.int32))
    valid = jnp.asarray([2500, 7, 1800], jnp.int32)
    cases = [
        ("merge_join_counts", lambda up: jax.vmap(
            lambda x, y: merge_join_counts(x, y, use_pallas=up))(a, b)),
        ("merge_join_pairs", lambda up: jax.vmap(
            lambda lo, st: merge_join_pairs(lo, st, 2500, use_pallas=up))(lower, starts)),
        # vmap of vmap: both mapped axes fold into the kernel's problem axis
        ("merge_join_counts-nested", lambda up: jax.vmap(jax.vmap(
            lambda x, y: merge_join_counts(x, y, use_pallas=up)))(a[None], b[None])),
    ]
    for parts in (1, 4, 16):
        cases += [
            (f"hash_partition-p{parts}", lambda up, parts=parts: jax.vmap(
                lambda k: hash_partition(k, parts, use_pallas=up))(keys)),
            (f"hash_partition_pack-p{parts}", lambda up, parts=parts: jax.vmap(
                lambda k, c: hash_partition_pack(k, c, parts, use_pallas=up))(keys, valid)),
        ]
    # an unbatched operand broadcasts across the mapped axis
    cases.append(("merge_join_counts-broadcast", lambda up: jax.vmap(
        lambda x: merge_join_counts(x, b[0], use_pallas=up))(a)))
    return cases


BATCHED_CASES = _batched_cases()


@pytest.mark.parametrize("name,run", BATCHED_CASES, ids=[c[0] for c in BATCHED_CASES])
def test_vmapped_kernels_match_ref(name, run):
    """The dataplane vmaps every kernel over a bucket's stages; under vmap the
    kernels fold the mapped axis into their problem axis (one grid row per
    problem) and must stay bit-identical to the vmapped jnp reference."""
    for k, r in zip(run(True), run(False)):
        np.testing.assert_array_equal(np.asarray(k), np.asarray(r))


def test_kernel_choice_follows_platform(monkeypatch):
    from repro.kernels import ops

    assert ops.probe_use_pallas() is (jax.default_backend() == "tpu")
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops.probe_use_pallas() is True
    monkeypatch.setattr(ops, "on_tpu", lambda: False)
    assert ops.probe_use_pallas() is False


def test_importing_repro_starts_no_backend():
    """The kernel choice is made when a kernel is traced: importing the
    package (every module of the join engine) must not start a JAX backend,
    which on a TPU host would take the chip."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    code = textwrap.dedent("""
        import importlib, pkgutil
        import repro
        from jax._src import xla_bridge
        for m in pkgutil.walk_packages(repro.__path__, "repro."):
            if m.name.startswith("repro.launch"):
                continue  # the LM launcher configures XLA flags as it imports
            importlib.import_module(m.name)
        assert not xla_bridge.backends_are_initialized(), "a backend started"
    """)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(
        filter(None, [src, os.environ.get("PYTHONPATH")]))}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


# ---------------------------------------------------------------------------
# ssd
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bh,s,p,n,chunk", [
    (2, 64, 16, 32, 16),
    (3, 128, 32, 64, 32),
    (1, 64, 64, 128, 64),
])
def test_ssd_kernel_matches_recurrence(bh, s, p, n, chunk):
    rng = np.random.default_rng(bh * s + p)
    x = rng.normal(size=(bh, s, p)).astype(np.float32)
    dt = rng.uniform(0.01, 0.2, size=(bh, s)).astype(np.float32)
    a = -rng.uniform(0.5, 2.0, size=(bh,)).astype(np.float32)
    b = rng.normal(size=(bh, s, n)).astype(np.float32)
    c = rng.normal(size=(bh, s, n)).astype(np.float32)

    y_k, st_k = ssd_chunk(*map(jnp.asarray, (x, dt, a, b, c)), chunk=chunk)

    # oracle: naive per-token recurrence (ssd_reference vectorizes `a` per head, not
    # per batch — run one (batch·head) slice at a time with H=1, groups=1)
    for i in range(bh):
        y_i, st_i = ssd_reference(
            jnp.asarray(x[i : i + 1, :, None, :]),
            jnp.asarray(dt[i : i + 1, :, None]),
            jnp.asarray(a[i : i + 1]),
            jnp.asarray(b[i : i + 1, :, None, :]),
            jnp.asarray(c[i : i + 1, :, None, :]),
        )
        np.testing.assert_allclose(
            np.asarray(y_k[i]), np.asarray(y_i[0, :, 0, :]), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(st_k[i]), np.asarray(st_i[0, 0]), rtol=2e-4, atol=2e-4
        )


def test_ssd_kernel_matches_ops_oracle():
    """Pallas path ≡ the jnp chunked oracle in ops.py (same chunking)."""
    rng = np.random.default_rng(3)
    bh, s, p, n, chunk = 2, 128, 16, 32, 32
    args = (
        rng.normal(size=(bh, s, p)).astype(np.float32),
        rng.uniform(0.01, 0.2, size=(bh, s)).astype(np.float32),
        -rng.uniform(0.5, 2.0, size=(bh,)).astype(np.float32),
        rng.normal(size=(bh, s, n)).astype(np.float32),
        rng.normal(size=(bh, s, n)).astype(np.float32),
    )
    jargs = tuple(map(jnp.asarray, args))
    y1, s1 = ssd_chunk(*jargs, chunk=chunk, use_pallas=True)
    y2, s2 = ssd_chunk(*jargs, chunk=chunk, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5, atol=1e-5)
