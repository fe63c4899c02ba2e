"""Kernel micro-benches: the pure-jnp oracles timed on CPU (wall time here is a CPU
number — the TPU story is the §Roofline analysis), plus interpreter-mode runs of the
Pallas kernels to keep their schedule exercised end-to-end.

The jnp-path cases (the production CPU hot path — `probe_use_pallas()` is False
off-TPU) are snapshotted to ``BENCH_kernels.json`` at the repo root (override
with ``BENCH_KERNELS_RESULTS_PATH``) in the same per-case schema as the other
benches, so ``compare_bench.py --bench kernels`` gates warm regressions in CI.
Interpret-mode Pallas timings are report-only: the interpreter is orders of
magnitude slower and exists to validate the kernel schedule, not to be fast.
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import (
    flash_attention,
    hash_partition,
    hash_partition_pack,
    merge_join_counts,
    merge_join_pairs,
    ssd_chunk,
)

RESULTS_PATH = Path(
    os.environ.get(
        "BENCH_KERNELS_RESULTS_PATH",
        Path(__file__).resolve().parents[1] / "BENCH_kernels.json",
    )
)


def _time(fn, *args, reps=3):
    t0 = time.time()
    out = fn(*args)  # compile/warm
    jax.block_until_ready(out)
    cold = (time.time() - t0) * 1e6
    samples = []
    for _ in range(reps):
        t0 = time.time()
        out = fn(*args)
        jax.block_until_ready(out)
        samples.append((time.time() - t0) * 1e6)
    return min(samples), cold


def run(report):
    rng = np.random.default_rng(0)
    records = []

    def case(name, us, cold_us, derived=""):
        # compare_bench schema: the jnp path is the gated warm figure; kernels
        # have no retry loop, so the retries column is structurally zero
        records.append(
            {
                "case": name,
                "dataplane_warm_us": round(us, 1),
                "dataplane_cold_us": round(cold_us, 1),
                "dataplane_retries": 0,
            }
        )
        report(f"kernels/{name}", us, derived)

    a = jnp.asarray(np.sort(rng.integers(0, 10_000, 4096).astype(np.int32)))
    b = jnp.asarray(np.sort(rng.integers(0, 10_000, 16_384).astype(np.int32)))
    us, cold = _time(lambda a, b: merge_join_counts(a, b, use_pallas=False), a, b)
    case("merge_join/ref_4k_16k", us, cold, "jnp searchsorted oracle")
    us, _ = _time(lambda a, b: merge_join_counts(a, b, use_pallas=True), a, b)
    report("kernels/merge_join/pallas_interp_4k_16k", us, "interpret=True (CPU)")

    # pair-emission expansion (the warm local-join hot path): counts → starts
    # exactly as local_sorted_join computes them
    lo, up = merge_join_counts(a, b, use_pallas=False)
    counts = up - lo
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    cap_out = 1 << 14
    us, cold = _time(
        lambda l, s: merge_join_pairs(l, s, cap_out, use_pallas=False),
        lo.astype(jnp.int32), starts,
    )
    case("merge_join_pairs/ref_4k_cap16k", us, cold, "jnp searchsorted expansion")
    us, _ = _time(
        lambda l, s: merge_join_pairs(l, s, cap_out, use_pallas=True),
        lo.astype(jnp.int32), starts,
    )
    report("kernels/merge_join_pairs/pallas_interp_4k_cap16k", us, "interpret=True (CPU)")

    keys = jnp.asarray(rng.integers(0, 2**62, 1 << 14).astype(np.int64))
    us, cold = _time(lambda k: hash_partition(k, 64, use_pallas=False), keys)
    case("hash_partition/ref_16k_p64", us, cold, "jnp oracle")
    us, _ = _time(lambda k: hash_partition(k, 64, use_pallas=True), keys)
    report("kernels/hash_partition/pallas_interp_16k_p64", us, "interpret=True (CPU)")

    # fused partition+pack (the exchange send-buffer producer)
    cnt = jnp.int32((1 << 14) - 37)
    us, cold = _time(lambda k: hash_partition_pack(k, cnt, 8, use_pallas=False), keys)
    case("hash_partition_pack/ref_16k_p8", us, cold, "jnp fused pack oracle")
    us, _ = _time(lambda k: hash_partition_pack(k, cnt, 8, use_pallas=True), keys)
    report("kernels/hash_partition_pack/pallas_interp_16k_p8", us, "interpret=True (CPU)")

    bh, s, p, n = 4, 512, 64, 128
    args = (
        jnp.asarray(rng.normal(size=(bh, s, p)).astype(np.float32)),
        jnp.asarray(rng.uniform(0.01, 0.2, size=(bh, s)).astype(np.float32)),
        jnp.asarray(-rng.uniform(0.5, 2.0, size=(bh,)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(bh, s, n)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(bh, s, n)).astype(np.float32)),
    )
    us, cold = _time(lambda *a: ssd_chunk(*a, chunk=64, use_pallas=False), *args)
    case("ssd/ref_bh4_s512", us, cold, "jnp chunked oracle")
    us, _ = _time(lambda *a: ssd_chunk(*a, chunk=64, use_pallas=True), *args)
    report("kernels/ssd/pallas_interp_bh4_s512", us, "interpret=True (CPU)")

    q = jnp.asarray(rng.normal(size=(4, 512, 64)).astype(np.float32))
    kk = jnp.asarray(rng.normal(size=(4, 512, 64)).astype(np.float32))
    vv = jnp.asarray(rng.normal(size=(4, 512, 64)).astype(np.float32))
    us, cold = _time(lambda a, b, c: flash_attention(a, b, c, use_pallas=False), q, kk, vv)
    case("flash_attn/ref_bh4_s512_d64", us, cold, "jnp softmax oracle")
    us, _ = _time(lambda a, b, c: flash_attention(a, b, c, use_pallas=True), q, kk, vv)
    report("kernels/flash_attn/pallas_interp_bh4_s512_d64", us, "interpret=True (CPU)")

    snapshot = {"bench": "kernels", "device_count": len(jax.devices()), "cases": records}
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
    report("kernels/json", 0.0, f"snapshot {len(history)} appended to {RESULTS_PATH.name}")


if __name__ == "__main__":
    from repro.mpc.executors import enable_compile_cache

    enable_compile_cache()
    run(lambda name, us, derived="": print(f"{name},{us:.1f},{derived}"))
