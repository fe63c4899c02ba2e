"""Sorted-key join probe as a Pallas TPU kernel.

TPU adaptation of the per-machine hash-join probe (DESIGN.md §2.4): GPU hash probes
rely on shared-memory scatter; on TPU we sort both sides (XLA sort is an efficient
bitonic network on TPU) and compute, for every key of A, its match range [lower, upper)
in B with a **tiled compare-reduce**: an A-tile (BLOCK_A keys) sits in VMEM while the
kernel marches over B in BLOCK_B-sized VMEM blocks, accumulating
    lower[i] += Σ_j [b_j <  a_i]      upper[i] += Σ_j [b_j <= a_i]
— branch-free VPU work with perfectly sequential HBM reads (no data-dependent control
flow, which the TPU vector unit cannot do). The compare-reduce does O(N·M / BLOCK)
lane-ops but runs at full vector width; for the |B| ranges the engine feeds it
(capacity-bounded partitions), it beats a gather-based binary search on TPU.

Grid: (n_problems, n_a_tiles, n_b_blocks); B blocks iterate in the minor grid dimension
so the accumulators live in the output block across the B sweep (revisited output
block). The leading dimension runs a batch of independent probes (the dataplane
vmaps the probe over the stages of a bucket).

Every block is 1024 keys: XLA tiles a 1-D int32 array in HBM by 1024 elements
(layout ``T(1024)``), and Mosaic refuses a 1-D block whose tiling differs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_A = 1024
BLOCK_B = 1024
BLOCK_T = 1024


def _kernel(a_ref, b_ref, lower_ref, upper_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        lower_ref[...] = jnp.zeros_like(lower_ref)
        upper_ref[...] = jnp.zeros_like(upper_ref)

    a = a_ref[...]          # (BLOCK_A,)
    b = b_ref[...]          # (BLOCK_B,)
    lt = (b[None, :] < a[:, None]).astype(jnp.int32)
    le = (b[None, :] <= a[:, None]).astype(jnp.int32)
    lower_ref[...] += lt.sum(axis=1)
    upper_ref[...] += le.sum(axis=1)


def _pairs_kernel(starts_ref, dl_ref, ds_ref, a_ref, b_ref, st_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        a_ref[...] = jnp.zeros_like(a_ref)
        b_ref[...] = jnp.zeros_like(b_ref)
        st_ref[...] = jnp.zeros_like(st_ref)

    i = pl.program_id(1)
    t = i * BLOCK_T + jax.lax.broadcasted_iota(jnp.int32, (BLOCK_T, 1), 0)[:, 0]
    s = starts_ref[...]     # (BLOCK_A,) sorted ascending, sentinel-padded
    hit = s[None, :] <= t[:, None]
    # telescoping compare-reduce: with K(t) = max{j : starts[j] <= t},
    #   Σ_j hit          = K + 1          (starts is nondecreasing)
    #   Σ_j Δlower · hit = lower[K]       (Δ telescopes regardless of sign)
    #   Σ_j Δstarts· hit = starts[K]
    a_ref[...] += hit.astype(jnp.int32).sum(axis=1)
    b_ref[...] += jnp.where(hit, dl_ref[...][None, :], 0).sum(axis=1)
    st_ref[...] += jnp.where(hit, ds_ref[...][None, :], 0).sum(axis=1)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        a_ref[...] = a_ref[...] - 1                     # a_idx = K
        b_ref[...] = b_ref[...] + (t - st_ref[...])     # b_idx = lower[K] + (t - starts[K])


def merge_join_pairs_pallas(
    starts: jax.Array, dlower: jax.Array, dstarts: jax.Array,
    cap_out: int, interpret: bool = True,
):
    """Expand per-key match ranges into the flat (a_idx, b_idx) pair list, for S
    independent problems at once.

    starts (S, N) int32: per problem, the exclusive prefix sum of per-key match
    counts (starts[:, 0] must be 0; pad with +2^31-1 sentinels). dlower/dstarts
    (S, N): first differences of the per-key `lower` bound and of `starts` (pad
    with 0). For output slot t in [0, cap_out): a_idx[t] = max{i : starts[i] <= t},
    b_idx[t] = lower[a_idx] + (t - starts[a_idx]). Returns (a_idx, b_idx,
    starts_at) int32 (S, cap_out); starts_at is a scratch output (starts[a_idx]
    accumulator) callers discard. The problems are laid end to end in 1-D
    arrays, one grid row each, so every block is a 1-D tile of 1024.
    """
    n_b, n = starts.shape
    assert n % BLOCK_A == 0 and cap_out % BLOCK_T == 0, (n, cap_out)
    na, nt = n // BLOCK_A, cap_out // BLOCK_T
    in_spec = pl.BlockSpec((BLOCK_A,), lambda k, i, j: (k * na + j,))
    out_spec = pl.BlockSpec((BLOCK_T,), lambda k, i, j: (k * nt + i,))
    outs = pl.pallas_call(
        _pairs_kernel,
        grid=(n_b, nt, na),
        in_specs=[in_spec] * 3,
        out_specs=[out_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((n_b * cap_out,), jnp.int32)] * 3,
        interpret=interpret,
    )(starts.reshape(-1), dlower.reshape(-1), dstarts.reshape(-1))
    return tuple(o.reshape(n_b, cap_out) for o in outs)


def merge_join_counts_pallas(
    a_keys: jax.Array, b_keys: jax.Array, interpret: bool = True
):
    """a_keys (S, N), b_keys (S, M) int32, each row sorted ascending (padding:
    +2^31-1 sentinels work because they never compare below real keys): S
    independent probes. Returns (lower, upper) int32 (S, N). The probes are laid
    end to end in 1-D arrays, one grid row each, so every block is a 1-D tile."""
    n_b, n = a_keys.shape
    m = b_keys.shape[1]
    assert n % BLOCK_A == 0 and m % BLOCK_B == 0, (n, m)
    na, nb = n // BLOCK_A, m // BLOCK_B
    out_spec = pl.BlockSpec((BLOCK_A,), lambda k, i, j: (k * na + i,))
    lower, upper = pl.pallas_call(
        _kernel,
        grid=(n_b, na, nb),
        in_specs=[
            pl.BlockSpec((BLOCK_A,), lambda k, i, j: (k * na + i,)),
            pl.BlockSpec((BLOCK_B,), lambda k, i, j: (k * nb + j,)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((n_b * n,), jnp.int32)] * 2,
        interpret=interpret,
    )(a_keys.reshape(-1), b_keys.reshape(-1))
    return lower.reshape(n_b, n), upper.reshape(n_b, n)
