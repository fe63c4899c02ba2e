"""Public jit'd wrappers for the Pallas kernels.

On a TPU the kernels compile to Mosaic; on any other backend they run under the
Pallas interpreter (which executes the kernel body faithfully, including the
grid/BlockSpec schedule). The platform is asked when a wrapper is called —
that is, when the caller is traced — never when the module is imported, since
asking starts a JAX backend and, on a TPU host, takes the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import hash_partition as _hp
from . import merge_join as _mj
from . import ssd as _ssd
from . import ref as _ref


def on_tpu() -> bool:
    """Whether the default JAX backend is a TPU (starts the backend if needed)."""
    return jax.default_backend() == "tpu"


def probe_use_pallas() -> bool:
    """Whether dataplane shard_map bodies should trace the Pallas kernels.

    On a TPU the kernels compile to Mosaic — always use them. Elsewhere they
    would run under the Pallas *interpreter*, which is bit-identical to the
    jnp reference (asserted in tests/test_kernels.py) but traces to a much
    larger graph: the reference path compiles ~2× faster and runs ~3× faster
    on CPU, which matters when an executor fuses hundreds of stages into a
    handful of executables. Tests that want interpret-mode Pallas pass
    ``use_pallas=True`` to the wrappers below."""
    return on_tpu()


def _kernel_entry(*static: str):
    """jit ``fn`` with ``static`` argnames plus an ``interpret`` flag that the
    returned wrapper fills from the platform at call time (so the choice is
    part of jit's cache key, not a module constant)."""

    def wrap(fn):
        jitted = jax.jit(fn, static_argnames=static + ("interpret",))

        @functools.wraps(fn)
        def call(*args, **kwargs):
            return jitted(*args, interpret=not on_tpu(), **kwargs)

        return call

    return wrap


@_kernel_entry("causal", "bq", "bk", "use_pallas")
def flash_attention(q, k, v, causal: bool = True, bq: int = 128, bk: int = 128,
                    use_pallas: bool = True, *, interpret: bool = False):
    """Online-softmax attention: q (BH,Sq,D), k/v (BH,Sk,D) → (BH,Sq,D)."""
    if not use_pallas:
        return _ref.flash_attention_ref(q, k, v, causal=causal)
    bq = min(bq, q.shape[1])
    bk = min(bk, k.shape[1])
    return _fa.flash_attention_pallas(
        q, k, v, causal=causal, bq=bq, bk=bk, interpret=interpret
    )


def _one_problem(kernel, *args):
    """Run ``kernel`` — which takes and returns arrays with a leading problem
    axis — on one problem. Under ``vmap`` the mapped axis is folded into the
    problem axis, so the kernel keeps its 1-D blocks and gains a grid row per
    problem; Pallas' own batching rule would give 2-D blocks of one row, which
    the TPU compiler refuses."""

    @jax.custom_batching.custom_vmap
    def run(*xs):
        return kernel(*xs)

    @run.def_vmap
    def _fold(axis_size, in_batched, *xs):
        xs = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
              for x, b in zip(xs, in_batched)]
        outs = run(*(x.reshape((-1,) + x.shape[2:]) for x in xs))
        outs = tuple(o.reshape((axis_size, -1) + o.shape[1:]) for o in outs)
        return outs, (True,) * len(outs)

    return tuple(o[0] for o in run(*(x[None] for x in args)))


def fold64(keys: jax.Array) -> jax.Array:
    """Fold int64 join keys to int32 lanes for the TPU kernels (xor-fold)."""
    k = keys.astype(jnp.uint64)
    return (jnp.uint32(0xFFFFFFFF) & (k ^ (k >> 32)).astype(jnp.uint32)).astype(jnp.int32)


def _pad_to(x: jax.Array, mult: int, fill) -> jax.Array:
    pad = (-x.shape[0]) % mult
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    return x


@_kernel_entry("use_pallas")
def merge_join_counts(a_keys: jax.Array, b_keys: jax.Array, use_pallas: bool = True,
                      *, interpret: bool = False):
    """Sorted int32 keys → (lower, upper) match ranges of each a in b.
    Handles arbitrary lengths by sentinel padding (INT32_MAX sorts last)."""
    n, m = a_keys.shape[0], b_keys.shape[0]
    if not use_pallas:
        return _ref.merge_join_counts_ref(a_keys, b_keys)
    big = jnp.iinfo(jnp.int32).max
    a_p = _pad_to(a_keys, _mj.BLOCK, big)
    b_p = _pad_to(b_keys, _mj.BLOCK, big)
    # the schedule is built outside the per-problem kernel call, so that a
    # vmapped probe traces it once
    sched, _ = _mj.counts_schedule(a_p[None], b_p[None])
    lower, upper = _one_problem(
        functools.partial(_mj.merge_join_counts_pallas, interpret=interpret), sched[0], a_p, b_p
    )
    # padded B sentinels never compare < or <= real keys except vs the padded A
    # sentinels; trim A and clamp to the true M.
    return jnp.minimum(lower[:n], m), jnp.minimum(upper[:n], m)


@_kernel_entry("cap_out", "use_pallas")
def merge_join_pairs(lower: jax.Array, starts: jax.Array, cap_out: int,
                     use_pallas: bool = True, *, interpret: bool = False):
    """Expand sorted-merge match ranges to the flat (a_idx, b_idx) pair list.

    lower (N,) int32: per-A-key lower bound in sorted B; starts (N,) int32:
    exclusive prefix sum of per-key match counts (starts[0] == 0 — guaranteed
    when starts = cumsum(counts) - counts). Output slot t in [0, cap_out) maps
    to the key a_idx[t] = max{i : starts[i] <= t} and b_idx[t] = lower[a_idx] +
    (t - starts[a_idx]); slots at or past the true total alias the last key, so
    callers must mask by the total count. a_idx is clipped to [0, N-1]; b_idx
    is returned unclipped."""
    n = starts.shape[0]
    if n == 0:
        z = jnp.zeros((cap_out,), jnp.int32)
        return z, z
    if not use_pallas:
        return _ref.merge_join_pairs_ref(lower, starts, cap_out)
    big = jnp.iinfo(jnp.int32).max
    starts_p = _pad_to(starts.astype(jnp.int32), _mj.BLOCK, big)
    # lower - starts, differenced within each block (the block's first key keeps
    # its own value): the kernel's telescoping sums end at the last hit
    v = _pad_to(lower.astype(jnp.int32) - starts.astype(jnp.int32), _mj.BLOCK, 0)
    v = v.reshape(-1, _mj.BLOCK)
    dd = (v - jnp.pad(v[:, :-1], ((0, 0), (1, 0)))).reshape(-1)
    cap_p = -(-cap_out // _mj.BLOCK) * _mj.BLOCK
    sched, _ = _mj.pairs_schedule(starts_p[None], cap_p)
    a_idx, b_idx = _one_problem(
        functools.partial(_mj.merge_join_pairs_pallas, cap_out=cap_p, interpret=interpret),
        sched[0], starts_p, dd,
    )
    return jnp.clip(a_idx[:cap_out], 0, n - 1), b_idx[:cap_out]


@_kernel_entry("n_parts", "use_pallas")
def hash_partition_pack(keys: jax.Array, count: jax.Array, n_parts: int,
                        use_pallas: bool = True, *, interpret: bool = False):
    """Fused exchange send side: → (part (N,) int32 with n_parts marking rows at or
    past `count`, slot (N,) stable in-partition rank, send_counts (n_parts,))."""
    n = keys.shape[0]
    if keys.dtype in (jnp.int64, jnp.uint64):
        keys = fold64(keys)
    count = jnp.asarray(count, jnp.int32).reshape(())
    if not use_pallas:
        part, slot, hist = _ref.hash_partition_pack_ref(keys, count, n_parts, tile=n)
        return part, slot, hist.sum(axis=0)
    keys_p = _pad_to(keys, _hp.BLOCK, 0)
    # padding rows sit past `count` (count <= n), so the kernel ghosts them
    part, slot, hist = _one_problem(
        functools.partial(_hp.hash_partition_pack_pallas, n_parts=n_parts, interpret=interpret),
        keys_p, count,
    )
    return part[:n], slot[:n], hist.sum(axis=0)


@_kernel_entry("n_parts", "use_pallas")
def hash_partition(keys: jax.Array, n_parts: int, use_pallas: bool = True,
                   *, interpret: bool = False):
    """→ (part (N,), hist (P,)) partition ids + global histogram."""
    n = keys.shape[0]
    if keys.dtype in (jnp.int64, jnp.uint64):
        keys = fold64(keys)
    if not use_pallas:
        part, hist = _ref.hash_partition_ref(keys, n_parts, tile=n)
        return part, hist.sum(axis=0)
    keys_p = _pad_to(keys, _hp.BLOCK, 0)
    part, hist = _one_problem(
        functools.partial(_hp.hash_partition_pallas, n_parts=n_parts, interpret=interpret),
        keys_p,
    )
    part = part[:n]
    hist = hist.sum(axis=0)
    if keys_p.shape[0] != n:  # remove the padding keys' contribution (they hash as 0)
        pad_part, _ = _ref.hash_partition_ref(
            jnp.zeros((keys_p.shape[0] - n,), jnp.int32), n_parts, tile=1
        )
        hist = hist - jnp.bincount(pad_part, length=n_parts).astype(hist.dtype)
    return part, hist


@_kernel_entry("chunk", "use_pallas")
def ssd_chunk(x, dt, a, b_ssm, c_ssm, chunk: int = 64, use_pallas: bool = True,
              *, interpret: bool = False):
    """(BH,S,P) SSD over chunks → (y, final_state). fp32."""
    if not use_pallas:
        # jnp oracle: sequential over chunks via the per-chunk reference
        bh, s, p = x.shape
        n = b_ssm.shape[-1]
        nc = s // chunk

        def per_bh(xb, dtb, ab, bb, cb):
            def step(state, idx):
                sl = lambda arr: jax.lax.dynamic_slice_in_dim(arr, idx * chunk, chunk)
                y, state = _ref.ssd_chunk_ref(sl(xb), sl(dtb), ab, sl(bb), sl(cb), state)
                return state, y

            state0 = jnp.zeros((p, n), jnp.float32)
            state, ys = jax.lax.scan(step, state0, jnp.arange(nc))
            return ys.reshape(s, p), state

        return jax.vmap(per_bh)(x, dt, a, b_ssm, c_ssm)
    return _ssd.ssd_chunk_pallas(x, dt, a, b_ssm, c_ssm, chunk, interpret=interpret)
