"""Sorted-key join probe and pair expansion as Pallas TPU kernels, on a
merge-path schedule.

TPU adaptation of the per-machine hash-join probe (DESIGN.md §2.4): GPU hash probes
rely on shared-memory scatter; on TPU we sort both sides (XLA sort is an efficient
bitonic network on TPU) and compute, for every key of A, its match range [lower, upper)
in B with a **tiled compare-reduce**: an A tile (1024 keys) sits in VMEM against one
B block (1024 keys) per grid step, accumulating
    lower[i] += Σ_j [b_j <  a_i]      upper[i] += Σ_j [b_j <= a_i]
— branch-free VPU work over whole blocks, with no data-dependent control flow inside a
block, which the TPU vector unit cannot do.

**Schedule.** Both sides are sorted, so an A tile needs only the B blocks that its key
range reaches. For tile i with first key lo and last key hi, let L = #{b < lo} and
F = #{b <= hi}. Every B block before block ⌊L/1024⌋ holds keys below lo (each
counts 1024 to both bounds of every key of the tile), and every block after the one
holding position F-1 holds keys above hi (each counts 0). So the tile visits blocks
first = ⌊L/1024⌋ .. last = max(first, ⌊(F-1)/1024⌋), and its first step starts both
accumulators at 1024·first. A tile whose keys are all equal (a heavy key, or the
+2^31-1 sentinels that pad a side) visits only those two ends: the blocks between
hold that key alone, so the second step restarts upper at 1024·last, and its block
holds nothing below the key, so lower keeps what the first step counted. The pair
expansion is the same walk with the sides swapped: output tile t (slots T0 .. T1)
needs the A blocks from the one holding K(T0) to the one holding K(T1),
K(x) = max{j : starts[j] <= x}.

**Static bound.** The grid is (problems, S) with S a function of the shapes alone:
S = min(na·nb, 2·(na + nb - 1)) for the probe (na A tiles, nb B blocks) and
min(nt·na, nt + na - 1) for the expansion (nt output tiles). The bound holds with
duplicates and sentinels. For the probe, split each tile's positions into
[L, R) and [E, F) (R = #{b < hi}, E = #{b <= lo}): lo of a tile is at least hi of the
tile before, so each family of ranges is disjoint and in order, and two neighbours
share at most one block: each family reaches at most nb + na - 1 blocks in all. A tile
with lo < hi has E <= R, so its visit is exactly the blocks of [L, F) = [L, R) ∪ [E, F).
A tile with lo == hi, or with no B key in [lo, hi], has both ranges empty and takes at
most 2 steps, which is no more than the 2 blocks its ranges were allowed. For the
expansion, consecutive output tiles share at most one A block. A probe of at most
SWEEP_STEPS tile-block pairs, or whose bound is no shorter, keeps the dense sweep,
as a constant schedule.

The schedule is built on the device (``counts_schedule``, ``pairs_schedule``, pure
functions that also return the steps in use): ⌊L/1024⌋ is the number of B blocks
whose last key is below lo, ⌊(F-1)/1024⌋ + 1 the number whose first key is at or
below hi, and sums over the tiles that start at or before each step lay the tiles'
runs end to end, padded to S by repeating the last (tile, block) with the step
marked idle: no new DMA, nothing added. No value of a key reaches a grid extent or
a static argument, so every input of one shape runs one executable. It reaches the
kernel as scalar prefetch (SMEM), one int32 a step,
``tile << (block_bits + 3) | block << 3 | flags`` (COUNTS, FIRST, REBASE), as a 2-D
(problems, S) array. A tile's output block stays resident while its steps run
(they are contiguous).

Every block is 1024 keys: XLA tiles a 1-D int32 array in HBM by 1024 elements
(layout ``T(1024)``), and Mosaic refuses a 1-D block whose tiling differs.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs

BLOCK = 1024
#: the flags of a packed step: it counts (an idle step does not); it is its
#: tile's first (both bounds restart at its block's base); upper alone restarts
COUNTS, FIRST, REBASE = 1, 2, 4
FLAG_BITS = 3
#: int32 schedule steps one kernel call prefetches: half of a v5e core's 1 MiB of SMEM
SMEM_STEPS = 1 << 17
#: a probe of at most this many tile-block pairs sweeps them all: about a
#: millisecond of a v5e, less than a schedule costs to trace and lower in every
#: process that meets the shape
SWEEP_STEPS = 256


def _steps(dense: int, merge_path: int) -> int:
    return dense if dense <= max(merge_path, SWEEP_STEPS) else merge_path


def counts_steps(na: int, nb: int) -> int:
    """Static schedule length of the probe: na A tiles against nb B blocks."""
    return _steps(na * nb, 2 * (na + nb - 1))


def pairs_steps(nt: int, na: int) -> int:
    """Static schedule length of the expansion: nt output tiles over na A blocks."""
    return _steps(nt * na, nt + na - 1)


def _block_bits(n_tiles: int, n_blocks: int) -> int:
    """Bits of a packed step that hold the block; the tile sits above them."""
    bits = max(1, (n_blocks - 1).bit_length())
    if max(1, (n_tiles - 1).bit_length()) + bits + FLAG_BITS > 31:
        raise ValueError(f"{n_tiles} tiles x {n_blocks} blocks overflow an int32 step")
    return bits


def _runs(first, last, ends_only, n_steps: int, block_bits: int):
    """Steps of per-tile block runs, in tile order, padded to ``n_steps``, for
    each problem: first, last (problems, tiles) int32, ends_only bool. Tile i
    visits blocks first[i]..last[i], or only {first[i], last[i]} where ends_only[i].
    → (packed (problems, n_steps) int32, active steps (problems,)).

    Step s of tile i packs to ``h[i] + 8·s``, h[i] fixed by the tile's first
    step, plus FIRST on that step and, on the second step of an ends-only tile
    of two, a jump to ``last[i]`` and REBASE; so each step is a sum over the
    tiles that start at or before it. Every probe shape a process meets, the
    cold submit's included, traces, batches and lowers this anew, so it is
    written in few ``lax`` primitives: each ``jnp`` function would add a nested
    ``jit`` to trace and batch."""
    problems, n_tiles = first.shape
    span = lax.sub(last, first)
    two = lax.convert_element_type(lax.bitwise_and(ends_only, lax.gt(span, 0)), jnp.int32)
    lens = lax.add(lax.select(ends_only, two, span), 1)
    ends = lax.cumsum(lens, axis=1)
    starts = lax.sub(ends, lens)
    tile = lax.shift_left(lax.broadcasted_iota(jnp.int32, first.shape, 1), block_bits + FLAG_BITS)
    h = lax.add(tile, lax.add(lax.shift_left(lax.sub(first, starts), FLAG_BITS), COUNTS))
    jump = lax.sub(h, lax.pad(h, jnp.int32(0), ((0, 0, 0), (1, -1, 0))))
    second = lax.mul(lax.add(lax.shift_left(lax.sub(span, 1), FLAG_BITS), REBASE), two)
    grid = (problems, n_steps, n_tiles)
    per_tile = lambda x: lax.broadcast_in_dim(x, grid, (0, 2))
    s = lax.broadcasted_iota(jnp.int32, grid, 1)
    zero = lax.full(grid, 0, jnp.int32)
    at = lax.sub(s, per_tile(starts))          # the step's place in each tile's run
    terms = lax.add(lax.select(lax.ge(at, zero), per_tile(jump), zero),
                    lax.add(lax.select(lax.eq(at, zero), lax.full(grid, FIRST, jnp.int32), zero),
                            lax.select(lax.eq(at, lax.full(grid, 1, jnp.int32)), per_tile(second),
                                       zero)))
    step = lax.broadcasted_iota(jnp.int32, (problems, n_steps), 1)
    packed = lax.add(lax.reduce_sum(terms, (2,)), lax.shift_left(step, FLAG_BITS))
    total = lax.slice_in_dim(ends, n_tiles - 1, n_tiles, axis=1)
    idle = lax.bitwise_or(lax.shift_left(lax.slice_in_dim(last, n_tiles - 1, n_tiles, axis=1),
                                         FLAG_BITS), (n_tiles - 1) << (block_bits + FLAG_BITS))
    wide = lambda x: lax.broadcast_in_dim(x, step.shape, (0, 1))
    return (lax.select(lax.lt(step, wide(total)), packed, wide(idle)),
            lax.reshape(total, (problems,)))


def _sweep(problems: int, n_tiles: int, n_blocks: int):
    """The dense schedule, every tile against every block: a constant, for the
    shapes whose merge-path bound is no shorter."""
    tile, block = np.divmod(np.arange(n_tiles * n_blocks, dtype=np.int32), n_blocks)
    packed = (tile << (_block_bits(n_tiles, n_blocks) + FLAG_BITS) | block << FLAG_BITS | COUNTS
              | np.where(block == 0, FIRST, 0))
    return np.broadcast_to(packed, (problems, packed.size)), np.full(problems, packed.size)


def _blocks_below(bounds, x, *, strict: bool):
    """#{j : bounds[p, j] < x[p, i]} (<= if not strict), per problem p and query i."""
    grid = x.shape + bounds.shape[-1:]
    below = (lax.lt if strict else lax.le)(lax.broadcast_in_dim(bounds, grid, (0, 2)),
                                           lax.broadcast_in_dim(x, grid, (0, 1)))
    return lax.reduce_sum(lax.convert_element_type(below, jnp.int32), (2,))


def _every(x, start: int):
    """Keys start, start + 1024, ... of each row of x (problems, n)."""
    return lax.slice(x, (0, start), x.shape, (1, BLOCK))


def counts_schedule(a_keys: jax.Array, b_keys: jax.Array):
    """Merge-path schedules of S probes: a_keys (S, N), b_keys (S, M), rows sorted,
    N and M multiples of 1024. → (packed steps (S, counts_steps(na, nb)) int32,
    active steps (S,))."""
    problems = a_keys.shape[0]
    na, nb = a_keys.shape[1] // BLOCK, b_keys.shape[1] // BLOCK
    if counts_steps(na, nb) == na * nb:
        return _sweep(problems, na, nb)
    lo, hi = _every(a_keys, 0), _every(a_keys, BLOCK - 1)
    # ⌊L/1024⌋ is the number of B blocks whose last key is below lo, and
    # ⌊(F-1)/1024⌋ + 1 the number whose first key is at or below hi
    first = lax.min(_blocks_below(_every(b_keys, BLOCK - 1), lo, strict=True), nb - 1)
    last = lax.max(first, lax.sub(_blocks_below(_every(b_keys, 0), hi, strict=False), 1))
    return _runs(first, last, lax.eq(lo, hi), counts_steps(na, nb), _block_bits(na, nb))


def pairs_schedule(starts: jax.Array, cap_out: int):
    """Merge-path schedules of S expansions: starts (S, N) rows nondecreasing, N
    and cap_out multiples of 1024. → (packed steps (S, pairs_steps(nt, na)) int32,
    active steps (S,))."""
    problems, n = starts.shape
    na, nt = n // BLOCK, cap_out // BLOCK
    if pairs_steps(nt, na) == nt * na:
        return _sweep(problems, nt, na)
    heads = _every(starts, 0)
    t0 = lax.mul(lax.broadcasted_iota(jnp.int32, (problems, nt), 1), BLOCK)
    # the block holding K(x) is the last block whose first start is <= x
    below = lambda x: lax.max(lax.sub(_blocks_below(heads, x, strict=False), 1), 0)
    first = below(t0)
    last = below(lax.add(t0, BLOCK - 1))
    return _runs(first, last, lax.full((problems, nt), False), pairs_steps(nt, na),
                 _block_bits(nt, na))


def _field(packed, shift: int, bits: int):
    return lax.bitwise_and(lax.shift_right_logical(packed, shift), (1 << bits) - 1)


def _step(sched_ref, block_bits: int):
    """→ (tile, block, flags) of this grid step. ``lax`` alone, like the
    schedule: the kernel is traced anew for every probe shape."""
    packed = sched_ref[pl.program_id(0), pl.program_id(1)]
    return (lax.shift_right_logical(packed, block_bits + FLAG_BITS),
            _field(packed, FLAG_BITS, block_bits), _field(packed, 0, FLAG_BITS))


def _has(flags, flag: int):
    return lax.ne(lax.bitwise_and(flags, flag), 0)


def _restart(restart, base, acc):
    """The accumulator ``acc`` (BLOCK,), or ``base`` everywhere if ``restart``."""
    return lax.select(lax.broadcast(restart, acc.shape), lax.broadcast(base, acc.shape), acc)


def _counts_kernel(sched_ref, a_ref, b_ref, lower_ref, upper_ref, *, block_bits: int):
    _, block, flags = _step(sched_ref, block_bits)

    @pl.when(_has(flags, COUNTS))
    def _compare():
        # a tile's first step starts both bounds at the keys of the blocks before
        # it; the second step of an ends-only tile restarts upper alone, at the
        # block holding the end of its one key (the blocks between hold that key
        # and nothing else, and this block holds nothing below it)
        a, b = a_ref[...], b_ref[...]
        base = lax.mul(block, BLOCK)
        lower = _restart(_has(flags, FIRST), base, lower_ref[...])
        upper = _restart(_has(flags, FIRST | REBASE), base, upper_ref[...])
        lower_ref[...] = lower + (b[None, :] < a[:, None]).astype(jnp.int32).sum(axis=1)
        upper_ref[...] = upper + (b[None, :] <= a[:, None]).astype(jnp.int32).sum(axis=1)


def _pairs_kernel(sched_ref, starts_ref, dd_ref, a_ref, b_ref, *, block_bits: int):
    tile, block, flags = _step(sched_ref, block_bits)

    @pl.when(_has(flags, COUNTS))
    def _expand():
        # starts is nondecreasing, so the hits of slot t are a prefix, and the last
        # visited block that holds one holds K(t); the blocks before the tile's
        # first are hit in full. dd telescopes within a block to lower[q] -
        # starts[q] at its last hit q. The first visited block holds a hit for
        # every slot of the tile, so b is written at the tile's first step.
        t = lax.mul(tile, BLOCK) + lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)[:, 0]
        hit = starts_ref[...][None, :] <= t[:, None]
        n_hit = hit.astype(jnp.int32).sum(axis=1)
        a_ref[...] = _restart(_has(flags, FIRST), lax.sub(lax.mul(block, BLOCK), 1),
                              a_ref[...]) + n_hit
        at_k = (hit.astype(jnp.int32) * dd_ref[...][None, :]).sum(axis=1) + t
        b_ref[...] = lax.select(n_hit > 0, at_k, b_ref[...])


def _scheduled_call(kernel, sched, block_bits, by_tile, in_blocks, out_len, interpret,
                    *operands):
    """pallas_call of ``kernel`` on a (problems, steps) grid whose step → (tile,
    block) map is ``sched``. Operand i is blocked by the step's tile if
    by_tile[i], else by its block, and a problem has in_blocks[i] of its
    blocks; the two outputs are blocked by the tile. A call prefetches at most
    SMEM_STEPS steps: more problems than that holds run in several calls."""
    group = max(1, SMEM_STEPS // sched.shape[1])
    if sched.shape[0] > group:
        parts = [_scheduled_call(kernel, sched[i:i + group], block_bits, by_tile, in_blocks,
                                 out_len, interpret, *(x[i:i + group] for x in operands))
                 for i in range(0, sched.shape[0], group)]
        return tuple(jnp.concatenate(o) for o in zip(*parts))

    def spec(tiled, per_problem):
        pick = ((lambda v: lax.shift_right_logical(v, block_bits + FLAG_BITS)) if tiled
                else (lambda v: _field(v, FLAG_BITS, block_bits)))
        return pl.BlockSpec((BLOCK,),
                            lambda k, s, sc: (lax.add(lax.mul(k, per_problem), pick(sc[k, s])),))

    problems = sched.shape[0]
    outs = pl.pallas_call(
        functools.partial(kernel, block_bits=block_bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=sched.shape,
            in_specs=[spec(t, n) for t, n in zip(by_tile, in_blocks)],
            out_specs=[spec(True, out_len // BLOCK)] * 2,
        ),
        out_shape=[jax.ShapeDtypeStruct((problems * out_len,), jnp.int32)] * 2,
        interpret=interpret,
    )(sched, *(x.reshape(-1) for x in operands))
    return tuple(o.reshape(problems, out_len) for o in outs)


def merge_join_pairs_pallas(
    sched: jax.Array, starts: jax.Array, dd: jax.Array, cap_out: int, interpret: bool = True,
):
    """Expand per-key match ranges into the flat (a_idx, b_idx) pair list, for S
    independent problems at once, on the schedule ``pairs_schedule(starts,
    cap_out)`` gives.

    starts (S, N) int32: per problem, the exclusive prefix sum of per-key match
    counts (starts[:, 0] must be 0; pad with +2^31-1 sentinels). dd (S, N): the
    first differences of lower - starts within each 1024-key block, the first
    key of a block taking its own value (pad with 0). For output slot t in
    [0, cap_out): a_idx[t] = max{i : starts[i] <= t}, b_idx[t] = lower[a_idx] +
    (t - starts[a_idx]). Returns (a_idx, b_idx) int32 (S, cap_out). The problems
    are laid end to end in 1-D arrays, so every block is a 1-D tile of 1024.
    Tracing opens a ``kernel.probe_schedule`` span.
    """
    problems, n = starts.shape
    assert n % BLOCK == 0 and cap_out % BLOCK == 0, (n, cap_out)
    na, nt = n // BLOCK, cap_out // BLOCK
    with obs.span("kernel.probe_schedule", problems=problems, n=n, cap_out=cap_out,
                  steps=pairs_steps(nt, na), dense_steps=nt * na):
        return _scheduled_call(_pairs_kernel, sched, _block_bits(nt, na), (False, False),
                               (na, na), cap_out, interpret, starts, dd)


def merge_join_counts_pallas(
    sched: jax.Array, a_keys: jax.Array, b_keys: jax.Array, interpret: bool = True
):
    """a_keys (S, N), b_keys (S, M) int32, each row sorted ascending (padding:
    +2^31-1 sentinels work because they never compare below real keys): S
    independent probes, on the schedule ``counts_schedule(a_keys, b_keys)``
    gives. Returns (lower, upper) int32 (S, N): lower = #{b < a}, upper =
    #{b <= a} over each row of b. The probes are laid end to end in 1-D arrays,
    one grid row each, so every block is a 1-D tile. Tracing opens a
    ``kernel.probe_schedule`` span."""
    problems, n = a_keys.shape
    m = b_keys.shape[1]
    assert n % BLOCK == 0 and m % BLOCK == 0, (n, m)
    na, nb = n // BLOCK, m // BLOCK
    with obs.span("kernel.probe_schedule", problems=problems, n=n, m=m,
                  steps=counts_steps(na, nb), dense_steps=na * nb):
        return _scheduled_call(_counts_kernel, sched, _block_bits(na, nb), (True, False),
                               (na, nb), n, interpret, a_keys, b_keys)
