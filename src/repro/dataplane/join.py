"""Distributed equi-join on a device mesh: exchange + local sorted join.

The local primitives (`local_sorted_join`, `local_semijoin`, `local_unique`)
all run on the merge_join_counts Pallas probe with static shapes; the sharded
primitives (`sharded_join_step`, `sharded_semijoin`, `sharded_intersect`,
`sharded_colocated_join`) wrap them in `shard_map` bodies around
capacity-padded `hash_exchange` collectives (`sharded_colocated_join` is the
communication-free member: fragments already co-located by a grid route).
Together with `repro.dataplane.grid` they lower every stage emitted by the
round-program compiler (repro.mpc.program) onto a device mesh — the
`DataplaneExecutor` (repro.mpc.executors) drives one primitive per RoundOp.

Overflow contract: every sharded primitive returns ``ovf`` of shape (p, 2) —
column 0 counts *slot* (routing-buffer) overflow, column 1 counts *output*
overflow — so the executor's retry can double only the capacity that actually
overflowed (and re-randomize routing for slot overflow).

`hypercube_binary_join` is the original one-round routed join
R(A,B) ⋈ S(B,C) → (A,B,C), now a thin wrapper over `sharded_join_step`.
Output stays device-local (the MPC model's contract: every result tuple
materializes on some machine).

The simulator remains the load oracle; tests/test_dataplane_subprocess.py
checks both produce identical result sets on 8 fake host devices.

Device word contract: values are int32 with INT32_MAX reserved as the padding
sentinel (same convention as the kernels)."""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels.ops import merge_join_counts, merge_join_pairs, probe_use_pallas
from .exchange import batched_hash_exchange, hash_exchange, salt_offset


def local_sorted_join(
    a_rows: jax.Array, a_count: jax.Array,      # (capA, wa): join key in col ka
    b_rows: jax.Array, b_count: jax.Array,      # (capB, wb): join key in col kb
    ka: int, kb: int, cap_out: int,
    a_keys: Optional[jax.Array] = None,         # optional precomputed (capA,)
    b_keys: Optional[jax.Array] = None,         # join keys (pads may be any value)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """→ (out (cap_out, wa+wb-1), count, overflow). Key written once (A's columns,
    then B's non-key columns).  ``a_keys``/``b_keys`` override the key columns
    (composite-key joins rank their key tuples densely and pass the ranks)."""
    capa, wa = a_rows.shape
    capb, wb = b_rows.shape
    big = jnp.iinfo(jnp.int32).max

    a_keys = a_rows[:, ka] if a_keys is None else a_keys
    b_keys = b_rows[:, kb] if b_keys is None else b_keys
    a_keys = jnp.where(jnp.arange(capa) < a_count, a_keys, big)
    b_keys = jnp.where(jnp.arange(capb) < b_count, b_keys, big)
    a_ord = jnp.argsort(a_keys)
    b_ord = jnp.argsort(b_keys)
    a_k = a_keys[a_ord]
    b_k = b_keys[b_ord]

    lower, upper = merge_join_counts(a_k, b_k, use_pallas=probe_use_pallas())
    # sentinel keys must not match each other
    real_a = a_k < big
    counts = jnp.where(real_a, upper - lower, 0)
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)  # output offset per a-row
    total = counts.sum()
    overflow = jnp.maximum(total - cap_out, 0)

    # range expansion (merge_join_pairs kernel): out row t ← a_idx(t) =
    # max{i : starts[i] <= t}, b_idx(t) = lower[a_idx] + (t - starts[a_idx])
    t = jnp.arange(cap_out)
    a_idx, b_idx = merge_join_pairs(
        lower.astype(jnp.int32), starts, cap_out, use_pallas=probe_use_pallas()
    )
    b_idx = jnp.clip(b_idx, 0, capb - 1)
    valid = t < jnp.minimum(total, cap_out)

    # gather output rows through the sort permutation (composed index gathers —
    # the full sorted row matrices are never materialized)
    a_part = a_rows[a_ord[a_idx]]                                   # (cap_out, wa)
    b_cols = [c for c in range(wb) if c != kb]
    b_part = b_rows[b_ord[b_idx]][:, jnp.array(b_cols, jnp.int32)] if b_cols else jnp.zeros(
        (cap_out, 0), b_rows.dtype
    )
    out = jnp.concatenate([a_part, b_part], axis=1)
    out = jnp.where(valid[:, None], out, 0)
    return out, jnp.minimum(total, cap_out), overflow


def _compact_prefix(rows: jax.Array, keep: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Stable-compact kept rows to a zero-padded valid prefix. rows (cap, ...).

    Sort-free: the destination of a kept row is its rank among kept rows
    (exclusive prefix sum); dropped rows scatter out of bounds and vanish
    (`mode="drop"`), leaving zeros — identical output to the former stable
    argsort at O(n) instead of O(n log n)."""
    cap = rows.shape[0]
    cnt = keep.sum()
    dest = jnp.where(keep, jnp.cumsum(keep) - 1, cap)
    out = jnp.zeros_like(rows).at[dest].set(rows, mode="drop")
    return out, cnt


def local_unique(vals: jax.Array, count: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(cap,) padded value list → sorted distinct values in a valid prefix."""
    cap = vals.shape[0]
    big = jnp.iinfo(jnp.int32).max
    v = jnp.sort(jnp.where(jnp.arange(cap) < count, vals, big))
    first = jnp.concatenate([jnp.ones((1,), bool), v[1:] != v[:-1]])
    return _compact_prefix(v, first & (v < big))


def local_semijoin(
    rows: jax.Array, count: jax.Array, col: int, keys: jax.Array, kcount: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Keep rows whose rows[:, col] appears in keys[:kcount] (device-local
    semi-join via the merge_join_counts probe). Output rows are reordered by
    key and compacted to a valid prefix (multiset semantics)."""
    cap, _ = rows.shape
    capk = keys.shape[0]
    big = jnp.iinfo(jnp.int32).max
    rk = jnp.where(jnp.arange(cap) < count, rows[:, col], big)
    order = jnp.argsort(rk)
    rows_s, rk_s = rows[order], rk[order]
    kv = jnp.sort(jnp.where(jnp.arange(capk) < kcount, keys, big))
    lower, upper = merge_join_counts(rk_s, kv, use_pallas=probe_use_pallas())
    member = (upper > lower) & (rk_s < big)
    return _compact_prefix(rows_s, member)


def _composite_rank_keys(
    a_cols: Sequence[jax.Array], a_valid: jax.Array,
    b_cols: Sequence[jax.Array], b_valid: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Dense lexicographic rank of key *tuples* across both sides.

    Equal tuples (on either side) get equal ranks, so a single-column sorted
    join on the ranks is exactly the multi-column equi-join.  Ranks fit int32
    (< capA + capB); invalid rows sort last and never produce a rank that a
    valid row carries, so the caller's sentinel masking stays correct."""
    na = a_valid.shape[0]
    big = jnp.iinfo(jnp.int32).max
    valid = jnp.concatenate([a_valid, b_valid])
    cols = [
        jnp.where(valid, jnp.concatenate([ac, bc]), big)
        for ac, bc in zip(a_cols, b_cols)
    ]
    order = jnp.lexsort(tuple(reversed(cols)))   # lexsort: LAST key is primary
    scols = [c[order] for c in cols]
    diff = scols[0][1:] != scols[0][:-1]
    for c in scols[1:]:
        diff = diff | (c[1:] != c[:-1])
    first = jnp.concatenate([jnp.ones((1,), bool), diff])
    gid = (jnp.cumsum(first) - 1).astype(jnp.int32)
    ranks = jnp.zeros_like(gid).at[order].set(gid)
    return ranks[:na], ranks[na:]


def _packed_keys(rows: jax.Array, cols: Sequence[int], mults: jax.Array) -> jax.Array:
    """Mixed-radix int32 packing of the key tuple rows[:, cols]:
    key = ((c0·m0 + c1)·m1 + c2)···.  ``mults`` is a traced (len(cols)-1,)
    vector of per-position radices (strict bounds on the column values, shared
    by both join sides).  Collision-free iff every value is in [0, m_i) and the
    product of radices (times max c0 + 1) stays below 2^31 — the host-side
    eligibility check the executor performs before choosing this path."""
    k = rows[:, cols[0]].astype(jnp.int32)
    for i, c in enumerate(cols[1:]):
        k = k * mults[i] + rows[:, c].astype(jnp.int32)
    return k


def local_join_count(
    a_rows: jax.Array, a_count: jax.Array,
    b_rows: jax.Array, b_count: jax.Array,
    ka: int, kb: int,
    dup_pairs: Tuple[Tuple[int, int], ...] = (),
    key_mults: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact device-local match count for `local_join_filtered` — no expansion,
    no row gathers (keys only, `jnp.sort` instead of argsort).  The executor's
    count-then-emit pass runs this to size the emit's cap_out exactly."""
    capa, _ = a_rows.shape
    capb, _ = b_rows.shape
    big = jnp.iinfo(jnp.int32).max
    a_valid = jnp.arange(capa) < a_count
    b_valid = jnp.arange(capb) < b_count
    if not dup_pairs:
        a_keys, b_keys = a_rows[:, ka], b_rows[:, kb]
    elif key_mults is not None:
        a_keys = _packed_keys(a_rows, [ka] + [ca for ca, _ in dup_pairs], key_mults)
        b_keys = _packed_keys(b_rows, [kb] + [cb for _, cb in dup_pairs], key_mults)
    else:
        a_keys, b_keys = _composite_rank_keys(
            [a_rows[:, ka]] + [a_rows[:, ca] for ca, _ in dup_pairs], a_valid,
            [b_rows[:, kb]] + [b_rows[:, cb] for _, cb in dup_pairs], b_valid,
        )
    a_k = jnp.sort(jnp.where(a_valid, a_keys, big))
    b_k = jnp.sort(jnp.where(b_valid, b_keys, big))
    lower, upper = merge_join_counts(a_k, b_k, use_pallas=probe_use_pallas())
    return jnp.where(a_k < big, upper - lower, 0).sum().astype(jnp.int32)


def local_join_filtered(
    a_rows: jax.Array, a_count: jax.Array,
    b_rows: jax.Array, b_count: jax.Array,
    ka: int, kb: int, cap_out: int,
    dup_pairs: Tuple[Tuple[int, int], ...] = (),
    key_mults: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`local_sorted_join` with duplicated attributes folded into the key.

    ``dup_pairs`` lists (a_col, b_col) pairs (b_col ≠ kb) of attributes shared
    beyond the join key — the cyclic-subquery case.  The full key tuple
    (key, dup_1, dup_2, ...) is folded to one int32 key and the join runs on
    the folded keys, so ``cap_out`` (and the output-overflow channel) meters
    only TRUE matches.  Two folding strategies:

    * ``key_mults`` given — mixed-radix *packing* (`_packed_keys`): one
      multiply-add per extra column, no sorting.  Only valid when the caller
      has checked the key space fits int32 (the executor's key-compression
      eligibility check); radices are traced so one executable serves every
      bucket that passes the check.
    * otherwise — dense lexicographic *ranking* (`_composite_rank_keys`), the
      checked fallback: always fits int32 (ranks < capA + capB) at the price
      of a lexsort over both sides.

    The previous implementation materialized the key-only join and
    equality-filtered afterwards, which made the capacity requirement the
    per-cell *cartesian* size — on self-join-shaped queries (every LocalJoin
    chain level of a clique pattern) that overflowed every reasonable output
    cap.  The duplicate B-side columns are equal by construction and dropped;
    output scheme is A's columns then B's columns minus kb and minus the dup
    b_cols."""
    if not dup_pairs:
        return local_sorted_join(a_rows, a_count, b_rows, b_count, ka, kb, cap_out)
    capa, wa = a_rows.shape
    capb, wb = b_rows.shape
    a_valid = jnp.arange(capa) < a_count
    b_valid = jnp.arange(capb) < b_count
    if key_mults is not None:
        a_keys = _packed_keys(a_rows, [ka] + [ca for ca, _ in dup_pairs], key_mults)
        b_keys = _packed_keys(b_rows, [kb] + [cb for _, cb in dup_pairs], key_mults)
    else:
        a_keys, b_keys = _composite_rank_keys(
            [a_rows[:, ka]] + [a_rows[:, ca] for ca, _ in dup_pairs], a_valid,
            [b_rows[:, kb]] + [b_rows[:, cb] for _, cb in dup_pairs], b_valid,
        )
    out, cnt, ovf = local_sorted_join(
        a_rows, a_count, b_rows, b_count, ka, kb, cap_out,
        a_keys=a_keys, b_keys=b_keys,
    )
    b_cols = [c for c in range(wb) if c != kb]
    drop = {wa + b_cols.index(cb) for _, cb in dup_pairs}
    keep_cols = [c for c in range(out.shape[1]) if c not in drop]
    return out[:, jnp.array(keep_cols, jnp.int32)], cnt, ovf


@lru_cache(maxsize=512)
def _join_step_fn(mesh, axis_name, ka, kb, cap_slot, cap_mid, cap_out, dup_pairs):
    """Build (once per static structure) the jitted shard_map join step.
    jit's own cache handles input-shape variation, and the salt rides along as
    a traced scalar — one compiled executable serves every (H, η) stage of the
    same shape; this cache keeps repeated executor calls from re-tracing."""

    p = mesh.shape[axis_name]

    def body(a_rows, a_cnt, b_rows, b_cnt, off):
        a_rows, a_cnt, b_rows, b_cnt = a_rows[0], a_cnt[0], b_rows[0], b_cnt[0]
        a2, ca, s1, m1 = hash_exchange(a_rows, a_cnt, ka, axis_name, p, cap_slot, cap_mid, off)
        b2, cb, s2, m2 = hash_exchange(b_rows, b_cnt, kb, axis_name, p, cap_slot, cap_mid, off)
        out, cnt, o3 = local_join_filtered(a2, ca, b2, cb, ka, kb, cap_out, dup_pairs)
        # exchange-receive (cap_mid) overflow counts as routing, not output
        ovf = jnp.stack([s1 + s2 + m1 + m2, o3]).astype(jnp.int32)
        return out[None], cnt[None], ovf[None]

    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name, None, None), P(axis_name), P(axis_name, None, None), P(axis_name), P()),
        out_specs=(P(axis_name, None, None), P(axis_name), P(axis_name, None)),
        check_vma=False,
    ))


def sharded_join_step(
    mesh,
    axis_name: str,
    a_global: jax.Array, a_counts: jax.Array,   # (p, capA, wa), (p,) device-sharded
    b_global: jax.Array, b_counts: jax.Array,
    ka: int, kb: int,
    cap_slot: int, cap_mid: int, cap_out: int,
    dup_pairs: Tuple[Tuple[int, int], ...] = (),
    salt: int = 0,
):
    """One distributed binary-join step under shard_map: both sides are
    hash-exchanged on their key column, then joined locally (with optional
    duplicate-attribute filtering).  Inputs/outputs sharded over axis 0.
    Returns (out (p, cap_out, w), counts (p,), overflow (p, 2) [slot, out])."""
    fn = _join_step_fn(
        mesh, axis_name, ka, kb, cap_slot, cap_mid, cap_out, tuple(dup_pairs)
    )
    return fn(a_global, a_counts, b_global, b_counts, jnp.int32(salt_offset(salt)))


@lru_cache(maxsize=512)
def _semijoin_fn(mesh, axis_name, cols, cap_slot, cap_out):
    p = mesh.shape[axis_name]

    def body(rows, cnt, offs, *pieces):
        rows, cnt = rows[0], cnt[0]
        ovf_slot = jnp.zeros((), jnp.int32)
        ovf_out = jnp.zeros((), jnp.int32)
        for i, col in enumerate(cols):
            pv, pc = pieces[2 * i][0], pieces[2 * i + 1][0]
            rows, cnt, o_s, o_o = hash_exchange(
                rows, cnt, col, axis_name, p, cap_slot, cap_out, offs[i]
            )
            ovf_slot += o_s.astype(jnp.int32)
            ovf_out += o_o.astype(jnp.int32)
            rows, cnt = local_semijoin(rows, cnt, col, pv, pc)
        return rows[None], cnt[None], jnp.stack([ovf_slot, ovf_out])[None]

    piece_specs = []
    for _ in cols:
        piece_specs += [P(axis_name, None), P(axis_name)]
    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name, None, None), P(axis_name), P(None), *piece_specs),
        out_specs=(P(axis_name, None, None), P(axis_name), P(axis_name, None)),
        check_vma=False,
    ))


def sharded_semijoin(
    mesh,
    axis_name: str,
    rows_global: jax.Array, counts: jax.Array,          # (p, cap, w), (p,)
    filters: Sequence[Tuple[int, int, jax.Array, jax.Array]],
    cap_slot: int, cap_out: int,
):
    """Semi-join a sharded relation against co-located unary pieces.

    ``filters`` is a static sequence of (col, salt, piece_vals (p, capx),
    piece_counts (p,)): for each entry the rows are hash-exchanged on ``col``
    with ``salt`` (the same salt that distributed the piece, so piece and rows
    land on the same device) and filtered by membership.  Lowers the SemiJoin
    op of the round-program IR.  Returns (rows, counts, overflow (p, 2))."""
    cols = tuple(int(col) for col, _, _, _ in filters)
    offs = jnp.asarray([salt_offset(int(s)) for _, s, _, _ in filters], jnp.int32)
    piece_args = []
    for _, _, pv, pc in filters:
        piece_args += [pv, pc]
    fn = _semijoin_fn(mesh, axis_name, cols, cap_slot, cap_out)
    return fn(rows_global, counts, offs, *piece_args)


@lru_cache(maxsize=512)
def _intersect_fn(mesh, axis_name, n, cap_slot, cap_out):
    p = mesh.shape[axis_name]

    def body(off, *flat):
        ovf_slot = jnp.zeros((), jnp.int32)
        ovf_out = jnp.zeros((), jnp.int32)
        cur = None
        cur_cnt = None
        for i in range(n):
            v, c = flat[2 * i][0], flat[2 * i + 1][0]
            ex, exc, o_s, o_o = hash_exchange(
                v[:, None], c, 0, axis_name, p, cap_slot, cap_out, off
            )
            ovf_slot += o_s.astype(jnp.int32)
            ovf_out += o_o.astype(jnp.int32)
            uv, uc = local_unique(ex[:, 0], exc)
            if cur is None:
                cur, cur_cnt = uv, uc
            else:
                kept, kc = local_semijoin(cur[:, None], cur_cnt, 0, uv, uc)
                cur, cur_cnt = kept[:, 0], kc
        return cur[None], cur_cnt[None], jnp.stack([ovf_slot, ovf_out])[None]

    specs = [P()]
    for _ in range(n):
        specs += [P(axis_name, None), P(axis_name)]
    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=(P(axis_name, None), P(axis_name), P(axis_name, None)),
        check_vma=False,
    ))


def sharded_intersect(
    mesh,
    axis_name: str,
    pieces: Sequence[Tuple[jax.Array, jax.Array]],      # [(vals (p, cap_i), counts (p,))]
    salt: int,
    cap_slot: int, cap_out: int,
):
    """Distributed intersection of unary relations (the R''_X(η) step).

    Every piece is hash-exchanged on its value with the shared ``salt`` (all
    copies of a value meet on one device), deduplicated, and intersected
    locally via the merge_join_counts membership probe.  Lowers the
    HashPartition op of the round-program IR.  Returns
    (vals (p, cap_out), counts (p,), overflow (p, 2)) distributed by
    hash(value, salt) — ready to serve as a `sharded_semijoin` filter."""
    args = []
    for pv, pc in pieces:
        args += [pv, pc]
    fn = _intersect_fn(mesh, axis_name, len(pieces), cap_slot, cap_out)
    return fn(jnp.int32(salt_offset(salt)), *args)


@lru_cache(maxsize=512)
def _colocated_join_fn(mesh, axis_name, ka, kb, cap_out, dup_pairs):
    def body(a_rows, a_cnt, b_rows, b_cnt):
        out, cnt, ovf = local_join_filtered(
            a_rows[0], a_cnt[0], b_rows[0], b_cnt[0], ka, kb, cap_out, dup_pairs
        )
        # no exchange ⇒ no slot channel; only output capacity can overflow
        return out[None], cnt[None], jnp.stack(
            [jnp.zeros((), jnp.int32), ovf.astype(jnp.int32)]
        )[None]

    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name, None, None), P(axis_name), P(axis_name, None, None), P(axis_name)),
        out_specs=(P(axis_name, None, None), P(axis_name), P(axis_name, None)),
        check_vma=False,
    ))


def sharded_colocated_join(
    mesh,
    axis_name: str,
    a_global: jax.Array, a_counts: jax.Array,   # (p, capA, wa), (p,) device-sharded
    b_global: jax.Array, b_counts: jax.Array,
    ka: int, kb: int,
    cap_out: int,
    dup_pairs: Tuple[Tuple[int, int], ...] = (),
):
    """A purely device-local join step under shard_map — **no communication**.

    Lowers the LocalJoin op of the round-program IR: after `sharded_grid_route`
    every fragment of a virtual grid cell lives on device ``cell % p`` tagged
    with the cell id in column 0, so joining on the cell-id columns (with
    ``dup_pairs`` folding the attributes shared inside the cell into the
    composite join key) reproduces each cell's local join without moving a
    byte.  Returns
    (out (p, cap_out, w), counts (p,), overflow (p, 2) [always-0 slot, out])."""
    fn = _colocated_join_fn(mesh, axis_name, ka, kb, cap_out, tuple(dup_pairs))
    return fn(a_global, a_counts, b_global, b_counts)


# ---------------------------------------------------------------------------
# Stage-batched twins (one fused dispatch per geometry bucket)
#
# Each `batched_sharded_*` takes the same operands as its per-stage twin with
# one extra leading *stage* axis (s, p, ...) plus per-stage traced salts, and
# performs the whole bucket in a single jitted shard_map call: local compute is
# vmapped over the stage axis and the exchanges share one `all_to_all`
# (`batched_hash_exchange`).  Overflow comes back per stage — (s, p, 2) with
# the usual [slot, out] channels — so the executor's retry re-runs only the
# stages that tripped, at doubled caps and fresh attempt salts.
# ---------------------------------------------------------------------------


@lru_cache(maxsize=512)
def _batched_intersect_fn(mesh, axis_name, n, cap_slot, cap_out):
    p = mesh.shape[axis_name]

    def body(offs, *flat):
        s = offs.shape[0]                       # offs (s,) replicated
        ovf_slot = jnp.zeros((s,), jnp.int32)
        ovf_out = jnp.zeros((s,), jnp.int32)
        cur = None
        cur_cnt = None
        for i in range(n):
            v, c = flat[2 * i][:, 0, :], flat[2 * i + 1][:, 0]   # (s, cap_i), (s,)
            ex, exc, o_s, o_o = batched_hash_exchange(
                v[:, :, None], c, 0, axis_name, p, cap_slot, cap_out, offs
            )
            ovf_slot += o_s.astype(jnp.int32)
            ovf_out += o_o.astype(jnp.int32)
            uv, uc = jax.vmap(local_unique)(ex[:, :, 0], exc)
            if cur is None:
                cur, cur_cnt = uv, uc
            else:
                kept, kc = jax.vmap(local_semijoin, in_axes=(0, 0, None, 0, 0))(
                    cur[:, :, None], cur_cnt, 0, uv, uc
                )
                cur, cur_cnt = kept[:, :, 0], kc
        ovf = jnp.stack([ovf_slot, ovf_out], axis=-1)            # (s, 2)
        return cur[:, None, :], cur_cnt[:, None], ovf[:, None, :]

    specs = [P(None)]
    for _ in range(n):
        specs += [P(None, axis_name, None), P(None, axis_name)]
    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=(P(None, axis_name, None), P(None, axis_name), P(None, axis_name, None)),
        check_vma=False,
    ))


def batched_sharded_intersect(
    mesh,
    axis_name: str,
    pieces: Sequence[Tuple[jax.Array, jax.Array]],  # [(vals (s, p, cap_i), counts (s, p))]
    offs: jax.Array,                                # (s,) per-stage salt offsets
    cap_slot: int, cap_out: int,
    invoke: bool = True,
):
    """Stage-batched `sharded_intersect`: s stages' R''_X intersections through
    one dispatch.  Returns (vals (s, p, cap_out), counts (s, p), ovf (s, p, 2));
    with ``invoke=False`` returns ``(jitted_fn, args)`` instead, so the
    scheduler can AOT-compile distinct signatures concurrently and execute
    serially (concurrent collective *executions* deadlock the rendezvous)."""
    args = []
    for pv, pc in pieces:
        args += [pv, pc]
    fn = _batched_intersect_fn(mesh, axis_name, len(pieces), cap_slot, cap_out)
    if not invoke:
        return fn, (offs, *args)
    return fn(offs, *args)


@lru_cache(maxsize=512)
def _batched_semijoin_fn(mesh, axis_name, col, cap_slot, cap_out):
    p = mesh.shape[axis_name]

    def body(rows, cnt, offs, pv, pc):
        rows, cnt = rows[:, 0], cnt[:, 0]       # offs (s,) replicated
        pv, pc = pv[:, 0], pc[:, 0]
        rows, cnt, o_s, o_o = batched_hash_exchange(
            rows, cnt, col, axis_name, p, cap_slot, cap_out, offs
        )
        rows, cnt = jax.vmap(local_semijoin, in_axes=(0, 0, None, 0, 0))(
            rows, cnt, col, pv, pc
        )
        ovf = jnp.stack([o_s.astype(jnp.int32), o_o.astype(jnp.int32)], axis=-1)
        return rows[:, None], cnt[:, None], ovf[:, None, :]

    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, axis_name, None, None), P(None, axis_name), P(None),
            P(None, axis_name, None), P(None, axis_name),
        ),
        out_specs=(
            P(None, axis_name, None, None), P(None, axis_name), P(None, axis_name, None),
        ),
        check_vma=False,
    ))


def batched_sharded_semijoin(
    mesh,
    axis_name: str,
    rows_global: jax.Array, counts: jax.Array,      # (s, p, cap, w), (s, p)
    col: int,
    offs: jax.Array,                                # (s,) piece-distribution offsets
    piece_vals: jax.Array, piece_counts: jax.Array, # (s, p, capx), (s, p)
    cap_slot: int, cap_out: int,
    invoke: bool = True,
):
    """Stage-batched `sharded_semijoin` (single filter — the executor's shape):
    every stage's rows are exchanged on ``col`` with its own pinned piece salt
    and membership-filtered against its co-located piece, in one dispatch.
    Returns (rows (s, p, cap_out, w), counts (s, p), ovf (s, p, 2)); with
    ``invoke=False`` returns ``(jitted_fn, args)`` for AOT compilation."""
    fn = _batched_semijoin_fn(mesh, axis_name, col, cap_slot, cap_out)
    if not invoke:
        return fn, (rows_global, counts, offs, piece_vals, piece_counts)
    return fn(rows_global, counts, offs, piece_vals, piece_counts)


@lru_cache(maxsize=512)
def _batched_colocated_join_fn(mesh, axis_name, ka, kb, cap_out, dup_pairs, packed):
    def body(a_rows, a_cnt, b_rows, b_cnt, mults):
        # mults (s, ndup) replicated; packed is static, so the unpacked variant
        # traces no use of it (it rides along as a zero-size dummy)
        out, cnt, ovf = jax.vmap(
            lambda ar, ac, br, bc, m: local_join_filtered(
                ar, ac, br, bc, ka=ka, kb=kb, cap_out=cap_out,
                dup_pairs=dup_pairs, key_mults=m if packed else None,
            )
        )(a_rows[:, 0], a_cnt[:, 0], b_rows[:, 0], b_cnt[:, 0], mults)
        ovf2 = jnp.stack(
            [jnp.zeros_like(ovf, jnp.int32), ovf.astype(jnp.int32)], axis=-1
        )
        return out[:, None], cnt[:, None], ovf2[:, None, :]

    # the stacked input blocks are rebuilt host-side per dispatch, so their
    # device copies are single-use: donating them lets XLA reuse the pages
    # for the (equally large) expansion buffers
    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, axis_name, None, None), P(None, axis_name),
            P(None, axis_name, None, None), P(None, axis_name), P(None, None),
        ),
        out_specs=(
            P(None, axis_name, None, None), P(None, axis_name), P(None, axis_name, None),
        ),
        check_vma=False,
    ), donate_argnums=(0, 2))


def batched_sharded_colocated_join(
    mesh,
    axis_name: str,
    a_global: jax.Array, a_counts: jax.Array,   # (s, p, capA, wa), (s, p)
    b_global: jax.Array, b_counts: jax.Array,
    ka: int, kb: int,
    cap_out: int,
    dup_pairs: Tuple[Tuple[int, int], ...] = (),
    key_mults: Optional[jax.Array] = None,      # (s, ndup) int32 packing radices
    invoke: bool = True,
):
    """Stage-batched `sharded_colocated_join`: s communication-free per-cell
    joins in one dispatch (vmapped `local_join_filtered`; the slot channel is
    structurally zero).  ``key_mults`` selects the packed int32 composite-key
    path (see `local_join_filtered`); radices are traced, so packed buckets of
    one shape share an executable.  Returns (out (s, p, cap_out, w),
    counts (s, p), ovf (s, p, 2)); with ``invoke=False`` returns
    ``(jitted_fn, args)`` for AOT compilation."""
    packed = key_mults is not None
    if key_mults is None:
        key_mults = jnp.zeros((a_global.shape[0], max(1, len(dup_pairs))), jnp.int32)
    fn = _batched_colocated_join_fn(
        mesh, axis_name, ka, kb, cap_out, tuple(dup_pairs), packed
    )
    if not invoke:
        return fn, (a_global, a_counts, b_global, b_counts, key_mults)
    return fn(a_global, a_counts, b_global, b_counts, key_mults)


@lru_cache(maxsize=512)
def _batched_colocated_count_fn(mesh, axis_name, ka, kb, dup_pairs, packed):
    def body(a_rows, a_cnt, b_rows, b_cnt, mults):
        cnt = jax.vmap(
            lambda ar, ac, br, bc, m: local_join_count(
                ar, ac, br, bc, ka=ka, kb=kb,
                dup_pairs=dup_pairs, key_mults=m if packed else None,
            )
        )(a_rows[:, 0], a_cnt[:, 0], b_rows[:, 0], b_cnt[:, 0], mults)
        s = cnt.shape[0]
        return cnt[:, None], jnp.zeros((s, 1, 2), jnp.int32)

    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, axis_name, None, None), P(None, axis_name),
            P(None, axis_name, None, None), P(None, axis_name), P(None, None),
        ),
        out_specs=(P(None, axis_name), P(None, axis_name, None)),
        check_vma=False,
    ))


def batched_sharded_colocated_join_count(
    mesh,
    axis_name: str,
    a_global: jax.Array, a_counts: jax.Array,   # (s, p, capA, wa), (s, p)
    b_global: jax.Array, b_counts: jax.Array,
    ka: int, kb: int,
    dup_pairs: Tuple[Tuple[int, int], ...] = (),
    key_mults: Optional[jax.Array] = None,
    invoke: bool = True,
):
    """Count-only twin of `batched_sharded_colocated_join`: the exact per-device
    match totals (s, p) with no expansion, so the executor can size the emit
    pass's cap_out exactly (count-then-emit).  Returns (counts (s, p),
    ovf (s, p, 2) structurally zero); ``invoke=False`` → ``(jitted_fn, args)``."""
    packed = key_mults is not None
    if key_mults is None:
        key_mults = jnp.zeros((a_global.shape[0], max(1, len(dup_pairs))), jnp.int32)
    fn = _batched_colocated_count_fn(mesh, axis_name, ka, kb, tuple(dup_pairs), packed)
    if not invoke:
        return fn, (a_global, a_counts, b_global, b_counts, key_mults)
    return fn(a_global, a_counts, b_global, b_counts, key_mults)


def hypercube_binary_join(
    mesh,
    axis_name: str,
    a_global: jax.Array, a_counts: jax.Array,   # (p, capA, wa), (p,) device-sharded
    b_global: jax.Array, b_counts: jax.Array,
    ka: int, kb: int,
    cap_slot: int, cap_mid: int, cap_out: int,
):
    """The one-round routed join R(A,B) ⋈ S(B,C): a single `sharded_join_step`
    with no duplicate attributes (kept as the named Lemma 3.3 entry point;
    overflow is reported as a single combined (p,) counter)."""
    out, cnt, ovf = sharded_join_step(
        mesh, axis_name, a_global, a_counts, b_global, b_counts,
        ka, kb, cap_slot, cap_mid, cap_out,
    )
    return out, cnt, ovf.sum(axis=-1)
