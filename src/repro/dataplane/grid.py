"""Step-3 grid routing on the device mesh (the GridRoute op's dataplane lowering).

The Lemma 3.1 cartesian grid over the isolated R''_X lists is composed with
the Lemma 3.3 HyperCube over L \\ I via the Lemma 3.2 matrix: virtual machine
``v = cp_cell * hc_size + hc_cell``.  `sharded_grid_route` realizes both sides
of that composition with one primitive: every row is *replicated* to its set
of destination virtual cells (a static per-fragment fan-out), tagged with the
cell id in a new leading column, and exchanged with the same capacity-padded
``all_to_all`` the hash exchange uses — virtual cell ``v`` lives on device
``v % p``.  Afterwards all fragments of a cell are co-located, so the LocalJoin
op lowers to communication-free `sharded_colocated_join` steps keyed on the
cell column.

Destination sets come from the *same* geometry the simulator uses:

  * isolated pieces — global tuple ids ``offset(device) + arange(count)``
    (offsets derived from the BroadcastSizes piece counts in sorted-device
    order, see ``stage_geometry``), mapped through
    ``CartesianGrid.cells_for_ids`` (lists beyond t' are broadcast to every
    CP cell), then replicated across every HyperCube column;
  * light-edge residents — per-attribute salted coordinate hashes mapped
    through ``HyperCubeGrid.cells_for`` (free dims enumerated), then
    replicated across every CP row.

Both sides share the static cell-contribution helpers (`cp_cell_contribs`,
`hc_cell_contribs`) with the grids' numpy/jnp coordinate methods, so the
dataplane and the simulator enumerate identical cells by construction.

Overflow contract matches repro.dataplane.join: ``ovf`` is (p, 2) with
column 0 = send-slot overflow, column 1 = output overflow.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..mpc.cartesian import CartesianGrid, cp_cell_contribs, cp_cells_dev
from ..mpc.hypercube import HyperCubeGrid, hc_cell_contribs, hc_cells_dev
from .exchange import batched_exchange_by_partition, exchange_by_partition


# ---------------------------------------------------------------------------
# Route specs (static, hashable — they key the jit/shard_map cache)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CPRouteSpec:
    """Destination rule for one isolated R''_X list (Lemma 3.1 side)."""

    dims: Tuple[int, ...]       # CP grid dimensions (size-desc list order)
    list_idx: int               # this list's position in the size-desc order
    t_prime: int                # lists ≥ t' are broadcast to every CP cell
    hc_size: int                # HyperCube columns to replicate across

    @property
    def fanout(self) -> int:
        cp_size = math.prod(self.dims) if self.dims else 1
        if self.list_idx < self.t_prime:
            n_other = cp_size // self.dims[self.list_idx]
        else:
            n_other = cp_size
        return n_other * self.hc_size


@dataclass(frozen=True)
class HCRouteSpec:
    """Destination rule for one light-edge fragment (Lemma 3.3 side)."""

    fixed: Tuple[Tuple[int, int, int], ...]   # (column, share, flat stride)
    free_contribs: Tuple[int, ...]            # flat ids of the free-dim combos
    cp_size: int                              # CP rows to replicate across
    hc_size: int

    @property
    def fanout(self) -> int:
        return len(self.free_contribs) * self.cp_size


def cp_route_spec(grid: CartesianGrid, list_idx: int, hc_size: int) -> CPRouteSpec:
    return CPRouteSpec(
        dims=tuple(grid.dims), list_idx=list_idx, t_prime=grid.t_prime,
        hc_size=hc_size,
    )


def hc_route_spec(
    grid: HyperCubeGrid, scheme: Sequence[str], cp_size: int
) -> HCRouteSpec:
    """Spec for a fragment over ``scheme``: every scheme attribute present in
    the grid becomes a hashed (fixed) coordinate, the rest enumerate."""
    fixed_attrs = [a for a in scheme if a in grid.attrs]
    strides, contribs = hc_cell_contribs(grid.attrs, grid.dims, fixed_attrs)
    fixed = tuple(
        (list(scheme).index(a), grid.share(a), strides[a]) for a in fixed_attrs
    )
    return HCRouteSpec(
        fixed=fixed, free_contribs=contribs, cp_size=cp_size, hc_size=grid.size
    )


# ---------------------------------------------------------------------------
# Device-side pieces
# ---------------------------------------------------------------------------


def coord_hash(vals: jax.Array, salt: jax.Array) -> jax.Array:
    """Per-attribute coordinate hash: uint32 avalanche mix of (value, salt).
    Every device evaluates the same function (shared randomness, paper
    footnote 2); the salt is traced so a retry's fresh randomness does not
    retrace the executable."""
    h = vals.astype(jnp.uint32) * jnp.uint32(2654435761) + salt.astype(jnp.uint32)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    return h


def replicate_to_cells(
    rows: jax.Array,        # (cap, w) valid-prefix padded
    count: jax.Array,       # scalar
    dests: jax.Array,       # (cap, R) destination virtual cells per row
    axis_name: str,
    p: int,
    cap_slot: int,
    cap_out: int,
):
    """Inside shard_map: send one copy of each row to every destination cell,
    tagged with the cell id in a new leading column; cell v → device v % p.
    Returns (out (cap_out, 1+w), count, ovf_slot, ovf_out)."""
    cap, w = rows.shape
    fanout = dests.shape[1]
    rep = jnp.repeat(rows, fanout, axis=0)              # keeps prefix validity
    v = dests.reshape(-1).astype(jnp.int32)
    tagged = jnp.concatenate([v[:, None], rep], axis=1)
    return exchange_by_partition(
        tagged, count * fanout, v % p, axis_name, p, cap_slot, cap_out
    )


@lru_cache(maxsize=512)
def _cp_route_fn(mesh, axis_name, spec: CPRouteSpec, cap_slot, cap_out):
    p = mesh.shape[axis_name]
    cp_size = math.prod(spec.dims) if spec.dims else 1

    def body(rows, cnts, offs):
        rows, cnt, off = rows[0], cnts[0], offs[0]
        cap = rows.shape[0]
        ids = off.astype(jnp.int32) + jnp.arange(cap, dtype=jnp.int32)
        if spec.list_idx < spec.t_prime:
            cells = cp_cells_dev(ids, spec.dims, spec.list_idx)
        else:   # too small to matter: broadcast to every CP cell (Lemma 3.1)
            cells = jnp.broadcast_to(
                jnp.arange(cp_size, dtype=jnp.int32)[None, :], (cap, cp_size)
            )
        dests = (
            cells[:, :, None] * spec.hc_size
            + jnp.arange(spec.hc_size, dtype=jnp.int32)[None, None, :]
        ).reshape(cap, -1)
        out, c, o_s, o_o = replicate_to_cells(
            rows, cnt, dests, axis_name, p, cap_slot, cap_out
        )
        return out[None], c[None], jnp.stack([o_s, o_o]).astype(jnp.int32)[None]

    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name, None, None), P(axis_name), P(axis_name)),
        out_specs=(P(axis_name, None, None), P(axis_name), P(axis_name, None)),
        check_vma=False,
    ))


@lru_cache(maxsize=512)
def _hc_route_fn(mesh, axis_name, spec: HCRouteSpec, cap_slot, cap_out):
    p = mesh.shape[axis_name]

    def body(rows, cnts, salts):
        rows, cnt = rows[0], cnts[0]
        cap = rows.shape[0]
        coords = [
            (coord_hash(rows[:, col], salts[i]) % jnp.uint32(share), stride)
            for i, (col, share, stride) in enumerate(spec.fixed)
        ]
        cells = hc_cells_dev(coords, spec.free_contribs, cap)
        dests = (
            jnp.arange(spec.cp_size, dtype=jnp.int32)[None, :, None] * spec.hc_size
            + cells[:, None, :]
        ).reshape(cap, -1)
        out, c, o_s, o_o = replicate_to_cells(
            rows, cnt, dests, axis_name, p, cap_slot, cap_out
        )
        return out[None], c[None], jnp.stack([o_s, o_o]).astype(jnp.int32)[None]

    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name, None, None), P(axis_name), P(None)),
        out_specs=(P(axis_name, None, None), P(axis_name), P(axis_name, None)),
        check_vma=False,
    ))


# ---------------------------------------------------------------------------
# Stage-batched grid routing (one fused dispatch per geometry bucket)
#
# The batched twins make the *geometry itself* traced data: a stage's grid
# dims, cell strides, and enumeration tables arrive as per-stage arrays
# instead of compile-time constants, and the per-row copy count is padded to
# a bucket-wide pow2 ``fanout`` with -1 sentinel entries (ghosted by the
# exchange, never sent).  One compiled executable therefore serves *every*
# stage whose route has the same static shape bundle — (fixed hash columns,
# padded fanout, block caps) — no matter what CP grid or HyperCube shares the
# broadcast sizes produced; cold time stops scaling with the number of
# distinct stage geometries.
#
# The destination algebra is an exact refactoring of the unbatched
# enumeration (same host helpers `cp_cell_contribs` / `hc_cell_contribs`,
# same copy order):
#
#   CP side:  v = (id mod dim) · S + T_k,   S = stride·hc_size,
#             T = [contrib_j·hc_size + h]   (j outer, h inner)
#   HC side:  v = Σ_f coord_f·stride_f + T_k,
#             T = [cp_row·hc_size + free_contrib_j]   (cp_row outer)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CPBatchSig:
    """Static shape bundle of a batched CP-side route: only the padded
    fanout — dims, strides, and tables are traced per-stage data."""

    fanout: int


@dataclass(frozen=True)
class HCBatchSig:
    """Static shape bundle of a batched HC-side route: which row columns are
    hashed into coordinates, and the padded fanout."""

    cols: Tuple[int, ...]
    fanout: int


def _pad_table(t, fanout: int):
    """Pad a destination-offset table to ``fanout`` with -1 sentinels."""
    import numpy as np

    out = np.full((fanout,), -1, dtype=np.int32)
    out[: len(t)] = t
    return out


def cp_batch_params(grid: Optional[CartesianGrid], list_idx: int, hc_size: int):
    """Per-stage traced operands of the batched CP route for one isolated
    list: (sig fanout source, dim, scale S, offset table T).  Lists beyond t'
    broadcast to every CP cell (dim = 1, S = 0, T enumerates the full grid)."""
    if grid is not None and list_idx < grid.t_prime:
        stride, contribs = cp_cell_contribs(grid.dims, list_idx)
        dim = grid.dims[list_idx]
        scale = stride * hc_size
        table = [c * hc_size + h for c in contribs for h in range(hc_size)]
    else:
        cp_size = grid.size if grid is not None else 1
        dim, scale = 1, 0
        table = [c * hc_size + h for c in range(cp_size) for h in range(hc_size)]
    return dim, scale, table


def hc_batch_params(grid: HyperCubeGrid, scheme: Sequence[str], cp_size: int):
    """Per-stage traced operands of the batched HC route for one light
    fragment: (fixed column indices, shares, strides, offset table T)."""
    fixed_attrs = [a for a in scheme if a in grid.attrs]
    strides, contribs = hc_cell_contribs(grid.attrs, grid.dims, fixed_attrs)
    cols = tuple(list(scheme).index(a) for a in fixed_attrs)
    shares = [grid.share(a) for a in fixed_attrs]
    stride_list = [strides[a] for a in fixed_attrs]
    table = [cp * grid.size + fc for cp in range(cp_size) for fc in contribs]
    return cols, shares, stride_list, table


def batched_replicate_to_cells(
    rows: jax.Array,        # (s, cap, w) valid-prefix padded
    counts: jax.Array,      # (s,)
    dests: jax.Array,       # (s, cap, F) destination cells; -1 = sentinel copy
    axis_name: str,
    p: int,
    cap_slot: int,
    cap_out: int,
):
    """Inside shard_map: stage-batched `replicate_to_cells` — every stage's
    rows are fanned out to their destination cells and the whole stack shares
    one `all_to_all`.  Sentinel (-1) destinations are ghosted: the copy is
    never sent, so pow2 fanout padding cannot change results or overflow.
    Returns (out (s, cap_out, 1+w), counts (s,), ovf_slot (s,), ovf_out (s,))."""
    s, cap, w = rows.shape
    fanout = dests.shape[2]
    rep = jnp.repeat(rows, fanout, axis=1)              # keeps prefix validity
    v = dests.reshape(s, cap * fanout).astype(jnp.int32)
    tagged = jnp.concatenate([v[:, :, None], rep], axis=2)
    part = jnp.where(v < 0, p, v % p)                   # sentinel → ghost
    return batched_exchange_by_partition(
        tagged, counts * fanout, part, axis_name, p, cap_slot, cap_out
    )


@lru_cache(maxsize=512)
def _batched_cp_route_fn(mesh, axis_name, sig: CPBatchSig, cap_slot, cap_out):
    p = mesh.shape[axis_name]

    def body(rows, cnts, offs, dims, scales, table):
        rows, cnt, off = rows[:, 0], cnts[:, 0], offs[:, 0]     # (s, cap, w) ...
        s, cap, _ = rows.shape
        ids = off[:, None].astype(jnp.int32) + jnp.arange(cap, dtype=jnp.int32)
        own = (ids % dims[:, None]).astype(jnp.int32)
        dests = own[:, :, None] * scales[:, None, None] + table[:, None, :]
        dests = jnp.where(table[:, None, :] < 0, -1, dests)
        out, c, o_s, o_o = batched_replicate_to_cells(
            rows, cnt, dests, axis_name, p, cap_slot, cap_out
        )
        ovf = jnp.stack([o_s.astype(jnp.int32), o_o.astype(jnp.int32)], axis=-1)
        return out[:, None], c[:, None], ovf[:, None, :]

    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, axis_name, None, None), P(None, axis_name), P(None, axis_name),
            P(None), P(None), P(None, None),
        ),
        out_specs=(
            P(None, axis_name, None, None), P(None, axis_name), P(None, axis_name, None),
        ),
        check_vma=False,
    ), donate_argnums=(0,))


@lru_cache(maxsize=512)
def _batched_hc_route_fn(mesh, axis_name, sig: HCBatchSig, cap_slot, cap_out):
    p = mesh.shape[axis_name]

    def body(rows, cnts, salts, shares, strides, table):
        rows, cnt = rows[:, 0], cnts[:, 0]      # (s, cap, w); rest replicated
        s, cap, _ = rows.shape
        flat = jnp.zeros((s, cap), jnp.int32)
        for f, col in enumerate(sig.cols):
            coord = coord_hash(rows[:, :, col], salts[:, f, None]) % shares[:, f, None]
            flat = flat + coord.astype(jnp.int32) * strides[:, f, None]
        dests = flat[:, :, None] + table[:, None, :]
        dests = jnp.where(table[:, None, :] < 0, -1, dests)
        out, c, o_s, o_o = batched_replicate_to_cells(
            rows, cnt, dests, axis_name, p, cap_slot, cap_out
        )
        ovf = jnp.stack([o_s.astype(jnp.int32), o_o.astype(jnp.int32)], axis=-1)
        return out[:, None], c[:, None], ovf[:, None, :]

    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, axis_name, None, None), P(None, axis_name),
            P(None, None), P(None, None), P(None, None), P(None, None),
        ),
        out_specs=(
            P(None, axis_name, None, None), P(None, axis_name), P(None, axis_name, None),
        ),
        check_vma=False,
    ), donate_argnums=(0,))


def _dest_hist(counts: jax.Array, dests: jax.Array, p: int) -> jax.Array:
    """(s,) valid row counts + (s, cap, F) destination cells (-1 = ghost) →
    (s, p) per-destination-device copy histogram: exactly the send-slot
    occupancy the emit pass's `pack_by_partition` will see, so its column sums
    across source devices are the exact receive sizes."""
    s, cap, fanout = dests.shape
    v = dests.reshape(s, cap * fanout)
    valid = (
        jnp.arange(cap * fanout, dtype=jnp.int32)[None, :]
        < (counts * fanout)[:, None]
    )
    dst = jnp.where(valid & (v >= 0), v % p, p)
    return jax.vmap(lambda d: jnp.zeros((p + 1,), jnp.int32).at[d].add(1))(dst)[:, :p]


@lru_cache(maxsize=512)
def _batched_cp_route_count_fn(mesh, axis_name, sig: CPBatchSig):
    p = mesh.shape[axis_name]

    def body(rows, cnts, offs, dims, scales, table):
        rows, cnt, off = rows[:, 0], cnts[:, 0], offs[:, 0]
        s, cap, _ = rows.shape
        ids = off[:, None].astype(jnp.int32) + jnp.arange(cap, dtype=jnp.int32)
        own = (ids % dims[:, None]).astype(jnp.int32)
        dests = own[:, :, None] * scales[:, None, None] + table[:, None, :]
        dests = jnp.where(table[:, None, :] < 0, -1, dests)
        return (_dest_hist(cnt, dests, p)[:, None],)

    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, axis_name, None, None), P(None, axis_name), P(None, axis_name),
            P(None), P(None), P(None, None),
        ),
        out_specs=(P(None, axis_name, None),),
        check_vma=False,
    ))


@lru_cache(maxsize=512)
def _batched_hc_route_count_fn(mesh, axis_name, sig: HCBatchSig):
    p = mesh.shape[axis_name]

    def body(rows, cnts, salts, shares, strides, table):
        rows, cnt = rows[:, 0], cnts[:, 0]
        s, cap, _ = rows.shape
        flat = jnp.zeros((s, cap), jnp.int32)
        for f, col in enumerate(sig.cols):
            coord = coord_hash(rows[:, :, col], salts[:, f, None]) % shares[:, f, None]
            flat = flat + coord.astype(jnp.int32) * strides[:, f, None]
        dests = flat[:, :, None] + table[:, None, :]
        dests = jnp.where(table[:, None, :] < 0, -1, dests)
        return (_dest_hist(cnt, dests, p)[:, None],)

    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, axis_name, None, None), P(None, axis_name),
            P(None, None), P(None, None), P(None, None), P(None, None),
        ),
        out_specs=(P(None, axis_name, None),),
        check_vma=False,
    ))


def batched_sharded_grid_route_count(
    mesh,
    axis_name: str,
    rows: jax.Array,
    counts: jax.Array,
    sig,
    *,
    offsets=None,
    dims=None,
    scales=None,
    salts=None,
    shares=None,
    strides=None,
    table=None,
    invoke: bool = True,
):
    """Count-only twin of `batched_sharded_grid_route`: the exact per-stage
    (p_src, p_dst) copy histograms with **no collective** — the destination
    algebra is identical (same traced geometry operands, same salts), only the
    exchange is replaced by a per-device histogram.  The executor's
    count-then-emit pass sizes the emit's cap_slot (max entry) and cap_out
    (max column sum) exactly from the result.  Returns ``(hist (s, p, p),)``;
    with ``invoke=False`` returns ``(jitted_fn, args)``."""
    import numpy as np

    if isinstance(sig, CPBatchSig):
        fn = _batched_cp_route_count_fn(mesh, axis_name, sig)
        args = (
            rows, counts,
            np.asarray(offsets, dtype=np.int32),
            np.asarray(dims, dtype=np.int32),
            np.asarray(scales, dtype=np.int32),
            np.asarray(table, dtype=np.int32),
        )
    elif isinstance(sig, HCBatchSig):
        fn = _batched_hc_route_count_fn(mesh, axis_name, sig)
        args = (
            rows, counts,
            np.asarray(salts, dtype=np.uint32),
            np.asarray(shares, dtype=np.uint32),
            np.asarray(strides, dtype=np.int32),
            np.asarray(table, dtype=np.int32),
        )
    else:
        raise TypeError(f"unknown grid-route signature {sig!r}")
    if not invoke:
        return fn, args
    return fn(*args)


def batched_sharded_grid_route(
    mesh,
    axis_name: str,
    rows: jax.Array,            # (s, p, cap, w) stage-stacked padded blocks
    counts: jax.Array,          # (s, p)
    sig,                        # CPBatchSig | HCBatchSig (shared by the bucket)
    *,
    offsets=None,               # (s, p) global-id bases          (CP side)
    dims=None,                  # (s,) own-list grid dimension    (CP side)
    scales=None,                # (s,) stride · hc_size           (CP side)
    salts=None,                 # (s, n_fixed) coordinate salts   (HC side)
    shares=None,                # (s, n_fixed) attribute shares   (HC side)
    strides=None,               # (s, n_fixed) flat-cell strides  (HC side)
    table=None,                 # (s, sig.fanout) cell-offset table, -1-padded
    cap_slot: int,
    cap_out: int,
    invoke: bool = True,
):
    """Stage-batched `sharded_grid_route`: every stage of a geometry bucket
    is fanned out to its virtual cells through one dispatch and one
    `all_to_all`; the grid geometry rides along as traced per-stage operands
    (see `cp_batch_params` / `hc_batch_params`).  Returns
    (out (s, p, cap_out, 1+w), counts (s, p), ovf (s, p, 2)); with
    ``invoke=False`` returns ``(jitted_fn, args)`` for AOT compilation."""
    import numpy as np

    if isinstance(sig, CPBatchSig):
        fn = _batched_cp_route_fn(mesh, axis_name, sig, cap_slot, cap_out)
        args = (
            rows, counts,
            np.asarray(offsets, dtype=np.int32),
            np.asarray(dims, dtype=np.int32),
            np.asarray(scales, dtype=np.int32),
            np.asarray(table, dtype=np.int32),
        )
    elif isinstance(sig, HCBatchSig):
        fn = _batched_hc_route_fn(mesh, axis_name, sig, cap_slot, cap_out)
        args = (
            rows, counts,
            np.asarray(salts, dtype=np.uint32),
            np.asarray(shares, dtype=np.uint32),
            np.asarray(strides, dtype=np.int32),
            np.asarray(table, dtype=np.int32),
        )
    else:
        raise TypeError(f"unknown grid-route signature {sig!r}")
    if not invoke:
        return fn, args
    return fn(*args)


def sharded_grid_route(
    mesh,
    axis_name: str,
    rows: jax.Array,            # (p, cap, w) device-sharded padded blocks
    counts: jax.Array,          # (p,)
    spec,                       # CPRouteSpec | HCRouteSpec
    *,
    offsets: Optional[jax.Array] = None,    # (p,) global-id bases (CP side)
    salts: Optional[Sequence[int]] = None,  # per-fixed-attr salts (HC side)
    cap_slot: int,
    cap_out: int,
):
    """Route one fragment to its step-3 virtual grid cells (GridRoute lowering).

    Returns (out (p, cap_out, 1+w), counts (p,), ovf (p, 2)); column 0 of every
    output row is the destination cell id (the Lemma 3.2 virtual machine),
    columns 1.. are the original row."""
    if isinstance(spec, CPRouteSpec):
        if offsets is None:
            raise ValueError("CP-side grid route needs per-device id offsets")
        fn = _cp_route_fn(mesh, axis_name, spec, cap_slot, cap_out)
        return fn(rows, counts, jnp.asarray(offsets, dtype=jnp.int32))
    if isinstance(spec, HCRouteSpec):
        if salts is None:
            raise ValueError("HC-side grid route needs per-attribute salts")
        fn = _hc_route_fn(mesh, axis_name, spec, cap_slot, cap_out)
        return fn(rows, counts, jnp.asarray(list(salts), dtype=jnp.uint32))
    raise TypeError(f"unknown grid-route spec {spec!r}")
