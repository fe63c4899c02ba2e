"""Hash partitioning (the exchange's send side) as a Pallas TPU kernel.

Computes, per input tile, (i) the partition id of every key under a multiplicative
uint32 mix and (ii) the tile's partition histogram — the send-count matrix the padded
all_to_all exchange is sized from (repro/dataplane). The histogram is a one-hot
reduction over the (BLOCK × P) one-hot — no scatter (TPU has no shared-memory
atomics; this is the standard TPU radix-count shape).

TPU layout rules the kernels follow: key blocks are 1024 long (XLA's 1-D int32
tiling); the per-tile histogram is an (n_tiles, 1, P) array whose tile axis is
squeezed out of the block, so the block's last two dims equal the array's; the
valid-row counts sit in SMEM. A leading grid dimension runs a batch of
independent key lists laid end to end (the dataplane vmaps the send side over
the stages of a bucket).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import MIX_A, MIX_B

BLOCK = 1024


def _partition(keys: jax.Array, n_parts: int) -> jax.Array:
    k = keys.astype(jnp.uint32)
    h = (k ^ (k >> 16)) * jnp.uint32(MIX_A)
    h = (h ^ (h >> 13)) * jnp.uint32(MIX_B)
    h = h ^ (h >> 16)
    return (h % jnp.uint32(n_parts)).astype(jnp.int32)


def _kernel(keys_ref, part_ref, hist_ref, *, n_parts: int):
    part = _partition(keys_ref[...], n_parts)
    part_ref[...] = part
    iota = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, n_parts), 1)
    onehot = (part[:, None] == iota).astype(jnp.int32)
    hist_ref[...] = onehot.sum(axis=0)[None, :]


def _pack_kernel(count_ref, keys_ref, part_ref, slot_ref, hist_ref, base_ref, *, n_parts: int):
    k, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        base_ref[...] = jnp.zeros_like(base_ref)

    part = _partition(keys_ref[...], n_parts)
    # rows past the valid count go to a ghost partition (id == n_parts) so they
    # neither claim slots nor show up in the send histogram
    idx = i * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)[:, 0]
    part = jnp.where(idx < count_ref[k], part, jnp.int32(n_parts))
    part_ref[...] = part
    iota = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, n_parts + 1), 1)
    onehot = (part[:, None] == iota).astype(jnp.int32)
    # exclusive in-tile rank = (strictly-lower-triangular ones) @ one-hot, on the
    # MXU: 0/1 operands are exact in bf16 and the f32 sums stay below 2^24
    rows = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)
    below = (cols < rows).astype(jnp.bfloat16)
    before = jnp.dot(below, onehot.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    within = (before.astype(jnp.int32) * onehot).sum(axis=1)
    # slot = running base from earlier tiles + exclusive rank within this tile
    base = base_ref[...]                                  # (1, n_parts + 1)
    slot_ref[...] = within + (onehot * base).sum(axis=1)
    tile_hist = onehot.sum(axis=0, keepdims=True)        # (1, n_parts + 1)
    hist_ref[...] = tile_hist[:, :n_parts]
    base_ref[...] = base + tile_hist


def hash_partition_pack_pallas(
    keys: jax.Array, count: jax.Array, n_parts: int, interpret: bool = True
):
    """Fused exchange send side: hash + partition id + in-partition slot + histogram
    in one pass, for S independent key lists. keys (S, N) int32, N % BLOCK == 0;
    count (S,) int32 valid prefix lengths. → (part (S, N) with n_parts marking
    invalid rows, slot (S, N) stable rank within the row's partition, hist
    (S, N/BLOCK, P) per-tile send counts). The grid (S, N/BLOCK) is sequential,
    carrying each list's running per-partition base in a revisited (1, P+1)
    output block so `slot` is globally correct without a second pass."""
    n_b, n = keys.shape
    assert n % BLOCK == 0, n
    nt = n // BLOCK
    kernel = lambda cr, kr, pr, sr, hr, br: _pack_kernel(
        cr, kr, pr, sr, hr, br, n_parts=n_parts
    )
    key_spec = pl.BlockSpec((BLOCK,), lambda k, i: (k * nt + i,))
    part, slot, hist, _base = pl.pallas_call(
        kernel,
        grid=(n_b, nt),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), key_spec],
        out_specs=[
            key_spec,
            key_spec,
            pl.BlockSpec((None, 1, n_parts), lambda k, i: (k * nt + i, 0, 0)),
            pl.BlockSpec((None, 1, n_parts + 1), lambda k, i: (k, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_b * n,), jnp.int32),
            jax.ShapeDtypeStruct((n_b * n,), jnp.int32),
            jax.ShapeDtypeStruct((n_b * nt, 1, n_parts), jnp.int32),
            jax.ShapeDtypeStruct((n_b, 1, n_parts + 1), jnp.int32),
        ],
        interpret=interpret,
    )(count, keys.reshape(-1))
    return part.reshape(n_b, n), slot.reshape(n_b, n), hist.reshape(n_b, nt, n_parts)


def hash_partition_pallas(
    keys: jax.Array, n_parts: int, interpret: bool = True
):
    """keys (S, N) int32/uint32, N % BLOCK == 0 → (part (S, N), hist (S, N/BLOCK, P))."""
    n_b, n = keys.shape
    assert n % BLOCK == 0, n
    nt = n // BLOCK
    kernel = lambda kr, pr, hr: _kernel(kr, pr, hr, n_parts=n_parts)
    key_spec = pl.BlockSpec((BLOCK,), lambda k, i: (k * nt + i,))
    part, hist = pl.pallas_call(
        kernel,
        grid=(n_b, nt),
        in_specs=[key_spec],
        out_specs=[
            key_spec,
            pl.BlockSpec((None, 1, n_parts), lambda k, i: (k * nt + i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_b * n,), jnp.int32),
            jax.ShapeDtypeStruct((n_b * nt, 1, n_parts), jnp.int32),
        ],
        interpret=interpret,
    )(keys.reshape(-1))
    return part.reshape(n_b, n), hist.reshape(n_b, nt, n_parts)
