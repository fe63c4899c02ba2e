"""Hierarchical gradient synchronization for multi-pod meshes.

On a (pod, data, model) mesh the naive DP gradient all-reduce spans pod × data —
crossing the (slower, oversubscribed) inter-pod links with full payload. The
hierarchical schedule:

    1. reduce-scatter within the pod over "data"   (fast intra-pod ICI)
    2. all-reduce the 1/16 shards across "pod"     (inter-pod traffic ÷ 16)
    3. all-gather within the pod over "data"

moves 2/16 of the payload across pods instead of 2×. Implemented as a shard_map so
the schedule is explicit in the HLO (the dry-run's collective table shows the swap);
`sync_grads(grads, mesh, axes)` is a drop-in used by the train driver when the mesh
has a "pod" axis. Composes with int8 compression (optimizer.py): quantize before
step 1, dequantize after step 3.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _hier_one(g: jax.Array, data_size: int) -> jax.Array:
    """Inside shard_map: g is the device-local gradient block (already summed over
    model-parallel partial terms by GSPMD before entry). ``data_size`` is the
    static "data" axis extent (shapes below depend on it, so it must be a
    Python int, not a collective result)."""
    # flatten so the scatter axis always divides
    flat = g.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % data_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    # 1. reduce-scatter over data (psum_scatter)
    shard = jax.lax.psum_scatter(
        flat.reshape(data_size, -1), "data", scatter_dimension=0, tiled=False
    )
    # 2. all-reduce across pods
    shard = jax.lax.psum(shard, "pod")
    # 3. all-gather back over data
    full = jax.lax.all_gather(shard, "data", axis=0, tiled=False).reshape(-1)
    if pad:
        full = full[:n]
    return full.reshape(g.shape)


def hierarchical_mean(grads: Any, mesh, replicated_specs) -> Any:
    """All leaves are replicated inputs per (pod, data) and already divided by the
    global batch; returns the cross-replica mean with the hierarchical schedule."""

    n_rep = mesh.shape["pod"] * mesh.shape["data"]
    data_size = mesh.shape["data"]

    def body(g):
        return jax.tree.map(lambda x: _hier_one(x, data_size) / n_rep, g)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(replicated_specs,), out_specs=replicated_specs,
        check_vma=False,
    )
    return fn(grads)
