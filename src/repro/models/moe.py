"""Mixture-of-Experts FFN: shared experts + fine-grained routed experts (top-k).

Three dispatch paths (cfg.moe_dispatch):

  * "a2a"   — production path: shard_map over the model axis; tokens are packed into
              per-expert capacity buffers locally, exchanged with a single
              ``all_to_all`` to the expert owners, processed batched, and returned with
              a second all_to_all. This is the join paper's mechanism transplanted:
              a skew-aware partitioned exchange with capacity bounds playing the role
              of the engine's padded relation buffers (DESIGN.md §4). Requires a mesh.
  * "dense" — einsum-only fallback: computes every expert on every token and combines
              with sparse gates. No data-dependent comm (pure GSPMD), ~E/top_k compute
              waste; kept as the naive baseline for §Perf.
  * "loop"  — single-device reference used by smoke tests and as the numerical oracle
              for the a2a path (python loop over experts, exact dropless).

Capacity: cap = ceil(T_local · top_k / E · capacity_factor), tokens beyond an expert's
capacity are dropped (their combine weight is zero) — the standard GShard contract; the
"loop" oracle is dropless, so tests compare with capacity_factor large enough to make
drops impossible.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.ctx import current_axes, shard


def moe_params(cfg, key, dtype) -> dict:
    d, dff, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s,
        "w_gate": jax.random.normal(ks[1], (e, d, dff), dtype) * s,
        "w_up": jax.random.normal(ks[2], (e, d, dff), dtype) * s,
        "w_out": jax.random.normal(ks[3], (e, dff, d), dtype) * (dff ** -0.5),
    }
    if cfg.n_shared_experts:
        dsh = cfg.d_ff_expert * cfg.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": jax.random.normal(k1, (d, dsh), dtype) * s,
            "w_up": jax.random.normal(k2, (d, dsh), dtype) * s,
            "w_out": jax.random.normal(k3, (dsh, d), dtype) * (dsh ** -0.5),
        }
    return p


def _expert_ffn(p, x, e_idx=None):
    """x (..., d) through expert weights; if e_idx is None, weights are (E,d,f)."""
    wg, wu, wo = p["w_gate"], p["w_up"], p["w_out"]
    if e_idx is not None:
        wg, wu, wo = wg[e_idx], wu[e_idx], wo[e_idx]
        h = jax.nn.silu(x @ wg) * (x @ wu)
        return h @ wo
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, wg)) * jnp.einsum("td,edf->tef", x, wu)
    return jnp.einsum("tef,efd->ted", h, wo)


def _router(cfg, p, x_flat):
    """x (T, d) → (probs (T,E) fp32, topk_idx (T,k), topk_w (T,k) normalized).
    fp32 accumulation via the dot (no fp32 copy of the token stream)."""
    logits = jnp.einsum(
        "td,de->te", x_flat, p["router"].astype(x_flat.dtype),
        preferred_element_type=jnp.float32,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, cfg.top_k)
    topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
    return probs, topk_idx, topk_w


def _aux_loss(cfg, probs, topk_idx):
    """Switch-style load-balance loss: E · Σ_e f_e · P_e."""
    e = cfg.n_experts
    f = jnp.mean(
        jnp.sum(jax.nn.one_hot(topk_idx, e, dtype=jnp.float32), axis=1), axis=0
    ) / cfg.top_k
    pmean = jnp.mean(probs, axis=0)
    return e * jnp.sum(f * pmean)


# ---------------------------------------------------------------------------


def _moe_loop(cfg, p, x_flat):
    """Dropless python-loop oracle (single device / smoke tests)."""
    probs, topk_idx, topk_w = _router(cfg, p, x_flat)
    out = jnp.zeros_like(x_flat)
    for e in range(cfg.n_experts):
        w_e = jnp.sum(jnp.where(topk_idx == e, topk_w, 0.0), axis=-1)  # (T,)
        y = _expert_ffn(p, x_flat, e_idx=e)
        out = out + y * w_e[:, None].astype(x_flat.dtype)
    return out, _aux_loss(cfg, probs, topk_idx)


def _moe_dense(cfg, p, x_flat):
    """Every expert on every token; sparse combine. Naive §Perf baseline."""
    probs, topk_idx, topk_w = _router(cfg, p, x_flat)
    onehot = jax.nn.one_hot(topk_idx, cfg.n_experts, dtype=jnp.float32)  # (T,k,E)
    gates = jnp.einsum("tk,tke->te", topk_w, onehot)
    y = _expert_ffn(p, x_flat)  # (T,E,d)
    out = jnp.einsum("te,ted->td", gates.astype(x_flat.dtype), y)
    return out, _aux_loss(cfg, probs, topk_idx)


def _pack_capacity(cfg, x_flat, topk_idx, topk_w, cap):
    """Pack tokens into per-expert capacity buffers (E, cap, d) + combine metadata.

    Returns (buffers, (slot_pos (T,k), keep (T,k))) where slot_pos is each (token,
    slot)'s position inside its expert buffer; dropped entries have keep=False."""
    t, k = topk_idx.shape
    e = cfg.n_experts
    flat_expert = topk_idx.reshape(-1)                       # (T*k,) expert per entry
    # position within expert via cumsum over one-hot (GShard trick)
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)  # (T*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) * onehot            # 1-based where routed
    slot = jnp.sum(pos_in_e, axis=-1) - 1                     # (T*k,)
    keep = (slot >= 0) & (slot < cap)
    buffers = jnp.zeros((e, cap, x_flat.shape[-1]), x_flat.dtype)
    src = jnp.repeat(x_flat, k, axis=0)                       # (T*k, d)
    buffers = buffers.at[flat_expert, jnp.clip(slot, 0, cap - 1)].set(
        jnp.where(keep[:, None], src, 0.0), mode="drop"
    )
    return buffers, (slot.reshape(t, k), keep.reshape(t, k))


def _moe_a2a(cfg, p, x_flat, axes):
    """shard_map all_to_all dispatch over the model axis (expert parallelism)."""
    tp = axes.model
    mesh = jax.sharding.get_abstract_mesh()
    tp_size = mesh.shape[tp]
    e = cfg.n_experts
    assert e % tp_size == 0, (e, tp_size)
    e_loc = e // tp_size
    t = x_flat.shape[0]

    probs, topk_idx, topk_w = _router(cfg, p, x_flat)
    aux = _aux_loss(cfg, probs, topk_idx)

    # tokens partitioned over dp AND tp: each device dispatches its own token slice;
    # with sequence parallelism on, this is exactly the residual sharding (no reshard).
    # Decode batches are small: fall back to tp-only sharding (dp groups dispatch
    # redundantly — standard decode EP) or, for tiny T, to the dense path.
    import numpy as np

    dp = axes.data
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    n_tok = x_flat.shape[0]
    if n_tok % (dp_size * tp_size) == 0:
        tok_spec: tuple = tuple(dp) + (tp,)
    elif n_tok % tp_size == 0:
        tok_spec = (tp,)
    else:
        return _moe_dense(cfg, p, x_flat)

    def body(x_loc, idx_loc, w_loc, wg, wu, wo):
        t_loc = x_loc.shape[0]
        cap = int(math.ceil(t_loc * cfg.top_k / e * cfg.capacity_factor))
        # small local batches (decode): pad capacity toward dropless
        cap = max(cap, min(t_loc, 8), 1)
        buffers, (slot, keep) = _pack_capacity(cfg, x_loc, idx_loc, w_loc, cap)
        # (E, cap, d) → (tp, E_loc, cap, d) → a2a → (tp, E_loc, cap, d) from all peers
        buffers = buffers.reshape(tp_size, e_loc, cap, -1)
        recv = jax.lax.all_to_all(buffers, tp, split_axis=0, concat_axis=0, tiled=False)
        # recv: (tp, E_loc, cap, d) — tokens from every peer for MY experts
        recv = recv.transpose(1, 0, 2, 3).reshape(e_loc, tp_size * cap, -1)
        hs = []
        for j in range(e_loc):
            hs.append(_expert_ffn({"w_gate": wg, "w_up": wu, "w_out": wo}, recv[j], e_idx=j))
        y = jnp.stack(hs, axis=0)  # (E_loc, tp*cap, d)
        y = y.reshape(e_loc, tp_size, cap, -1).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(y, tp, split_axis=0, concat_axis=0, tiled=False)
        back = back.reshape(e, cap, -1)  # my tokens, processed by their experts
        # combine: gather each (token, slot)'s row
        flat_e = idx_loc.reshape(-1)
        flat_s = jnp.clip(slot.reshape(-1), 0, cap - 1)
        picked = back[flat_e, flat_s]  # (T*k, d)
        w_flat = jnp.where(keep.reshape(-1), w_loc.reshape(-1), 0.0)
        out = jnp.sum(
            (picked * w_flat[:, None].astype(picked.dtype)).reshape(t_loc, cfg.top_k, -1),
            axis=1,
        )
        return out


    body_sm = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(tok_spec, None),         # x (T, d): tokens sharded over dp × tp
            P(tok_spec, None),
            P(tok_spec, None),
            P(tp, None, None),         # expert weights sharded over model axis (EP)
            P(tp, None, None),
            P(tp, None, None),
        ),
        out_specs=P(tok_spec, None),
        check_vma=False,
    )
    out = body_sm(x_flat, topk_idx, topk_w, p["w_gate"], p["w_up"], p["w_out"])
    return out, aux


def moe_apply(cfg, p: dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x (B,S,d) → (out (B,S,d), aux_loss scalar)."""
    b, s, d = x.shape
    x_flat = x.reshape(b * s, d)
    axes = current_axes()
    dispatch = cfg.moe_dispatch
    if axes is None and dispatch == "a2a":
        dispatch = "loop"
    if dispatch == "a2a":
        out, aux = _moe_a2a(cfg, p, x_flat, axes)
    elif dispatch in ("dense", "einsum"):
        out, aux = _moe_dense(cfg, p, x_flat)
    elif dispatch == "loop":
        out, aux = _moe_loop(cfg, p, x_flat)
    else:
        raise ValueError(f"unknown moe_dispatch {dispatch!r}")

    if cfg.n_shared_experts:
        sp = p["shared"]
        h = jax.nn.silu(x_flat @ sp["w_gate"]) * (x_flat @ sp["w_up"])
        out = out + h @ sp["w_out"]
    return out.reshape(b, s, d), aux
