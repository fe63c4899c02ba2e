"""Explicit split-KV distributed decode attention (flash-decoding across the model
axis) — the shard_map twin of the GSPMD-derived path in models/attention.py.

Each model-shard holds a sequence slice of the KV cache; it computes partial
(m_i = max score, l_i = Σ exp, acc_i = Σ exp·V) over its slice, then one psum-style
combine with global max stabilization reconstructs the exact softmax:

    m = pmax(m_i);  l = Σ_i l_i·e^{m_i-m};  out = Σ_i acc_i·e^{m_i-m} / l

Communication per step: O(B·H·(2 + hd)) — independent of sequence length, which is
what makes 500k-token decode collective-light (see the long_500k roofline rows)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def split_kv_decode_attention(
    mesh,
    axis_name: str,
    q: jax.Array,          # (B, H, hd) — replicated over the model axis
    k_cache: jax.Array,    # (B, S, KV, hd) — S sharded over the model axis
    v_cache: jax.Array,
):

    def body(q, k, v):
        b, h, hd = q.shape
        kv = k.shape[2]
        rep = h // kv
        qg = q.reshape(b, kv, rep, hd)
        s = jnp.einsum("bkrd,bskd->bkrs", qg, k).astype(jnp.float32) * (hd ** -0.5)
        m_loc = s.max(axis=-1)                                   # (B,KV,rep)
        m = jax.lax.pmax(m_loc, axis_name)
        e = jnp.exp(s - m[..., None])
        l_loc = e.sum(axis=-1)
        acc_loc = jnp.einsum("bkrs,bskd->bkrd", e.astype(v.dtype), v)
        l = jax.lax.psum(l_loc, axis_name)
        acc = jax.lax.psum(acc_loc, axis_name)
        out = acc / l[..., None].astype(acc.dtype)
        return out.reshape(b, h, hd)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(None, axis_name, None, None), P(None, axis_name, None, None)),
        out_specs=P(),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache)


def reference_decode_attention(q, k_cache, v_cache):
    """Single-device oracle."""
    b, h, hd = q.shape
    kv = k_cache.shape[2]
    rep = h // kv
    qg = q.reshape(b, kv, rep, hd)
    s = jnp.einsum("bkrd,bskd->bkrs", qg, k_cache).astype(jnp.float32) * (hd ** -0.5)
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkrs,bskd->bkrd", w, v_cache)
    return out.reshape(b, h, hd)
