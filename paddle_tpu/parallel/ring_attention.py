"""Ring attention: sequence/context parallelism over an `sp` mesh axis.

The reference has no sequence parallelism (SURVEY.md §5.7: sequence scaling
= memory passes only); this is the net-new long-context capability.  Design
follows the blockwise-attention + KV-rotation scheme (Ring Attention): each
device holds a sequence shard of Q/K/V; KV blocks rotate around the ICI
ring via `ppermute` while each device accumulates its Q-block's attention
with numerically-stable online softmax, so attention over sequence length
L costs O(L/n) memory per device and overlaps compute with neighbor
exchange.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


_NEG = -1e9


def _block_attn(q, k, v, q_off, k_off, scale, causal):
    """Attention of one (Q-block, KV-block) pair with global-position causal
    masking; returns unnormalized o, row max m, row sum l."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        lq, lk = q.shape[2], k.shape[2]
        qpos = q_off + jnp.arange(lq)[:, None]
        kpos = k_off + jnp.arange(lk)[None, :]
        s = jnp.where(kpos > qpos, _NEG, s)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o, m, l


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Runs on each device inside shard_map; q/k/v are local seq shards
    (B, H, L_local, dh)."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    lq = q.shape[2]
    lk = k.shape[2]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    q_off = my * lq

    m0 = jnp.full(q.shape[:-1], _NEG, dtype=jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], dtype=jnp.float32)
    o0 = jnp.zeros(q.shape, dtype=jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        m, l, o, k_blk, v_blk = carry
        src = (my - i) % n  # owner of the KV block currently held
        ob, mb, lb = _block_attn(
            q.astype(jnp.float32),
            k_blk.astype(jnp.float32),
            v_blk.astype(jnp.float32),
            q_off,
            src * lk,
            scale,
            causal,
        )
        m_new = jnp.maximum(m, mb)
        corr_old = jnp.exp(m - m_new)
        corr_new = jnp.exp(mb - m_new)
        l = l * corr_old + lb * corr_new
        o = o * corr_old[..., None] + ob * corr_new[..., None]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return m_new, l, o, k_blk, v_blk

    m, l, o, _, _ = jax.lax.fori_loop(0, n, body, (m0, l0, o0, k, v))
    # fully-masked rows (causal, first block) have l == 0; emit zeros
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = o / safe_l[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    mesh: Optional[Mesh] = None,
    axis_name: str = "sp",
    causal: bool = False,
    batch_axis: Optional[str] = "dp",
):
    """Attention over sequence-sharded q/k/v of shape (B, H, L, dh).

    With a mesh carrying `axis_name`, L is sharded over it and the KV ring
    runs over ICI; without one this reduces to plain (flash-style blockwise)
    attention semantics on one device.
    """
    if mesh is None or axis_name not in mesh.shape:
        # single-shard fallback: same math, one block
        o, m, l = _block_attn(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
            0, 0, 1.0 / (q.shape[-1] ** 0.5), causal,
        )
        safe_l = jnp.where(l == 0.0, 1.0, l)
        return (o / safe_l[..., None]).astype(q.dtype)

    b_ax = batch_axis if (batch_axis and batch_axis in mesh.shape) else None
    spec = P(b_ax, None, axis_name, None)
    fn = functools.partial(_ring_attention_local, axis_name=axis_name, causal=causal)
    shard = jax.shard_map(
        lambda q_, k_, v_: fn(q_, k_, v_),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return shard(q, k, v)
