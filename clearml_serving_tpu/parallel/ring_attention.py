"""Ring attention: sequence-parallel exact attention for long context.

The reference has no long-context story (SURVEY.md §5.7 "Absent in the
reference") — this is a new TPU-first design obligation. Sequences are sharded
over the ``sp`` mesh axis; each device holds a [B, S/sp, H, D] slice of q/k/v.
KV blocks rotate around the ring with ``ppermute`` (ICI neighbor exchange,
overlappable with compute by XLA) while each device accumulates its queries'
attention with a numerically-stable streaming softmax (flash-attention style
running max / denominator). Peak memory is O(S/sp) per device instead of O(S),
so context length scales linearly with the ring size.

Causal mode uses block-level structure: a KV block strictly in the future is
skipped wholesale; the diagonal block applies the intra-block causal mask;
past blocks attend densely.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _stream_block(q, k, v, o, m, l, mask):
    """One flash-style accumulation step.

    q: [B,Sq,H,D]  k,v: [B,Sk,H,D]  o: [B,Sq,H,D]  m,l: [B,Sq,H]
    mask: additive [Sq,Sk] or None.
    """
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * (q.shape[-1] ** -0.5)
    if mask is not None:
        scores = scores + mask[None, None]
    block_max = jnp.max(scores, axis=-1)                     # [B,H,Sq]
    block_max = jnp.maximum(block_max, -1e30)                # guard all-masked rows
    m_bhq = jnp.moveaxis(m, -1, 1)                           # [B,H,Sq]
    m_new = jnp.maximum(m_bhq, block_max)
    probs = jnp.exp(scores - m_new[..., None])               # [B,H,Sq,Sk]
    correction = jnp.exp(m_bhq - m_new)                      # [B,H,Sq]
    l_new = jnp.moveaxis(l, -1, 1) * correction + jnp.sum(probs, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    corr_bqh = jnp.moveaxis(correction, 1, -1)               # [B,Sq,H]
    o_new = o * corr_bqh[..., None] + pv.astype(jnp.float32)
    return o_new, jnp.moveaxis(m_new, 1, -1), jnp.moveaxis(l_new, 1, -1)


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Per-device body (runs under shard_map). q,k,v: [B, S_local, H, D]."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, s_q, h, d = q.shape

    # accumulators start as constants; mark them device-varying over the ring
    # axis so the fori_loop carry type matches the body outputs (JAX vma
    # rules)
    def varying(x):
        return lax.pcast(x, axis_name, to="varying")

    o = varying(jnp.zeros((b, s_q, h, d), jnp.float32))
    m = varying(jnp.full((b, s_q, h), -jnp.inf, jnp.float32))
    l = varying(jnp.zeros((b, s_q, h), jnp.float32))

    causal_mask = jnp.where(
        jnp.tril(jnp.ones((s_q, s_q), dtype=bool)), 0.0, -jnp.inf
    ).astype(jnp.float32)

    zeros_mask = jnp.zeros((s_q, s_q), jnp.float32)
    neginf_mask = jnp.full((s_q, s_q), -jnp.inf, jnp.float32)

    def _mask_for(step):
        if not causal:
            return zeros_mask
        # which global block the current k/v came from: future blocks are
        # fully masked, the diagonal block gets the intra-block causal mask,
        # past blocks attend densely. Additive-mask select keeps the traced
        # structure identical across ring steps (shard_map-friendly).
        kv_idx = (my_idx - step) % axis_size
        return jnp.where(
            kv_idx == my_idx,
            causal_mask,
            jnp.where(kv_idx > my_idx, neginf_mask, zeros_mask),
        )

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(step, carry):
        k_cur, v_cur, o, m, l = carry
        o, m, l = _stream_block(q, k_cur, v_cur, o, m, l, _mask_for(step))
        # rotate kv to the next device (ring neighbor exchange over ICI)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return k_next, v_next, o, m, l

    # last block computes without the (discarded) final rotation
    k, v, o, m, l = lax.fori_loop(0, axis_size - 1, body, (k, v, o, m, l))
    o, m, l = _stream_block(q, k, v, o, m, l, _mask_for(axis_size - 1))
    # all-masked rows (can happen only if s_q rows saw nothing) -> zero output
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (o / safe_l[..., None]).astype(q.dtype)


def ring_attention(
    q, k, v, mesh, axis_name: str = "sp", causal: bool = True,
):
    """Exact attention over sequence shards.

    q, k, v: [B, S, H, D] global arrays (sharded/shardable over `axis_name` on
    dim 1). Returns [B, S, H, D] with the same sharding.
    """
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
