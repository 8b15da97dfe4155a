"""Plain reference of the Mixtral-8x7B decoder (arXiv:2401.04088; HF
``MixtralForCausalLM``): Mistral's attention block, and in place of the
feed-forward a sparse mixture of 8 SiLU-gated experts. The router takes the
softmax over all experts, keeps the top 2 and renormalises their weights; a
token's output is the weighted sum of its two experts. No capacity limit and
no dropped token. float32, highest matmul precision, no cache.

Experts are visited one at a time (``lax.scan`` over the expert axis), each
over every token with a weight that is 0 where the router did not choose it:
the same sum, and one expert's float32 weights (0.7 GB at these widths) in
memory at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import mistral


def moe_feed_forward(model, f32, w, h):
    n_e, k = int(model["n_experts"]), int(model.get("moe_top_k", 2))
    probs = jax.nn.softmax(h @ f32(w["w_router"]), axis=-1)          # [S, E]
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    def one_expert(out, e):
        weight = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)   # [S]
        gate, up = f32(w.expert("w_gate_e", e)), f32(w.expert("w_up_e", e))
        y = (jax.nn.silu(h @ gate) * (h @ up)) @ f32(w.expert("w_down_e", e))
        return out + weight[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(n_e))
    return out


def forward(model, weights, tokens, positions):
    return mistral.forward(model, weights, tokens, positions, ffn=moe_feed_forward)
