"""Plain reference of the Mistral-7B decoder (arXiv:2310.06825; HF
``MistralForCausalLM``): pre-norm blocks of grouped-query attention with
rotary embeddings (rotate-half layout) and a SiLU-gated feed-forward.
v0.3 has no sliding window. float32, highest matmul precision, no cache, no
batching, no kernel; one ``jax.jit`` per layer so that a layer is one
dispatch and its float32 weights exist only while it runs.

Departure from the publication: none in the mathematics. ``w[name]`` is a
served leaf and ``f32`` makes it float32 where it is used: the int8 weights
the system serves, as scale * q.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [S, H, D]; rotate-half rotary embedding at ``positions`` [S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(model, f32, w, h, positions):
    s = h.shape[0]
    n_h, n_kv = int(model["n_heads"]), int(model["n_kv_heads"])
    d = int(model.get("head_dim") or model["dim"] // n_h)
    q = rope((h @ f32(w["wq"])).reshape(s, n_h, d), positions, model["rope_theta"])
    k = rope((h @ f32(w["wk"])).reshape(s, n_kv, d), positions, model["rope_theta"])
    v = (h @ f32(w["wv"])).reshape(s, n_kv, d)
    k = jnp.repeat(k, n_h // n_kv, axis=1)   # query head i reads kv head i // g
    v = jnp.repeat(v, n_h // n_kv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * d ** -0.5
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hst,thd->shd", probs, v).reshape(s, n_h * d)
    return out @ f32(w["wo"])


def feed_forward(model, f32, w, h):
    return (jax.nn.silu(h @ f32(w["w_gate"])) * (h @ f32(w["w_up"]))) @ f32(w["w_down"])


def block(model, f32, w, x, positions, ffn=feed_forward):
    eps = model["norm_eps"]
    x = x + attention(model, f32, w, rms_norm(x, f32(w["attn_norm"]), eps), positions)
    return x + ffn(model, f32, w, rms_norm(x, f32(w["ffn_norm"]), eps))


def forward(model, weights, tokens, positions, ffn=feed_forward):
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = weights.embed(tokens)
        step = jax.jit(lambda layers, i, x: block(
            model, weights.f32, weights.view(layers, i), x, pos, ffn))
        for i in range(int(model["n_layers"])):
            x = step(*weights.layer_args(i), x)
        x = rms_norm(x[positions], weights.final_norm, model["norm_eps"])
        return jax.jit(lambda x, head: x @ weights.f32(head))(x, weights.lm_head)
