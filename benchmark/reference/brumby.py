"""Plain reference of the Brumby-14B-Base decoder (manifestai/Brumby-14B-Base,
``model_type: brumby``; Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239): pre-norm blocks in which POWER RETENTION of
degree 2 takes the place of attention, and a SiLU-gated feed-forward. There is
no attention layer in the model.

What is computed here is the ATTENTION FORM, with no state, no chunks and no
kernel. For token t of a layer with normed input h_t:

    q = RoPE(RMSNorm_head(h Wq))   40 heads x 128
    k = RoPE(RMSNorm_head(h Wk))    8 heads x 128      v = h Wv   8 x 128
    log g_t = log sigmoid(h_t Wg)   one forget gate per key-value head
    a_ts = (q_t^i . k_s^j)^2 * exp(sum_{r=s+1..t} log g_r^j)     s <= t
    y_t^i = sum_s a_ts v_s^j / sum_s a_ts          query head i of kv head j

No softmax and no 1/sqrt(d): a scale of the scores cancels in the quotient.
float32, highest matmul precision, one ``jax.jit`` per layer like
``mistral.forward`` (whose loop cannot be handed another token mixing, so it
is written out here), the rows of a layer's scores in query blocks so that a
4096-token probe fits (40 heads x 512 x 4108 float32 = 336 MB a block), and
the output head in blocks of the vocabulary (151936 x 5120 float32 would be
3.1 GB at once). ``rms_norm``, ``rope`` and ``feed_forward`` are Mistral's.

Departures from the publication, and what is assumed (the model's
``config.json`` has no key for the retention; each item is also in the
configuration file's ``assumed``): degree 2; the gate as log sigmoid of a
linear map of the block's normed input, one per key-value head; QK-norm and
rotary embeddings kept from the model's Qwen3 lineage; the quotient by the
plain sum of the weights a_ts, with no epsilon and no other stabiliser. The
sum of log g is taken as a difference of running sums in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import mistral

Q_BLOCK = 512
VOCAB_BLOCK = 16384


def retention(model, f32, w, h, positions):
    s = h.shape[0]
    n_h, n_kv = int(model["n_heads"]), int(model["n_kv_heads"])
    d = int(model.get("head_dim") or model["dim"] // n_h)
    eps, theta = model["norm_eps"], model["rope_theta"]
    q = (h @ f32(w["wq"])).reshape(s, n_h, d)
    k = (h @ f32(w["wk"])).reshape(s, n_kv, d)
    v = (h @ f32(w["wv"])).reshape(s, n_kv, d)
    q = mistral.rope(mistral.rms_norm(q, f32(w["q_norm"]), eps), positions, theta)
    k = mistral.rope(mistral.rms_norm(k, f32(w["k_norm"]), eps), positions, theta)
    log_g = jax.nn.log_sigmoid(h @ f32(w["wg"]))                   # [S, Hkv]
    cum = jnp.cumsum(log_g, axis=0)
    q = q.reshape(s, n_kv, n_h // n_kv, d)    # query head i reads kv head i // g

    def rows(t0):
        """y of the queries t0 .. t0 + block against every key before them."""
        blk = min(Q_BLOCK, s)
        qb = jax.lax.dynamic_slice_in_dim(q, t0, blk)
        cb = jax.lax.dynamic_slice_in_dim(cum, t0, blk)
        pb = jax.lax.dynamic_slice_in_dim(positions, t0, blk)
        scores = jnp.einsum("tkgd,skd->kgts", qb, k) ** 2
        causal = (pb[:, None] >= positions[None, :])[None]         # [1, t, s]
        decay = jnp.exp(jnp.where(
            causal, cb.T[:, :, None] - cum.T[:, None, :], 0.0))    # [Hkv, t, s]
        a = jnp.where(causal[:, None], scores * decay[:, None], 0.0)
        num = jnp.einsum("kgts,skd->tkgd", a, v)
        return num / jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None]

    blk = min(Q_BLOCK, s)
    # block starts; the last block is moved back so that it ends at s
    starts = jnp.minimum(jnp.arange(0, s, blk), s - blk)
    out = jax.lax.map(rows, starts)                                # [nb, blk, ...]
    y = jnp.zeros((s, n_kv, n_h // n_kv, d), jnp.float32)
    for i in range(starts.shape[0]):
        y = jax.lax.dynamic_update_slice_in_dim(y, out[i], starts[i], 0)
    return y.reshape(s, n_h * d) @ f32(w["wo"])


def block(model, f32, w, x, positions):
    eps = model["norm_eps"]
    x = x + retention(model, f32, w,
                      mistral.rms_norm(x, f32(w["attn_norm"]), eps), positions)
    return x + mistral.feed_forward(
        model, f32, w, mistral.rms_norm(x, f32(w["ffn_norm"]), eps))


def forward(model, weights, tokens, positions):
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = weights.embed(tokens)
        step = jax.jit(lambda layers, i, x: block(
            model, weights.f32, weights.view(layers, i), x, pos))
        for i in range(int(model["n_layers"])):
            x = step(*weights.layer_args(i), x)
        x = mistral.rms_norm(x[positions], weights.final_norm, model["norm_eps"])
        head = jax.jit(lambda x, part: x @ weights.f32(part))
        vocab = int(model["vocab_size"])
        return jnp.concatenate([
            head(x, jax.tree_util.tree_map(
                lambda a: a[..., j:j + VOCAB_BLOCK], weights.lm_head))
            for j in range(0, vocab, VOCAB_BLOCK)
        ], axis=-1)
