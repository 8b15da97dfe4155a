"""Plain reference of Falcon-H1 (``model_type: falcon_h1``;
https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct): pre-norm blocks in
which a Mamba-2 mixer and grouped-query attention run IN PARALLEL on the same
normed input, a SiLU-gated feed-forward, and muP multipliers on every branch.
float32, highest matmul precision; the recurrence is a ``lax.scan`` over
TOKENS (no chunks: nothing of the served kernels' algebra), the attention a
full causal softmax, no cache, no batching, no kernel; one ``jax.jit`` per
layer so that a layer's float32 weights (1.72 GB) exist only while it runs,
and the output head in blocks of the vocabulary (261,120 x 5,120 float32
would be 5.35 GB at once).

One block, as this repository reads the published model (T tokens, RMSNorm
with eps ``norm_eps``; H = ``mamba_n_heads`` heads of P = ``mamba_d_head``
channels, G = ``mamba_n_groups`` groups, N = ``mamba_d_state``):

    x0 = E[tok] * embedding_multiplier
    u  = RMSNorm_in(x)
    -- mixer (Mamba-2) --
    p  = ((u * ssm_in_multiplier) W_in) * mup     columns z | x | B | C | dt
         mup = ssm_multipliers[0..4], one value per slice
    c_t = conv_b + sum_{k=0..3} conv_w[k] * xBC_{t-3+k}     over [x|B|C]
          channels, each channel alone, inputs before the sequence are 0
    x, B, C = silu(c)
    dt = softplus(dt + dt_bias)      a = exp(dt * A),  A = -exp(A_log) per head
    h_t = a_t h_{t-1} + dt_t * x_t (outer) B_t        per head [P, N]; the B, C
    y_t = h_t C_t + D x_t                             of a group serve H / G heads
    y  = GroupRMSNorm(y * silu(z); G groups of d_ssm / G, weight)
    m  = (y W_out) * ssm_out_multiplier
    -- attention, the same u --
    q, k, v = (u * attention_in_multiplier) W_q,k,v ;  k *= key_multiplier
    q, k = RoPE(theta, all D dims) ;  o = softmax(q k^T / sqrt(D), causal) v
    t  = (o W_o) * attention_out_multiplier
    x  = x + m + t
    -- MLP --
    g  = RMSNorm_ff(x)
    x  = x + (W_down (silu(g W_gate * mlp_multipliers[0]) * (g W_up))) * mlp_multipliers[1]
    logits = (RMSNorm_f(x_L) W_head) * lm_head_multiplier

ASSUMED, where ``config.json`` has no key (each also in the configuration
file's ``assumed``): the grouped norm has ``mamba_n_groups`` groups; the gate
comes FIRST (``mamba_norm_before_gate`` false is published; that it means
``norm(y * silu(z))`` is the Mamba-2 code's reading); ``time_step`` limits
are (0, inf), so dt is not clipped; ``D`` multiplies the convolved,
activated x; the two halves of a head are rotated against each other (the
repo's rotary layout, a permutation of weight columns); the state is float32.
Departures from the published description: none known.

``state_dtype`` (a measuring device of the tolerance, never the yardstick):
the state rounded to that type after every token, one precision under the
configuration's, which the comparison has to refuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import mistral

VOCAB_BLOCK = 17408


def _sizes(model):
    d_ssm, n_h = int(model["mamba_d_ssm"]), int(model["mamba_n_heads"])
    g, n = int(model.get("mamba_n_groups", 1)), int(model["mamba_d_state"])
    return d_ssm, n_h, d_ssm // n_h, g, n


def mixer(model, f32, w, u, state_dtype=None):
    t = u.shape[0]
    d_ssm, n_h, p_, g, n = _sizes(model)
    k_conv = int(model.get("mamba_d_conv", 4))
    mults = [float(v) for v in model.get("ssm_multipliers", [1.0] * 5)]
    widths = (d_ssm, d_ssm, g * n, g * n, n_h)
    mup = jnp.concatenate([jnp.full((wd,), v, jnp.float32)
                           for wd, v in zip(widths, mults)])
    p = ((u * float(model.get("ssm_in_multiplier", 1.0))) @ f32(w["w_in"])) * mup
    z, xbc, dt = p[:, :d_ssm], p[:, d_ssm:2 * d_ssm + 2 * g * n], p[:, -n_h:]
    # the causal depthwise convolution: zeros before the sequence
    padded = jnp.concatenate(
        [jnp.zeros((k_conv - 1, xbc.shape[1]), jnp.float32), xbc])
    conv_w = f32(w["conv_w"])
    c = f32(w["conv_b"]) + sum(
        conv_w[k] * padded[k:k + t] for k in range(k_conv))
    c = jax.nn.silu(c)
    xs = c[:, :d_ssm].reshape(t, n_h, p_)
    bm = jnp.repeat(c[:, d_ssm:d_ssm + g * n].reshape(t, g, n), n_h // g, axis=1)
    cm = jnp.repeat(c[:, d_ssm + g * n:].reshape(t, g, n), n_h // g, axis=1)
    dt = jax.nn.softplus(dt + f32(w["dt_bias"]))               # [T, H]
    a = jnp.exp(-dt * jnp.exp(f32(w["a_log"])))

    def token(h, xs_):
        a_t, dt_t, x_t, b_t, c_t = xs_
        h = a_t[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] \
            * b_t[:, None, :]                                  # [H, P, N]
        if state_dtype is not None:
            # reduce_precision, not a pair of converts: the TPU compiler
            # drops a round trip through a narrower type (excess precision)
            kind = jnp.finfo(state_dtype)
            h = jax.lax.reduce_precision(h, kind.nexp, kind.nmant)
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((n_h, p_, n), jnp.float32), (a, dt, xs, bm, cm))
    y = (y + f32(w["d_skip"])[None, :, None] * xs).reshape(t, d_ssm)
    y = (y * jax.nn.silu(z)).reshape(t, g, d_ssm // g)
    y = y * jax.lax.rsqrt(
        jnp.mean(y * y, axis=-1, keepdims=True) + model["norm_eps"])
    y = y.reshape(t, d_ssm) * f32(w["ssm_norm"])
    return (y @ f32(w["w_out"])) * float(model.get("ssm_out_multiplier", 1.0))


def attention(model, f32, w, u, positions):
    s = u.shape[0]
    n_h, n_kv = int(model["n_heads"]), int(model["n_kv_heads"])
    d = int(model.get("head_dim") or model["dim"] // n_h)
    theta = float(model["rope_theta"])      # the published 1e11 is an integer
    a_in = u * float(model.get("attention_in_multiplier", 1.0))
    q = mistral.rope((a_in @ f32(w["wq"])).reshape(s, n_h, d), positions, theta)
    k = (a_in @ f32(w["wk"])) * float(model.get("key_multiplier", 1.0))
    k = mistral.rope(k.reshape(s, n_kv, d), positions, theta)
    v = (a_in @ f32(w["wv"])).reshape(s, n_kv, d)
    k = jnp.repeat(k, n_h // n_kv, axis=1)   # query head i reads kv head i // g
    v = jnp.repeat(v, n_h // n_kv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * d ** -0.5
    causal = positions[:, None] >= positions[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hst,thd->shd", probs, v).reshape(s, n_h * d)
    return (out @ f32(w["wo"])) * float(
        model.get("attention_out_multiplier", 1.0))


def feed_forward(model, f32, w, g):
    m0, m1 = (float(v) for v in model.get("mlp_multipliers", [1.0, 1.0]))
    gate = jax.nn.silu((g @ f32(w["w_gate"])) * m0)
    return ((gate * (g @ f32(w["w_up"]))) @ f32(w["w_down"])) * m1


def block(model, f32, w, x, positions, state_dtype=None):
    eps = model["norm_eps"]
    u = mistral.rms_norm(x, f32(w["attn_norm"]), eps)
    x = x + mixer(model, f32, w, u, state_dtype) \
        + attention(model, f32, w, u, positions)
    return x + feed_forward(
        model, f32, w, mistral.rms_norm(x, f32(w["ffn_norm"]), eps))


def forward(model, weights, tokens, positions, state_dtype=None):
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = weights.embed(tokens) * float(
            model.get("embedding_multiplier", 1.0))
        step = jax.jit(lambda layers, i, x: block(
            model, weights.f32, weights.view(layers, i), x, pos, state_dtype))
        for i in range(int(model["n_layers"])):
            x = step(*weights.layer_args(i), x)
        x = mistral.rms_norm(x[positions], weights.final_norm, model["norm_eps"])
        head = jax.jit(lambda x, part: x @ weights.f32(part))
        vocab = int(model["vocab_size"])
        logits = jnp.concatenate([
            head(x, jax.tree_util.tree_map(
                lambda a: a[..., j:j + VOCAB_BLOCK], weights.lm_head))
            for j in range(0, vocab, VOCAB_BLOCK)
        ], axis=-1)
        return logits * float(model.get("lm_head_multiplier", 1.0))
