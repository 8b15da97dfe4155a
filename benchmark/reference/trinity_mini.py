"""Plain reference of Trinity-Mini (``model_type: afmoe``;
https://huggingface.co/arcee-ai/Trinity-Mini): a decoder that mixes a sliding
window (three layers of four) and full attention without positions (the
fourth) over grouped-query K/V, gates the attention output, norms both sides
of every sublayer, and routes its feed-forward by sigmoid scores with one
shared expert. float32, highest matmul precision, no cache, no kernel, the
mask built from positions, exact top-k, experts one at a time; query blocks,
column groups and token blocks only bound the temporaries (a dequantised
float32 expert layer is 3.2 GB whole, so it is never whole).

T tokens, d = ``dim``, RMSNorm with eps ``norm_eps`` everywhere:

    h_0 = E[tok] * sqrt(d)                          (mup_enabled; ASSUMED)
    layer l, kind = layer_types[l]:
      a = RMSNorm_in(h)
      q = W^Q a [T, H, D]   k = W^K a [T, Hkv, D]   v = W^V a   g = W^G a
      q = RMSNorm_q(q), k = RMSNorm_k(k)   over a head's D dims, one weight
                                           [D] each
      sliding_attention: q, k rotated (rope_theta, all D dims, no scaling);
                         visible s: t - sliding_window < s <= t
      full_attention:    NO rotation (NoPE); visible s: s <= t
      p = softmax_s(q . k / sqrt(D)) over the visible s, H / Hkv query heads
          a K/V head;  o = sum_s p v
      y = W^O (o * sigmoid(g))             elementwise over the H x D values
      h = h + RMSNorm_post_attn(y)
      m = RMSNorm_pre_mlp(h)
      l < num_dense_layers:  z = W^D (silu(W^G1 m) * W^U m), width ffn_dim
      else: s = sigmoid(W^R m) (router_experts outputs)
            C = top-k of s + b             (b selects and does not weigh)
            w_e = s_e / sum_C s * route_scale        (route_norm)
            z = sum over e in C AND HELD of w_e SwiGLU_e(m)
                + SwiGLU_shared(m)         (width moe_intermediate_size x
                                            n_shared_experts)
      h = h + RMSNorm_post_mlp(z)
    logits = W^head RMSNorm_f(h)                    (untied)

ASSUMED, where the published ``config.json`` has no key (each also in the
configuration file): the gate is computed from the block's normed input and
is elementwise (``gate_proj`` d x H D), not one value a head; rotation on
the sliding layers only; the window's edge (``sliding_window`` keys = the
token and the ``sliding_window - 1`` before it); the embedding's sqrt(d);
``n_group`` = ``topk_group`` = 1, so one group; b is seeded normal x 0.02;
the two halves of a head are rotated against each other (the repo's rotary
layout, a permutation of weight columns); ``load_balance_coeff`` and
``use_grouped_mm`` do not touch the forward pass. ``experts_held`` =
[first, count]: the routed experts of this chip; what absent ones would add
is left out, as the served program leaves it out (the benchmark's
configuration holds all).

Weights: ``weights.layer_args(i)`` gives an unrolled layer's leaves; where
the layer table's tail repeats (``scan_layers``, models/dots3_note.py
``layer_plan``) the entry after the unrolled layers is the repeating tail,
``{"p<j>": leaves stacked over the repetitions}``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.dots3_note import (
    FULL, WINDOW, _groups, _rounded, by_blocks, layer_plan, rms_norm, rope,
    swiglu,
)

QUERY_BLOCK = 128     # queries scored at once, all heads
TOKEN_BLOCK = 1024    # tokens through an expert at once
HEAD_GROUPS = 16      # column groups of the head (a group in float32 at once)


def layer_kinds(model):
    n_dense = int(model.get("num_dense_layers", 2))
    return [(a, "dense" if i < n_dense else "moe")
            for i, a in enumerate(model["layer_types"])]


def visible_mask(model, attn, q_pos, k_pos, windowed=True):
    """[Q, S] bool from positions: causal, and on a sliding layer the
    window's lower edge."""
    vis = q_pos[:, None] >= k_pos[None, :]
    if attn == WINDOW and windowed:
        vis = jnp.logical_and(
            vis, k_pos[None, :] > q_pos[:, None] - int(model["sliding_window"]))
    return vis


def attention(model, f32, w, a, positions, attn, windowed=True, gated=True,
              rope_full=False, row_dtype=None):
    """y [S, dim] of one attention layer on its normed input ``a``. The
    controls: ``windowed`` False attends every causal key on a sliding
    layer, ``gated`` False drops the gate, ``rope_full`` rotates a full
    layer too; ``row_dtype`` rounds what a cache would hold (k after its
    norm and rotation, v) through that type."""
    s = a.shape[0]
    n_heads, n_kv = int(model["n_heads"]), int(model["n_kv_heads"])
    d = int(model["head_dim"])
    g = n_heads // n_kv
    eps, theta = model["norm_eps"], float(model["rope_theta"])
    q = rms_norm((a @ f32(w["wq"])).reshape(s, n_heads, d),
                 f32(w["q_norm"]), eps)
    k = rms_norm((a @ f32(w["wk"])).reshape(s, n_kv, d), f32(w["k_norm"]),
                 eps)
    v = (a @ f32(w["wv"])).reshape(s, n_kv, d)
    gate = jax.nn.sigmoid(a @ f32(w["w_attn_gate"]))
    if attn == WINDOW or rope_full:
        q, k = rope(q, positions, theta), rope(k, positions, theta)
    k, v = _rounded(k, row_dtype), _rounded(v, row_dtype)
    scale = d ** -0.5

    def one(qb, pos):
        vis = visible_mask(model, attn, pos, positions, windowed)
        qg = qb.reshape(-1, n_kv, g, d)
        score = jnp.einsum("qkgd,skd->kgqs", qg, k) * scale
        score = jnp.where(vis[None, None], score, -jnp.inf)
        top = jnp.maximum(jnp.max(score, axis=-1, keepdims=True), -1e30)
        p = jnp.where(vis[None, None], jnp.exp(score - top), 0.0)
        denom = jnp.sum(p, axis=-1, keepdims=True)
        p = p / jnp.where(denom == 0.0, 1.0, denom)
        return jnp.einsum("kgqs,skd->qkgd", p, v).reshape(-1, n_heads * d)

    o = by_blocks(one, QUERY_BLOCK, q, positions)
    if gated:
        o = o * gate
    return o @ f32(w["wo"])


def route(model, f32, w, m, bias=True):
    """(gates [S, k], experts [S, k]); ``bias`` False is a control."""
    k = int(model["moe_top_k"])
    score = jax.nn.sigmoid(m @ f32(w["w_router"]))
    pick = score + f32(w["router_bias"]) if bias else score
    _, top_e = jax.lax.top_k(pick, k)
    top_p = jnp.take_along_axis(score, top_e, axis=-1)
    top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
    return top_p * float(model.get("route_scale", 1.0)), top_e


def moe_feed_forward(model, f32, w, m, bias=True, shared=True):
    """The held experts' share of the routed sum, one expert at a time,
    plus (``shared``) the shared expert."""
    first, count = (int(v) for v in model.get(
        "experts_held", (0, model["router_experts"])))
    top_p, top_e = route(model, f32, w, m, bias)

    def one_expert(out, e):
        weight = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), axis=-1)
        gate, up = f32(w.expert("w_gate_e", e)), f32(w.expert("w_up_e", e))
        down = f32(w.expert("w_down_e", e))
        y = by_blocks(lambda x: (jax.nn.silu(x @ gate) * (x @ up)) @ down,
                      TOKEN_BLOCK, m)
        return out + weight[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), jnp.arange(count))
    return out + swiglu(f32, w, m) if shared else out


def block(model, f32, w, x, positions, kind, windowed=True, gated=True,
          bias=True, rope_full=False, post_norms=True, row_dtype=None,
          traced=True):
    """(x after the layer, (the attention's output after W^O, the
    feed-forward's output), both before their post norms; None unless
    ``traced``). ``post_norms`` False drops both output norms (a control)."""
    attn, ffn = kind
    eps = model["norm_eps"]
    y = attention(model, f32, w, rms_norm(x, f32(w["attn_norm"]), eps),
                  positions, attn, windowed, gated, rope_full, row_dtype)
    x = x + (rms_norm(y, f32(w["post_attn_norm"]), eps) if post_norms else y)
    m = rms_norm(x, f32(w["ffn_norm"]), eps)
    z = (swiglu(f32, w, m) if ffn == "dense"
         else moe_feed_forward(model, f32, w, m, bias))
    x = x + (rms_norm(z, f32(w["post_ffn_norm"]), eps) if post_norms else z)
    return x, ((y, z) if traced else None)


def layer_weights(model, weights):
    """Per layer (leaves, index): an unrolled layer whole, a layer of the
    repeating tail by its repetition."""
    kinds = layer_kinds(model)
    lead, period = layer_plan(kinds, bool(model.get("scan_layers")))
    out = []
    for i in range(len(kinds)):
        if i < lead:
            out.append(weights.layer_args(i))
        else:
            group = weights.layer_args(lead)[0]
            out.append((group["p{}".format((i - lead) % period)],
                        jnp.int32((i - lead) // period)))
    return out


def head(f32, x, lm_head):
    """x [Q, dim] @ W^head, HEAD_GROUPS column groups at a time: the whole
    head in float32 (1.6 GB at 200,192 rows) never exists."""
    width = jax.tree_util.tree_leaves(lm_head)[0].shape[-1]
    n = HEAD_GROUPS if width % HEAD_GROUPS == 0 else 1
    out = jax.lax.map(lambda cols: x @ f32(cols), _groups(lm_head, -1, n))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], width)


def forward(model, weights, tokens, positions, trace=None,
            precision="highest", **controls):
    """[len(positions), vocab] float32 logits of a full causal pass.
    ``trace`` (a list) receives per layer (attention output, feed-forward
    output); ``controls`` (windowed / gated / bias / post_norms = False,
    rope_full = True) switch a mechanism, ``row_dtype`` rounds the K/V rows
    a cache would hold: float8_e4m3fn is the reading one precision below the
    configured bfloat16, which the cell's tolerance has to refuse."""
    with jax.default_matmul_precision(precision):
        pos = jnp.arange(tokens.shape[0])
        scale = model.get("embed_scale", True)
        scale = (float(model["dim"]) ** 0.5 if scale is True
                 else float(scale or 1.0))
        x = weights.embed(tokens) * scale
        step = jax.jit(
            lambda layers, i, x, kind: block(
                model, weights.f32, weights.view(layers, i), x, pos, kind,
                traced=trace is not None, **controls),
            static_argnums=(3,))
        for kind, (layers, i) in zip(layer_kinds(model),
                                     layer_weights(model, weights)):
            x, seen = step(layers, i, x, kind)
            if trace is not None:
                trace.append(seen)
        x = rms_norm(x[positions], weights.final_norm, model["norm_eps"])
        return jax.jit(lambda x, w: head(weights.f32, x, w))(
            x, weights.lm_head)
