"""Plain reference of the dots3-note-prev language model (``model_type:
dots3_note``; https://huggingface.co/dots-studio/dots3-note-prev): a decoder
whose every layer attends through a LATENT (DeepSeek-V2/V3 MLA), in two
shapes, and whose feed-forward is a sigmoid-routed mixture with one shared
expert. float32, highest matmul precision, no cache, no kernel, the
NON-absorbed form (keys and values are expanded from the latent for every
head), exact top-k; query blocks and head groups only bound the temporaries.

h_t = RMSNorm(x_t), eps ``norm_eps``; x <- x + attn(h); x <- x + ffn(h).

Latent attention, both layer kinds (sizes by kind: the plain keys on a
``full_attention`` layer, the ``swa_*`` keys on a ``sliding_attention`` one):

    c^Q_t = s_q RMSNorm(W^DQ h_t)            [q^N_ti ; q^R_ti] = W^UQ_i c^Q_t
    [c_t ; k^R_t] = W^DKV h_t                c_t <- s_kv RMSNorm(c_t)
    q^R, k^R rotated (k^R one for all heads)
    k^N_si = W^UK_i c_s                      v_si = W^UV_i c_s
    a_tsi = softmax_s((q^N_ti . k^N_si + q^R_ti . k^R_s) / sqrt(d_n + d_r))
            over the VISIBLE s
    o_ti = sum_s a_tsi v_si                  g_ti = sigmoid(w^G_i . h_t)
    y_t = W^O concat_i(g_ti o_ti)            (headwise gate, arXiv:2505.06708)

    s_q = sqrt(dim / q_lora_rank), s_kv = sqrt(dim / kv_lora_rank)
    (``apply_mla_qkv_lora_rescale``, read as LongCat-Flash's
    mla_scale_q_lora / mla_scale_kv_lora: ASSUMED). ``rope_scaling`` null.

Visible s on a full layer: the learned selection (DeepSeek-V3.2's indexer)

    q^I_tj = W^IQ_j c^Q_t (j = 1..index_n_heads; the first qk_rope_head_dim
             of index_head_dim dims rotated: ASSUMED from V3.2)
    k^I_s = LayerNorm(W^IK h_s) (same rotation)      w_t = W^IW h_t
    I_ts = J^-1/2 D^-1/2 sum_j w_tj ReLU(q^I_tj . k^I_s)
    S_t = the index_topk largest I_ts among s <= t (all of them while
          t < index_topk)

Visible s on a window layer: t - sliding_window_size < s <= t (513 = the
token and the 512 before it: ASSUMED).

Feed-forward: the first ``first_k_dense_replace`` layers SwiGLU(ffn_dim).
The others: s_t = sigmoid(W^R h_t); chosen = top-k of s_t + b (``noaux_tc``:
the bias selects and does not weigh; one group); g_te = s_te / sum_chosen s
x routed_scaling_factor; y_t = sum over e chosen AND HELD of g_te
SwiGLU_e(h_t) + SwiGLU_shared(h_t). ``experts_held`` = [first, count]: the
experts of this chip (one of the chips that share each layer); what the
absent experts would add is left out, as the served program leaves it out.

Departures from the publications: V3.2's Hadamard rotation of the indexer's
q and k is left out (a rotation of both sides leaves the product alone), as
is its FP8 storage (a storage format is another configuration). The rotary
embedding rotates the two halves of the rotated dims against each other, not
interleaved pairs: a permutation of the weights' columns.

Weights: ``weights.layer_args(i)`` gives a leading layer's leaves; under
``scan_layers`` the entry after the leading layers is the repeating tail,
``{"p<j>": leaves stacked over the repetitions}`` (models/dots3_note.py
``layer_plan``, repeated here in plain Python).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FULL, WINDOW = "full_attention", "sliding_attention"
QUERY_BLOCK = 64      # queries scored at once
HEAD_GROUP = 16       # heads whose keys and values exist at once
TOKEN_BLOCK = 1024    # tokens through a feed-forward at once
FFN_GROUP = 1536      # hidden columns of a feed-forward at once


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def rope(x, positions, theta):
    """x [S, (H,) D], its two halves rotated against each other."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_plan(kinds, scan):
    n = len(kinds)
    if scan:
        for lead in range(n):
            tail = kinds[lead:]
            for p in range(1, len(tail) // 2 + 1):
                if len(tail) % p == 0 and tail == tail[:p] * (len(tail) // p):
                    return lead, p
    return n, 0


def layer_kinds(model):
    n_dense = int(model.get("first_k_dense_replace", 1))
    return [(a, "dense" if i < n_dense else "moe")
            for i, a in enumerate(model["layer_types"])]


def sizes(model, attn):
    pre = "" if attn == FULL else "swa_"
    return dict(
        heads=int(model["n_heads"] if attn == FULL
                  else model["swa_num_attention_heads"]),
        q_rank=int(model[pre + "q_lora_rank"]),
        d_c=int(model[pre + "kv_lora_rank"]),
        d_n=int(model[pre + "qk_nope_head_dim"]),
        d_r=int(model[pre + "qk_rope_head_dim"]),
        d_v=int(model[pre + "v_head_dim"]),
        theta=float(model["rope_theta"] if attn == FULL
                    else model["swa_rope_theta"]),
    )


def by_blocks(fn, size, *arrays):
    """``fn`` over blocks of ``size`` rows of the arrays (padded with zeros;
    a pad row's result is cut off): what bounds a pass's temporaries."""
    n = arrays[0].shape[0]
    count = -(-n // size)
    pad = count * size - n
    cut = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (count, size) + a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda block: fn(*block), tuple(cut))
    return out.reshape((count * size,) + out.shape[2:])[:n]


def selection(model, f32, w, h, c_q, positions, indexer=True, row_dtype=None):
    """[S, S] bool: which keys each query of a full layer sees. With
    ``indexer`` False: plain causal (a control)."""
    s = h.shape[0]
    if not indexer:
        return positions[:, None] >= positions[None, :]
    z = sizes(model, FULL)
    n_j, d_i = int(model["index_n_heads"]), int(model["index_head_dim"])
    topk = min(int(model["index_topk"]), s)
    d_r, theta = z["d_r"], z["theta"]
    k_i = layer_norm(h @ f32(w["wi_k"]), f32(w["wi_k_norm"]),
                     f32(w["wi_k_bias"]), model["norm_eps"])
    k_i = _rounded(jnp.concatenate(
        [rope(k_i[:, :d_r], positions, theta), k_i[:, d_r:]], axis=-1),
        row_dtype)
    w_i = (h @ f32(w["wi_w"])) * (n_j * d_i) ** -0.5
    wi_q = f32(w["wi_q"])

    def one(cq, wt, pos):
        q = (cq @ wi_q).reshape(-1, n_j, d_i)
        q = jnp.concatenate(
            [rope(q[..., :d_r], pos, theta), q[..., d_r:]], axis=-1)
        score = jnp.einsum("qjd,sd->qjs", q, k_i)
        score = jnp.sum(jax.nn.relu(score) * wt[:, :, None], axis=1)
        vis = pos[:, None] >= positions[None, :]
        score = jnp.where(vis, score, -jnp.inf)
        _, idx = jax.lax.top_k(score, topk)                       # [Q, K]
        keep = jnp.arange(topk)[None] < jnp.minimum(pos + 1, topk)[:, None]
        rows = jnp.arange(q.shape[0])[:, None]
        return jnp.logical_and(
            jnp.zeros((q.shape[0], s), bool).at[rows, idx].max(keep), vis)

    return by_blocks(one, QUERY_BLOCK, c_q, w_i, positions)


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(x.dtype)


def latent_attention(model, f32, w, h, positions, attn, indexer=True,
                     windowed=True, row_dtype=None):
    """(y [S, dim], visible [S, S] bool). The controls: ``indexer`` False
    attends every causal key on a full layer, ``windowed`` False on a window
    layer; ``row_dtype`` rounds what a cache would hold (c, k^R, k^I) through
    that type: the reading one precision below the configured rows. Heads a
    group at a time (their keys and values for the whole sequence, expanded
    from the latent) and queries a block at a time; a group's gated outputs
    go through its rows of W^O and add up."""
    z = sizes(model, attn)
    s, dim = h.shape
    heads, d_c, d_n, d_r, d_v = (z[k] for k in ("heads", "d_c", "d_n", "d_r",
                                               "d_v"))
    eps = model["norm_eps"]
    rescale = bool(model.get("apply_mla_qkv_lora_rescale", True))
    s_q = (dim / z["q_rank"]) ** 0.5 if rescale else 1.0
    s_kv = (dim / d_c) ** 0.5 if rescale else 1.0
    c_q = s_q * rms_norm(h @ f32(w["wq_a"]), f32(w["q_a_norm"]), eps)
    ckv = h @ f32(w["wkv_a"])
    c = _rounded(s_kv * rms_norm(ckv[:, :d_c], f32(w["kv_a_norm"]), eps),
                 row_dtype)
    k_r = _rounded(rope(ckv[:, d_c:], positions, z["theta"]), row_dtype)
    if attn == FULL:
        visible = selection(model, f32, w, h, c_q, positions, indexer,
                            row_dtype)
    else:
        visible = positions[:, None] >= positions[None, :]
        if windowed:
            visible = jnp.logical_and(
                visible, positions[None, :] > positions[:, None]
                - int(model["sliding_window_size"]))
    group = min(HEAD_GROUP, heads)
    n_groups = heads // group
    # [groups, ..., heads of the group, ...]: one group's weights a scan step
    w_q = f32(w["wq_b"]).reshape(z["q_rank"], n_groups, group, d_n + d_r)
    w_kv = f32(w["wkv_b"]).reshape(d_c, n_groups, group, d_n + d_v)
    w_o = f32(w["wo"]).reshape(n_groups, group, d_v, dim)
    gate = jax.nn.sigmoid(h @ f32(w["w_attn_gate"]))               # [S, H]
    gate = gate.reshape(s, n_groups, group)
    scale = (d_n + d_r) ** -0.5

    def one_group(y, ws):
        wq_g, wkv_g, wo_g, gate_g = ws
        kv = jnp.einsum("sc,chn->shn", c, wkv_g)
        k_n, v = kv[..., :d_n], kv[..., d_n:]

        def one(cq, pos, vis, gt):
            q = jnp.einsum("qr,rhn->qhn", cq, wq_g)
            q_n, q_r = q[..., :d_n], rope(q[..., d_n:], pos, z["theta"])
            score = (jnp.einsum("qhd,shd->hqs", q_n, k_n)
                     + jnp.einsum("qhd,sd->hqs", q_r, k_r)) * scale
            score = jnp.where(vis[None], score, -jnp.inf)
            top = jnp.maximum(jnp.max(score, axis=-1, keepdims=True), -1e30)
            p = jnp.where(vis[None], jnp.exp(score - top), 0.0)
            denom = jnp.sum(p, axis=-1, keepdims=True)
            p = p / jnp.where(denom == 0.0, 1.0, denom)
            o = jnp.einsum("hqs,shd->qhd", p, v) * gt[..., None]
            return jnp.einsum("qhd,hdm->qm", o, wo_g)

        return y + by_blocks(one, QUERY_BLOCK, c_q, positions, visible,
                             gate_g), None

    y, _ = jax.lax.scan(
        one_group, jnp.zeros_like(h),
        (jnp.moveaxis(w_q, 1, 0), jnp.moveaxis(w_kv, 1, 0), w_o,
         jnp.moveaxis(gate, 1, 0)))
    return y, visible


def _groups(leaf, axis, n):
    """A served leaf's arrays cut into ``n`` groups along ``axis`` (-1: the
    columns, -2: the rows), the groups in front; a scale that does not vary
    along the axis is repeated."""
    def cut(a):
        if a.shape[axis] == 1:
            return jnp.broadcast_to(a[None], (n,) + a.shape)
        at = a.ndim + axis
        shape = a.shape[:at] + (n, a.shape[at] // n) + a.shape[at + 1:]
        return jnp.moveaxis(a.reshape(shape), at, 0)

    return jax.tree_util.tree_map(cut, leaf)


def swiglu(f32, w, h):
    """SwiGLU, FFN_GROUP hidden columns and TOKEN_BLOCK tokens at a time."""
    gate, up, down = w["w_gate"], w["w_up"], w["w_down"]
    width = jax.tree_util.tree_leaves(gate)[0].shape[-1]
    n = width // FFN_GROUP if width % FFN_GROUP == 0 else 1

    def one_group(y, ws):
        g, u, d = (f32(x) for x in ws)
        return y + by_blocks(
            lambda x: (jax.nn.silu(x @ g) * (x @ u)) @ d, TOKEN_BLOCK, h), None

    y, _ = jax.lax.scan(
        one_group, jnp.zeros_like(h),
        (_groups(gate, -1, n), _groups(up, -1, n), _groups(down, -2, n)))
    return y


def route(model, f32, w, h, bias=True):
    """(gates [S, k], experts [S, k]); ``bias`` False is a control."""
    k = int(model["moe_top_k"])
    score = jax.nn.sigmoid(h @ f32(w["w_router"]))
    pick = score + f32(w["router_bias"]) if bias else score
    _, top_e = jax.lax.top_k(pick, k)
    top_p = jnp.take_along_axis(score, top_e, axis=-1)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p * float(model.get("routed_scaling_factor", 1.0)), top_e


def moe_feed_forward(model, f32, w, h, bias=True, shared=True):
    """The held experts' share of the routed sum, one expert at a time, plus
    (``shared``) the shared expert."""
    first, count = (int(v) for v in model.get(
        "experts_held", (0, model["router_experts"])))
    top_p, top_e = route(model, f32, w, h, bias)

    def one_expert(out, e):
        weight = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), axis=-1)
        gate, up = f32(w.expert("w_gate_e", e)), f32(w.expert("w_up_e", e))
        down = f32(w.expert("w_down_e", e))
        y = by_blocks(lambda x: (jax.nn.silu(x @ gate) * (x @ up)) @ down,
                      TOKEN_BLOCK, h)
        return out + weight[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(count))
    return out + swiglu(f32, w, h) if shared else out


def block(model, f32, w, x, positions, kind, indexer=True, windowed=True,
          bias=True, row_dtype=None, traced=True):
    """(x after the layer, (the attention's output, its visible mask, the
    feed-forward's output)); the latter None unless ``traced`` (they are a
    launch's largest results)."""
    attn, ffn = kind
    eps = model["norm_eps"]
    y, visible = latent_attention(
        model, f32, w, rms_norm(x, f32(w["attn_norm"]), eps), positions,
        attn, indexer, windowed, row_dtype)
    x = x + y
    h = rms_norm(x, f32(w["ffn_norm"]), eps)
    f = (swiglu(f32, w, h) if ffn == "dense"
         else moe_feed_forward(model, f32, w, h, bias))
    return x + f, ((y, visible, f) if traced else None)


def layer_weights(model, weights):
    """Per layer (leaves, index): a leading layer whole, a layer of the
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


def forward(model, weights, tokens, positions, trace=None,
            precision="highest", **controls):
    """[len(positions), vocab] float32 logits of a full causal pass.
    ``trace`` (a list) receives per layer (attention output, visible mask,
    feed-forward output); ``controls`` (indexer / windowed / bias = False)
    switch a mechanism off, ``row_dtype`` rounds the cached rows;
    ``precision`` "bfloat16" is the reading one step below the configured
    one, which the cell's tolerance has to refuse (by hand)."""
    with jax.default_matmul_precision(precision):
        pos = jnp.arange(tokens.shape[0])
        x = weights.embed(tokens)
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
        return jax.jit(lambda x, head: x @ weights.f32(head))(
            x, weights.lm_head)
