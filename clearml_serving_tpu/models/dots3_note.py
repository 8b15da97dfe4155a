"""A decoder with LATENT attention in two shapes and sigmoid-routed experts
(``model_type: dots3_note``; the language model of dots3-note-prev).

Per layer, from the published keys (pre-norm residual: x + attn(norm x),
x + ffn(norm x); RMSNorm, eps ``norm_eps``):

- *Latent attention* (DeepSeek-V2's MLA), both layer kinds. c^Q = s_q
  RMSNorm(W^DQ h); [q^N_i ; q^R_i] = W^UQ_i c^Q, q^R rotated; [c ; k^R] =
  W^DKV h, c <- s_kv RMSNorm(c), k^R rotated, one for all heads; scores
  (q^N_i . W^UK_i c_s + q^R_i . k^R_s) / sqrt(d_n + d_r) over the visible s;
  o_i = sum_s a_is W^UV_i c_s; y = W^O concat_i(g_i o_i), g_i = sigmoid(w^G_i
  . h) (the headwise gate). s_q = sqrt(dim / q_lora_rank), s_kv = sqrt(dim /
  kv_lora_rank) under ``apply_mla_qkv_lora_rescale``. The served path is the
  ABSORBED form: q~_i = W^UK_i^T q^N_i scores c_s directly and W^UV_i is
  applied after the sum, so the cache holds c_s and k^R_s only
  (ops/latent_attention.py; llm/kv_cache.py, the latent layout).
- *Full layers* (``layer_types[i] == "full_attention"``): visible s = the
  ``index_topk`` keys of the learned selection (DeepSeek-V3.2's indexer):
  q^I_j = W^IQ_j c^Q (the first ``qk_rope_head_dim`` dims rotated), k^I =
  LayerNorm(W^IK h) (same rotation), w = W^IW h, I_ts = J^-1/2 D^-1/2 sum_j
  w_tj ReLU(q^I_tj . k^I_s); the cache holds k^I_s in a plane of its own.
- *Window layers* (``"sliding_attention"``): the ``swa_*`` sizes, visible
  t - ``sliding_window_size`` < s <= t, no indexer.
- *FFN*: the first ``first_k_dense_replace`` layers SwiGLU of width
  ``ffn_dim``; the others route: s = sigmoid(W^R h), chosen = top-k of s + b
  (the bias selects and does not weigh), g = s / sum_chosen s x
  ``routed_scaling_factor``, y = sum over e chosen AND HELD of g_e
  SwiGLU_e(h) + the shared expert. ``experts_held`` = [first, count] names
  the experts this chip holds (expert parallelism: one of the chips that
  share each layer); what the absent experts would add is left out, and the
  partial result goes on (no stand-in for the other chips).

Rotary layout: the two halves of the rotated dims are rotated against each
other (models/llama._apply_rope), not interleaved pairs: a permutation of
the published layout that a checkpoint converter owns.

Served from ``engine.cache=paged`` only: ``forward_ragged`` /
``decode_paged`` with llama's signatures, the pools a pytree of planes
(``paged_layout``). ``scan_layers`` scans the repeating tail of the layer
table and unrolls the layers before it.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import register_model
from .llama import _rms_norm, _rope, moe_dropless, moe_route

FULL, WINDOW = "full_attention", "sliding_attention"
# matmul weights that engine.weight_quant=int8 packs (per output channel)
_QUANT_KEYS = (
    "wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "wi_q", "w_gate", "w_up",
    "w_down", "w_gate_e", "w_up_e", "w_down_e", "lm_head",
)
_DEFAULTS = {
    "dtype": "bfloat16", "norm_eps": 1e-5, "hidden_act": "silu",
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "routed_scaling_factor": 1.0, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "apply_mla_qkv_lora_rescale": True,
    "tie_embeddings": False, "scan_layers": False,
}


def pad128(n: int) -> int:
    return -(-int(n) // 128) * 128


# a layer's routed stacks: what ``held_experts`` reads of its dict
_EXPERT_KEYS = ("w_gate_e", "w_up_e", "w_down_e")


def held_experts(h, top_p, top_e, valid, held, layer, dtype):
    """The routed sum of a chip that HOLDS experts ``held`` = (first, count)
    of a large routed set, for tokens h [T, dim] with the router's choice
    (``models/llama.moe_route``): (y [T, dim], hit [count] int32: the held
    experts a valid token chose, took [T, k]: the choices that stayed
    here). The Mosaic kernel reads and multiplies the hit experts only
    (ops/moe_experts.py), on the chip and at shapes it takes; its twin
    ``moe_dropless`` sends every token through every held expert. ``layer``
    holds the stacks, or under ``"experts"`` (stacks with a leading axis,
    index): a scan's stacked operand, whole, and the repetition."""
    from ..ops import moe_experts as me
    from ..ops.quant import dequantize

    first, n_held = held
    stacks, rep = layer.get("experts") or (layer, None)
    stacks = [stacks[name] for name in _EXPERT_KEYS]
    local = top_e - first
    here = jnp.logical_and(local >= 0, local < n_held)
    took = jnp.logical_and(here, valid[:, None])
    gates, hit = me.expert_gates(top_p, local, took, n_held)
    if me.moe_kernel_unsupported_reason(
            h.shape[0], h.dtype, stacks[0]) is None:
        y = me.moe_experts(h, gates, *me.visit_order(hit), *stacks, layer=rep)
    else:
        y = moe_dropless(
            h, top_p, jnp.where(here, local, n_held),
            *(dequantize(w["_q8"], w["_scale"], dtype)
              if isinstance(w, dict) else w for w in stacks))
    return y, hit, took


def first_expert_stack(layers):
    """The first ``w_gate_e`` leaf of ``params["layers"]`` (a list of layer
    dicts and, under scan_layers, one group of stacked ones), or None."""
    for layer in layers:
        if "w_gate_e" in layer:
            return layer["w_gate_e"]
        for part in layer.values():
            if isinstance(part, dict) and "w_gate_e" in part:
                return part["w_gate_e"]
    return None


def scan_operands(layers, lead: int, x):
    """(what a layer scan slices of the group ``layers[lead]`` = {"p<j>":
    layer leaves stacked over the repetitions}, {"p<j>": the expert stacks,
    whole}). Where a pass over x [T, dim] takes the experts' kernel, the
    stacks stay OUT of the sliced operands: the kernel takes the stacked
    operand and a repetition index, where a slice handed to a custom call
    would be copied out of the stack first. Else the group, and {}."""
    from ..ops.moe_experts import moe_kernel_unsupported_reason

    group, w_gate = layers[lead], first_expert_stack(layers)
    if w_gate is None or moe_kernel_unsupported_reason(
            x.shape[0], x.dtype, w_gate) is not None:
        return group, {}
    whole = {name: {k: layer[k] for k in _EXPERT_KEYS if k in layer}
             for name, layer in group.items()}
    sliced = {name: {k: v for k, v in layer.items() if k not in whole[name]}
              for name, layer in group.items()}
    return sliced, whole


def scanned_layer(group, whole, name: str, rep):
    """Position ``name`` of a scan body's slice, with its expert stacks
    (``scan_operands``) and the repetition ``rep`` where they stayed whole."""
    layer = group[name]
    return dict(layer, experts=(whole[name], rep)) if whole.get(name) \
        else layer


def layer_plan(kinds, scan: bool):
    """(lead, period): the layers before the repeating tail, and the tail's
    period (0 = nothing repeats: every layer is unrolled). The shortest lead
    whose tail is at least two whole repetitions of its shortest period."""
    n = len(kinds)
    if scan:
        for lead in range(n):
            tail = kinds[lead:]
            for p in range(1, len(tail) // 2 + 1):
                if len(tail) % p == 0 and tail == tail[:p] * (len(tail) // p):
                    return lead, p
    return n, 0


@register_model("dots3_note")
def build(config: dict) -> SimpleNamespace:
    cfg = dict(_DEFAULTS)
    cfg.update(config or {})
    vocab, dim = int(cfg["vocab_size"]), int(cfg["dim"])
    n_layers = int(cfg["n_layers"])
    eps = float(cfg["norm_eps"])
    dtype = jnp.dtype(cfg["dtype"])
    if cfg["hidden_act"] != "silu":
        raise ValueError("dots3_note: hidden_act must be 'silu'")
    if cfg["scoring_func"] != "sigmoid":
        raise ValueError("dots3_note: scoring_func must be 'sigmoid'")
    if not cfg["norm_topk_prob"]:
        raise ValueError(
            "dots3_note: norm_topk_prob must be true (the router "
            "renormalises the chosen experts' scores: models/llama.moe_route)"
        )
    if cfg.get("kv_quant"):
        raise ValueError(
            "kv_quant cannot serve the latent page layout: its rows are "
            "bfloat16 by the configuration (a quantised latent or "
            "indexer-key plane is another configuration, ROADMAP.md)"
        )
    if cfg.get("lora_rank"):
        raise ValueError(
            "lora adapters are not served on the latent page layout yet: "
            "the low-rank projections have no adapter rows"
        )
    layer_types = list(cfg["layer_types"])
    if len(layer_types) != n_layers or set(layer_types) - {FULL, WINDOW}:
        raise ValueError(
            "layer_types must name n_layers={} layers, each {!r} or {!r}"
            .format(n_layers, FULL, WINDOW)
        )
    window = int(cfg["sliding_window_size"])
    topk = int(cfg["index_topk"])
    idx_heads, idx_dim = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    rescale = bool(cfg["apply_mla_qkv_lora_rescale"])

    def shape(prefix, heads_key, theta_key):
        g = lambda k: int(cfg[prefix + k])  # noqa: E731
        s = SimpleNamespace(
            heads=int(cfg[heads_key]), q_rank=g("q_lora_rank"),
            d_c=g("kv_lora_rank"), d_n=g("qk_nope_head_dim"),
            d_r=g("qk_rope_head_dim"), d_v=g("v_head_dim"),
            theta=float(cfg[theta_key]),
        )
        s.width = pad128(s.d_c + s.d_r)        # a cached row, whole tiles
        s.scale = (s.d_n + s.d_r) ** -0.5
        s.s_q = (dim / s.q_rank) ** 0.5 if rescale else 1.0
        s.s_kv = (dim / s.d_c) ** 0.5 if rescale else 1.0
        return s

    shapes = {
        FULL: shape("", "n_heads", "rope_theta"),
        WINDOW: shape("swa_", "swa_num_attention_heads", "swa_rope_theta"),
    }
    ffn_dim = int(cfg["ffn_dim"])
    moe_dim = int(cfg["moe_intermediate_size"])
    n_router = int(cfg["router_experts"])
    top_k = int(cfg["moe_top_k"])
    first_held, n_held = (int(v) for v in cfg.get(
        "experts_held", (0, n_router)))
    if not 0 <= first_held < first_held + n_held <= n_router:
        raise ValueError(
            "experts_held [first, count] must lie inside the router's {} "
            "experts".format(n_router)
        )
    shared_dim = moe_dim * int(cfg["n_shared_experts"])
    route_scale = float(cfg["routed_scaling_factor"])
    n_dense = int(cfg["first_k_dense_replace"])
    kinds = [(a, "dense" if i < n_dense else "moe")
             for i, a in enumerate(layer_types)]
    lead, period = layer_plan(kinds, bool(cfg["scan_layers"]))
    n_rep = (n_layers - lead) // period if period else 0
    n_full = sum(1 for a in layer_types if a == FULL)
    n_window = n_layers - n_full

    def plane_of(i):
        """Layer i's index in its kind's planes: static for a leading layer;
        for position j of the period (base, stride), plane = base + r *
        stride in repetition r."""
        return sum(1 for a in layer_types[:i] if a == layer_types[i])

    # -- init ---------------------------------------------------------------

    def _dense(key, shp, fan_in):
        return (jax.random.normal(key, shp, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def _init_layer(key, kind):
        attn, ffn = kind
        s = shapes[attn]
        k = jax.random.split(key, 16)
        out = {
            "attn_norm": jnp.ones((dim,), dtype),
            "ffn_norm": jnp.ones((dim,), dtype),
            "wq_a": _dense(k[0], (dim, s.q_rank), dim),
            "q_a_norm": jnp.ones((s.q_rank,), dtype),
            # the normed latents carry the rescale (mean square s_q ** 2,
            # s_kv ** 2): their projections are drawn for that input, so
            # that queries, keys and values come out at unit variance like
            # every other projection's output (scores of unit scale, as a
            # trained model's are; at fan_in ** -0.5 they are s_q * s_kv = 7
            # times that and the softmax turns every rounding into another
            # key)
            "wq_b": _dense(k[1], (s.q_rank, s.heads * (s.d_n + s.d_r)),
                           s.q_rank * s.s_q ** 2),
            "wkv_a": _dense(k[2], (dim, s.d_c + s.d_r), dim),
            "kv_a_norm": jnp.ones((s.d_c,), dtype),
            "wkv_b": _dense(k[3], (s.d_c, s.heads * (s.d_n + s.d_v)),
                            s.d_c * s.s_kv ** 2),
            "wo": _dense(k[4], (s.heads * s.d_v, dim), s.heads * s.d_v),
            "w_attn_gate": _dense(k[5], (dim, s.heads), dim),
        }
        if attn == FULL:
            out.update({
                "wi_q": _dense(k[6], (s.q_rank, idx_heads * idx_dim),
                               s.q_rank * s.s_q ** 2),
                "wi_k": _dense(k[7], (dim, idx_dim), dim),
                "wi_k_norm": jnp.ones((idx_dim,), dtype),
                "wi_k_bias": jnp.zeros((idx_dim,), dtype),
                "wi_w": _dense(k[8], (dim, idx_heads), dim),
            })
        if ffn == "dense":
            width = ffn_dim
        else:
            width = shared_dim
            out.update({
                "w_router": _dense(k[9], (dim, n_router), dim).astype(
                    jnp.float32),
                # seeded, small and non-zero: the selection bias is a
                # trained quantity, and a zero one would leave the path
                # that adds it untested
                "router_bias": 0.02 * jax.random.normal(
                    k[10], (n_router,), jnp.float32),
                "w_gate_e": _dense(k[11], (n_held, dim, moe_dim), dim),
                "w_up_e": _dense(k[12], (n_held, dim, moe_dim), dim),
                "w_down_e": _dense(k[13], (n_held, moe_dim, dim), moe_dim),
            })
        out.update({
            "w_gate": _dense(k[14], (dim, width), dim),
            "w_up": _dense(k[15], (dim, width), dim),
            "w_down": _dense(jax.random.fold_in(key, 99), (width, dim),
                             width),
        })
        return out

    def _quantize(tree):
        from ..ops.quant import quantize_int8

        out = {}
        for name, leaf in tree.items():
            if name in _QUANT_KEYS:
                q, scale = quantize_int8(leaf, axis=-2)
                out[name] = {"_q8": q, "_scale": scale}
            else:
                out[name] = leaf
        return out

    def init(rng, weight_quant: Optional[str] = None) -> Dict[str, Any]:
        """Random parameters; ``weight_quant`` "int8" packs each matmul
        weight as it is generated, one jitted layer at a time, so the
        full-precision tree never exists. ``params["layers"]`` is a list:
        the leading layers' dicts and, under scan_layers, one group
        {"p0": .., "p<period-1>": ..} whose leaves stack the repetitions."""
        if weight_quant not in (None, "", "int8"):
            raise ValueError(
                "dots3_note serves weight_quant 'int8' or none (got {!r}): "
                "the int4 kernels know llama's projections only"
                .format(weight_quant)
            )
        quant = _quantize if weight_quant else (lambda tree: tree)
        keys = jax.random.split(rng, 3)
        params: Dict[str, Any] = {
            "embed": _dense(keys[0], (vocab, dim), dim),
            "final_norm": jnp.ones((dim,), dtype),
        }
        params.update(jax.jit(
            lambda k: quant({"lm_head": _dense(k, (dim, vocab), dim)})
        )(keys[1]))
        layer_keys = jax.random.split(keys[2], n_layers)
        layers = []
        for i in range(lead):
            layers.append(jax.jit(
                lambda k, kind=kinds[i]: quant(_init_layer(k, kind))
            )(layer_keys[i]))
        if period:
            group = {}
            for j in range(period):
                ks = layer_keys[lead + j::period]
                group["p{}".format(j)] = jax.lax.map(
                    lambda k, kind=kinds[lead + j]: quant(
                        _init_layer(k, kind)), ks)
            layers.append(group)
        params["layers"] = layers
        return params

    # -- layer math ---------------------------------------------------------

    def _w(layer, name):
        w = layer[name]
        if isinstance(w, dict):
            from ..ops.quant import dequantize

            return dequantize(w["_q8"], w["_scale"], dtype)
        return w

    def _mm(layer, name, x):
        return x @ _w(layer, name)

    def _rot(x, cos, sin):
        """x [T, (H,) D] rotated, its two halves against each other; cos /
        sin [T, D/2]."""
        if x.ndim == 3:
            cos, sin = cos[:, None], sin[:, None]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)

    def _layer_norm(x, weight, bias):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
        out = (x32 - mean) * jax.lax.rsqrt(var + eps)
        return (out * weight.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(x.dtype)

    def _use_kernel(pools):
        from ..ops.paged_attention import paged_kernel_unsupported_reason

        some = pools[0][FULL if n_full else WINDOW]
        return paged_kernel_unsupported_reason(
            layout.row_widths, some.shape[3], some.dtype
        ) is None

    def _attention(layer, attn, h, pos, plane, pools, ctx):
        """One latent attention layer over T tokens (h [T, dim], positions
        ``pos`` [T]): rows written, keys chosen, the absorbed attention, the
        gate and the output projection. Returns (y [T, dim], pools)."""
        from ..ops import latent_attention as la

        s = shapes[attn]
        t = h.shape[0]
        k_pools, v_pools = pools
        kernel = ctx["kernel"]
        with jax.named_scope("latent_qkv"):
            cos, sin = _rope(pos, s.d_r, s.theta)
            c_q = _rms_norm(_mm(layer, "wq_a", h), layer["q_a_norm"], eps)
            c_q = c_q * jnp.asarray(s.s_q, c_q.dtype)
            q = _mm(layer, "wq_b", c_q).reshape(t, s.heads, s.d_n + s.d_r)
            q_n, q_r = q[..., :s.d_n], _rot(q[..., s.d_n:], cos, sin)
            ckv = _mm(layer, "wkv_a", h)
            c = _rms_norm(ckv[:, :s.d_c], layer["kv_a_norm"], eps)
            c = c * jnp.asarray(s.s_kv, c.dtype)
            k_r = _rot(ckv[:, s.d_c:], cos, sin)
            pad = s.width - s.d_c - s.d_r
            row = jnp.concatenate(
                [c, k_r, jnp.zeros((t, pad), c.dtype)], axis=-1)
            w_kv = _w(layer, "wkv_b").reshape(s.d_c, s.heads, s.d_n + s.d_v)
            q_abs = jnp.einsum("thn,chn->thc", q_n, w_kv[..., :s.d_n])
            q_lat = jnp.concatenate(
                [q_abs, q_r, jnp.zeros((t, s.heads, pad), q_abs.dtype)],
                axis=-1,
            ) * jnp.asarray(s.scale, q_abs.dtype)
            if attn == FULL:
                q_i = _mm(layer, "wi_q", c_q).reshape(t, idx_heads, idx_dim)
                k_i = _layer_norm(h @ layer["wi_k"], layer["wi_k_norm"],
                                  layer["wi_k_bias"])
                q_i = jnp.concatenate(
                    [_rot(q_i[..., :s.d_r], cos, sin), q_i[..., s.d_r:]], -1)
                k_i = jnp.concatenate(
                    [_rot(k_i[:, :s.d_r], cos, sin), k_i[:, s.d_r:]], -1)
                w_i = h @ layer["wi_w"]
        write = la.latent_kv_write if kernel else la.latent_kv_write_xla
        with jax.named_scope("kv_write"):
            k_pools = dict(k_pools)
            k_pools[attn] = write(
                k_pools[attn], row, ctx["write_page"], ctx["write_offset"],
                layer=plane,
            )
            if attn == FULL:
                v_pools = dict(v_pools)
                k_row = jnp.pad(
                    k_i, ((0, 0), (0, v_pools["index"].shape[-1] - idx_dim)))
                v_pools["index"] = write(
                    v_pools["index"], k_row, ctx["write_page"],
                    ctx["write_offset"], layer=plane,
                )
        selected = None
        if attn == FULL:
            sel = la.index_select(
                q_i, w_i, v_pools["index"], ctx["page_table"],
                ctx["tok_row"], pos, ctx["tok_valid"], layer=plane,
                topk=topk,
            )
            selected = sel[:3]
        with jax.named_scope("attn"):
            common = dict(layer=plane, v_width=s.d_c)
            if not kernel:
                o_lat = la.latent_attention_xla(
                    q_lat, k_pools[attn], ctx["page_table"], ctx["tok_row"],
                    pos, ctx["tok_valid"], selected=selected,
                    window=0 if attn == FULL else window, **common,
                )
            elif ctx["mode"] == "decode":
                o_lat = la.latent_attention_decode(
                    q_lat, k_pools[attn], ctx["page_table"],
                    ctx["attend_lens"], selected=selected,
                    window=0 if attn == FULL else window, **common,
                )
            elif attn == FULL:
                o_lat = la.latent_ragged_attention(
                    q_lat, k_pools[attn], selected=selected,
                    tok_valid=ctx["tok_valid"], **common,
                )
            else:
                o_lat = ctx["back"](la.latent_ragged_attention(
                    ctx["place"](q_lat), k_pools[attn], ctx["page_table"],
                    ctx["kv_lens"], ctx["row_starts"], ctx["row_lens"],
                    ctx["item_rows"], ctx["item_q0"], window=window,
                    tile=ctx["tile"], **common,
                ))
            o = jnp.einsum("thc,chv->thv", o_lat.astype(h.dtype),
                           w_kv[..., s.d_n:])
        with jax.named_scope("oproj"):
            gate = jax.nn.sigmoid(
                (h @ layer["w_attn_gate"]).astype(jnp.float32))
            o = (o * gate[..., None].astype(o.dtype)).reshape(t, -1)
            y = _mm(layer, "wo", o)
        if ctx.get("probe") is not None:
            # by hand and in tests: what this layer's attention gave, and on
            # a full layer which positions it chose (best first) and how many
            ctx["probe"].append(
                (y, sel[3], sel[2]) if attn == FULL else (y, None, None))
        return y.astype(h.dtype), (k_pools, v_pools)

    def _swiglu(layer, h):
        return _mm(
            layer, "w_down",
            jax.nn.silu(_mm(layer, "w_gate", h)) * _mm(layer, "w_up", h),
        )

    def _ffn(layer, ffn, h, valid, counters):
        if ffn == "dense":
            with jax.named_scope("ffn"):
                return _swiglu(layer, h), counters
        with jax.named_scope("moe"):
            logits = h.astype(jnp.float32) @ layer["w_router"]
            top_p, top_e = moe_route(
                logits, top_k, scoring="sigmoid", bias=layer["router_bias"],
                scale=route_scale,
            )
            y, hit, took = held_experts(
                h, top_p, top_e, valid, (first_held, n_held), layer, dtype)
            with jax.named_scope("moe_shared"):
                y = y + _swiglu(layer, h)
            counters = counters + jnp.stack([
                jnp.sum(hit), jnp.sum(took.astype(jnp.int32)), jnp.int32(1),
            ] + [jnp.int32(0)] * (counters.shape[0] - 3))
        return y.astype(h.dtype), counters

    def _layer(x, layer, kind, plane, pools, ctx):
        attn, ffn = kind
        k_pools, v_pools = pools
        counters = v_pools["counters"]
        h = _rms_norm(x, layer["attn_norm"], eps)
        y, (k_pools, v_pools) = _attention(
            layer, attn, h, ctx["pos"], plane, (k_pools, v_pools), ctx)
        x = x + y
        h = _rms_norm(x, layer["ffn_norm"], eps)
        y, counters = _ffn(layer, ffn, h, ctx["tok_valid"], counters)
        if ctx.get("probe") is not None:
            ctx["probe"][-1] += (y,)
        v_pools = dict(v_pools)
        v_pools["counters"] = counters
        return x + y, (k_pools, v_pools)

    def _layers(params, x, pools, ctx):
        layers = params["layers"]
        for i in range(lead):
            x, pools = _layer(x, layers[i], kinds[i], plane_of(i), pools, ctx)
        if period:
            base = [plane_of(lead + j) for j in range(period)]
            stride = [
                sum(1 for a in layer_types[lead:lead + period]
                    if a == layer_types[lead + j])
                for j in range(period)
            ]

            group, whole = scan_operands(layers, lead, x)

            def body(carry, xs):
                x, pools = carry
                group, r = xs
                for j in range(period):
                    x, pools = _layer(
                        x, scanned_layer(group, whole, "p{}".format(j), r),
                        kinds[lead + j], base[j] + r * stride[j], pools, ctx,
                    )
                return (x, pools), None

            (x, pools), _ = jax.lax.scan(
                body, (x, pools),
                (group, jnp.arange(n_rep, dtype=jnp.int32)),
            )
        return x, pools

    @jax.named_scope("logits")
    def _logits(params, x):
        x = _rms_norm(x, params["final_norm"], eps)
        return _mm(params, "lm_head", x).astype(jnp.float32)

    def _ragged_tile():
        from ..ops.paged_attention import ragged_query_tile

        return ragged_query_tile(1, layout.n_heads, layout.head_dim, dtype)

    def forward_ragged(
        params, tokens, tok_pos, tok_row, tok_valid, tok_slot, row_last,
        k_pools, v_pools, page_table, kv_lens, row_starts, row_lens,
        write_page, write_offset, item_rows=None, item_q0=None,
        lora_idx=None, *, probe=False, **unsupported,
    ):
        """models/llama.forward_ragged over the latent planes: the same
        operands (the planner's, ops/paged_attention.py) and the same
        results, (row logits [R, vocab], k_pools, v_pools). ``probe``
        (unrolled layers only) adds a fourth: per layer (attention output
        [C, dim], chosen positions [C, K] and their count [C] on a full
        layer, feed-forward output [C, dim]) — the layer-level comparison
        against the reference."""
        if probe and period:
            raise ValueError("probe needs unrolled layers: scan_layers off")
        if lora_idx is not None or any(
            v is not None for v in unsupported.values()
        ):
            raise ValueError(
                "the latent page layout serves plain ragged rows only: no "
                "lora rows, no scale pools, no verify rows ({})".format(
                    sorted(k for k, v in unsupported.items() if v is not None)
                    or "lora_idx")
            )
        from ..ops.paged_attention import _RAGGED_QB, ragged_view_tokens

        kernel = _use_kernel((k_pools, v_pools))
        # the two token axes of a ragged pass (models/llama._ragged_axes):
        # everything per token runs on the compact axis, the window kernel
        # reads a row's queries in the aligned view
        c = tokens.shape[0]
        view = ragged_view_tokens(
            c, row_starts.shape[0], _RAGGED_QB if kernel else 1)
        slot_tok = jnp.full((view,), c, jnp.int32).at[tok_slot].set(
            jnp.arange(c, dtype=jnp.int32), mode="drop")

        def place(a):
            return a.at[slot_tok].get(mode="fill", fill_value=0)

        def back(a):
            return a.at[tok_slot].get(mode="fill", fill_value=0)

        ctx = dict(
            mode="ragged", kernel=kernel, pos=tok_pos, tok_row=tok_row,
            tok_valid=tok_valid, page_table=page_table, kv_lens=kv_lens,
            row_starts=row_starts, row_lens=row_lens, write_page=write_page,
            write_offset=write_offset, item_rows=item_rows, item_q0=item_q0,
            place=place, back=back, tile=_ragged_tile(),
            probe=[] if probe else None,
        )
        x = params["embed"][tokens]
        x, pools = _layers(params, x, (k_pools, v_pools), ctx)
        out = (_logits(params, x[row_last]),) + pools
        return out + (ctx["probe"],) if probe else out

    def decode_paged(
        params, tokens, k_pools, v_pools, page_table, lengths, write_page,
        write_offset, lora_idx=None, *, active=None, **unsupported,
    ):
        """models/llama.decode_paged over the latent planes: one token a
        row at position ``lengths[b]``; a row ``active`` masks out attends
        nothing."""
        if lora_idx is not None or any(
            v is not None for v in unsupported.values()
        ):
            raise ValueError(
                "the latent page layout has no lora rows and no scale pools")
        b = tokens.shape[0]
        live = jnp.ones((b,), bool) if active is None else active
        ctx = dict(
            mode="decode", kernel=_use_kernel((k_pools, v_pools)),
            pos=lengths, tok_row=jnp.arange(b, dtype=jnp.int32),
            tok_valid=live, page_table=page_table,
            attend_lens=jnp.where(live, lengths + 1, 0),
            write_page=write_page, write_offset=write_offset,
        )
        x = params["embed"][tokens]
        x, pools = _layers(params, x, (k_pools, v_pools), ctx)
        return (_logits(params, x),) + pools

    # -- the page layout (llm/kv_cache.PagedKVCache) --------------------------

    def init_pools(num_pages: int, page_size: int):
        """The planes of the pool, every one [layers of the kind, 1, pages,
        page, row width] and all under ONE page id: ``k`` the latent rows
        ([c ; k^R ; 0]) by layer kind, ``v`` the indexer's keys of the full
        layers, and the experts' counters (hit, local assignments, layers)
        that ride the launch's carry beside them."""
        def plane(n, width):
            return jnp.zeros((max(n, 1), 1, num_pages, page_size, width),
                             dtype)

        k = {FULL: plane(n_full, shapes[FULL].width),
             WINDOW: plane(n_window, shapes[WINDOW].width)}
        v = {"index": plane(n_full, pad128(idx_dim)),
             "counters": jnp.zeros((8,), jnp.int32)}
        return k, v

    layout = SimpleNamespace(
        kind="latent",
        init_pools=init_pools,
        row_widths=(shapes[FULL].width, shapes[WINDOW].width,
                    pad128(idx_dim)),
        # what the ragged planner sizes its query tile from
        # (ops.paged_attention.ragged_query_tile): one shared "kv head"
        n_heads=max(shapes[FULL].heads, shapes[WINDOW].heads),
        head_dim=max(shapes[FULL].width, shapes[WINDOW].width),
        n_full=n_full, n_window=n_window, window=window, index_topk=topk,
        experts_held=n_held, moe_layers=n_layers - n_dense,
    )

    def _paged_only(name):
        def refuse(*_a, **_k):
            raise ValueError(
                "{}: the latent attention of this model is served from "
                "engine.cache=paged only (it keeps no per-head K/V for a "
                "dense cache)".format(name)
            )

        return refuse

    return SimpleNamespace(
        init=init,
        forward_ragged=forward_ragged,
        decode_paged=decode_paged,
        verify_paged=None,
        ffn=_ffn,
        expert_stack=lambda params: first_expert_stack(params["layers"]),
        paged_layout=layout,
        attention="latent",
        prepare_params=lambda params: params,
        config=cfg,
        head_dim=layout.head_dim,
        n_kv_heads=1,
        n_heads=layout.n_heads,
        n_layers=n_layers,
        lora_rank=0,
        max_loras=0,
        paged_unsupported_reason=None,
        layer_plan=(lead, period),
        **{name: _paged_only(name) for name in (
            "apply", "init_cache", "prefill", "prefill_chunk", "decode",
            "verify")},
        prefill_ring=None,
        prefill_pipeline=None,
    )
