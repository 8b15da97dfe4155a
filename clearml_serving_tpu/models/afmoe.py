"""A decoder whose layers mix a sliding WINDOW and full attention over
per-head K/V, with a gated attention output, sandwich norms and
sigmoid-routed experts (``model_type: afmoe``; Arcee Trinity).

Per layer, from the published keys (T tokens, RMSNorm with eps ``norm_eps``):

    h_0 = E[tok] * embed_scale                      (mup_enabled: sqrt(dim))
    a = RMSNorm_in(h)
    q = W^Q a [T, H, D]   k = W^K a [T, Hkv, D]   v = W^V a   g = W^G a [T, HD]
    q = RMSNorm_q(q), k = RMSNorm_k(k)              over a head's D dims
    a ``sliding_attention`` layer rotates q and k (``rope_theta``, all D
      dims) and sees t - sliding_window < s <= t;
    a ``full_attention`` layer does NOT rotate (NoPE) and sees s <= t
    o = softmax_s(q . k / sqrt(D)) v                float32, H / Hkv a kv head
    y = W^O (o * sigmoid(g))                        elementwise gate
    h = h + RMSNorm_post_attn(y)
    m = RMSNorm_pre_mlp(h)
    layer < num_dense_layers: z = SwiGLU(m), width ``ffn_dim``
    else: s = sigmoid(W^R m) (float32); chosen = top-k of s + b (the bias
      selects and does not weigh); w = s / sum_chosen s * route_scale;
      z = sum over e chosen AND HELD of w_e SwiGLU_e(m) + SwiGLU_shared(m)
    h = h + RMSNorm_post_mlp(z)
    logits = W^head RMSNorm_f(h)

``experts_held`` = [first, count] names the routed experts this chip holds
(models/dots3_note.py: route over all, compute the held part, no stand-in
for the other chips). Rotary layout: the two halves of a head against each
other (models/llama._apply_rope), a permutation of the published columns.

Served from ``engine.cache=paged`` only, over the STANDARD pools
``[L, Hkv, N, P, D]``: ``forward_ragged`` / ``decode_paged`` with llama's
signatures. The window is a static argument of the two paged kernels by
layer kind (ops/paged_attention.py, docs/window_attention.md); every layer
keeps every page. ``v_pools`` may arrive as ``(pool, counters)``: the
experts' counters (hit, assignments that stayed here, expert layers run)
then ride the launch's carry beside the pool and come back the same way.
``scan_layers`` scans the repeating tail of the layer table where one
repeats at least twice (models/dots3_note.layer_plan) and unrolls the rest.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import register_model
from .dots3_note import (
    FULL, WINDOW, first_expert_stack, held_experts, layer_plan,
    scan_operands, scanned_layer,
)
from .llama import _rms_norm, _rope, moe_route

# matmul weights that engine.weight_quant=int8 packs (per output channel)
_QUANT_KEYS = (
    "wq", "wk", "wv", "w_attn_gate", "wo", "w_gate", "w_up", "w_down",
    "w_gate_e", "w_up_e", "w_down_e", "lm_head",
)
_DEFAULTS = {
    "dtype": "bfloat16", "norm_eps": 1e-5, "hidden_act": "silu",
    "scoring_func": "sigmoid", "route_norm": True, "route_scale": 1.0,
    "n_shared_experts": 1, "num_dense_layers": 2, "embed_scale": True,
    "tie_embeddings": False, "scan_layers": False, "rope_theta": 10000.0,
}
N_COUNTERS = 8   # hit, local assignments, expert layers run; the rest spare


@register_model("afmoe")
def build(config: dict) -> SimpleNamespace:
    cfg = dict(_DEFAULTS)
    cfg.update(config or {})
    vocab, dim = int(cfg["vocab_size"]), int(cfg["dim"])
    n_layers = int(cfg["n_layers"])
    n_heads, n_kv = int(cfg["n_heads"]), int(cfg["n_kv_heads"])
    head_dim = int(cfg.get("head_dim") or dim // n_heads)
    group = n_heads // n_kv
    eps = float(cfg["norm_eps"])
    theta = float(cfg["rope_theta"])
    dtype = jnp.dtype(cfg["dtype"])
    if cfg["hidden_act"] != "silu":
        raise ValueError("afmoe: hidden_act must be 'silu'")
    if cfg["scoring_func"] != "sigmoid":
        raise ValueError("afmoe: scoring_func must be 'sigmoid'")
    if not cfg["route_norm"]:
        raise ValueError(
            "afmoe: route_norm must be true (the router renormalises the "
            "chosen experts' scores: models/llama.moe_route)"
        )
    if cfg["tie_embeddings"]:
        raise ValueError("afmoe: the head is untied (tie_embeddings false)")
    if cfg.get("kv_quant"):
        raise ValueError(
            "kv_quant cannot serve a windowed model yet: the window bound of "
            "the paged kernels is not implemented for int8 pools "
            "(ops.paged_attention.window_kernel_unsupported_reason)"
        )
    if cfg.get("lora_rank"):
        raise ValueError(
            "lora adapters are not served by arch afmoe yet: its "
            "projections have no adapter rows"
        )
    layer_types = list(cfg["layer_types"])
    if len(layer_types) != n_layers or set(layer_types) - {FULL, WINDOW}:
        raise ValueError(
            "layer_types must name n_layers={} layers, each {!r} or {!r}"
            .format(n_layers, FULL, WINDOW)
        )
    window = int(cfg.get("sliding_window") or 0)
    if WINDOW in layer_types and window <= 0:
        raise ValueError(
            "afmoe: 'sliding_attention' layers need a positive sliding_window")
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
    route_scale = float(cfg["route_scale"])
    n_dense = int(cfg["num_dense_layers"])
    embed_scale = cfg["embed_scale"]
    embed_scale = (dim ** 0.5 if embed_scale is True
                   else float(embed_scale or 1.0))
    kinds = [(a, "dense" if i < n_dense else "moe")
             for i, a in enumerate(layer_types)]
    lead, period = layer_plan(kinds, bool(cfg["scan_layers"]))
    n_rep = (n_layers - lead) // period if period else 0
    n_full = layer_types.count(FULL)

    # -- init ---------------------------------------------------------------

    def _dense(key, shp, fan_in):
        return (jax.random.normal(key, shp, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def _init_layer(key, kind):
        # every projection at fan_in ** -0.5: its output has unit variance
        # for a unit-variance input, and RMSNorm_q / RMSNorm_k put the
        # scores at unit scale whatever the projections give
        _attn, ffn = kind
        k = jax.random.split(key, 13)
        ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
        out = {
            "attn_norm": ones(dim), "post_attn_norm": ones(dim),
            "ffn_norm": ones(dim), "post_ffn_norm": ones(dim),
            "q_norm": ones(head_dim), "k_norm": ones(head_dim),
            "wq": _dense(k[0], (dim, n_heads * head_dim), dim),
            "wk": _dense(k[1], (dim, n_kv * head_dim), dim),
            "wv": _dense(k[2], (dim, n_kv * head_dim), dim),
            "w_attn_gate": _dense(k[3], (dim, n_heads * head_dim), dim),
            "wo": _dense(k[4], (n_heads * head_dim, dim),
                         n_heads * head_dim),
        }
        if ffn == "dense":
            width = ffn_dim
        else:
            width = shared_dim
            out.update({
                "w_router": _dense(k[5], (dim, n_router), dim).astype(
                    jnp.float32),
                # seeded, small and non-zero: the selection bias is a
                # trained quantity, and a zero one would leave the path
                # that adds it untested
                "router_bias": 0.02 * jax.random.normal(
                    k[6], (n_router,), jnp.float32),
                "w_gate_e": _dense(k[7], (n_held, dim, moe_dim), dim),
                "w_up_e": _dense(k[8], (n_held, dim, moe_dim), dim),
                "w_down_e": _dense(k[9], (n_held, moe_dim, dim), moe_dim),
            })
        out.update({
            "w_gate": _dense(k[10], (dim, width), dim),
            "w_up": _dense(k[11], (dim, width), dim),
            "w_down": _dense(k[12], (width, dim), width),
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
        full-precision tree never exists (norms, the router and its bias
        stay as drawn). ``params["layers"]`` is a list: the unrolled layers'
        dicts and, where the tail repeats, one group {"p0": .., "p<period -
        1>": ..} whose leaves stack the repetitions."""
        if weight_quant not in (None, "", "int8"):
            raise ValueError(
                "afmoe serves weight_quant 'int8' or none (got {!r}): the "
                "int4 kernels know llama's projections only"
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
            group_ = {}
            for j in range(period):
                ks = layer_keys[lead + j::period]
                group_["p{}".format(j)] = jax.lax.map(
                    lambda k, kind=kinds[lead + j]: quant(
                        _init_layer(k, kind)), ks)
            layers.append(group_)
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
        """x [T, H, D] rotated, its two halves against each other; cos /
        sin [T, D/2]."""
        cos, sin = cos[:, None], sin[:, None]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)

    def _use_kernel(k_pools):
        from ..ops.paged_attention import paged_kernel_unsupported_reason

        return paged_kernel_unsupported_reason(
            head_dim, k_pools.shape[3], k_pools.dtype) is None

    def _attention(layer, attn, h, li, pools, ctx):
        """One attention layer over T tokens (h [T, dim], normed): K/V
        written into layer ``li`` of the stacked pools, the paged attention
        under the layer kind's bound, the gate and the output projection.
        Returns (y [T, dim], pools)."""
        from ..ops import paged_attention as pa

        t = h.shape[0]
        kernel = ctx["kernel"]
        with jax.named_scope("qkv"):
            q = _mm(layer, "wq", h).reshape(t, n_heads, head_dim)
            k = _mm(layer, "wk", h).reshape(t, n_kv, head_dim)
            v = _mm(layer, "wv", h).reshape(t, n_kv, head_dim)
            gate = _mm(layer, "w_attn_gate", h)
            q = _rms_norm(q, layer["q_norm"], eps)
            k = _rms_norm(k, layer["k_norm"], eps)
            # a full layer rotates nothing (NoPE); ``rope_full`` is a
            # control of the layer check, never on the served path
            if attn == WINDOW or ctx.get("rope_full"):
                q, k = _rot(q, *ctx["cos_sin"]), _rot(k, *ctx["cos_sin"])
        write = pa.paged_kv_write if kernel else pa.paged_kv_write_xla
        with jax.named_scope("kv_write"):
            pools = write(pools[0], pools[1], k, v, ctx["write_page"],
                          ctx["write_offset"], layer=li)
        bound = window if (
            attn == WINDOW and ctx.get("windowed", True)) else 0
        with jax.named_scope("attn"):
            if ctx["mode"] == "decode":
                attend = pa.paged_attention if kernel \
                    else pa.paged_attention_xla
                o = attend(
                    q.reshape(t, n_kv, group, head_dim), pools[0], pools[1],
                    ctx["page_table"], ctx["attend_lens"], layer=li,
                    window=bound,
                ).reshape(t, n_heads * head_dim)
            else:
                q_view = ctx["place"](q.reshape(t, n_heads * head_dim))
                q_view = q_view.reshape(-1, n_kv, group, head_dim)
                args = (q_view, pools[0], pools[1], ctx["page_table"],
                        ctx["kv_lens"], ctx["row_starts"], ctx["row_lens"])
                if kernel:
                    o = pa.ragged_paged_attention(
                        *args, item_rows=ctx["item_rows"],
                        item_q0=ctx["item_q0"], layer=li, window=bound)
                else:
                    o = pa.ragged_paged_attention_xla(
                        *args, layer=li, window=bound)
                o = ctx["back"](o.reshape(-1, n_heads * head_dim))
        with jax.named_scope("oproj"):
            if ctx.get("gated", True):
                o = o * jax.nn.sigmoid(
                    gate.astype(jnp.float32)).astype(o.dtype)
            y = _mm(layer, "wo", o.astype(h.dtype))
        return y.astype(h.dtype), pools

    def _swiglu(layer, h):
        return _mm(
            layer, "w_down",
            jax.nn.silu(_mm(layer, "w_gate", h)) * _mm(layer, "w_up", h),
        )

    def _ffn(layer, ffn, h, valid, counters, bias=True):
        """(z [T, dim], counters). ``counters`` None counts nothing."""
        if ffn == "dense":
            with jax.named_scope("ffn"):
                return _swiglu(layer, h), counters
        with jax.named_scope("moe"):
            logits = h.astype(jnp.float32) @ layer["w_router"]
            top_p, top_e = moe_route(
                logits, top_k, scoring="sigmoid",
                bias=layer["router_bias"] if bias else 0.0, scale=route_scale,
            )
            y, hit, took = held_experts(
                h, top_p, top_e, valid, (first_held, n_held), layer, dtype)
            with jax.named_scope("moe_shared"):
                y = y + _swiglu(layer, h)
            if counters is not None:
                counters = counters + jnp.stack([
                    jnp.sum(hit), jnp.sum(took.astype(jnp.int32)),
                    jnp.int32(1),
                ] + [jnp.int32(0)] * (counters.shape[0] - 3))
        return y.astype(h.dtype), counters

    def _layer(x, layer, kind, li, carry, ctx):
        attn, ffn = kind
        pools, counters = carry
        h = _rms_norm(x, layer["attn_norm"], eps)
        y, pools = _attention(layer, attn, h, li, pools, ctx)
        if ctx.get("probe") is not None:
            ctx["probe"].append((y,))
        if ctx.get("post_norms", True):
            y = _rms_norm(y, layer["post_attn_norm"], eps)
        x = x + y
        h = _rms_norm(x, layer["ffn_norm"], eps)
        z, counters = _ffn(layer, ffn, h, ctx["tok_valid"], counters,
                           ctx.get("bias", True))
        if ctx.get("probe") is not None:
            ctx["probe"][-1] += (z,)
        if ctx.get("post_norms", True):
            z = _rms_norm(z, layer["post_ffn_norm"], eps)
        return x + z, (pools, counters)

    def _layers(params, x, carry, ctx):
        layers = params["layers"]
        for i in range(lead):
            x, carry = _layer(x, layers[i], kinds[i], i, carry, ctx)
        if period:
            group, whole = scan_operands(layers, lead, x)

            def body(state, xs):
                x, carry = state
                group_, r = xs
                for j in range(period):
                    x, carry = _layer(
                        x, scanned_layer(group_, whole, "p{}".format(j), r),
                        kinds[lead + j], lead + r * period + j, carry, ctx,
                    )
                return (x, carry), None

            (x, carry), _ = jax.lax.scan(
                body, (x, carry),
                (group, jnp.arange(n_rep, dtype=jnp.int32)),
            )
        return x, carry

    def _embed(params, tokens):
        x = params["embed"][tokens]
        return x * jnp.asarray(embed_scale, x.dtype)

    @jax.named_scope("logits")
    def _logits(params, x):
        x = _rms_norm(x, params["final_norm"], eps)
        return _mm(params, "lm_head", x).astype(jnp.float32)

    def _split(v_pools):
        """(V pool, counters or None) of what the engine handed over."""
        return v_pools if isinstance(v_pools, tuple) else (v_pools, None)

    def _join(logits, pools, counters):
        v = pools[1] if counters is None else (pools[1], counters)
        return logits, pools[0], v

    def _refuse_rows(where, lora_idx, unsupported):
        given = sorted(k for k, v in unsupported.items() if v is not None)
        if lora_idx is not None:
            given.append("lora_idx")
        if given:
            raise ValueError(
                "arch afmoe {} serves plain rows only: no lora rows, no "
                "scale pools (kv_quant), no verify rows and no draft trees "
                "({})".format(where, ", ".join(given))
            )

    def forward_ragged(
        params, tokens, tok_pos, tok_row, tok_valid, tok_slot, row_last,
        k_pools, v_pools, page_table, kv_lens, row_starts, row_lens,
        write_page, write_offset, item_rows=None, item_q0=None,
        lora_idx=None, *, probe=False, controls=None, **unsupported,
    ):
        """models/llama.forward_ragged for this architecture: the same
        operands (the planner's, ops/paged_attention.py) and the same
        results, (row logits [R, vocab], k_pools, v_pools). ``probe``
        (unrolled layers only) adds a fourth: per layer (attention output
        after W^O, feed-forward output), both before their post norms;
        ``controls`` (by hand and in tests only: ``windowed`` / ``gated`` /
        ``bias`` / ``post_norms`` False, ``rope_full`` True) switch a
        mechanism."""
        if probe and period:
            raise ValueError("probe needs unrolled layers: scan_layers off")
        _refuse_rows("forward_ragged", lora_idx, unsupported)
        from ..ops.paged_attention import _RAGGED_QB, ragged_view_tokens

        kernel = _use_kernel(k_pools)
        # the two token axes of a ragged pass (models/llama._ragged_axes):
        # everything per token runs on the compact axis, the kernel reads a
        # row's queries in the aligned view
        c = tokens.shape[0]
        view = ragged_view_tokens(
            c, row_starts.shape[0], _RAGGED_QB if kernel else 1)
        slot_tok = jnp.full((view,), c, jnp.int32).at[tok_slot].set(
            jnp.arange(c, dtype=jnp.int32), mode="drop")
        v_pool, counters = _split(v_pools)
        ctx = dict(
            controls or {}, mode="ragged", kernel=kernel,
            cos_sin=_rope(tok_pos, head_dim, theta), tok_valid=tok_valid,
            page_table=page_table, kv_lens=kv_lens, row_starts=row_starts,
            row_lens=row_lens, write_page=write_page,
            write_offset=write_offset, item_rows=item_rows, item_q0=item_q0,
            place=lambda a: a.at[slot_tok].get(mode="fill", fill_value=0),
            back=lambda a: a.at[tok_slot].get(mode="fill", fill_value=0),
            probe=[] if probe else None,
        )
        x, (pools, counters) = _layers(
            params, _embed(params, tokens), ((k_pools, v_pool), counters),
            ctx)
        out = _join(_logits(params, x[row_last]), pools, counters)
        return out + (ctx["probe"],) if probe else out

    def decode_paged(
        params, tokens, k_pools, v_pools, page_table, lengths, write_page,
        write_offset, lora_idx=None, *, active=None, **unsupported,
    ):
        """models/llama.decode_paged for this architecture: one token a row
        at position ``lengths[b]``; a row ``active`` masks out attends
        nothing and counts nothing."""
        _refuse_rows("decode_paged", lora_idx, unsupported)
        b = tokens.shape[0]
        live = jnp.ones((b,), bool) if active is None else active
        v_pool, counters = _split(v_pools)
        ctx = dict(
            mode="decode", kernel=_use_kernel(k_pools),
            cos_sin=_rope(lengths, head_dim, theta), tok_valid=live,
            page_table=page_table,
            attend_lens=jnp.where(live, lengths + 1, 0),
            write_page=write_page, write_offset=write_offset,
        )
        x, (pools, counters) = _layers(
            params, _embed(params, tokens), ((k_pools, v_pool), counters),
            ctx)
        return _join(_logits(params, x), pools, counters)

    def _paged_only(name):
        def refuse(*_a, **_k):
            raise ValueError(
                "{}: arch afmoe is served from engine.cache=paged only (its "
                "window lives in the paged kernels; it has no dense-cache "
                "path)".format(name)
            )

        return refuse

    return SimpleNamespace(
        init=init,
        forward_ragged=forward_ragged,
        decode_paged=decode_paged,
        verify_paged=None,
        ffn=_ffn,
        expert_stack=lambda params: first_expert_stack(params["layers"]),
        # what the engine reads to lift its sliding_window refusal and to
        # count a launch's keys by layer kind (llm/engine.py)
        paged_window=SimpleNamespace(
            window=window, n_full=n_full, n_window=n_layers - n_full,
            experts_held=n_held, counters=N_COUNTERS,
        ),
        # weight_quant rides init (above): nothing is left to prepare
        prepare_params=lambda params: params,
        config=cfg,
        head_dim=head_dim,
        n_kv_heads=n_kv,
        n_heads=n_heads,
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
