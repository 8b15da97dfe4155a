"""A decoder whose every block runs a Mamba-2 mixer and grouped-query
attention IN PARALLEL on the same normed input (``model_type: falcon_h1``;
TII Falcon-H1), with muP multipliers on every branch.

One block, T tokens (RMSNorm with eps ``norm_eps``; ``*_multiplier`` keys as
published):

    x0 = E[tok] * embedding_multiplier
    u  = RMSNorm_in(x)
    -- mixer (Mamba-2 / SSD; H heads of P channels, G groups, state N) --
    p  = ((u * ssm_in_multiplier) W_in) * mup      [z d_ssm | x d_ssm | B G N | C G N | dt H]
         mup = ssm_multipliers[0..4] over the z, x, B, C, dt slices
    c_t = conv_b + sum_{k=0..3} conv_w[k] * xBC_{t-3+k}   depthwise over [x|B|C], causal
    x, B, C = silu(c)
    dt = softplus(dt + dt_bias)    a = exp(dt * A),  A = -exp(A_log)  per head
    h_t = a_t h_{t-1} + B_t (dt_t x_t)^T    y_t = C_t^T h_t + D x_t   (ops/mamba2.py)
    y  = GroupRMSNorm(y * silu(z); G groups, weight)      the gate FIRST
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

The multipliers sit on ACTIVATIONS, so int8 weights stay plain. Rotary layout:
the two halves of a head against each other (models/llama._apply_rope).

A row of this model owns TWO things in one engine (docs/hybrid_cache.md): its
K/V pages in the standard pools ``[L, Hkv, N, P, D]`` (the attention half
runs ops/paged_attention.py's kernels as arch llama does) and one SLOT of the
row state (slot = batch row): per layer ``h`` [H, N, P] float32 and the
convolution's window ``conv`` [d_conv - 1, d_ssm + 2 G N] float32, the last
three inputs of the row. The state rides the launch's carry beside the V
pool: ``v_pools`` arrives and leaves as ``(pool, {"h": .., "conv": ..})``
(``PagedKVCache.v_carry``). A row whose tokens start at position 0 finds its
slot as the last owner left it and counts it as zero: that follows from the
launch's own operands (``kv_lens - row_lens == 0``), so no flag crosses.
Rows with ONE token (decode rows; a one-token end of a prompt) go through
``mamba2_ssd_update``, rows with more through ``mamba2_ssd_chunk``.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import register_model
from .llama import _rms_norm, _rope

# matmul weights that engine.weight_quant=int8 packs (per output channel)
_QUANT_KEYS = (
    "w_in", "w_out", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "lm_head",
)
_DEFAULTS = {
    "dtype": "bfloat16", "norm_eps": 1e-5, "hidden_act": "silu",
    "tie_embeddings": False, "scan_layers": False, "rope_theta": 10000.0,
    "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_chunk_size": 128,
    "mamba_rms_norm": True, "mamba_norm_before_gate": False,
    "mamba_conv_bias": True,
    "embedding_multiplier": 1.0, "lm_head_multiplier": 1.0,
    "ssm_in_multiplier": 1.0, "ssm_out_multiplier": 1.0,
    "ssm_multipliers": [1.0, 1.0, 1.0, 1.0, 1.0],
    "attention_in_multiplier": 1.0, "attention_out_multiplier": 1.0,
    "key_multiplier": 1.0, "mlp_multipliers": [1.0, 1.0],
}
_REFUSED = ("attention_bias", "mlp_bias", "projectors_bias", "mamba_proj_bias")


@register_model("falcon_h1")
def build(config: dict) -> SimpleNamespace:
    cfg = dict(_DEFAULTS)
    cfg.update(config or {})
    vocab, dim = int(cfg["vocab_size"]), int(cfg["dim"])
    n_layers = int(cfg["n_layers"])
    n_heads, n_kv = int(cfg["n_heads"]), int(cfg["n_kv_heads"])
    head_dim = int(cfg.get("head_dim") or dim // n_heads)
    group = n_heads // n_kv
    ffn_dim = int(cfg["ffn_dim"])
    eps = float(cfg["norm_eps"])
    theta = float(cfg["rope_theta"])
    dtype = jnp.dtype(cfg["dtype"])
    scan_layers = bool(cfg["scan_layers"])
    d_ssm = int(cfg["mamba_d_ssm"])
    m_heads = int(cfg["mamba_n_heads"])
    m_head = int(cfg.get("mamba_d_head") or d_ssm // m_heads)
    d_state = int(cfg["mamba_d_state"])
    m_groups = int(cfg["mamba_n_groups"])
    d_conv = int(cfg["mamba_d_conv"])
    conv_dim = d_ssm + 2 * m_groups * d_state
    in_dim = 2 * d_ssm + 2 * m_groups * d_state + m_heads
    if cfg["hidden_act"] != "silu":
        raise ValueError("falcon_h1: hidden_act must be 'silu'")
    if cfg["tie_embeddings"]:
        raise ValueError("falcon_h1: the head is untied (tie_embeddings false)")
    if m_heads * m_head != d_ssm or m_heads % m_groups:
        raise ValueError(
            "falcon_h1: mamba_n_heads {} x mamba_d_head {} must give "
            "mamba_d_ssm {}, in mamba_n_groups {} equal groups".format(
                m_heads, m_head, d_ssm, m_groups))
    if not cfg["mamba_rms_norm"] or cfg["mamba_norm_before_gate"]:
        raise ValueError(
            "falcon_h1: the mixer's output is gated FIRST and then normed by "
            "groups (mamba_rms_norm true, mamba_norm_before_gate false)")
    if not cfg["mamba_conv_bias"]:
        raise ValueError("falcon_h1: the convolution has a bias")
    for key in _REFUSED:
        if cfg.get(key):
            raise ValueError("falcon_h1: {} must be false".format(key))
    if cfg.get("kv_quant"):
        raise ValueError(
            "kv_quant cannot serve arch falcon_h1: its rows carry a float32 "
            "state beside their pages, and scale pools have no place in "
            "that carry yet")
    if cfg.get("lora_rank"):
        raise ValueError(
            "lora adapters are not served by arch falcon_h1 yet: its "
            "projections have no adapter rows")
    mult = {k: float(cfg[k]) for k in (
        "embedding_multiplier", "lm_head_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier", "attention_in_multiplier",
        "attention_out_multiplier", "key_multiplier")}
    ssm_mults = [float(v) for v in cfg["ssm_multipliers"]]
    mlp_mults = [float(v) for v in cfg["mlp_multipliers"]]
    widths = (d_ssm, d_ssm, m_groups * d_state, m_groups * d_state, m_heads)
    cuts = [sum(widths[:i]) for i in range(len(widths) + 1)]

    def _mup(values):
        """[in_dim] float32: one value per slice of the in-projection."""
        return jnp.concatenate([
            jnp.full((w,), v, jnp.float32) for w, v in zip(widths, values)])

    # -- init ---------------------------------------------------------------

    def _dense(key, shp, fan_in, undo=1.0):
        # ``undo``: the multiplier the program applies to this projection's
        # output. Trained muP weights have absorbed theirs; random ones are
        # drawn so that every branch comes out at unit variance WITH the
        # multipliers on (a comparison against the reference must see all
        # three branches, and a forgotten multiplier must show)
        return (jax.random.normal(key, shp, jnp.float32)
                * (fan_in ** -0.5 / undo)).astype(dtype)

    def _init_layer(key):
        k = jax.random.split(key, 13)
        ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
        w_in = jax.random.normal(k[0], (dim, in_dim), jnp.float32) * (
            dim ** -0.5 / (mult["ssm_in_multiplier"] * _mup(ssm_mults)))
        # the recurrence's own parameters as the Mamba-2 family draws them:
        # dt log-uniform in [1e-3, 1e-1] through the inverse softplus,
        # A = -U(1, 16), D = 1: decay times from under a token to ~1000
        dt0 = jnp.exp(jax.random.uniform(
            k[1], (m_heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "attn_norm": ones(dim), "ffn_norm": ones(dim),
            "w_in": w_in.astype(dtype),
            "conv_w": (jax.random.normal(k[2], (d_conv, conv_dim), jnp.float32)
                       * d_conv ** -0.5),
            "conv_b": 0.1 * jax.random.normal(k[3], (conv_dim,), jnp.float32),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "a_log": jnp.log(jax.random.uniform(
                k[4], (m_heads,), jnp.float32, 1.0, 16.0)),
            "d_skip": jnp.ones((m_heads,), jnp.float32),
            "ssm_norm": ones(d_ssm),
            "w_out": _dense(k[5], (d_ssm, dim), d_ssm,
                            mult["ssm_out_multiplier"]),
            "wq": _dense(k[6], (dim, n_heads * head_dim), dim,
                         mult["attention_in_multiplier"]),
            "wk": _dense(k[7], (dim, n_kv * head_dim), dim,
                         mult["attention_in_multiplier"]
                         * mult["key_multiplier"]),
            "wv": _dense(k[8], (dim, n_kv * head_dim), dim,
                         mult["attention_in_multiplier"]),
            "wo": _dense(k[9], (n_heads * head_dim, dim), n_heads * head_dim,
                         mult["attention_out_multiplier"]),
            "w_gate": _dense(k[10], (dim, ffn_dim), dim, mlp_mults[0]),
            "w_up": _dense(k[11], (dim, ffn_dim), dim),
            "w_down": _dense(k[12], (ffn_dim, dim), ffn_dim, mlp_mults[1]),
        }

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
        full-precision tree never exists. ``params["layers"]`` is ONE dict
        whose leaves stack the layers (every block is the same kind)."""
        if weight_quant not in (None, "", "int8"):
            raise ValueError(
                "falcon_h1 serves weight_quant 'int8' or none (got {!r}): "
                "the int4 kernels know llama's projections only"
                .format(weight_quant))
        quant = _quantize if weight_quant else (lambda tree: tree)
        keys = jax.random.split(rng, 3)
        params: Dict[str, Any] = {
            "embed": _dense(keys[0], (vocab, dim), 1.0,
                            mult["embedding_multiplier"]),
            "final_norm": jnp.ones((dim,), dtype),
        }
        params.update(jax.jit(lambda k: quant({"lm_head": _dense(
            k, (dim, vocab), dim, mult["lm_head_multiplier"])}))(keys[1]))
        params["layers"] = jax.lax.map(
            lambda k: quant(_init_layer(k)),
            jax.random.split(keys[2], n_layers))
        return params

    def init_state(slots: int):
        """The row state of ``slots`` batch rows (and the kernels' null slot
        behind them), zero: named planes, each [L, slots + 1, ...]."""
        from ..ops.mamba2 import state_shape

        return {
            "h": jnp.zeros(state_shape(
                n_layers, slots, m_heads, d_state, m_head), jnp.float32),
            "conv": jnp.zeros(
                (n_layers, slots + 1, d_conv - 1, conv_dim), jnp.float32),
        }

    # -- layer math ---------------------------------------------------------

    def _mm(layer, name, x):
        w = layer[name]
        if isinstance(w, dict):
            from ..ops.quant import dequantize

            w = dequantize(w["_q8"], w["_scale"], dtype)
        return x @ w

    def _scaled(x, value, ctx):
        if value == 1.0 or not ctx.get("multipliers", True):
            return x
        return x * jnp.asarray(value, x.dtype)

    def _rot(x, cos, sin):
        cos, sin = cos[:, None], sin[:, None]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)

    def _paged_kernel(k_pools):
        from ..ops.paged_attention import paged_kernel_unsupported_reason

        return paged_kernel_unsupported_reason(
            head_dim, k_pools.shape[3], k_pools.dtype) is None

    def _ssd_kernel(tokens):
        from ..ops.mamba2 import ssd_kernel_unsupported_reason

        return ssd_kernel_unsupported_reason(
            m_heads, m_groups, m_head, d_state, tokens) is None

    def _attention(layer, u, li, pools, ctx):
        """The attention half over T tokens (u [T, dim], normed): K/V into
        layer ``li`` of the stacked pools, the paged attention, W^O.
        Returns (t [T, dim], pools)."""
        from ..ops import paged_attention as pa

        t = u.shape[0]
        kernel = ctx["paged_kernel"]
        a_in = _scaled(u, mult["attention_in_multiplier"], ctx)
        with jax.named_scope("qkv"):
            q = _mm(layer, "wq", a_in).reshape(t, n_heads, head_dim)
            k = _scaled(_mm(layer, "wk", a_in), mult["key_multiplier"], ctx)
            k = k.reshape(t, n_kv, head_dim)
            v = _mm(layer, "wv", a_in).reshape(t, n_kv, head_dim)
            q, k = _rot(q, *ctx["cos_sin"]), _rot(k, *ctx["cos_sin"])
        write = pa.paged_kv_write if kernel else pa.paged_kv_write_xla
        with jax.named_scope("kv_write"):
            pools = write(pools[0], pools[1], k, v, ctx["write_page"],
                          ctx["write_offset"], layer=li)
        with jax.named_scope("attn"):
            if ctx["mode"] == "decode":
                attend = pa.paged_attention if kernel \
                    else pa.paged_attention_xla
                o = attend(
                    q.reshape(t, n_kv, group, head_dim), pools[0], pools[1],
                    ctx["page_table"], ctx["attend_lens"], layer=li,
                ).reshape(t, n_heads * head_dim)
            else:
                q_view = ctx["place"](q.reshape(t, n_heads * head_dim))
                q_view = q_view.reshape(-1, n_kv, group, head_dim)
                args = (q_view, pools[0], pools[1], ctx["page_table"],
                        ctx["kv_lens"], ctx["row_starts"], ctx["row_lens"])
                if kernel:
                    o = pa.ragged_paged_attention(
                        *args, item_rows=ctx["item_rows"],
                        item_q0=ctx["item_q0"], layer=li)
                else:
                    o = pa.ragged_paged_attention_xla(*args, layer=li)
                o = ctx["back"](o.reshape(-1, n_heads * head_dim))
        with jax.named_scope("oproj"):
            y = _mm(layer, "wo", o.astype(u.dtype))
        return _scaled(y, mult["attention_out_multiplier"], ctx), pools

    def _conv(layer, xbc, window, ctx):
        """The causal depthwise convolution over the launch's tokens (xbc
        [T, conv_dim] float32), a row's first tokens reading the row's
        carried ``window`` [B, d_conv - 1, conv_dim] (zero for a row that
        starts its sequence). Returns (c [T, conv_dim], the rows' new
        windows [B, d_conv - 1, conv_dim])."""
        t, b, w = xbc.shape[0], window.shape[0], d_conv - 1
        window = jnp.where(ctx["reset"][:, None, None], 0.0, window)
        comb = jnp.concatenate([window.reshape(b * w, conv_dim), xbc])
        back = jnp.arange(d_conv, dtype=jnp.int32)[None, :]     # k tokens back
        off = ctx["tok_off"][:, None]
        here = jnp.arange(t, dtype=jnp.int32)[:, None]
        idx = jnp.where(off >= back, b * w + here - back,
                        ctx["tok_row"][:, None] * w + w + off - back)
        taps = comb[idx]                                       # [T, d_conv, C]
        c = layer["conv_b"] + jnp.sum(
            taps * layer["conv_w"][::-1][None], axis=1)
        n = ctx["row_lens"][:, None]
        rel = n - w + jnp.arange(w, dtype=jnp.int32)[None, :]   # [B, w]
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        src = jnp.where(rel >= 0, b * w + ctx["row_first"][:, None] + rel,
                        rows * w + w + rel)
        new = jnp.where((n > 0)[..., None], comb[jnp.clip(
            src, 0, comb.shape[0] - 1)], window)
        return c, new

    def _mixer(layer, u, li, state, ctx):
        """The Mamba-2 half over T tokens. Returns (m [T, dim], state)."""
        from ..ops import mamba2

        t = u.shape[0]
        b = ctx["reset"].shape[0]
        with jax.named_scope("ssm_in"):
            p = _mm(layer, "w_in", _scaled(u, mult["ssm_in_multiplier"], ctx))
            p = p.astype(jnp.float32)
            if ctx.get("multipliers", True):
                p = p * _mup(ssm_mults)
            z, xbc, dt = (p[:, :cuts[1]], p[:, cuts[1]:cuts[4]],
                          p[:, cuts[4]:])
        with jax.named_scope("ssm_conv"):
            c, window = _conv(layer, xbc, state["conv"][li, :b], ctx)
            c = jax.nn.silu(c)
            zero = jnp.int32(0)
            conv = jax.lax.dynamic_update_slice(
                state["conv"], window[None],
                (jnp.asarray(li, jnp.int32), zero, zero, zero))
            xs = c[:, :d_ssm].reshape(t, m_heads, m_head)
            bm = c[:, d_ssm:d_ssm + m_groups * d_state].reshape(
                t, m_groups, d_state)
            cm = c[:, d_ssm + m_groups * d_state:].reshape(
                t, m_groups, d_state)
            dt = jax.nn.softplus(dt + layer["dt_bias"])        # [T, H]
            log_decay = -dt * jnp.exp(layer["a_log"])
            dtx = dt[..., None] * xs
        kernel = ctx["ssd_kernel"]
        kw = {"layer": li, "round_state": bool(ctx.get("round_state"))}
        h = state["h"]
        ragged = ctx["mode"] == "ragged"
        if ragged:
            with jax.named_scope("mamba2_ssd_chunk"):
                chunk = (mamba2.mamba2_ssd_chunk if kernel
                         else mamba2.mamba2_ssd_chunk_xla)
                y, h = chunk(dtx, log_decay, bm, cm, ctx["tok_row"],
                             ctx["tok_many"], *ctx["rows_many"],
                             ctx["reset"], h, **kw)
        with jax.named_scope("mamba2_ssd_update"):
            update = (mamba2.mamba2_ssd_update if kernel
                      else mamba2.mamba2_ssd_update_xla)
            # a row's one token: its last of the launch; a decode pass IS
            # one token a row
            at = (lambda a: a[ctx["row_last"]]) if ragged else (lambda a: a)
            y1, h = update(at(dtx), jnp.exp(at(log_decay)), at(bm), at(cm),
                           *ctx["rows_one"], ctx["reset"], h, **kw)
            y = jnp.where(ctx["tok_one"][:, None, None],
                          y1[ctx["tok_row"]], y) if ragged else y1
        with jax.named_scope("ssm_out"):
            y = (y + layer["d_skip"][None, :, None] * xs).reshape(t, d_ssm)
            per = d_ssm // m_groups

            def norm(a):
                a = a.reshape(t, m_groups, per)
                a = a * jax.lax.rsqrt(
                    jnp.mean(a * a, axis=-1, keepdims=True) + eps)
                return a.reshape(t, d_ssm) * layer["ssm_norm"].astype(
                    jnp.float32)

            if ctx.get("gate_after_norm"):
                y = norm(y) * jax.nn.silu(z)
            else:
                y = norm(y * jax.nn.silu(z))
            m = _mm(layer, "w_out", y.astype(u.dtype))
        return (_scaled(m, mult["ssm_out_multiplier"], ctx),
                {"h": h, "conv": conv})

    def _mlp(layer, g, ctx):
        with jax.named_scope("ffn"):
            gate = _scaled(_mm(layer, "w_gate", g), mlp_mults[0], ctx)
            y = _mm(layer, "w_down", jax.nn.silu(gate) * _mm(layer, "w_up", g))
            return _scaled(y, mlp_mults[1], ctx)

    def _layer(x, layer, li, carry, ctx):
        pools, state = carry
        u = _rms_norm(x, layer["attn_norm"], eps)
        mixed = jnp.zeros_like(x)
        if not ctx.get("drop_mixer"):
            m, state = _mixer(layer, u, li, state, ctx)
            mixed = mixed + m.astype(x.dtype)
        if not ctx.get("drop_attention"):
            a, pools = _attention(layer, u, li, pools, ctx)
            mixed = mixed + a.astype(x.dtype)
        x = x + mixed
        g = _rms_norm(x, layer["ffn_norm"], eps)
        return x + _mlp(layer, g, ctx).astype(x.dtype), (pools, state)

    def _layers(params, x, carry, ctx):
        layers = params["layers"]
        if scan_layers:
            def body(state, xs):
                x, carry = state
                layer, li = xs
                return _layer(x, layer, li, carry, ctx), None

            (x, carry), _ = jax.lax.scan(
                body, (x, carry),
                (layers, jnp.arange(n_layers, dtype=jnp.int32)))
            return x, carry
        for i in range(n_layers):
            layer = jax.tree.map(lambda a, i=i: a[i], layers)
            x, carry = _layer(x, layer, i, carry, ctx)
        return x, carry

    def _embed(params, tokens, ctx):
        return _scaled(params["embed"][tokens],
                       mult["embedding_multiplier"], ctx)

    @jax.named_scope("logits")
    def _logits(params, x, ctx):
        x = _rms_norm(x, params["final_norm"], eps)
        y = _mm(params, "lm_head", x).astype(jnp.float32)
        if ctx.get("multipliers", True):
            y = y * mult["lm_head_multiplier"]
        return y

    def _split(v_pools):
        if not isinstance(v_pools, tuple):
            raise ValueError(
                "arch falcon_h1 needs its row state beside the V pool "
                "(v_pools = (pool, {'h': .., 'conv': ..}): "
                "PagedKVCache(row_state=...).v_carry)")
        return v_pools

    def _refuse_rows(where, lora_idx, unsupported):
        given = sorted(k for k, v in unsupported.items() if v is not None)
        if lora_idx is not None:
            given.append("lora_idx")
        if given:
            raise ValueError(
                "arch falcon_h1 {} serves plain rows only: no lora rows, no "
                "scale pools (kv_quant), no verify rows and no draft trees "
                "({})".format(where, ", ".join(given)))

    def forward_ragged(
        params, tokens, tok_pos, tok_row, tok_valid, tok_slot, row_last,
        k_pools, v_pools, page_table, kv_lens, row_starts, row_lens,
        write_page, write_offset, item_rows=None, item_q0=None,
        lora_idx=None, *, controls=None, **unsupported,
    ):
        """models/llama.forward_ragged for this architecture: the same
        operands and the same results, (row logits [R, vocab], k_pools,
        v_pools), with ``v_pools`` = (V pool, the row state). ``controls``
        (tests only: ``multipliers`` False, ``gate_after_norm`` /
        ``round_state`` / ``drop_attention`` / ``drop_mixer`` True) switch a
        mechanism."""
        _refuse_rows("forward_ragged", lora_idx, unsupported)
        from ..ops.mamba2 import compact_rows
        from ..ops.paged_attention import _RAGGED_QB, ragged_view_tokens

        kernel = _paged_kernel(k_pools)
        c = tokens.shape[0]
        view = ragged_view_tokens(
            c, row_starts.shape[0], _RAGGED_QB if kernel else 1)
        slot_tok = jnp.full((view,), c, jnp.int32).at[tok_slot].set(
            jnp.arange(c, dtype=jnp.int32), mode="drop")
        v_pool, state = _split(v_pools)
        first_pos = kv_lens - row_lens          # a row's tokens before this
        one, many = row_lens == 1, row_lens > 1
        ctx = dict(
            controls or {}, mode="ragged", paged_kernel=kernel,
            ssd_kernel=_ssd_kernel(c),
            cos_sin=_rope(tok_pos, head_dim, theta),
            page_table=page_table, kv_lens=kv_lens, row_starts=row_starts,
            row_lens=row_lens, write_page=write_page,
            write_offset=write_offset, item_rows=item_rows, item_q0=item_q0,
            place=lambda a: a.at[slot_tok].get(mode="fill", fill_value=0),
            back=lambda a: a.at[tok_slot].get(mode="fill", fill_value=0),
            # the mixer's view of the launch
            reset=(first_pos == 0) & (row_lens > 0),
            tok_row=tok_row, row_last=row_last,
            row_first=row_last - jnp.maximum(row_lens - 1, 0),
            # a token's place in its row's share of THIS launch; a pad
            # says d_conv: it then reads the launch's tokens, never a window
            tok_off=jnp.where(tok_valid, tok_pos - first_pos[tok_row],
                              d_conv),
            tok_one=tok_valid & one[tok_row],
            tok_many=tok_valid & many[tok_row],
            rows_one=compact_rows(one), rows_many=compact_rows(many),
        )
        x, (pools, state) = _layers(
            params, _embed(params, tokens, ctx), ((k_pools, v_pool), state),
            ctx)
        return (_logits(params, x[row_last], ctx), pools[0],
                (pools[1], state))

    def decode_paged(
        params, tokens, k_pools, v_pools, page_table, lengths, write_page,
        write_offset, lora_idx=None, *, active=None, controls=None,
        **unsupported,
    ):
        """models/llama.decode_paged for this architecture: one token a row
        at position ``lengths[b]``; a row ``active`` masks out attends
        nothing and its slot comes back bit for bit."""
        _refuse_rows("decode_paged", lora_idx, unsupported)
        from ..ops.mamba2 import compact_rows

        b = tokens.shape[0]
        live = jnp.ones((b,), bool) if active is None else active
        v_pool, state = _split(v_pools)
        rows = jnp.arange(b, dtype=jnp.int32)
        ctx = dict(
            controls or {}, mode="decode", paged_kernel=_paged_kernel(k_pools),
            ssd_kernel=_ssd_kernel(None),
            cos_sin=_rope(lengths, head_dim, theta), page_table=page_table,
            attend_lens=jnp.where(live, lengths + 1, 0),
            write_page=write_page, write_offset=write_offset,
            reset=live & (lengths == 0), tok_row=rows, row_last=rows,
            row_first=rows, row_lens=live.astype(jnp.int32),
            tok_off=jnp.where(live, 0, d_conv),
            rows_one=compact_rows(live),
        )
        x, (pools, state) = _layers(
            params, _embed(params, tokens, ctx), ((k_pools, v_pool), state),
            ctx)
        return _logits(params, x, ctx), pools[0], (pools[1], state)

    def _paged_only(name):
        def refuse(*_a, **_k):
            raise ValueError(
                "{}: arch falcon_h1 is served from engine.cache=paged only "
                "(a row owns K/V pages and a state slot; it has no "
                "dense-cache path)".format(name))

        return refuse

    return SimpleNamespace(
        init=init,
        init_state=init_state,
        forward_ragged=forward_ragged,
        decode_paged=decode_paged,
        verify_paged=None,
        # what the engine reads to hold the slot pool beside the pages and
        # to count a launch's work on the mixer (llm/engine.py)
        row_state=SimpleNamespace(
            kind="mamba2", n_heads=m_heads, n_groups=m_groups,
            head_dim=m_head, d_state=d_state, d_conv=d_conv,
            conv_dim=conv_dim, chunk_size=int(cfg["mamba_chunk_size"]),
        ),
        prepare_params=lambda params: params,
        config=cfg,
        head_dim=head_dim,
        n_kv_heads=n_kv,
        n_heads=n_heads,
        n_layers=n_layers,
        lora_rank=0,
        max_loras=0,
        paged_unsupported_reason=None,
        **{name: _paged_only(name) for name in (
            "apply", "init_cache", "prefill", "prefill_chunk", "decode",
            "verify")},
        prefill_ring=None,
        prefill_pipeline=None,
    )
