"""Llama-3-family decoder in pure functional JAX (flagship LLM architecture).

TPU-first design notes:
- bf16 params/activations by default (MXU-native), fp32 RMSNorm accumulation;
- GQA (n_kv_heads < n_heads) with head-batched einsums — no per-head Python
  loops, everything a single large matmul per projection so XLA tiles it onto
  the MXU;
- rotary embeddings precomputed per call from positions (static shapes under
  jit; positions are data, not shape);
- decode path takes a dense KV cache laid out [layers, batch, max_len, kv_heads,
  head_dim] so a TP mesh can shard kv_heads over the `tp` axis and the cache
  rides HBM untouched between steps. The paged-KV variant used by the LLM
  engine lives in clearml_serving_tpu/llm/kv_cache.py and reuses these weights.

Replaces the reference's vLLM model executor (CUDA) as the compute path behind
the OpenAI-compatible route surface (reference preprocess_service.py:619-1348).
"""

from __future__ import annotations

import math
from functools import partial
from types import SimpleNamespace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import register_model

# Named configs: full Llama-3-8B plus scaled-down variants for tests/benches.
PRESETS: Dict[str, Dict[str, Any]] = {
    "llama3-8b": dict(
        vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, rope_theta=500000.0, norm_eps=1e-5, max_seq_len=8192,
    ),
    "llama3-1b": dict(  # llama-3.2-1B-shaped
        vocab_size=128256, dim=2048, n_layers=16, n_heads=32, n_kv_heads=8,
        ffn_dim=8192, rope_theta=500000.0, norm_eps=1e-5, max_seq_len=8192,
    ),
    "llama-tiny": dict(  # CI-sized
        vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=128, rope_theta=10000.0, norm_eps=1e-5, max_seq_len=256,
    ),
}


def resolve_config(config: dict) -> dict:
    cfg = dict(PRESETS.get(config.get("preset", ""), {}))
    cfg.update({k: v for k, v in config.items() if k != "preset"})
    cfg.setdefault("dtype", "bfloat16")
    cfg.setdefault("tie_embeddings", False)
    cfg.setdefault("rope_theta", 10000.0)
    cfg.setdefault("norm_eps", 1e-5)
    cfg.setdefault("max_seq_len", 4096)
    return cfg


def _rms_norm(x, weight, eps, offset=0.0):
    # fp32 accumulation regardless of activation dtype. ``offset`` supports
    # the Gemma convention of zero-initialized weights applied as (1 + w).
    x32 = x.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (norm * (offset + weight.astype(jnp.float32))).astype(x.dtype)


def _softcap(x, cap):
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(x / cap)


def _rope_freqs(head_dim: int, theta: float, rope_scaling: Optional[dict]):
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if not rope_scaling:
        return freqs
    rope_type = rope_scaling.get("rope_type") or rope_scaling.get("type")
    if rope_type == "linear":
        # position-interpolation (Chen et al.): every frequency shrinks by
        # 1/factor, equivalent to scaling positions down
        return freqs / float(rope_scaling["factor"])
    if rope_type == "longrope":
        # position-dependent; applied in _rope — validate here (fail fast
        # at build instead of inside the first traced forward)
        hd2 = head_dim // 2
        for key in ("short_factor", "long_factor"):
            fac = rope_scaling.get(key)
            if fac is None or len(fac) != hd2:
                raise ValueError(
                    "rope_scaling.{} must list head_dim/2 = {} per-dim "
                    "factors".format(key, hd2)
                )
        if not rope_scaling.get("original_max_position_embeddings"):
            raise ValueError(
                "longrope rope_scaling needs original_max_position_embeddings"
            )
        return freqs
    if rope_type == "yarn":
        # YaRN (Peng et al.): NTK-by-parts — high frequencies extrapolate
        # (unscaled), low frequencies interpolate (1/factor), a linear ramp
        # between wavelength bands derived from beta_fast/beta_slow blends
        # the middle; the attention temperature rides cos/sin in _rope.
        # Mirrors transformers' _compute_yarn_parameters exactly: band
        # indices live in FULL head_dim space (clamped to head_dim-1, not
        # head_dim//2-1), truncate floors/ceils them (default on), missing
        # original_max_position_embeddings falls back to the deployed
        # length (injected by build from max_seq_len).
        factor = float(rope_scaling["factor"])
        orig = float(
            rope_scaling.get("original_max_position_embeddings")
            or rope_scaling.get("max_position_embeddings")
            or 4096
        )
        beta_fast = float(rope_scaling.get("beta_fast") or 32.0)
        beta_slow = float(rope_scaling.get("beta_slow") or 1.0)
        hd2 = head_dim // 2

        def band(beta):
            # dim index whose wavelength covers `beta` periods over orig
            return head_dim * math.log(orig / (beta * 2.0 * math.pi)) / (
                2.0 * math.log(theta)
            )

        low, high = band(beta_fast), band(beta_slow)
        if rope_scaling.get("truncate", True):
            low, high = math.floor(low), math.ceil(high)
        low = max(low, 0)
        high = min(high, head_dim - 1)
        if low == high:
            high += 0.001  # prevent singularity
        ramp = jnp.clip(
            (jnp.arange(hd2, dtype=jnp.float32) - low) / (high - low),
            0.0, 1.0,
        )
        extrap_w = 1.0 - ramp  # 1 = keep unscaled, 0 = fully interpolated
        return (freqs / factor) * (1.0 - extrap_w) + freqs * extrap_w
    if rope_type != "llama3":
        raise ValueError(
            "unsupported rope_scaling type {!r} (supported: llama3, "
            "linear, yarn, longrope)".format(rope_type)
        )
    # Llama-3.1 frequency-dependent scaling: long wavelengths scale by
    # 1/factor, short ones stay, the middle band interpolates smoothly.
    factor = float(rope_scaling["factor"])
    low = float(rope_scaling.get("low_freq_factor", 1.0))
    high = float(rope_scaling.get("high_freq_factor", 4.0))
    orig = float(rope_scaling.get("original_max_position_embeddings", 8192))
    wavelen = 2.0 * jnp.pi / freqs
    low_wavelen = orig / low
    high_wavelen = orig / high
    smooth = (orig / wavelen - low) / (high - low)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    scaled = (1.0 - smooth) * freqs / factor + smooth * freqs
    return jnp.where(
        wavelen > low_wavelen, freqs / factor,
        jnp.where(wavelen < high_wavelen, freqs, scaled),
    )


def _yarn_attention_factor(rope_scaling: dict) -> float:
    """YaRN attention temperature on cos/sin: explicit attention_factor,
    else DeepSeek's mscale pair, else 0.1*ln(factor)+1 (the paper's
    default; HF _compute_yarn_parameters order)."""
    att = rope_scaling.get("attention_factor")
    if att is not None:
        return float(att)
    factor = float(rope_scaling["factor"])

    def get_mscale(scale, m=1.0):
        return 1.0 if scale <= 1.0 else 0.1 * m * math.log(scale) + 1.0

    mscale = rope_scaling.get("mscale")
    mscale_all_dim = rope_scaling.get("mscale_all_dim")
    # HF semantics: the DeepSeek pair applies only when BOTH are truthy
    if mscale and mscale_all_dim:
        return get_mscale(factor, float(mscale)) / get_mscale(
            factor, float(mscale_all_dim)
        )
    return get_mscale(factor)


def _rope(positions: jnp.ndarray, head_dim: int, theta: float,
          rope_scaling: Optional[dict] = None):
    """cos/sin tables for given positions: [..., head_dim//2]."""
    rope_type = (
        (rope_scaling.get("rope_type") or rope_scaling.get("type"))
        if rope_scaling
        else None
    )
    if rope_type == "yarn":
        freqs = _rope_freqs(head_dim, theta, rope_scaling)
        att = _yarn_attention_factor(rope_scaling)
        angles = positions.astype(jnp.float32)[..., None] * freqs
        return jnp.cos(angles) * att, jnp.sin(angles) * att
    if rope_type == "longrope":
        # Phi-3 LongRoPE (vLLM Phi3LongRoPEScaledRotaryEmbedding layout):
        # per-dim rescale factors — SHORT factors for positions inside the
        # original training window, LONG factors beyond it (a per-position
        # selection, so one table serves any mix of contexts) — plus a
        # global attention scale on cos/sin:
        # sqrt(1 + ln(max/orig)/ln(orig)) unless the checkpoint pins one.
        base = _rope_freqs(head_dim, theta, None)
        short = jnp.asarray(rope_scaling["short_factor"], jnp.float32)
        long = jnp.asarray(rope_scaling["long_factor"], jnp.float32)
        orig = float(rope_scaling["original_max_position_embeddings"])
        max_pos = float(
            rope_scaling.get("max_position_embeddings")
            or rope_scaling.get("max_seq_len")
            or orig
        )
        att = rope_scaling.get("attention_factor")
        if att is None:
            # plain-python math: this is a config constant, and _rope runs
            # under jit (jnp here would try to concretize a tracer)
            scale = max(max_pos / orig, 1.0)
            att = (
                1.0
                if scale <= 1.0
                else math.sqrt(1.0 + math.log(scale) / math.log(orig))
            )
        pos = positions.astype(jnp.float32)[..., None]            # [..., 1]
        freqs = jnp.where(pos < orig, base / short, base / long)  # [..., hd/2]
        angles = pos * freqs
        return jnp.cos(angles) * att, jnp.sin(angles) * att
    freqs = _rope_freqs(head_dim, theta, rope_scaling)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., hd/2]
    return jnp.cos(angles), jnp.sin(angles)


def _apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x: [B, S, H, D]; cos/sin: [B, S, D/2] -> broadcast over heads."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def moe_route(router_logits, top_k: int, *, scoring: str = "softmax",
              bias=None, scale: float = 1.0):
    """The router's choice for [T, E] float32 logits: (gates [T, k], experts
    [T, k]), the gates renormalised over the chosen experts. ``softmax``
    (Mixtral): top-k of the probabilities. ``sigmoid`` (DeepSeek-V3's
    ``noaux_tc``, one group): top-k of score + ``bias``, gated by the score
    alone (the bias selects and does not weigh), times ``scale``."""
    if scoring == "softmax":
        probs = jax.nn.softmax(router_logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, top_k)            # [T, k]
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(router_logits)
        _, top_e = jax.lax.top_k(probs + bias, top_k)
        top_p = jnp.take_along_axis(probs, top_e, axis=-1)
    else:
        raise ValueError("unknown router scoring {!r}".format(scoring))
    # mixtral renormalizes the chosen experts' probabilities
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if scale != 1.0:
        top_p = top_p * scale
    return top_p, top_e


def moe_dropless(tokens, top_p, top_e, w_gate_e, w_up_e, w_down_e):
    """Every token through ALL the experts of the stacks [E, ...], combined
    by its gates: no capacity, no cross-token interaction. ``top_e`` [T, k]
    indexes the stacks; an index past them (an expert this chip does not
    hold) adds nothing."""
    n_tok, n_experts = tokens.shape[0], w_gate_e.shape[0]
    weights = jnp.zeros((n_tok, n_experts), jnp.float32).at[
        jnp.arange(n_tok)[:, None], top_e
    ].add(top_p)
    # the expert axis is a BATCH axis of every product, on both
    # operands: left free on the weights alone ("td,edf->etf") the v5e
    # compiler may re-lay the whole [E, D, F] stack out of the layer
    # scan for the product (tests/test_tpu_compile.py -k compact_axis)
    per_expert = jnp.broadcast_to(tokens[None], (n_experts,) + tokens.shape)
    h = jax.nn.silu(
        jnp.einsum("etd,edf->etf", per_expert, w_gate_e)
    ) * jnp.einsum("etd,edf->etf", per_expert, w_up_e)
    expert_out = jnp.einsum("etf,efd->etd", h, w_down_e)
    return jnp.einsum("te,etd->td", weights.astype(tokens.dtype), expert_out)


@register_model("llama")
def build(config: dict) -> SimpleNamespace:
    cfg = resolve_config(config)
    vocab = int(cfg["vocab_size"])
    dim = int(cfg["dim"])
    n_layers = int(cfg["n_layers"])
    n_heads = int(cfg["n_heads"])
    n_kv = int(cfg["n_kv_heads"])
    ffn_dim = int(cfg["ffn_dim"])
    theta = float(cfg["rope_theta"])
    rope_scaling = cfg.get("rope_scaling") or None
    eps = float(cfg["norm_eps"])
    dtype = jnp.dtype(cfg["dtype"])
    # head_dim may be decoupled from dim (Gemma-2: 16 heads x 256 > dim)
    head_dim = int(cfg.get("head_dim") or dim // n_heads)
    _rt = (
        (rope_scaling.get("rope_type") or rope_scaling.get("type"))
        if rope_scaling
        else None
    )
    if _rt == "longrope":
        # the attention scale needs the DEPLOYED context length; HF keeps it
        # outside the rope_scaling dict, so default it from the model's own
        # max_seq_len rather than silently degrading to scale 1.0
        rope_scaling = dict(rope_scaling)
        rope_scaling.setdefault(
            "max_position_embeddings", int(cfg.get("max_seq_len") or 0) or None
        )
    elif _rt == "yarn":
        # HF falls back to config.max_position_embeddings when the dict
        # omits the original window; a silent 4096 default would shift the
        # correction bands and diverge from the HF tables
        rope_scaling = dict(rope_scaling)
        rope_scaling.setdefault(
            "original_max_position_embeddings",
            int(cfg.get("max_seq_len") or 0) or None,
        )
    _rope_freqs(head_dim, theta, rope_scaling)  # fail fast on bad cfg
    assert n_heads % n_kv == 0, "n_heads must be divisible by n_kv_heads"
    group = n_heads // n_kv

    # Gemma-family deltas over the llama skeleton:
    # - norm_offset: RMSNorm weights stored zero-init, applied as (1 + w)
    # - hidden_act "gelu_tanh": GeGLU instead of SiLU-GLU
    # - embed_scale: embeddings multiplied by sqrt(dim) (converter supplies
    #   the numeric value)
    # - query_scale: attention score scale override (Gemma-2's
    #   query_pre_attn_scalar**-0.5 instead of head_dim**-0.5)
    # - attn/final logit softcap (Gemma-2)
    # - post_block_norms: extra norms on each sublayer OUTPUT before the
    #   residual add (Gemma-2's post_attention/post_feedforward norms)
    # - alt_window: per-layer local/global attention interleave (Gemma-2);
    #   each layer carries an ``attn_global`` scalar selecting its mask
    norm_offset = 1.0 if cfg.get("norm_offset") else 0.0
    hidden_act = str(cfg.get("hidden_act", "silu"))
    if hidden_act == "silu":
        _act = jax.nn.silu
    elif hidden_act in ("gelu_tanh", "gelu_pytorch_tanh"):
        _act = partial(jax.nn.gelu, approximate=True)
    elif hidden_act == "gelu":
        _act = partial(jax.nn.gelu, approximate=False)
    else:
        raise ValueError("unsupported hidden_act {!r}".format(hidden_act))
    embed_scale = float(cfg.get("embed_scale") or 0.0)
    query_scale = float(cfg.get("query_scale") or head_dim ** -0.5)
    attn_softcap = float(cfg.get("attn_logit_softcap") or 0.0)
    final_softcap = float(cfg.get("final_logit_softcap") or 0.0)
    post_block_norms = bool(cfg.get("post_block_norms"))
    alt_window = bool(cfg.get("alt_window"))

    # -- init ---------------------------------------------------------------

    # scan_layers: stack layer params [L, ...] and lax.scan over them — XLA
    # compiles ONE layer instead of n_layers unrolled copies. Essential for
    # deep models: the unrolled 32-layer 8B graph takes many minutes to
    # compile; the scanned one compiles like a 1-layer model.
    scan_layers = bool(cfg.get("scan_layers", False))

    # sparse MoE FFN (Mixtral-style): n_experts stacked expert FFNs behind a
    # top-k router; expert weights shard over the mesh's ``ep`` axis
    n_experts = int(cfg.get("n_experts", 0) or 0)
    moe = n_experts > 1
    moe_top_k = int(cfg.get("moe_top_k", 2))
    moe_capacity = float(cfg.get("moe_capacity_factor", 1.25))

    # family deltas over the llama skeleton:
    # - attn_bias: Qwen2-style additive QKV biases
    # - sliding_window: Mistral-style local attention — key t is visible to
    #   query position p iff p - W < t <= p (0 disables)
    attn_bias = bool(cfg.get("attn_bias", False))
    sliding_window = int(cfg.get("sliding_window", 0) or 0)
    # - qk_norm: RMSNorm over head_dim of every query and key head, before
    #   the rotary embedding (Qwen3 lineage)
    # - attention="power_retention": no softmax attention at all; every layer
    #   mixes tokens through power retention of degree 2 with one learned
    #   forget gate per key-value head (ops/power_retention.py). Served from
    #   the engine's state cache (engine.cache=state, docs/state_cache.md):
    #   a sequence owns a fixed-size recurrent state, not K/V pages
    qk_norm = bool(cfg.get("qk_norm", False))
    attention = str(cfg.get("attention") or "softmax")
    if attention not in ("softmax", "power_retention"):
        raise ValueError(
            "attention must be 'softmax' or 'power_retention' (got {!r})"
            .format(attention)
        )
    retention = attention == "power_retention"
    if retention and int(cfg.get("retention_degree", 2)) != 2:
        raise ValueError(
            "power retention is implemented for degree 2 only (got "
            "retention_degree={!r})".format(cfg.get("retention_degree"))
        )
    # state_round="bfloat16": the recurrent state rounded to bfloat16 after
    # every update. A measuring device, never a deployment: the precision
    # one step below the float32 the configuration states, which the plain
    # reference has to tell apart (benchmark probes.tolerance)
    state_round = str(cfg.get("state_round") or "")
    if state_round not in ("", "bfloat16"):
        raise ValueError("state_round must be 'bfloat16' or unset")
    if retention and (sliding_window or cfg.get("attn_logit_softcap")
                      or cfg.get("kv_quant")):
        raise ValueError(
            "attention='power_retention' has no scores to window or softcap "
            "and no K/V to quantise: drop sliding_window / "
            "attn_logit_softcap / kv_quant"
        )

    # multi-LoRA serving (models/lora.py): stacked [A+1, in, r]/[A+1, r, out]
    # factors per targeted projection, gathered per batch slot by lora_idx
    # inside the layer body — one executable serves any adapter mix
    lora_rank, lora_targets, max_loras = 0, (), 0
    if cfg.get("lora_rank"):
        from . import lora as lora_lib

        lora_rank, lora_targets, max_loras = lora_lib.lora_spec(cfg)
        if moe and any(t in ("w_gate", "w_up", "w_down") for t in lora_targets):
            raise ValueError(
                "lora FFN targets are unsupported for MoE layers "
                "(expert-stacked weights); use attention targets"
            )

    if alt_window and not sliding_window:
        raise ValueError("alt_window needs a nonzero sliding_window")
    # per-layer global/full-attention flags for the Gemma-2 interleave:
    # default is the Gemma-2 pattern (odd layers global, even local)
    attn_global_layers = cfg.get("attn_global_layers")
    if alt_window and attn_global_layers is None:
        attn_global_layers = [1.0 if (i % 2 == 1) else 0.0 for i in range(n_layers)]
    norm_init = jnp.zeros if norm_offset else jnp.ones

    def _init_layer(key):
        def dense(k, shape, fan_in):
            return (
                jax.random.normal(k, shape, dtype=jnp.float32) * fan_in ** -0.5
            ).astype(dtype)

        k = jax.random.split(key, 8)
        out = {
            "attn_norm": norm_init((dim,), dtype),
            "wq": dense(k[0], (dim, n_heads * head_dim), dim),
            "wk": dense(k[1], (dim, n_kv * head_dim), dim),
            "wv": dense(k[2], (dim, n_kv * head_dim), dim),
            "wo": dense(k[3], (n_heads * head_dim, dim), n_heads * head_dim),
            "ffn_norm": norm_init((dim,), dtype),
        }
        if post_block_norms:
            out.update(
                post_attn_norm=norm_init((dim,), dtype),
                post_ffn_norm=norm_init((dim,), dtype),
            )
        if alt_window:
            out["attn_global"] = jnp.zeros((), jnp.float32)  # set by init()
        if qk_norm:
            out.update(q_norm=norm_init((head_dim,), dtype),
                       k_norm=norm_init((head_dim,), dtype))
        if retention:
            # the one weight the layer adds to a GQA block: a forget gate
            # per key-value head (its key folds in, so the other leaves of
            # a seed are what a softmax model of these sizes gets)
            out["wg"] = dense(jax.random.fold_in(key, 101), (dim, n_kv), dim)
        if attn_bias:
            out.update(
                bq=jnp.zeros((n_heads * head_dim,), dtype),
                bk=jnp.zeros((n_kv * head_dim,), dtype),
                bv=jnp.zeros((n_kv * head_dim,), dtype),
            )
        if moe:
            out.update(
                w_router=dense(k[7], (dim, n_experts), dim),
                w_gate_e=dense(k[4], (n_experts, dim, ffn_dim), dim),
                w_up_e=dense(k[5], (n_experts, dim, ffn_dim), dim),
                w_down_e=dense(k[6], (n_experts, ffn_dim, dim), ffn_dim),
            )
        else:
            out.update(
                w_gate=dense(k[4], (dim, ffn_dim), dim),
                w_up=dense(k[5], (dim, ffn_dim), dim),
                w_down=dense(k[6], (ffn_dim, dim), ffn_dim),
            )
        if lora_rank:
            from . import lora as lora_lib

            for t in lora_targets:
                d_in, d_out = lora_lib.target_dims(cfg, t)
                out["lora_a_" + t] = jnp.zeros(
                    (max_loras + 1, d_in, lora_rank), dtype
                )
                out["lora_b_" + t] = jnp.zeros(
                    (max_loras + 1, lora_rank, d_out), dtype
                )
        return out

    def init(rng, weight_quant: Optional[str] = None) -> Dict[str, Any]:
        """Random parameters. ``weight_quant`` ("int8"/"int4") quantizes
        each tensor as it is generated (ops/quant.quantize_llama_params, one
        jitted layer at a time), so the full-precision tree never exists —
        llama3-8b is 16 GB in bf16 and 8.6 GB packed, and only the second
        initializes inside one 16 GB chip. The engine detects the packed
        leaves and skips its own quantization pass."""
        def dense(key, shape, fan_in):
            return (
                jax.random.normal(key, shape, dtype=jnp.float32) * fan_in ** -0.5
            ).astype(dtype)

        if weight_quant:
            from ..ops.quant import quantize_llama_params

            if weight_quant not in ("int8", "int4"):
                raise ValueError(
                    "unsupported weight_quant mode {!r} (expected 'int8' or "
                    "'int4')".format(weight_quant)
                )
            quant = partial(
                quantize_llama_params, bits=4 if weight_quant == "int4" else 8
            )
            # jitted under quantization only: XLA then frees each tensor's
            # full-precision temporaries before the next one is generated
            compiled = jax.jit
        else:
            def quant(tree):
                return tree

            def compiled(fn):
                return fn

        def init_head(key):
            return quant({"lm_head": dense(key, (dim, vocab), dim)})

        def init_layer(key):
            return quant(_init_layer(key))

        keys = jax.random.split(rng, 3)
        params: Dict[str, Any] = {
            "embed": dense(keys[0], (vocab, dim), dim),
            "final_norm": norm_init((dim,), dtype),
        }
        if not cfg["tie_embeddings"]:
            params.update(compiled(init_head)(keys[1]))
        layer_keys = jax.random.split(keys[2], n_layers)
        if scan_layers:
            # sequential map under quantization: vmap would materialize
            # every layer's full-precision weights at once
            params["layers"] = (
                jax.lax.map(init_layer, layer_keys)
                if weight_quant
                else jax.vmap(_init_layer)(layer_keys)
            )
            if alt_window:
                params["layers"]["attn_global"] = jnp.asarray(
                    attn_global_layers, jnp.float32
                )
        else:
            init_one = compiled(init_layer)
            params["layers"] = [init_one(k) for k in layer_keys]
            if alt_window:
                for i, layer in enumerate(params["layers"]):
                    layer["attn_global"] = jnp.asarray(
                        attn_global_layers[i], jnp.float32
                    )
        return params


    # -- shared layer math ----------------------------------------------------

    def _w(container, name):
        """Weight accessor with inline dequantization: a leaf may be a plain
        array, {"_q8": int8, "_scale": f32}, or {"_q4": packed uint8,
        "_scale4": f32} (ops/quant.py). Because this runs INSIDE the
        (possibly scanned) layer body, XLA dequantizes one layer at a time
        next to its consumer matmul — weights at rest stay quantized in HBM
        even under scan_layers."""
        w = container[name]
        if isinstance(w, dict) and "_q8" in w:
            from ..ops.quant import dequantize

            return dequantize(w["_q8"], w["_scale"], dtype)
        if isinstance(w, dict) and "_q4" in w:
            from ..ops.quant import dequantize_int4

            return dequantize_int4(w["_q4"], w["_scale4"], dtype)
        return w

    # w4a16 serving (docs/w4a16.md): decode-shaped matmuls on int4 leaves
    # route through the Pallas fused dequant-matmul — packed nibbles stream
    # HBM->VMEM and unpack next to the MXU, so the HBM weight read is
    # structurally 4-bit instead of fusion-dependent. cfg int4_fused=False
    # pins the XLA inline-dequant path (the arm tests/test_fused_matmul.py
    # compares); misaligned shapes, prefill-sized M, and non-TPU backends
    # take that same path, byte-identically — the decision is
    # ops.fused_matmul.int4_kernel_unsupported_reason, which the engine's
    # health block reports for its decode shapes.
    int4_fused = bool(cfg.get("int4_fused", True))

    def _mm(container, name, x):
        """``x @ weight`` with quantization-aware routing. The ONE place a
        plain projection matmul touches its (possibly quantized) weight —
        MoE expert einsums and the tied-embedding lm_head keep the _w
        accessor (different contraction shapes; fallback matrix in
        docs/w4a16.md)."""
        w = container[name]
        if int4_fused and isinstance(w, dict) and "_q4" in w:
            from ..ops.fused_matmul import (
                fused_int4_matmul,
                int4_kernel_unsupported_reason,
            )

            if int4_kernel_unsupported_reason(
                x, w["_q4"], w["_scale4"]
            ) is None:
                return fused_int4_matmul(x, w["_q4"], w["_scale4"])
        return x @ _w(container, name)

    def _visible_w(q_pos, t_pos, window):
        """Causal visibility (key position t, query position q): t <= q,
        windowed to q - W < t when ``window`` is set. The ONE place the
        window semantics live — every attention path builds its mask here."""
        ok = t_pos <= q_pos
        if window:
            ok = ok & (t_pos > q_pos - window)
        return ok

    def _build_masks(build_fn):
        """``build_fn(window) -> mask``. Uniform models get one mask; under
        the Gemma-2 interleave (alt_window) BOTH masks build once per forward
        and each layer selects its own via ``attn_global`` (a scanned scalar,
        so lax.scan keeps one compiled layer body)."""
        if alt_window:
            return (build_fn(0), build_fn(sliding_window))
        return build_fn(sliding_window)

    def _layer_mask(layer, masks):
        if not alt_window:
            return masks
        mask_global, mask_local = masks
        return jnp.where(layer["attn_global"] != 0, mask_global, mask_local)

    def _lora_delta(layer, name, x, lora_idx):
        """Batched per-slot LoRA delta: x [B,S,in] -> [B,S,out]. The gather
        by lora_idx [B] selects each slot's adapter from the [A+1, ...]
        stacks (index 0 = zeros = base model); two rank-r matmuls with f32
        accumulation. Runs inside the (scanned) layer body so the stacks ride
        the same layout machinery as the base weights."""
        a = layer["lora_a_" + name][lora_idx]                  # [B, in, r]
        b = layer["lora_b_" + name][lora_idx]                  # [B, r, out]
        h = jnp.einsum("bsi,bir->bsr", x, a, preferred_element_type=jnp.float32)
        return jnp.einsum(
            "bsr,bro->bso", h, b, preferred_element_type=jnp.float32
        ).astype(x.dtype)

    def _with_lora(layer, name, x, y, lora_idx):
        if lora_idx is None or name not in lora_targets:
            return y
        return y + _lora_delta(layer, name, x, lora_idx)

    # jax.named_scope on the sections of a step (qkv, attn, kv_write, oproj,
    # ffn / moe, logits): a traced operation's op_name metadata then says
    # which section it belongs to, not only XLA's `fusion.225`

    @jax.named_scope("qkv")
    def _qkv(layer, x, cos, sin, lora_idx=None):
        b, s, _ = x.shape
        q = _with_lora(layer, "wq", x, _mm(layer, "wq", x), lora_idx)
        k = _with_lora(layer, "wk", x, _mm(layer, "wk", x), lora_idx)
        v = _with_lora(layer, "wv", x, _mm(layer, "wv", x), lora_idx)
        if attn_bias:  # Qwen2-style QKV biases (kept full precision)
            q = q + layer["bq"]
            k = k + layer["bk"]
            v = v + layer["bv"]
        q = q.reshape(b, s, n_heads, head_dim)
        k = k.reshape(b, s, n_kv, head_dim)
        v = v.reshape(b, s, n_kv, head_dim)
        if qk_norm:
            q = _rms_norm(q, layer["q_norm"], eps, norm_offset)
            k = _rms_norm(k, layer["k_norm"], eps, norm_offset)
        return _apply_rope(q, cos, sin), _apply_rope(k, cos, sin), v

    @jax.named_scope("qkv")
    def _log_gate(layer, x):
        """log g of power retention, [..., n_kv] float32: log sigmoid of a
        projection of the block's normed input, one gate per kv head."""
        return jax.nn.log_sigmoid(
            x.astype(jnp.float32) @ layer["wg"].astype(jnp.float32)
        )

    @jax.named_scope("oproj")
    def _oproj(layer, attn, lora_idx=None):
        return _with_lora(layer, "wo", attn, _mm(layer, "wo", attn), lora_idx)

    @jax.named_scope("attn")
    def _attend(q, k, v, mask):
        """q: [B,S,Hq,D]; k,v: [B,T,Hkv,D]; mask: [B,1,S,T] additive."""
        b, s, _, _ = q.shape
        t = k.shape[1]
        # Group query heads over their shared KV head: [B,S,Hkv,G,D].
        qg = q.reshape(b, s, n_kv, group, head_dim)
        scores = jnp.einsum(
            "bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32
        ) * query_scale
        if attn_softcap:
            scores = _softcap(scores, attn_softcap)  # before the mask (HF)
        scores = scores + mask[:, :, None, :, :]  # mask broadcast over groups
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
        return out.reshape(b, s, n_heads * head_dim)

    @jax.named_scope("retention")
    def _retain_full(q, k, v, log_g):
        """Power retention in its attention form over whole sequences (the
        full causal ``apply``; no state). q [B,S,Hq,D]; k, v [B,S,Hkv,D];
        log_g [B,S,Hkv]."""
        from ..ops.power_retention import power_retention_attention

        b, s = q.shape[:2]
        y = power_retention_attention(
            q.reshape(b, s, n_kv, group, head_dim), k, v, log_g
        )
        return y.reshape(b, s, n_heads * head_dim).astype(q.dtype)

    def _ffn_dense(layer, x, lora_idx=None):
        gate = _with_lora(layer, "w_gate", x, _mm(layer, "w_gate", x), lora_idx)
        up = _with_lora(layer, "w_up", x, _mm(layer, "w_up", x), lora_idx)
        h = _act(gate) * up
        return _with_lora(layer, "w_down", h, _mm(layer, "w_down", h), lora_idx)

    def _moe_routing(layer, tokens):
        router_logits = (
            tokens.astype(jnp.float32) @ _w(layer, "w_router").astype(jnp.float32)
        )                                                         # [T, E]
        return moe_route(router_logits, moe_top_k)

    def _ffn_moe(layer, x, valid=None):
        """Mixtral-style sparse MoE FFN, GShard dispatch (TPU-first: the
        token->expert routing is expressed as one-hot einsums over a fixed
        capacity, so everything is static-shape batched matmuls — expert
        weights stack [E, ...] and shard over the mesh's ``ep`` axis, with
        XLA inserting the all-to-alls).

        ``valid`` [B, S] (bool) excludes right-padding from routing —
        without it one sequence's pad tokens would consume expert capacity
        and evict another sequence's REAL tokens. Exact w.r.t. top-k routing
        EXCEPT under overflow of valid tokens (capacity_factor * tokens * k
        / E per expert, standard GShard drop).
        """
        b, s, d_ = x.shape
        tokens = x.reshape(b * s, d_)
        n_tok = b * s
        top_p, top_e = _moe_routing(layer, tokens)

        capacity = max(1, int(moe_capacity * n_tok * moe_top_k / n_experts))
        # position of each (token, slot) within its expert's capacity buffer
        onehot = jax.nn.one_hot(top_e, n_experts, dtype=jnp.int32)  # [T,k,E]
        if valid is not None:
            onehot = onehot * valid.reshape(n_tok, 1, 1).astype(jnp.int32)
        # rank tokens per expert by arrival order across (slot-major) choices
        flat = onehot.reshape(n_tok * moe_top_k, n_experts)
        pos_in_expert = (jnp.cumsum(flat, axis=0) - 1).reshape(
            n_tok, moe_top_k, n_experts
        )
        within = (pos_in_expert < capacity) & (onehot > 0)
        # dispatch tensor [T, E, C]: one-hot of each kept (token, expert, pos)
        pos_oh = jax.nn.one_hot(
            jnp.where(within, pos_in_expert, capacity), capacity, dtype=x.dtype
        )                                                         # [T,k,E,C]
        dispatch = jnp.einsum("tke,tkec->tec", onehot.astype(x.dtype), pos_oh)
        combine = jnp.einsum(
            "tke,tkec->tec",
            (top_p.astype(jnp.float32)[:, :, None] * onehot).astype(x.dtype),
            pos_oh,
        )
        expert_in = jnp.einsum("tec,td->ecd", dispatch, tokens)   # [E,C,D]
        h = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", expert_in, _w(layer, "w_gate_e"))
        ) * jnp.einsum("ecd,edf->ecf", expert_in, _w(layer, "w_up_e"))
        expert_out = jnp.einsum("ecf,efd->ecd", h, _w(layer, "w_down_e"))
        out = jnp.einsum("tec,ecd->td", combine, expert_out)      # [T, D]
        return out.reshape(b, s, d_).astype(x.dtype)

    def _ffn_moe_dropless(layer, x):
        """Dropless MoE for decode: every token computes ALL experts and
        combines the top-k — no capacity, no cross-token interaction, so an
        inactive slot can never evict an active one and quality never
        depends on batch occupancy (inference references like vLLM apply no
        capacity either). E× FFN FLOPs on a [B, 1, D] decode step is cheap;
        the GShard dispatch path stays for prefill's long sequences."""
        b, s, d_ = x.shape
        tokens = x.reshape(b * s, d_)
        top_p, top_e = _moe_routing(layer, tokens)
        out = moe_dropless(
            tokens, top_p, top_e, _w(layer, "w_gate_e"), _w(layer, "w_up_e"),
            _w(layer, "w_down_e"),
        )
        return out.reshape(b, s, d_).astype(x.dtype)

    @jax.named_scope("moe" if moe else "ffn")
    def _ffn(layer, x, valid=None, dropless=False, lora_idx=None):
        if moe:
            # decode and speculative verification must be dropless: capacity
            # dropping makes logits depend on batch occupancy, which would
            # break greedy-exactness (verify's argmax must equal decode's)
            if dropless or x.shape[1] == 1:
                return _ffn_moe_dropless(layer, x)
            return _ffn_moe(layer, x, valid)
        return _ffn_dense(layer, x, lora_idx)

    @jax.named_scope("logits")
    def _logits(params, x):
        x = _rms_norm(x, params["final_norm"], eps, norm_offset)
        if "lm_head" in params:
            out = _mm(params, "lm_head", x).astype(jnp.float32)
        else:
            out = (x @ params["embed"].T).astype(jnp.float32)
        if final_softcap:
            out = _softcap(out, final_softcap)
        return out

    def _embed(params, tokens):
        x = params["embed"][tokens]
        if embed_scale:
            # Gemma normalizer: applied in the ACTIVATION dtype like HF
            # (sqrt(dim) cast to bf16/f32 before the multiply)
            x = x * jnp.asarray(embed_scale, x.dtype)
        return x

    def _block(layer, x, attn_fn, lora_idx, ffn_kwargs=None):
        """One decoder block around pluggable attention: pre-norm ->
        attention -> (post-norm) -> residual -> pre-norm -> FFN ->
        (post-norm) -> residual. The ONE place the residual structure
        lives — every forward path (full, prefill, chunk, decode) runs
        through it, so family deltas (Gemma-2 post-block norms, norm
        offsets) apply everywhere by construction."""
        h = _rms_norm(x, layer["attn_norm"], eps, norm_offset)
        attn_out = _oproj(layer, attn_fn(layer, h), lora_idx)
        if post_block_norms:
            attn_out = _rms_norm(attn_out, layer["post_attn_norm"], eps, norm_offset)
        x = x + attn_out
        h = _rms_norm(x, layer["ffn_norm"], eps, norm_offset)
        ffn_out = _ffn(layer, h, lora_idx=lora_idx, **(ffn_kwargs or {}))
        if post_block_norms:
            ffn_out = _rms_norm(ffn_out, layer["post_ffn_norm"], eps, norm_offset)
        return x + ffn_out

    # -- full causal forward (training / no-cache prefill) -------------------

    def apply(params, tokens: jnp.ndarray, positions: Optional[jnp.ndarray] = None,
              lora_idx: Optional[jnp.ndarray] = None):
        """tokens: [B, S] int32 -> logits [B, S, vocab] (causal)."""
        b, s = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        cos, sin = _rope(positions, head_dim, theta, rope_scaling)
        idx = jnp.arange(s)
        masks = _build_masks(
            lambda w: jnp.broadcast_to(
                jnp.where(
                    _visible_w(idx[:, None], idx[None, :], w), 0.0, -jnp.inf
                ).astype(jnp.float32)[None, None],
                (b, 1, s, s),
            )
        )
        x = _embed(params, tokens)

        def layer_body(x, layer):
            def attn(layer_, h):
                q, k, v = _qkv(layer_, h, cos, sin, lora_idx)
                if retention:
                    return _retain_full(q, k, v, _log_gate(layer_, h))
                return _attend(q, k, v, _layer_mask(layer_, masks))

            return _block(layer, x, attn, lora_idx)

        if scan_layers:
            x, _ = jax.lax.scan(
                lambda x, layer: (layer_body(x, layer), None), x, params["layers"]
            )
        else:
            for layer in params["layers"]:
                x = layer_body(x, layer)
        return _logits(params, x)

    # -- dense KV cache serving path -----------------------------------------

    # int8 KV cache (cfg kv_quant="int8"): K/V store as int8 with a per
    # (token, head) f32 scale — cache HBM roughly halves, which is what buys
    # the larger decode batches on a 16 GB chip (weights int8 + bf16 KV at
    # b=32/s=1024 for an 8B model would not fit). Dequant happens next to the
    # attention matmul (XLA fuses it into the HBM read).
    kv_quant = str(cfg.get("kv_quant") or "")
    if kv_quant not in ("", "int8"):
        raise ValueError("kv_quant must be 'int8' (got {!r})".format(kv_quant))

    def _kv_store(x):
        """bf16 [..., D] -> (stored, scale|None): per-vector symmetric int8."""
        if not kv_quant:
            return x.astype(dtype), None
        x32 = x.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(x32), axis=-1)
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        q = jnp.clip(
            jnp.round(x32 / scale[..., None]), -127, 127
        ).astype(jnp.int8)
        return q, scale.astype(jnp.float32)

    def _kv_load(stored, scale):
        if scale is None:
            return stored
        return (stored.astype(jnp.float32) * scale[..., None]).astype(dtype)

    def init_cache(batch: int, max_len: int) -> Dict[str, jnp.ndarray]:
        shape = (n_layers, batch, max_len, n_kv, head_dim)
        out = {
            "k": jnp.zeros(shape, jnp.int8 if kv_quant else dtype),
            "v": jnp.zeros(shape, jnp.int8 if kv_quant else dtype),
            "length": jnp.zeros((batch,), jnp.int32),
        }
        if kv_quant:
            out["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
            out["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        return out

    def _prefill_impl(params, tokens, seq_lens, cache, attend_fn, lora_idx=None):
        """Shared prefill body: embed -> layers (attend_fn pluggable) ->
        last-token logits + freshly written cache. Only the LAST position's
        hidden state is projected to vocab — materializing [B, S, vocab] to
        keep one row would make throwaway logits the memory ceiling exactly
        on the long-S ring path."""
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        cos, sin = _rope(positions, head_dim, theta, rope_scaling)
        ffn_valid = positions < seq_lens[:, None]  # pads never route (MoE)
        x = _embed(params, tokens)

        def layer_body(x, layer):
            stash = []

            def attn(layer_, h):
                q, k, v = _qkv(layer_, h, cos, sin, lora_idx)
                stash.append((k, v))
                return attend_fn(layer_, q, k, v)

            x = _block(layer, x, attn, lora_idx, ffn_kwargs={"valid": ffn_valid})
            return x, stash[0]

        if scan_layers:
            x, (k_stack, v_stack) = jax.lax.scan(layer_body, x, params["layers"])
        else:
            new_k, new_v = [], []
            for layer in params["layers"]:
                x, (k, v) = layer_body(x, layer)
                new_k.append(k)
                new_v.append(v)
            k_stack = jnp.stack(new_k)                             # [L,B,S,Hkv,D]
            v_stack = jnp.stack(new_v)
        last_x = jnp.take_along_axis(
            x, (seq_lens - 1)[:, None, None].clip(0), axis=1
        )                                                          # [B, 1, D]
        last = _logits(params, last_x)[:, 0]                       # [B, vocab]
        max_len = cache["k"].shape[2]
        pad = max_len - s
        pad5 = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
        k_q, k_s = _kv_store(k_stack)
        v_q, v_s = _kv_store(v_stack)
        cache = {
            "k": jnp.pad(k_q, pad5),
            "v": jnp.pad(v_q, pad5),
            "length": seq_lens.astype(jnp.int32),
        }
        if kv_quant:
            cache["k_scale"] = jnp.pad(k_s, pad5[:-1])
            cache["v_scale"] = jnp.pad(v_s, pad5[:-1])
        return last, cache

    def prefill(params, tokens: jnp.ndarray, seq_lens: jnp.ndarray, cache,
                lora_idx: Optional[jnp.ndarray] = None):
        """Right-padded tokens [B, S]; seq_lens [B]. Writes the cache and
        returns (last-token logits [B, vocab], cache)."""
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        valid = positions < seq_lens[:, None]                      # [B, S]
        idx = jnp.arange(s)

        def build(w):
            causal = _visible_w(idx[:, None], idx[None, :], w)
            mask_b = causal[None] & valid[:, None, :]              # [B, S, T]
            return jnp.where(mask_b, 0.0, -jnp.inf).astype(jnp.float32)[:, None]

        masks = _build_masks(build)

        def attend(layer, q, k, v):
            return _attend(q, k, v, _layer_mask(layer, masks))

        return _prefill_impl(params, tokens, seq_lens, cache, attend, lora_idx)

    def _cached_chunk_layers(params, tokens, start, cache, ffn_kwargs,
                             lora_idx=None):
        """Shared layer loop for multi-token cached processing (chunked
        prefill AND speculative verification): embed ``tokens`` [B, C] at
        absolute positions ``start``..``start+C``, write their K/V into the
        cache at those positions (per-sequence dynamic_update_slice), attend
        causally over the whole sequence (cache beyond the chunk end is
        stale -> masked), and return (x [B,C,D], {"k","v"[,scales]})."""
        b, c = tokens.shape
        max_len = cache["k"].shape[2]
        positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None]  # [B, C]
        cos, sin = _rope(positions, head_dim, theta, rope_scaling)
        x = _embed(params, tokens)
        t_idx = jnp.arange(max_len, dtype=jnp.int32)
        masks = _build_masks(
            lambda w: jnp.where(
                _visible_w(positions[:, :, None], t_idx[None, None, :], w),
                0.0,
                -jnp.inf,
            ).astype(jnp.float32)[:, None]                         # [B,1,C,T]
        )

        def _write_chunk(buf, values, width):
            """Per-sequence dynamic_update_slice of a [B, C, ...] chunk into
            a [B, T, ...] buffer at each row's start position."""
            zeros = (0,) * width
            return jax.vmap(
                lambda b_, v_, p: jax.lax.dynamic_update_slice(
                    b_, v_, (p,) + zeros
                )
            )(buf, values.astype(buf.dtype), start)

        def layer_body(carry, layer_and_kv):
            x = carry
            if kv_quant:
                layer, k_cache, v_cache, k_sc, v_sc = layer_and_kv
            else:
                layer, k_cache, v_cache = layer_and_kv
                k_sc = v_sc = None
            stash = []

            def attn(layer_, h):
                q, k, v = _qkv(layer_, h, cos, sin, lora_idx)
                k_q, k_s = _kv_store(k)
                v_q, v_s = _kv_store(v)
                k_c = _write_chunk(k_cache, k_q, 2)
                v_c = _write_chunk(v_cache, v_q, 2)
                if kv_quant:
                    k_s_c = _write_chunk(k_sc, k_s, 1)
                    v_s_c = _write_chunk(v_sc, v_s, 1)
                    stash.append((k_c, v_c, k_s_c, v_s_c))
                    k_full = _kv_load(k_c, k_s_c)
                    v_full = _kv_load(v_c, v_s_c)
                else:
                    stash.append((k_c, v_c))
                    k_full, v_full = k_c, v_c
                return _attend(q, k_full, v_full, _layer_mask(layer_, masks))

            x = _block(layer, x, attn, lora_idx, ffn_kwargs=ffn_kwargs)
            return x, stash[0]

        if kv_quant:
            xs = (params["layers"], cache["k"], cache["v"],
                  cache["k_scale"], cache["v_scale"])
        else:
            xs = (params["layers"], cache["k"], cache["v"])
        if scan_layers:
            x, new_bufs = jax.lax.scan(lambda x, t: layer_body(x, t), x, xs)
        else:
            per_layer = []
            for i, layer in enumerate(params["layers"]):
                tup = tuple(a[i] for a in xs[1:])
                x, bufs = layer_body(x, (layer,) + tup)
                per_layer.append(bufs)
            new_bufs = tuple(
                jnp.stack([bufs[j] for bufs in per_layer])
                for j in range(len(per_layer[0]))
            )
        out = {"k": new_bufs[0], "v": new_bufs[1]}
        if kv_quant:
            out["k_scale"] = new_bufs[2]
            out["v_scale"] = new_bufs[3]
        return x, out

    def prefill_chunk(params, tokens: jnp.ndarray, start: jnp.ndarray,
                      last_rel: jnp.ndarray, cache, *, with_logits: bool = True,
                      lora_idx: Optional[jnp.ndarray] = None):
        """Incremental (chunked) prefill: process ``tokens`` [B, C] at
        absolute positions ``start``..``start+C``, attending over everything
        already in ``cache`` plus the chunk itself (causal). Returns logits
        at relative index ``last_rel`` (the prompt's final real token in the
        — possibly right-padded — last chunk; [B, vocab]) and the extended
        cache. Pad positions write masked-out K/V exactly like plain
        prefill's bucket padding.

        Bounding each prefill dispatch to C tokens lets decode chunks
        interleave on the device stream between prompt segments — a full-
        prompt prefill would occupy the queue for the whole prompt (the
        chunked-prefill TTFT/TPOT smoothing from the serving literature).
        """
        b, c = tokens.shape
        ffn_valid = (
            jnp.arange(c, dtype=jnp.int32)[None] <= last_rel[:, None]
        )  # pad tail of the final chunk never routes (MoE)
        x, new_kv = _cached_chunk_layers(
            params, tokens, start, cache, ffn_kwargs={"valid": ffn_valid},
            lora_idx=lora_idx,
        )
        if with_logits:
            last_x = jnp.take_along_axis(
                x, last_rel[:, None, None].clip(0, c - 1), axis=1
            )                                                              # [B,1,D]
            last = _logits(params, last_x)[:, 0]                           # [B, vocab]
        else:
            # non-final chunks: skip final-norm + lm_head — for an 8B model
            # that matmul reads the whole vocab projection from HBM just to
            # be discarded
            last = jnp.zeros((b, 1), jnp.float32)
        cache = dict(
            new_kv,
            length=jnp.maximum(
                cache["length"], start + last_rel + 1
            ).astype(jnp.int32),
        )
        return last, cache

    def prefill_pipeline(params, tokens: jnp.ndarray, seq_lens: jnp.ndarray,
                         cache, *, stages: int, chunk: int):
        """Pipeline-parallel chunked prefill over the mesh's ``pp`` axis.

        TRUE pipeline parallelism (a GPipe-style inference schedule), not
        just weight-stack sharding: the scan-stacked layers reshape to
        [stages, L/stages] slabs (the pp-sharded layer axis splits
        contiguously, so each pp device group holds exactly one slab), the
        prompt splits into sequence chunks (the microbatches), and chunks
        flow through stages — at tick t stage s processes chunk t-s, so
        after the S-tick fill every pp group computes concurrently instead
        of idling while other groups' layers run. Activations hop stages
        through a shifted [stages, ...] buffer; XLA lowers the shift across
        the pp-sharded axis to a collective-permute on ICI. Causality makes
        sequence chunks valid microbatches: chunk c attends over its
        stage's cache slab holding chunks 0..c, which necessarily passed
        through that stage on earlier ticks.

        Scope (callers fall back to prefill_chunk): scan_layers stacked
        weights, dense KV (no kv_quant), dense FFN (no MoE), no LoRA.
        Reference parity: vLLM serves pipeline-parallel over NCCL P2P
        (--pipeline-parallel-size); this is the GSPMD equivalent.
        """
        if not scan_layers:
            raise ValueError("prefill_pipeline requires scan_layers")
        if kv_quant:
            raise ValueError("prefill_pipeline does not support kv_quant")
        if n_experts:
            raise ValueError("prefill_pipeline does not support MoE")
        if n_layers % stages:
            raise ValueError(
                "stages {} must divide n_layers {}".format(stages, n_layers)
            )
        b, s = tokens.shape
        if s % chunk:
            raise ValueError("padded length {} not a multiple of chunk {}".format(s, chunk))
        m = s // chunk
        lps = n_layers // stages
        layers_st = jax.tree.map(
            lambda a: a.reshape((stages, lps) + a.shape[1:]), params["layers"]
        )
        max_len = cache["k"].shape[2]
        kc = cache["k"].reshape(stages, lps, b, max_len, n_kv, head_dim)
        vc = cache["v"].reshape(stages, lps, b, max_len, n_kv, head_dim)
        emb_all = _embed(params, tokens)                        # [b, s, d]
        dim_model = emb_all.shape[-1]
        x_buf = jnp.zeros((stages, b, chunk, dim_model), emb_all.dtype)
        out = jnp.zeros((b, s, dim_model), emb_all.dtype)
        t_idx = jnp.arange(max_len, dtype=jnp.int32)

        def stage_apply(w_slab, x, kc_s, vc_s, c_idx):
            """One stage's layers over one chunk. c_idx: which chunk this
            stage holds this tick (may be out of range — the caller masks
            the cache commit, so clamped garbage writes are discarded)."""
            start = jnp.clip(c_idx, 0, m - 1) * chunk            # scalar
            rel = jnp.arange(chunk, dtype=jnp.int32)
            positions = jnp.broadcast_to(start + rel, (b, chunk))
            cos, sin = _rope(positions, head_dim, theta, rope_scaling)
            masks = _build_masks(
                lambda w: jnp.where(
                    _visible_w(positions[:, :, None], t_idx[None, None, :], w)
                    & (t_idx[None, None, :] < seq_lens[:, None, None]),
                    0.0,
                    -jnp.inf,
                ).astype(jnp.float32)[:, None]                   # [b,1,C,T]
            )

            def layer_body(x, wkv):
                w_l, k_l, v_l = wkv
                stash = []

                def attn(layer_, h):
                    q, k, v = _qkv(layer_, h, cos, sin, None)
                    k_c = jax.lax.dynamic_update_slice(
                        k_l, k.astype(k_l.dtype), (0, start, 0, 0)
                    )
                    v_c = jax.lax.dynamic_update_slice(
                        v_l, v.astype(v_l.dtype), (0, start, 0, 0)
                    )
                    stash.append((k_c, v_c))
                    return _attend(q, k_c, v_c, _layer_mask(layer_, masks))

                x = _block(w_l, x, attn, None)
                return x, stash[0]

            x, (kc_new, vc_new) = jax.lax.scan(
                layer_body, x, (w_slab, kc_s, vc_s)
            )
            return x, kc_new, vc_new

        def tick(t, carry):
            x_buf, kc, vc, out = carry
            inj = jax.lax.dynamic_slice(
                emb_all,
                (0, jnp.clip(t, 0, m - 1) * chunk, 0),
                (b, chunk, dim_model),
            )
            # stage hop expressed as roll+set rather than concat of slices:
            # concatenate along the pp-SHARDED stage axis has been observed
            # to miscompile on XLA:CPU (wrong values, not just reordering) —
            # roll lowers to a clean collective-permute on every backend
            x_in = jnp.roll(x_buf, 1, axis=0).at[0].set(inj)
            cs = t - jnp.arange(stages, dtype=jnp.int32)         # [stages]
            x_out, kc_new, vc_new = jax.vmap(stage_apply)(
                layers_st, x_in, kc, vc, cs
            )
            valid = (cs >= 0) & (cs < m)
            sel = valid[:, None, None, None, None, None]
            kc = jnp.where(sel, kc_new, kc)
            vc = jnp.where(sel, vc_new, vc)
            # drain: the LAST stage just finished chunk t-(stages-1)
            c_last = t - (stages - 1)
            drained = jax.lax.dynamic_update_slice(
                out,
                x_out[-1].astype(out.dtype),
                (0, jnp.clip(c_last, 0, m - 1) * chunk, 0),
            )
            out = jnp.where((c_last >= 0) & (c_last < m), drained, out)
            return x_out, kc, vc, out

        x_buf, kc, vc, out = jax.lax.fori_loop(
            0, m + stages - 1, lambda t, c: tick(t, c),
            (x_buf, kc, vc, out),
        )
        last_x = jnp.take_along_axis(
            out, (seq_lens - 1)[:, None, None].clip(0, s - 1), axis=1
        )                                                        # [b,1,d]
        last = _logits(params, last_x)[:, 0]
        new_cache = {
            "k": kc.reshape(n_layers, b, max_len, n_kv, head_dim),
            "v": vc.reshape(n_layers, b, max_len, n_kv, head_dim),
            "length": jnp.maximum(cache["length"], seq_lens).astype(jnp.int32),
        }
        return last, new_cache

    def verify(params, tokens: jnp.ndarray, cache,
               lora_idx: Optional[jnp.ndarray] = None):
        """Speculative verification: process ``tokens`` [B, S] (the pending
        token followed by S-1 draft tokens) at absolute positions
        ``length``..``length+S-1``, attending causally over the cache plus
        the chunk itself, and return logits at ALL S positions
        ([B, S, vocab]) plus the cache with the chunk's K/V written.

        ``length`` is deliberately NOT advanced: the caller accepts some
        prefix of the drafts (argmax match) and sets the new length itself —
        K/V written past the accepted point sit beyond ``length``, are
        masked by every later attention, and get overwritten by subsequent
        writes at the same positions. One weight read serves S positions,
        which is the entire speculative-decoding win on an HBM-bound decode
        (and amortizes the per-dispatch host cost the same way the fused
        decode scan does).

        MoE routes DROPLESS here (like decode, unlike batched prefill):
        capacity dropping would make verify's argmax depend on batch
        occupancy and break the token-identical-to-plain-greedy guarantee.
        """
        start = cache["length"]                                    # [B]
        x, new_kv = _cached_chunk_layers(
            params, tokens, start, cache, ffn_kwargs={"dropless": True},
            lora_idx=lora_idx,
        )
        logits = _logits(params, x)                                # [B, S, vocab]
        return logits, dict(new_kv, length=start)

    def prefill_ring(params, tokens: jnp.ndarray, seq_lens: jnp.ndarray, cache,
                     mesh, lora_idx: Optional[jnp.ndarray] = None):
        """Sequence-parallel long-prompt prefill: exact ring attention over
        the mesh's ``sp`` axis (parallel/ring_attention.py shard_map +
        ppermute), so a single prompt's attention spreads across chips and
        context length is bounded by the SLICE's HBM, not one chip's.

        Same contract as :func:`prefill` (right-padded [B, S] tokens, S must
        divide the sp axis). Causal masking inside the ring keeps valid
        tokens from attending right-padding; padded positions' K/V land in
        the cache but sit beyond ``length`` and are masked by decode."""
        from ..parallel.ring_attention import ring_attention

        b, s = tokens.shape

        def attend_sp(layer, q, k, v):
            # GQA: repeat KV heads to query heads for the ring (activation
            # cost only; weights untouched)
            kf = jnp.repeat(k, group, axis=2)
            vf = jnp.repeat(v, group, axis=2)
            out = ring_attention(q, kf, vf, mesh, axis_name="sp", causal=True)
            return out.reshape(b, s, n_heads * head_dim).astype(q.dtype)

        return _prefill_impl(params, tokens, seq_lens, cache, attend_sp, lora_idx)

    def decode(params, tokens: jnp.ndarray, cache,
               lora_idx: Optional[jnp.ndarray] = None):
        """One decode step. tokens: [B] int32. Returns (logits [B, vocab], cache)."""
        b = tokens.shape[0]
        positions = cache["length"][:, None]                       # [B, 1]
        cos, sin = _rope(positions, head_dim, theta, rope_scaling)
        max_len = cache["k"].shape[2]
        t_idx = jnp.arange(max_len, dtype=jnp.int32)[None]         # [1, T]
        masks = _build_masks(
            lambda w: jnp.where(
                _visible_w(cache["length"][:, None], t_idx, w), 0.0, -jnp.inf
            ).astype(jnp.float32)[:, None, None]
        )
        x = _embed(params, tokens)[:, None]                        # [B, 1, dim]
        # Per-sequence scatter at each sequence's own length (overwrite, so
        # stale values from a recycled batch slot cannot leak through).
        write = (t_idx == cache["length"][:, None])[:, :, None, None]  # [B,T,1,1]
        write_s = write[..., 0]                                    # [B,T,1]

        def layer_body(x, xs):
            if kv_quant:
                layer, k_cache_l, v_cache_l, k_sc_l, v_sc_l = xs
            else:
                layer, k_cache_l, v_cache_l = xs
                k_sc_l = v_sc_l = None
            stash = []

            def attn(layer_, h):
                q, k, v = _qkv(layer_, h, cos, sin, lora_idx)      # k,v: [B,1,Hkv,D]
                # cast/quantize to the cache storage: params may be a
                # different precision than the cache
                k_q, k_s = _kv_store(k)
                v_q, v_s = _kv_store(v)
                k_cache = jnp.where(write, k_q.astype(k_cache_l.dtype), k_cache_l)
                v_cache = jnp.where(write, v_q.astype(v_cache_l.dtype), v_cache_l)
                if kv_quant:
                    k_sc = jnp.where(write_s, k_s, k_sc_l)
                    v_sc = jnp.where(write_s, v_s, v_sc_l)
                    stash.append((k_cache, v_cache, k_sc, v_sc))
                    k_full = _kv_load(k_cache, k_sc)
                    v_full = _kv_load(v_cache, v_sc)
                else:
                    stash.append((k_cache, v_cache))
                    k_full, v_full = k_cache, v_cache
                return _attend(q, k_full, v_full, _layer_mask(layer_, masks))

            x = _block(layer, x, attn, lora_idx)
            return x, stash[0]

        if kv_quant:
            xs_all = (params["layers"], cache["k"], cache["v"],
                      cache["k_scale"], cache["v_scale"])
        else:
            xs_all = (params["layers"], cache["k"], cache["v"])
        if scan_layers:
            x, new_bufs = jax.lax.scan(layer_body, x, xs_all)
        else:
            per_layer = []
            for li, layer in enumerate(params["layers"]):
                tup = tuple(a[li] for a in xs_all[1:])
                x, bufs = layer_body(x, (layer,) + tup)
                per_layer.append(bufs)
            new_bufs = tuple(
                jnp.stack([bufs[j] for bufs in per_layer])
                for j in range(len(per_layer[0]))
            )
        logits = _logits(params, x)[:, 0]
        cache = {
            "k": new_bufs[0],
            "v": new_bufs[1],
            "length": cache["length"] + 1,
        }
        if kv_quant:
            cache["k_scale"] = new_bufs[2]
            cache["v_scale"] = new_bufs[3]
        return logits, cache

    # -- paged KV serving path (pools from llm/kv_cache.PagedKVCache) --------
    #
    # The stacked pools [L, Hkv, N, P, D] (+ [L, Hkv, N, P] scale pools
    # under kv_quant) stay ONE buffer for a whole launch. The three paged
    # passes carry them through the loop over the layers next to ``x`` and
    # name a layer by its index: the write goes into the stack at
    # [l, head, page, offset] (in place: the engine donates the pools), and
    # the attention entry points read layer ``l`` of the stack (ops/
    # paged_attention.py). Handing each layer its own [Hkv, N, P, D] slice
    # instead makes XLA copy the whole layer out of the stack and back in
    # every layer of every pass (PERF.md, PR 25).

    def _paged_pools(k_pools, v_pools, k_scales, v_scales, who):
        if not kv_quant:
            return (k_pools, v_pools)
        if k_scales is None:
            raise ValueError("kv_quant {} needs k_scales/v_scales".format(who))
        return (k_pools, v_pools, k_scales, v_scales)

    def _paged_write(pools, li, k, v, write_page, write_offset):
        """The stacked pools with layer ``li``'s new K/V ([*I, Hkv, D], I
        the shape of ``write_page`` / ``write_offset``) stored at
        [li, head, page, offset], through ops.paged_attention's write (the
        page-patching kernel where the attention kernels run, a row scatter
        elsewhere; both in place). Under kv_quant the values quantize
        through the dense path's _kv_store and the per-(token, head) scales
        land at the same (page, offset) of the scale pools — one lifecycle
        per page id."""
        from ..ops.paged_attention import (
            paged_kernel_unsupported_reason,
            paged_kv_write,
            paged_kv_write_xla,
        )

        write = (
            paged_kv_write
            if paged_kernel_unsupported_reason(
                head_dim, pools[0].shape[3], pools[0].dtype
            ) is None
            else paged_kv_write_xla
        )
        with jax.named_scope("kv_write"):
            wp, wo = write_page.reshape(-1), write_offset.reshape(-1)
            k_q, k_s = _kv_store(k)
            v_q, v_s = _kv_store(v)
            new = write(
                pools[0], pools[1], k_q.reshape(-1, n_kv, head_dim),
                v_q.reshape(-1, n_kv, head_dim), wp, wo, layer=li,
            )
            if kv_quant:
                new += paged_kv_write_xla(
                    pools[2], pools[3], k_s.reshape(-1, n_kv),
                    v_s.reshape(-1, n_kv), wp, wo, layer=li,
                )
            return new

    def _paged_layers(params, x, pools, layer_fn):
        """``layer_fn(x, layer, li, pools) -> (x, pools)`` over the layers,
        the pools in the loop's CARRY (never its scanned inputs or outputs);
        the unrolled loop is the same body under a static index."""
        if scan_layers:
            def body(carry, xs):
                return layer_fn(carry[0], xs[0], xs[1], carry[1]), None

            n = pools[0].shape[0]
            (x, pools), _ = jax.lax.scan(
                body, (x, pools),
                (params["layers"], jnp.arange(n, dtype=jnp.int32)),
            )
        else:
            for li, layer in enumerate(params["layers"]):
                x, pools = layer_fn(x, layer, li, pools)
        return x, pools

    def decode_paged(
        params,
        tokens,        # [B] int32
        k_pools,       # [L, Hkv, N, P, D] (int8 under kv_quant)
        v_pools,       # [L, Hkv, N, P, D]
        page_table,    # [B, PP] int32
        lengths,       # [B] int32 tokens present BEFORE this step
        write_page,    # [B] int32 page id for the new token
        write_offset,  # [B] int32 offset within that page
        lora_idx=None,  # [B] int32 adapter index per slot (None = base)
        *,
        k_scales=None,  # [L, Hkv, N, P] f32 scale pools (kv_quant only)
        v_scales=None,
        active=None,    # [B] bool: rows this step advances (None = all)
    ):
        """One decode step over paged KV: writes the new token's K/V into the
        stacked pools (at (layer, page, offset)), then attends via
        ops.paged_attention on that layer of the stack. Returns
        (logits [B, vocab], k_pools, v_pools) —
        plus the updated scale pools when ``kv_quant`` is on: the new
        token's K/V quantize through the dense path's _kv_store and the
        per-(token, head) scales scatter beside the int8 pages; dequant
        happens inside the attention kernel.

        A row that ``active`` masks out attends NOTHING (length 0: the
        kernel spends no DMA and no flash block on it, its attention output
        is zeros) and its logits mean nothing; the caller discards its
        token and points its write at the null page."""
        from ..ops.paged_attention import (
            paged_attention,
            paged_attention_xla,
            paged_kernel_unsupported_reason,
        )

        pools = _paged_pools(k_pools, v_pools, k_scales, v_scales,
                             "decode_paged")
        # kernel or XLA gather: one pure decision over (head_dim, page size,
        # pool dtype, backend) — the engine's health block evaluates the
        # same function with the same arguments
        attend = (
            paged_attention
            if paged_kernel_unsupported_reason(
                head_dim, k_pools.shape[3], k_pools.dtype
            ) is None
            else paged_attention_xla
        )
        b = tokens.shape[0]
        positions = lengths[:, None]                               # [B, 1]
        cos, sin = _rope(positions, head_dim, theta, rope_scaling)
        x = _embed(params, tokens)[:, None]                        # [B, 1, dim]
        # the Pallas kernel scales scores by head_dim**-0.5 internally; a
        # family query_scale override folds into q before the kernel
        q_prescale = query_scale * (head_dim ** 0.5)
        attend_lens = lengths + 1
        if active is not None:
            attend_lens = jnp.where(active, attend_lens, 0)

        def layer_fn(x, layer, li, pools):
            stash = []

            def attn_fn(layer_, h):
                q, k, v = _qkv(layer_, h, cos, sin, lora_idx)      # q [B,1,H,D]
                new = _paged_write(
                    pools, li, k[:, 0], v[:, 0], write_page, write_offset
                )
                stash.append(new)
                q_grouped = q[:, 0].reshape(b, n_kv, group, head_dim)
                if q_prescale != 1.0:
                    q_grouped = q_grouped * jnp.asarray(q_prescale, q_grouped.dtype)
                with jax.named_scope("attn"):
                    attn = attend(
                        q_grouped, new[0], new[1], page_table, attend_lens,
                        layer=li, **dict(zip(("k_scale", "v_scale"), new[2:]))
                    )                                              # [B,Hkv,G,D]
                return attn.reshape(b, 1, n_heads * head_dim).astype(x.dtype)

            x = _block(layer, x, attn_fn, lora_idx)
            return x, stash[0]

        x, pools = _paged_layers(params, x, pools, layer_fn)
        logits = _logits(params, x)[:, 0]
        return (logits,) + pools

    def verify_paged(
        params,
        tokens,        # [B, S] int32: pending token + S-1 drafts
        k_pools,       # [L, Hkv, N, P, D] (int8 under kv_quant)
        v_pools,       # [L, Hkv, N, P, D]
        page_table,    # [B, PP] int32
        lengths,       # [B] int32 tokens present BEFORE this chunk
        lora_idx=None,
        *,
        k_scales=None,  # [L, Hkv, N, P] f32 scale pools (kv_quant only)
        v_scales=None,
    ):
        """Speculative verification over paged KV (vLLM spec-decode on a
        paged cache). Same contract as :func:`verify`: logits at ALL S
        positions, lengths NOT advanced — the caller accepts a draft
        prefix and sets pool lengths itself; K/V written past the accepted
        point sit beyond ``lengths`` and are overwritten by later writes
        at the same positions.

        The chunk's K/V scatter into the pools at coords derived from the
        page table (position p -> table[b, p // P], p % P), so the caller
        only pre-allocates pages; write coordinates stay dynamic, which a
        host-precomputed coord list could not be (accepted counts are a
        device-side value). Attention gathers each sequence's table to a
        dense [cap] run — capacity bandwidth, like the XLA-gather decode
        fallback — and reuses ``_attend`` so query_scale/softcap families
        verify exactly like they decode. Under ``kv_quant`` the chunk's K/V
        quantize before the scatter and the gather dequantizes with the
        scale pools (returned updated, like decode_paged)."""
        from ..ops.paged_attention import gather_pages

        pools = _paged_pools(k_pools, v_pools, k_scales, v_scales,
                             "verify_paged")
        b, s = tokens.shape
        pp = page_table.shape[1]
        page = k_pools.shape[3]
        cap = pp * page
        positions = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        cos, sin = _rope(positions, head_dim, theta, rope_scaling)
        x = _embed(params, tokens)                                 # [B, S, dim]
        wp = jnp.take_along_axis(page_table, positions // page, axis=1)
        wo = positions % page                                      # [B, S]
        # causal bound per query position; table slots past each row's
        # allocation hold page 0 (garbage) but always sit beyond the bound
        t_idx = jnp.arange(cap, dtype=jnp.int32)[None, None]       # [1,1,cap]
        mask = jnp.where(
            t_idx < (positions[:, :, None] + 1), 0.0, -jnp.inf
        ).astype(jnp.float32)[:, None]                             # [B,1,S,cap]

        def layer_fn(x, layer, li, pools):
            stash = []

            def attn_fn(layer_, h):
                q, k, v = _qkv(layer_, h, cos, sin, lora_idx)      # k,v [B,S,Hkv,D]
                new = _paged_write(pools, li, k, v, wp, wo)
                stash.append(new)
                # this layer's pages of every row, [Hkv, B, PP, P(, D)] ->
                # [B, cap, Hkv(, D)] (table order IS sequence-position order)
                kg, vg = (
                    gather_pages(pool, page_table, li)
                    .transpose(1, 2, 3, 0, 4).reshape(b, cap, n_kv, head_dim)
                    for pool in new[:2]
                )
                if kv_quant:
                    # dequant the gathered run with its scale rows ([B, cap,
                    # Hkv]), f32 math like the dense path's _kv_load
                    ksg, vsg = (
                        gather_pages(pool, page_table, li)
                        .transpose(1, 2, 3, 0).reshape(b, cap, n_kv)
                        for pool in new[2:]
                    )
                    kg = kg.astype(jnp.float32) * ksg[..., None]
                    vg = vg.astype(jnp.float32) * vsg[..., None]
                return _attend(q, kg.astype(q.dtype), vg.astype(q.dtype), mask)

            # dropless MoE like verify(): capacity dropping would make the
            # accept chain depend on batch occupancy
            x = _block(layer, x, attn_fn, lora_idx,
                       ffn_kwargs={"dropless": True})
            return x, stash[0]

        x, pools = _paged_layers(params, x, pools, layer_fn)
        return (_logits(params, x),) + pools

    # -- ragged mixed prefill+decode step (docs/ragged_attention.md) ---------

    def _ragged_axes(tok_slot, rows, q_block):
        """The two token axes of a ragged pass. Everything per token (the
        embedding, norms, QKV, RoPE, the pool writes, O, the FFN, the logit
        gathers) runs on the COMPACT axis: C = ``tok_slot.shape[0]`` rows,
        the launch's tokens packed row after row, pads at the end. A
        token-mixing kernel reads a row's tokens by the row's start, which
        ops.ragged_layout aligns to ``q_block``: its operands live in the
        aligned VIEW of ops.ragged_view_tokens(C, rows, q_block) rows, and
        only they. ``tok_slot`` [C] is a compact token's place in the view
        (pads: out of range). Returns (view rows, place, back): ``place``
        lays a [C, ...] operand out in the view (zeros where no token
        lives), ``back`` reads a [view, ...] result at the tokens' places
        (zeros for pads). Both are row gathers; the view's side of the map
        is built once a pass."""
        from ..ops.paged_attention import ragged_view_tokens

        c = tok_slot.shape[0]
        view = ragged_view_tokens(c, rows, q_block)
        slot_tok = jnp.full((view,), c, jnp.int32).at[tok_slot].set(
            jnp.arange(c, dtype=jnp.int32), mode="drop"
        )

        def place(a):
            return a.at[slot_tok].get(mode="fill", fill_value=0)

        def back(a):
            return a.at[tok_slot].get(mode="fill", fill_value=0)

        return view, place, back

    def forward_ragged(
        params,
        tokens,        # [C] int32 the launch's tokens, packed (compact axis)
        tok_pos,       # [C] int32 absolute position of each token in its row
        tok_row,       # [C] int32 owning batch row per token (pads -> 0)
        tok_valid,     # [C] bool real tokens (pads never route in MoE)
        tok_slot,      # [C] int32 each token's place in the kernel's aligned
                       #  view (row_starts[row] + its index; pads >= view)
        row_last,      # [R] int32 compact index of each row's last real token
        k_pools,       # [L, Hkv, N, P, D] (int8 under kv_quant)
        v_pools,
        page_table,    # [R, PP] int32
        kv_lens,       # [R] int32 tokens present AFTER this chunk's writes
        row_starts,    # [R] int32 row map of the VIEW (ops.ragged_layout)
        row_lens,      # [R] int32 query tokens per row (0 = idle row)
        write_page,    # [C] int32 per-token write coords (pads -> null page)
        write_offset,  # [C] int32
        item_rows=None,   # [NI] int32 the kernel's work plan (host-built,
        item_q0=None,     #  ops.ragged_work_items; required with the kernel)
        lora_idx=None,    # [R] int32 adapter index per row (None = base)
        *,
        k_scales=None,  # [L, Hkv, N, P] f32 scale pools (kv_quant only)
        v_scales=None,
        row_logit_idx=None,  # [R, W] int32 compact token indices to read
                             # logits at (None = row_last only)
        tree_anc=None,       # [view, DMAX] int32 per-token ancestor lists
                             # for draft-TREE verify rows, in the VIEW's
                             # order (None = plain causal;
                             # ops.paged_attention.tree_ancestors layout)
    ):
        """ONE forward step over a ragged mixed batch: each row is at an
        arbitrary phase — decode rows contribute one query token (plus
        reserved multi-step pad positions), spec-verify rows a known
        draft chain of q=k+1 candidate tokens, prefill rows a prompt
        chunk — packed into a token-major operand (PAPERS.md "Ragged
        Paged Attention"). Every token embeds at its own absolute
        position, writes its K/V into the stacked paged pools at
        host-precomputed (page, offset) coords — decode_paged's scatter, with
        the chunk's quantized scales beside int8 pages — and attends
        through ops.ragged_paged_attention with per-row causal bounds. The
        dense layers multiply the C packed tokens; only q and the
        attention output pass through the kernel's aligned view
        (:func:`_ragged_axes`), whose row map the kernel takes as before.
        Returns (row logits [R, vocab] at each row's last real token,
        updated pools); when ``row_logit_idx`` [R, W] is given, the
        spec-verify gather ([R, W, vocab] logits at the W requested flat
        positions per row — a draft chain needs logits at EVERY candidate
        position, not just the last) is returned BESIDE the last-token
        logits, whose compute path stays byte-for-byte the default one:
        ((last, gathered), *pools). A decode row's logits are numerically
        the decode path's logits, which is what the engine's
        ragged-vs-two-dispatch byte-identity rests on."""
        from ..ops.paged_attention import (
            _RAGGED_QB,
            paged_kernel_unsupported_reason,
            ragged_paged_attention,
            ragged_paged_attention_xla,
        )

        pools = _paged_pools(k_pools, v_pools, k_scales, v_scales,
                             "forward_ragged")
        # same pure decision as decode_paged; the kernel needs the caller's
        # work plan (item_rows/item_q0) and raises without it
        use_kernel = paged_kernel_unsupported_reason(
            head_dim, k_pools.shape[3], k_pools.dtype
        ) is None
        c = tokens.shape[0]
        # the alignment is the kernel's constant; its XLA twin packs rows
        # densely, so there the view is the compact axis
        view, place, back = _ragged_axes(
            tok_slot, row_starts.shape[0], _RAGGED_QB if use_kernel else 1
        )
        if tree_anc is not None and tree_anc.shape[0] != view:
            raise ValueError(
                "forward_ragged: tree_anc has {} rows, the aligned view "
                "{}".format(tree_anc.shape[0], view)
            )
        positions = tok_pos[:, None]                               # [C, 1]
        cos, sin = _rope(positions, head_dim, theta, rope_scaling)
        x = _embed(params, tokens)[:, None]                        # [C, 1, dim]
        tok_lora = lora_idx[tok_row] if lora_idx is not None else None
        q_prescale = query_scale * (head_dim ** 0.5)

        def layer_fn(x, layer, li, pools):
            stash = []

            def attn_fn(layer_, h):
                q, k, v = _qkv(layer_, h, cos, sin, tok_lora)  # [C,1,H,D]
                new = _paged_write(
                    pools, li, k[:, 0], v[:, 0], write_page, write_offset
                )
                stash.append(new)
                q_flat = q[:, 0].reshape(c, n_heads * head_dim)
                if q_prescale != 1.0:
                    q_flat = q_flat * jnp.asarray(q_prescale, q_flat.dtype)
                kw = dict(zip(("k_scale", "v_scale"), new[2:]),
                          tree_anc=tree_anc, layer=li)
                with jax.named_scope("attn"):
                    q_grouped = place(q_flat).reshape(
                        view, n_kv, group, head_dim
                    )
                    if use_kernel:
                        attn = ragged_paged_attention(
                            q_grouped, new[0], new[1], page_table, kv_lens,
                            row_starts, row_lens,
                            item_rows=item_rows, item_q0=item_q0, **kw,
                        )                                       # [view,Hkv,G,D]
                    else:
                        attn = ragged_paged_attention_xla(
                            q_grouped, new[0], new[1], page_table, kv_lens,
                            row_starts, row_lens, **kw,
                        )
                    attn = back(attn.reshape(view, n_heads * head_dim))
                return attn.reshape(c, 1, n_heads * head_dim).astype(x.dtype)

            # dropless MoE: capacity dropping would make a row's tokens
            # depend on what the OTHER rows put in the launch — the ragged
            # scheduler requires per-row determinism (like verify)
            x = _block(layer, x, attn_fn, tok_lora,
                       ffn_kwargs={"valid": tok_valid[:, None],
                                   "dropless": True})
            return x, stash[0]

        x, pools = _paged_layers(params, x, pools, layer_fn)
        last_x = x[:, 0][row_last][:, None]                    # [R, 1, dim]
        logits = _logits(params, last_x)[:, 0]                 # [R, vocab]
        if row_logit_idx is not None:
            # spec-verify gather: [R, W] flat indices -> [R, W, vocab].
            # W is small (k+1), so the extra lm_head rows cost R*W matvecs,
            # never a T-wide logits materialization. The last-token logits
            # keep their own (unchanged) compute path so every non-verify
            # consumer stays bitwise identical across spec/no-spec launches.
            sel_x = x[:, 0][row_logit_idx]                     # [R, W, dim]
            gathered = _logits(params, sel_x)                  # [R, W, vocab]
            return ((logits, gathered),) + pools
        return (logits,) + pools

    # -- power retention over the state cache (docs/state_cache.md) ----------
    #
    # The second implementer of the engine's cache contract: a sequence owns
    # one SLOT of the stacked float32 pools S [L, slots, Hkv, D, rows] and
    # z [L, slots, Hkv, zrows, D] (slot = batch row), whatever its length.
    # The pools ride the layer loop's carry like the paged K/V stacks
    # (_paged_layers) and both kernels update layer ``li`` of the stack in
    # place (ops/power_retention.py). Everything around the token mixing —
    # embedding, norms, QKV / O / FFN matmuls, logits — is the code the
    # softmax models run (_block, _qkv, _ffn, _logits).

    def init_state(slots: int):
        from ..ops.power_retention import state_shapes

        s_shape, z_shape = state_shapes(n_layers, slots, n_kv, head_dim)
        return (jnp.zeros(s_shape, jnp.float32),
                jnp.zeros(z_shape, jnp.float32))

    def _retention_ops():
        """(update, chunk): the Pallas kernels where they compile, their XLA
        twins elsewhere: one pure decision over (head_dim, backend), which
        the engine's health block evaluates with the same arguments."""
        from ..ops import power_retention as pr

        if pr.retention_kernel_unsupported_reason(head_dim) is None:
            ops = pr.power_retention_update, pr.power_retention_chunk
        else:
            ops = pr.power_retention_update_xla, pr.power_retention_chunk_xla
        if state_round:
            ops = tuple(partial(f, round_state=True) for f in ops)
        return ops

    def decode_state(
        params,
        tokens,        # [B] int32
        s_pool,        # [L, slots, Hkv, D, rows] float32
        z_pool,        # [L, slots, Hkv, zrows, D] float32
        positions,     # [B] int32 absolute position of this token
        active,        # [B] bool rows that really advance
    ):
        """One decode step through the state pools: every ``active`` row's
        slot takes its token (S <- g S + v phi(k)^T) and the row's query
        heads read the new state. A row that is not active (an idle slot, a
        row in prefill, the pad positions of a multi-step window that closed
        early) leaves its slot bit for bit as it was: a recurrent state has
        no length to hide a stray write behind. Returns (logits [B, vocab],
        s_pool, z_pool)."""
        update, _ = _retention_ops()
        b = tokens.shape[0]
        cos, sin = _rope(positions[:, None], head_dim, theta, rope_scaling)
        x = _embed(params, tokens)[:, None]                        # [B, 1, dim]
        no_reset = jnp.zeros((b,), bool)

        def layer_fn(x, layer, li, pools):
            stash = []

            def attn_fn(layer_, h):
                q, k, v = _qkv(layer_, h, cos, sin)                # q [B,1,H,D]
                log_g = _log_gate(layer_, h)[:, 0]                 # [B, Hkv]
                with jax.named_scope("retention"), \
                        jax.named_scope("state_update"):
                    y, s_new, z_new = update(
                        q[:, 0].reshape(b, n_kv, group, head_dim),
                        k[:, 0], v[:, 0], log_g, active, no_reset,
                        pools[0], pools[1], layer=li,
                    )
                stash.append((s_new, z_new))
                return y.reshape(b, 1, n_heads * head_dim).astype(x.dtype)

            x = _block(layer, x, attn_fn, None)
            return x, stash[0]

        x, pools = _paged_layers(params, x, (s_pool, z_pool), layer_fn)
        return (_logits(params, x)[:, 0],) + pools

    def forward_ragged_state(
        params,
        tokens,        # [T] int32 the launch's tokens on the aligned VIEW (below)
        tok_pos,       # [T] int32 absolute position of each token in its row
        tok_row,       # [T] int32 owning batch row per token (pads -> 0)
        tok_valid,     # [T] bool real tokens of THIS pass
        row_last,      # [R] int32 flat index of each row's last real token
        s_pool,        # [L, slots, Hkv, D, rows] float32 (slot = row)
        z_pool,        # [L, slots, Hkv, zrows, D] float32
        row_starts,    # [R] int32 row map of the view (8-aligned starts)
        row_lens,      # [R] int32 tokens of this pass per row (0 = idle)
        row_reset,     # [R] bool the row's slot counts as zero before it
    ):
        """forward_ragged over the state cache: ONE pass over a ragged mixed
        batch in which a decode row brings one token and a prefill row a
        chunk of its prompt. Rows of one token go through the update kernel
        (bandwidth-bound: the slot streams through once), longer rows
        through the chunk kernel (the attention form inside the chunk, the
        read-out of the state before it, one state update); idle rows and
        pad positions touch no slot. A row whose chunk starts its sequence
        says so in ``row_reset``: whatever the slot's last owner left counts
        as zero. Unlike forward_ragged, the whole pass runs on ONE token
        axis, the kernels' aligned view (ops.ragged_layout at
        ops.power_retention.ROW_ALIGN; the engine makes its compact axis
        that view for this cache). Returns (logits [R, vocab] at each row's
        last real token, s_pool, z_pool)."""
        del tok_row, tok_valid       # the row map says the same, per row
        update, chunk = _retention_ops()
        t = tokens.shape[0]
        cos, sin = _rope(tok_pos[:, None], head_dim, theta, rope_scaling)
        x = _embed(params, tokens)[:, None]                        # [T, 1, dim]
        one, many = row_lens == 1, row_lens > 1
        first = jnp.clip(row_starts, 0, t - 1)

        def layer_fn(x, layer, li, pools):
            stash = []

            def attn_fn(layer_, h):
                q, k, v = _qkv(layer_, h, cos, sin)                # [T,1,H,D]
                log_g = _log_gate(layer_, h)[:, 0]                 # [T, Hkv]
                qg = q[:, 0].reshape(t, n_kv, group, head_dim)
                k0, v0 = k[:, 0], v[:, 0]
                with jax.named_scope("retention"):
                    y, s_new, z_new = chunk(
                        qg, k0, v0, log_g, row_starts, row_lens, many,
                        row_reset, pools[0], pools[1], layer=li,
                    )
                    with jax.named_scope("state_update"):
                        y1, s_new, z_new = update(
                            qg[first], k0[first], v0[first], log_g[first],
                            one, row_reset, s_new, z_new, layer=li,
                        )
                    y = y.at[jnp.where(one, first, t)].set(y1, mode="drop")
                stash.append((s_new, z_new))
                return y.reshape(t, 1, n_heads * head_dim).astype(x.dtype)

            x = _block(layer, x, attn_fn, None)
            return x, stash[0]

        x, pools = _paged_layers(params, x, (s_pool, z_pool), layer_fn)
        last_x = x[:, 0][row_last][:, None]                    # [R, 1, dim]
        return (_logits(params, last_x)[:, 0],) + pools

    def prepare_params(params):
        """Adapt a loaded param pytree to this build's layout: under
        scan_layers, a list/tuple of per-layer dicts (e.g. from a checkpoint
        converter) is stacked into the [L, ...] pytree lax.scan consumes.
        When the build enables LoRA, checkpoints that predate it get zero
        adapter stacks backfilled (index 0 = base model)."""
        layers = params.get("layers")
        if scan_layers and isinstance(layers, (list, tuple)):
            params = dict(params)
            params["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
        elif not scan_layers and isinstance(layers, dict) and "wq" in layers:
            params = dict(params)
            params["layers"] = [
                jax.tree.map(lambda x: x[i], layers) for i in range(n_layers)
            ]
        if lora_rank:
            from . import lora as lora_lib

            params = dict(params)
            layers = params["layers"]
            if isinstance(layers, dict):
                if "lora_a_" + lora_targets[0] not in layers:
                    layers = dict(layers)
                    for t in lora_targets:
                        d_in, d_out = lora_lib.target_dims(cfg, t)
                        layers["lora_a_" + t] = jnp.zeros(
                            (n_layers, max_loras + 1, d_in, lora_rank), dtype
                        )
                        layers["lora_b_" + t] = jnp.zeros(
                            (n_layers, max_loras + 1, lora_rank, d_out), dtype
                        )
                    params["layers"] = layers
            else:
                if layers and "lora_a_" + lora_targets[0] not in layers[0]:
                    new_layers = []
                    for layer in layers:
                        layer = dict(layer)
                        for t in lora_targets:
                            d_in, d_out = lora_lib.target_dims(cfg, t)
                            layer["lora_a_" + t] = jnp.zeros(
                                (max_loras + 1, d_in, lora_rank), dtype
                            )
                            layer["lora_b_" + t] = jnp.zeros(
                                (max_loras + 1, lora_rank, d_out), dtype
                            )
                        new_layers.append(layer)
                    params["layers"] = new_layers
        return params

    return SimpleNamespace(
        init=init,
        apply=apply,
        init_cache=init_cache,
        prefill=prefill,
        prefill_chunk=prefill_chunk,
        ffn=_ffn,
        # ring attention masks plain-causally inside the ring with the
        # default head_dim**-0.5 score scale and no soft-capping, so any
        # family that windows, rescales, or softcaps is unsupported on the
        # sp long-prefill path (engine falls back to plain prefill when
        # this is None)
        prefill_ring=(
            None
            if (
                retention
                or sliding_window
                or attn_softcap
                or abs(query_scale - head_dim ** -0.5) > 1e-12
            )
            else prefill_ring
        ),
        decode=decode,
        verify=verify,
        decode_paged=decode_paged,
        verify_paged=verify_paged,
        # ragged mixed prefill+decode step (docs/ragged_attention.md): the
        # engine's token-budget scheduler drives one of these per iteration
        forward_ragged=forward_ragged,
        # power retention (attention="power_retention"): served from the
        # engine's state cache only (engine.cache=state)
        attention=attention,
        init_state=init_state if retention else None,
        decode_state=decode_state if retention else None,
        forward_ragged_state=forward_ragged_state if retention else None,
        # pipeline-parallel prefill: gated to configs whose forward the
        # pipeline stage body reproduces exactly (see prefill_pipeline doc)
        prefill_pipeline=(
            prefill_pipeline
            if (scan_layers and not kv_quant and not n_experts
                and not retention)
            else None
        ),
        prepare_params=prepare_params,
        config=cfg,
        head_dim=head_dim,
        n_kv_heads=n_kv,
        n_heads=n_heads,
        n_layers=n_layers,
        lora_rank=lora_rank,
        max_loras=max_loras,
        # the paged kernel has no score soft-capping; the engine refuses
        # cache=paged for such models (alt_window is covered by the existing
        # sliding_window guard). kv_quant="int8" is supported on BOTH cache
        # backends since the int8 paged pools landed (docs/paged_kv_quant.md).
        paged_unsupported_reason=(
            "attention logit softcapping (Gemma-2) is not supported by the "
            "paged decode kernel; use engine.cache=dense"
            if attn_softcap
            else None
        ),
    )
