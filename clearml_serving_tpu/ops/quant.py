"""Weight quantization for HBM-constrained serving.

An 8B-param model in bf16 (16 GB) does not fit one v5e chip's HBM next to a KV
cache — int8 weights (8 GB) do. Symmetric per-output-channel int8 with an f32
scale; dequantization happens in VMEM fused into the matmul by XLA, so HBM
traffic (the decode bottleneck) halves.

int4 halves it again (8B -> ~4 GB): symmetric **group-quantized** 4-bit
(AWQ/GPTQ-style w4a16 — per-(128-input-row group, output channel) scales
recover most of the quality a single per-channel scale loses at 4 bits), two
nibbles packed per uint8 byte so the HBM win is real on every backend rather
than depending on XLA s4 packing. Unpack (mask/shift) + dequant fuse into the
consumer matmul's operand pipeline exactly like the int8 path.

Leaf formats (pytree leaves produced by quantize_llama_params):
    int8: {"_q8": int8 [..., K, N],     "_scale":  f32 [..., 1, N]}
    int4: {"_q4": uint8 [..., K//2, N], "_scale4": f32 [..., K//g, N]}
_scale4 has the same rank as the weight (groups axis in the K slot), so TP
sharding specs transfer unchanged (parallel/sharding.py).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp


def quantize_int8(w: jnp.ndarray, axis: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """w (float) -> (w_int8, scale_f32). `axis` is the reduction (input) axis;
    scales are per-output-channel."""
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=axis, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def int8_matmul(x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """x [..., K] @ dequant(q [K, N]) — dequant fuses into the matmul."""
    return (x @ dequantize(q, scale, x.dtype)).astype(x.dtype)


INT4_GROUP = 128  # input rows per scale group (AWQ/GPTQ convention)


def int4_groups(k: int, group: int = INT4_GROUP) -> int:
    """Number of scale groups for a K-row input dim: K//group, falling back
    to one per-channel group when K doesn't divide (the single source of
    truth for the fallback rule — quantize_int4 and random tree builders
    must agree or benchmark trees diverge from real-checkpoint trees)."""
    return k // group if group and k % group == 0 else 1


def quantize_int4(
    w: jnp.ndarray, axis: int = -2, group: int = INT4_GROUP
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """w float [..., K, N] -> (packed uint8 [..., K//2, N], scale f32
    [..., K//group, N]). Symmetric signed 4-bit in [-8, 7], stored as
    unsigned nibbles (q+8); rows 2i/2i+1 pack into byte i's low/high nibble.
    K not divisible by ``group`` falls back to one group (per-channel)."""
    if axis not in (-2, w.ndim - 2):
        raise ValueError("int4 quantization packs along axis -2")
    k, n = w.shape[-2], w.shape[-1]
    if k % 2:
        raise ValueError("int4 packing needs an even input dim, got {}".format(k))
    g = k // int4_groups(k, group)
    w32 = w.astype(jnp.float32)
    shaped = w32.reshape(*w.shape[:-2], k // g, g, n)
    absmax = jnp.max(jnp.abs(shaped), axis=-2, keepdims=True)   # [.., K//g, 1, N]
    scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
    q = jnp.clip(jnp.round(shaped / scale), -8, 7)
    u = (q + 8).astype(jnp.uint8).reshape(*w.shape[:-2], k, n)
    packed = u[..., 0::2, :] | (u[..., 1::2, :] << 4)           # [.., K//2, N]
    return packed, jnp.squeeze(scale, -2).astype(jnp.float32)


def dequantize_int4(
    packed: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.bfloat16
) -> jnp.ndarray:
    """Inverse of quantize_int4 (run INSIDE jit: XLA fuses unpack + scale
    into the consumer matmul, weights at rest stay 4-bit in HBM)."""
    k2, n = packed.shape[-2], packed.shape[-1]
    lo = (packed & 0xF).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    q = jnp.stack([lo, hi], axis=-2)                            # [.., K//2, 2, N]
    qf = q.reshape(*packed.shape[:-2], k2 * 2, n).astype(jnp.float32) - 8.0
    ng = scale.shape[-2]
    g = (k2 * 2) // ng
    shaped = qf.reshape(*qf.shape[:-2], ng, g, n) * scale[..., :, None, :]
    return shaped.reshape(qf.shape).astype(dtype)


def detect_weight_quant(params: Any) -> str:
    """"int4"/"int8" when the pytree already holds packed quantized leaves
    (e.g. a bundle written by scripts/quantize_ckpt.py), else "". Lets the
    engine pick the quantized TP sharding specs and report the right
    weight_quant without re-deriving it from config."""
    if isinstance(params, dict):
        if "_q4" in params:
            return "int4"
        if "_q8" in params:
            return "int8"
        for value in params.values():
            found = detect_weight_quant(value)
            if found:
                return found
        return ""
    if isinstance(params, (list, tuple)):
        for value in params:
            found = detect_weight_quant(value)
            if found:
                return found
    return ""


def quantize_llama_params(
    params: Dict[str, Any], bits: int = 8, group: int = INT4_GROUP
) -> Dict[str, Any]:
    """Quantize every projection matrix of a llama param pytree to int8 (or
    group-int4 with ``bits=4``); norms/embeddings stay bf16. Serve by calling
    `dequant_llama_params` INSIDE the jitted step function (see
    llm/engine.py) — XLA then fuses each dequant next to its consumer matmul
    and frees the bf16 buffer after use, so weights at rest stay quantized.
    Calling dequant eagerly (outside jit) materializes a full bf16 copy and
    defeats the purpose."""
    if bits not in (4, 8):
        raise ValueError("bits must be 4 or 8, got {}".format(bits))
    quant_keys = {
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
        # MoE expert stacks [E, in, out] quantize the same way (axis=-2 is
        # still the reduction dim); the small router stays full precision
        "w_gate_e", "w_up_e", "w_down_e",
    }

    def _q(tree):
        if isinstance(tree, dict):
            out = {}
            for key, value in tree.items():
                if key in quant_keys:
                    # axis=-2 is the input (reduction) dim for both plain
                    # [in, out] matrices and scan_layers-stacked [L, in, out]
                    if bits == 4:
                        qv, s = quantize_int4(value, axis=-2, group=group)
                        out[key] = {"_q4": qv, "_scale4": s}
                    else:
                        qv, s = quantize_int8(value, axis=-2)
                        out[key] = {"_q8": qv, "_scale": s}
                else:
                    out[key] = _q(value)
            return out
        if isinstance(tree, list):
            return [_q(v) for v in tree]
        return tree

    return _q(params)


def random_quantized_llama(config: dict, seed: int = 0, bits: int = 8):
    """(bundle, params) for a scan-layers build with the int8/int4 tree
    generated DIRECTLY (``bundle.init(..., weight_quant=...)``) —
    full-precision weights are never materialized, so an 8B model
    initializes inside a single chip's HBM. For benchmarks; real
    checkpoints go through quantize_llama_params instead."""
    import jax

    from ..models import llama

    if bits not in (4, 8):
        raise ValueError("bits must be 4 or 8, got {}".format(bits))
    bundle = llama.build(dict(config, scan_layers=True))
    params = bundle.init(
        jax.random.PRNGKey(seed), weight_quant="int4" if bits == 4 else "int8"
    )
    return bundle, params


def dequant_llama_params(params: Dict[str, Any], dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Inverse transform (inside jit: XLA fuses dequant into consumers)."""

    def _dq(tree):
        if isinstance(tree, dict):
            if "_q8" in tree:
                return dequantize(tree["_q8"], tree["_scale"], dtype)
            if "_q4" in tree:
                return dequantize_int4(tree["_q4"], tree["_scale4"], dtype)
            return {k: _dq(v) for k, v in tree.items()}
        if isinstance(tree, list):
            return [_dq(v) for v in tree]
        return tree

    return _dq(params)
