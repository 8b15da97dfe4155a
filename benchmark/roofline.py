"""Peaks by device kind, and the operations and bytes a decoder pass needs.

Kept with the benchmark so that no PR that claims a gain can change how a
roofline share is counted. Shapes come from the configuration file
(``model`` block: dim, n_layers, n_heads, n_kv_heads, head_dim, ffn_dim,
vocab_size, n_experts, moe_top_k). What is counted is what the *algorithm*
needs: an expert layer needs its top-k experts per token, whatever the
program computes (today it runs every expert for every token).
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``. A device that is not in
    peaks.json is an error, never a default."""
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            "device kind {!r} is not in benchmark/peaks.json: add its "
            "published peaks with their source".format(device_kind)
        )
    return table[device_kind]


def _dims(model: dict):
    head_dim = int(model.get("head_dim") or model["dim"] // model["n_heads"])
    experts = int(model.get("n_experts") or 0)
    return (int(model["dim"]), int(model["n_layers"]), int(model["n_heads"]),
            int(model["n_kv_heads"]), head_dim, int(model["ffn_dim"]),
            int(model["vocab_size"]), experts, int(model.get("moe_top_k", 2)))


def layer_matmul_params(model: dict, active: bool) -> int:
    """Weights of one layer's matrix multiplications: all of them, or with
    ``active`` those one token needs (top-k experts, and the router)."""
    d, _, h, kv, hd, f, _, e, k = _dims(model)
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    if e > 1:
        return attn + d * e + 3 * d * f * (k if active else e)
    return attn + 3 * d * f


def weight_bytes(model: dict, bytes_per_weight: float = 1.0) -> float:
    """Bytes of all matmul weights as served (int8: one byte each; the
    per-channel scales and norms are under 0.1% and left out), lm_head in,
    embedding out (a pass reads rows of it, not the table)."""
    d, n_layers, *_ = _dims(model)
    vocab = int(model["vocab_size"])
    return bytes_per_weight * (
        n_layers * layer_matmul_params(model, active=False) + d * vocab
    )


def kv_bytes_per_token(model: dict, bytes_per_value: float = 2.0) -> float:
    """K and V of one token over all layers (bf16 pool: 2 bytes a value)."""
    _, n_layers, _, kv, hd, *_ = _dims(model)
    return 2 * n_layers * kv * hd * bytes_per_value


def pass_flops(model: dict, new_tokens: int, logit_rows: int,
               attended_tokens: int) -> float:
    """Operations one forward pass needs: 2 per weight per new token in the
    layers (active experts only), the lm_head for the rows whose logits are
    read, and attention's QK^T and PV: 4 * head_dim per (query head, attended
    token) — ``attended_tokens`` is the sum over new tokens of the context
    each attends to."""
    d, n_layers, h, _, hd, _, vocab, *_ = _dims(model)
    return (
        2.0 * n_layers * layer_matmul_params(model, active=True) * new_tokens
        + 2.0 * d * vocab * logit_rows
        + 4.0 * n_layers * h * hd * attended_tokens
    )


def pass_bytes(model: dict, kv_tokens_read: int, new_tokens: int,
               bytes_per_weight: float = 1.0) -> float:
    """Bytes one forward pass has to move: every weight once, the K and V of
    every context token its rows attend to once, the new tokens' K and V
    written once."""
    return (
        weight_bytes(model, bytes_per_weight)
        + kv_bytes_per_token(model) * (kv_tokens_read + new_tokens)
    )


def min_seconds(flops: float, nbytes: float, peaks: dict) -> dict:
    """The least time the chip could take, and which bound sets it. The
    weights are int8 at rest and multiplied in bf16, so the bf16 peak is the
    compute bound."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
