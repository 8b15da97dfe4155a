"""Operations and bytes of a decoder that mixes sliding-window and full
attention layers over per-head K/V pages and routes its feed-forward
(``arch: afmoe``; reference/trinity_mini.py gives the equations), counted as
roofline.py counts a K/V decoder: what the ALGORITHM needs, whatever the
program computes. Shapes come from the configuration's ``model`` block
(sut.model_block); peaks and ``min_seconds`` are roofline.py's.

Bytes. The weights of the layers once a pass (int8: one byte each): every
layer's five attention projections (W^Q, W^K, W^V, the gate W^G, W^O), the
dense layers' feed-forward, an expert layer's router and shared expert, the
head; and a routed expert's three matrices only where a HELD expert received
a token (the program's counter ``moe.experts_hit`` sums them over the expert
layers of every pass). K/V rows (bfloat16): a key a layer's kernel has to
read is its K and its V row of every kv head, once (the program's ``window``
counters give the keys by layer kind, layers counted: all visible keys on a
full layer, at most the window on a sliding one).

Operations. Two per weight per token in what a token passes through (the
routed experts by ``moe.local_assignments``: top-k a token), the head for
the rows whose logits are read, and attention's QK^T and PV: 4 x head_dim a
query head per (query, visible key) pair.
"""

from __future__ import annotations

FULL, WINDOW = "full_attention", "sliding_attention"


def layer_counts(model: dict) -> dict:
    types = list(model["layer_types"])
    n_dense = min(int(model.get("num_dense_layers", 2)), len(types))
    return {"full": types.count(FULL), "window": types.count(WINDOW),
            "dense": n_dense, "moe": len(types) - n_dense}


def _head_dim(model: dict) -> int:
    return int(model.get("head_dim") or model["dim"] // model["n_heads"])


def attention_params(model: dict) -> int:
    """Matmul weights of one layer's attention: W^Q, the gate W^G and W^O
    (dim x heads x head_dim each), W^K and W^V (dim x kv heads x head_dim)."""
    d, hd = int(model["dim"]), _head_dim(model)
    return d * hd * (3 * int(model["n_heads"]) + 2 * int(model["n_kv_heads"]))


def expert_params(model: dict) -> int:
    return 3 * int(model["dim"]) * int(model["moe_intermediate_size"])


def fixed_params(model: dict) -> int:
    """Weights every pass reads whatever the router chose: attention of every
    layer, the dense feed-forwards, routers and shared experts, the head."""
    d, c = int(model["dim"]), layer_counts(model)
    shared = int(model.get("n_shared_experts", 1)) * expert_params(model)
    return ((c["full"] + c["window"]) * attention_params(model)
            + c["dense"] * 3 * d * int(model["ffn_dim"])
            + c["moe"] * (d * int(model["router_experts"]) + shared)
            + d * int(model["vocab_size"]))


def key_bytes(model: dict, bytes_per_value: float = 2.0) -> float:
    """One key of one layer as the kernels read it: its K and V rows of
    every kv head."""
    return 2 * int(model["n_kv_heads"]) * _head_dim(model) * bytes_per_value


def attention_flops(model: dict, pairs: float) -> float:
    """(query, visible key) pairs, layers counted -> QK^T and PV."""
    return 4.0 * int(model["n_heads"]) * _head_dim(model) * pairs


def pass_bytes(model: dict, passes: float, experts_hit: float, keys: float,
               tokens: float) -> float:
    """Bytes the passes of a stretch must move: the fixed weights once a
    pass, a routed expert once where it was hit, the keys read (layers
    counted), and the new tokens' K/V rows written in every layer."""
    c = layer_counts(model)
    written = tokens * (c["full"] + c["window"]) * key_bytes(model)
    return (passes * fixed_params(model) + experts_hit * expert_params(model)
            + keys * key_bytes(model) + written)


def pass_flops(model: dict, tokens: float, logit_rows: float,
               local_assignments: float, pairs: float) -> float:
    d = int(model["dim"])
    per_token = fixed_params(model) - d * int(model["vocab_size"])
    return (2.0 * per_token * tokens
            + 2.0 * d * int(model["vocab_size"]) * logit_rows
            + 2.0 * expert_params(model) * local_assignments
            + attention_flops(model, pairs))
