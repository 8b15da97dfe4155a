"""Operations and bytes of a decoder whose layers attend through a LATENT
(``arch: dots3_note``; reference/dots3_note.py gives the equations), counted
as roofline.py counts a K/V decoder: what the ALGORITHM needs, whatever the
program computes. Shapes come from the configuration's ``model`` block
(sut.model_block); peaks and ``min_seconds`` are roofline.py's.

Bytes. The weights of the layers once a pass (int8: one byte each): every
layer's attention projections, the dense layers' feed-forward, an expert
layer's router and shared expert, the head; and a routed expert's three
matrices only where a HELD expert received a token (the program's counter
``moe.experts_hit`` sums them over the expert layers of every pass). Cache
rows (bfloat16, the values a row holds, not the padded tile): a full layer's
row reads the index key of every visible token and the latent row of every
selected one; a window layer's row the latent rows inside its window.

Operations. Two per weight per token in what a token passes through (the
held experts by ``moe.local_assignments``), the head for the rows whose
logits are read, the indexer's J x D products per (token, visible key), and
attention in the expanded form's count, 2 (d_n + d_r + d_v) a head per
(query, visible key): the absorbed form the program runs costs more, and the
least is what a roofline is read against.
"""

from __future__ import annotations

FULL, WINDOW = "full_attention", "sliding_attention"


def _kind(model: dict, attn: str) -> dict:
    pre = "" if attn == FULL else "swa_"
    return dict(
        heads=int(model["n_heads"] if attn == FULL
                  else model["swa_num_attention_heads"]),
        q_rank=int(model[pre + "q_lora_rank"]),
        d_c=int(model[pre + "kv_lora_rank"]),
        d_n=int(model[pre + "qk_nope_head_dim"]),
        d_r=int(model[pre + "qk_rope_head_dim"]),
        d_v=int(model[pre + "v_head_dim"]),
    )


def layer_counts(model: dict) -> dict:
    types = list(model["layer_types"])
    n_dense = int(model.get("first_k_dense_replace", 1))
    return {"full": types.count(FULL), "window": types.count(WINDOW),
            "dense": min(n_dense, len(types)),
            "moe": max(0, len(types) - n_dense)}


def attention_params(model: dict, attn: str) -> int:
    """Matmul weights of one layer's attention: the two low-rank pairs, the
    output projection, the headwise gate, and a full layer's indexer."""
    d, k = int(model["dim"]), _kind(model, attn)
    n = (d * k["q_rank"] + k["q_rank"] * k["heads"] * (k["d_n"] + k["d_r"])
         + d * (k["d_c"] + k["d_r"])
         + k["d_c"] * k["heads"] * (k["d_n"] + k["d_v"])
         + k["heads"] * k["d_v"] * d + d * k["heads"])
    if attn == FULL:
        j, di = int(model["index_n_heads"]), int(model["index_head_dim"])
        n += k["q_rank"] * j * di + d * di + d * j
    return n


def expert_params(model: dict) -> int:
    return 3 * int(model["dim"]) * int(model["moe_intermediate_size"])


def fixed_params(model: dict) -> int:
    """Weights every pass reads whatever the router chose: attention of every
    layer, the dense feed-forwards, routers and shared experts, the head."""
    d, c = int(model["dim"]), layer_counts(model)
    shared = int(model.get("n_shared_experts", 1)) * expert_params(model)
    return (c["full"] * attention_params(model, FULL)
            + c["window"] * attention_params(model, WINDOW)
            + c["dense"] * 3 * d * int(model["ffn_dim"])
            + c["moe"] * (d * int(model["router_experts"]) + shared)
            + d * int(model["vocab_size"]))


def row_bytes(model: dict, attn: str, bytes_per_value: float = 2.0) -> float:
    """A cached latent row as the algorithm reads it: c and k^R."""
    k = _kind(model, attn)
    return (k["d_c"] + k["d_r"]) * bytes_per_value


def index_key_bytes(model: dict, bytes_per_value: float = 2.0) -> float:
    return int(model["index_head_dim"]) * bytes_per_value


def cache_bytes(model: dict, keys_scored: float, keys_full: float,
                keys_window: float) -> float:
    """Index keys scored, latent rows read on full layers and on window
    layers (each summed over the layers of its kind) -> bytes."""
    return (keys_scored * index_key_bytes(model)
            + keys_full * row_bytes(model, FULL)
            + keys_window * row_bytes(model, WINDOW))


def attention_flops(model: dict, pairs_full: float, pairs_window: float) -> float:
    """(query, visible key) pairs, summed over the layers of a kind -> the
    expanded form's operations."""
    out = 0.0
    for attn, pairs in ((FULL, pairs_full), (WINDOW, pairs_window)):
        k = _kind(model, attn)
        out += 2.0 * k["heads"] * (k["d_n"] + k["d_r"] + k["d_v"]) * pairs
    return out


def index_flops(model: dict, keys_scored: float) -> float:
    return 2.0 * int(model["index_n_heads"]) * int(model["index_head_dim"]) \
        * keys_scored


def pass_bytes(model: dict, passes: float, experts_hit: float,
               keys_scored: float, keys_full: float, keys_window: float,
               tokens: float) -> float:
    """Bytes the passes of a stretch must move: the fixed weights once a
    pass, a routed expert once where it was hit, the cache rows read, and
    the new tokens' rows written."""
    c = layer_counts(model)
    written = tokens * (
        c["full"] * (row_bytes(model, FULL) + index_key_bytes(model))
        + c["window"] * row_bytes(model, WINDOW))
    return (passes * fixed_params(model) + experts_hit * expert_params(model)
            + cache_bytes(model, keys_scored, keys_full, keys_window)
            + written)


def pass_flops(model: dict, tokens: float, logit_rows: float,
               local_assignments: float, keys_scored: float,
               pairs_full: float, pairs_window: float) -> float:
    d = int(model["dim"])
    per_token = fixed_params(model) - d * int(model["vocab_size"])
    return (2.0 * per_token * tokens
            + 2.0 * d * int(model["vocab_size"]) * logit_rows
            + 2.0 * expert_params(model) * local_assignments
            + index_flops(model, keys_scored)
            + attention_flops(model, pairs_full, pairs_window))
