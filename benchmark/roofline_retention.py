"""The operations and bytes that power retention of degree 2 needs, for the
roofline shares of the state cache's cells (``retention_*_roofline``). Beside
``roofline.py`` and under the same rule: kept with the benchmark, counting what
the ALGORITHM needs and not what the program happens to compute.

Per layer, slot (= sequence) and key-value head the state is S in R^{rows x d}
and z in R^{rows}, float32, with rows = d (d + 1) / 2 the distinct products of
the symmetric second power of a d-wide key (8256 for d = 128; the program's
layout holds 8320 rows plus padding, which is its own business: a share is
counted against the 8256). Shapes come from the configuration file's ``model``
block (dim, n_layers, n_heads, n_kv_heads, head_dim, ffn_dim, vocab_size).
"""

from __future__ import annotations

from benchmark import roofline

STATE_BYTES = 4      # float32, stated in the configuration file


def _dims(model: dict):
    head_dim = int(model.get("head_dim") or model["dim"] // model["n_heads"])
    return (int(model["n_layers"]), int(model["n_heads"]),
            int(model["n_kv_heads"]), head_dim)


def feature_rows(model: dict) -> int:
    """Distinct products x_a x_b, a <= b, of one head's key."""
    d = _dims(model)[3]
    return d * (d + 1) // 2


def slot_bytes(model: dict, layers: int = None) -> float:
    """S and z of one slot over ``layers`` layers (default: all)."""
    n_layers, _, kv, d = _dims(model)
    layers = n_layers if layers is None else layers
    return float(STATE_BYTES * layers * kv * feature_rows(model) * (d + 1))


def update_bytes(model: dict, rows_advanced: float) -> float:
    """Bytes the one-token update has to move: every row that takes a token
    reads its slot once and writes it once, in every layer (q, k, v and y are
    under a thousandth of that and left out)."""
    return 2.0 * slot_bytes(model) * rows_advanced


def update_flops(model: dict, rows_advanced: float) -> float:
    """Operations of the one-token update: per layer and kv head the state
    update (2 per entry of S) and the read-out of the head group's queries
    (2 per entry of S and query head)."""
    n_layers, h, kv, d = _dims(model)
    return 2.0 * n_layers * feature_rows(model) * d * (kv + h) * rows_advanced


def chunk_flops(model: dict, tokens: float, chunk_rows: float) -> float:
    """Operations of the chunk form over ``tokens`` prompt tokens that came
    in ``chunk_rows`` chunks: per token and layer the read-out of the state
    before the chunk (2 rows d per query head) and its share of the chunk's
    one state update (2 rows d per kv head), plus inside the chunk QK^T and
    AV: 4 d per query head and causal pair, pairs = tokens * (mean chunk
    length + 1) / 2."""
    n_layers, h, kv, d = _dims(model)
    if tokens <= 0 or chunk_rows <= 0:
        return 0.0
    pairs = tokens * (tokens / chunk_rows + 1.0) / 2.0
    return n_layers * (2.0 * feature_rows(model) * d * (h + kv) * tokens
                       + 4.0 * d * h * pairs)


def chunk_bytes(model: dict, chunk_rows: float) -> float:
    """A chunk reads its row's slot once and writes it once, in every layer."""
    return 2.0 * slot_bytes(model) * chunk_rows


def pass_flops(model: dict, decode_tokens: float, prefill_tokens: float,
               chunk_rows: float, logit_rows: float) -> float:
    """Operations the model passes need: 2 per matmul weight and token in the
    layers, the output head for the rows whose logits are read, and the
    retention's own (``update_flops`` for the decode rows' tokens,
    ``chunk_flops`` for the prompts')."""
    n_layers = _dims(model)[0]
    new_tokens = decode_tokens + prefill_tokens
    return (
        2.0 * n_layers * roofline.layer_matmul_params(model, active=True) * new_tokens
        + 2.0 * int(model["dim"]) * int(model["vocab_size"]) * logit_rows
        + update_flops(model, decode_tokens)
        + chunk_flops(model, prefill_tokens, chunk_rows)
    )


def pass_bytes(model: dict, passes: float, decode_tokens: float,
               chunk_rows: float, bytes_per_weight: float = 1.0) -> float:
    """Bytes the model passes have to move: every weight once a pass, and the
    slot of every row that advanced, read and written once per pass it
    advanced in (a decode row per token, a prefill row per chunk)."""
    return (passes * roofline.weight_bytes(model, bytes_per_weight)
            + update_bytes(model, decode_tokens)
            + chunk_bytes(model, chunk_rows))
