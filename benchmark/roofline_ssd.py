"""Operations and bytes of a decoder whose every block runs a Mamba-2 mixer
beside grouped-query attention (``arch: falcon_h1``; reference/falcon_h1.py
gives the equations), counted as roofline.py counts a K/V decoder: what the
ALGORITHM needs, whatever the program computes. Shapes come from the
configuration's ``model`` block (sut.model_block); peaks and ``min_seconds``
are roofline.py's.

The state. A row's state of one layer is H heads x N state rows x P channels
of float32 (``state_bytes``: 32 x 256 x 128 x 4 = 4,194,304 B at the
published widths); the convolution's window (3 x 5,120 x 4 = 61,440 B) is
1.5% of it and left out, which only lowers a share.

``update_*``: a row that advances ONE token reads its state and writes it
back in every layer: 2 x 4.19 MB = 8.39 MB a row and layer; per state element
a decay, a rank-one update and the read-out: 5 operations.

``chunk_*``: a row that brings a chunk of a prompt reads and writes its state
ONCE for the whole chunk; a token's x, B, C, dt come in and its y goes out in
float32. Operations: what the recurrence itself needs, 4 x H x N x P a token
and layer (2 for the state's update, 2 for the read-out): the chunked form's
own algebra (the decays inside the chunk) is the program's choice and is not
counted, which only lowers the share.

``pass_*``: the whole model step. Bytes: every matmul weight once a pass
(int8: one byte each; in_proj 5120 x 9248, out_proj 4096 x 5120, q/k/v/o,
the three feed-forward matrices, and the head: 8 x 430.1 M + 1,336.9 M =
4,777.6 MB at eight blocks), the state read and written by every row that
advanced (one token or a chunk), K and V of every token a row attended, and
the new tokens' K/V written. Operations: two per weight per token in the
layers, the head for the rows whose logits are read, the recurrence's 4 x H
x N x P a token and layer, and attention's QK^T and PV.
"""

from __future__ import annotations


def _mixer(model: dict):
    d_ssm, n_h = int(model["mamba_d_ssm"]), int(model["mamba_n_heads"])
    return (d_ssm, n_h, d_ssm // n_h, int(model.get("mamba_n_groups", 1)),
            int(model["mamba_d_state"]))


def _head_dim(model: dict) -> int:
    return int(model.get("head_dim") or model["dim"] // model["n_heads"])


def state_elements(model: dict) -> int:
    """H x N x P: one row's state of one layer."""
    _, n_h, p, _, n = _mixer(model)
    return n_h * n * p


def state_bytes(model: dict) -> int:
    return 4 * state_elements(model)


def update_bytes(model: dict, rows: float) -> float:
    """``rows`` = rows x passes that advanced one token: each reads and
    writes its state in every layer."""
    return 2.0 * state_bytes(model) * int(model["n_layers"]) * rows


def update_flops(model: dict, rows: float) -> float:
    return 5.0 * state_elements(model) * int(model["n_layers"]) * rows


def token_operand_bytes(model: dict) -> int:
    """What the chunk kernel moves for one token of one layer besides the
    state, float32: dt x in, B, C and the decay in, y out."""
    d_ssm, n_h, _, g, n = _mixer(model)
    return 4 * (2 * d_ssm + 2 * g * n + n_h)


def chunk_bytes(model: dict, rows: float, tokens: float) -> float:
    layers = int(model["n_layers"])
    return layers * (2.0 * state_bytes(model) * rows
                     + token_operand_bytes(model) * tokens)


def chunk_flops(model: dict, tokens: float) -> float:
    return 4.0 * state_elements(model) * int(model["n_layers"]) * tokens


def layer_matmul_params(model: dict) -> int:
    """One block's matmul weights: in_proj (z, x, B, C, dt), out_proj, the
    four attention projections and the gated feed-forward."""
    d = int(model["dim"])
    d_ssm, n_h, _, g, n = _mixer(model)
    hd = _head_dim(model)
    mixer = d * (2 * d_ssm + 2 * g * n + n_h) + d_ssm * d
    attn = d * hd * (2 * int(model["n_heads"]) + 2 * int(model["n_kv_heads"]))
    return mixer + attn + 3 * d * int(model["ffn_dim"])


def weight_bytes(model: dict, bytes_per_weight: float = 1.0) -> float:
    """Every matmul weight as served (int8), the head in, the embedding out
    (a pass reads rows of it, not the table)."""
    return bytes_per_weight * (
        int(model["n_layers"]) * layer_matmul_params(model)
        + int(model["dim"]) * int(model["vocab_size"]))


def kv_bytes_per_token(model: dict, bytes_per_value: float = 2.0) -> float:
    return (2 * int(model["n_layers"]) * int(model["n_kv_heads"])
            * _head_dim(model) * bytes_per_value)


def pass_bytes(model: dict, passes: float, update_rows: float,
               chunk_rows: float, chunk_tokens: float,
               kv_tokens_read: float) -> float:
    new_tokens = update_rows + chunk_tokens
    return (passes * weight_bytes(model)
            + update_bytes(model, update_rows + chunk_rows)
            + kv_bytes_per_token(model) * (kv_tokens_read + new_tokens))


def pass_flops(model: dict, update_rows: float, chunk_tokens: float,
               logit_rows: float, attended_pairs: float) -> float:
    tokens = update_rows + chunk_tokens
    layers, d = int(model["n_layers"]), int(model["dim"])
    return (2.0 * layers * layer_matmul_params(model) * tokens
            + 2.0 * d * int(model["vocab_size"]) * logit_rows
            + chunk_flops(model, tokens)
            + 4.0 * layers * int(model["n_heads"]) * _head_dim(model)
            * attended_pairs)
