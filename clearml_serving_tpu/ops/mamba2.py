"""The selective state-space scan of a Mamba-2 mixer (Dao and Gu, "Transformers
are SSMs", arXiv:2405.21060: the SSD form) as two Pallas kernels over the
engine's row state, with a plain XLA twin each.

Per head (H heads of P channels, a state of N rows; B_t and C_t [N] are shared
by the H / G heads of a group) and token t of a sequence:

    h_t = a_t h_{t-1} + B_t (dt_t x_t)^T          a_t = exp(dt_t A),  A < 0
    y_t = C_t^T h_t                                [P]

(the skip ``D x_t``, the gate and the norm are the model's). What this file
is handed is already past the convolution, the activations and the softplus:
``dtx`` = dt * x, the decay (or its logarithm) per token and head, B and C.

The pool (float32; one SLOT per sequence, slot index = batch row, and one
NULL slot behind them that the kernels' idle steps read and write):

    h  [L, slots + 1, H, N, P]     h[.., n, p] = sum_s decay(s..t) B_s[n] dt_s x_s[p]

N lies on sublanes and P on lanes: a token's update is a lane row (dt x)
broadcast down the sublanes times a column (B) broadcast across the lanes,
and the read-out a multiply by the column C and a sum down the sublanes. Both
kernels update layer ``layer`` of the stack in place
(``input_output_aliases``). Rows they are not asked to touch come back bit
for bit: the rows to advance arrive COMPACTED (``compact_rows``: the live
slots first, the null slot after them), so a step past the last live row
names the null slot's block, which stays in VMEM, and moves nothing else.

``mamba2_ssd_update``: one token a row (decode rows): a row's state streams
through VMEM once, 2 x H x N x P x 4 bytes. Bound by HBM.

``mamba2_ssd_chunk``: the rows of a ragged launch that bring MORE than one
token, on the launch's compact token axis (T tokens, a row's tokens next to
each other; T is the launch's token budget, max(128, 4 x rows): 256 at 64
rows. A launch's share of a prompt is ONE SSD block, whatever its length up
to T: the published ``mamba_chunk_size`` 128 is the block length of the
publisher's kernels, not a quantity of the model, and the recurrence is the
same under every cut: tests/test_mamba2_kernels.py). The chunked form: inside the chunk ``(L o C B^T) (dt x)`` with L the decays
between two tokens of one row (computed ONCE a launch and head: rows do not
see each other through the row mask), against the carried state ``exp(cum_t)
C_t^T h_in``, and one state update per row, ``h_out = exp(cum_end) h_in +
(B o exp(cum_end - cum))^T (dt x)``. Decays, their running sums and the state
are float32; every matmul runs at full float32 precision.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 64 << 20
_MAX_TOKENS = 512     # the chunk kernel holds a [T, T] decay matrix per head


def state_shape(n_layers: int, slots: int, n_heads: int, d_state: int,
                head_dim: int):
    """The stacked pool: ``slots`` rows and the null slot behind them."""
    return (n_layers, slots + 1, n_heads, d_state, head_dim)


def ssd_kernel_unsupported_reason(
    n_heads: int, n_groups: int, head_dim: int, d_state: int,
    tokens: Optional[int] = None, *, platform: Optional[str] = None,
) -> Optional[str]:
    """Why a mixer of these sizes cannot take the Mosaic kernels, or None
    (``tokens``: the chunk kernel's token axis; None for the update kernel).
    Pure in its arguments (``paged_attention.paged_kernel_unsupported_reason``):
    the model calls it at trace time and the engine at construction for
    ``health()["kernels"]``."""
    platform = platform or jax.default_backend()
    if platform != "tpu":
        return "platform {}: the Mosaic kernels compile for TPU only".format(
            platform)
    if head_dim != 128 or d_state % 128:
        return ("mamba_d_head {} / mamba_d_state {}: the SSD kernels hold a "
                "head's state as [d_state, 128] tiles".format(
                    head_dim, d_state))
    if n_heads % n_groups or (n_heads // n_groups) % 8:
        return ("{} heads in {} groups: a kernel step takes one group's "
                "heads, whole 8-row tiles of them".format(n_heads, n_groups))
    if tokens is not None and (tokens % 8 or tokens > _MAX_TOKENS):
        return ("{} tokens a launch: the chunk kernel holds the launch's "
                "[T, T] decays in VMEM (T a multiple of 8, at most {})"
                .format(tokens, _MAX_TOKENS))
    return None


def compact_rows(active):
    """active [B] bool -> (rows [B] int32: the active slots in order, then
    the null slot B; count)."""
    b = active.shape[0]
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    count = jnp.sum(active.astype(jnp.int32))
    rows = jnp.where(jnp.arange(b, dtype=jnp.int32) < count, order, b)
    return rows.astype(jnp.int32), count.astype(jnp.int32)


def _advancing(rows, count, b):
    """[B] bool: the batch rows a compacted list (``compact_rows``) names."""
    return jnp.zeros((b + 1,), bool).at[rows].set(jnp.arange(b) < count)[:b]


def _layer_of(layer, h_pool):
    one = h_pool.ndim == 4
    if one:
        h_pool, layer = h_pool[None], 0
    return jnp.asarray(layer, jnp.int32).reshape(1), h_pool, one


def _stored(x, round_state: bool):
    """The new state as kept: float32, or with ``round_state`` its nearest
    bfloat16 (in the float32 pool): a CONTROL of the tests, one precision
    under the configuration's, never a way to serve."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if round_state else x


def _segments(tok_row, tok_mask):
    """same [T, T]: s <= t, both live, of one row."""
    t = tok_row.shape[0]
    idx = jnp.arange(t)
    return ((tok_row[:, None] == tok_row[None, :])
            & tok_mask[:, None] & tok_mask[None, :]
            & (idx[:, None] >= idx[None, :]))


def _row_cumsum(log_decay, same):
    """Inclusive running sum of ``log_decay`` [T, H] inside each row."""
    return jnp.einsum("ts,sh->th", same.astype(jnp.float32),
                      log_decay.astype(jnp.float32), precision=_HI)


# ------------------------------------------------------------ XLA twins

def mamba2_ssd_update_xla(dtx, decay, bm, cm, rows, count, reset, h_pool, *,
                          layer=None, round_state: bool = False):
    """One token per row through the state. dtx [B, H, P] = dt * x; decay
    [B, H] = exp(dt * A); bm, cm [B, G, N]; (rows, count) =
    ``compact_rows(active)``; reset [B] bool (a reset row's slot counts as
    zero before the update). Returns (y [B, H, P] float32: the read-out of
    the NEW state, 0 on rows that did not advance; the pool)."""
    layer, h_all, one = _layer_of(layer, h_pool)
    b, n_h, _ = dtx.shape
    g = bm.shape[1]
    active = _advancing(rows, count, b)
    f32 = jnp.float32
    h_old = h_all[layer[0], :b]                                # [B,H,N,P]
    h0 = jnp.where(reset[:, None, None, None], 0.0, h_old)
    bh = jnp.repeat(bm.astype(f32), n_h // g, axis=1)          # [B,H,N]
    ch = jnp.repeat(cm.astype(f32), n_h // g, axis=1)
    h_new = _stored(
        decay.astype(f32)[..., None, None] * h0
        + bh[..., :, None] * dtx.astype(f32)[..., None, :], round_state)
    y = jnp.sum(h_new * ch[..., :, None], axis=2)              # [B,H,P]
    on = active[:, None, None]
    h_all = h_all.at[layer[0], :b].set(
        jnp.where(on[..., None], h_new, h_old))
    y = jnp.where(on, y, 0.0)
    return (y, h_all[0]) if one else (y, h_all)


def mamba2_ssd_chunk_xla(dtx, log_decay, bm, cm, tok_row, tok_mask, rows,
                         count, reset, h_pool, *, layer=None,
                         round_state: bool = False):
    """The launch's multi-token rows through the state, in the chunked form.
    dtx [T, H, P]; log_decay [T, H] = dt * A; bm, cm [T, G, N]; tok_row [T]
    the owning batch row of a token, tok_mask [T] the tokens of the rows to
    advance; (rows, count) the compacted list of those rows; reset [B].
    Returns (y [T, H, P] float32, 0 on tokens outside the mask; the pool)."""
    layer, h_all, one = _layer_of(layer, h_pool)
    t, n_h, _ = dtx.shape
    b = reset.shape[0]
    g = bm.shape[1]
    f32 = jnp.float32
    dtx, bm, cm = dtx.astype(f32), bm.astype(f32), cm.astype(f32)
    same = _segments(tok_row, tok_mask)
    cum = _row_cumsum(log_decay, same)                          # [T,H]
    active = _advancing(rows, count, b)
    h_old = h_all[layer[0], :b]
    h0 = jnp.where(reset[:, None, None, None], 0.0, h_old)     # [B,H,N,P]
    per = n_h // g
    # inside the chunk
    scores = jnp.einsum("tgn,sgn->gts", cm, bm, precision=_HI)  # [G,T,T]
    decay = jnp.where(
        same[None], jnp.exp(jnp.minimum(
            cum.T[:, :, None] - cum.T[:, None, :], 0.0)), 0.0)  # [H,T,T]
    m = jnp.repeat(scores, per, axis=0) * decay
    y = jnp.einsum("hts,shp->thp", m, dtx, precision=_HI)
    # against the carried state, and the state's update, row by row
    onehot = ((tok_row[:, None] == jnp.arange(b)[None, :])
              & tok_mask[:, None] & active[None, :]).astype(f32)  # [T,B]
    ch = jnp.repeat(cm, per, axis=1)                            # [T,H,N]
    bh = jnp.repeat(bm, per, axis=1)
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "tb,thn,bhnp->thp", onehot, ch, h0, precision=_HI)
    big = jnp.float32(3.0e38)
    tot = jnp.min(jnp.where(onehot.T[:, :, None] > 0, cum[None], big),
                  axis=1)                                       # [B,H]
    tot = jnp.where(active[:, None], tot, 0.0)
    w = onehot[:, :, None] * jnp.exp(jnp.minimum(
        tot[None] - cum[:, None, :], 0.0))                      # [T,B,H]
    h_new = _stored(
        jnp.exp(tot)[..., None, None] * h0 + jnp.einsum(
            "tbh,thn,thp->bhnp", w, bh, dtx, precision=_HI), round_state)
    h_all = h_all.at[layer[0], :b].set(
        jnp.where(active[:, None, None, None], h_new, h_old))
    y = jnp.where(tok_mask[:, None, None], y, 0.0)
    return (y, h_all[0]) if one else (y, h_all)


# --------------------------------------------------------------- kernels

def _column(ref, r, n):
    """Row ``r`` of the block ``ref`` [1, 1, 8, n] (n on lanes) -> [n, 128]:
    the values down the sublanes, on every lane. One 128 x 128 transpose per
    128 values, each read from the block at a tile's edge."""
    parts = [
        jnp.broadcast_to(ref[0, 0, r:r + 1, i:i + 128], (128, 128)).T
        for i in range(0, n, 128)
    ]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)


def _update_kernel(
    # scalar prefetch (SMEM)
    layer_ref, rows_ref, reset_ref,
    # one (row, group): dtx, decay [1, 1, Hg, P] (decay on every lane);
    # bc [1, 1, 8, N]: row 0 B, row 1 C; the slot's block h [1, 1, Hg, N, P]
    dtx_ref, decay_ref, bc_ref, h_ref,
    y_ref, h_out,
    *, round_state: bool,
):
    del layer_ref
    slot = rows_ref[pl.program_id(1)]
    fresh = reset_ref[slot] != 0
    hg, n = h_ref.shape[2], h_ref.shape[3]
    b_col = _column(bc_ref, 0, n)                              # [N, P]
    c_col = _column(bc_ref, 1, n)
    for hd in range(hg):
        h_in = jnp.where(fresh, 0.0, h_ref[0, 0, hd])          # [N, P]
        h_new = _stored(
            decay_ref[0, 0, hd:hd + 1, :] * h_in
            + b_col * dtx_ref[0, 0, hd:hd + 1, :], round_state)
        h_out[0, 0, hd] = h_new
        y_ref[0, 0, hd:hd + 1, :] = jnp.sum(
            h_new * c_col, axis=0, keepdims=True)


def mamba2_ssd_update(dtx, decay, bm, cm, rows, count, reset, h_pool, *,
                      layer=None, interpret: bool = False,
                      round_state: bool = False):
    """:func:`mamba2_ssd_update_xla` as a Pallas kernel, the pool updated IN
    PLACE: per (group, row) the slot's state streams through VMEM once.
    Never the reference: it runs the kernel or raises."""
    layer, h_all, one = _layer_of(layer, h_pool)
    b, n_h, p = dtx.shape
    g, n = bm.shape[1], bm.shape[2]
    reason = None if interpret else ssd_kernel_unsupported_reason(
        n_h, g, p, n)
    if reason is not None:
        raise ValueError("mamba2_ssd_update: " + reason)
    hg = n_h // g
    f32 = jnp.float32

    def null_row(a):            # the null slot's operands: zeros
        return jnp.concatenate(
            [a.astype(f32), jnp.zeros((1,) + a.shape[1:], f32)], axis=0)

    dtx_g = null_row(dtx).reshape(b + 1, g, hg, p)
    decay_g = jnp.broadcast_to(
        null_row(decay)[..., None], (b + 1, n_h, p)).reshape(b + 1, g, hg, p)
    bc = jnp.concatenate([
        null_row(bm)[:, :, None], null_row(cm)[:, :, None],
        jnp.zeros((b + 1, g, 6, n), f32)], axis=2)             # [B+1,G,8,N]
    x_spec = pl.BlockSpec(
        (1, 1, hg, p), lambda j, i, l, rows, rs: (rows[i], j, 0, 0))
    bc_spec = pl.BlockSpec(
        (1, 1, 8, n), lambda j, i, l, rows, rs: (rows[i], j, 0, 0))
    h_spec = pl.BlockSpec(
        (1, 1, hg, n, p), lambda j, i, l, rows, rs: (l[0], rows[i], j, 0, 0))
    y, h_all = pl.pallas_call(
        functools.partial(_update_kernel, round_state=round_state),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,            # layer, rows, reset
            grid=(g, b),
            in_specs=[x_spec, x_spec, bc_spec, h_spec],
            out_specs=[x_spec, h_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((b + 1, g, hg, p), f32),
                   jax.ShapeDtypeStruct(h_all.shape, h_all.dtype)],
        # operands count the scalar prefetch: 3 + (dtx, decay, bc, h)
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mamba2_ssd_update",
    )(layer, rows.astype(jnp.int32),
      jnp.concatenate([reset, jnp.zeros((1,), bool)]).astype(jnp.int32),
      dtx_g, decay_g, bc, h_all)
    # a row that did not advance was never written: whatever its block of
    # the output held is not a number to be used
    active = _advancing(rows, count, b)
    y = jnp.where(active[:, None, None], y[:b].reshape(b, n_h, p), 0.0)
    return (y, h_all[0]) if one else (y, h_all)


def _chunk_kernel(
    # scalar prefetch (SMEM)
    layer_ref, rows_ref, count_ref, reset_ref,
    # one group, the whole compact token axis: dtx [1, T, Hg * P]; cum
    # [1, T, Hg] and cum_t [1, Hg, T] the running sums of log decay inside a
    # row; rid [T, 1] and rid_t [1, T] a token's row (-1: not in the mask);
    # cm, bm [1, T, N], bm_t [1, N, T]; the slot's block h [1, 1, Hg, N, P]
    dtx_ref, cum_ref, cum_t_ref, rid_ref, rid_t_ref, cm_ref, bm_ref,
    bm_t_ref, h_ref,
    y_ref, h_out,
    *, round_state: bool,
):
    del layer_ref
    i = pl.program_id(1)
    slot = rows_ref[i]
    count = count_ref[0]
    hg, p = h_ref.shape[2], h_ref.shape[4]
    t = dtx_ref.shape[1]
    rid, rid_t = rid_ref[...], rid_t_ref[...]                  # [T,1], [1,T]
    cm = cm_ref[0]

    @pl.when(i == 0)
    def _inside():
        # the decays between two tokens of one row, once a launch and head
        idx_t = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        idx_s = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        same = (rid == rid_t) & (rid >= 0) & (idx_t >= idx_s)
        scores = jnp.where(same, _dot_nt(cm, bm_ref[0]), 0.0)  # [T, T]
        for hd in range(hg):
            lanes = slice(hd * p, (hd + 1) * p)
            decay = jnp.exp(jnp.minimum(
                cum_ref[0, :, hd:hd + 1] - cum_t_ref[0, hd:hd + 1, :], 0.0))
            y_ref[0, :, lanes] = _dot(scores * decay, dtx_ref[0, :, lanes])

    @pl.when(i < count)
    def _row():
        fresh = reset_ref[slot] != 0
        in_col = rid == slot                                   # [T, 1]
        in_row = rid_t == slot                                 # [1, T]
        bm_t = bm_t_ref[0]                                     # [N, T]
        for hd in range(hg):
            lanes = slice(hd * p, (hd + 1) * p)
            c_col = cum_ref[0, :, hd:hd + 1]                   # [T, 1]
            c_row = cum_t_ref[0, hd:hd + 1, :]                 # [1, T]
            h_in = jnp.where(fresh, 0.0, h_ref[0, 0, hd])      # [N, P]
            # the row's last token holds the least sum (log decay <= 0)
            tot = jnp.min(jnp.where(in_row, c_row, 3.0e38), axis=1,
                          keepdims=True)                       # [1, 1]
            y_ref[0, :, lanes] += jnp.where(
                in_col, jnp.exp(c_col), 0.0) * _dot(cm, h_in)
            w = jnp.where(in_row, jnp.exp(jnp.minimum(tot - c_row, 0.0)), 0.0)
            h_out[0, 0, hd] = _stored(
                jnp.exp(tot) * h_in + _dot(bm_t * w, dtx_ref[0, :, lanes]),
                round_state)

    @pl.when(i >= count)
    def _idle():
        h_out[...] = h_ref[...]


def mamba2_ssd_chunk(dtx, log_decay, bm, cm, tok_row, tok_mask, rows, count,
                     reset, h_pool, *, layer=None, interpret: bool = False,
                     round_state: bool = False):
    """:func:`mamba2_ssd_chunk_xla` as a Pallas kernel, the pool updated IN
    PLACE. Per group: the decays inside the chunk once, then a step per row
    of the compacted list, its state through VMEM once. Never the
    reference: it runs the kernel or raises."""
    layer, h_all, one = _layer_of(layer, h_pool)
    t, n_h, p = dtx.shape
    b = reset.shape[0]
    g, n = bm.shape[1], bm.shape[2]
    reason = None if interpret else ssd_kernel_unsupported_reason(
        n_h, g, p, n, t)
    if reason is not None:
        raise ValueError("mamba2_ssd_chunk: " + reason)
    hg = n_h // g
    f32 = jnp.float32
    same = _segments(tok_row, tok_mask)
    cum = _row_cumsum(log_decay, same).reshape(t, g, hg)
    cum_g = jnp.moveaxis(cum, 1, 0)                            # [G,T,Hg]
    rid = jnp.where(tok_mask, tok_row, -1).astype(jnp.int32)
    cm_g = jnp.moveaxis(cm.astype(f32), 1, 0)                  # [G,T,N]
    bm_g = jnp.moveaxis(bm.astype(f32), 1, 0)
    dtx_g = jnp.moveaxis(
        dtx.astype(f32).reshape(t, g, hg * p), 1, 0)           # [G,T,Hg*P]

    def whole(shape):
        return pl.BlockSpec(shape, lambda j, i, *_: (0,) * len(shape))

    def group(shape):
        return pl.BlockSpec(
            (1,) + shape, lambda j, i, *_: (j,) + (0,) * len(shape))

    h_spec = pl.BlockSpec(
        (1, 1, hg, n, p),
        lambda j, i, l, rows, cnt, rs: (l[0], rows[i], j, 0, 0))
    y, h_all = pl.pallas_call(
        functools.partial(_chunk_kernel, round_state=round_state),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,            # layer, rows, count, reset
            grid=(g, b),
            in_specs=[
                group((t, hg * p)), group((t, hg)), group((hg, t)),
                whole((t, 1)), whole((1, t)), group((t, n)), group((t, n)),
                group((n, t)), h_spec,
            ],
            out_specs=[group((t, hg * p)), h_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((g, t, hg * p), f32),
                   jax.ShapeDtypeStruct(h_all.shape, h_all.dtype)],
        # operands count the scalar prefetch: 4 + 8 token operands + h
        input_output_aliases={12: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mamba2_ssd_chunk",
    )(layer, rows.astype(jnp.int32), count.reshape(1).astype(jnp.int32),
      jnp.concatenate([reset, jnp.zeros((1,), bool)]).astype(jnp.int32),
      dtx_g, cum_g, jnp.swapaxes(cum_g, 1, 2), rid[:, None], rid[None, :],
      cm_g, bm_g, jnp.swapaxes(bm_g, 1, 2), h_all)
    y = jnp.moveaxis(y, 0, 1).reshape(t, n_h, p)
    y = jnp.where(tok_mask[:, None, None], y, 0.0)
    return (y, h_all[0]) if one else (y, h_all)
