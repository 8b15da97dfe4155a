"""Power retention of degree 2 (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239): the recurrent form that the engine's
state cache serves, as two Pallas kernels with a plain XLA twin each.

Attention form (what benchmark/reference/brumby.py computes): for query head i
of key-value head j and s <= t,

    a_ts = (q_t^i . k_s^j)^2 * exp(sum_{r=s+1..t} log g_r^j)
    y_t^i = sum_s a_ts v_s^j / sum_s a_ts

Recurrent form: with phi the symmetric second power, phi(x).phi(y) = (x.y)^2,

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
    y_t^i = phi(q_t^i)^T S_t / phi(q_t^i)^T z_t

Layout of phi (this file's choice; docs/state_cache.md). The d*(d+1)/2 distinct
products x_a x_b are held as d/2 + 1 cyclic DIAGONALS of d lanes each:

    feat(x)[s, i] = x[i] * x[(i + s) mod d]        s = 0 .. d/2

Every unordered pair {a, b} lies on exactly one diagonal s = min(b-a, d-(b-a)),
once (twice on s = d/2, whose pairs {i, i + d/2} meet from both ends). With the
weights w = (1, 2, .., 2, 1) on the QUERY side only,

    sum_s w_s sum_i feat(q)[s, i] feat(k)[s, i] = (q . k)^2

exactly: the state holds the plain products feat(k) v^T and the weight rides on
the read-out. d = 128 gives 65 diagonals = 8320 rows (8256 distinct products
and the 64 pairs that s = 64 holds twice), every one a full 128-lane row made
by one lane rotation and one multiply: no gather, no outer product of 16384.

Pools (float32; one SLOT per sequence, slot index = batch row):

    S  [L, slots, Hkv, d, (d/2+1)*d]   S[.., c, s*d + i] = sum_t decay * v_t[c] feat(k_t)[s, i]
    z  [L, slots, Hkv, 8*ceil((d/2+1)/8), d]   z[.., s, i] = sum_t decay * feat(k_t)[s, i]

The value dimension c lies on sublanes and the feature rows on lanes, so that
one token's update is a sublane broadcast (feat(k)) times a lane broadcast (v)
and its read-out a multiply and a lane reduction: vector work the bandwidth of
the pool hides. Both kernels update the pools in place
(``input_output_aliases``) and take the stack plus a ``layer`` index, like the
paged kernels (PERF.md, PR 25). Rows they are not asked to touch come back bit
for bit. The update kernel pipelines a slot's blocks through VMEM with block
specs, and a skipped row's step names its neighbouring active row's block; on
the v5e such a step still costs about what one block's transfer does (5 us:
PERF.md, PR 26), which is little where nearly every row decodes. The chunk
kernel, where most rows have no chunk, fetches and stores the state by hand
for the rows that have one and moves nothing for the others.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_CHUNK = 128          # tokens per sub-chunk of the chunk kernel on the chip
ROW_ALIGN = 8         # the chunk kernel's row_starts: whole float32 sublane tiles
_VMEM_LIMIT = 64 << 20


# ------------------------------------------------------------------ layout

def n_shifts(head_dim: int) -> int:
    return head_dim // 2 + 1


def state_rows(head_dim: int) -> int:
    """Feature rows of one (slot, kv head): (d/2 + 1) * d."""
    return n_shifts(head_dim) * head_dim


def z_rows(head_dim: int) -> int:
    """Rows of z: the diagonals, padded to whole 8-row tiles (with 65 rows
    the v5e compiler gives the stack another layout than the kernels take
    and copies all of it around every call)."""
    return -(-n_shifts(head_dim) // 8) * 8


def shift_weights(head_dim: int):
    """w_s of the read-out: 1 on the diagonal s = 0, 2 on 0 < s < d/2 (each
    pair once), 1 on s = d/2 (each pair twice)."""
    return [1.0] + [2.0] * (head_dim // 2 - 1) + [1.0]


def state_shapes(n_layers: int, slots: int, n_kv: int, head_dim: int):
    """((S shape), (z shape)) of the stacked pools."""
    return (
        (n_layers, slots, n_kv, head_dim, state_rows(head_dim)),
        (n_layers, slots, n_kv, z_rows(head_dim), head_dim),
    )


def features(x):
    """x [..., d] -> feat(x) [..., d/2+1, d] (unweighted)."""
    d = x.shape[-1]
    return jnp.stack(
        [x * jnp.roll(x, -s, axis=-1) for s in range(n_shifts(d))], axis=-2
    )


def retention_kernel_unsupported_reason(
    head_dim: int, *, platform: Optional[str] = None
) -> Optional[str]:
    """Why a state pool of this head size cannot take the Mosaic kernels, or
    None. Pure in its arguments, like
    ``paged_attention.paged_kernel_unsupported_reason``: the model calls it at
    trace time and the engine at construction for ``health()["kernels"]``."""
    platform = platform or jax.default_backend()
    if platform != "tpu":
        return "platform {}: the Mosaic kernels compile for TPU only".format(
            platform
        )
    if head_dim != 128:
        return ("head_dim {}: the retention kernels hold one diagonal per "
                "128-lane row".format(head_dim))
    return None


def _stacked(layer, s_pool, z_pool):
    """(layer [1] int32, S [L, ...], z [L, ...], whether one layer came in)."""
    one = s_pool.ndim == 4
    if one:
        s_pool, z_pool = s_pool[None], z_pool[None]
        layer = 0
    return jnp.asarray(layer, jnp.int32).reshape(1), s_pool, z_pool, one


def _stored(x, round_state: bool):
    """What is kept of a new state: the float32 value, or with
    ``round_state`` its nearest bfloat16 (in the float32 pool). The second is
    NOT a way to serve: it is the configuration's state precision lowered by
    one step, which the reference comparison has to refuse (the measurement
    that sets ``probes.tolerance``; PERF.md, PR 26)."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if round_state else x


def _row_modes(active, reset):
    """Per batch row: 0 skip, 1 update, 2 update a slot that counts as zero."""
    return jnp.where(active, jnp.where(reset, 2, 1), 0).astype(jnp.int32)


def _row_plan(active, reset):
    """Per batch row, for the kernels' grid: ``mode`` (0 skip, 1 update, 2
    update a zeroed slot, 3 copy through) and ``amap``, the row whose block
    a step names. A skipped row names its nearest active neighbour (the one
    before it, else the one after), so its step moves no data; with no
    active row at all, row 0 is copied through once per head (the aliased
    output block must hold something when it is written back)."""
    b = active.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(active, idx, -1))
    nxt = jnp.flip(jax.lax.cummin(jnp.flip(jnp.where(active, idx, b))))
    amap = jnp.where(prev >= 0, prev, jnp.where(nxt < b, nxt, 0))
    mode = _row_modes(active, reset)
    mode = jnp.where(jnp.any(active), mode, mode.at[0].set(3))
    return amap.astype(jnp.int32), mode


# ------------------------------------------------------------ XLA twins

def power_retention_update_xla(q, k, v, log_g, active, reset, s_pool, z_pool,
                               *, layer=None, round_state: bool = False):
    """One token per row through the state: ``S <- g S + v feat(k)^T``, then
    the row's query heads read the NEW state. q [B, Hkv, G, d]; k, v
    [B, Hkv, d]; log_g [B, Hkv]; active, reset [B] bool (a reset row's slot
    counts as zero before the update; an inactive row's slot comes back bit
    for bit). Returns (y [B, Hkv, G, d] float32, S, z)."""
    layer, s_all, z_all, one = _stacked(layer, s_pool, z_pool)
    d = q.shape[-1]
    w = jnp.asarray(shift_weights(d), jnp.float32)
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s_old, z_old = s_all[layer[0]], z_all[layer[0]]
    keep = (~reset)[:, None, None, None]
    s0, z0 = jnp.where(keep, s_old, 0.0), jnp.where(keep, z_old, 0.0)
    g = jnp.exp(log_g.astype(jnp.float32))[..., None, None]
    fk = features(k)                                           # [B,Hkv,ND,d]
    fq = features(q) * w[:, None]                              # [B,Hkv,G,ND,d]
    rows = fk.shape[-2] * d
    s_new = _stored(
        g * s0 + v[..., :, None] * fk.reshape(fk.shape[:2] + (1, rows)),
        round_state)
    z_new = z0.at[:, :, :fk.shape[-2]].set(
        _stored(g * z0[:, :, :fk.shape[-2]] + fk, round_state))
    num = jnp.einsum("bhgr,bhcr->bhgc", fq.reshape(fq.shape[:3] + (rows,)),
                     s_new, precision=_HI)
    den = jnp.einsum("bhgsd,bhsd->bhg", fq, z_new[:, :, :fk.shape[-2]],
                     precision=_HI)
    on = active[:, None, None, None]
    y = jnp.where(on, num / jnp.where(on[..., 0], den, 1.0)[..., None], 0.0)
    s_all = s_all.at[layer[0]].set(jnp.where(on, s_new, s_old))
    z_all = z_all.at[layer[0]].set(jnp.where(on, z_new, z_old))
    return (y, s_all[0], z_all[0]) if one else (y, s_all, z_all)


def _row_gather(row_starts, row_lens, active, width, total):
    """idx [B, W] flat token index of each row position and its mask."""
    pos = jnp.arange(width, dtype=jnp.int32)[None]
    mask = (pos < row_lens[:, None]) & active[:, None]
    idx = jnp.clip(row_starts[:, None] + pos, 0, total - 1)
    return idx, mask


def _row_cumsum(log_g, row_starts, row_lens, active, width):
    """Inclusive sum of log g from each row's first token, scattered back to
    the flat token axis ([T, Hkv]; 0 on tokens of no active row)."""
    t = log_g.shape[0]
    idx, mask = _row_gather(row_starts, row_lens, active, width, t)
    cum = jnp.cumsum(
        jnp.where(mask[..., None], log_g.astype(jnp.float32)[idx], 0.0), axis=1
    )
    flat = jnp.zeros((t + 1,) + log_g.shape[1:], jnp.float32)
    return flat.at[jnp.where(mask, idx, t)].set(cum)[:t]


def power_retention_chunk_xla(q, k, v, log_g, row_starts, row_lens, active,
                              reset, s_pool, z_pool, *, layer=None,
                              round_state: bool = False):
    """A chunk of tokens per row through the state: inside the chunk the
    attention form, against the state before it the recurrent read-out, each
    decayed by the gates between, and ONE state update for the whole chunk.
    q [T, Hkv, G, d]; k, v [T, Hkv, d]; log_g [T, Hkv] on the flat ragged
    token axis; row b owns tokens row_starts[b] + [0, row_lens[b]); active,
    reset [B]. Tokens of no active row read 0 and write nothing. Returns
    (y [T, Hkv, G, d] float32, S, z)."""
    layer, s_all, z_all, one = _stacked(layer, s_pool, z_pool)
    t, _, _, d = q.shape
    nd = n_shifts(d)
    w = shift_weights(d)
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s_old, z_old = s_all[layer[0]], z_all[layer[0]]
    keep = (~reset)[:, None, None, None]
    s0, z0 = jnp.where(keep, s_old, 0.0), jnp.where(keep, z_old, 0.0)
    idx, m = _row_gather(row_starts, row_lens, active, t, t)
    qb, kb, vb = q[idx], k[idx], v[idx]                        # [B,C,...]
    cum = jnp.cumsum(
        jnp.where(m[..., None], log_g.astype(jnp.float32)[idx], 0.0), axis=1
    )                                                          # [B,C,Hkv]
    # inside the chunk
    sc = jnp.einsum("bthgd,bshd->bhgts", qb, kb, precision=_HI) ** 2
    tri = jnp.tril(jnp.ones((t, t), bool))
    ok = (m[:, :, None] & m[:, None, :] & tri[None])[:, None, None]
    dec = jnp.exp(jnp.where(
        ok, (cum[:, :, None] - cum[:, None, :]).transpose(0, 3, 1, 2)[:, :, None],
        0.0))
    a = jnp.where(ok, sc * dec, 0.0)                           # [B,Hkv,G,t,s]
    num = jnp.einsum("bhgts,bshc->bthgc", a, vb, precision=_HI)
    den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)            # [B,t,Hkv,G]
    # against the state before the chunk, one diagonal at a time
    e_t = jnp.exp(cum)[..., None]                              # [B,C,Hkv,1]
    tot = cum[:, -1]                                           # [B,Hkv]
    d_end = jnp.where(m[..., None], jnp.exp(tot[:, None] - cum), 0.0)
    s_new, z_new = [], []
    for s in range(nd):
        s_s, z_s = s0[..., s * d:(s + 1) * d], z0[:, :, s]
        fq = qb * jnp.roll(qb, -s, axis=-1) * w[s]             # [B,C,Hkv,G,d]
        num = num + e_t[..., None] * jnp.einsum(
            "bthgi,bhci->bthgc", fq, s_s, precision=_HI)
        den = den + e_t * jnp.einsum("bthgi,bhi->bthg", fq, z_s, precision=_HI)
        fk = kb * jnp.roll(kb, -s, axis=-1)                    # [B,C,Hkv,d]
        s_new.append(jnp.exp(tot)[..., None, None] * s_s + jnp.einsum(
            "bthc,bth,bthi->bhci", vb, d_end, fk, precision=_HI))
        z_new.append(jnp.exp(tot)[..., None] * z_s + jnp.einsum(
            "bth,bthi->bhi", d_end, fk, precision=_HI))
    s_new = _stored(jnp.concatenate(s_new, axis=-1), round_state)
    z_new = z0.at[:, :, :nd].set(_stored(jnp.stack(z_new, axis=2), round_state))
    live = m[..., None, None]                                  # [B,C,1,1]
    y = jnp.where(live[..., None],
                  num / jnp.where(live, den, 1.0)[..., None], 0.0)
    flat = jnp.zeros((t + 1,) + q.shape[1:], jnp.float32)
    y = flat.at[jnp.where(m, idx, t)].set(y)[:t]
    on = active[:, None, None, None]
    s_all = s_all.at[layer[0]].set(jnp.where(on, s_new, s_old))
    z_all = z_all.at[layer[0]].set(jnp.where(on, z_new, z_old))
    return (y, s_all[0], z_all[0]) if one else (y, s_all, z_all)


# --------------------------------------------------------------- kernels

def _roll_lanes(x, s, interpret):
    """x[..., (i + s) mod d] at lane i."""
    d = x.shape[-1]
    if s == 0:
        return x
    if interpret:
        return jnp.roll(x, -s, axis=-1)
    return pltpu.roll(x, d - s, x.ndim - 1)


def _dot_nt(a, b):
    """a [M, K] . b [N, K]^T in float32 at full precision."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)


def _update_kernel(
    # scalar prefetch (SMEM)
    layer_ref, amap_ref, mode_ref,
    # inputs: x [1, 1, XR, d]: rows 0..G-1 the queries, G the key, G+1 the
    # value, G+2 the gate g on every lane; the pools' blocks of one (slot,
    # head): s [1, 1, 1, d, ND*d], z [1, 1, 1, ND, d]
    x_ref, s_ref, z_ref,
    # outputs: y [1, 1, XR, d] (rows 0..G-1), and the pools' blocks again
    y_ref, s_out, z_out,
    *, groups: int, interpret: bool, round_state: bool,
):
    del layer_ref, amap_ref
    mode = mode_ref[pl.program_id(1)]
    d = x_ref.shape[-1]
    nd = n_shifts(d)
    w = shift_weights(d)
    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(mode == 3)
    def _copy():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]

    @pl.when((mode == 1) | (mode == 2))
    def _update():
        x = x_ref[0, 0]                                        # [XR, d]
        fresh = mode == 2
        g_row = x[groups + 2:groups + 3, :]                    # [1, d]
        # v[c] along sublanes, on every lane
        v_col = jnp.broadcast_to(x[groups + 1:groups + 2, :], (d, d)).T
        # z's pad rows (the diagonals fill 65 of its 72) go through as they are
        z_out[0, 0, 0, nd:, :] = z_ref[0, 0, 0, nd:, :]
        nums = [jnp.zeros((d, d), jnp.float32) for _ in range(groups)]
        dens = [jnp.zeros((1, d), jnp.float32) for _ in range(groups)]
        for s in range(nd):
            f = x * _roll_lanes(x, s, interpret)               # [XR, d]
            fk = f[groups:groups + 1, :]
            lanes = slice(s * d, (s + 1) * d)
            s_old = jnp.where(fresh, 0.0, s_ref[0, 0, 0, :, lanes])
            z_old = jnp.where(fresh, 0.0, z_ref[0, 0, 0, s:s + 1, :])
            s_new = _stored(g_row * s_old + v_col * fk, round_state)  # [d(c), d(i)]
            z_new = _stored(g_row * z_old + fk, round_state)
            s_out[0, 0, 0, :, lanes] = s_new
            z_out[0, 0, 0, s:s + 1, :] = z_new
            for h in range(groups):
                fq = f[h:h + 1, :] if w[s] == 1.0 else f[h:h + 1, :] * w[s]
                nums[h] = nums[h] + s_new * fq
                dens[h] = dens[h] + z_new * fq
        for h in range(groups):
            num = jnp.sum(nums[h].T, axis=0, keepdims=True)    # [1, d(c)]
            den = jnp.sum(dens[h], axis=1, keepdims=True)      # [1, 1]
            y_ref[0, 0, h:h + 1, :] = num / den


def power_retention_update(q, k, v, log_g, active, reset, s_pool, z_pool, *,
                           layer=None, interpret: bool = False,
                           round_state: bool = False):
    """:func:`power_retention_update_xla` as a Pallas kernel, the pools
    updated IN PLACE: per (kv head, row) the slot's S and z stream through
    VMEM once, ``S <- g S + v feat(k)^T``, and the head group's queries read
    the new state before it is written back. Rows that are not ``active``
    move no data. Never the reference: it runs the kernel or raises."""
    reason = None if interpret else retention_kernel_unsupported_reason(
        q.shape[-1])
    if reason is not None:
        raise ValueError("power_retention_update: " + reason)
    layer, s_all, z_all, one = _stacked(layer, s_pool, z_pool)
    b, hkv, groups, d = q.shape
    xr = -(-(groups + 3) // 8) * 8
    f32 = jnp.float32
    x = jnp.concatenate([
        q.astype(f32), k.astype(f32)[:, :, None], v.astype(f32)[:, :, None],
        jnp.broadcast_to(jnp.exp(log_g.astype(f32))[:, :, None, None],
                         (b, hkv, 1, d)),
        jnp.zeros((b, hkv, xr - groups - 3, d), f32),
    ], axis=2)
    amap, mode = _row_plan(active, reset)
    rows, nd = state_rows(d), z_rows(d)
    x_spec = pl.BlockSpec((1, 1, xr, d), lambda h, r, *_: (r, h, 0, 0))
    s_spec = pl.BlockSpec(
        (1, 1, 1, d, rows), lambda h, r, l, am, _: (l[0], am[r], h, 0, 0))
    z_spec = pl.BlockSpec(
        (1, 1, 1, nd, d), lambda h, r, l, am, _: (l[0], am[r], h, 0, 0))
    y, s_all, z_all = pl.pallas_call(
        functools.partial(_update_kernel, groups=groups, interpret=interpret,
                          round_state=round_state),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,            # layer, amap, mode
            grid=(hkv, b),
            in_specs=[x_spec, s_spec, z_spec],
            out_specs=[x_spec, s_spec, z_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, xr, d), f32),
                   jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct(z_all.shape, z_all.dtype)],
        # operands count the scalar prefetch: 3 + (x, s, z)
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="power_retention_update",
    )(layer, amap, mode, x, s_all, z_all)
    y = y[:, :, :groups]
    return (y, s_all[0], z_all[0]) if one else (y, s_all, z_all)


def _chunk_kernel(
    # scalar prefetch (SMEM)
    layer_ref, mode_ref, start_ref, len_ref,
    # inputs, one kv head of the whole flat token axis: q [1, G, Tp, d];
    # k, v [1, Tp, d]; cum [1, Tp, C] the row's inclusive sum of log g on
    # every lane; the pools [L, slots, Hkv, ...] stay where they are (ANY),
    # aliased to the outputs and unused
    q_ref, k_ref, v_ref, cum_ref, s_in, z_in,
    # outputs: y [1, G, Tp, d] and the pools again
    y_ref, s_hbm, z_hbm,
    # scratch: one (slot, head) of the state, and its copies' semaphores
    s_buf, z_buf, sems,
    *, groups: int, chunk: int, interpret: bool, round_state: bool,
):
    del s_in, z_in
    head, row = pl.program_id(0), pl.program_id(1)
    mode = mode_ref[row]
    layer = layer_ref[0]
    d = k_ref.shape[-1]
    c = chunk
    nd = n_shifts(d)
    w = shift_weights(d)
    f32 = jnp.float32

    @pl.when(row == 0)
    def _clear():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    def copies(store):
        """The slot's S and z of this head: HBM -> scratch, or back."""
        out = []
        for i, (hbm, buf) in enumerate(((s_hbm, s_buf), (z_hbm, z_buf))):
            ends = (hbm.at[layer, row, head], buf)
            out.append(pltpu.make_async_copy(
                *(ends[::-1] if store else ends), sems.at[i]))
        return out

    # A row that is not asked for costs its grid step and nothing else: the
    # state is fetched by hand only for the rows that have a chunk (a block
    # spec would move a slot's worth of bytes for every step it skips).
    @pl.when((mode == 1) | (mode == 2))
    def _chunk():
        start = pl.multiple_of(start_ref[row], 8)
        n = len_ref[row]

        @pl.when(mode == 1)
        def _fetch():
            for cp in copies(store=False):
                cp.start()
            for cp in copies(store=False):
                cp.wait()

        @pl.when(mode == 2)
        def _fresh():                 # the last owner's state counts as zero
            s_buf[...] = jnp.zeros(s_buf.shape, f32)
            z_buf[...] = jnp.zeros(z_buf.shape, f32)

        ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

        def sub(j, carry):
            t0 = pl.multiple_of(start + j * c, 8)
            nj = jnp.minimum(n - j * c, c)
            base = jnp.where(
                j == 0, 0.0, cum_ref[0, pl.ds(jnp.maximum(t0 - 1, 0), 1), :])
            last = cum_ref[0, pl.ds(t0 + nj - 1, 1), :] - base     # [1, C]
            live = ti < nj                                         # [C, C] by t
            cum_t = jnp.where(live, cum_ref[0, pl.ds(t0, c), :] - base, last)
            cum_s = cum_t.T
            causal = (si <= ti) & live
            decay = jnp.where(causal, jnp.exp(jnp.where(
                causal, cum_t - cum_s, 0.0)), 0.0)                 # [C, C]
            e_t = jnp.exp(cum_t[:, :1])                            # [C, 1]
            live_c = live[:, :1]
            kk = k_ref[0, pl.ds(t0, c), :]                         # [C, d]
            vv = v_ref[0, pl.ds(t0, c), :]
            for h in range(groups):
                qq = q_ref[0, h, pl.ds(t0, c), :]                  # [C, d]
                sc = _dot_nt(qq, kk)
                a = sc * sc * decay                                # [C, C]
                num = _dot(a, vv)                                  # [C, d]
                den = jnp.sum(a, axis=1, keepdims=True)            # [C, 1]
                pnum = jnp.zeros((c, d), f32)
                pden = jnp.zeros((c, d), f32)
                for s in range(nd):
                    fq = qq * _roll_lanes(qq, s, interpret)
                    if w[s] != 1.0:
                        fq = fq * w[s]
                    pnum = pnum + _dot_nt(fq, s_buf[:, s * d:(s + 1) * d])
                    pden = pden + fq * z_buf[s:s + 1, :]
                num = num + e_t * pnum
                den = den + e_t * jnp.sum(pden, axis=1, keepdims=True)
                y = num / jnp.where(live_c, den, 1.0)
                y_old = y_ref[0, h, pl.ds(t0, c), :]
                y_ref[0, h, pl.ds(t0, c), :] = jnp.where(live_c, y, y_old)
            # one update of the state for the whole sub-chunk
            d_end = jnp.where(live_c, jnp.exp(last[:, :1] - cum_t[:, :1]), 0.0)
            # [1, d]: a [1, 1] cannot be spread over sublanes and lanes at once
            g_tot = jnp.exp(jnp.broadcast_to(last[:, :1], (1, d)))
            v_t = (vv * d_end).T                                   # [d(c), C]
            for s in range(nd):
                fk = kk * _roll_lanes(kk, s, interpret)            # [C, d(i)]
                lanes = slice(s * d, (s + 1) * d)
                s_buf[:, lanes] = _stored(
                    g_tot * s_buf[:, lanes] + _dot(v_t, fk), round_state)
                z_buf[s:s + 1, :] = _stored(
                    g_tot * z_buf[s:s + 1, :]
                    + jnp.sum(fk * d_end, axis=0, keepdims=True), round_state)
            return carry

        jax.lax.fori_loop(0, (n + c - 1) // c, sub, 0)
        for cp in copies(store=True):
            cp.start()
        for cp in copies(store=True):
            cp.wait()


def power_retention_chunk(q, k, v, log_g, row_starts, row_lens, active, reset,
                          s_pool, z_pool, *, layer=None, chunk: int = _CHUNK,
                          interpret: bool = False, round_state: bool = False):
    """:func:`power_retention_chunk_xla` as a Pallas kernel, the pools
    updated IN PLACE: per (kv head, row) the slot's state is fetched once,
    the row's tokens pass in sub-chunks of ``chunk`` (inside-chunk term,
    the prior state's term, one state update each), and the state is written
    back once. ``row_starts`` must be multiples of 8 (the engine's ragged
    layout aligns them). Rows that are not ``active`` move no data and their
    tokens read 0. Never the reference."""
    reason = None if interpret else retention_kernel_unsupported_reason(
        q.shape[-1])
    if reason is not None:
        raise ValueError("power_retention_chunk: " + reason)
    layer, s_all, z_all, one = _stacked(layer, s_pool, z_pool)
    t, hkv, groups, d = q.shape
    b = row_starts.shape[0]
    f32 = jnp.float32
    tp = -(-(t + chunk) // 8) * 8
    cum = _row_cumsum(log_g, row_starts, row_lens, active, t)      # [T, Hkv]

    def heads_first(a):                 # [T, Hkv, ...] -> [Hkv, ..., Tp, d]
        a = jnp.pad(a.astype(f32), ((0, tp - t),) + ((0, 0),) * (a.ndim - 1))
        return jnp.moveaxis(a, 0, -2)

    q_h, k_h, v_h = heads_first(q), heads_first(k), heads_first(v)
    cum_h = jnp.broadcast_to(
        jnp.pad(cum, ((0, tp - t), (0, 0))).T[:, :, None], (hkv, tp, chunk))
    mode = _row_modes(active, reset)
    q_spec = pl.BlockSpec((1, groups, tp, d), lambda h, r, *_: (h, 0, 0, 0))
    t_spec = pl.BlockSpec((1, tp, d), lambda h, r, *_: (h, 0, 0))
    c_spec = pl.BlockSpec((1, tp, chunk), lambda h, r, *_: (h, 0, 0))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    y, s_all, z_all = pl.pallas_call(
        functools.partial(_chunk_kernel, groups=groups, chunk=chunk,
                          interpret=interpret, round_state=round_state),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,    # layer, mode, row_starts, row_lens
            grid=(hkv, b),
            in_specs=[q_spec, t_spec, t_spec, c_spec, anywhere, anywhere],
            out_specs=[q_spec, anywhere, anywhere],
            scratch_shapes=[
                pltpu.VMEM(s_all.shape[3:], f32),
                pltpu.VMEM(z_all.shape[3:], f32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((hkv, groups, tp, d), f32),
                   jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct(z_all.shape, z_all.dtype)],
        # operands count the scalar prefetch: 4 + (q, k, v, cum, s, z)
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="power_retention_chunk",
    )(layer, mode, row_starts.astype(jnp.int32),
      jnp.where(active, row_lens, 0).astype(jnp.int32),
      q_h, k_h, v_h, cum_h, s_all, z_all)
    y = jnp.moveaxis(y, 2, 0)[:t]                                  # [T,Hkv,G,d]
    return (y, s_all[0], z_all[0]) if one else (y, s_all, z_all)


# ------------------------------------------------------- attention form

def power_retention_attention(q, k, v, log_g):
    """The attention form over whole sequences, no state: q [B, S, Hkv, G, d];
    k, v [B, S, Hkv, d]; log_g [B, S, Hkv] -> y [B, S, Hkv, G, d] float32. The
    model's full causal ``apply`` and the tests' second opinion."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = q.shape[1]
    cum = jnp.cumsum(log_g.astype(jnp.float32), axis=1)            # [B,S,Hkv]
    sc = jnp.einsum("bthgd,bshd->bhgts", q, k, precision=_HI) ** 2
    tri = jnp.tril(jnp.ones((s, s), bool))
    diff = (cum[:, :, None] - cum[:, None, :]).transpose(0, 3, 1, 2)
    a = jnp.where(tri, sc * jnp.exp(jnp.where(tri, diff, 0.0))[:, :, None], 0.0)
    num = jnp.einsum("bhgts,bshc->bthgc", a, v, precision=_HI)
    den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)
    return num / den[..., None]
