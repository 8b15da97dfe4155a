"""Attention over a LATENT paged pool: Pallas TPU kernels + XLA twin.

A latent layer (multi-head latent attention, DeepSeek-V2's MLA) caches one
row a token for ALL heads: the compressed vector c_s and the rotated key
k^R_s, ``[c_s ; k^R_s ; 0]`` padded to whole 128-lane tiles. In the absorbed
form every head scores that row directly (q~ = [W^UK^T q^N ; q^R ; 0]) and
the value is the row's first ``v_width`` lanes (W^UV is applied after the
sum), so the pool is K and V at once and is read once:

    pool         [L, 1, N_pages, P, W]  the layer kind's plane of the page
                                        pool (llm/kv_cache.py, latent
                                        layout); ``layer`` names the plane's
                                        layer
    q            [T, H, W]              absorbed queries, the score scale
                                        folded in
    out          [T, H, v_width]        sum_s a_ts c_s

Which keys a query sees is an ARGUMENT of the two entry points, not a kernel
of its own: the causal bound always, a ``window`` beside it (query at
position p sees p - window < s <= p), or a ``selected`` set of (page,
offset) coordinates a token (a learned top-k: the rows are gathered out of
the pages token by token, by XLA, into a compact buffer that the same kernel
then walks as a contiguous run of pages; with a selected set every token is
a row of its own, because no two tokens share their keys).

One kernel body serves both entry points (``latent_attention_decode``: a row
is one token; ``latent_ragged_attention``: the ragged planner's work items,
ops.paged_attention.ragged_work_items, a tile of a row's queries an item).
The grid is (items, key blocks); a key block is ``pages_per_step`` pages, each
an operand of its own whose index map reads the page table in SMEM, so the
pipeline's own double buffering fetches block j+1 while block j is in the
flash update. Steps past an item's last page repeat that page's index (no
copy) and skip the update. A dead item (no row, no queries) reads page 0 and
writes a spare block past the output's end.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_STEP_KEYS = 128        # keys a grid step scores (pages_per_step * page)
_GATHER_PAGE = 256      # page size of the compact buffer of a selected set
_VMEM_LIMIT = 64 << 20


# ------------------------------------------------------------------ XLA twin

def latent_attention_xla(q, pool, page_table, tok_row, tok_pos, tok_valid, *,
                         layer, window=0, selected=None, v_width):
    """The twin of both kernels, token by token (the CPU path, and what the
    tests hold the kernels to): query ``t`` belongs to row ``tok_row[t]`` at
    absolute position ``tok_pos[t]`` and sees the cached positions
    ``tok_pos[t] - window < s <= tok_pos[t]`` of its row (``window`` 0: every
    ``s <= tok_pos[t]``), or, with ``selected`` = (page [T, K], offset
    [T, K], n [T]), the first ``n[t]`` of its K coordinates. An invalid
    token attends nothing and reads zeros. It gathers a whole row's pages a
    token: for small shapes only."""
    if selected is None:
        p = pool.shape[3]
        ctx = pool[layer, 0][page_table]                     # [R, PP, P, W]
        ctx = ctx.reshape(ctx.shape[0], -1, ctx.shape[-1])
        keys = ctx[tok_row]                                  # [T, S, W]
        s_pos = jnp.arange(page_table.shape[1] * p, dtype=jnp.int32)[None]
        vis = s_pos <= tok_pos[:, None]
        if window:
            vis &= s_pos > tok_pos[:, None] - window
    else:
        sel_page, sel_off, n_sel = selected
        keys = pool[layer, 0, sel_page, sel_off]             # [T, K, W]
        vis = (
            jnp.arange(sel_page.shape[1], dtype=jnp.int32)[None]
            < n_sel[:, None]
        )
    vis &= tok_valid[:, None]
    scores = jnp.einsum("thw,tsw->ths", q, keys,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(vis[:, None], scores, _NEG)
    top = jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.where(vis[:, None], jnp.exp(scores - top), 0.0)
    denom = jnp.sum(probs, axis=-1, keepdims=True)
    probs = probs / jnp.where(denom == 0.0, 1.0, denom)
    out = jnp.einsum("ths,tsv->thv", probs.astype(keys.dtype),
                     keys[..., :v_width],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def latent_kv_write_xla(pool, rows, write_page, write_offset, *, layer):
    """``rows`` [T, W] stored at ``pool[layer, 0, write_page[t],
    write_offset[t]]``; every index given, so that the scatter's window is
    one row (ops.paged_attention.paged_kv_write_xla says why)."""
    return pool.at[layer, 0, write_page, write_offset].set(
        rows.astype(pool.dtype)
    )


# ------------------------------------------------------------------- kernels

def _first_page(pos0, window, page):
    if not window:
        return jnp.int32(0)
    return jnp.maximum(pos0 - (window - 1), 0) // page


def _attention_kernel(
    # scalar prefetch (SMEM)
    layer_ref, qblk_ref, row_ref, pos0_ref, nq_ref, table_ref,
    # q [tq, H, W], then pages_per_step pages [P, W]
    q_ref, *rest,
    tq, heads, window, v_width, page, pages_per_step, n_kb,
):
    del layer_ref, qblk_ref, row_ref, table_ref
    key_refs = rest[:pages_per_step]
    out_ref, m_ref, l_ref, acc_ref = rest[pages_per_step:]
    i, j = pl.program_id(0), pl.program_id(1)
    pos0, nq = pos0_ref[i], nq_ref[i]
    m_rows = tq * heads
    step_keys = pages_per_step * page

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    key0 = (_first_page(pos0, window, page) + j * pages_per_step) * page

    @pl.when(jnp.logical_and(nq > 0, key0 <= pos0 + nq - 1))
    def _update():
        keys = (
            key_refs[0][...] if pages_per_step == 1
            else jnp.concatenate([r[...] for r in key_refs], axis=0)
        )                                                    # [SK, W]
        q = q_ref[...].reshape(m_rows, q_ref.shape[-1])
        s = jax.lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                    # [M, SK]
        key_pos = key0 + jax.lax.broadcasted_iota(
            jnp.int32, (m_rows, step_keys), 1
        )
        if tq == 1:
            q_pos = pos0
            vis = key_pos <= q_pos
        else:
            qi = jax.lax.broadcasted_iota(
                jnp.int32, (m_rows, step_keys), 0
            ) // heads
            q_pos = pos0 + qi
            vis = jnp.logical_and(key_pos <= q_pos, qi < nq)
        if window:
            vis = jnp.logical_and(vis, key_pos > q_pos - window)
        s = jnp.where(vis, s, _NEG)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(vis, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(keys.dtype), keys[:, :v_width],
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_kb - 1)
    def _store():
        denom = l_ref[:, :1]
        out = acc_ref[...] / jnp.where(denom == 0.0, 1.0, denom)
        out_ref[...] = out.reshape(out_ref.shape).astype(out_ref.dtype)


def _attention(q, pool, table, layer, item_qblk, item_row, item_pos0,
               item_nq, *, tq, window, v_width, pages_per_item, name,
               interpret):
    """The kernel over a work plan. ``q`` [NQ, H, W] in blocks of ``tq``
    tokens; item ``i`` owns q block ``item_qblk[i]`` (its first query at
    position ``item_pos0[i]`` of row ``item_row[i]``, ``item_nq[i]`` real
    queries). ``table`` [R, PP] maps a row's pages into ``pool`` [L, 1, N,
    P, W]; ``table`` None: row r's pages are the ``pages_per_item`` pages
    from r * pages_per_item on."""
    nq_tokens, heads, width = q.shape
    page = pool.shape[3]
    n_qblk = nq_tokens // tq
    n_items = item_row.shape[0]
    pages_per_step = max(1, _STEP_KEYS // page)
    if table is None:
        row_pages = pages_per_item
    elif window:
        row_pages = min(table.shape[1], -(-(window + tq + page - 2) // page) + 1)
    else:
        row_pages = table.shape[1]
    pages_per_step = min(pages_per_step, row_pages)
    n_kb = -(-row_pages // pages_per_step)
    contiguous = table is None
    if contiguous:
        table = jnp.zeros((1, 1), jnp.int32)

    def q_map(i, j, layer_r, qblk, row, pos0, nq, tab):
        return (jnp.minimum(qblk[i], n_qblk - 1), 0, 0)

    def out_map(i, j, layer_r, qblk, row, pos0, nq, tab):
        return (qblk[i], 0, 0)

    def key_map(kk, i, j, layer_r, qblk, row, pos0, nq, tab):
        r = jnp.maximum(row[i], 0)
        last = jnp.maximum(pos0[i] + jnp.maximum(nq[i], 1) - 1, 0) // page
        pp = jnp.minimum(
            _first_page(pos0[i], window, page) + j * pages_per_step + kk, last
        )
        pg = r * pages_per_item + pp if contiguous else tab[r, pp]
        live = jnp.logical_and(row[i] >= 0, nq[i] > 0)
        return (layer_r[0], 0, jnp.where(live, pg, 0), 0, 0)

    m_rows = tq * heads
    out = pl.pallas_call(
        functools.partial(
            _attention_kernel, tq=tq, heads=heads, window=window,
            v_width=v_width, page=page, pages_per_step=pages_per_step,
            n_kb=n_kb,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n_items, n_kb),
            in_specs=[pl.BlockSpec((tq, heads, width), q_map)] + [
                pl.BlockSpec((None, None, None, page, width),
                             functools.partial(key_map, kk))
                for kk in range(pages_per_step)
            ],
            out_specs=pl.BlockSpec((tq, heads, v_width), out_map),
            scratch_shapes=[
                pltpu.VMEM((m_rows, 128), jnp.float32),
                pltpu.VMEM((m_rows, 128), jnp.float32),
                pltpu.VMEM((m_rows, v_width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (nq_tokens + tq, heads, v_width), q.dtype
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name=name,
    )(layer, item_qblk, item_row, item_pos0, item_nq, table, q,
      *([pool] * pages_per_step))
    return out[:nq_tokens]


def _layer_scalar(layer):
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _selected_rows(pool, selected, layer):
    """The selected rows of every token, gathered token by token out of the
    pages into a compact [1, 1, T * K / GP, GP, W] pool of its own."""
    sel_page, sel_off, _ = selected
    t, k = sel_page.shape
    with jax.named_scope("index_gather"):
        rows = pool[layer, 0, sel_page, sel_off]             # [T, K, W]
    gp = math.gcd(k, _GATHER_PAGE)
    return rows.reshape(1, 1, t * k // gp, gp, rows.shape[-1]), k // gp


def _attend_selected(q, pool, selected, live, *, layer, v_width, name,
                     interpret):
    """Every token its own item over its gathered set: the first ``n`` of
    its keys, no window (the set IS the bound)."""
    t = q.shape[0]
    rows, pages_per_item = _selected_rows(pool, selected, layer)
    ids = jnp.arange(t, dtype=jnp.int32)
    return _attention(
        q, rows, None, _layer_scalar(0), ids, ids,
        jnp.maximum(selected[2] - 1, 0).astype(jnp.int32),
        jnp.where(jnp.logical_and(live, selected[2] > 0), 1, 0).astype(
            jnp.int32),
        tq=1, window=0, v_width=v_width, pages_per_item=pages_per_item,
        name=name, interpret=interpret,
    )


def latent_attention_decode(q, pool, page_table, lengths, *, layer, v_width,
                            window=0, selected=None, interpret=False):
    """One query token a row: ``q`` [B, H, W] against row b's first
    ``lengths[b]`` cached tokens (its own, just written, is the last; 0 =
    the row attends nothing and reads zeros), bounded by ``window`` or by
    the ``selected`` coordinates of each row. Returns [B, H, v_width]."""
    b = q.shape[0]
    live = lengths > 0
    if selected is not None:
        return _attend_selected(
            q, pool, selected, live, layer=layer, v_width=v_width,
            name="latent_attention_decode", interpret=interpret,
        )
    ids = jnp.arange(b, dtype=jnp.int32)
    return _attention(
        q, pool, page_table, _layer_scalar(layer), ids, ids,
        jnp.maximum(lengths - 1, 0).astype(jnp.int32),
        live.astype(jnp.int32),
        tq=1, window=window, v_width=v_width, pages_per_item=0,
        name="latent_attention_decode", interpret=interpret,
    )


def latent_ragged_attention(q, pool, page_table=None, kv_lens=None,
                            row_starts=None, row_lens=None, item_rows=None,
                            item_q0=None, *, layer, v_width, tile=None,
                            window=0, selected=None, tok_valid=None,
                            interpret=False):
    """A ragged mixed batch (ops.paged_attention's layout: row r's queries
    at ``q[row_starts[r] : row_starts[r] + row_lens[r]]`` of the aligned
    view, at positions ``kv_lens[r] - row_lens[r] + i``) over the planner's
    work items of ``tile`` queries. With ``selected`` the queries come on
    the COMPACT axis instead ([C, H, W], ``tok_valid`` [C]), each with its
    own coordinates, and the row map is not read."""
    if selected is not None:
        return _attend_selected(
            q, pool, selected, tok_valid, layer=layer, v_width=v_width,
            name="latent_ragged_attention", interpret=interpret,
        )
    live = item_rows >= 0
    rows = jnp.maximum(item_rows, 0)
    n_qblk = q.shape[0] // tile
    qblk = jnp.where(live, (row_starts[rows] + item_q0) // tile, n_qblk)
    nq = jnp.where(live, jnp.clip(row_lens[rows] - item_q0, 0, tile), 0)
    pos0 = jnp.maximum(kv_lens[rows] - row_lens[rows] + item_q0, 0)
    return _attention(
        q, pool, page_table, _layer_scalar(layer),
        qblk.astype(jnp.int32), item_rows.astype(jnp.int32),
        pos0.astype(jnp.int32), nq.astype(jnp.int32),
        tq=tile, window=window, v_width=v_width, pages_per_item=0,
        name="latent_ragged_attention", interpret=interpret,
    )


# ------------------------------------------------------------------ KV write

def _write_kernel(page_ref, off_ref, layer_ref, new_ref, pool_in, pool_hbm,
                  buf, sems, *, block):
    """ops.paged_attention._kv_write_kernel for one plane of one head: runs
    of tokens that share a page are one fetch of the page, a patch of its
    rows in VMEM and one store; two page buffers, so the fetch of run j+1
    flies while run j is patched, and a page is never read while a store to
    it is in flight."""
    del pool_in
    base = pl.program_id(0) * block
    layer = layer_ref[0]
    p = buf.shape[1]

    def copy(page, slot, store):
        ends = (pool_hbm.at[layer, 0, page], buf.at[slot])
        return pltpu.make_async_copy(
            *(ends[::-1] if store else ends), sems.at[slot, store]
        )

    def run_end(t0, page):
        return jax.lax.while_loop(
            lambda t: jnp.logical_and(
                t < block, page_ref[base + jnp.minimum(t, block - 1)] == page
            ),
            lambda t: t + 1, t0 + 1,
        )

    rows = jax.lax.broadcasted_iota(jnp.int32, (p, buf.shape[2]), 0)

    def patch(t, slot):
        here = rows == off_ref[base + t]
        buf[slot] = jnp.where(here, new_ref[pl.ds(t, 1), 0, :], buf[slot])

    page0 = page_ref[base]
    copy(page0, 0, 0).start()

    def run(carry):
        t0, j, page, prev = carry
        slot = jax.lax.rem(j, 2)
        t1 = run_end(t0, page)
        nxt = page_ref[base + jnp.minimum(t1, block - 1)]
        copy(page, slot, 0).wait()

        @pl.when(t1 < block)
        def _prefetch():
            @pl.when(j > 0)
            def _landed():
                copy(prev, 1 - slot, 1).wait()

            copy(nxt, 1 - slot, 0).start()

        jax.lax.fori_loop(t0, t1, lambda t, c: (patch(t, slot), c)[1], 0)
        copy(page, slot, 1).start()
        return t1, j + 1, nxt, page

    _, n_runs, _, last = jax.lax.while_loop(
        lambda c: c[0] < block, run, (0, 0, page0, page0)
    )

    @pl.when(n_runs > 1)
    def _before_last():
        copy(last, jax.lax.rem(n_runs, 2), 1).wait()

    copy(last, jax.lax.rem(n_runs - 1, 2), 1).wait()


def latent_kv_write(pool, rows, write_page, write_offset, *, layer,
                    interpret=False):
    """Pallas write of a launch's new rows ([T, W]) into one plane of the
    latent pool, IN PLACE: :func:`latent_kv_write_xla`'s result wherever
    coordinates are not duplicated (of duplicates the last wins; the
    engine's only duplicates are its pads on the null page)."""
    t, width = rows.shape
    page = pool.shape[3]
    fit = (2 << 20) // (16 * width * pool.dtype.itemsize)
    block = max(b for b in range(1, min(t, max(fit, 1)) + 1) if t % b == 0)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_write_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(t // block,),
            # [T, 1, W]: the token is an untiled axis, so a row is read at
            # any index (a [T, W] operand packs 16 rows a tile)
            in_specs=[pl.BlockSpec((block, 1, width),
                                   lambda i, *_: (i, 0, 0)),
                      anywhere],
            out_specs=anywhere,
            scratch_shapes=[
                pltpu.VMEM((2, page, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
        name="latent_kv_write",
    )(write_page, write_offset, _layer_scalar(layer),
      rows.astype(pool.dtype)[:, None], pool)


# ----------------------------------------------------- the learned selection

def index_select(q_idx, w_idx, key_pool, page_table, tok_row, tok_pos,
                 tok_valid, *, layer, topk, block_keys=1024):
    """The indexer of a full layer, in XLA: score every cached key of a
    token's row and keep the ``topk`` best, EXACTLY (``lax.top_k``; an
    approximate top-k is another result).

    I_ts = H^-1/2 D^-1/2 sum_j w_tj ReLU(q_tj . k_s) over the keys
    s <= tok_pos[t] of row tok_row[t]; ``q_idx`` [T, J, D], ``w_idx``
    [T, J], ``key_pool`` [L, 1, N, P, D]. The keys of a row are read page by
    page through ``page_table`` in blocks of ``block_keys`` (the [T, J,
    block] scores of a block are the largest temporary). Returns (page
    [T, K], offset [T, K], n [T], position [T, K]): the coordinates of the
    chosen keys best first, K = min(topk, the table's tokens), and how many
    of them are real (all visible keys while there are no more than K)."""
    t, heads, dim = q_idx.shape
    page = key_pool.shape[3]
    pp = page_table.shape[1]
    block_pages = max(1, min(block_keys // page, pp))
    n_blocks = -(-pp // block_pages)
    padded = jnp.pad(page_table, ((0, 0), (0, n_blocks * block_pages - pp)))
    blocks = padded.reshape(-1, n_blocks, block_pages).swapaxes(0, 1)
    scale = (heads * dim) ** -0.5
    w32 = w_idx.astype(jnp.float32) * scale

    with jax.named_scope("index_score"):
        def score(pages):                                    # [R, BP]
            keys = key_pool[layer, 0, pages]                 # [R, BP, P, D]
            keys = keys.reshape(keys.shape[0], -1, keys.shape[-1])
            keys = keys[tok_row][..., :dim]      # a plane's row is padded
            s = jnp.einsum("tjd,tsd->tjs", q_idx, keys,
                           preferred_element_type=jnp.float32)
            return jnp.sum(jax.nn.relu(s) * w32[:, :, None], axis=1)

        scores = jax.lax.map(score, blocks)                  # [NB, T, BK]
        scores = scores.swapaxes(0, 1).reshape(t, -1)[:, :pp * page]
        s_pos = jnp.arange(pp * page, dtype=jnp.int32)[None]
        vis = jnp.logical_and(s_pos <= tok_pos[:, None], tok_valid[:, None])
        scores = jnp.where(vis, scores, -jnp.inf)
    with jax.named_scope("index_topk"):
        k = min(int(topk), pp * page)
        _, pos = jax.lax.top_k(scores, k)                    # [T, K]
        n = jnp.where(tok_valid, jnp.minimum(tok_pos + 1, k), 0)
        sel_page = page_table[tok_row[:, None], pos // page]
        sel_off = pos % page
    return sel_page, sel_off, n.astype(jnp.int32), pos
