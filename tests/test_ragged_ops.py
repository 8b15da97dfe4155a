"""Ragged paged attention kernel tests (docs/ragged_attention.md): the
mixed prefill+decode Pallas kernel (interpret mode) against the ragged XLA
reference, the ragged reference against the per-row decode/dense references,
and the host-side layout and work plan (ISSUE 30: a grid step per row or
query tile of a row)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from clearml_serving_tpu.ops.paged_attention import (
    SMEM_BYTES,
    paged_attention,
    paged_attention_xla,
    paged_kernel_smem_bytes,
    paged_kernel_unsupported_reason,
    paged_kv_write,
    paged_kv_write_xla,
    ragged_item_count,
    ragged_layout,
    ragged_paged_attention,
    ragged_paged_attention_xla,
    ragged_query_tile,
    ragged_work_items,
)
from clearml_serving_tpu.ops import paged_attention as pa


def _quantize_pool(pool):
    """Per-(token, head) symmetric int8, mirroring models/llama._kv_store."""
    x = np.asarray(pool, np.float32)
    absmax = np.abs(x).max(axis=-1)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(x / scale[..., None]), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(scale)


def _setup(key, *, rows=4, hkv=2, g=2, d=64, page=16, pages_per_seq=6,
           row_lens=(1, 5, 1, 12), kv_extra=(7, 0, 30, 0), dtype=jnp.float32,
           slack=0):
    """Build a mixed batch: row_lens[r] query tokens per row (1 = decode),
    kv_lens = history + chunk, shuffled page tables. Returns the full
    operand set plus the work plan at the tile the kernel derives from
    these shapes NOW (a test that shrinks the tile patches it first);
    ``slack`` pads the token axis and the plan as the engine's static
    shapes do."""
    ks = jax.random.split(key, 3)
    n_pages = rows * pages_per_seq + 1
    k_pool = jax.random.normal(ks[0], (hkv, n_pages, page, d), jnp.float32)
    v_pool = jax.random.normal(ks[1], (hkv, n_pages, page, d), jnp.float32)
    order = np.random.RandomState(0).permutation(n_pages - 1) + 1
    page_table = order[: rows * pages_per_seq].reshape(rows, pages_per_seq)
    row_lens = np.asarray(row_lens, np.int32)
    kv_lens = row_lens + np.asarray(kv_extra, np.int32)
    assert kv_lens.max() <= pages_per_seq * page
    starts, t_pad = ragged_layout(row_lens)
    t_pad += 8 * slack
    q = jax.random.normal(ks[2], (t_pad, hkv, g, d), jnp.float32)
    tile = ragged_query_tile(hkv, g, d, dtype)
    item_rows, item_q0 = ragged_work_items(
        row_lens, tile,
        total=ragged_item_count(rows, t_pad, tile) if slack else None,
    )
    return (
        q.astype(dtype), k_pool.astype(dtype), v_pool.astype(dtype),
        jnp.asarray(page_table.astype(np.int32)), jnp.asarray(kv_lens),
        jnp.asarray(starts), jnp.asarray(row_lens),
        jnp.asarray(item_rows), jnp.asarray(item_q0),
    )


def _small_plan(monkeypatch, tile=16, sub_rows=16, block_tokens=32):
    """Tiles, sub tiles and context blocks small enough that short rows get
    several of each: the three follow from module constants and the shapes,
    no caller sets them (docs/ragged_attention.md)."""
    monkeypatch.setattr(pa, "_RAGGED_TILE_QUERIES", tile)
    monkeypatch.setattr(pa, "_RAGGED_SUB_ROWS", sub_rows)
    monkeypatch.setattr(pa, "_DECODE_BLOCK_TOKENS", block_tokens)


def _check(args, tol=2e-5, **kw):
    """Kernel (interpreted) against the XLA reference on every token: the
    ones no item owns read zeros in both."""
    *operands, item_rows, item_q0 = args
    ref = ragged_paged_attention_xla(*operands, **kw)
    out = ragged_paged_attention(
        *operands, item_rows=item_rows, item_q0=item_q0, interpret=True, **kw
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )
    return out


def test_ragged_layout_alignment():
    starts, t_pad = ragged_layout([1, 5, 0, 12], 8)
    assert t_pad % 8 == 0 and t_pad == 32
    # every row starts at a multiple of the 8 tokens a q / out copy moves
    assert list(starts[[0, 1, 3]]) == [0, 8, 16]
    # fixed `total` pads the token axis (static engine shapes)
    assert ragged_layout([1, 5, 0, 12], 8, total=48)[1] == 48
    # the XLA reference's rows pack densely
    assert list(ragged_layout([1, 5, 0, 12], 1)[0]) == [0, 1, 6, 6]
    with pytest.raises(ValueError):
        ragged_layout([64], 8, total=32)


def test_ragged_work_items():
    """A decode row and a verify row are one item, a prompt chunk one per
    query tile, idle rows none; the list pads with items of no row."""
    rows, q0 = ragged_work_items([1, 5, 0, 40, 16], 16)
    assert list(rows) == [0, 1, 3, 3, 3, 4]
    assert list(q0) == [0, 0, 0, 16, 32, 0]
    rows, q0 = ragged_work_items([1, 5, 0, 40, 16], 16, total=9)
    assert list(rows[6:]) == [-1, -1, -1] and list(q0[6:]) == [0, 0, 0]
    # the static length the engine uses holds any batch on its token axis
    assert ragged_item_count(5, 72, 16) == 9
    with pytest.raises(ValueError, match="work items"):
        ragged_work_items([40, 40], 16, total=5)
    assert list(ragged_work_items([0, 0], 16)[0]) == [-1]


def test_ragged_query_tile_follows_from_the_shapes():
    """128 queries at the cells' shapes (Hkv 8, G 4, D 128, bf16: 9.4 MB of
    flash state and q / out buffers), whole sub tiles of 128 MXU rows a
    head, fewer where the state would not fit the scratch budget."""
    assert ragged_query_tile(8, 4, 128, jnp.bfloat16) == 128
    assert ragged_query_tile(8, 1, 128, jnp.bfloat16) == 128
    assert ragged_query_tile(8, 8, 128, jnp.bfloat16) == 80   # 5 x 16
    assert ragged_query_tile(16, 8, 256, jnp.float32) == 16   # one sub tile


def test_ragged_xla_decode_rows_match_decode_reference():
    """All-decode ragged batch == the decode reference, row for row."""
    args = _setup(jax.random.PRNGKey(0), row_lens=(1, 1, 1, 1),
                  kv_extra=(4, 17, 30, 0))
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     _br, _bq) = args
    out = ragged_paged_attention_xla(
        q, k_pool, v_pool, page_table, kv_lens, starts, row_lens
    )
    # the decode reference consumes one query per row
    q_rows = jnp.stack([q[int(s)] for s in starts])        # [R, Hkv, G, D]
    ref = paged_attention_xla(q_rows, k_pool, v_pool, page_table, kv_lens)
    for r, s in enumerate(np.asarray(starts)):
        np.testing.assert_allclose(
            np.asarray(out[int(s)]), np.asarray(ref[r]), rtol=1e-6, atol=1e-6
        )


def test_ragged_xla_prefill_row_matches_dense_causal():
    """A prefill row's chunk must see its history + its own causal
    triangle — checked against an explicit dense softmax."""
    args = _setup(
        jax.random.PRNGKey(1), rows=1, hkv=2, g=2, d=32, page=8,
        pages_per_seq=4, row_lens=(6,), kv_extra=(10,),
    )
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     _br, _bq) = args
    out = ragged_paged_attention_xla(
        q, k_pool, v_pool, page_table, kv_lens, starts, row_lens
    )
    kv_len, row_len = int(kv_lens[0]), int(row_lens[0])
    base = kv_len - row_len
    pages = np.asarray(page_table[0])
    k = np.asarray(k_pool[:, pages]).reshape(2, -1, 32)
    v = np.asarray(v_pool[:, pages]).reshape(2, -1, 32)
    for i in range(row_len):
        bound = base + i + 1
        qi = np.asarray(q[i])                               # [Hkv, G, D]
        for h in range(2):
            scores = qi[h] @ k[h, :bound].T * (32 ** -0.5)  # [G, bound]
            p = np.exp(scores - scores.max(axis=-1, keepdims=True))
            p = p / p.sum(axis=-1, keepdims=True)
            want = p @ v[h, :bound]
            np.testing.assert_allclose(
                np.asarray(out[i, h]), want, rtol=1e-5, atol=1e-5
            )


@pytest.mark.parametrize("page", [16, 32])
@pytest.mark.parametrize("block_pages", [1, 2, 4])
def test_ragged_kernel_interpret_matches_xla(monkeypatch, page, block_pages):
    """Mixed row phases x page sizes x context blocks of 1, 2 and 4 pages,
    including a partial final page (kv not page-aligned), an idle row and a
    chunk of two tiles."""
    _small_plan(monkeypatch, tile=8, block_tokens=block_pages * page)
    _check(_setup(
        jax.random.PRNGKey(2), rows=5, hkv=2, g=2, d=64, page=page,
        pages_per_seq=4, row_lens=(1, 9, 1, 13, 0),
        kv_extra=(page * 2 + 3, 5, 0, 7, 0),
    ))


DECODE, VERIFY, CHUNK = (1, 40), (5, 61), (21, 13)   # (queries, history)


@pytest.mark.parametrize("order", [
    (DECODE, VERIFY, CHUNK), (DECODE, CHUNK, VERIFY), (VERIFY, DECODE, CHUNK),
    (VERIFY, CHUNK, DECODE), (CHUNK, DECODE, VERIFY), (CHUNK, VERIFY, DECODE),
], ids=lambda o: "-".join(str(n) for n, _ in o))
def test_ragged_kernel_rows_in_every_order(monkeypatch, order):
    """A decode row, a verify row and a two-tile chunk row in every order:
    whichever follows which, the next item's first block and queries are
    fetched while the one before is computed."""
    _small_plan(monkeypatch)
    _check(_setup(
        jax.random.PRNGKey(7), rows=3, pages_per_seq=5,
        row_lens=[n for n, _ in order], kv_extra=[h for _, h in order],
        slack=1,
    ))


@pytest.mark.parametrize("queries", [1, 7, 8, 9, 16, 17, 128])
def test_ragged_kernel_chunk_rows_of_every_size(monkeypatch, queries):
    """A chunk of 1, 7, 8 (one copy), 9, one tile (16), one tile + 1 and 128
    queries (eight tiles) between two decode rows, on a history that ends
    mid-page."""
    _small_plan(monkeypatch)
    _check(_setup(
        jax.random.PRNGKey(8), rows=3, pages_per_seq=10,
        row_lens=(1, queries, 1), kv_extra=(20, 27, 3),
    ))


@pytest.mark.parametrize("history, queries", [
    (16, 16),    # the chunk fills page 2 to its edge
    (15, 17),    # context ends at a block edge (32) exactly
    (32, 32),    # history is one whole block, the chunk the next
    (70, 20),    # several blocks, the last one partial
    (0, 90),     # no history: the triangle alone, over three blocks
], ids=["page-edge", "block-edge", "whole-blocks", "several", "triangle"])
def test_ragged_kernel_contexts_at_page_and_block_edges(
        monkeypatch, history, queries):
    _small_plan(monkeypatch)
    _check(_setup(
        jax.random.PRNGKey(9), rows=2, pages_per_seq=6,
        row_lens=(queries, 1), kv_extra=(history, 31),
    ))


@pytest.mark.parametrize("lens", [
    ((1, 1), (150, 3)), ((1, 1), (3, 150)),        # long decode row, short
    ((40, 1), (100, 2)), ((1, 40), (2, 100)),      # long chunk, short decode
    ((40, 40), (100, 0)), ((40, 40), (0, 100)),    # chunk after chunk
], ids=["long-short", "short-long", "chunk-decode", "decode-chunk",
        "deep-shallow", "shallow-deep"])
def test_ragged_kernel_long_rows_beside_short_ones(monkeypatch, lens):
    """The prefetch across items: a row of five blocks before and after a
    row of one, so an item's first block lands in either slot and is waited
    for by the item after the one that started it."""
    _small_plan(monkeypatch)
    row_lens, history = lens
    _check(_setup(
        jax.random.PRNGKey(10), rows=2, pages_per_seq=10,
        row_lens=row_lens, kv_extra=history,
    ))


@pytest.mark.parametrize("history", [0, 5, 20, 40, 90])
def test_ragged_kernel_several_tiles_of_one_row(monkeypatch, history):
    """Three tiles of one row, the first tile on one context block (history
    0, 5), two (20) or more: every further tile fetches the row's first
    block again, into the slot after its predecessor's last; a decode row
    before and after."""
    _small_plan(monkeypatch)
    _check(_setup(
        jax.random.PRNGKey(11), rows=3, pages_per_seq=10,
        row_lens=(1, 48, 1), kv_extra=(9, history, 33),
    ))


def test_ragged_kernel_idle_rows_and_padding_anywhere(monkeypatch):
    """Items of no row between live ones, at the head and at the tail, idle
    rows, and a row the engine dropped after planning (its item stays, its
    row_lens went to 0): none fetches anything, and their tokens read 0."""
    _small_plan(monkeypatch)
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     item_rows, item_q0) = _setup(
        jax.random.PRNGKey(12), rows=6, pages_per_seq=5,
        row_lens=(0, 1, 20, 0, 3, 1), kv_extra=(0, 40, 30, 0, 7, 12), slack=2,
    )
    rows = [-1, 1, -1, -1, 2, 2, -1, 4, 5, -1]
    q0 = [0, 0, 0, 0, 0, 16, 0, 0, 0, 0]
    dropped = np.asarray(row_lens).copy()
    dropped[4] = 0
    kv = np.asarray(kv_lens).copy()
    kv[4] -= 3
    out = _check((q, k_pool, v_pool, page_table, jnp.asarray(kv), starts,
                  jnp.asarray(dropped), jnp.asarray(rows, jnp.int32),
                  jnp.asarray(q0, jnp.int32)))
    s = int(starts[4])
    assert not np.asarray(out[s: s + 8]).any()
    assert not np.asarray(out[int(starts[5]) + 8:]).any()


def test_ragged_kernel_with_nothing_to_do():
    """The warmup's null launch: no row has queries, every item is padding;
    the output is the zeros it was born as."""
    args = _setup(jax.random.PRNGKey(13), row_lens=(0, 0, 0, 0),
                  kv_extra=(0, 0, 0, 0), slack=1)
    assert not np.asarray(_check(args)).any()


@pytest.mark.parametrize("g", [1, 4, 8])
@pytest.mark.parametrize("hkv", [1, 2, 8])
def test_ragged_kernel_head_counts(monkeypatch, hkv, g):
    """Hkv {1, 2, 8} x G {1, 4, 8}: every kv head of a row in one grid step,
    the sub tile 128 / G queries (here 32 rows a head)."""
    _small_plan(monkeypatch, tile=32, sub_rows=32)
    _check(_setup(
        jax.random.PRNGKey(14), rows=3, hkv=hkv, g=g, d=32, pages_per_seq=5,
        row_lens=(1, 37, 2), kv_extra=(50, 11, 0),
    ))


def test_ragged_kernel_pads_a_group_of_one_to_whole_bf16_tiles():
    """8 tokens of a bf16 head of G = 1 are half a (16, 128) tile: the
    wrapper pads the group with a query head of zeros and cuts it off."""
    args = _setup(jax.random.PRNGKey(15), rows=2, hkv=2, g=1, d=128,
                  row_lens=(1, 11), kv_extra=(30, 9), dtype=jnp.bfloat16)
    out = _check(args, tol=2e-2)
    assert out.shape == args[0].shape


@pytest.mark.parametrize("page", [16, 32])
def test_ragged_kernel_int8_interpret_matches_xla(monkeypatch, page):
    """int8 pools + pre-gathered per-row scale operands through the ragged
    kernel (interpret) against the ragged XLA dequant reference: blocks of
    two pages, the row's table not a multiple of them at page 32 (a partial
    last block of scales), a chunk of two tiles."""
    _small_plan(monkeypatch, tile=8, block_tokens=2 * page)
    args = _setup(
        jax.random.PRNGKey(3), rows=4, hkv=2, g=2, d=64, page=page,
        pages_per_seq=4 if page == 16 else 3, row_lens=(1, 7, 1, 10),
        kv_extra=(page + 1, 3, 2 * page, 0),
    )
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     item_rows, item_q0) = args
    k8, ks = _quantize_pool(k_pool)
    v8, vs = _quantize_pool(v_pool)
    ref = ragged_paged_attention_xla(
        q, k8, v8, page_table, kv_lens, starts, row_lens, ks, vs
    )
    _check((q, k8, v8) + args[3:], tol=2e-4, k_scale=ks, v_scale=vs)
    # dequant correctness vs a dequantized-pool run (same tolerance class
    # as the decode kernel's int8 test)
    kd = (np.asarray(k8, np.float32) * np.asarray(ks)[..., None])
    vd = (np.asarray(v8, np.float32) * np.asarray(vs)[..., None])
    dense = ragged_paged_attention_xla(
        q, jnp.asarray(kd), jnp.asarray(vd), page_table, kv_lens, starts,
        row_lens,
    )
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


def test_ragged_int8_requires_scales():
    args = _setup(jax.random.PRNGKey(4), row_lens=(1, 3, 1, 1))
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     _br, _bq) = args
    k8, _ks = _quantize_pool(k_pool)
    v8, _vs = _quantize_pool(v_pool)
    with pytest.raises(ValueError):
        ragged_paged_attention(
            q, k8, v8, page_table, kv_lens, starts, row_lens, interpret=True
        )


def test_ragged_kernel_never_returns_the_reference():
    """The kernel entry points run the kernel or raise: no work plan and
    shapes Mosaic cannot take (here D=64 pools, not interpreted) are errors
    naming the reason, never a quiet XLA reference."""
    args = _setup(jax.random.PRNGKey(5), row_lens=(1, 4, 1, 1))
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     br, bq) = args
    with pytest.raises(ValueError, match="item_rows"):
        ragged_paged_attention(
            q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
            interpret=True,
        )
    assert q.shape[-1] % 128  # the fixture's head_dim is off the lane tile
    with pytest.raises(ValueError, match="head_dim"):
        ragged_paged_attention(
            q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
            item_rows=br, item_q0=bq,
        )
    with pytest.raises(ValueError, match="head_dim"):
        paged_attention(q[:4], k_pool, v_pool, page_table, kv_lens)


def test_paged_kernel_routing_reasons():
    """paged_kernel_unsupported_reason is the one routing decision for
    both paged kernels (models/llama at trace time, the engine's health
    block at construction): platform, lane tile, dtype-dependent sublane
    tile — and None for the aligned Llama-3-8B shapes."""
    why = paged_kernel_unsupported_reason
    assert why(128, 16, jnp.bfloat16, platform="tpu") is None
    assert why(128, 32, jnp.int8, platform="tpu") is None
    assert "head_dim 64" in why(64, 16, jnp.bfloat16, platform="tpu")
    # int8 pools on the default 16-token pages miss the (32, 128) tile
    assert "page_size 16" in why(128, 16, jnp.int8, platform="tpu")
    assert "32-row" in why(128, 16, jnp.int8, platform="tpu")
    assert "page_size 8" in why(128, 8, jnp.bfloat16, platform="tpu")
    assert "platform cpu" in why(128, 16, jnp.bfloat16, platform="cpu")
    assert "platform cpu" in why(128, 16, jnp.bfloat16)  # tier-1 backend


def test_paged_kernel_smem_accounting():
    """The SMEM estimate the engine checks at construction reproduces what
    the v5e compiler allocated (docs/ragged_attention.md hardware notes):
    the page table pads to (8, 128) int32 tiles, the ancestor table rides
    flat, and 1 MiB is the chip's scalar memory."""
    # decode: s32[256, 896] fit, s32[250, 1024] did not
    assert paged_kernel_smem_bytes(256, 896) <= SMEM_BYTES
    assert paged_kernel_smem_bytes(250, 1024) > SMEM_BYTES
    # table, lengths, layer, the walk's block counter (ISSUE 28), 2 KB spare
    assert paged_kernel_smem_bytes(256, 896) == (
        256 * 896 * 4 + 1024 + 512 + 512 + 2048)
    # ragged + tree: T=8192 R=200 PP=768 on a plan of 264 items fits at
    # width 12, not at 13
    assert paged_kernel_smem_bytes(200, 768, 8192, 12, 264) <= SMEM_BYTES
    assert paged_kernel_smem_bytes(200, 768, 8192, 13, 264) > SMEM_BYTES
    # table, three row vectors, layer, the walk's counters, the plan's two
    # vectors (ISSUE 30), 2 KB spare
    assert paged_kernel_smem_bytes(32, 272, 352, 0, 34) == (
        32 * 384 * 4 + 3 * 512 + 2 * 512 + 2 * 512 + 2048)
    # the smoke's own configuration is three orders of magnitude inside
    assert paged_kernel_smem_bytes(8, 129, 184, 0, 9) < SMEM_BYTES // 50


# -- the stacked pool [L, Hkv, N, P, D] with a layer index (ISSUE 25) ---------


def _stack_with(layer, pool, layers=3):
    """A stack whose layer ``layer`` is ``pool`` and whose other layers are
    other data: reading the wrong layer cannot go unseen."""
    others = jax.random.normal(
        jax.random.PRNGKey(100 + layer), (layers,) + pool.shape, jnp.float32
    )
    if jnp.issubdtype(pool.dtype, jnp.integer):
        others = jnp.round(others * 40)
    return others.astype(pool.dtype).at[layer].set(pool)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("kind", ["bf16", "int8", "tree"])
def test_ragged_attention_reads_its_layer_of_the_stack(monkeypatch, kind, layer):
    """Ragged kernel (interpret) and XLA reference on the stack of L = 3
    with ``layer`` equal, bit for bit, the same entry point on
    ``pool[layer]`` — under jit with a TRACED layer, as the layer scan calls
    them — for bf16 pools, int8 pools with their scale stacks, and a batch
    with a draft-tree row; kernel and reference agree as before."""
    from clearml_serving_tpu.ops.paged_attention import tree_ancestors

    _small_plan(monkeypatch, tile=8)
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     item_rows, item_q0) = _setup(
        jax.random.PRNGKey(6), rows=4, hkv=2, g=2, d=128, page=16,
        pages_per_seq=4, row_lens=(1, 7, 1, 10), kv_extra=(17, 3, 32, 0),
    )
    kw, stacked_kw = {}, {}
    if kind == "int8":
        (k_pool, ks), (v_pool, vs) = _quantize_pool(k_pool), _quantize_pool(v_pool)
        kw = {"k_scale": ks, "v_scale": vs}
        stacked_kw = {n: _stack_with(layer, s) for n, s in kw.items()}
    else:
        q, k_pool, v_pool = (a.astype(jnp.bfloat16) for a in (q, k_pool, v_pool))
    if kind == "tree":
        # row 1 (7 tokens) is a binary draft tree; every other token plain
        anc = np.full((q.shape[0], 7), -1, np.int32)
        anc[:, 0] = -2
        s = int(starts[1])
        anc[s: s + 7] = tree_ancestors([-1, 0, 0, 1, 1, 2, 2], width=7)
        kw = stacked_kw = {"tree_anc": jnp.asarray(anc)}
    k_stack, v_stack = _stack_with(layer, k_pool), _stack_with(layer, v_pool)

    kernel = functools.partial(
        ragged_paged_attention, item_rows=item_rows, item_q0=item_q0,
        interpret=True,
    )
    for fn in (kernel, ragged_paged_attention_xla):
        want = fn(q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
                  **kw)
        got = jax.jit(
            lambda li, fn=fn: fn(q, k_stack, v_stack, page_table, kv_lens,
                                 starts, row_lens, layer=li, **stacked_kw)
        )(jnp.int32(layer))
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    np.testing.assert_allclose(
        np.asarray(kernel(q, k_stack, v_stack, page_table, kv_lens, starts,
                          row_lens, layer=layer, **stacked_kw), np.float32),
        np.asarray(want, np.float32), rtol=2e-2, atol=2e-2,
    )


# -- the write of new K/V into the pools: page-patching kernel vs row scatter --


def _write_case(dtype):
    """Coordinates of one launch: a prefill run that crosses from page 3
    into page 4, two decode rows between their pads on the null page, and a
    page left and come back to (5, 6, 5, 5)."""
    layers, hkv, pages, page, d = 3, 2, 12, 16, 128
    coords = ([(3, o) for o in range(10, 16)] + [(4, o) for o in range(10)]
              + [(7, 2)] + [(0, 0)] * 7 + [(8, 15)] + [(0, 0)] * 7
              + [(5, 1), (6, 3), (5, 2), (5, 9)] + [(0, 0)] * 4)
    wp, wo = (jnp.asarray(c, jnp.int32) for c in zip(*coords))

    def rnd(seed, shape):
        x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
        return jnp.round(x * 20).astype(dtype)

    stack = (layers, hkv, pages, page, d)
    new = (len(coords), hkv, d)
    return rnd(0, stack), rnd(1, stack), rnd(2, new), rnd(3, new), wp, wo


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
def test_kv_write_kernel_equals_the_row_scatter(dtype, layer):
    """Bit for bit on every page but the null page (page 0 takes every pad,
    and of duplicates XLA leaves the winner open), under jit with a traced
    layer; the other layers of the stack are untouched."""
    k, v, k_new, v_new, wp, wo = _write_case(dtype)
    want = paged_kv_write_xla(k, v, k_new, v_new, wp, wo, layer=layer)
    got = jax.jit(functools.partial(paged_kv_write, interpret=True))(
        k, v, k_new, v_new, wp, wo, layer=jnp.int32(layer))
    for old, w, g in zip((k, v), want, got):
        w, g = np.asarray(w), np.asarray(g)
        assert g[:, :, 1:].tobytes() == w[:, :, 1:].tobytes()
        assert not np.array_equal(g[layer], np.asarray(old)[layer])
        others = [i for i in range(3) if i != layer]
        assert np.array_equal(g[others], np.asarray(old)[others])
    # a written row holds its token
    assert np.array_equal(np.asarray(got[0])[layer, :, 8, 15],
                          np.asarray(k_new)[24])


def test_kv_write_kernel_on_one_layers_pool_and_duplicates():
    """The 4-D form, and two tokens on one coordinate: the later one wins."""
    k, v, k_new, v_new, wp, wo = _write_case(jnp.bfloat16)
    wo = wo.at[-5].set(1)                  # (5, 9) -> (5, 1), written before
    got = paged_kv_write(k[1], v[1], k_new, v_new, wp, wo, interpret=True)
    assert got[0].shape == k[1].shape
    assert np.array_equal(np.asarray(got[1])[:, 5, 1], np.asarray(v_new)[-5])
    want = paged_kv_write_xla(k[1], v[1], k_new, v_new, wp, wo)
    keep = np.ones(k.shape[2:4], bool)
    keep[0], keep[5, 1] = False, False
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(g)[:, keep], np.asarray(w)[:, keep])


def test_kv_write_kernel_over_several_grid_steps():
    """More tokens than one grid step holds (512 at Hkv <= 16, D = 128,
    bf16): a 1024-token prefill over 64 pages whose run crosses the steps'
    boundary mid-page, so the page is flushed and fetched again there."""
    hkv, d, page = 2, 128, 16
    t = jnp.arange(1024, dtype=jnp.int32) + 8        # starts mid-page
    wp, wo = 1 + t // page, t % page

    def rnd(seed, shape):
        x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
        return x.astype(jnp.bfloat16)

    k, v = rnd(0, (hkv, 70, page, d)), rnd(1, (hkv, 70, page, d))
    k_new, v_new = rnd(2, (1024, hkv, d)), rnd(3, (1024, hkv, d))
    want = paged_kv_write_xla(k, v, k_new, v_new, wp, wo)
    got = paged_kv_write(k, v, k_new, v_new, wp, wo, interpret=True)
    for w, g in zip(want, got):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()
