"""Ragged paged attention kernel tests (docs/ragged_attention.md): the
mixed prefill+decode Pallas kernel (interpret mode) against the ragged XLA
reference, the ragged reference against the per-row decode/dense references,
and the layout helper's q-block contract."""

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
    ragged_layout,
    ragged_paged_attention,
    ragged_paged_attention_xla,
)


def _quantize_pool(pool):
    """Per-(token, head) symmetric int8, mirroring models/llama._kv_store."""
    x = np.asarray(pool, np.float32)
    absmax = np.abs(x).max(axis=-1)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(x / scale[..., None]), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(scale)


def _setup(key, *, rows=4, hkv=2, g=2, d=64, page=16, pages_per_seq=6,
           row_lens=(1, 5, 1, 12), kv_extra=(7, 0, 30, 0), q_block=8):
    """Build a mixed batch: row_lens[r] query tokens per row (1 = decode),
    kv_lens = history + chunk. Returns the full operand set plus the
    layout metadata."""
    ks = jax.random.split(key, 3)
    n_pages = rows * pages_per_seq + 1
    k_pool = jax.random.normal(ks[0], (hkv, n_pages, page, d), jnp.float32)
    v_pool = jax.random.normal(ks[1], (hkv, n_pages, page, d), jnp.float32)
    page_table = np.zeros((rows, pages_per_seq), np.int32)
    for r in range(rows):
        page_table[r] = 1 + r * pages_per_seq + np.arange(pages_per_seq)
    row_lens = np.asarray(row_lens, np.int32)
    kv_lens = row_lens + np.asarray(kv_extra, np.int32)
    assert kv_lens.max() <= pages_per_seq * page
    starts, block_rows, block_q0, t_pad = ragged_layout(
        row_lens, q_block=q_block
    )
    q = jax.random.normal(ks[2], (t_pad, hkv, g, d), jnp.float32)
    return (
        q, k_pool, v_pool, jnp.asarray(page_table), jnp.asarray(kv_lens),
        jnp.asarray(starts), jnp.asarray(row_lens),
        jnp.asarray(block_rows), jnp.asarray(block_q0),
    )


def test_ragged_layout_alignment():
    starts, block_rows, block_q0, t_pad = ragged_layout([1, 5, 0, 12], 8)
    assert t_pad % 8 == 0
    # every row starts on a q-block boundary; idle rows own no block
    assert all(int(s) % 8 == 0 for s in starts)
    assert list(block_rows) == [0, 1, 3, 3]
    assert list(block_q0) == [0, 0, 0, 8]
    # fixed `total` pads with unowned blocks (static engine shapes)
    _, br2, _, t2 = ragged_layout([1, 5, 0, 12], 8, total=48)
    assert t2 == 48 and list(br2[4:]) == [-1, -1]
    with pytest.raises(ValueError):
        ragged_layout([64], 8, total=32)


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
@pytest.mark.parametrize("pages_per_block", [1, 2, 4])
def test_ragged_kernel_interpret_matches_xla(page, pages_per_block):
    """Mixed row phases x page sizes x DMA block sizes, including a partial
    final chunk (kv not page-aligned) and an idle row."""
    args = _setup(
        jax.random.PRNGKey(2), rows=5, hkv=2, g=2, d=64, page=page,
        pages_per_seq=4, row_lens=(1, 9, 1, 13, 0),
        kv_extra=(page * 2 + 3, 5, 0, 7, 0),
    )
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     block_rows, block_q0) = args
    ref = ragged_paged_attention_xla(
        q, k_pool, v_pool, page_table, kv_lens, starts, row_lens
    )
    out = ragged_paged_attention(
        q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
        block_rows=block_rows, block_q0=block_q0,
        pages_per_block=pages_per_block, interpret=True,
    )
    # compare only owned tokens (unowned blocks hold zeros in both)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("page", [16, 32])
def test_ragged_kernel_int8_interpret_matches_xla(page):
    """int8 pools + pre-gathered per-row scale operands through the ragged
    kernel (interpret) against the ragged XLA dequant reference."""
    args = _setup(
        jax.random.PRNGKey(3), rows=4, hkv=2, g=2, d=64, page=page,
        pages_per_seq=4, row_lens=(1, 7, 1, 10),
        kv_extra=(page + 1, 3, 2 * page, 0),
    )
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     block_rows, block_q0) = args
    k8, ks = _quantize_pool(k_pool)
    v8, vs = _quantize_pool(v_pool)
    ref = ragged_paged_attention_xla(
        q, k8, v8, page_table, kv_lens, starts, row_lens, ks, vs
    )
    out = ragged_paged_attention(
        q, k8, v8, page_table, kv_lens, starts, row_lens,
        block_rows=block_rows, block_q0=block_q0,
        k_scale=ks, v_scale=vs, pages_per_block=2, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )
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
    """The kernel entry points run the kernel or raise: no block metadata
    and shapes Mosaic cannot take (here D=64 pools, not interpreted) are
    errors naming the reason, never a quiet XLA reference."""
    args = _setup(jax.random.PRNGKey(5), row_lens=(1, 4, 1, 1))
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     br, bq) = args
    with pytest.raises(ValueError, match="block_rows"):
        ragged_paged_attention(
            q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
            interpret=True,
        )
    assert q.shape[-1] % 128  # the fixture's head_dim is off the lane tile
    with pytest.raises(ValueError, match="head_dim"):
        ragged_paged_attention(
            q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
            block_rows=br, block_q0=bq,
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
    # ragged + tree: T=8192 R=200 PP=768 compiled at width 12, not at 13
    assert paged_kernel_smem_bytes(200, 768, 8192, 12) <= SMEM_BYTES
    assert paged_kernel_smem_bytes(200, 768, 8192, 13) > SMEM_BYTES
    # the smoke's own configuration is three orders of magnitude inside
    assert paged_kernel_smem_bytes(8, 129, 184) < SMEM_BYTES // 50


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
def test_ragged_attention_reads_its_layer_of_the_stack(kind, layer):
    """Ragged kernel (interpret) and XLA reference on the stack of L = 3
    with ``layer`` equal, bit for bit, the same entry point on
    ``pool[layer]`` — under jit with a TRACED layer, as the layer scan calls
    them — for bf16 pools, int8 pools with their scale stacks, and a batch
    with a draft-tree row; kernel and reference agree as before."""
    from clearml_serving_tpu.ops.paged_attention import tree_ancestors

    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     block_rows, block_q0) = _setup(
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
        ragged_paged_attention, block_rows=block_rows, block_q0=block_q0,
        pages_per_block=2, interpret=True,
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
