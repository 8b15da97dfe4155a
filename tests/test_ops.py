import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import fill_pages

from clearml_serving_tpu.llm.kv_cache import PagePool, PagedKVCache
from clearml_serving_tpu.ops import paged_attention as pa
from clearml_serving_tpu.ops.paged_attention import paged_attention, paged_attention_xla
from clearml_serving_tpu.ops.quant import (
    dequant_llama_params,
    dequantize,
    dequantize_int4,
    int8_matmul,
    quantize_int4,
    quantize_int8,
    quantize_llama_params,
)


def _dense_reference(q, k, v, lengths):
    """q: [B,Hkv,G,D]; k/v: [B,T,Hkv,D] dense with per-seq lengths."""
    d = q.shape[-1]
    t_idx = jnp.arange(k.shape[1])[None]
    valid = t_idx < lengths[:, None]
    scores = jnp.einsum("bkgd,btkd->bkgt", q, k) * (d ** -0.5)
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgt,btkd->bkgd", probs, v)


def _random_paged_setup(rng, b=3, hkv=2, g=4, d=64, page_size=8, pages_per_seq=4):
    keys = jax.random.split(rng, 5)
    num_pages = b * pages_per_seq + 1
    q = jax.random.normal(keys[0], (b, hkv, g, d), jnp.float32)
    k_pool = jax.random.normal(keys[1], (hkv, num_pages, page_size, d), jnp.float32)
    v_pool = jax.random.normal(keys[2], (hkv, num_pages, page_size, d), jnp.float32)
    # distinct page ids per sequence (page 0 reserved as the null page)
    ids = np.arange(1, b * pages_per_seq + 1, dtype=np.int32)
    np.random.default_rng(0).shuffle(ids)
    page_table = jnp.asarray(ids.reshape(b, pages_per_seq))
    lengths = jnp.asarray([page_size * pages_per_seq, 13, 1], jnp.int32)
    return q, k_pool, v_pool, page_table, lengths


@pytest.fixture
def block_pages(monkeypatch):
    """Pages the decode kernel takes as one block: the kernel derives them
    from its shapes (``decode_pages_per_block``), under a ceiling of
    ``_DECODE_BLOCK_TOKENS`` that small test shapes never reach; lowering
    the ceiling is how a test gets several blocks out of a short row."""
    def set_pages(pages, page_size=8):
        monkeypatch.setattr(pa, "_DECODE_BLOCK_TOKENS", pages * page_size)

    return set_pages


def test_paged_attention_xla_matches_dense():
    q, k_pool, v_pool, page_table, lengths = _random_paged_setup(jax.random.PRNGKey(0))
    out = paged_attention_xla(q, k_pool, v_pool, page_table, lengths)
    # dense equivalent: gather pages manually ([Hkv,B,PP,P,D] -> [B,T,Hkv,D])
    b, hkv, g, d = q.shape
    k = k_pool[:, page_table].reshape(hkv, b, -1, d).transpose(1, 2, 0, 3)
    v = v_pool[:, page_table].reshape(hkv, b, -1, d).transpose(1, 2, 0, 3)
    ref = _dense_reference(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_paged_attention_pallas_interpret_matches_xla():
    q, k_pool, v_pool, page_table, lengths = _random_paged_setup(jax.random.PRNGKey(1))
    ref = paged_attention_xla(q, k_pool, v_pool, page_table, lengths)
    out = paged_attention(q, k_pool, v_pool, page_table, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_paged_attention_single_token_sequence():
    q, k_pool, v_pool, page_table, lengths = _random_paged_setup(jax.random.PRNGKey(2))
    lengths = jnp.asarray([1, 1, 1], jnp.int32)
    ref = paged_attention_xla(q, k_pool, v_pool, page_table, lengths)
    out = paged_attention(q, k_pool, v_pool, page_table, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def _quantize_pool(pool):
    """Per-(token, head) symmetric int8 like models/llama._kv_store."""
    x = np.asarray(pool, np.float32)
    absmax = np.abs(x).max(-1)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(x / scale[..., None]), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(scale)


def test_paged_attention_int8_xla_matches_dequantized_dense():
    """The int8 XLA reference == running the bf16 reference over the
    eagerly dequantized pools (the scale operands ARE the dequant)."""
    q, k_pool, v_pool, page_table, lengths = _random_paged_setup(jax.random.PRNGKey(3))
    k8, ks = _quantize_pool(k_pool)
    v8, vs = _quantize_pool(v_pool)
    out = paged_attention_xla(q, k8, v8, page_table, lengths, ks, vs)
    kd = k8.astype(jnp.float32) * ks[..., None]
    vd = v8.astype(jnp.float32) * vs[..., None]
    ref = paged_attention_xla(q, kd, vd, page_table, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pb", [1, 2, 32])
def test_paged_attention_int8_pallas_interpret_matches_xla(pb, block_pages):
    """Tentpole parity gate (tier-1): the Pallas int8 kernel — in-kernel
    dequant fused into the flash update — must match the XLA int8 gather
    reference to (better than) bf16 epsilon in interpret mode, including
    ragged lengths and an empty row."""
    q, k_pool, v_pool, page_table, lengths = _random_paged_setup(jax.random.PRNGKey(4))
    k8, ks = _quantize_pool(k_pool)
    v8, vs = _quantize_pool(v_pool)
    lengths = jnp.asarray([int(lengths[0]), 13, 0], jnp.int32)
    ref = paged_attention_xla(q, k8, v8, page_table, lengths, ks, vs)
    block_pages(pb)
    out = paged_attention(
        q, k8, v8, page_table, lengths, k_scale=ks, v_scale=vs, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_paged_attention_int8_partial_last_block_scales(block_pages):
    """pages_per_seq NOT a multiple of pages_per_block, with live tokens in
    the final partial block: the kernel's fixed-width scale-window slices
    must not clamp into earlier rows (the gathered scales pad up to a
    block-token multiple). Regression for the r5 review finding."""
    q, k_pool, v_pool, page_table, lengths = _random_paged_setup(
        jax.random.PRNGKey(6), pages_per_seq=6, page_size=8
    )
    k8, ks = _quantize_pool(k_pool)
    v8, vs = _quantize_pool(v_pool)
    # lengths reach into the 6-page (48-token) capacity's final block when
    # pb=4 (block = 32 tokens): tokens 33..47 live in the partial block
    lengths = jnp.asarray([47, 35, 48], jnp.int32)
    ref = paged_attention_xla(q, k8, v8, page_table, lengths, ks, vs)
    block_pages(4)
    out = paged_attention(
        q, k8, v8, page_table, lengths, k_scale=ks, v_scale=vs, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_paged_attention_int8_requires_scales():
    q, k_pool, v_pool, page_table, lengths = _random_paged_setup(jax.random.PRNGKey(5))
    k8, _ = _quantize_pool(k_pool)
    v8, _ = _quantize_pool(v_pool)
    with pytest.raises(ValueError):
        paged_attention(q, k8, v8, page_table, lengths, interpret=True)


class TestPagePool:
    def test_alloc_free_cycle(self):
        pool = PagePool(num_pages=10, page_size=4, max_slots=3)
        assert pool.free_pages == 9  # page 0 reserved as the null page
        pages = pool.allocate(0, 10)  # 3 pages
        assert len(pages) == 3 and pool.free_pages == 6
        assert 0 not in pages
        pool.allocate(1, 4)
        assert pool.free_pages == 5
        pool.free(0)
        assert pool.free_pages == 8
        assert pool.slot_length(0) == 0

    def test_extend_allocates_on_boundary(self):
        pool = PagePool(num_pages=5, page_size=4, max_slots=1)
        pool.allocate(0, 4)
        assert pool.free_pages == 3  # 5 pages - null page - 1 allocated
        assert len(pool.extend(0, 1)) == 1    # crosses into page 2
        assert pool.slot_length(0) == 5
        assert pool.extend(0, 1) == []        # still inside page 2
        assert pool.slot_length(0) == 6
        assert len(pool.extend(0, 7)) == 2    # 6 -> 13 tokens spans two new pages

    def test_page_table_overflow_raises(self):
        pool = PagePool(num_pages=8, page_size=4, max_slots=1)
        pool.allocate(0, 12)  # 3 pages
        with pytest.raises(ValueError):
            pool.page_table(pages_per_seq=2)

    def test_exhaustion(self):
        pool = PagePool(num_pages=3, page_size=4, max_slots=2)
        pool.allocate(0, 8)  # 2 of the 2 allocatable pages (page 0 reserved)
        assert not pool.can_allocate(1)
        with pytest.raises(MemoryError):
            pool.allocate(1, 4)

    def test_page_table_shape(self):
        pool = PagePool(num_pages=8, page_size=4, max_slots=2)
        pool.allocate(1, 6)
        table = pool.page_table(pages_per_seq=4)
        assert table.shape == (2, 4)
        assert (table[0] == 0).all()
        assert table[1, :2].tolist() == pool._slot_pages[1]


def test_paged_kv_cache_roundtrip():
    cache = PagedKVCache(
        n_layers=2, n_kv_heads=2, head_dim=8, num_pages=8, page_size=4, max_slots=2,
        dtype="float32",
    )
    length = 6
    # stacked [L, S, Hkv, D]
    k_stack = jnp.stack(
        [jnp.arange(length * 2 * 8, dtype=jnp.float32).reshape(length, 2, 8) + li
         for li in range(2)]
    )
    v_stack = k_stack + 100
    fill_pages(cache, 0, k_stack, v_stack)
    assert cache.pool.slot_length(0) == 6

    # append one token: [L, Hkv, D]
    k_new = jnp.stack([jnp.full((2, 8), 7.0 + li) for li in range(2)])
    v_new = k_new + 2
    fill_pages(cache, 0, k_new[:, None], v_new[:, None], append=True)
    assert cache.pool.slot_length(0) == 7

    # reconstruct the sequence from pages and compare (layer 0)
    table = cache.pool.page_table(cache.max_pages_per_seq(16))
    k_l0, _ = cache.layer(0)                      # [Hkv, N, P, D]
    gathered = np.asarray(k_l0[:, table[0]])      # [Hkv, PP, P, D]
    gathered = gathered.transpose(1, 2, 0, 3).reshape(-1, 2, 8)[:7]
    np.testing.assert_allclose(gathered[:6], np.asarray(k_stack[0]))
    np.testing.assert_allclose(gathered[6], np.asarray(k_new[0]))


def test_paged_kv_cache_int8_roundtrip():
    """int8 pools: prompt scatter + per-token append store int8 values with
    their scale rows; dequantizing page-by-page recovers the source K/V to
    int8 precision (|err| <= scale/2 per element)."""
    cache = PagedKVCache(
        n_layers=2, n_kv_heads=2, head_dim=8, num_pages=8, page_size=4,
        max_slots=2, dtype="float32", kv_quant="int8",
    )
    assert cache.has_scales and cache.pool_dtype == "int8"
    assert cache.k_scale.shape == (2, 2, 8, 4)
    rng = np.random.default_rng(7)
    length = 6
    k_src = rng.normal(size=(2, length, 2, 8)).astype(np.float32)
    v_src = k_src + 0.5

    def store(x):  # [L, S, Hkv, D] -> (int8, scale [L, S, Hkv])
        absmax = np.abs(x).max(-1)
        scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
        q = np.clip(np.round(x / scale[..., None]), -127, 127).astype(np.int8)
        return jnp.asarray(q), jnp.asarray(scale)

    k_q, k_s = store(k_src)
    v_q, v_s = store(v_src)
    fill_pages(cache, 0, k_q, v_q, k_s, v_s)

    k_tok = rng.normal(size=(2, 2, 8)).astype(np.float32)
    kt_q, kt_s = store(k_tok[:, None])  # [L,1,Hkv,D]
    fill_pages(cache, 0, kt_q, kt_q, kt_s, kt_s, append=True)
    assert cache.pool.slot_length(0) == 7

    table = cache.pool.page_table(cache.max_pages_per_seq(16))
    k_l0 = np.asarray(cache.k[0][:, table[0]])          # [Hkv, PP, P, D] int8
    s_l0 = np.asarray(cache.k_scale[0][:, table[0]])    # [Hkv, PP, P]
    deq = (k_l0.astype(np.float32) * s_l0[..., None])
    deq = deq.transpose(1, 2, 0, 3).reshape(-1, 2, 8)[:7]
    np.testing.assert_allclose(deq[:6], k_src[0], atol=np.abs(k_src).max() / 127)
    np.testing.assert_allclose(
        deq[6], k_tok[0], atol=np.abs(k_tok).max() / 127
    )


def test_quantize_roundtrip():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    q, scale = quantize_int8(w, axis=0)
    assert q.dtype == jnp.int8 and scale.shape == (1, 32)
    w2 = dequantize(q, scale, jnp.float32)
    # int8 symmetric quantization: error bounded by scale/2 per element
    assert float(jnp.max(jnp.abs(w2 - w) / scale)) <= 0.51


def test_int8_matmul_close():
    rng = jax.random.PRNGKey(1)
    x = jax.random.normal(rng, (4, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 32), jnp.float32)
    q, scale = quantize_int8(w, axis=0)
    exact = x @ w
    approx = int8_matmul(x, q, scale)
    rel = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
    assert rel < 0.02


def test_int4_roundtrip_grouped():
    # K=256 with group 128 -> 2 scale groups; error bounded by scale/2
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 32), jnp.float32)
    packed, scale = quantize_int4(w)
    assert packed.dtype == jnp.uint8 and packed.shape == (128, 32)
    assert scale.shape == (2, 32)
    w2 = dequantize_int4(packed, scale, jnp.float32)
    per_elem_scale = jnp.repeat(scale, 128, axis=0)
    assert float(jnp.max(jnp.abs(w2 - w) / per_elem_scale)) <= 0.51


def test_int4_roundtrip_single_group_fallback():
    # K=64 < group -> one per-channel group, still packs two rows per byte
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 16), jnp.float32)
    packed, scale = quantize_int4(w)
    assert packed.shape == (32, 16) and scale.shape == (1, 16)
    w2 = dequantize_int4(packed, scale, jnp.float32)
    assert float(jnp.max(jnp.abs(w2 - w) / scale)) <= 0.51


def test_int4_stacked_layers():
    # scan_layers-stacked [L, K, N] quantizes per layer independently
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 256, 16), jnp.float32)
    packed, scale = quantize_int4(w)
    assert packed.shape == (3, 128, 16) and scale.shape == (3, 2, 16)
    w2 = dequantize_int4(packed, scale, jnp.float32)
    p0, s0 = quantize_int4(w[1])
    np.testing.assert_allclose(
        np.asarray(w2[1]), np.asarray(dequantize_int4(p0, s0, jnp.float32))
    )


def test_int4_matmul_close():
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 32), jnp.float32)
    packed, scale = quantize_int4(w)
    exact = x @ w
    approx = x @ dequantize_int4(packed, scale, jnp.float32)
    rel = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
    # int4 noise floor on gaussian weights: step=absmax/7, absmax~=3sigma
    # over a 128-row group -> per-element rel noise ~ 3/(7*sqrt(12)) ~ 0.12.
    # Real checkpoints do better (outlier structure); random ones can't.
    assert rel < 0.15, rel


def test_int4_llama_forward_close():
    from clearml_serving_tpu import models

    bundle = models.build_model("llama", {"preset": "llama-tiny", "dtype": "float32"})
    params = bundle.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 512)
    ref = bundle.apply(params, tokens)
    qparams = quantize_llama_params(params, bits=4)
    # the tree really is 4-bit: projections hold packed uint8 at half rows
    wq = qparams["layers"][0]["wq"]
    assert wq["_q4"].dtype == jnp.uint8
    assert wq["_q4"].shape[-2] == params["layers"][0]["wq"].shape[-2] // 2
    # and its quantised leaves (values + scales, what a decode pass reads
    # of the projections) weigh about half of the int8 tree's
    def quant_bytes(tree):
        names = {"_q4", "_scale4", "_q8", "_scale"}
        return sum(
            leaf.nbytes
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
            if names & {getattr(key, "key", None) for key in path}
        )

    ratio = quant_bytes(qparams) / quant_bytes(quantize_llama_params(params))
    assert 0.4 <= ratio <= 0.6, ratio
    out = bundle.apply(dequant_llama_params(qparams, jnp.float32), tokens)
    denom = float(jnp.std(ref))
    drift = float(jnp.max(jnp.abs(out - ref))) / denom
    # int4's ~12% per-matmul noise compounds through 2 layers + lm_head on
    # random weights; the exactness of the MECHANICS is pinned by the
    # roundtrip and accessor tests above, this guards against gross breakage
    assert drift < 2.5, drift


def test_int4_model_accessor_inline_dequant():
    """The model's _w accessor must serve an int4 tree directly (no eager
    dequant) — apply on the quantized tree equals apply on the dequantized
    tree."""
    from clearml_serving_tpu import models

    bundle = models.build_model("llama", {"preset": "llama-tiny", "dtype": "float32"})
    params = bundle.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 512)
    qparams = quantize_llama_params(params, bits=4)
    direct = bundle.apply(qparams, tokens)
    via_dequant = bundle.apply(dequant_llama_params(qparams, jnp.float32), tokens)
    np.testing.assert_allclose(
        np.asarray(direct), np.asarray(via_dequant), rtol=2e-4, atol=2e-4
    )


def test_quantized_llama_forward_close():
    from clearml_serving_tpu import models

    bundle = models.build_model("llama", {"preset": "llama-tiny", "dtype": "float32"})
    params = bundle.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 512)
    ref = bundle.apply(params, tokens)
    qparams = quantize_llama_params(params)
    out = bundle.apply(dequant_llama_params(qparams, jnp.float32), tokens)
    # logits drift stays small relative to the logit scale
    denom = float(jnp.std(ref))
    drift = float(jnp.max(jnp.abs(out - ref))) / denom
    assert drift < 0.25, drift


@pytest.mark.parametrize("pb", [1, 2, 3, 4, 8])
def test_paged_attention_block_sizes(pb, block_pages):
    """The kernel must be exact for any split of a row into blocks of
    pages (incl. non-dividing tails)."""
    q, k_pool, v_pool, page_table, lengths = _random_paged_setup(jax.random.PRNGKey(3))
    ref = paged_attention_xla(q, k_pool, v_pool, page_table, lengths)
    block_pages(pb)
    out = paged_attention(q, k_pool, v_pool, page_table, lengths, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_paged_attention_block_sizes_and_bf16():
    """... and for bf16 pools."""
    q, k_pool, v_pool, page_table, lengths = _random_paged_setup(jax.random.PRNGKey(3))
    qb = q.astype(jnp.bfloat16)
    kb = k_pool.astype(jnp.bfloat16)
    vb = v_pool.astype(jnp.bfloat16)
    refb = paged_attention_xla(qb, kb, vb, page_table, lengths)
    outb = paged_attention(qb, kb, vb, page_table, lengths, interpret=True)
    np.testing.assert_allclose(
        np.asarray(outb, np.float32), np.asarray(refb, np.float32),
        rtol=2e-2, atol=2e-2,
    )


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
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_paged_attention_reads_its_layer_of_the_stack(kind, layer, block_pages):
    """Decode kernel (interpret) and XLA reference on the stack of L = 3 with
    ``layer`` equal, bit for bit, the same entry point on ``pool[layer]`` —
    under jit with a TRACED layer, as the layer scan calls them — and agree
    with each other as before."""
    q, k_pool, v_pool, page_table, lengths = _random_paged_setup(
        jax.random.PRNGKey(7), d=128, page_size=16
    )
    lengths = jnp.asarray([int(lengths[0]), 13, 0], jnp.int32)
    scales, stacked_scales = {}, {}
    if kind == "int8":
        (k_pool, ks), (v_pool, vs) = _quantize_pool(k_pool), _quantize_pool(v_pool)
        scales = {"k_scale": ks, "v_scale": vs}
        stacked_scales = {n: _stack_with(layer, s) for n, s in scales.items()}
    else:
        q, k_pool, v_pool = (a.astype(jnp.bfloat16) for a in (q, k_pool, v_pool))
    k_stack, v_stack = _stack_with(layer, k_pool), _stack_with(layer, v_pool)
    assert k_stack.shape == (3,) + k_pool.shape

    block_pages(2, page_size=16)
    kernel = functools.partial(paged_attention, interpret=True)
    for fn in (kernel, paged_attention_xla):
        want = fn(q, k_pool, v_pool, page_table, lengths, **scales)
        got = jax.jit(
            lambda li, fn=fn: fn(q, k_stack, v_stack, page_table, lengths,
                                 layer=li, **stacked_scales)
        )(jnp.int32(layer))
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    np.testing.assert_allclose(
        np.asarray(kernel(q, k_stack, v_stack, page_table, lengths,
                          layer=layer, **stacked_scales), np.float32),
        np.asarray(want, np.float32), rtol=2e-2, atol=2e-2,
    )


def test_paged_attention_stack_and_layer_go_together():
    q, k_pool, v_pool, page_table, lengths = _random_paged_setup(jax.random.PRNGKey(8))
    with pytest.raises(ValueError, match="needs its layer"):
        paged_attention(q, k_pool[None], v_pool[None], page_table, lengths,
                        interpret=True)
    with pytest.raises(ValueError, match="layer indexes a stacked"):
        paged_attention(q, k_pool, v_pool, page_table, lengths, layer=0,
                        interpret=True)


# -- the decode kernel's work plan (ISSUE 28): a grid step owns a row with all
# its kv heads, a row of length 0 does nothing, and the next live row's first
# block is fetched while this row's last is computed ---------------------------


def _plan_setup(lengths, hkv=2, g=4, d=32, page_size=8, pages_per_seq=10, seed=11):
    b = len(lengths)
    q, k_pool, v_pool, page_table, _ = _random_paged_setup(
        jax.random.PRNGKey(seed), b=b, hkv=hkv, g=g, d=d, page_size=page_size,
        pages_per_seq=pages_per_seq,
    )
    return q, k_pool, v_pool, page_table, jnp.asarray(lengths, jnp.int32)


def _assert_kernel_is_the_reference(q, k_pool, v_pool, page_table, lengths, **scales):
    ref = paged_attention_xla(q, k_pool, v_pool, page_table, lengths, **scales)
    out = paged_attention(q, k_pool, v_pool, page_table, lengths,
                          interpret=True, **scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    dead = np.asarray(lengths) == 0
    assert not np.asarray(out)[dead].any()          # a dead row reads zeros


# blocks of 2 pages of 8: a row of 80 tokens is five blocks
@pytest.mark.parametrize("lengths", [
    [0, 0, 37, 80], [37, 0, 0, 80], [37, 80, 0, 0], [0, 37, 0, 80, 0],
    [0, 0, 0, 0], [0, 0, 23, 0], [23], [0],
], ids=["dead_first", "dead_between", "dead_last", "dead_around", "all_dead",
        "one_live", "one_row", "one_dead_row"])
def test_decode_kernel_live_and_dead_rows_in_every_order(lengths, block_pages):
    block_pages(2)
    _assert_kernel_is_the_reference(*_plan_setup(lengths))


@pytest.mark.parametrize("length", [1, 8, 9, 16, 17, 32, 33, 79, 80])
def test_decode_kernel_lengths_at_page_and_block_edges(length, block_pages):
    """1, a page's multiple (8), a block's multiple (16, 32, the row's whole
    table 80) and one past each; a neighbour on either side."""
    block_pages(2)
    _assert_kernel_is_the_reference(*_plan_setup([5, length, 80]))


@pytest.mark.parametrize("lengths", [
    [80, 3], [3, 80], [80, 0, 3, 0, 80], [16, 16, 16], [17, 1, 33, 1],
], ids=["long_short", "short_long", "long_dead_short_dead_long", "one_block_each",
        "tails"])
def test_decode_kernel_prefetches_across_rows(lengths, block_pages):
    """The next live row's first block goes into the slot this row's last
    block does not hold, whatever the parity of the blocks walked so far."""
    block_pages(2)
    _assert_kernel_is_the_reference(*_plan_setup(lengths))


@pytest.mark.parametrize("g", [1, 4, 8])
@pytest.mark.parametrize("hkv", [1, 2, 8])
def test_decode_kernel_heads_and_groups(hkv, g, block_pages):
    block_pages(2)
    _assert_kernel_is_the_reference(
        *_plan_setup([21, 0, 80, 16], hkv=hkv, g=g, seed=hkv * 10 + g))


def test_decode_kernel_at_its_own_block_size():
    """Nothing patched: 70 pages of 8 at Hkv 2 are two blocks of 64 and 6."""
    args = _plan_setup([560, 0, 513, 512, 1], pages_per_seq=70, d=16)
    assert pa.decode_pages_per_block(2, 16, 8, 70, jnp.float32) == 64
    _assert_kernel_is_the_reference(*args)


@pytest.mark.parametrize("lengths", [[79, 0, 33, 96], [96, 64, 65, 1]])
def test_decode_kernel_int8_pools_at_page_32_with_a_partial_last_block(lengths, block_pages):
    """int8 pools on the 32-token pages the chip wants, blocks of 2 pages, 3
    pages a row: the last block is one page, and the pre-gathered scales pad
    up to a block's multiple."""
    q, k_pool, v_pool, page_table, lengths = _plan_setup(
        lengths, page_size=32, pages_per_seq=3)
    (k8, ks), (v8, vs) = _quantize_pool(k_pool), _quantize_pool(v_pool)
    block_pages(2, page_size=32)
    _assert_kernel_is_the_reference(
        q, k8, v8, page_table, lengths, k_scale=ks, v_scale=vs)


@pytest.mark.parametrize("layer", [1, 2])
def test_decode_kernel_dead_rows_on_a_layer_of_the_stack(layer, block_pages):
    q, k_pool, v_pool, page_table, lengths = _plan_setup([0, 37, 0, 80])
    block_pages(2)
    want = paged_attention(q, k_pool, v_pool, page_table, lengths, interpret=True)
    got = jax.jit(lambda li: paged_attention(
        q, _stack_with(layer, k_pool), _stack_with(layer, v_pool), page_table,
        lengths, layer=li, interpret=True))(jnp.int32(layer))
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("hkv, d, page, pages_per_seq, dtype, want", [
    (8, 128, 16, 256, jnp.bfloat16, 32),     # the benchmark's cells: 4 MB
    (8, 128, 32, 128, jnp.int8, 16),         # int8: the token ceiling binds
    (32, 128, 16, 256, jnp.bfloat16, 8),     # no GQA: a quarter of the tokens
    (1, 128, 16, 256, jnp.bfloat16, 32),
    (8, 128, 16, 4, jnp.bfloat16, 4),        # a short table is one block
    (64, 256, 16, 256, jnp.float32, 1),      # never under one page
])
def test_decode_block_follows_from_the_shapes(hkv, d, page, pages_per_seq, dtype, want):
    pb = pa.decode_pages_per_block(hkv, d, page, pages_per_seq, dtype)
    assert pb == want
    if pb > 1:
        assert 4 * hkv * pb * page * d * jnp.dtype(dtype).itemsize <= 4 << 20
