"""Paged-KV serving path: model-level and engine-level equivalence with the
dense cache path (same greedy tokens / logits)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import fill_pages

from clearml_serving_tpu import models
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.llm.kv_cache import PagedKVCache


@pytest.fixture(scope="module")
def tiny():
    bundle = models.build_model("llama", {"preset": "llama-tiny", "dtype": "float32"})
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


def test_decode_paged_matches_dense(tiny):
    bundle, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, 512)
    seq_lens = jnp.array([9, 5], jnp.int32)

    # dense reference
    dense_cache = bundle.init_cache(2, 32)
    last_dense, dense_cache = bundle.prefill(params, tokens, seq_lens, dense_cache)

    # paged: write prompts into pools, then decode step by step
    cache = PagedKVCache(
        bundle.n_layers, bundle.n_kv_heads, bundle.head_dim,
        num_pages=32, page_size=4, max_slots=2, dtype="float32",
    )
    mini = bundle.init_cache(1, 16)
    for slot, n in ((0, 9), (1, 5)):
        last, filled = bundle.prefill(
            params, tokens[slot:slot + 1, :16][:, : mini["k"].shape[2]],
            jnp.asarray([n], jnp.int32), mini,
        )
        fill_pages(cache, slot, filled["k"][:, 0, :n], filled["v"][:, 0, :n])

    next_tokens = jnp.argmax(last_dense, axis=-1).astype(jnp.int32)
    pool = cache.pool
    for step in range(4):
        lengths0 = pool.lengths().copy()
        wp = np.zeros(2, np.int32)
        wo = np.zeros(2, np.int32)
        for slot in (0, 1):
            start = pool.slot_length(slot)
            pool.extend(slot, 1)
            ((wp[slot], wo[slot]),) = pool.token_coords(slot, start, 1)
        logits_paged, cache.k, cache.v = bundle.decode_paged(
            params, next_tokens, cache.k, cache.v,
            jnp.asarray(pool.page_table(8)), jnp.asarray(lengths0),
            jnp.asarray(wp), jnp.asarray(wo),
        )
        logits_dense, dense_cache = bundle.decode(params, next_tokens, dense_cache)
        np.testing.assert_allclose(
            np.asarray(logits_paged), np.asarray(logits_dense), rtol=2e-3, atol=2e-3
        )
        next_tokens = jnp.argmax(logits_dense, axis=-1).astype(jnp.int32)


def _collect(engine, req):
    async def run():
        out = []
        async for token in engine.generate(req):
            out.append(token)
        return out

    return asyncio.run(run())


def test_scan_layers_paged_engine_matches(tiny):
    """scan_layers + paged cache produce the same greedy tokens as the plain
    unrolled dense engine; and with int8 on BOTH engines (same quantized
    weights, list vs stacked) outputs still agree — the configuration an 8B
    model is served in (int8 weights with ``scan_layers``)."""
    bundle_u, params_u = tiny
    bundle_s = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32", "scan_layers": True}
    )
    params_s = dict(params_u)
    params_s["layers"] = jax.tree.map(
        lambda *xs: jnp.stack(xs), *params_u["layers"]
    )
    common = dict(max_batch=2, max_seq_len=64, prefill_buckets=[16],
                  eos_token_id=257, decode_steps=3)
    p = [256, 11, 12, 13]

    dense = LLMEngineCore(bundle_u, params_u, cache_mode="dense", **common)
    paged_scan = LLMEngineCore(
        bundle_s, params_s, cache_mode="paged", page_size=4, **common
    )
    assert _collect(dense, GenRequest(prompt_ids=p, max_new_tokens=6)) == _collect(
        paged_scan, GenRequest(prompt_ids=p, max_new_tokens=6)
    )

    dense_q = LLMEngineCore(
        bundle_u, params_u, cache_mode="dense", quantize="int8", **common
    )
    paged_scan_q = LLMEngineCore(
        bundle_s, params_s, cache_mode="paged", page_size=4, quantize="int8", **common
    )
    assert _collect(dense_q, GenRequest(prompt_ids=p, max_new_tokens=6)) == _collect(
        paged_scan_q, GenRequest(prompt_ids=p, max_new_tokens=6)
    )


def test_paged_engine_matches_dense_engine(tiny):
    bundle, params = tiny
    prompts = [[256, 1, 2, 3], [256, 9, 8, 7, 6, 5], [256, 42]]
    common = dict(max_batch=2, max_seq_len=64, prefill_buckets=[16],
                  eos_token_id=257, decode_steps=3)

    dense = LLMEngineCore(bundle, params, cache_mode="dense", **common)
    paged = LLMEngineCore(bundle, params, cache_mode="paged", page_size=4, **common)

    for p in prompts:
        r_dense = _collect(dense, GenRequest(prompt_ids=p, max_new_tokens=7))
        r_paged = _collect(paged, GenRequest(prompt_ids=p, max_new_tokens=7))
        assert r_dense == r_paged, (p, r_dense, r_paged)

    # pages recycle: after all requests finished, the pool is fully free again
    assert paged.paged_cache.pool.free_pages == paged.paged_cache.pool.num_pages - 1


def test_paged_engine_concurrent(tiny):
    bundle, params = tiny

    async def run():
        engine = LLMEngineCore(
            bundle, params, cache_mode="paged", page_size=4,
            max_batch=2, max_seq_len=64, prefill_buckets=[16],
            eos_token_id=257, decode_steps=3,
        )
        results = await asyncio.gather(
            *[
                _collect_async(engine, GenRequest(prompt_ids=[256, i], max_new_tokens=5))
                for i in range(4)  # more requests than slots
            ]
        )
        return results, engine

    async def _collect_async(engine, req):
        out = []
        async for token in engine.generate(req):
            out.append(token)
        return out

    results, engine = asyncio.run(run())
    assert len(results) == 4 and all(len(r) >= 1 for r in results)
    assert engine.paged_cache.pool.free_pages == engine.paged_cache.pool.num_pages - 1


def _verify_rows(engine) -> int:
    """Speculative verify rows the engine's ragged launches have carried."""
    return engine.lifecycle_stats()["ragged"]["step_rows"]["spec_verify"]


def test_paged_speculative_matches_plain_paged(tiny):
    """Speculation over the paged cache (verify rows of the ragged step:
    over-allocate / truncate) is greedy-EXACT: outputs are token-identical
    to the plain paged engine — drafts hitting (repetitive prompt) and
    missing alike — and every over-allocated page rolls back to the pool."""
    bundle, params = tiny
    prompts = [
        [256] + [10, 20, 30, 10, 20, 30, 10, 20],   # repetitive: drafts hit
        [256] + list(range(40, 52)),                # no repeats: drafts miss
        [256, 99],                                  # tiny prompt
    ]
    common = dict(max_batch=2, max_seq_len=64, prefill_buckets=[16, 32],
                  eos_token_id=257, decode_steps=3)

    plain = LLMEngineCore(bundle, params, cache_mode="paged", page_size=4,
                          **common)
    spec = LLMEngineCore(
        bundle, params, cache_mode="paged", page_size=4,
        speculation="ngram", spec_k=3, spec_ngram=2, **common,
    )
    for p in prompts:
        r_plain = _collect(plain, GenRequest(prompt_ids=p, max_new_tokens=24))
        r_spec = _collect(spec, GenRequest(prompt_ids=p, max_new_tokens=24))
        assert r_plain == r_spec, (p, r_plain, r_spec)
    assert _verify_rows(spec) > 0, "no verify row ever rode a launch"
    assert _verify_rows(plain) == 0
    # truncate + finish-free bookkeeping: no page leaked
    assert spec.paged_cache.pool.free_pages == spec.paged_cache.pool.num_pages - 1


def test_paged_speculative_mixed_batch(tiny):
    """Concurrent greedy + seeded-sampled requests on the paged spec engine:
    per-slot gating keeps speculation active and both outputs match the
    plain paged engine token-for-token."""
    bundle, params = tiny
    reqs = [
        dict(prompt_ids=[256, 1, 2, 1, 2, 1, 2], max_new_tokens=10),
        dict(prompt_ids=[256, 5], max_new_tokens=10, temperature=0.9, seed=42),
    ]
    common = dict(max_batch=2, max_seq_len=64, prefill_buckets=[16],
                  eos_token_id=257, decode_steps=2)

    async def run(engine):
        return await asyncio.gather(*[
            _gather_one(engine, GenRequest(**r)) for r in reqs
        ])

    async def _gather_one(engine, req):
        out = []
        async for t in engine.generate(req):
            out.append(t)
        return out

    plain = asyncio.run(run(LLMEngineCore(
        bundle, params, cache_mode="paged", page_size=4, **common)))
    spec_engine = LLMEngineCore(
        bundle, params, cache_mode="paged", page_size=4,
        speculation="ngram", spec_k=3, **common,
    )
    spec = asyncio.run(run(spec_engine))
    assert spec == plain
    assert spec_engine.paged_cache.pool.free_pages == (
        spec_engine.paged_cache.pool.num_pages - 1
    )


def test_paged_speculative_pool_slack_fallback(tiny):
    """A pool with no room for the serial scan's slack (decode_steps * (k+1)
    tokens a slot) still serves verify rows, which over-allocate k+1 tokens
    for one launch and roll back: exact greedy output, nothing leaked."""
    bundle, params = tiny
    common = dict(max_batch=1, max_seq_len=64, prefill_buckets=[16],
                  eos_token_id=257, decode_steps=3)
    p = [256, 1, 2, 1, 2, 1]

    plain = LLMEngineCore(bundle, params, cache_mode="paged", page_size=4,
                          **common)
    want = _collect(plain, GenRequest(prompt_ids=p, max_new_tokens=8))

    # pool: 5 usable pages = 20 tokens — enough for the 6-token prompt, the
    # 8 new tokens and one verify row's k+1 = 6 on top (at most 19), but
    # NOT for 6 + decode_steps*(k+1) = 24 tokens => 6 pages
    spec = LLMEngineCore(
        bundle, params, cache_mode="paged", page_size=4,
        speculation="ngram", spec_k=5,
        num_pages=6,
        **common,
    )
    got = _collect(spec, GenRequest(prompt_ids=p, max_new_tokens=8))
    assert got == want
    assert _verify_rows(spec) > 0, "no verify row ever rode a launch"
    assert spec.paged_cache.pool.free_pages == spec.paged_cache.pool.num_pages - 1


def test_paged_pool_exhaustion_fails_only_that_request(tiny):
    """An undersized pool (oversubscription) must fail only the sequence that
    hits capacity, not the whole engine."""
    bundle, params = tiny

    async def run():
        engine = LLMEngineCore(
            bundle, params, cache_mode="paged", page_size=4,
            max_batch=2, max_seq_len=64, prefill_buckets=[16],
            eos_token_id=None, decode_steps=3,
            num_pages=2 + 16 // 4 + 1,  # room for ~1 bucket prompt + a little
        )
        ok = err = 0
        for want in (6, 40):
            try:
                out = []
                async for t in engine.generate(
                    GenRequest(prompt_ids=[256, 1, 2], max_new_tokens=want)
                ):
                    out.append(t)
                ok += 1
            except MemoryError:
                err += 1
        # engine still serves after the failure
        out = []
        async for t in engine.generate(GenRequest(prompt_ids=[256, 9], max_new_tokens=4)):
            out.append(t)
        return ok, err, len(out)

    ok, err, n = asyncio.run(run())
    assert err >= 1, "long generation should exhaust the tiny pool"
    assert n >= 1, "engine must keep serving after a capacity failure"


def test_paged_sampled_speculation(tiny):
    """Rejection-sampled speculation over the paged cache: a temperature>0
    request alone rides verify rows, completes the full budget, and the
    over-allocated pages roll back (pool fully free afterwards)."""
    bundle, params = tiny
    engine = LLMEngineCore(
        bundle, params, cache_mode="paged", page_size=4,
        speculation="ngram", spec_k=3,
        max_batch=2, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, decode_steps=2,
    )
    out = _collect(engine, GenRequest(
        prompt_ids=[256, 5, 6, 5, 6], max_new_tokens=12, temperature=0.9))
    assert len(out) == 12
    assert _verify_rows(engine) > 0, "sampled-only paged batch skipped the chain"
    assert engine.paged_cache.pool.free_pages == (
        engine.paged_cache.pool.num_pages - 1
    )
