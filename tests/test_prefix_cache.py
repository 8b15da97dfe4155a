"""Radix prefix caching tests (llm/prefix_cache.py + engine hit paths).

Correctness bar: an engine WITH the prefix cache must emit exactly the greedy
tokens of an engine WITHOUT it, for both the first (miss+store) and second
(hit) admission of a shared prompt, and for prompts sharing only a prefix —
on BOTH cache backends. On the paged backend a hit must additionally share
pages PHYSICALLY (same page ids in both slots' tables, no KV copies).
"""

import asyncio

import jax
import numpy as np
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.llm.kv_cache import PagePool
from clearml_serving_tpu.llm.prefix_cache import RadixPrefixCache

CFG = {"preset": "llama-tiny", "dtype": "float32"}


@pytest.fixture(scope="module")
def parts():
    bundle = models.build_model("llama", CFG)
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


def _engine(bundle, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 160)
    kw.setdefault("prefill_buckets", [32, 64, 128])
    kw.setdefault("eos_token_id", None)
    kw.setdefault("decode_steps", 2)
    return LLMEngineCore(bundle, params, **kw)


def _gen(engine, prompt, n=6):
    async def run():
        req = GenRequest(prompt_ids=list(prompt), max_new_tokens=n)
        out = [t async for t in engine.generate(req)]
        return out

    return asyncio.run(run())


# -- unit (dense payloads) ----------------------------------------------------


def test_block_alignment_and_partial_hits():
    cache = RadixPrefixCache(max_nodes=16, block=4)
    ids = list(range(11))  # prefix cap = floor(10/4)*4 = 8
    assert cache.longest_prefix_len(len(ids)) == 8
    k = np.zeros((2, 1, 16, 2, 4), np.float32)
    cache.store(ids, 0, {"k": k, "v": k})
    hit = cache.lookup(ids, 0)
    assert hit is not None and hit["len"] == 8
    assert hit["bufs"]["k"].shape[2] == 8
    # a prompt sharing only the first 4 tokens hits PARTIALLY at block
    # granularity (the old exact-match LRU missed here)
    part = cache.lookup(ids[:4] + [99, 98, 97, 96, 95], 0)
    assert part is not None and part["len"] == 4
    assert part["bufs"]["k"].shape[2] == 4
    # a LONGER prompt sharing the 8-prefix hits the full stored run
    assert cache.lookup(ids[:8] + [55, 44, 33], 0)["len"] == 8
    # nothing shared at all -> miss
    assert cache.lookup([7, 7, 7, 7, 7], 0) is None


def test_store_extends_existing_path():
    cache = RadixPrefixCache(max_nodes=16, block=2)
    k8 = np.zeros((1, 1, 8, 1, 2), np.float32)
    cache.store([1, 2, 3], 0, {"k": k8, "v": k8})         # one block [1,2]
    assert len(cache) == 1
    cache.store([1, 2, 5, 6, 7], 0, {"k": k8, "v": k8})   # adds [5,6] below
    assert len(cache) == 2
    hit = cache.lookup([1, 2, 5, 6, 9], 0)
    assert hit["len"] == 4


def test_uncount_hit_reclassifies_as_miss():
    """A hit the engine cannot use (no prefill bucket fits) must not inflate
    the hit rate or the tokens-saved counter."""
    cache = RadixPrefixCache(max_nodes=16, block=2)
    k = np.zeros((1, 1, 8, 1, 2), np.float32)
    cache.store([1, 2, 3], 0, {"k": k, "v": k})
    hit = cache.lookup([1, 2, 9], 0)
    assert cache.hits == 1 and cache.hit_tokens == 2
    cache.uncount_hit(hit)
    assert cache.hits == 0 and cache.misses == 1 and cache.hit_tokens == 0


def test_lora_namespaces_are_separate():
    cache = RadixPrefixCache(max_nodes=16, block=2)
    ids = [1, 2, 3, 4, 5]
    k = np.zeros((1, 1, 8, 1, 2), np.float32)
    cache.store(ids, 0, {"k": k, "v": k})
    assert cache.lookup(ids, 0) is not None
    assert cache.lookup(ids, 1) is None  # adapter 1 never stored


def test_lru_leaf_eviction():
    cache = RadixPrefixCache(max_nodes=2, block=2)
    k = np.zeros((1, 1, 8, 1, 2), np.float32)
    cache.store([1, 2, 3], 0, {"k": k, "v": k})
    cache.store([4, 5, 6], 0, {"k": k, "v": k})
    assert cache.lookup([1, 2, 3], 0) is not None  # touch -> MRU
    cache.store([7, 8, 9], 0, {"k": k, "v": k})    # evicts the [4,5] leaf
    assert cache.lookup([4, 5, 6], 0) is None
    assert cache.lookup([1, 2, 3], 0) is not None
    assert cache.lookup([7, 8, 9], 0) is not None
    assert cache.evictions == 1


def test_eviction_is_leaf_first():
    """A deep path evicts from the leaf upward — an interior block with a
    surviving child is never dropped."""
    cache = RadixPrefixCache(max_nodes=3, block=2)
    k = np.zeros((1, 1, 16, 1, 2), np.float32)
    cache.store([1, 2, 3, 4, 5, 6, 7], 0, {"k": k, "v": k})  # 3 chained nodes
    cache.store([9, 9, 9], 0, {"k": k, "v": k})              # over budget
    # the chain's LEAF [5,6] went, its ancestors survived
    assert cache.lookup([1, 2, 3, 4, 0, 0, 0], 0)["len"] == 4
    assert cache.lookup([9, 9, 0], 0) is not None


def test_byte_budget_eviction():
    k = np.zeros((1, 1, 8, 1, 2), np.float32)  # 64 B per 2-token block slice
    per_block = k[:, :, :2].nbytes * 2  # k + v
    cache = RadixPrefixCache(max_nodes=64, block=2, max_bytes=2 * per_block)
    cache.store([1, 2, 3], 0, {"k": k, "v": k})
    cache.store([4, 5, 6], 0, {"k": k, "v": k})
    cache.store([7, 8, 9], 0, {"k": k, "v": k})
    assert cache.total_bytes <= 2 * per_block
    assert len(cache) == 2


# -- unit (paged payloads) ----------------------------------------------------


def _paged_cache(block=4, page_size=2, **kw):
    pool = PagePool(num_pages=32, page_size=page_size, max_slots=4)
    cache = RadixPrefixCache(
        block=block, pool=pool, page_bytes=64, **kw
    )
    return cache, pool


def test_store_pages_takes_refs_and_lookup_pins():
    cache, pool = _paged_cache()
    ids = [1, 2, 3, 4, 5, 6]  # store cap = 4 tokens = 2 pages
    pool.allocate(0, 6)
    pages = pool.slot_pages(0)
    cache.store_pages(ids, 0, pages)
    assert cache.cached_pages == 2
    assert pool.page_refcount(pages[0]) == 2  # slot + cache
    # slot finishes: cache ref keeps the prefix pages alive
    pool.free(0)
    assert pool.page_refcount(pages[0]) == 1
    assert pool.page_refcount(pages[2]) == 0  # unshared tail page freed
    hit = cache.lookup_pages([1, 2, 3, 4, 9, 9], 0)
    assert hit["len"] == 4 and hit["pages"] == pages[:2]
    assert pool.page_refcount(pages[0]) == 2  # pinned for the admission
    cache.release(hit)
    assert pool.page_refcount(pages[0]) == 1


def test_paged_eviction_never_frees_live_slot_pages():
    """Evicting a cached block whose pages a live slot still maps only drops
    the cache's reference — the pages stay allocated until the slot frees."""
    cache, pool = _paged_cache(max_nodes=1)
    pool.allocate(0, 6)
    pages0 = pool.slot_pages(0)
    cache.store_pages([1, 2, 3, 4, 5, 6], 0, pages0)
    # second prompt evicts the first (max_nodes=1) while slot 0 is LIVE
    pool.allocate(1, 6)
    cache.store_pages([7, 8, 9, 10, 11, 12], 0, pool.slot_pages(1))
    assert cache.evictions == 1
    # slot 0's pages were NOT recycled (refcount dropped to the slot's own)
    for p in pages0:
        assert pool.page_refcount(p) == 1
    free_before = pool.free_pages
    pool.free(0)
    assert pool.free_pages == free_before + len(pages0)


def test_pin_run_protects_stored_run_from_eviction():
    """The preemptible batch lane's contract (docs/slo_scheduling.md): a
    pinned run (a preempted request's stored history) survives LRU eviction
    under budget pressure; unpinning re-enables eviction."""
    cache, pool = _paged_cache(max_nodes=1)
    pool.allocate(0, 6)
    pages0 = pool.slot_pages(0)
    cache.store_pages([1, 2, 3, 4, 5, 6], 0, pages0)
    pin = cache.pin_run([1, 2, 3, 4, 5, 6], 0)
    assert pin is not None and pin["len"] == 4
    # over max_nodes with the only other leaf pinned: the NEW store's own
    # nodes are the eviction candidates, the pinned run survives
    pool.allocate(1, 6)
    cache.store_pages([7, 8, 9, 10, 11, 12], 0, pool.slot_pages(1))
    # (5-token query: the final token always computes live, so a 4-token
    # query can match at most 0)
    assert cache.match_len([1, 2, 3, 4, 9]) == 4, "pinned run was evicted"
    # a resume-style lookup still hits and pins pages as usual
    hit = cache.lookup_pages([1, 2, 3, 4, 9, 9], 0)
    assert hit is not None and hit["len"] == 4
    cache.release(hit)
    # unpin: deferred eviction brings the tree back under budget, and the
    # previously pinned run is evictable again
    cache.unpin_run(pin)
    assert len(cache) <= 1
    pool.allocate(2, 6)
    cache.store_pages([13, 14, 15, 16, 17, 18], 0, pool.slot_pages(2))
    assert cache.match_len([1, 2, 3, 4, 9]) == 0, (
        "unpinned run must be evictable"
    )


def test_pin_run_miss_returns_none_and_unpin_tolerates_it():
    cache, pool = _paged_cache()
    assert cache.pin_run([1, 2, 3], 0) is None  # nothing stored
    cache.unpin_run(None)  # no-op by contract


# -- engine (dense backend) ---------------------------------------------------


def test_hit_emits_identical_tokens(parts):
    bundle, params = parts
    prompt = [(i * 7 + 3) % 256 for i in range(40)]  # > one 16-token block

    plain = _engine(bundle, params)
    want = _gen(plain, prompt)
    plain.stop()

    cached = _engine(bundle, params, prefix_cache=8, prefix_block=16)
    first = _gen(cached, prompt)   # miss + store
    second = _gen(cached, prompt)  # hit
    assert cached._prefix.hits == 1
    assert cached._prefix.misses == 1
    cached.stop()
    assert first == want
    assert second == want


def test_shared_system_prefix_divergent_tails(parts):
    bundle, params = parts
    system = [(i * 5 + 1) % 256 for i in range(32)]
    tail_a = [9, 8, 7, 6, 5]
    tail_b = [100, 101, 102]

    plain = _engine(bundle, params)
    want_a = _gen(plain, system + tail_a)
    want_b = _gen(plain, system + tail_b)
    plain.stop()

    cached = _engine(bundle, params, prefix_cache=8, prefix_block=16)
    got_a = _gen(cached, system + tail_a)  # stores the 32-token prefix
    got_b = _gen(cached, system + tail_b)  # hits it, prefills only the tail
    assert cached._prefix.hits >= 1
    cached.stop()
    assert got_a == want_a
    assert got_b == want_b


def test_prefix_composes_with_chunked_prefill(parts):
    bundle, params = parts
    prompt = [(i * 11 + 2) % 256 for i in range(50)]

    plain = _engine(bundle, params)
    want = _gen(plain, prompt)
    plain.stop()

    cached = _engine(
        bundle, params, prefix_cache=8, prefix_block=16, chunked_prefill_size=16
    )
    first = _gen(cached, prompt)
    second = _gen(cached, prompt)
    cached.stop()
    assert first == want
    assert second == want


def test_prefix_composes_with_lora(parts):
    """Adapter-specific prefixes: the same prompt under two adapters must not
    cross-contaminate cached KV."""
    from clearml_serving_tpu.models import lora as lora_lib

    bundle = models.build_model(
        "llama", dict(CFG, lora_rank=4, max_loras=2)
    )
    params = bundle.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(3)
    ad = {}
    for t in ("wq", "wv"):
        d_in, d_out = lora_lib.target_dims(bundle.config, t)
        k1, k2, rng = jax.random.split(rng, 3)
        ad[t] = {
            "a": 0.2 * np.asarray(
                jax.random.normal(k1, (bundle.n_layers, d_in, 4))
            ),
            "b": 0.2 * np.asarray(
                jax.random.normal(k2, (bundle.n_layers, 4, d_out))
            ),
        }
    adapters = {"tuned": ad}
    prompt = [(i * 3 + 5) % 256 for i in range(36)]

    def gen(engine, adapter):
        async def run():
            req = GenRequest(
                prompt_ids=list(prompt), max_new_tokens=6, adapter=adapter
            )
            return [t async for t in engine.generate(req)]

        return asyncio.run(run())

    plain = _engine(bundle, params, lora_adapters=adapters)
    want_base = gen(plain, None)
    want_tuned = gen(plain, "tuned")
    plain.stop()

    cached = _engine(
        bundle, params, lora_adapters=adapters, prefix_cache=8, prefix_block=16
    )
    assert gen(cached, None) == want_base     # miss+store (base key)
    assert gen(cached, "tuned") == want_tuned  # MISS: adapter key differs
    assert gen(cached, "tuned") == want_tuned  # hit on the adapter's entry
    assert gen(cached, None) == want_base      # hit on the base entry
    cached.stop()


# -- engine (paged backend: zero-copy page sharing) ---------------------------


def test_paged_hit_emits_identical_tokens(parts):
    bundle, params = parts
    prompt = [(i * 7 + 3) % 256 for i in range(40)]

    plain = _engine(bundle, params, cache_mode="paged", page_size=4)
    want = _gen(plain, prompt)
    plain.stop()

    cached = _engine(
        bundle, params, cache_mode="paged", page_size=4,
        prefix_cache=64, prefix_block=16,
    )
    first = _gen(cached, prompt)   # miss + zero-copy store
    second = _gen(cached, prompt)  # hit: shared pages map by reference
    assert cached._prefix.hits == 1
    assert cached._prefix.misses == 1
    assert cached._prefix.hit_tokens == 32
    cached.stop()
    assert first == want
    assert second == want


def test_paged_hit_physically_shares_pages(parts):
    """Two concurrent admissions sharing a prefix must point their page
    tables at the SAME pool pages for the shared run (zero KV copies), and
    finishing/eviction must never free a page the other still references."""
    bundle, params = parts
    system = [(i * 5 + 1) % 256 for i in range(32)]

    engine = _engine(
        bundle, params, cache_mode="paged", page_size=4,
        prefix_cache=64, prefix_block=16,
    )
    pool = engine.paged_cache.pool
    # admission 1 stores the 32-token prefix by reference to its own pages
    _gen(engine, system + [9, 8, 7])
    # cache kept the prefix pages alive after the request finished
    stats = engine._prefix.stats()
    assert stats["cached_pages"] >= 32 // 4

    captured = {}
    orig = pool.map_shared

    def spy(slot, shared_pages, prefix_len):
        captured["pages"] = list(shared_pages)
        captured["prefix_len"] = prefix_len
        captured["slot"] = slot
        return orig(slot, shared_pages, prefix_len)

    pool.map_shared = spy
    _gen(engine, system + [100, 101, 102])  # hit -> maps shared pages
    assert captured, "paged hit never took the zero-copy mapping path"
    assert captured["prefix_len"] == 32
    # the mapped pages ARE the cached pages (by id — no copies were made)
    hit = engine._prefix.lookup_pages(system + [1, 2, 3], 0)
    assert hit["pages"] == captured["pages"]
    engine._prefix.release(hit)
    # pool accounting intact: every page the cache references is allocated
    for p in captured["pages"]:
        assert pool.page_refcount(p) >= 1
    engine.stop()


def test_paged_prefix_pool_fully_recycles_after_eviction(parts):
    """Dropping every cached node returns the pool to fully-free — no page
    leaks from the ref/unref protocol."""
    bundle, params = parts
    engine = _engine(
        bundle, params, cache_mode="paged", page_size=4,
        prefix_cache=64, prefix_block=16,
    )
    pool = engine.paged_cache.pool
    _gen(engine, [(i * 7 + 3) % 256 for i in range(40)])
    _gen(engine, [(i * 11 + 5) % 256 for i in range(36)])
    assert pool.free_pages < pool.num_pages - 1  # cache holds pages
    # force-evict everything
    engine._prefix.max_nodes = 0
    with engine._prefix._lock:
        engine._prefix._evict_over_budget()
    assert pool.free_pages == pool.num_pages - 1
    engine.stop()


def test_paged_prefix_composes_with_speculation(parts):
    """Prefix sharing + n-gram speculation on the paged engine: exact greedy
    equivalence and no page leaks (spec over-allocation truncates correctly
    around shared pages)."""
    bundle, params = parts
    prompt = [256 % 256] + [10, 20, 30, 10, 20, 30, 10, 20] * 3

    plain = _engine(bundle, params, cache_mode="paged", page_size=4)
    want = _gen(plain, prompt, n=12)
    plain.stop()

    engine = _engine(
        bundle, params, cache_mode="paged", page_size=4,
        prefix_cache=64, prefix_block=16,
        speculation="ngram", spec_k=3, spec_ngram=2,
    )
    assert _gen(engine, prompt, n=12) == want
    assert _gen(engine, prompt, n=12) == want
    assert engine._prefix.hits >= 1
    engine.stop()
