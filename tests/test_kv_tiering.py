"""Host-RAM KV tiering tests (docs/kv_tiering.md): the HostKVTier
allocator, demote/promote byte round-trips at the pool level, run-level LRU
demotion vs pinned runs, the sanitizer's two-tier invariants, engine
stream byte-identity across a demote→promote cycle (both schedulers, both
pipeline depths, greedy + seeded, int8 KV, armed sanitizer; by an explicit
spill and by a working set over the device budget), and the chaos fallback
paths for the ``engine.kv.demote``/``engine.kv.promote`` seams."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import fill_pages

from clearml_serving_tpu import models
from clearml_serving_tpu.llm import faults
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.llm.kv_cache import HostKVTier, PagedKVCache
from clearml_serving_tpu.llm.kv_sanitizer import KVSanitizer, KVSanitizerError
from clearml_serving_tpu.llm.prefix_cache import RadixPrefixCache

QCFG = {"preset": "llama-tiny", "dtype": "float32", "kv_quant": "int8"}


@pytest.fixture(autouse=True)
def _armed_sanitizer(monkeypatch):
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def parts():
    bundle = models.build_model("llama", QCFG)
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


# -- HostKVTier allocator ------------------------------------------------------


def test_host_tier_allocator_roundtrip():
    tier = HostKVTier(4, 8, 2, 2, 16, dtype=np.int8, quantized=True)
    assert tier.free_pages == 4 and tier.used_pages == 0
    ids = tier.allocate(3)
    assert len(set(ids)) == 3 and tier.used_pages == 3
    with pytest.raises(MemoryError):
        tier.allocate(2)
    tier.free(ids[:2])
    assert tier.free_pages == 3
    with pytest.raises(RuntimeError):
        tier.free([ids[0]])  # double free
    snap = tier.snapshot()
    assert len(snap["free"]) + len(snap["used"]) == snap["num_pages"]
    assert tier.hk_scale is not None and tier.quantized
    # page_bytes covers K+V slabs and both scale rows
    assert tier.page_bytes == 2 * tier.hk[0].nbytes + 2 * tier.hk_scale[0].nbytes


# -- pool-level demote/promote byte round-trip --------------------------------


def _tiered_parts(num_pages=9, host_pages=6, page_size=4, head_dim=8):
    pc = PagedKVCache(
        2, 2, head_dim, num_pages=num_pages, page_size=page_size,
        max_slots=2, kv_quant="int8",
    )
    pc.enable_host_tier(host_pages)
    cache = RadixPrefixCache(
        block=page_size, pool=pc.pool, page_bytes=64, backend=pc,
    )
    return pc, cache


def _fill_slot(pc, slot, tokens, seed=0):
    L, H, D = pc.k.shape[0], pc.k.shape[1], pc.k.shape[4]
    rng = np.random.default_rng(seed)
    k = rng.integers(-100, 100, (L, tokens, H, D)).astype(np.int8)
    v = rng.integers(-100, 100, (L, tokens, H, D)).astype(np.int8)
    ks = rng.random((L, tokens, H)).astype(np.float32)
    vs = rng.random((L, tokens, H)).astype(np.float32)
    fill_pages(pc, slot, k, v, ks, vs)


def test_demote_promote_pages_byte_identical():
    pc, cache = _tiered_parts()
    ids = list(range(9))
    _fill_slot(pc, 0, 9)
    cache.store_pages(ids, 0, pc.pool.slot_pages(0))
    run_pages = pc.pool.slot_pages(0)[:2]
    before = {
        "k": np.asarray(pc.k[:, :, run_pages]).copy(),
        "v": np.asarray(pc.v[:, :, run_pages]).copy(),
        "ks": np.asarray(pc.k_scale[:, :, run_pages]).copy(),
        "vs": np.asarray(pc.v_scale[:, :, run_pages]).copy(),
    }
    pc.pool.free(0)
    sanitizer = KVSanitizer(pc.pool, prefix_cache=cache, paged_cache=pc)
    moved = cache.spill(0)
    assert moved == 2
    sanitizer.check("post-demote", drained=True)
    hit = cache.lookup_pages(ids)
    assert hit is not None and hit["tier"] == "host"
    sanitizer.check("post-promote")
    after_pages = hit["pages"]
    assert np.array_equal(before["k"], np.asarray(pc.k[:, :, after_pages]))
    assert np.array_equal(before["v"], np.asarray(pc.v[:, :, after_pages]))
    # the scale rows demoted and promoted WITH their pages
    assert np.array_equal(
        before["ks"], np.asarray(pc.k_scale[:, :, after_pages])
    )
    assert np.array_equal(
        before["vs"], np.asarray(pc.v_scale[:, :, after_pages])
    )
    pc.reap_promotions(force=True)
    stats = pc.tier_stats()
    assert stats["demoted_pages_total"] == 2
    assert stats["promoted_pages_total"] == 2
    assert stats["promotions_reaped"] == 1
    cache.release(hit)
    sanitizer.check("end", drained=True)


def test_bf16_pools_tier_without_scales():
    """Unquantized pools tier too: bf16 slabs, no scale buffers."""
    pc = PagedKVCache(2, 2, 8, num_pages=9, page_size=4, max_slots=2,
                      dtype="bfloat16")
    pc.enable_host_tier(6)
    cache = RadixPrefixCache(block=4, pool=pc.pool, page_bytes=64,
                             backend=pc)
    L, S, H, D = 2, 9, 2, 8
    k = jnp.arange(L * S * H * D, dtype=jnp.float32).reshape(
        L, S, H, D
    ).astype(jnp.bfloat16)
    fill_pages(pc, 0, k, k + 1)
    ids = list(range(9))
    cache.store_pages(ids, 0, pc.pool.slot_pages(0))
    before = np.asarray(
        pc.k[:, :, pc.pool.slot_pages(0)[:2]].astype(jnp.float32)
    ).copy()
    pc.pool.free(0)
    assert cache.spill(0) == 2
    hit = cache.lookup_pages(ids)
    assert hit["tier"] == "host"
    after = np.asarray(pc.k[:, :, hit["pages"]].astype(jnp.float32))
    assert np.array_equal(before, after)
    assert pc.host_tier.hk_scale is None and not pc.host_tier.quantized
    cache.release(hit)
    pc.reap_promotions(force=True)
    KVSanitizer(pc.pool, prefix_cache=cache, paged_cache=pc).check(
        "bf16", drained=True
    )


def test_second_lookup_after_promotion_is_hbm():
    pc, cache = _tiered_parts()
    ids = list(range(9))
    _fill_slot(pc, 0, 9)
    cache.store_pages(ids, 0, pc.pool.slot_pages(0))
    pc.pool.free(0)
    cache.spill(0)
    first = cache.lookup_pages(ids)
    cache.release(first)
    second = cache.lookup_pages(ids)
    assert second["tier"] == "hbm"  # promoted in place: resident again
    cache.release(second)
    assert cache.stats()["hits_by_tier"] == {"hbm": 1, "host": 1}


def test_match_len_counts_resident_run_only():
    pc, cache = _tiered_parts()
    ids = list(range(9))
    _fill_slot(pc, 0, 9)
    cache.store_pages(ids, 0, pc.pool.slot_pages(0))
    pc.pool.free(0)
    assert cache.match_len(ids) == 8
    cache.spill(0)
    # demoted pages will need fresh device allocations at promotion: the
    # admission headroom check must not subtract them
    assert cache.match_len(ids) == 0


# -- LRU / budgets / pins ------------------------------------------------------


def test_device_budget_demotes_lru_run_whole(monkeypatch):
    """Storing a new run over the device budget demotes the OLD run top to
    bottom (run-level LRU) — the new run stays fully resident."""
    pc = PagedKVCache(2, 2, 8, num_pages=17, page_size=4, max_slots=2,
                      kv_quant="int8")
    pc.enable_host_tier(8)
    cache = RadixPrefixCache(
        block=4, pool=pc.pool, page_bytes=64, backend=pc, max_pages=2,
    )
    a, b = list(range(9)), list(range(100, 109))
    _fill_slot(pc, 0, 9, seed=1)
    cache.store_pages(a, 0, pc.pool.slot_pages(0))
    _fill_slot(pc, 1, 9, seed=2)
    cache.store_pages(b, 0, pc.pool.slot_pages(1))
    s = cache.stats()
    assert s["cached_pages"] == 2 and s["host_pages"] == 2
    assert s["demotions"] == 1  # one batched round moved the whole run
    # run B resident (hbm hit), run A demoted (host hit)
    hit_b = cache.lookup_pages(b)
    assert hit_b["tier"] == "hbm"
    cache.release(hit_b)
    hit_a = cache.lookup_pages(a)
    assert hit_a["tier"] == "host"
    cache.release(hit_a)
    pc.pool.free(0)
    pc.pool.free(1)
    KVSanitizer(pc.pool, prefix_cache=cache, paged_cache=pc).check(
        "lru", drained=True
    )


def test_host_budget_drops_lru_but_skips_pinned():
    """Host-tier LRU drops for real under the host budget; pinned runs are
    immune to BOTH motions (never demoted, never host-dropped)."""
    pc = PagedKVCache(2, 2, 8, num_pages=33, page_size=4, max_slots=4,
                      kv_quant="int8")
    pc.enable_host_tier(16)
    cache = RadixPrefixCache(
        block=4, pool=pc.pool, page_bytes=64, backend=pc,
        host_max_pages=2,
    )
    runs = [list(range(i * 100, i * 100 + 9)) for i in range(3)]
    for slot, ids in enumerate(runs):
        _fill_slot(pc, slot, 9, seed=slot)
        cache.store_pages(ids, 0, pc.pool.slot_pages(slot))
        pc.pool.free(slot)
    pin = cache.pin_run(runs[0])
    assert pin is not None and pin["host_nodes"] == 0
    # 4 unpinned pages demote into a 2-page host budget: the older host
    # run (run 1) LRU-drops for real; the pinned run 0 stays RESIDENT
    cache.spill(0)
    s = cache.stats()
    assert s["host_pages"] == 2 and s["cached_pages"] == 2
    hit0 = cache.lookup_pages(runs[0])
    assert hit0 is not None and hit0["tier"] == "hbm"   # pinned: resident
    cache.release(hit0)
    assert cache.lookup_pages(runs[1]) is None  # LRU victim dropped for real
    hit2 = cache.lookup_pages(runs[2])
    assert hit2 is not None and hit2["tier"] == "host"
    cache.release(hit2)
    # a pin taken on a DEMOTED run reports the promotion plan
    pin2 = cache.pin_run(runs[2])
    assert pin2 is not None and pin2["host_nodes"] == 0  # just promoted
    cache.unpin_run(pin2)
    cache.unpin_run(pin)
    KVSanitizer(pc.pool, prefix_cache=cache, paged_cache=pc).check(
        "host-lru", drained=True
    )


def test_pinned_runs_are_never_demoted():
    pc, cache = _tiered_parts(num_pages=17, host_pages=8)
    ids = list(range(9))
    _fill_slot(pc, 0, 9)
    cache.store_pages(ids, 0, pc.pool.slot_pages(0))
    pc.pool.free(0)
    pin = cache.pin_run(ids)
    assert cache.spill(0) == 0  # whole run pinned: nothing to demote
    assert cache.stats()["cached_pages"] == 2
    cache.unpin_run(pin)
    assert cache.spill(0) == 2


def test_store_reonlines_demoted_path_by_reference():
    """A store whose walk crosses demoted nodes re-points them at the
    admitting slot's own pages (zero copies) before attaching below."""
    pc, cache = _tiered_parts(num_pages=17, host_pages=8)
    ids = list(range(13))  # 12 cacheable tokens = 3 blocks
    _fill_slot(pc, 0, 13)
    cache.store_pages(ids[:9], 0, pc.pool.slot_pages(0))  # 2 blocks
    cache.spill(0)
    assert cache.stats()["host_pages"] == 2
    cache.store_pages(ids, 0, pc.pool.slot_pages(0))      # extends to 3
    s = cache.stats()
    assert s["host_pages"] == 0 and s["cached_pages"] == 3
    assert s["promotions"] == 1  # one run re-onlined by reference
    hit = cache.lookup_pages(ids)
    assert hit["tier"] == "hbm" and hit["len"] == 12
    cache.release(hit)
    pc.pool.free(0)
    KVSanitizer(pc.pool, prefix_cache=cache, paged_cache=pc).check(
        "reonline", drained=True
    )


def test_promotion_pool_pressure_falls_back_to_resident_prefix():
    """No free device pages for the promotion: the demoted suffix drops
    and the hit shortens (recompute), leak-free."""
    pc, cache = _tiered_parts(num_pages=9, host_pages=8)
    ids = list(range(9))
    _fill_slot(pc, 0, 9)
    cache.store_pages(ids, 0, pc.pool.slot_pages(0))
    cache.spill(0)
    pc.pool.free(0)
    # grab every free page so allocate_cache_pages must fail
    hog = pc.pool.allocate(1, 8 * pc.pool.page_size)
    assert hog is not None
    hit = cache.lookup_pages(ids)
    assert hit is None  # whole run was demoted; nothing resident remains
    assert cache.stats()["host_pages"] == 0  # dropped, not leaked
    pc.pool.free(1)
    KVSanitizer(pc.pool, prefix_cache=cache, paged_cache=pc).check(
        "fallback", drained=True
    )


def test_promotion_failure_never_drops_pinned_suffix():
    """A pin_run holder was PROMISED its (demoted) history survives: a
    different request's failed promotion must not drop the pinned suffix —
    the hit shortens, the pinned run stays for the pin holder's resume."""
    pc, cache = _tiered_parts(num_pages=9, host_pages=8)
    ids = list(range(9))
    _fill_slot(pc, 0, 9)
    cache.store_pages(ids, 0, pc.pool.slot_pages(0))
    cache.spill(0)
    pc.pool.free(0)
    pin = cache.pin_run(ids)
    assert pin is not None and pin["host_nodes"] == 2
    # exhaust the pool so promotion's allocate_cache_pages must fail
    pc.pool.allocate(1, 8 * pc.pool.page_size)
    hit = cache.lookup_pages(ids)
    assert hit is None  # fully demoted run: hit degrades to a miss
    # ...but the pinned host run SURVIVED for the pin holder
    assert cache.stats()["host_pages"] == 2
    pc.pool.free(1)
    resumed = cache.lookup_pages(ids)
    assert resumed is not None and resumed["tier"] == "host"
    cache.release(resumed)
    cache.unpin_run(pin)
    KVSanitizer(pc.pool, prefix_cache=cache, paged_cache=pc).check(
        "pinned-survives", drained=True
    )


def test_host_tier_knob_validation(parts):
    """Inert host-tier configs fail at construction (= endpoint load),
    naming the knob — a budget that silently does nothing reads as
    'tiering on' to the operator."""
    bundle, params = parts
    with pytest.raises(ValueError, match="prefix_cache_host_pages"):
        _engine(bundle, params, prefix_cache_host_bytes=1 << 20)
    with pytest.raises(ValueError, match="cache_mode='paged'"):
        _engine(bundle, params, host_pages=16, cache_mode="dense")
    with pytest.raises(ValueError, match="prefix_cache"):
        _engine(bundle, params, host_pages=16, prefix_cache=None)


# -- sanitizer two-tier violations --------------------------------------------


def test_sanitizer_catches_dual_payload_node():
    pc, cache = _tiered_parts()
    ids = list(range(9))
    _fill_slot(pc, 0, 9)
    cache.store_pages(ids, 0, pc.pool.slot_pages(0))
    cache.spill(0)
    node = next(iter(cache._leaf_nodes))
    node.pages = [1]  # corrupt: both tiers at once
    with pytest.raises(KVSanitizerError, match="exactly one tier"):
        KVSanitizer(pc.pool, prefix_cache=cache, paged_cache=pc).check("dual")


def test_sanitizer_catches_orphaned_host_page():
    pc, cache = _tiered_parts()
    ids = list(range(9))
    _fill_slot(pc, 0, 9)
    cache.store_pages(ids, 0, pc.pool.slot_pages(0))
    cache.spill(0)
    pc.host_tier.allocate(1)  # allocated but referenced by no node
    with pytest.raises(KVSanitizerError, match="ownership"):
        KVSanitizer(pc.pool, prefix_cache=cache, paged_cache=pc).check("orphan")


def test_sanitizer_catches_host_free_list_corruption():
    pc, cache = _tiered_parts()
    pc.host_tier._free.append(pc.host_tier._free[-1])  # duplicate id
    with pytest.raises(KVSanitizerError, match="duplicates"):
        KVSanitizer(pc.pool, prefix_cache=cache, paged_cache=pc).check("dupe")


def test_sanitizer_catches_lost_host_free():
    """A dropped node that forgot to free its host ids leaves the id
    allocated-but-unreferenced — the drain audit names it."""
    pc, cache = _tiered_parts()
    ids = list(range(9))
    _fill_slot(pc, 0, 9)
    cache.store_pages(ids, 0, pc.pool.slot_pages(0))
    cache.spill(0)
    pc.pool.free(0)
    # simulate the bug: node dropped without HostKVTier.free
    node = next(iter(cache._leaf_nodes))
    node.host_pages = None
    with pytest.raises(KVSanitizerError):
        KVSanitizer(pc.pool, prefix_cache=cache, paged_cache=pc).check(
            "lost-free", drained=True
        )


# -- engine byte-identity ------------------------------------------------------


def _engine(bundle, params, host_pages=None, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("prefill_buckets", [16, 32, 64])
    kw.setdefault("eos_token_id", None)
    kw.setdefault("decode_steps", 2)
    kw.setdefault("cache_mode", "paged")
    kw.setdefault("page_size", 16)
    kw.setdefault("prefix_cache", 64)
    kw.setdefault("prefix_block", 16)
    if host_pages:
        kw["prefix_cache_host_pages"] = host_pages
    return LLMEngineCore(bundle, params, **kw)


def _gen(engine, prompt, n=8, **req_kw):
    async def run():
        req = GenRequest(prompt_ids=list(prompt), max_new_tokens=n, **req_kw)
        out = [t async for t in engine.generate(req)]
        await engine.wait_drained()
        return out

    return asyncio.run(run())


PROMPT = [(7 * i + 3) % 100 + 1 for i in range(40)]  # 2 cached blocks
OTHER = [(11 * i + 5) % 100 + 1 for i in range(40)]  # pushes PROMPT out


@pytest.mark.parametrize("depth,demoted_by", [
    (1, "spill"), (2, "spill"), (1, "budget"),
])
def test_demoted_warm_hit_streams_byte_identical(parts, depth, demoted_by):
    """ACCEPTANCE: a demoted-then-promoted prefix run produces streams
    byte-identical to an always-resident warm hit — greedy, int8 KV,
    pipeline depth 1 and 2, armed sanitizer. ``budget``: nobody spills by
    hand; the working set (two prompts of two blocks) is over the device
    budget of two pages, so the second prompt's store demotes the first,
    whose revisit is a host hit where an untiered engine prefills cold."""
    bundle, params = parts
    control = _engine(bundle, params, pipeline_depth=depth)
    _gen(control, PROMPT)
    resident = _gen(control, PROMPT)
    assert control._prefix.stats()["hits_by_tier"]["hbm"] >= 1
    control.stop()

    tight = {"prefix_cache_pages": 2} if demoted_by == "budget" else {}
    tiered = _engine(
        bundle, params, host_pages=16, pipeline_depth=depth, **tight
    )
    _gen(tiered, PROMPT)
    if demoted_by == "budget":
        _gen(tiered, OTHER)
    else:
        assert tiered._prefix.spill(0) == 2
    promoted = _gen(tiered, PROMPT)
    assert promoted == resident
    stats = tiered.lifecycle_stats()["kv_tier"]
    assert stats["hits_by_tier"]["host"] >= 1
    assert stats["demoted_pages_total"] >= 2
    assert stats["promoted_pages_total"] == 2
    assert stats["demotions"] >= 1 and stats["promotions"] >= 1
    assert 0.0 <= stats["promo_overlap_ratio"] <= 1.0
    assert tiered._sanitizer.failures == 0
    tiered.stop()
    if demoted_by != "budget":
        return

    untiered = _engine(bundle, params, pipeline_depth=depth, **tight)
    _gen(untiered, PROMPT)
    _gen(untiered, OTHER)
    assert _gen(untiered, PROMPT) == resident
    s = untiered._prefix.stats()
    assert s["hits_by_tier"] == {"hbm": 0, "host": 0}, s  # evicted: cold
    assert s["evictions"] >= 1 and s["demotions"] == 0
    assert untiered._sanitizer.failures == 0
    untiered.stop()


def test_demoted_warm_hit_seeded_sampling_replays(parts):
    bundle, params = parts
    engine = _engine(bundle, params, host_pages=16)
    a = _gen(engine, PROMPT, temperature=0.8, seed=1234)
    engine._prefix.spill(0)
    b = _gen(engine, PROMPT, temperature=0.8, seed=1234)
    assert a == b
    assert engine.lifecycle_stats()["kv_tier"]["hits_by_tier"]["host"] >= 1
    engine.stop()


# -- chaos: fault seams --------------------------------------------------------


def test_chaos_promote_fault_falls_back_to_recompute(parts):
    """Injected engine.kv.promote mid-admission: the hit degrades to a
    recompute, the stream is unchanged, and nothing leaks (armed
    sanitizer + explicit drained audit)."""
    bundle, params = parts
    engine = _engine(bundle, params, host_pages=16)
    cold = _gen(engine, PROMPT)
    engine._prefix.spill(0)
    faults.configure([
        {"point": "engine.kv.promote", "action": "raise", "times": 1},
    ])
    try:
        warm = _gen(engine, PROMPT)
    finally:
        faults.clear()
    assert warm == cold
    s = engine._prefix.stats()
    assert s["host_pages"] == 0      # demoted suffix dropped, ids freed
    assert s["hits_by_tier"]["host"] == 0
    assert engine._sanitizer is not None
    assert engine._sanitizer.failures == 0
    engine.stop()


def test_chaos_demote_fault_drops_for_real(parts):
    """Injected engine.kv.demote: eviction drops instead of demoting —
    the next visit is a cold recompute but accounting stays clean."""
    bundle, params = parts
    engine = _engine(bundle, params, host_pages=16,
                     prefix_cache_pages=2)
    cold = _gen(engine, PROMPT)
    other = [(11 * i + 5) % 100 + 1 for i in range(40)]
    faults.configure([
        {"point": "engine.kv.demote", "action": "raise", "times": -1},
    ])
    try:
        _gen(engine, other)  # stores over budget: eviction must drop
    finally:
        faults.clear()
    s = engine._prefix.stats()
    assert s["host_pages"] == 0 and s["demotions"] == 0
    assert s["evictions"] >= 1
    warm = _gen(engine, PROMPT)
    assert warm == cold
    assert engine._sanitizer is not None and engine._sanitizer.failures == 0
    engine.stop()
