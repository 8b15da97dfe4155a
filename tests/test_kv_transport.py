"""Disaggregated prefill/decode tests (docs/disaggregation.md): the
SharedSlabTransport mailbox, pool-level export/import byte round-trips,
``store_shipped`` attach semantics, the host-tier auto-sizer, role-aware
routing, and the group end-to-end contracts — two-replica disaggregated
streams exactly equal monolithic single-replica streams (greedy + seeded,
int8 paged KV, armed sanitizer), ship/receive chaos fallbacks, and the
kill-prefill-replica-mid-ship drain."""

import asyncio

import jax
import numpy as np
import pytest
from conftest import fill_pages

from clearml_serving_tpu import models
from clearml_serving_tpu.errors import HostTierAutoSizeError
from clearml_serving_tpu.llm import faults
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.llm.kv_cache import (
    PagedKVCache,
    available_host_memory_bytes,
)
from clearml_serving_tpu.llm.kv_transport import (
    KVShipment,
    SharedSlabTransport,
    shipment_key,
)
from clearml_serving_tpu.llm.prefix_cache import RadixPrefixCache
from clearml_serving_tpu.llm.replica import ReplicaGroup
from clearml_serving_tpu.serving.replica_router import ReplicaRouter

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


# -- shipment keys ------------------------------------------------------------


def test_shipment_key_block_aligned_and_lora_namespaced():
    ids = list(range(1, 20))
    k1 = shipment_key(ids, 8)
    # the final token never ships: any prompt sharing the storable prefix
    # derives the same key
    assert shipment_key(ids[:17], 8) == k1          # depth 16 both
    assert shipment_key(ids + [99], 8) == k1        # still depth 16
    assert shipment_key(ids + list(range(90, 96)), 8) != k1  # depth 24
    assert shipment_key(list(range(2, 21)), 8) != k1  # different tokens
    assert shipment_key(ids, 8, lora=1) != k1       # per-adapter namespace
    # block size reaches the key THROUGH the alignment depth (replicas in
    # one group share a block config, so sender and receiver agree)
    assert shipment_key(ids, 5) != k1               # depth 15, not 16


def _shipment(pages=2, page_size=4, value=7, quantized=False, **kw):
    shape = (pages, 1, 1, page_size, 2)
    hk = np.full(shape, value, np.int8)
    kwargs = dict(
        key=kw.pop("key", b"k" * 16), src="r0",
        prefix_len=pages * page_size, page_size=page_size, lora=0,
        hk=hk, hv=hk.copy(),
    )
    if quantized:
        kwargs["hk_scale"] = np.ones(shape[:-1], np.float32)
        kwargs["hv_scale"] = np.ones(shape[:-1], np.float32)
    kwargs.update(kw)
    return KVShipment(**kwargs)


# -- SharedSlabTransport mailbox ----------------------------------------------


def test_transport_send_recv_is_consume_once():
    t = SharedSlabTransport(capacity_pages=8)
    ep = t.register("decode")
    assert ep.recv(b"k" * 16) is None
    assert t.send("decode", _shipment()) is True
    got = ep.recv(b"k" * 16)
    assert got is not None and got.pages == 2
    assert ep.recv(b"k" * 16) is None       # consumed
    assert t.received == 1 and t.sent == 1 and t.dropped == 0


def test_transport_capacity_drops_oldest_first():
    t = SharedSlabTransport(capacity_pages=4)
    t.register("decode")
    assert t.send("decode", _shipment(key=b"a" * 16))
    assert t.send("decode", _shipment(key=b"b" * 16))
    # a third 2-page shipment exceeds the 4-page slab: the OLDEST ages out
    assert t.send("decode", _shipment(key=b"c" * 16))
    assert t.recv("decode", b"a" * 16) is None
    assert t.recv("decode", b"b" * 16) is not None
    assert t.recv("decode", b"c" * 16) is not None
    assert t.dropped == 1 and t.dropped_pages == 2


def test_transport_oversized_shipment_is_dropped_not_queued():
    t = SharedSlabTransport(capacity_pages=4)
    t.register("decode")
    assert t.send("decode", _shipment(pages=8, key=b"z" * 16)) is False
    assert t.dropped == 1
    assert t.recv("decode", b"z" * 16) is None


def test_transport_reship_replaces_stale_payload():
    t = SharedSlabTransport(capacity_pages=8)
    t.register("decode")
    t.send("decode", _shipment(value=1))
    t.send("decode", _shipment(value=2))
    got = t.recv("decode", b"k" * 16)
    assert int(got.hk[0, 0, 0, 0, 0]) == 2
    assert t.stats()["queued"]["decode"] == {"shipments": 0, "pages": 0}


def test_transport_rejects_nonpositive_capacity():
    with pytest.raises(ValueError):
        SharedSlabTransport(capacity_pages=0)


# -- pool-level export/import round trip --------------------------------------


def _paged(num_pages=9, page_size=4, kv_quant="int8"):
    return PagedKVCache(
        2, 2, 8, num_pages=num_pages, page_size=page_size, max_slots=2,
        kv_quant=kv_quant,
    )


def _fill_slot(pc, slot, tokens, seed=0):
    """Write deterministic prompt KV (+ scales on int8 pools) into a slot."""
    rng = np.random.default_rng(seed)
    shape = (2, tokens, 2, 8)   # [L, S, Hkv, D]
    if pc.kv_quant:
        k = rng.integers(-100, 100, shape).astype(np.int8)
        v = rng.integers(-100, 100, shape).astype(np.int8)
        ks = rng.random(shape[:-1], np.float32)
        vs = rng.random(shape[:-1], np.float32)
        fill_pages(pc, slot, k, v, ks, vs)
    else:
        k = rng.random(shape, np.float32)
        v = rng.random(shape, np.float32)
        fill_pages(pc, slot, k, v)


@pytest.mark.parametrize("kv_quant", ["int8", ""])
def test_export_import_roundtrip_bytes(kv_quant):
    src = _paged(kv_quant=kv_quant)
    dst = _paged(kv_quant=kv_quant)
    _fill_slot(src, 0, 8, seed=3)
    pages = src.pool.slot_pages(0)
    slabs = src.export_pages(pages)
    assert slabs["hk"].shape[0] == len(pages) == 2
    fresh = dst.pool.allocate_cache_pages(len(pages))
    dst.import_pages(
        slabs["hk"], slabs["hv"], fresh,
        slabs.get("hk_scale"), slabs.get("hv_scale"),
    )
    assert dst.reap_promotions(force=True) == 1
    out = dst.export_pages(fresh)
    for name in slabs:
        np.testing.assert_array_equal(slabs[name], out[name])
    dst.pool.unref_pages(fresh)
    src.pool.free(0)


def test_import_pages_validates_scales_and_row_count():
    dst = _paged(kv_quant="int8")
    rows = np.zeros((2, 2, 2, 4, 8), np.int8)
    with pytest.raises(ValueError):
        dst.import_pages(rows, rows, [1, 2])        # int8 pool, no scales
    with pytest.raises(ValueError):
        dst.import_pages(rows, rows, [1, 2, 3],
                         np.zeros((2, 2, 2, 4), np.float32),
                         np.zeros((2, 2, 2, 4), np.float32))  # 2 rows != 3


# -- store_shipped (radix attach) ---------------------------------------------


def _export_shipment(pc, slot, ids, block):
    p = ((len(ids) - 1) // block) * block
    pages = pc.pool.slot_pages(slot)[: p // pc.pool.page_size]
    slabs = pc.export_pages(pages)
    return KVShipment(
        key=shipment_key(ids, block, 0), src="r0", prefix_len=p,
        page_size=pc.pool.page_size, lora=0,
        hk=slabs["hk"], hv=slabs["hv"],
        hk_scale=slabs.get("hk_scale"), hv_scale=slabs.get("hv_scale"),
    )


def test_store_shipped_attaches_only_missing_blocks():
    from clearml_serving_tpu.llm.kv_sanitizer import KVSanitizer

    block = 4
    src = _paged()
    ids = list(range(10, 23))    # 13 tokens -> 12 storable = 3 blocks
    _fill_slot(src, 0, 13, seed=5)
    shipment = _export_shipment(src, 0, ids, block)
    assert shipment.pages == 3

    dst = _paged(num_pages=17)
    cache = RadixPrefixCache(block=block, pool=dst.pool, page_bytes=64)
    # pre-store the FIRST block by reference from a live slot: the import
    # must then attach only the two missing blocks
    _fill_slot(dst, 0, 5, seed=6)
    cache.store_pages(ids[:5], 0, dst.pool.slot_pages(0))
    assert cache.match_len(ids) == block
    imported = cache.store_shipped(ids, 0, shipment, dst)
    assert imported == 2
    assert dst.reap_promotions(force=True) == 1
    assert cache.match_len(ids) == 12
    # re-import of the same shipment: nothing missing, nothing allocated
    assert cache.store_shipped(ids, 0, shipment, dst) == 0
    # a hit over the shipped run pins/maps like any radix hit
    hit = cache.lookup_pages(ids)
    assert hit is not None and hit["len"] == 12
    cache.release(hit)
    dst.pool.free(0)
    KVSanitizer(dst.pool, prefix_cache=cache).check("shipped", drained=True)


def test_store_shipped_validates_geometry():
    src = _paged()
    ids = list(range(9))
    _fill_slot(src, 0, 9, seed=1)
    shipment = _export_shipment(src, 0, ids, 4)
    dst_wrong_page = _paged(page_size=8)
    cache = RadixPrefixCache(
        block=8, pool=dst_wrong_page.pool, page_bytes=64
    )
    with pytest.raises(ValueError):
        cache.store_shipped(ids, 0, shipment, dst_wrong_page)
    # scale mismatch: strip the scales off an int8 shipment
    shipment.hk_scale = None
    shipment.hv_scale = None
    dst = _paged()
    cache2 = RadixPrefixCache(block=4, pool=dst.pool, page_bytes=64)
    with pytest.raises(ValueError):
        cache2.store_shipped(ids, 0, shipment, dst)


def test_store_shipped_pool_pressure_is_leak_free():
    from clearml_serving_tpu.llm.kv_sanitizer import KVSanitizer

    src = _paged()
    ids = list(range(13))
    _fill_slot(src, 0, 13, seed=2)
    shipment = _export_shipment(src, 0, ids, 4)
    dst = _paged(num_pages=3)    # 2 usable pages < the 3-page shipment
    cache = RadixPrefixCache(block=4, pool=dst.pool, page_bytes=64)
    with pytest.raises(MemoryError):
        cache.store_shipped(ids, 0, shipment, dst)
    assert cache.match_len(ids) == 0
    KVSanitizer(dst.pool, prefix_cache=cache).check("pressure", drained=True)


# -- host-tier auto-sizing (aux prefix_cache_host_mb: "auto") ------------------


def test_meminfo_probe_parses_and_names_failures(tmp_path):
    good = tmp_path / "meminfo"
    good.write_text("MemTotal: 100 kB\nMemAvailable:     2048 kB\n")
    assert available_host_memory_bytes(str(good)) == 2048 * 1024
    with pytest.raises(HostTierAutoSizeError, match="auto"):
        available_host_memory_bytes(str(tmp_path / "missing"))
    no_field = tmp_path / "nofield"
    no_field.write_text("MemTotal: 100 kB\n")
    with pytest.raises(HostTierAutoSizeError, match="MemAvailable"):
        available_host_memory_bytes(str(no_field))


def _auto_engine(bundle, params, monkeypatch, avail_bytes, **overrides):
    from clearml_serving_tpu.llm import kv_cache

    monkeypatch.setattr(
        kv_cache, "available_host_memory_bytes", lambda *a: avail_bytes
    )
    cfg = dict(
        max_batch=2, max_seq_len=64, prefill_buckets=[16, 32],
        eos_token_id=None, decode_steps=1, cache_mode="paged",
        page_size=16, prefix_cache=64, prefix_block=16,
        prefix_cache_host_bytes="auto",
    )
    cfg.update(overrides)
    return LLMEngineCore(bundle, params, **cfg)


def test_auto_host_tier_sizes_clamped_from_meminfo(parts, monkeypatch):
    from clearml_serving_tpu.llm.engine import (
        _AUTO_HOST_TIER_MIN_BYTES,
    )

    bundle, params = parts
    engine = _auto_engine(bundle, params, monkeypatch, 512 << 20)
    tier = engine.paged_cache.host_tier
    assert tier is not None
    page_bytes = (
        sum(engine.paged_cache.pool_bytes().values())
        // engine.paged_cache.pool.num_pages
    )
    assert tier.num_pages == max(1, (256 << 20) // page_bytes)
    engine.stop()
    # a tiny host still gets the clamp floor's worth of pages
    engine2 = _auto_engine(bundle, params, monkeypatch, 8 << 20)
    assert engine2.paged_cache.host_tier.num_pages == max(
        1, _AUTO_HOST_TIER_MIN_BYTES // page_bytes
    )
    engine2.stop()


def test_auto_host_tier_divides_by_cohosted_worker_count(parts, monkeypatch):
    """The half-of-MemAvailable heuristic is PER HOST: process-backend
    workers co-hosted on one machine (TPUSERVE_COHOSTED_PROCS,
    serving/process_replica.py) must split the budget, or an N-worker
    fleet over-commits host RAM N times over."""
    from clearml_serving_tpu.llm.kv_cache import cohosted_worker_processes

    bundle, params = parts
    monkeypatch.delenv("TPUSERVE_COHOSTED_PROCS", raising=False)
    assert cohosted_worker_processes() == 1
    solo = _auto_engine(bundle, params, monkeypatch, 4 << 30)
    solo_pages = solo.paged_cache.host_tier.num_pages
    solo.stop()

    monkeypatch.setenv("TPUSERVE_COHOSTED_PROCS", "2")
    assert cohosted_worker_processes() == 2
    duo = _auto_engine(bundle, params, monkeypatch, 4 << 30)
    assert duo.paged_cache.host_tier.num_pages == solo_pages // 2
    duo.stop()

    # garbage / sub-1 values degrade to the solo divisor, never crash
    monkeypatch.setenv("TPUSERVE_COHOSTED_PROCS", "banana")
    assert cohosted_worker_processes() == 1
    monkeypatch.setenv("TPUSERVE_COHOSTED_PROCS", "0")
    assert cohosted_worker_processes() == 1


def test_auto_host_tier_probe_failure_fails_construction(parts, monkeypatch):
    from clearml_serving_tpu.llm import kv_cache

    bundle, params = parts

    def boom(*a):
        raise HostTierAutoSizeError("no /proc/meminfo on this platform")

    monkeypatch.setattr(kv_cache, "available_host_memory_bytes", boom)
    cfg = dict(
        max_batch=2, max_seq_len=64, prefill_buckets=[16, 32],
        cache_mode="paged", page_size=16, prefix_cache=64,
        prefix_block=16, prefix_cache_host_bytes="auto",
    )
    with pytest.raises(HostTierAutoSizeError, match="platform"):
        LLMEngineCore(bundle, params, **cfg)


def test_auto_host_tier_knob_conflicts_are_named(parts):
    bundle, params = parts
    cfg = dict(
        max_batch=2, max_seq_len=64, prefill_buckets=[16, 32],
        cache_mode="paged", page_size=16, prefix_cache=64, prefix_block=16,
    )
    with pytest.raises(ValueError, match="prefix_cache_host_pages"):
        LLMEngineCore(
            bundle, params, prefix_cache_host_bytes="auto",
            prefix_cache_host_pages=8, **cfg
        )
    with pytest.raises(ValueError, match="auto"):
        LLMEngineCore(
            bundle, params, prefix_cache_host_bytes="always", **cfg
        )
    # auto on a dense engine fails like an explicit page count would
    with pytest.raises(ValueError, match="paged"):
        LLMEngineCore(
            bundle, params, max_batch=2, max_seq_len=64,
            prefill_buckets=[16, 32], cache_mode="dense",
            prefix_cache=64, prefix_block=16,
            prefix_cache_host_bytes="auto",
        )


# -- role-aware routing (stub level) ------------------------------------------


class StubReplica:
    def __init__(self, index, ready=True, warmed=True, depth=0, stage=0):
        self.index = index
        self.name = "r{}".format(index)
        self.engine_ready = ready
        self.warmed = warmed
        self.queue_depth = depth
        self.brownout_stage = stage
        self.warming = False

    def invalidate_warm(self):
        self.warmed = False

    def begin_warm(self):
        self.warmed = True


def _role_router(roles, stubs=None, **kw):
    stubs = stubs or [StubReplica(i) for i in range(len(roles))]
    return ReplicaRouter(
        stubs,
        roles={s.name: r for s, r in zip(stubs, roles)},
        **kw
    ), stubs


def _req(ids, priority="interactive"):
    return GenRequest(prompt_ids=list(ids), priority=priority)


def test_streams_route_to_decode_capable_members_only():
    router, stubs = _role_router(["prefill", "decode", "hybrid"])
    for seed in range(8):
        ids = [(seed * 31 + i) % 97 + 1 for i in range(40)]
        replica, route = router.pick(_req(ids))
        assert router.role_of(replica.name) in ("decode", "hybrid")


def test_empty_decode_class_degrades_to_any_ring_member():
    router, stubs = _role_router(["prefill", "decode"])
    stubs[1].engine_ready = False   # the only decode member leaves
    router.sweep()
    # hybrid degradation: the prefill-role member takes the stream
    # rather than shedding it (route label = HRW order within the
    # degraded candidate set)
    replica, route = router.pick(_req(list(range(40))))
    assert replica.name == "r0"
    assert route in ("affine", "rebalance")


def test_pick_prefill_prefers_dedicated_and_skips_brownout():
    router, stubs = _role_router(["prefill", "decode", "hybrid"])
    pre = router.pick_prefill(_req(list(range(40))), exclude="r1")
    assert pre is not None and pre.name == "r0"     # dedicated wins
    stubs[0].brownout_stage = 2                     # browned out: skip
    pre = router.pick_prefill(_req(list(range(40))), exclude="r1")
    assert pre is not None and pre.name == "r2"     # hybrid fallback
    stubs[2].engine_ready = False
    router.sweep()
    assert router.pick_prefill(_req(list(range(40))), exclude="r1") is None


def test_router_stats_carry_roles():
    router, _ = _role_router(["prefill", "decode"])
    stats = router.stats()
    assert stats["roles"] == {"r0": "prefill", "r1": "decode"}


def test_router_rejects_bad_roles():
    stubs = [StubReplica(0), StubReplica(1)]
    with pytest.raises(ValueError, match="role"):
        ReplicaRouter(stubs, roles={"r0": "decoder", "r1": "decode"})
    with pytest.raises(ValueError, match="unknown replica"):
        ReplicaRouter(stubs, roles={"rX": "decode"})


# -- group end-to-end (real engines, int8 paged KV) ---------------------------


def _make_group(bundle, params, n=2, roles=None, kv_backend="shared",
                **overrides):
    cfg = dict(
        max_batch=2, max_seq_len=128, prefill_buckets=[16, 32, 64],
        eos_token_id=None, decode_steps=1, cache_mode="paged",
        page_size=16, prefix_cache=64, prefix_block=16, num_pages=65,
        pipeline_depth=1,
    )
    cfg.update(overrides)
    engines = [
        LLMEngineCore(bundle, params, replica="r{}".format(i), **cfg)
        for i in range(n)
    ]
    return ReplicaGroup(engines, roles=roles, kv_transport_backend=kv_backend)


# both KV transport backends run the SAME chaos contracts (the socket
# variants are tier-2: they re-build full engine fleets, so they ride the
# `slow` lane alongside the process-backend suite)
BACKENDS = [
    "shared",
    pytest.param("socket", marks=pytest.mark.slow),
]


def _conv(seed, n=44):
    return [(seed * 29 + i * 7) % 200 + 1 for i in range(n)]


async def _collect(group, ids, n=5, **kw):
    request = GenRequest(prompt_ids=list(ids), max_new_tokens=n, **kw)
    out = []
    async for token in group.generate(request):
        out.append(int(token))
    return out, request


def _drained_clean(group):
    async def check():
        await group.wait_drained()

    asyncio.run(check())
    for replica in group.replicas:
        sanitizer = replica.engine._sanitizer
        assert sanitizer is not None
        assert sanitizer.stats()["failures"] == 0


def test_group_roles_validation():
    # length mismatch and bad values fail at construction (endpoint load)
    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engines = [
        LLMEngineCore(
            bundle, params, max_batch=1, max_seq_len=32,
            prefill_buckets=[16], cache_mode="paged", page_size=16,
            prefix_cache=16, prefix_block=16,
        )
        for _ in range(2)
    ]
    with pytest.raises(ValueError, match="replica_roles"):
        ReplicaGroup(list(engines), roles=["prefill"])
    with pytest.raises(ValueError, match="prefill/decode/hybrid"):
        ReplicaGroup(list(engines), roles=["prefill", "decoder"])
    with pytest.raises(ValueError, match="decode-capable"):
        ReplicaGroup(list(engines), roles=["prefill", "prefill"])
    # dense engines cannot disaggregate (no pages to ship)
    dense = [
        LLMEngineCore(
            bundle, params, max_batch=1, max_seq_len=32,
            prefill_buckets=[16], cache_mode="dense",
        )
        for _ in range(2)
    ]
    with pytest.raises(ValueError, match="paged"):
        ReplicaGroup(dense, roles=["prefill", "decode"])
    for e in engines + dense:
        e.stop()


def test_group_rejects_unknown_kv_transport_backend(parts):
    bundle, params = parts
    engines = [
        LLMEngineCore(
            bundle, params, replica="r{}".format(i), max_batch=1,
            max_seq_len=32, prefill_buckets=[16], cache_mode="paged",
            page_size=16, prefix_cache=16, prefix_block=16,
        )
        for i in range(2)
    ]
    with pytest.raises(ValueError, match="kv_transport_backend"):
        ReplicaGroup(
            engines, roles=["prefill", "decode"],
            kv_transport_backend="carrier-pigeon",
        )
    for e in engines:
        e.stop()


def test_disagg_streams_equal_monolithic_greedy_and_seeded(parts):
    """The ISSUE-14 byte-identity contract: a two-replica disaggregated
    fleet's streams exactly equal a monolithic single replica's (greedy
    + seeded, int8 paged KV, armed sanitizer), and the decode replica's
    admissions HIT the shipped prefix (recompute none of the shipped
    KV)."""
    bundle, params = parts

    async def scenario():
        prompts = [_conv(1), _conv(2, n=60), _conv(3, n=33)]
        mono = _make_group(bundle, params, n=1)
        expected = []
        for i, ids in enumerate(prompts):
            expected.append((await _collect(mono, ids))[0])
        seeded_exp = (await _collect(mono, prompts[0], seed=77,
                                     temperature=0.8))[0]
        await mono.wait_drained()
        mono.stop()

        disagg = _make_group(
            bundle, params, n=2, roles=["prefill", "decode"]
        )
        got = []
        for ids in prompts:
            got.append((await _collect(disagg, ids))[0])
        seeded_got = (await _collect(disagg, prompts[0], seed=77,
                                     temperature=0.8))[0]
        assert got == expected
        assert seeded_got == seeded_exp
        decode = disagg.replicas[1].engine
        prefill = disagg.replicas[0].engine
        ship = decode._kv_ship_snapshot()
        assert ship["role"] == "decode"
        assert ship["receives"] >= 3 and ship["hits"] >= 3
        assert ship["recomputes"] == 0 and ship["hit_rate"] == 1.0
        sent = prefill._kv_ship_snapshot()
        assert sent["ships"] >= 3 and sent["ship_pages"] > 0
        assert disagg._disagg_snapshot()["ship_leg_failures"] == 0
        # the decode replica never ran a cold prefill for shipped work:
        # its prefix-cache hits cover every shipped admission
        await disagg.wait_drained()
        return disagg

    group = asyncio.run(scenario())
    _drained_clean(group)
    group.stop()


def test_warm_turns_skip_the_ship_leg(parts):
    bundle, params = parts

    async def scenario():
        group = _make_group(
            bundle, params, n=2, roles=["prefill", "decode"]
        )
        ids = _conv(9)
        await _collect(group, ids)
        legs0 = group.ship_legs
        await _collect(group, ids)      # same conversation: decode is warm
        assert group.ship_warm_skips >= 1
        assert group.ship_legs == legs0
        await group.wait_drained()
        return group

    group = asyncio.run(scenario())
    _drained_clean(group)
    group.stop()


@pytest.mark.parametrize("kv_backend", BACKENDS)
def test_ship_fault_falls_back_to_decode_recompute(parts, kv_backend):
    """Chaos: an injected ``engine.kv.ship`` fault at the prefill commit
    drops the shipment leak-free; the stream completes byte-identically
    via decode-side recompute and the drop is counted. Runs identically
    over the in-process slab and the socket wire."""
    bundle, params = parts

    async def scenario():
        ids = _conv(11)
        mono = _make_group(bundle, params, n=1)
        expected = (await _collect(mono, ids))[0]
        await mono.wait_drained()
        mono.stop()

        group = _make_group(
            bundle, params, n=2, roles=["prefill", "decode"],
            kv_backend=kv_backend,
        )
        faults.configure([
            {"point": "engine.kv.ship", "action": "raise"},
        ])
        try:
            got, _ = await _collect(group, ids)
        finally:
            faults.clear()
        assert got == expected
        prefill = group.replicas[0].engine._kv_ship_snapshot()
        decode = group.replicas[1].engine._kv_ship_snapshot()
        assert prefill["ship_drops"] >= 1 and prefill["ships"] == 0
        assert decode["receives"] == 0
        assert decode["recomputes"] >= 1 and decode["hits"] == 0
        await group.wait_drained()
        return group

    group = asyncio.run(scenario())
    _drained_clean(group)
    group.stop()


@pytest.mark.parametrize("kv_backend", BACKENDS)
def test_receive_fault_reroutes_to_hybrid(parts, kv_backend):
    """Chaos: an injected ``engine.kv.receive`` fault on the decode
    replica re-routes the stream to a hybrid-capable sibling (recompute
    there), leak-free and byte-identical. Runs identically over the
    in-process slab and the socket wire."""
    bundle, params = parts

    async def scenario():
        ids = _conv(13)
        mono = _make_group(bundle, params, n=1)
        expected = (await _collect(mono, ids))[0]
        await mono.wait_drained()
        mono.stop()

        group = _make_group(
            bundle, params, n=3, roles=["prefill", "decode", "hybrid"],
            kv_backend=kv_backend,
        )
        # route the stream at a DECODE-role member so the receive runs
        # there (a hybrid pick would already be the fallback)
        decode_name = next(
            r.name for r in group.replicas
            if group.router.role_of(r.name) == "decode"
        )
        faults.configure([
            {"point": "engine.kv.receive", "action": "raise", "times": 1},
        ])
        try:
            request = GenRequest(prompt_ids=list(ids), max_new_tokens=5)
            request._replica_name = decode_name
            got = []
            async for token in group.generate(request):
                got.append(int(token))
        finally:
            faults.clear()
        assert got == expected
        assert group.receive_reroutes == 1
        # the stream ran on the hybrid member, not the faulted decode one
        assert group.router.role_of(request._replica_name) == "hybrid"
        decode = group._replica_by_name(decode_name).engine
        assert decode._kv_ship_snapshot()["receive_failures"] == 1
        await group.wait_drained()
        return group

    group = asyncio.run(scenario())
    _drained_clean(group)
    group.stop()


@pytest.mark.parametrize("kv_backend", BACKENDS)
def test_kill_prefill_replica_mid_ship_resumes_on_remaining(parts, kv_backend):
    """Chaos: the prefill replica dies mid-ship-leg — the stream still
    completes on the decode replica (hybrid degradation: it prefills for
    itself), zero page leaks; once the prefill replica is gone entirely,
    later requests skip the leg (pick_prefill returns None). Runs
    identically over the in-process slab and the socket wire; the
    process-backend variant (real SIGKILL of the worker) lives in
    tests/test_process_replica.py."""
    bundle, params = parts

    async def scenario():
        ids = _conv(17)
        mono = _make_group(bundle, params, n=1)
        expected = (await _collect(mono, ids))[0]
        await mono.wait_drained()
        mono.stop()

        group = _make_group(
            bundle, params, n=2, roles=["prefill", "decode"],
            kv_backend=kv_backend,
        )
        # leg 1: the prefill replica fails MID-ADMISSION (raise inside
        # its prefill worker); the leg is best-effort so the stream
        # completes via decode-side recompute
        faults.configure([
            {"point": "engine.prefill", "action": "raise", "times": 1},
        ])
        try:
            got, _ = await _collect(group, ids)
        finally:
            faults.clear()
        assert got == expected
        assert group.ship_leg_failures == 1
        # now KILL the prefill replica outright: later disaggregated
        # requests degrade to hybrid (no leg at all), streams unaffected
        group.replicas[0].engine.stop()
        group.router.sweep()
        legs0 = group.ship_legs
        got2, _ = await _collect(group, _conv(18))
        assert len(got2) == 5
        assert group.ship_legs == legs0     # no prefill-capable member
        await group.replicas[1].engine.wait_drained()
        return group

    group = asyncio.run(scenario())
    for replica in group.replicas[1:]:
        sanitizer = replica.engine._sanitizer
        assert sanitizer is not None and sanitizer.stats()["failures"] == 0
    group.stop()


# -- draft-ahead KV shipping (docs/spec_decode_trees.md) ----------------------


def test_draft_ahead_overlaps_ragged_prefill_over_socket(parts):
    """The draft-ahead certificate's clean path, over the REAL wire: a
    ragged prefill replica ships storable pages at chunk boundaries
    (unsealed partial frames overlapping the prefill tail) and seals at
    commit; the decode replica's admission hits the shipped prefix, the
    stream is byte-identical to a monolithic replica's, and the overlap
    gauge is live (> 0)."""
    bundle, params = parts
    ragged = dict(scheduler="ragged", step_token_budget=16)

    async def scenario():
        ids = _conv(21, n=60)       # spans several 16-token ragged chunks
        mono = _make_group(bundle, params, n=1, **ragged)
        expected = (await _collect(mono, ids))[0]
        await mono.wait_drained()
        mono.stop()

        group = _make_group(
            bundle, params, n=2, roles=["prefill", "decode"],
            kv_backend="socket", **ragged,
        )
        got = (await _collect(group, ids))[0]
        assert got == expected
        prefill = group.replicas[0].engine._kv_ship_snapshot()
        decode = group.replicas[1].engine._kv_ship_snapshot()
        # the prefix head rode unsealed frames ahead of the commit seal
        assert prefill["draft_ships"] >= 1
        assert prefill["draft_pages"] >= 1
        assert prefill["draft_aborts"] == 0
        assert prefill["overlap_ratio"] > 0
        assert prefill["ships"] >= 1
        assert prefill["ship_pages"] > prefill["draft_pages"]  # seal pages
        # transport saw the assembly seal exactly once per ship
        transport = decode["transport"]
        assert transport["partial_frames"] >= 1
        assert transport["assembled"] == prefill["ships"]
        assert transport["assembly_drops"] == 0
        # decode replica recomputed none of the shipped prefix
        assert decode["receives"] >= 1 and decode["hits"] >= 1
        assert decode["recomputes"] == 0 and decode["hit_rate"] == 1.0
        await group.wait_drained()
        return group

    group = asyncio.run(scenario())
    _drained_clean(group)
    group.stop()


@pytest.mark.chaos
def test_partial_ship_fault_drops_to_recompute(parts):
    """Chaos: an injected ``kv.ship.partial`` fault mid-draft-ahead
    aborts the job's whole partial stream AND the commit seal — the
    receiver's unsealed assembly is never consumable, the decode replica
    recomputes, the stream stays byte-identical, and nothing leaks on
    either side."""
    bundle, params = parts
    ragged = dict(scheduler="ragged", step_token_budget=16)

    async def scenario():
        ids = _conv(23, n=60)
        mono = _make_group(bundle, params, n=1, **ragged)
        expected = (await _collect(mono, ids))[0]
        await mono.wait_drained()
        mono.stop()

        group = _make_group(
            bundle, params, n=2, roles=["prefill", "decode"], **ragged,
        )
        faults.configure([
            {"point": "kv.ship.partial", "action": "raise", "times": 1},
        ])
        try:
            got, _ = await _collect(group, ids)
        finally:
            faults.clear()
        assert got == expected
        prefill = group.replicas[0].engine._kv_ship_snapshot()
        decode = group.replicas[1].engine._kv_ship_snapshot()
        assert prefill["draft_aborts"] >= 1
        assert prefill["ships"] == 0            # the seal was skipped
        assert prefill["ship_drops"] >= 1
        assert decode["receives"] == 0
        assert decode["recomputes"] >= 1 and decode["hits"] == 0
        await group.wait_drained()
        return group

    group = asyncio.run(scenario())
    _drained_clean(group)
    group.stop()
