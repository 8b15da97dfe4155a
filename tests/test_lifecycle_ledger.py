"""Runtime ownership ledger (llm/lifecycle_ledger.py): unit pairing
semantics, the engine integration (strict-armed clean runs stay leak-free;
lifecycle_stats()/health() carry the ledger block), and the chaos seam —
``engine.ledger.leak`` suppresses one real release firing and the strict
ledger must fail the drain audit naming the lost resource and its acquire
site. Node pins are invisible to page-refcount accounting, so this leak
class is provable by the ledger ALONE (the KV sanitizer stays green
through it)."""

import asyncio
import os
import time

import jax
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.llm import faults, lifecycle_ledger
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.llm.kv_cache import HostKVTier, PagePool
from clearml_serving_tpu.llm.lifecycle_ledger import (
    LedgerError,
    OwnershipLedger,
)
from clearml_serving_tpu.llm.prefix_cache import RadixPrefixCache


@pytest.fixture(scope="module")
def parts():
    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


@pytest.fixture(autouse=True)
def clean_state():
    faults.clear()
    lifecycle_ledger.get().reset(strict=False)
    yield
    faults.clear()
    lifecycle_ledger.get().reset(strict=False)
    lifecycle_ledger.disarm()


async def _collect(engine, req):
    out = []
    async for token in engine.generate(req):
        out.append(token)
    return out


# -- unit: pairing semantics --------------------------------------------------


def test_acquire_release_balances():
    ledger = OwnershipLedger(strict=True)
    ledger.acquire("pages.slot", key=0, n=3, domain="pool")
    assert ledger.outstanding()["pages.slot"] == 3
    ledger.release("pages.slot", key=0, n=3, domain="pool")
    assert ledger.outstanding()["pages.slot"] == 0
    ledger.check("drain", drained=True)  # no raise
    assert ledger.stats()["leaks"] == 0


def test_release_all_of_key():
    ledger = OwnershipLedger(strict=True)
    ledger.acquire("pages.slot", key=1, n=2, domain="pool")
    ledger.acquire("pages.slot", key=1, n=4, domain="pool")
    ledger.release("pages.slot", key=1, domain="pool", all_of_key=True)
    assert ledger.outstanding()["pages.slot"] == 0
    # a second all-of-key release of an empty slot is a legitimate
    # defensive free, never a violation
    ledger.release("pages.slot", key=1, domain="pool", all_of_key=True)
    assert ledger.stats()["double_releases"] == 0


def test_double_release_is_a_violation():
    ledger = OwnershipLedger(strict=True)
    ledger.acquire("pages.pin", key=(1, 2), n=2, domain="pool")
    ledger.release("pages.pin", key=(1, 2), n=2, domain="pool")
    ledger.release("pages.pin", key=(1, 2), n=2, domain="pool")
    assert ledger.stats()["double_releases"] == 1
    with pytest.raises(LedgerError, match="double free"):
        ledger.check("step")


def test_drain_audit_names_resource_and_site():
    ledger = OwnershipLedger(strict=True)
    ledger.acquire("prefix.resume_pin", key=7, domain="cache")
    with pytest.raises(LedgerError) as info:
        ledger.check("drain", drained=True)
    assert info.value.resource == "prefix.resume_pin"
    assert info.value.site  # file:line of the acquiring caller
    assert "still outstanding at the drained boundary" in str(info.value)


def test_drain_audit_respects_domains():
    """Co-hosted engines audit only their own primitives: a foreign
    domain's outstanding entry never fails this engine's drain."""
    ledger = OwnershipLedger(strict=True)
    ledger.acquire("pages.slot", key=0, n=1, domain="other-engine-pool")
    ledger.check("drain", drained=True, domains=["my-pool"])  # no raise
    with pytest.raises(LedgerError):
        ledger.check("drain", drained=True,
                     domains=["other-engine-pool", "my-pool"])


def test_cache_scoped_resources_exempt_from_drain_zero():
    ledger = OwnershipLedger(strict=True)
    ledger.acquire("pages.ref", n=4, domain="pool")
    ledger.acquire("host.pages", n=2, domain="tier")
    ledger.acquire("transport.shipment", key=b"k", domain="transport")
    ledger.check("drain", drained=True)  # cache-lifetime holds are legal
    assert ledger.outstanding()["pages.ref"] == 4


def test_request_audit_owner_attribution():
    ledger = OwnershipLedger(strict=True)
    with ledger.owner("req:a"):
        ledger.acquire("prefix.hit", key=1, domain="cache")
    with ledger.owner("req:b"):
        ledger.acquire("prefix.hit", key=2, domain="cache")
    ledger.release("prefix.hit", key=2, domain="cache")
    ledger.audit_request("req:b", "emit-finish")  # b released: clean
    with pytest.raises(LedgerError, match="req:a"):
        ledger.audit_request("req:a", "emit-finish")


def test_shared_key_release_discharges_the_releasers_slab():
    """Two requests sharing one resource key (the same grammar, the same
    pinned page run): a release attributed to request A must discharge
    A's slab, not whichever was newest — or the survivor's request-exit
    audit reports a phantom leak on healthy code."""
    ledger = OwnershipLedger(strict=True)
    with ledger.owner("req:a"):
        ledger.acquire("guided.ref", key="g", domain="eng")
    with ledger.owner("req:b"):
        ledger.acquire("guided.ref", key="g", domain="eng")
    # A finishes first; without owner preference this would pop B's slab
    ledger.release("guided.ref", key="g", domain="eng", owner="req:a")
    ledger.audit_request("req:a", "emit-finish")  # clean
    ledger.release("guided.ref", key="g", domain="eng", owner="req:b")
    ledger.audit_request("req:b", "emit-finish")  # clean
    assert ledger.outstanding()["guided.ref"] == 0
    # the thread-local owner context works as the implicit preference too
    with ledger.owner("req:c"):
        ledger.acquire("pages.pin", key=(1, 2), n=2, domain="pool")
    with ledger.owner("req:d"):
        ledger.acquire("pages.pin", key=(1, 2), n=2, domain="pool")
    with ledger.owner("req:c"):
        ledger.release("pages.pin", key=(1, 2), n=2, domain="pool")
    ledger.audit_request("req:c", "emit-finish")  # clean
    with pytest.raises(LedgerError, match="req:d"):
        ledger.audit_request("req:d", "emit-finish")


def test_leak_counted_once_across_repeated_audits():
    """A leaked entry survives in the books, but the leaks counter counts
    lost frees, not the drains that observed them — and the violations
    list must not grow per drained boundary on a long-lived server."""
    ledger = OwnershipLedger(strict=False)
    with ledger.owner("req:x"):
        ledger.acquire("prefix.resume_pin", key=1, domain="cache")
    for _ in range(5):
        ledger.check("drain", drained=True)
    assert ledger.stats()["leaks"] == 1
    assert ledger.stats()["violations"] == 1
    # the request-exit audit does not re-count what the drain reported
    ledger.audit_request("req:x", "fail")
    assert ledger.stats()["leaks"] == 1


def test_count_mode_records_without_raising():
    ledger = OwnershipLedger(strict=False)
    ledger.acquire("pages.pin", key=(3,), domain="pool")
    ledger.audit_request("req:x", "fail")  # no owner match: clean
    with ledger.owner("req:y"):
        ledger.acquire("pages.pin", key=(4,), domain="pool")
    ledger.audit_request("req:y", "fail")
    ledger.check("drain", drained=True)
    stats = ledger.stats()
    assert stats["leaks"] >= 2 and stats["violations"] >= 2


def test_unknown_resource_rejected():
    ledger = OwnershipLedger()
    with pytest.raises(ValueError, match="unknown ledger resource"):
        ledger.acquire("nope", key=1)
    with pytest.raises(ValueError, match="unknown ledger resource"):
        ledger.release("nope", key=1)


def test_env_arming(monkeypatch):
    monkeypatch.delenv(lifecycle_ledger.ENV, raising=False)
    assert not lifecycle_ledger.enabled()
    monkeypatch.setenv(lifecycle_ledger.ENV, "1")
    assert lifecycle_ledger.enabled() and not lifecycle_ledger.strict_enabled()
    monkeypatch.setenv(lifecycle_ledger.ENV, "strict")
    assert lifecycle_ledger.enabled() and lifecycle_ledger.strict_enabled()


def test_module_helpers_noop_when_disarmed():
    lifecycle_ledger.disarm()
    before = lifecycle_ledger.get().stats()["acquires"]
    lifecycle_ledger.acquire("pages.slot", key=0, n=5, domain="p")
    lifecycle_ledger.release("pages.slot", key=0, n=5, domain="p")
    assert lifecycle_ledger.get().stats()["acquires"] == before


# -- primitives record through the module seam --------------------------------


def test_pool_and_cache_record_when_armed():
    ledger = lifecycle_ledger.arm(strict=True)
    pool = PagePool(9, 4, 2)
    cache = RadixPrefixCache(block=4, pool=pool, page_bytes=8)
    ids = list(range(9))   # 9 tokens -> 8 storable (2 blocks = 2 pages)
    pool.allocate(0, 9)
    assert ledger.outstanding()["pages.slot"] == 3
    cache.store_pages(ids, 0, pool.slot_pages(0))
    assert ledger.outstanding()["pages.ref"] == 2
    hit = cache.lookup_pages(ids)
    assert ledger.outstanding()["prefix.hit"] == 1
    assert ledger.outstanding()["pages.pin"] == 2
    cache.release(hit)
    pool.free(0)
    assert ledger.outstanding()["prefix.hit"] == 0
    assert ledger.outstanding()["pages.pin"] == 0
    assert ledger.outstanding()["pages.slot"] == 0
    ledger.check("drain", drained=True, domains=[pool, cache])


def test_host_tier_records_when_armed():
    import numpy as np

    ledger = lifecycle_ledger.arm(strict=True)
    tier = HostKVTier(4, 4, 1, 1, 2, dtype=np.int8, quantized=False)
    ids = tier.allocate(3)
    assert ledger.outstanding()["host.pages"] == 3
    tier.free(ids)
    assert ledger.outstanding()["host.pages"] == 0


def test_resources_cover_ledger_only_registry_entries():
    """Every "static": False protocol the analyzer defers to the ledger is
    a resource the ledger actually tracks (the fail-open contract)."""
    from clearml_serving_tpu.analyze.rules_lifecycle import (
        LIFECYCLE_REGISTRY,
    )

    deferred = {
        e["resource"]
        for entries in LIFECYCLE_REGISTRY.values()
        for e in entries
        if not e.get("static", True)
    }
    assert deferred <= set(lifecycle_ledger.RESOURCES)
    for resource in deferred:
        assert resource in lifecycle_ledger.RESOURCES


# -- engine integration -------------------------------------------------------


def _make_engine(bundle, params, **kwargs):
    kwargs.setdefault("max_batch", 2)
    kwargs.setdefault("max_seq_len", 128)
    kwargs.setdefault("prefill_buckets", [16, 32])
    kwargs.setdefault("eos_token_id", 257)
    return LLMEngineCore(bundle, params, **kwargs)


@pytest.mark.parametrize("traffic", ["plain", "preempted"])
def test_engine_clean_run_is_leak_free_strict(parts, monkeypatch, traffic):
    """A strict-armed paged engine serves and drains with zero leaks, and
    lifecycle_stats()/health() expose the ledger block. ``preempted``: a
    batch request loses its only slot to an interactive one and resumes
    from its pinned history: the preempt -> pin -> resume -> unpin round
    trip pairs every acquire too."""
    bundle, params = parts
    monkeypatch.setenv("TPUSERVE_LEDGER", "strict")
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")

    async def plain(engine):
        for seed in (1, 2, 1):
            out = await _collect(
                engine,
                GenRequest(prompt_ids=[256, seed] + list(range(2, 18)),
                           max_new_tokens=4),
            )
            assert out

    async def preempted(engine):
        batch = GenRequest(
            prompt_ids=[256] + [(i * 3 + 1) % 250 for i in range(16)],
            max_new_tokens=24, priority="batch",
        )
        b_task = asyncio.create_task(_collect(engine, batch))
        while batch.produced < 4:
            await asyncio.sleep(0.005)
        hi = GenRequest(prompt_ids=[256, 9], max_new_tokens=2)
        assert await asyncio.wait_for(_collect(engine, hi), timeout=60)
        assert len(await asyncio.wait_for(b_task, timeout=60)) == 24
        assert engine.counters["preemptions"] >= 1, "no preemption happened"

    async def run():
        kw = dict(cache_mode="paged", page_size=16, prefix_cache=64,
                  prefix_block=16)
        if traffic == "preempted":
            kw.update(max_batch=1, decode_steps=2, prefill_buckets=[32, 64],
                      eos_token_id=None)
        engine = _make_engine(bundle, params, **kw)
        assert engine._ledger is not None, "TPUSERVE_LEDGER did not arm"
        engine._ledger.reset(strict=True)
        await (preempted if traffic == "preempted" else plain)(engine)
        await engine.wait_drained()
        return engine

    engine = asyncio.run(run())
    block = engine.lifecycle_stats()["ledger"]
    assert block["strict"] is True
    assert block["leaks"] == 0 and block["double_releases"] == 0
    assert block["acquires"] > 0
    for resource in ("pages.slot", "pages.pin", "prefix.hit",
                     "prefix.resume_pin", "slot.quarantine", "guided.ref"):
        assert block["outstanding"][resource] == 0, (resource, block)
    assert engine.health()["ledger"]["leaks"] == 0
    assert engine._sanitizer.stats()["failures"] == 0
    engine.stop()


def test_engine_without_env_has_no_ledger(parts, monkeypatch):
    bundle, params = parts
    monkeypatch.delenv("TPUSERVE_LEDGER", raising=False)
    engine = _make_engine(bundle, params)
    assert engine._ledger is None
    assert engine.lifecycle_stats()["ledger"] is None
    engine.stop()


@pytest.mark.chaos
def test_ledger_leak_seam_caught_at_drain_strict(parts, monkeypatch):
    """Acceptance (end to end): the ``engine.ledger.leak`` seam suppresses
    ONE resume-pin release on the preemption resume path — a lost free on
    radix NODES, invisible to page accounting (the KV sanitizer stays
    green) — and the strict ledger fails the drain audit naming
    ``prefix.resume_pin`` and the pin_run acquire site in engine.py."""
    bundle, params = parts
    monkeypatch.setenv("TPUSERVE_LEDGER", "strict")
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")

    async def run():
        engine = _make_engine(
            bundle, params, max_batch=1, decode_steps=2, cache_mode="paged",
            page_size=16, prefix_cache=64, prefix_block=16,
            prefill_buckets=[32, 64], eos_token_id=None,
        )
        assert engine._ledger is not None
        engine._ledger.reset(strict=True)
        batch = GenRequest(
            prompt_ids=[256] + [(i * 3 + 1) % 250 for i in range(16)],
            max_new_tokens=24, priority="batch",
        )
        b_task = asyncio.create_task(_collect(engine, batch))
        while batch.produced < 4:
            await asyncio.sleep(0.005)
        # the preemption pins the victim's stored history; the seam then
        # eats the unpin when the resume leg's admission releases it
        faults.configure([
            {"point": "engine.ledger.leak", "times": 1,
             "message": "lost unpin"},
        ])
        out_hi = await asyncio.wait_for(
            _collect(engine, GenRequest(prompt_ids=[256, 9],
                                        max_new_tokens=2)),
            timeout=60,
        )
        assert len(out_hi) >= 1
        out_b = await asyncio.wait_for(b_task, timeout=60)
        assert len(out_b) == 24
        t0 = time.monotonic()
        while not engine._loop_task.done() and time.monotonic() - t0 < 15.0:
            await asyncio.sleep(0.01)
        assert engine._loop_task.done(), "loop should fail at the drain audit"
        return engine, engine._loop_task.exception()

    engine, exc = asyncio.run(run())
    assert engine.counters["preemptions"] >= 1, "no preemption: seam unhit"
    assert isinstance(exc, LedgerError), exc
    assert exc.resource == "prefix.resume_pin"
    assert "engine.py" in exc.site, exc.site  # the pin_run acquire site
    # the page books balanced throughout: only the LEDGER sees this class
    assert engine._sanitizer is not None
    assert engine._sanitizer.stats()["failures"] == 0
    engine.stop()


def test_ragged_job_failure_arm_reclaim_is_load_bearing(parts, monkeypatch):
    """Runtime mutation gate for this PR's _start_ragged_job fix (its
    static TPU701 finding is annotation-covered, so the LEDGER carries the
    regression): with the failure arm's slot reclaim disabled (the pre-fix
    behavior), a raise AFTER the prefix hit's map_shared strands the
    mapped pages on a slot no job owns, and the strict ledger's drain
    audit must fail naming pages.slot — sanitizer OFF on purpose: the
    ledger alone suffices, and names the resource, not just page ids.
    (The fixed path's cleanliness is covered by
    test_engine_clean_run_is_leak_free_strict and the ragged chaos
    suite.)"""
    bundle, params = parts
    monkeypatch.setenv("TPUSERVE_LEDGER", "strict")

    def build():
        monkeypatch.setenv("TPUSERVE_SANITIZE", "0")
        engine = _make_engine(
            bundle, params, cache_mode="paged", page_size=16,
            prefix_cache=64, prefix_block=16, scheduler="ragged",
            eos_token_id=None,
        )
        assert engine._ledger is not None
        engine._ledger.reset(strict=True)
        return engine

    async def run(engine, break_reclaim):
        shared = [256] + list(range(1, 32))
        # request A stores the shared prefix at commit
        out = await _collect(
            engine, GenRequest(prompt_ids=shared + [40], max_new_tokens=2)
        )
        assert out
        await engine.wait_drained()
        if break_reclaim:
            # the pre-fix behavior: the failure arm loses the mapped pages
            monkeypatch.setattr(
                engine, "_free_ragged_slot", lambda slot: None
            )
        # request B hits the prefix; release() dies once AFTER map_shared
        real_release = engine._prefix.release
        state = {"armed": True}

        def exploding_release(hit):
            # the pin drops normally; the failure lands AFTER it — the
            # modeled defect is strictly "the try body raised after
            # map_shared", leaving only the slot's mapped pages at risk
            result = real_release(hit)
            if state["armed"]:
                state["armed"] = False
                raise RuntimeError("post-map_shared failure")
            return result

        monkeypatch.setattr(engine._prefix, "release", exploding_release)
        with pytest.raises(RuntimeError, match="post-map_shared failure"):
            await _collect(
                engine,
                GenRequest(prompt_ids=shared + [41], max_new_tokens=2),
            )
        monkeypatch.setattr(engine._prefix, "release", real_release)
        # the loop reaches its drained boundary (B was the only request):
        # the drain audit runs there and decides the loop task's fate
        t0 = time.monotonic()
        while not engine._loop_task.done() and time.monotonic() - t0 < 15.0:
            await asyncio.sleep(0.01)
        assert engine._loop_task.done()
        return engine._loop_task

    engine = build()
    task = asyncio.run(run(engine, break_reclaim=True))
    exc = task.exception()
    assert isinstance(exc, LedgerError), exc
    assert exc.resource == "pages.slot"
    engine.stop()


# the ledger_pairing scenario's seeded defects (drop_release_on_raise,
# double_free) are proven caught by tests/test_schedule_explorer.py's
# parametrized mutation self-test — the --self-test acceptance for this
# PR's defect classes lives there with the other eight.


def test_explorer_scenario_restores_ledger_mode():
    """The ledger_pairing scenario arms the process-wide ledger strict for
    its own run; a co-armed count-mode harness must get count mode BACK
    (a leaked strict=True would turn later checks into raises)."""
    from clearml_serving_tpu.llm.schedule_explorer import explore

    lifecycle_ledger.arm(strict=False)
    explore("ledger_pairing", schedules=2, seed=0)
    assert lifecycle_ledger.armed()
    assert lifecycle_ledger.get().strict is False
