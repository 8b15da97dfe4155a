"""Fault-injection chaos suite (llm/faults.py seam; docs/robustness.md).

Proves the request-lifecycle hardening contracts end to end on CPU:

- a poisoned decode step fails ONLY the affected request; concurrently
  active requests complete and the engine keeps serving without a restart;
- the watchdog detects a stuck decode loop, fails the stalled batch with a
  structured error, flips not-ready, and recovers;
- admission sheds (queue bound / KV-pool saturation) raise structured 429s;
- queue-wait / TTFT / total deadlines fail requests with structured 408s;
- the gRPC client retries transient upstream codes with backoff and maps
  exhaustion to 503/504 instead of raw tracebacks.

All tests are fast and deterministic (faults fire on exact match/points, no
sleeps racing compiles beyond an explicit warmup) — they run inside tier-1
(`scripts/tier1.sh`); select just this suite with `pytest -m chaos`.
"""

import asyncio
import threading
import time
import types

import jax
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.errors import (
    DeadlineExceededError,
    EngineOverloadedError,
    EngineStepError,
    EngineStuckError,
    EngineUnavailableError,
    UpstreamTimeoutError,
    UpstreamUnavailableError,
)
from clearml_serving_tpu.llm import engine as engine_mod
from clearml_serving_tpu.llm import faults
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.llm.kv_sanitizer import KVSanitizerError

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module")
def parts():
    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(autouse=True)
def armed_sanitizer(monkeypatch):
    """Every engine this suite builds runs with the KV sanitizer armed:
    recovery paths must not merely produce the right tokens — page
    accounting must balance after every step and at drain
    (docs/static_analysis.md, invariant list)."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")


@pytest.fixture(autouse=True)
def armed_compile_sentry(monkeypatch):
    """The compile sentry rides along non-strict (like the KV sanitizer):
    chaos engines exercise recovery paths with the compile hook live, so
    the seam itself is proven inert under faults. No fence is ever set
    here, so every compile counts as warmup and nothing can raise."""
    monkeypatch.setenv("TPUSERVE_COMPILE_SENTRY", "1")
    yield
    from clearml_serving_tpu.llm import compile_sentry

    if compile_sentry._sentry is not None:
        compile_sentry._sentry.reset(strict=False)


@pytest.fixture(autouse=True)
def armed_ledger(monkeypatch):
    """The ownership ledger rides along in count mode (docs/
    static_analysis.md TPU7xx): every chaos engine records acquire/release
    pairing through its recovery paths, proving the bookkeeping itself is
    inert under faults. Count mode, not strict — several tests here leak
    DELIBERATELY (that is what they test), and their own assertions own
    the failure; the strict end-to-end case lives in
    tests/test_lifecycle_ledger.py."""
    monkeypatch.setenv("TPUSERVE_LEDGER", "1")
    from clearml_serving_tpu.llm import lifecycle_ledger

    lifecycle_ledger.arm(strict=False).reset(strict=False)
    yield
    lifecycle_ledger.get().reset(strict=False)
    lifecycle_ledger.disarm()


@pytest.fixture(autouse=True)
def armed_shard_sentry(monkeypatch):
    """The sharding sentry rides along in count mode (docs/
    static_analysis.md TPU8xx): every chaos engine audits its live arrays
    against the declared builder specs through the recovery paths, proving
    failure handling never silently host-materializes or reshards the
    chained state. Count mode, not strict — fault recovery is allowed to
    fail requests, not to drift layouts; each test's teardown asserts the
    audit stayed clean."""
    monkeypatch.setenv("TPUSERVE_SHARD_SENTRY", "1")
    from clearml_serving_tpu.llm import sharding_sentry

    sharding_sentry.arm(strict=False).reset(strict=False)
    yield
    stats = sharding_sentry.get().stats()
    sharding_sentry.get().reset(strict=False)
    sharding_sentry.disarm()
    assert stats["implicit_transfers"] == 0, stats["events"][:5]
    assert stats["unplanned_reshards"] == 0, stats["events"][:5]


def _make_engine(bundle, params, **kwargs):
    kwargs.setdefault("max_batch", 4)
    kwargs.setdefault("max_seq_len", 128)
    kwargs.setdefault("prefill_buckets", [16, 32])
    kwargs.setdefault("eos_token_id", 257)
    return LLMEngineCore(bundle, params, **kwargs)


async def _collect(engine, req):
    out = []
    async for token in engine.generate(req):
        out.append(token)
    return out


# -- decode-step poison: failure isolation ------------------------------------


def test_poisoned_decode_fails_only_that_request(parts):
    """Acceptance: with fault injection poisoning one request's decode step,
    that request fails with a structured error while a concurrently active
    request completes and the engine serves new requests — no restart."""
    bundle, params = parts
    marker = 300  # token only the poisoned request's prompt contains

    async def run():
        engine = _make_engine(bundle, params, decode_steps=1)
        # warm up (compile the decode chunk) before arming the fault
        await _collect(engine, GenRequest(prompt_ids=[256, 1], max_new_tokens=2))

        a = GenRequest(prompt_ids=[256, 5, 6], max_new_tokens=12)
        a_task = asyncio.create_task(_collect(engine, a))
        # wait until A is decoding so the poison has a live co-resident
        while a.produced < 2:
            await asyncio.sleep(0.01)
        faults.configure([
            {"point": "engine.decode", "action": "raise",
             "match_token": marker, "times": 1, "message": "poisoned step"},
        ])
        b = GenRequest(prompt_ids=[256, marker, 7], max_new_tokens=12)
        with pytest.raises(EngineStepError):
            await _collect(engine, b)
        # the co-resident completes in full
        out_a = await a_task
        assert len(out_a) == 12 or 257 in out_a
        # and the engine keeps serving new work without a process restart
        out_c = await _collect(
            engine, GenRequest(prompt_ids=[256, 9], max_new_tokens=4)
        )
        assert len(out_c) >= 1
        return engine

    engine = asyncio.run(run())
    assert engine.counters["step_failures"] == 1
    assert engine.active_slots == 0


def test_batch_wide_decode_failure_recovers_engine(parts):
    """An unattributable dispatch exception fails the in-flight batch with
    structured errors but the loop survives: new requests are served by the
    same engine instance."""
    bundle, params = parts

    async def run():
        engine = _make_engine(bundle, params, decode_steps=1)
        await _collect(engine, GenRequest(prompt_ids=[256, 1], max_new_tokens=2))
        faults.configure([
            {"point": "engine.decode", "action": "raise", "times": 1,
             "message": "device exploded"},
        ])
        with pytest.raises(EngineStepError):
            await _collect(
                engine, GenRequest(prompt_ids=[256, 2], max_new_tokens=8)
            )
        out = await _collect(
            engine, GenRequest(prompt_ids=[256, 3], max_new_tokens=4)
        )
        assert len(out) >= 1
        return engine

    engine = asyncio.run(run())
    assert engine.counters["step_failures"] == 1


def test_paged_poison_recovery_conserves_pages(parts):
    """Paged-cache variant of poison isolation, audited: the sanitizer
    checks refcount conservation after every decode step INCLUDING the
    recovery epoch, and at drain every page is back on the free list."""
    bundle, params = parts
    marker = 310

    async def run():
        engine = _make_engine(
            bundle, params, decode_steps=1, cache_mode="paged", page_size=16
        )
        assert engine._sanitizer is not None, "TPUSERVE_SANITIZE did not arm"
        await _collect(engine, GenRequest(prompt_ids=[256, 1], max_new_tokens=2))
        a = GenRequest(prompt_ids=[256, 5, 6], max_new_tokens=10)
        a_task = asyncio.create_task(_collect(engine, a))
        while a.produced < 2:
            await asyncio.sleep(0.01)
        faults.configure([
            {"point": "engine.decode", "action": "raise",
             "match_token": marker, "times": 1, "message": "poisoned step"},
        ])
        b = GenRequest(prompt_ids=[256, marker, 7], max_new_tokens=10)
        with pytest.raises(EngineStepError):
            await _collect(engine, b)
        out_a = await a_task
        assert len(out_a) >= 1
        # wait for drain so the drain-audit (strictest check) also ran
        t0 = time.monotonic()
        while (
            engine._loop_task is not None
            and not engine._loop_task.done()
            and time.monotonic() - t0 < 10.0
        ):
            await asyncio.sleep(0.01)
        if engine._loop_task is not None and engine._loop_task.done():
            assert engine._loop_task.exception() is None
        return engine

    engine = asyncio.run(run())
    stats = engine._sanitizer.stats()
    assert stats["checks"] > 0 and stats["failures"] == 0
    pool = engine.paged_cache.pool
    # no prefix cache configured: at drain every usable page is free again
    assert pool.free_pages == pool.num_pages - 1


def test_paged_poison_recovery_conserves_pages_int8(parts):
    """int8 paged KV (docs/paged_kv_quant.md) under chaos: poison recovery
    with int8 pools + radix shared-prefix reuse + copy-on-write, audited by
    the armed sanitizer (scale rows share the page lifecycle, so a clean
    page balance proves the scale pools balanced too)."""
    bundle, _ = parts
    qbundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32",
                  "kv_quant": "int8"}
    )
    params = parts[1]
    marker = 311
    shared = [256] + [(i * 3 + 1) % 250 for i in range(31)]

    async def run():
        engine = _make_engine(
            qbundle, params, decode_steps=1, cache_mode="paged",
            page_size=16, prefill_buckets=[32, 64],
            prefix_cache=8, prefix_block=16,
            # eos disabled: the pin-induced CoW below needs request A still
            # decoding when the pin lands (a sampled 257 would race it)
            eos_token_id=None,
        )
        assert engine._sanitizer is not None, "TPUSERVE_SANITIZE did not arm"
        assert engine.paged_cache.pool_dtype == "int8"
        # cold admission stores the shared prefix; the next two map it by
        # reference and their first decode write CoWs the shared tail page
        await _collect(
            engine, GenRequest(prompt_ids=shared, max_new_tokens=2)
        )
        a = GenRequest(prompt_ids=shared + [5], max_new_tokens=10)
        a_task = asyncio.create_task(_collect(engine, a))
        while a.produced < 2:
            await asyncio.sleep(0.01)
        # force a copy-on-write under live int8 decode: pin A's tail page
        # (an ACCOUNTED transient ref, like an in-flight admission holds) so
        # the next mid-page extend must give the slot a private copy — data
        # plane AND scale rows (kv_cache.apply_pending_cow)
        pool = engine.paged_cache.pool
        a_slot = next(
            (s for s, r in enumerate(engine._slot_req) if r is a), None
        )
        assert a_slot is not None, "request A left its slot before the pin"
        pinned = [pool.slot_pages(a_slot)[-1]]
        pool.pin_pages(pinned)
        while pool.cow_events < 1 and a.produced < 8:
            await asyncio.sleep(0.01)
        pool.unpin_pages(pinned)
        faults.configure([
            {"point": "engine.decode", "action": "raise",
             "match_token": marker, "times": 1, "message": "poisoned step"},
        ])
        b = GenRequest(prompt_ids=shared + [marker], max_new_tokens=10)
        with pytest.raises(EngineStepError):
            await _collect(engine, b)
        out_a = await a_task
        assert len(out_a) >= 1
        t0 = time.monotonic()
        while (
            engine._loop_task is not None
            and not engine._loop_task.done()
            and time.monotonic() - t0 < 10.0
        ):
            await asyncio.sleep(0.01)
        if engine._loop_task is not None and engine._loop_task.done():
            assert engine._loop_task.exception() is None
        return engine

    engine = asyncio.run(run())
    stats = engine._sanitizer.stats()
    assert stats["checks"] > 0 and stats["failures"] == 0
    assert engine._prefix.hits >= 1          # shared-prefix reuse happened
    assert engine.paged_cache.pool.cow_events >= 1  # CoW exercised
    pool = engine.paged_cache.pool
    # at drain: only the radix cache may keep pages; every page it holds is
    # accounted (the sanitizer's drain audit proved conservation already)
    assert pool.free_pages == (
        pool.num_pages - 1 - engine._prefix.cached_pages
    )
    engine.stop()


def test_deliberate_leak_is_caught_with_named_pages(parts):
    """Acceptance: a seeded teardown bug (engine.release fault swallows the
    page free) must fail CLOSED — the sanitizer's drain audit raises
    KVSanitizerError naming the leaked pages, instead of the pool quietly
    shrinking forever."""
    bundle, params = parts

    async def run():
        engine = _make_engine(
            bundle, params, decode_steps=1, cache_mode="paged", page_size=16
        )
        assert engine._sanitizer is not None
        # clean warmup request: proves the audit passes when teardown works
        await _collect(engine, GenRequest(prompt_ids=[256, 1], max_new_tokens=2))
        faults.configure([
            {"point": "engine.release", "times": 1, "message": "lost free"},
        ])
        out = await _collect(
            engine, GenRequest(prompt_ids=[256, 2, 3], max_new_tokens=3)
        )
        assert out, "the request itself succeeds; the leak is in teardown"
        t0 = time.monotonic()
        while not engine._loop_task.done() and time.monotonic() - t0 < 10.0:
            await asyncio.sleep(0.01)
        assert engine._loop_task.done(), "loop should exit at drain"
        return engine, engine._loop_task.exception()

    engine, exc = asyncio.run(run())
    assert isinstance(exc, KVSanitizerError), exc
    assert exc.where == "drain"
    assert exc.pages, "diagnostic must name the leaked page ids"
    assert "leaked pages at drain" in str(exc)
    assert all(str(p) in str(exc) for p in exc.pages)
    assert engine._sanitizer.stats()["failures"] == 1


# -- watchdog: stuck loop detection + supervised recovery ---------------------


def test_watchdog_trips_on_stalled_decode_and_recovers(parts):
    """A wedged decode dispatch (worker-thread stall) trips the watchdog:
    the stalled request fails with EngineStuckError, the engine reports
    not-ready while recovering, then flips back to ready and serves new
    requests — all inside one process."""
    bundle, params = parts

    async def run():
        engine = _make_engine(
            bundle, params, decode_steps=1, watchdog_interval=0.3
        )
        await _collect(engine, GenRequest(prompt_ids=[256, 1], max_new_tokens=2))
        assert engine.is_ready
        # quiesce the pipelined loop before arming the one-shot stall: a
        # leftover in-flight chunk's retire would burn the firing while the
        # engine is idle (no active slots -> no watchdog trip)
        await engine.wait_drained()
        faults.configure([
            {"point": "engine.decode.stall", "action": "delay",
             "delay": 1.2, "times": 1},
        ])
        req = GenRequest(prompt_ids=[256, 4, 5], max_new_tokens=50)
        task = asyncio.create_task(_collect(engine, req))
        saw_not_ready = False
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0:
            await asyncio.sleep(0.01)
            if not engine.is_ready:
                saw_not_ready = True
            if task.done():
                break
        with pytest.raises(EngineStuckError):
            await task
        assert saw_not_ready, "/ready never observed the recovery window"
        assert engine.counters["watchdog_trips"] >= 1
        # the stalled dispatch drains and the engine flips back to ready
        t0 = time.monotonic()
        while not engine.is_ready and time.monotonic() - t0 < 10.0:
            await asyncio.sleep(0.01)
        assert engine.is_ready
        out = await _collect(
            engine, GenRequest(prompt_ids=[256, 8], max_new_tokens=3)
        )
        assert len(out) >= 1
        return engine

    engine = asyncio.run(run())
    assert engine.health()["ready"]


# -- watchdog: a ragged launch that never comes back ---------------------------

_STATE_CFG = dict(vocab_size=300, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                  head_dim=16, ffn_dim=96, scan_layers=True, dtype="float32",
                  attention="power_retention", retention_degree=2, qk_norm=True,
                  norm_eps=1e-6, rope_theta=1e6, max_seq_len=512)
_RAGGED_KINDS = {
    "paged": (None, dict(cache_mode="paged", page_size=8, num_pages=64)),
    "state": (_STATE_CFG, dict(cache_mode="state")),
}


@pytest.mark.parametrize("cache", list(_RAGGED_KINDS))
def test_watchdog_trips_on_a_stalled_ragged_wait_and_recovers(
        parts, cache, monkeypatch):
    """The ragged step waits for its launch in a worker thread: a launch
    that does not come back (``engine.decode.stall`` in that worker) leaves
    the event loop free, so the watchdog trips, ``health()`` answers during
    the stall, the decoding victim fails with EngineStuckError, and when the
    worker returns ``_ragged_recover`` takes the surviving admission's chunk
    back (pages truncated / state rewound), which then finishes with the
    tokens of an undisturbed run; the engine serves on. No wall clock is
    judged: the engine's ``time.monotonic`` stands still until the test
    moves it, the stall is armed from inside the step that carries a decode
    row beside a prompt chunk, and it lasts until the test has seen the
    trip."""
    cfg, kw = _RAGGED_KINDS[cache]
    if cfg is None:
        bundle, params = parts
    else:
        bundle = models.build_model("llama", cfg)
        params = bundle.init(jax.random.PRNGKey(0))
    kw = dict(kw, max_batch=2, eos_token_id=None, scheduler="ragged",
              step_token_budget=16, decode_steps=2, watchdog_interval=1.0)
    victim = [256, 4, 5]
    survivor = [256] + [(5 * j) % 250 + 1 for j in range(40)]   # three chunks

    async def undisturbed():
        engine = _make_engine(bundle, params, **kw)
        out = await _collect(
            engine, GenRequest(prompt_ids=list(survivor), max_new_tokens=6))
        await engine.wait_drained()
        engine.stop()
        return out

    want = asyncio.run(undisturbed())

    now = [1000.0]
    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
        monotonic=lambda: now[0], perf_counter=time.perf_counter,
        time=time.time))
    stalled, release, survivors = threading.Event(), threading.Event(), []

    def stall(seconds):
        stalled.set()
        release.wait(seconds)       # the bound is a net, not a measurement

    monkeypatch.setattr(faults, "time", types.SimpleNamespace(sleep=stall))

    async def run():
        engine = _make_engine(bundle, params, **kw)
        prepare = engine._prepare_ragged

        def arm(mask, epoch):
            plan = prepare(mask, epoch)
            if (plan is not None and plan["decode_mask"].any()
                    and plan["shares"] and not faults.active()):
                faults.configure([
                    {"point": "engine.decode.stall", "action": "delay",
                     "delay": 120.0, "times": 1},
                ])
            return plan

        engine._prepare_ragged = arm
        recover = engine._ragged_recover

        async def recovered(plan):
            survivors.extend(job.request for job, _ in plan["shares"])
            await recover(plan)

        engine._ragged_recover = recovered
        a = GenRequest(prompt_ids=list(victim), max_new_tokens=100)
        a_task = asyncio.create_task(_collect(engine, a))
        while a.produced < 2:
            await asyncio.sleep(0.005)
        b = GenRequest(prompt_ids=list(survivor), max_new_tokens=6)
        b_task = asyncio.create_task(_collect(engine, b))
        while not stalled.is_set():
            await asyncio.sleep(0.005)      # the loop is free while it waits
        assert engine.health()["ready"] and not a_task.done()
        now[0] += 10.0                      # ten intervals without progress
        while engine.counters["watchdog_trips"] < 1:
            await asyncio.sleep(0.005)
        # the launch is still out: the engine answers, and says not ready
        assert not engine.health()["ready"]
        with pytest.raises(EngineStuckError):
            await a_task
        assert not b_task.done()
        release.set()
        assert await b_task == want
        assert survivors == [b] and engine.is_ready
        out = await _collect(
            engine, GenRequest(prompt_ids=[256, 9], max_new_tokens=3))
        assert len(out) == 3
        await engine.wait_drained()
        return engine

    engine = asyncio.run(run())
    assert engine.counters["watchdog_trips"] == 1
    stats = engine.lifecycle_stats()
    if cache == "paged":
        pool = engine.paged_cache.pool
        assert pool.free_pages == pool.num_pages - 1
    else:
        assert stats["state_pool"]["rewinds"] >= 1
        assert stats["state_pool"]["in_use"] == 0
    assert engine.health()["ready"]
    engine.stop()


# -- admission shedding -------------------------------------------------------


def test_queue_bound_sheds_with_retry_after(parts):
    bundle, params = parts

    async def run():
        engine = _make_engine(bundle, params, max_batch=1, max_pending=1)
        a = GenRequest(prompt_ids=[256, 1], max_new_tokens=10_000)
        agen = engine.generate(a)
        await agen.__anext__()  # A holds the single slot
        b = GenRequest(prompt_ids=[256, 2], max_new_tokens=2)
        b_task = asyncio.create_task(_collect(engine, b))
        while engine._pending.qsize() < 1:  # B parked in the queue
            await asyncio.sleep(0.005)
        c = GenRequest(prompt_ids=[256, 3], max_new_tokens=2)
        with pytest.raises(EngineOverloadedError) as ei:
            async for _ in engine.generate(c):
                pass
        assert ei.value.status == 429 and ei.value.retry_after is not None
        await agen.aclose()  # free the slot; B proceeds
        out_b = await b_task
        assert len(out_b) >= 1
        return engine

    engine = asyncio.run(run())
    assert engine.counters["sheds_queue"] == 1


def test_pool_saturation_sheds_paged_admission(parts):
    """With admission control on, a prompt the KV pool cannot hold right now
    is shed 429 at the front door instead of queueing forever."""
    bundle, params = parts
    engine = _make_engine(
        bundle, params, cache_mode="paged", page_size=16, max_batch=2,
        max_pending=8,
    )
    pool = engine.paged_cache.pool
    # occupy nearly the whole pool via a raw slot allocation
    free0 = pool.free_pages
    pool.allocate(0, (free0 - 1) * pool.page_size)
    big = GenRequest(prompt_ids=list(range(64)), max_new_tokens=2)
    with pytest.raises(EngineOverloadedError):
        engine.check_admission(big)
    assert engine.counters["sheds_pool"] == 1
    pool.free(0)
    engine.check_admission(big)  # headroom restored -> admissible again


def test_pool_shed_accounts_for_cached_prefix(parts):
    """The headroom check must charge only the NON-cached tail: a request
    whose prefix the radix cache already holds is admissible where a cold
    prompt of the same length is shed."""
    bundle, params = parts

    async def run():
        engine = _make_engine(
            bundle, params, cache_mode="paged", page_size=4, max_batch=2,
            max_pending=8, prefix_cache=64, prefix_block=16,
        )
        system = [(i * 5 + 1) % 256 for i in range(32)]
        await _collect(engine, GenRequest(
            prompt_ids=system + [9], max_new_tokens=2
        ))
        return engine, system

    engine, system = asyncio.run(run())
    pool = engine.paged_cache.pool
    assert engine._prefix.match_len(system + [7], 0) == 32
    # leave exactly 2 free pages (8 tokens of headroom)
    pool.allocate(0, (pool.free_pages - 2) * 4)
    warm = GenRequest(prompt_ids=system + [7], max_new_tokens=2)
    engine.check_admission(warm)  # 32/33 tokens cached -> 1 page suffices
    cold = GenRequest(prompt_ids=list(range(33)), max_new_tokens=2)
    with pytest.raises(EngineOverloadedError):
        engine.check_admission(cold)
    pool.free(0)


def test_injected_admission_shed(parts):
    bundle, params = parts
    engine = _make_engine(bundle, params)
    faults.configure([{"point": "engine.admit", "times": 1}])
    with pytest.raises(EngineOverloadedError):
        engine.check_admission(GenRequest(prompt_ids=[256], max_new_tokens=1))
    engine.check_admission(GenRequest(prompt_ids=[256], max_new_tokens=1))


def test_stopped_engine_is_unavailable(parts):
    bundle, params = parts

    async def run():
        engine = _make_engine(bundle, params)
        engine.stop()
        with pytest.raises(EngineUnavailableError):
            async for _ in engine.generate(
                GenRequest(prompt_ids=[256], max_new_tokens=1)
            ):
                pass
        return engine

    engine = asyncio.run(run())
    assert not engine.is_ready


# -- deadlines ----------------------------------------------------------------


def test_ttft_deadline_on_slow_prefill(parts):
    """Delayed prefill (injected) blows the request's TTFT budget: the
    request fails 408/ttft at the commit boundary, the engine stays up."""
    bundle, params = parts
    marker = 301

    async def run():
        engine = _make_engine(bundle, params)
        await _collect(engine, GenRequest(prompt_ids=[256, 1], max_new_tokens=2))
        faults.configure([
            {"point": "engine.prefill", "action": "delay", "delay": 0.4,
             "match_token": marker, "times": 1},
        ])
        req = GenRequest(
            prompt_ids=[256, marker], max_new_tokens=4, ttft_timeout=0.1
        )
        with pytest.raises(DeadlineExceededError) as ei:
            await _collect(engine, req)
        assert ei.value.stage == "ttft" and ei.value.status == 408
        out = await _collect(
            engine, GenRequest(prompt_ids=[256, 2], max_new_tokens=3)
        )
        assert len(out) >= 1
        return engine

    engine = asyncio.run(run())
    assert engine.counters["deadline_ttft"] == 1


def test_queue_wait_deadline_expires_parked_request(parts):
    bundle, params = parts

    async def run():
        engine = _make_engine(bundle, params, max_batch=1, decode_steps=1)
        a = GenRequest(prompt_ids=[256, 1], max_new_tokens=10_000)
        agen = engine.generate(a)
        await agen.__anext__()  # A pins the only slot
        b = GenRequest(
            prompt_ids=[256, 2], max_new_tokens=2, queue_timeout=0.1
        )
        with pytest.raises(DeadlineExceededError) as ei:
            await _collect(engine, b)
        assert ei.value.stage == "queue"
        await agen.aclose()
        return engine

    engine = asyncio.run(run())
    assert engine.counters["deadline_queue"] == 1


def test_total_deadline_cuts_generation_short(parts):
    bundle, params = parts

    async def run():
        engine = _make_engine(bundle, params, decode_steps=1)
        await _collect(engine, GenRequest(prompt_ids=[256, 1], max_new_tokens=2))
        req = GenRequest(
            prompt_ids=[256, 3], max_new_tokens=100_000, total_timeout=0.25
        )
        got = []
        with pytest.raises(DeadlineExceededError) as ei:
            async for tok in engine.generate(req):
                got.append(tok)
        assert ei.value.stage == "total"
        assert got, "some tokens should stream before the budget elapses"
        return engine

    engine = asyncio.run(run())
    assert engine.counters["deadline_total"] >= 1
    assert engine.active_slots == 0  # slot + pages reclaimed


# -- gRPC retry/backoff -------------------------------------------------------


class _FakeRpcError(Exception):
    def __init__(self, code):
        super().__init__("fake upstream error {}".format(code))
        self.grpc_code = code


def _grpc_client(monkeypatch):
    from clearml_serving_tpu.engines.grpc_client import JaxGrpcEngineRequest

    monkeypatch.setenv("TPUSERVE_GRPC_RETRY_BACKOFF", "0.001")
    monkeypatch.setenv("TPUSERVE_GRPC_RETRY_BACKOFF_MAX", "0.002")
    return object.__new__(JaxGrpcEngineRequest)


def test_grpc_transient_errors_retry_then_succeed(monkeypatch):
    from clearml_serving_tpu.engines import grpc_client as gc

    cli = _grpc_client(monkeypatch)
    calls = []

    async def flaky(payload, timeout=None):
        calls.append(1)
        if len(calls) < 3:
            raise _FakeRpcError("UNAVAILABLE")
        return b"ok"

    before = dict(gc.RETRY_STATS)
    out = asyncio.run(cli._call_with_retry(flaky, b"req", timeout=1.0))
    assert out == b"ok" and len(calls) == 3
    assert gc.RETRY_STATS["retries"] - before["retries"] == 2


def test_grpc_retry_budget_maps_to_structured_errors(monkeypatch):
    cli = _grpc_client(monkeypatch)

    async def always_unavailable(payload, timeout=None):
        raise _FakeRpcError("UNAVAILABLE")

    async def always_deadline(payload, timeout=None):
        raise _FakeRpcError("DEADLINE_EXCEEDED")

    with pytest.raises(UpstreamUnavailableError) as ei:
        asyncio.run(cli._call_with_retry(always_unavailable, b"r", timeout=1.0))
    assert ei.value.status == 503 and ei.value.retry_after is not None
    with pytest.raises(UpstreamTimeoutError) as ei:
        asyncio.run(cli._call_with_retry(always_deadline, b"r", timeout=1.0))
    assert ei.value.status == 504


def test_grpc_non_transient_errors_do_not_retry(monkeypatch):
    cli = _grpc_client(monkeypatch)
    calls = []

    async def internal(payload, timeout=None):
        calls.append(1)
        raise _FakeRpcError("INTERNAL")

    with pytest.raises(_FakeRpcError):
        asyncio.run(cli._call_with_retry(internal, b"r", timeout=1.0))
    assert len(calls) == 1


def test_grpc_injected_fault_exercises_retry_path(monkeypatch):
    """The faults seam covers the gRPC path too: injected UNAVAILABLE on the
    first two attempts, then the real call runs."""
    cli = _grpc_client(monkeypatch)
    faults.configure([
        {"point": "grpc.call", "grpc_code": "UNAVAILABLE", "times": 2},
    ])
    calls = []

    async def ok(payload, timeout=None):
        calls.append(1)
        return b"fine"

    out = asyncio.run(cli._call_with_retry(ok, b"r", timeout=1.0))
    assert out == b"fine" and len(calls) == 1


# -- pipelined decode under chaos (docs/pipelined_decode.md) ------------------


def test_watchdog_recovery_with_nonempty_inflight_queue(parts):
    """Depth-2 pipeline, paged backend, several live requests: a stall at
    the retire stage trips the watchdog WHILE a younger chunk is still in
    flight. Recovery must discard the whole in-flight queue under the epoch
    bump, execute the deferred (quarantined) frees, flip back to ready, and
    keep page accounting balanced (armed sanitizer) — then serve again."""
    bundle, params = parts

    async def run():
        engine = _make_engine(
            bundle, params, decode_steps=2, watchdog_interval=0.3,
            cache_mode="paged", page_size=4, pipeline_depth=2,
            eos_token_id=None,  # victims must still be decoding at the stall
        )
        assert engine.pipeline_depth == 2
        assert engine._sanitizer is not None
        reqs = [
            GenRequest(prompt_ids=[256, 1 + i], max_new_tokens=2)
            for i in range(3)
        ]
        await asyncio.gather(*(_collect(engine, r) for r in reqs))
        await engine.wait_drained()
        victims = [
            GenRequest(prompt_ids=[256, 40 + i], max_new_tokens=600)
            for i in range(3)
        ]
        tasks = [asyncio.create_task(_collect(engine, v)) for v in victims]
        # arm the stall only once every victim holds a slot — a victim
        # still mid-admission at the trip would be committed afterwards
        # and complete normally
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0 and not all(
            v.produced >= 1 for v in victims
        ):
            await asyncio.sleep(0.01)
        assert all(v.produced >= 1 for v in victims)
        faults.configure([
            {"point": "engine.decode.stall", "action": "delay",
             "delay": 1.2, "times": 1},
        ])
        done, pending = await asyncio.wait(tasks, timeout=15.0)
        assert not pending
        errors = [t.exception() for t in tasks]
        assert all(isinstance(e, EngineStuckError) for e in errors), errors
        assert engine.counters["watchdog_trips"] >= 1
        # the pipeline was discarded wholesale
        t0 = time.monotonic()
        while not engine.is_ready and time.monotonic() - t0 < 10.0:
            await asyncio.sleep(0.01)
        assert engine.is_ready
        assert not engine._inflight and not engine._quarantine
        # still serves, and page accounting balances through drain
        out = await _collect(
            engine, GenRequest(prompt_ids=[256, 9], max_new_tokens=3)
        )
        assert len(out) >= 1
        await engine.wait_drained()
        assert engine.paged_cache.pool.free_pages == (
            engine.paged_cache.pool.num_pages - 1
        )
        return engine

    engine = asyncio.run(run())
    assert engine.health()["ready"]


def test_retire_fault_isolates_matched_request(parts):
    """An engine.decode.retire fault matched to one request fails ONLY that
    request (EngineStepError); the rest of the chunk still emits, the other
    requests complete, and the paged pool balances at drain."""
    bundle, params = parts
    marker = 301

    async def run():
        engine = _make_engine(
            bundle, params, decode_steps=2, cache_mode="paged", page_size=4,
            pipeline_depth=2,
            eos_token_id=None,  # exact token counts below
        )
        await _collect(engine, GenRequest(prompt_ids=[256, 1], max_new_tokens=2))
        await engine.wait_drained()
        faults.configure([
            {"point": "engine.decode.retire", "match_token": marker,
             "times": 1, "message": "retire blew up"},
        ])
        poisoned = GenRequest(prompt_ids=[256, marker], max_new_tokens=40)
        healthy = GenRequest(prompt_ids=[256, 7], max_new_tokens=6)
        p_task = asyncio.create_task(_collect(engine, poisoned))
        h_task = asyncio.create_task(_collect(engine, healthy))
        out_h = await asyncio.wait_for(h_task, timeout=30)
        with pytest.raises(EngineStepError):
            await asyncio.wait_for(p_task, timeout=30)
        assert len(out_h) == 6, "healthy request must emit every token"
        assert engine.counters["step_failures"] >= 1
        await engine.wait_drained()
        assert engine.paged_cache.pool.free_pages == (
            engine.paged_cache.pool.num_pages - 1
        )
        return engine

    engine = asyncio.run(run())
    assert engine.is_ready


def test_injected_class_shed(parts):
    """The engine.admit.class seam forces a class-policy shed: structured
    429 carrying the request's priority class, booked under reason
    'class'."""
    bundle, params = parts
    engine = _make_engine(bundle, params)
    faults.configure([{"point": "engine.admit.class", "times": 1}])
    with pytest.raises(EngineOverloadedError) as ei:
        engine.check_admission(
            GenRequest(prompt_ids=[256], max_new_tokens=1, priority="batch")
        )
    assert ei.value.shed_class == "batch"
    assert engine._class_sheds["class"]["batch"] == 1
    engine.check_admission(
        GenRequest(prompt_ids=[256], max_new_tokens=1, priority="batch")
    )
    engine.stop()


# -- preemptible batch lane under chaos (docs/slo_scheduling.md) --------------


def test_preempt_fault_mid_commit_aborts_without_leaking_pages(parts):
    """An engine.preempt fault fires mid-preemption — AFTER the victim's
    generated-so-far KV was committed into the radix cache, BEFORE the slot
    free/requeue. The preemption must abort cleanly: the victim keeps
    decoding, a later retry succeeds, and page accounting stays balanced
    under the armed sanitizer (the radix store alone is a normal
    admission-commit store)."""
    bundle, params = parts

    async def run():
        engine = _make_engine(
            bundle, params, max_batch=1, decode_steps=2, cache_mode="paged",
            page_size=16, prefix_cache=64, prefix_block=16,
            prefill_buckets=[32, 64], eos_token_id=None,
        )
        assert engine._sanitizer is not None, "TPUSERVE_SANITIZE did not arm"
        batch = GenRequest(
            prompt_ids=[256] + [(i * 3 + 1) % 250 for i in range(16)],
            max_new_tokens=30, priority="batch",
        )
        b_task = asyncio.create_task(_collect(engine, batch))
        while batch.produced < 4:
            await asyncio.sleep(0.005)
        # the FIRST preemption attempt dies mid-commit; the retry (next
        # chunk boundary) must succeed
        faults.configure([{"point": "engine.preempt", "times": 1}])
        hi = GenRequest(prompt_ids=[256, 9], max_new_tokens=2)
        out_hi = await asyncio.wait_for(_collect(engine, hi), timeout=60)
        assert len(out_hi) >= 1
        out_b = await asyncio.wait_for(b_task, timeout=60)
        assert len(out_b) == 30
        await engine.wait_drained()
        return engine

    engine = asyncio.run(run())
    assert engine.counters["preemptions"] >= 1, "retry never preempted"
    stats = engine._sanitizer.stats()
    assert stats["checks"] > 0 and stats["failures"] == 0
    pool = engine.paged_cache.pool
    assert pool.free_pages == (
        pool.num_pages - 1 - engine._prefix.cached_pages
    )
    engine.stop()


def test_seeded_interactive_stream_identical_across_batch_preemption(parts):
    """Acceptance (ISSUE 6): a SEEDED interactive stream must be
    byte-identical whether or not a batch neighbor was preempted — seeded
    sampling keys on (seed, tokens-generated) per slot, so scheduler
    decisions about neighbors must never leak into the stream."""
    bundle, params = parts
    seed_req = dict(
        prompt_ids=[256, 11, 12, 13], max_new_tokens=16, temperature=0.9,
        seed=1234,
    )

    def make_engine():
        return _make_engine(
            bundle, params, max_batch=2, decode_steps=2, cache_mode="paged",
            page_size=16, prefix_cache=64, prefix_block=16,
            prefill_buckets=[16, 32], eos_token_id=None,
        )

    async def alone():
        engine = make_engine()
        out = await _collect(engine, GenRequest(**seed_req))
        await engine.wait_drained()
        engine.stop()
        return out

    async def with_preempted_neighbors():
        engine = make_engine()
        victims = [
            GenRequest(
                prompt_ids=[256, 40 + i, 41], max_new_tokens=40,
                priority="batch",
            )
            for i in range(2)
        ]
        tasks = [asyncio.create_task(_collect(engine, v)) for v in victims]
        while not all(v.produced >= 2 for v in victims):
            await asyncio.sleep(0.005)
        # both slots busy with batch work: the seeded interactive request
        # forces a preemption
        out = await asyncio.wait_for(
            _collect(engine, GenRequest(**seed_req)), timeout=60
        )
        for t in tasks:
            await asyncio.wait_for(t, timeout=60)
        await engine.wait_drained()
        return engine, out

    expected = asyncio.run(alone())
    engine, got = asyncio.run(with_preempted_neighbors())
    assert engine.counters["preemptions"] >= 1, "no neighbor was preempted"
    assert got == expected, "seeded stream diverged across preemption"
    stats = engine._sanitizer.stats()
    assert stats["checks"] > 0 and stats["failures"] == 0
    engine.stop()


def test_stop_with_chunks_in_flight_reclaims_pages(parts):
    """stop() while the depth-2 pipeline holds undelivered chunks: every
    consumer unblocks with EngineUnavailableError and the loop's exit path
    reclaims all pages despite the dropped in-flight queue."""
    bundle, params = parts

    async def run():
        engine = _make_engine(
            bundle, params, decode_steps=2, cache_mode="paged", page_size=4,
            pipeline_depth=2,
            eos_token_id=None,  # long-runners must still be live at stop()
        )
        reqs = [
            GenRequest(prompt_ids=[256, 20 + i], max_new_tokens=10_000)
            for i in range(2)
        ]
        tasks = [asyncio.create_task(_collect(engine, r)) for r in reqs]
        # let decode reach a pipelined steady state
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0 and not all(
            r.produced > 2 for r in reqs
        ):
            await asyncio.sleep(0.01)
        engine.stop()
        for t in tasks:
            with pytest.raises(EngineUnavailableError):
                await asyncio.wait_for(t, timeout=15)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0 and not engine._loop_task.done():
            await asyncio.sleep(0.01)
        assert engine._loop_task.done()
        pool = engine.paged_cache.pool
        assert pool.free_pages == pool.num_pages - 1
        assert not engine._quarantine
        return engine

    asyncio.run(run())


def test_dispatch_prepare_seam_fails_batch_structurally(parts):
    """The engine.dispatch.prepare yield-point seam (interleaving-explorer
    boundary, docs/static_analysis.md) is a live fault point: a raise-once
    spec there fails the in-flight batch with a structured error and the
    engine keeps serving — armed sanitizer balancing the books."""
    bundle, params = parts

    async def run():
        engine = _make_engine(bundle, params, decode_steps=1)
        await _collect(engine, GenRequest(prompt_ids=[256, 1], max_new_tokens=2))
        faults.configure([
            {"point": "engine.dispatch.prepare", "action": "raise",
             "times": 1, "message": "prep seam"},
        ])
        with pytest.raises(EngineStepError):
            await _collect(
                engine, GenRequest(prompt_ids=[256, 2], max_new_tokens=8)
            )
        out = await _collect(
            engine, GenRequest(prompt_ids=[256, 3], max_new_tokens=4)
        )
        assert len(out) >= 1
        return engine

    engine = asyncio.run(run())
    assert engine.counters["step_failures"] == 1


def test_drain_seam_fires_at_the_drained_boundary(parts):
    """engine.drain fires exactly once per drain, at the boundary the
    drained sanitizer audit runs on."""
    bundle, params = parts

    async def run():
        engine = _make_engine(bundle, params, decode_steps=1)
        spec = faults.FaultSpec(point="engine.drain", action="delay",
                                delay=0.0, times=-1)
        faults.configure([spec])
        await _collect(engine, GenRequest(prompt_ids=[256, 4], max_new_tokens=2))
        await engine.wait_drained()
        return spec.fired

    fired = asyncio.run(run())
    assert fired >= 1
