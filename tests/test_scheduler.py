"""SLO-aware scheduling invariants (docs/slo_scheduling.md).

Covers the scheduler contracts the loadtest harness's headline claim rests
on: earliest-deadline-first ordering within a priority class, strict class
order across classes, the starvation floor that keeps batch work moving,
class-aware shedding with a drain-rate-derived Retry-After, brownout
hysteresis (no flapping across a threshold), the brownout stage effects,
and preempt -> resume radix replay correctness under the armed KV
sanitizer.
"""

import asyncio
import time

import jax
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.errors import EngineOverloadedError
from clearml_serving_tpu.llm.engine import (
    GenRequest,
    LLMEngineCore,
    PRIORITY_CLASSES,
    _BrownoutController,
    _ClassedPendingQueue,
)


@pytest.fixture(scope="module")
def parts():
    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


@pytest.fixture(autouse=True)
def armed_sanitizer(monkeypatch):
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")


def _req(cls="interactive", deadline=None, ids=(1, 2)):
    r = GenRequest(prompt_ids=list(ids), max_new_tokens=2, priority=cls)
    r._deadline = deadline
    return r


async def _collect(engine, req):
    out = []
    async for token in engine.generate(req):
        out.append(token)
    return out


# -- queue invariants ---------------------------------------------------------


def test_edf_ordering_within_a_class():
    q = _ClassedPendingQueue()
    late = _req(deadline=100.0)
    early = _req(deadline=10.0)
    never = _req(deadline=None)  # no deadline: after every deadlined one
    q.put_nowait(never)
    q.put_nowait(late)
    q.put_nowait(early)
    assert q.get_nowait() is early
    assert q.get_nowait() is late
    assert q.get_nowait() is never


def test_strict_cross_class_dispatch_order():
    q = _ClassedPendingQueue()
    b = _req("batch", deadline=1.0)          # earliest deadline overall...
    e = _req("best_effort", deadline=2.0)
    i = _req("interactive", deadline=999.0)  # ...but interactive still wins
    q.put_nowait(b)
    q.put_nowait(e)
    q.put_nowait(i)
    assert q.get_nowait() is i
    assert q.get_nowait() is b               # then strict class order
    assert q.get_nowait() is e


def test_starvation_floor_admits_batch_within_n_interactive_pops():
    floor = 3
    q = _ClassedPendingQueue(starvation_floor=floor)
    batch = _req("batch")
    q.put_nowait(batch)
    popped = []
    # keep one interactive queued at all times; the batch request must pop
    # within `floor` + 1 pops despite the constant higher-class pressure
    for _ in range(floor + 1):
        q.put_nowait(_req("interactive"))
        popped.append(q.get_nowait())
    assert batch in popped, "batch request starved past the floor"
    assert popped.index(batch) == floor


def test_waiting_skips_cancelled_and_failed_entries():
    """_maybe_preempt sizes preemption off waiting('interactive'): a
    cancelled/expired request still sitting in the heap must not count,
    or batch slots get preempted (and their budget burned) for a corpse
    the admission pop will simply discard."""
    q = _ClassedPendingQueue()
    live, dead, failed = _req(), _req(), _req()
    dead.cancelled = True
    failed.error = RuntimeError("expired")
    for r in (live, dead, failed):
        q.put_nowait(r)
    assert q.waiting("interactive") == 1
    assert q.qsize() == 3  # raw depth still reflects heap residency


def test_pool_pressure_ignores_reclaimable_prefix_cache_pages(parts):
    """A warm-but-idle radix cache retains pages up to its budget; those
    are reclaimable on demand and must not read as pool occupancy, or the
    brownout stage pins high with zero traffic."""
    bundle, params = parts

    async def run():
        engine = LLMEngineCore(
            bundle, params, max_batch=2, max_seq_len=128,
            prefill_buckets=[32, 64], eos_token_id=None, decode_steps=1,
            cache_mode="paged", page_size=16, prefix_cache=64,
            prefix_block=16, prefix_cache_pages=32, max_pending=8,
        )
        # warm the cache well past half the pool, then go idle
        for i in range(4):
            req = GenRequest(
                prompt_ids=[(i * 29 + j) % 250 + 1 for j in range(33)],
                max_new_tokens=2,
            )
            async for _ in engine.generate(req):
                pass
        await engine.wait_drained()
        return engine

    engine = asyncio.run(run())
    assert engine._prefix.cached_pages >= 8  # the cache IS warm
    score, signals = engine._pressure_score()
    assert signals["pool"] < 0.2, signals
    engine.stop()


def test_shed_lowest_never_evicts_midstream_resume():
    """A preempted batch request waiting to resume has already streamed
    tokens to an attached consumer: shedding it turns an in-progress 200
    into a mid-stream 429 and discards its committed KV. Fresh queued work
    sheds first; with only resumes queued, nothing is evicted (the arrival
    sheds at the door instead)."""
    q = _ClassedPendingQueue()
    resume = _req("batch")
    resume.produced = 7  # mid-stream: preempted after 7 emitted tokens
    fresh = _req("batch")
    q.put_nowait(resume)
    q.put_nowait(fresh)
    assert q.shed_lowest("interactive") is fresh
    assert q.shed_lowest("interactive") is None  # resume is immune


def test_retry_after_hint_anchors_drain_rate_at_now(parts):
    """A wedged loop must not advertise the drain rate of a historical
    burst: the hint's rate window is anchored at now, so the longer the
    engine goes without commits, the longer the advertised backoff."""
    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=1, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, decode_steps=1, max_pending=8,
    )
    now = time.monotonic()
    # 8 commits in half a second... ten seconds ago
    engine._admit_times.extend(now - 10.0 + 0.0625 * i for i in range(8))
    hint = engine._retry_after_hint(ahead=4)
    # stale-burst rate would be 14/s -> ~0.36s; now-anchored is ~0.7/s
    assert hint >= 5.0, hint
    engine.stop()


def test_brownout_deadline_signal_needs_minimum_volume(parts):
    """One expired request against zero admissions is a deadline ratio of
    1.0 — without a volume floor a single misbehaving client slams an idle
    engine into stage-3 brownout."""
    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=1, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, decode_steps=1, max_pending=8,
    )
    engine._pressure_window = (time.monotonic() - 6.0, 0, 0, 0)
    engine.counters["deadline_queue"] = 1
    _, signals = engine._pressure_score()
    assert "deadline" not in signals, signals
    # at volume the ratio counts
    engine._pressure_window = (time.monotonic() - 6.0, 0, 0, 0)
    engine.counters["deadline_queue"] = 3
    engine._admit_count = 1
    _, signals = engine._pressure_score()
    assert signals.get("deadline") == 0.75, signals
    engine.stop()


def test_queue_depths_and_snapshot():
    q = _ClassedPendingQueue()
    q.put_nowait(_req("interactive"))
    q.put_nowait(_req("batch"))
    q.put_nowait(_req("batch"))
    assert q.depths() == {"interactive": 1, "batch": 2, "best_effort": 0}
    assert q.qsize() == 3 and not q.empty()
    assert len(q.requests()) == 3
    assert len(q.pop_all()) == 3 and q.empty()


def test_shed_lowest_takes_strictly_lower_class_latest_deadline():
    q = _ClassedPendingQueue()
    b1 = _req("batch", deadline=5.0)
    b2 = _req("batch", deadline=50.0)
    q.put_nowait(b1)
    q.put_nowait(b2)
    # an interactive arrival evicts the LATEST-deadline batch request
    victim = q.shed_lowest("interactive")
    assert victim is b2
    # batch cannot evict batch (strictly lower only)
    assert q.shed_lowest("batch") is None
    # best_effort has nothing below it
    assert q.shed_lowest("best_effort") is None
    assert q.get_nowait() is b1


# -- brownout controller ------------------------------------------------------


def test_brownout_hysteresis_no_flapping_across_threshold():
    c = _BrownoutController(dwell=10.0)
    t = 1000.0
    assert c.update(0.2, now=t) == 0
    # oscillate tightly around the stage-1 UP threshold (0.70): once up,
    # the stage must hold — dropping needs score < DOWN (0.50) AND dwell
    assert c.update(0.71, now=t + 1) == 1
    transitions_after_up = c.transitions
    for k in range(20):
        score = 0.69 if k % 2 else 0.71
        c.update(score, now=t + 1 + 0.1 * k)
    assert c.stage == 1
    assert c.transitions == transitions_after_up, "stage flapped"
    # below DOWN but inside the dwell window: still held
    assert c.update(0.1, now=t + 5) == 1
    # below DOWN past the dwell: steps down one stage
    assert c.update(0.1, now=t + 12) == 0


def test_brownout_raises_immediately_and_steps_down_one_at_a_time():
    c = _BrownoutController(dwell=1.0)
    t = 0.0
    assert c.update(0.99, now=t) == 3          # straight to the top stage
    assert c.update(0.0, now=t + 0.5) == 3     # dwell holds it
    assert c.update(0.0, now=t + 2.0) == 2     # one stage per dwell
    assert c.update(0.0, now=t + 4.0) == 1
    assert c.update(0.0, now=t + 6.0) == 0


# -- admission: class-aware shedding + Retry-After ----------------------------


def test_priority_validation(parts):
    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None,
    )
    with pytest.raises(ValueError, match="priority"):
        engine.validate(
            GenRequest(prompt_ids=[1], max_new_tokens=1, priority="vip")
        )
    for cls in PRIORITY_CLASSES:
        engine.validate(
            GenRequest(prompt_ids=[1], max_new_tokens=1, priority=cls)
        )
    engine.stop()


def test_retry_after_hint_grows_with_queue_depth(parts):
    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=1, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, max_pending=4,
    )
    # seed an observed drain rate of 2 admissions/s
    t0 = time.monotonic()
    engine._admit_times.extend([t0 - 1.0, t0 - 0.5, t0])
    h0 = engine._retry_after_hint(ahead=0)
    h4 = engine._retry_after_hint(ahead=4)
    h12 = engine._retry_after_hint(ahead=12)
    assert h0 < h4 < h12
    assert h4 == pytest.approx((4 + 1) / 2.0, rel=0.01)
    # no drain observed yet: the fallback still grows with depth
    engine._admit_times.clear()
    assert engine._retry_after_hint(ahead=0) < engine._retry_after_hint(
        ahead=10
    )
    engine.stop()


def test_queue_full_shed_carries_drain_rate_retry_after(parts):
    """Satellite: the PR 2 queue-shed branch now derives Retry-After from
    the observed drain rate — the hint must grow with the queue depth."""
    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=1, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, max_pending=2,
    )
    t0 = time.monotonic()
    engine._admit_times.extend([t0 - 2.0, t0 - 1.0, t0])  # 1 admission/s
    # park one interactive request in the queue (no loop running: nothing
    # drains it)
    parked = _req("interactive")
    engine._pending.put_nowait(parked)
    shallow = None
    try:
        engine.check_admission(_req("interactive"))
    except EngineOverloadedError:
        pytest.fail("one queued request is under the bound of 2")
    engine._pending.put_nowait(_req("interactive"))
    with pytest.raises(EngineOverloadedError) as shallow:
        engine.check_admission(_req("interactive"))
    engine._pending.put_nowait(_req("interactive"))
    engine._pending.put_nowait(_req("interactive"))
    with pytest.raises(EngineOverloadedError) as deep:
        engine.check_admission(_req("interactive"))
    assert shallow.value.retry_after is not None
    assert deep.value.retry_after > shallow.value.retry_after
    assert shallow.value.status == 429
    engine.stop()


def test_interactive_arrival_evicts_queued_best_effort(parts):
    """Class-aware shedding: with the queue at its bound, a higher-class
    arrival evicts the lowest-class queued request (429 delivered on ITS
    stream) instead of shedding the arrival."""
    bundle, params = parts

    async def run():
        engine = LLMEngineCore(
            bundle, params, max_batch=1, max_seq_len=64,
            prefill_buckets=[16], eos_token_id=None, max_pending=1,
            decode_steps=1,
        )
        a = GenRequest(prompt_ids=[1, 2], max_new_tokens=10_000)
        agen = engine.generate(a)
        await agen.__anext__()  # A pins the only slot
        be = GenRequest(
            prompt_ids=[1, 3], max_new_tokens=2, priority="best_effort"
        )
        be_task = asyncio.create_task(_collect(engine, be))
        while engine._pending.qsize() < 1:
            await asyncio.sleep(0.005)
        # queue full: an interactive arrival must ADMIT by evicting `be`
        hi = GenRequest(prompt_ids=[1, 4], max_new_tokens=2)
        hi_task = asyncio.create_task(_collect(engine, hi))
        with pytest.raises(EngineOverloadedError) as ei:
            await be_task
        assert ei.value.shed_class == "best_effort"
        assert ei.value.retry_after is not None
        await agen.aclose()  # free the slot; the interactive request runs
        out = await asyncio.wait_for(hi_task, timeout=30)
        assert len(out) >= 1
        return engine

    engine = asyncio.run(run())
    assert engine._class_sheds["queue"]["best_effort"] == 1
    # a best_effort arrival into an all-higher queue sheds ITSELF
    assert engine.counters["sheds_queue"] == 1
    engine.stop()


# -- brownout stage effects ---------------------------------------------------


def test_brownout_stage2_caps_batch_tokens_not_interactive(parts):
    bundle, params = parts

    async def run():
        engine = LLMEngineCore(
            bundle, params, max_batch=2, max_seq_len=128,
            prefill_buckets=[16], eos_token_id=None, decode_steps=1,
            brownout=True, brownout_batch_cap=3, brownout_dwell=120.0,
        )
        engine._brownout.stage = 2
        engine._brownout._changed_at = time.monotonic()  # dwell holds it
        batch = GenRequest(
            prompt_ids=[1, 2], max_new_tokens=50, priority="batch"
        )
        inter = GenRequest(prompt_ids=[1, 3], max_new_tokens=6)
        out_b, out_i = await asyncio.gather(
            _collect(engine, batch), _collect(engine, inter)
        )
        assert len(out_b) == 3, "batch-lane cap must bite at stage 2"
        assert len(out_i) == 6, "interactive is never capped"
        return engine

    engine = asyncio.run(run())
    engine.stop()


def test_brownout_stage3_sheds_best_effort_at_the_door(parts):
    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, brownout=True, brownout_dwell=120.0,
    )
    engine._brownout.stage = 3
    engine._brownout._changed_at = time.monotonic()
    with pytest.raises(EngineOverloadedError) as ei:
        engine.check_admission(
            GenRequest(prompt_ids=[1], max_new_tokens=1,
                       priority="best_effort")
        )
    assert ei.value.shed_class == "best_effort"
    # interactive and batch still admit at stage 3
    engine.check_admission(GenRequest(prompt_ids=[1], max_new_tokens=1))
    engine.check_admission(
        GenRequest(prompt_ids=[1], max_new_tokens=1, priority="batch")
    )
    assert engine._class_sheds["brownout"]["best_effort"] == 1
    engine.stop()


# -- preemption: resume replays through the radix cache -----------------------


def test_preempt_resume_radix_replay_byte_identical(parts):
    """A preempted batch request's stream must be byte-identical to an
    uncontended run: its generated-so-far KV is committed into the radix
    prefix cache at preemption, so the resume prefills only the tail and
    greedy decoding continues exactly — audited by the armed sanitizer."""
    bundle, params = parts
    prompt = [(i * 7 + 3) % 250 + 1 for i in range(17)]
    n_new = 24

    def make_engine():
        return LLMEngineCore(
            bundle, params, max_batch=1, max_seq_len=128,
            prefill_buckets=[32, 64], eos_token_id=None, decode_steps=2,
            cache_mode="paged", page_size=16, prefix_cache=64,
            prefix_block=16, preempt_batch=True, preempt_budget=2,
        )

    async def control():
        engine = make_engine()
        req = GenRequest(
            prompt_ids=list(prompt), max_new_tokens=n_new, priority="batch"
        )
        out = await _collect(engine, req)
        await engine.wait_drained()
        engine.stop()
        return out

    async def contended():
        engine = make_engine()
        assert engine._sanitizer is not None, "TPUSERVE_SANITIZE did not arm"
        batch = GenRequest(
            prompt_ids=list(prompt), max_new_tokens=n_new, priority="batch"
        )
        b_task = asyncio.create_task(_collect(engine, batch))
        while batch.produced < 6:
            await asyncio.sleep(0.005)
        # slot pressure + queued interactive work => preemption
        hi = GenRequest(prompt_ids=[1, 9, 9], max_new_tokens=2)
        out_hi = await asyncio.wait_for(_collect(engine, hi), timeout=60)
        assert len(out_hi) >= 1
        out_b = await asyncio.wait_for(b_task, timeout=60)
        await engine.wait_drained()
        return engine, out_b

    expected = asyncio.run(control())
    engine, got = asyncio.run(contended())
    assert engine.counters["preemptions"] >= 1, "no preemption happened"
    assert got == expected, "preempt->resume diverged from the clean run"
    assert engine._prefix.hits >= 1, "resume did not hit the radix cache"
    stats = engine._sanitizer.stats()
    assert stats["checks"] > 0 and stats["failures"] == 0
    pool = engine.paged_cache.pool
    assert pool.free_pages == (
        pool.num_pages - 1 - engine._prefix.cached_pages
    )
    engine.stop()


def test_preempt_pins_history_until_resume(parts):
    """Preemption must PIN the victim's stored history against radix
    eviction while it waits in the queue (prefix_cache.pin_run): the lane's
    near-zero-prefill resume promise would otherwise silently degrade to a
    full re-prefill whenever pool pressure LRU-evicts the stored run. The
    pin is released by the resume's admission lookup — no pinned nodes may
    outlive the run."""
    bundle, params = parts
    prompt = [(i * 11 + 5) % 250 + 1 for i in range(17)]

    async def run():
        engine = LLMEngineCore(
            bundle, params, max_batch=1, max_seq_len=128,
            prefill_buckets=[32, 64], eos_token_id=None, decode_steps=1,
            cache_mode="paged", page_size=16, prefix_cache=64,
            prefix_block=16, prefix_cache_pages=2,  # tight: eviction churns
            preempt_batch=True, preempt_budget=2,
        )
        batch = GenRequest(
            prompt_ids=list(prompt), max_new_tokens=24, priority="batch"
        )
        b_task = asyncio.create_task(_collect(engine, batch))
        while batch.produced < 4:
            await asyncio.sleep(0.005)
        hi = GenRequest(prompt_ids=[1, 9, 9], max_new_tokens=24)
        hi_task = asyncio.create_task(_collect(engine, hi))
        while engine.counters["preemptions"] < 1:
            await asyncio.sleep(0.005)
        # victim waits in the queue (the single slot is busy with `hi`):
        # its history must be pinned and still served by the cache
        assert batch._resume_pin is not None, "preemption took no pin"
        history_len = len(batch.prompt_ids)
        assert engine._prefix.match_len(batch.prompt_ids) >= (
            (history_len - 1) // 16 * 16
        ), "pinned history not cached while queued"
        await asyncio.wait_for(hi_task, timeout=60)
        out_b = await asyncio.wait_for(b_task, timeout=60)
        assert len(out_b) == 24
        await engine.wait_drained()
        return engine, batch

    engine, batch = asyncio.run(run())
    assert batch._resume_pin is None, "resume admission must release the pin"
    # no pinned node outlives the preempt->resume round trip
    with engine._prefix._lock:
        stack = list(engine._prefix._roots.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            assert node.pinned == 0, "leaked pin on a radix node"
    stats = engine._sanitizer.stats() if engine._sanitizer else None
    assert stats is None or stats["failures"] == 0
    engine.stop()


def test_preempt_budget_makes_request_immune(parts):
    """A request that exhausted its preemption budget is no longer a victim
    (the starvation guarantee): with budget 0, interactive arrivals wait
    for the batch slot instead of preempting it."""
    bundle, params = parts

    async def run():
        engine = LLMEngineCore(
            bundle, params, max_batch=1, max_seq_len=128,
            prefill_buckets=[16], eos_token_id=None, decode_steps=1,
            cache_mode="paged", page_size=16, preempt_batch=True,
            preempt_budget=0,
        )
        batch = GenRequest(
            prompt_ids=[1, 2, 3], max_new_tokens=12, priority="batch"
        )
        b_task = asyncio.create_task(_collect(engine, batch))
        while batch.produced < 2:
            await asyncio.sleep(0.005)
        hi = GenRequest(prompt_ids=[1, 5], max_new_tokens=2)
        out_hi = await asyncio.wait_for(_collect(engine, hi), timeout=60)
        out_b = await b_task
        assert len(out_b) == 12, "budget-exhausted batch run must finish"
        assert len(out_hi) >= 1
        return engine

    engine = asyncio.run(run())
    assert engine.counters["preemptions"] == 0
    engine.stop()


# -- ragged scheduler: brownout on the token budget ---------------------------


def test_brownout_stage3_shrinks_ragged_step_token_budget(parts):
    """The legacy stage-3 hook was _prefill_gate.set_budget(1); under the
    ragged scheduler the gate no longer exists — stage 3 must instead
    shrink the effective step token budget, so decode slots drain ahead of
    new admission chunks, and restore it when the stage drops
    (docs/ragged_attention.md)."""
    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, brownout=True, brownout_dwell=120.0,
        cache_mode="paged", step_token_budget=128,
    )
    try:
        assert engine._prefill_gate is None  # the gate is gone in ragged mode
        assert engine._effective_token_budget() == 128
        engine._brownout.stage = 3
        engine._brownout._changed_at = time.monotonic()
        shrunk = engine._effective_token_budget()
        assert shrunk < 128
        assert shrunk > engine.max_batch  # decode rows always still fit
        assert engine.lifecycle_stats()["ragged"]["effective_budget"] == shrunk
        # admission work under stage 3 is bounded by the shrunken budget:
        # a planned step may hand prefill jobs at most shrunk - n_decode
        # tokens, exactly the legacy drain-ahead-of-admissions behavior
        engine._brownout.stage = 0
        assert engine._effective_token_budget() == 128
    finally:
        engine.stop()


def test_brownout_stage3_budget_accounts_multi_token_rows(parts):
    """ISSUE 13 satellite: a q=4 decode row is FOUR tokens of the step
    budget. Under the stage-3 shrunken budget the planner collapses the
    multi-step windows until the launch's token demand fits — decode
    keeps draining, admissions keep their minimal chunk, and nothing
    over-commits the brownout ceiling."""
    import numpy as np

    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=6, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, decode_steps=4, scheduler="ragged",
        step_token_budget=64, cache_mode="paged",
        brownout=True, brownout_dwell=120.0,
    )
    try:
        for slot in range(6):
            req = GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=50)
            req.prompt_len = 3
            req.produced = 1
            engine._slot_req[slot] = req
            engine.paged_cache.pool.allocate(slot, 3)
        active = np.ones(6, bool)
        plan = engine._prepare_ragged(active, 0)
        assert plan["launch_steps"] == 4
        assert plan["used_tokens"] == 6 * 4
        assert plan["used_tokens"] <= engine._effective_token_budget()
        engine._brownout.stage = 3
        engine._brownout._changed_at = time.monotonic()
        eff = engine._effective_token_budget()
        assert eff < 64
        plan = engine._prepare_ragged(active, 0)
        assert plan["launch_steps"] < 4, "windows must collapse at stage 3"
        assert plan["used_tokens"] <= eff
        engine._brownout.stage = 0
        plan = engine._prepare_ragged(active, 0)
        assert plan["launch_steps"] == 4  # restored with the stage drop
    finally:
        for slot in range(6):
            engine._slot_req[slot] = None
            engine.paged_cache.pool.free(slot)
        engine.stop()


def test_brownout_stage2_cap_clamps_ragged_window_midstream(parts):
    """ISSUE 13 satellite: the stage-2 batch max_new_tokens cap clamps a
    multi-step window MID-WINDOW — a batch row 30 tokens into a capped-
    at-32 stream gets a 2-token window, not a full q=4 one (the window
    never dispatches compute the cap will throw away)."""
    import numpy as np

    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, decode_steps=4, scheduler="ragged",
        step_token_budget=64, cache_mode="paged",
        brownout=True, brownout_batch_cap=32, brownout_dwell=120.0,
    )
    try:
        req = GenRequest(
            prompt_ids=[1, 2, 3], max_new_tokens=50, priority="batch"
        )
        req.prompt_len = 3
        req.produced = 30
        engine._slot_req[0] = req
        engine.paged_cache.pool.allocate(0, 32)
        active = np.array([True, False])
        plan = engine._prepare_ragged(active, 0)
        assert plan["row_steps"][0] == 4        # no cap: full window
        engine._brownout.stage = 2
        engine._brownout._changed_at = time.monotonic()
        plan = engine._prepare_ragged(active, 0)
        assert plan["row_steps"][0] == 2        # cap clamps mid-window
    finally:
        engine._slot_req[0] = None
        engine.paged_cache.pool.free(0)
        engine.stop()


def test_brownout_stage2_cap_exact_with_multi_step_chunks(parts):
    """Two-dispatch scheduler: the stage-2 cap landing MID-CHUNK of a
    decode_steps=4 pipelined chunk still delivers exactly the cap (the
    chunk's surplus tokens are dropped at retire) — the multi-token-chunk
    analog of the ragged window clamp."""
    bundle, params = parts

    async def run():
        engine = LLMEngineCore(
            bundle, params, max_batch=2, max_seq_len=128,
            prefill_buckets=[16], eos_token_id=None, decode_steps=4,
            brownout=True, brownout_batch_cap=5, brownout_dwell=120.0,
        )
        engine._brownout.stage = 2
        engine._brownout._changed_at = time.monotonic()
        batch = GenRequest(
            prompt_ids=[1, 2], max_new_tokens=50, priority="batch"
        )
        out_b = await _collect(engine, batch)
        await engine.wait_drained()
        assert len(out_b) == 5, "cap must bite mid-chunk, surplus dropped"
        return engine

    engine = asyncio.run(run())
    engine.stop()


def test_brownout_stage3_still_sets_gate_budget_on_two_dispatch(parts):
    """Legacy two-dispatch engines keep the historical gate hook: the
    stage transition shrinks the per-chunk segment budget to 1 and
    restores the configured value on the way down."""
    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, brownout=True, brownout_dwell=0.0,
        prefill_segments_per_decode=3,
    )
    try:
        gate = engine._prefill_gate
        assert gate is not None and gate._spc == 3
        engine._brownout_checked = 0.0
        engine._brownout.update = lambda *a, **k: 3  # force stage
        engine._brownout.stage = 0
        engine._update_brownout()
        assert gate._spc == 1
        engine._brownout.update = lambda *a, **k: 0
        engine._brownout.stage = 3
        engine._brownout_checked = 0.0
        engine._update_brownout()
        assert gate._spc == 3
    finally:
        engine.stop()


# -- the cache kind decides the scheduler --------------------------------------

_RETENTION_CFG = dict(
    vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
    ffn_dim=96, scan_layers=True, dtype="float32",
    attention="power_retention", retention_degree=2, qk_norm=True,
    norm_eps=1e-6, rope_theta=1e6, max_seq_len=512,
)


@pytest.fixture(scope="module")
def parts_of(parts):
    """(bundle, params) that a cache kind can serve: the softmax model of
    ``parts`` on dense and paged, a power-retention model on state."""
    retention = models.build_model("llama", _RETENTION_CFG)
    state = (retention, retention.init(jax.random.PRNGKey(3)))
    return {"dense": parts, "paged": parts, "state": state}


def _kind_engine(parts_of, cache_mode, **kw):
    bundle, params = parts_of[cache_mode]
    return LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, cache_mode=cache_mode, **kw,
    )


@pytest.mark.parametrize("cache_mode,scheduler", [
    ("dense", "two_dispatch"), ("paged", "ragged"), ("state", "ragged"),
])
def test_cache_kind_decides_the_scheduler(parts_of, cache_mode, scheduler):
    """No ``scheduler`` argument: the engine runs, and reports, the cache
    kind's own; naming that same scheduler changes nothing."""
    for kw in ({}, {"scheduler": scheduler}):
        engine = _kind_engine(parts_of, cache_mode, **kw)
        try:
            assert engine.health()["scheduler"] == scheduler
            assert engine.lifecycle_stats()["scheduler"] == scheduler
            ragged = scheduler == "ragged"
            assert (engine._prefill_gate is None) == ragged
            assert (engine.lifecycle_stats()["ragged"] is not None) == ragged
        finally:
            engine.stop()


@pytest.mark.parametrize("cache_mode,scheduler", [
    ("paged", "two_dispatch"), ("state", "two_dispatch"), ("dense", "ragged"),
])
def test_scheduler_that_contradicts_the_cache_is_refused(
        parts_of, cache_mode, scheduler):
    with pytest.raises(ValueError) as err:
        _kind_engine(parts_of, cache_mode, scheduler=scheduler)
    message = str(err.value)
    assert "cache={} runs the".format(cache_mode) in message
    assert "scheduler={!r} exists only on cache=".format(scheduler) in message


def test_scheduler_environment_variable_is_gone(parts_of, monkeypatch):
    """TPUSERVE_SCHEDULER chose the scheduler once; it is read no more."""
    monkeypatch.setenv("TPUSERVE_SCHEDULER", "two_dispatch")
    engine = _kind_engine(parts_of, "paged")
    try:
        assert engine.health()["scheduler"] == "ragged"
        out = asyncio.run(_collect(
            engine, GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=3)
        ))
        assert len(out) == 3
        assert engine.counters["ragged_steps"] >= 1
    finally:
        engine.stop()
