"""The ragged step keeps ONE launch in flight (docs/ragged_attention.md, "A
launch in flight"): with launch N enqueued, launch N+1 is planned from the
host state as it will stand once N retires, uploaded and enqueued, and only
then is N read back, retired and emitted; the decode rows' tokens stay on the
device between the two. ``pipeline_depth`` is the depth: at 1 the step is the
serial one, through the same function.

(1) a request's token ids at depth 2 are its ids at depth 1: greedy, seeded,
    guided and logprob rows, an EOS found inside a launch, a consumer that
    cancels on a token (what a stop string does);
(2) with a backlog deeper than the budget launches go behind the one in
    flight and starve 0 on an injected clock; with a free slot and a short
    backlog none does (a newcomer could have used the next launch);
(3) a row that ends by a stop token at N rides N+1 once: counted, nothing of
    it emitted, its pages and its state slot back, the sanitizer clean;
(4) a watchdog trip, and a worker's fault, with two launches outstanding
    recover both, armed from inside the engine's own step on a clock that
    stands still;
(5) a verify row keeps the step serial."""

import asyncio
import itertools
import threading
import time
import types

import jax
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.errors import EngineStepError, EngineStuckError
from clearml_serving_tpu.llm import engine as engine_mod
from clearml_serving_tpu.llm import faults
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.llm.guided import GuidedSpec
from clearml_serving_tpu.llm.tokenizer import ByteTokenizer

from test_falcon_h1_model import TINY as HYBRID_CFG

TOK = ByteTokenizer(512)
STATE_CFG = dict(vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                 head_dim=16, ffn_dim=96, scan_layers=True, dtype="float32",
                 attention="power_retention", retention_degree=2, qk_norm=True,
                 norm_eps=1e-6, rope_theta=1e6, max_seq_len=512)
KINDS = {
    "paged": ("llama", {"preset": "llama-tiny", "dtype": "float32"},
              dict(cache_mode="paged", page_size=8, num_pages=128)),
    "state": ("llama", STATE_CFG, dict(cache_mode="state")),
    # pages AND a slot of row state for every row (docs/hybrid_cache.md)
    "hybrid": ("falcon_h1", dict(HYBRID_CFG, vocab_size=512),
               dict(cache_mode="paged", page_size=8, num_pages=128,
                    prefix_cache=0)),
}


@pytest.fixture(scope="module")
def parts():
    built = {}

    def of(kind):
        if kind not in built:
            arch, cfg, _ = KINDS[kind]
            bundle = models.build_model(arch, cfg)
            built[kind] = bundle, bundle.init(jax.random.PRNGKey(2))
        return built[kind]

    return of


@pytest.fixture(params=list(KINDS))
def kind(request):
    return request.param


@pytest.fixture(autouse=True)
def _armed(monkeypatch):
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    yield
    faults.clear()


def _engine(kind, parts, depth, **kw):
    args = dict(max_batch=3, max_seq_len=256, eos_token_id=TOK.eos_token_id,
                tokenizer=TOK, decode_steps=4, step_token_budget=16,
                pipeline_depth=depth)
    args.update(KINDS[kind][2])
    args.update(kw)
    return LLMEngineCore(*parts(kind), **args)


def _ids(seed, n):
    return [TOK.bos_token_id] + [(seed * 31 + i * 7) % 90 + 33 for i in range(n)]


async def _collect(engine, req, cancel_at=None):
    out = []
    async for tok in engine.generate(req):
        out.append(tok)
        if cancel_at is not None and len(out) == cancel_at:
            req.cancel()               # a stop string matched in the text
            break
    return out


def _clean(engine):
    """Nothing is owned after the drain, and the armed sanitizer agrees."""
    stats = engine.lifecycle_stats()
    if engine.paged_cache is not None:
        pool = engine.paged_cache.pool
        assert pool.free_pages == pool.num_pages - 1       # the null page
        assert engine._sanitizer.stats()["failures"] == 0
        assert engine._sanitizer.stats()["checks"] > 0
    if engine.state_cache is not None:
        assert stats["state_pool"]["in_use"] == 0
    assert not engine._quarantine and not engine._ragged_flights
    return stats


# -- (1) depth 2 serves what depth 1 serves ------------------------------------


BIAS = {65 + i: 2.0 - 0.1 * i for i in range(12)}
TRAFFIC = [
    ("greedy_long", _ids(3, 70), 10, {}),
    ("logprobs", _ids(4, 33), 9, dict(logprobs=3)),
    ("seeded", _ids(5, 40), 9, dict(temperature=0.9, top_k=20, seed=11)),
    ("penalties", _ids(6, 25), 9,
     dict(logit_bias=BIAS, min_tokens=3, presence_penalty=0.5, logprobs=1)),
    ("guided", TOK.encode("Q:"), 12,
     dict(temperature=0.8, seed=5, guided=GuidedSpec("regex", "(yes|no|maybe)"))),
    ("second_long", _ids(8, 61), 7, {}),
]


def _serve(engine, stop_of=None):
    """The whole traffic at once on three rows, so prompts wait behind
    prompts; ``stop_of`` gives two more requests an ending only the device
    finds: a stop token in the middle of an answer, and a consumer that
    cancels at a token."""

    async def run():
        jobs = [
            _collect(engine, GenRequest(prompt_ids=list(ids), max_new_tokens=n, **kw))
            for _name, ids, n, kw in TRAFFIC
        ]
        if stop_of is not None:
            jobs.append(_collect(engine, GenRequest(
                prompt_ids=_ids(9, 30), max_new_tokens=40,
                stop_token_ids=[stop_of])))
            jobs.append(_collect(engine, GenRequest(
                prompt_ids=_ids(9, 30), max_new_tokens=40), cancel_at=5))
        outs = await asyncio.gather(*jobs)
        await engine.wait_drained()
        return outs

    return asyncio.run(run())


@pytest.mark.parametrize("kind", ["paged", "state", "hybrid"])
def test_a_stream_at_depth_2_is_the_stream_at_depth_1(kind, parts):
    # ONE engine, its depth moved between the passes (the step reads it as
    # it runs): the programs compile once
    engine = _engine(kind, parts, 1)
    try:
        free = asyncio.run(_collect(engine, GenRequest(
            prompt_ids=_ids(9, 30), max_new_tokens=40)))
        # a token the free answer reaches in its middle, and not before
        at = next(i for i in range(6, len(free)) if free[i] not in free[:i])
        want = _serve(engine, stop_of=free[at])
        serial = _clean(engine)["ragged"]
        assert serial["launches_behind"] == serial["surplus_rows"] == 0
        engine.pipeline_depth = 2
        got = _serve(engine, stop_of=free[at])
        ragged = _clean(engine)["ragged"]
    finally:
        engine.stop()
    assert want[-2] == free[: at + 1] and want[-1] == free[:5]
    assert got == want
    assert TOK.decode(got[4]) in ("yes", "no", "maybe")
    steps = ragged["steps"] - serial["steps"]
    assert 0 < ragged["launches_behind"] < steps
    assert ragged["first_tokens"] == 2 * len(want) + 1


# -- (2) when it engages, and what the starved chip reads ----------------------


TICK_MS = 0.01


@pytest.fixture
def counted_clock(monkeypatch):
    """Every read of the timeline's clock is one tick after the one before,
    whichever thread asks: a starve is arithmetic on the order of the reads."""
    reads = itertools.count(1)
    monkeypatch.setattr(engine_mod, "_clock",
                        lambda: next(reads) * TICK_MS * 1e-3)


class Launches:
    """Each ragged launch as it lands: its seq, whether it went behind the
    one in flight, and the starve the timeline read for it (None: the first
    launch since a park)."""

    def __init__(self, engine):
        self.seen, self.behind = [], {}
        dispatch, landed = engine._dispatch_ragged_device, engine._cycle.landed
        starve = engine._cycle.starve

        def spy(plan):
            self.behind[plan["seq"]] = plan["behind"]
            return dispatch(plan)

        def on_landed(seq, launch_at, stamps, now):
            before = starve.n, starve.total_ms
            landed(seq, launch_at, stamps, now)
            self.seen.append((seq, None if starve.n == before[0]
                              else starve.total_ms - before[1]))

        engine._dispatch_ragged_device, engine._cycle.landed = spy, on_landed


def test_a_backlog_deeper_than_the_budget_starves_nothing(kind, parts, counted_clock):
    """Three prompts of several budgets each on three rows: the next launch
    is spoken for, so it goes behind the one in flight, and the timeline
    reads 0 for it; the serial launches between read what they always did."""
    engine = _engine(kind, parts, 2)
    tape = Launches(engine)

    async def run():
        await asyncio.gather(*(
            _collect(engine, GenRequest(prompt_ids=_ids(s, 90), max_new_tokens=6))
            for s in (1, 2, 3)))
        await engine.wait_drained()

    try:
        asyncio.run(run())
        stats = _clean(engine)
    finally:
        engine.stop()
    behind = [s for seq, s in tape.seen if tape.behind.get(seq)]
    serial = [s for seq, s in tape.seen
              if tape.behind.get(seq) is False and s is not None]
    assert len(behind) >= 10 and all(s == 0.0 for s in behind)
    assert all(s > 0.0 for s in serial)
    ragged = stats["ragged"]
    assert ragged["launches_behind"] == len(behind)
    assert ragged["launches_behind"] > 0.6 * ragged["steps"]
    assert stats["pipeline"]["starve_ms"]["count"] >= len(behind)


def test_a_newcomer_could_use_the_next_launch_so_none_goes_ahead(kind, parts):
    """One prompt a little over one budget with rows to spare: what the
    first launch leaves is less than the next one's budget, and a slot is
    free, so a request arriving now would ride the next launch: the step
    stays serial, launch after launch."""
    engine = _engine(kind, parts, 2)

    async def run():
        out = await _collect(
            engine, GenRequest(prompt_ids=_ids(4, 19), max_new_tokens=12))
        await engine.wait_drained()
        return out

    try:
        assert len(asyncio.run(run())) == 12
        stats = _clean(engine)
    finally:
        engine.stop()
    assert stats["ragged"]["steps"] >= 2
    assert stats["ragged"]["launches_behind"] == 0
    assert stats["ragged"]["surplus_rows"] == 0


# -- (3) the surplus step -------------------------------------------------------


def test_a_row_that_ends_on_the_device_rides_one_launch_more(kind, parts):
    """A decodes beside B's long prompt. Inside the engine's own step, as a
    launch is planned BEHIND one that carries A, A is given the token that
    launch in flight is about to sample as its stop token: the host learns
    of the ending at that launch's retire, when the next already carries A.
    Nothing of the surplus step is emitted, it is counted, A's slot leaves
    quarantine when the surplus launch retires, and B is served whole."""
    prompt_a, prompt_b = _ids(9, 30), _ids(7, 150)
    engine = _engine(kind, parts, 1)
    try:
        free = asyncio.run(_collect(engine, GenRequest(
            prompt_ids=list(prompt_a), max_new_tokens=60)))
        want_b = asyncio.run(_collect(engine, GenRequest(
            prompt_ids=list(prompt_b), max_new_tokens=5)))
    except BaseException:
        engine.stop()
        raise
    engine.pipeline_depth = 2           # the step reads it as it runs
    a = GenRequest(prompt_ids=list(prompt_a), max_new_tokens=60)
    b = GenRequest(prompt_ids=list(prompt_b), max_new_tokens=5)
    prepare, armed = engine._prepare_ragged, []

    def arm(mask, epoch):
        plan = prepare(mask, epoch)
        if (plan is not None and plan["behind"] and not armed
                and a in plan["row_req"] and a.produced >= 2):
            # the launch in flight samples A's token number produced + 1
            armed.append(a.produced)
            a.stop_token_ids = [free[a.produced]]
        return plan

    engine._prepare_ragged = arm

    async def run():
        a_task = asyncio.create_task(_collect(engine, a))
        while a.produced < 1:
            await asyncio.sleep(0.001)
        got_b = await _collect(engine, b)
        got_a = await a_task
        await engine.wait_drained()
        return got_a, got_b

    try:
        got_a, got_b = asyncio.run(run())
        stats = _clean(engine)
    finally:
        engine.stop()
    assert armed and got_a == free[: armed[0] + 1]
    assert got_b == want_b
    assert stats["ragged"]["surplus_rows"] == 1
    assert stats["ragged"]["launches_behind"] >= 5


# -- (4) two launches outstanding, and something breaks ------------------------


def _still(monkeypatch):
    """The engine's ``time.monotonic`` stands still until the test moves it,
    and a fault's delay lasts until the test releases it."""
    now = [time.monotonic()]
    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
        monotonic=lambda: now[0], perf_counter=time.perf_counter,
        time=time.time))
    stalled, release = threading.Event(), threading.Event()

    def stall(seconds):
        stalled.set()
        release.wait(seconds)       # the bound is a net, not a measurement

    monkeypatch.setattr(faults, "time", types.SimpleNamespace(sleep=stall))
    return now, stalled, release


@pytest.mark.parametrize("kind", ["paged", "state", "hybrid"])
def test_a_watchdog_trip_with_two_launches_out_recovers_both(
        kind, parts, monkeypatch):
    """A victim decodes beside a survivor's long prompt. The stall is armed
    as a launch is planned behind the one in flight, and bites that older
    launch's read: two launches are out when the watchdog trips. The victim
    fails; the recovery waits the younger launch out and takes the
    survivor's chunks of BOTH back (pages to what the older launch found,
    a state to its start); the survivor then finishes with the tokens of an
    undisturbed run and the engine serves on."""
    survivor = _ids(5, 120)
    kw = dict(max_batch=2, eos_token_id=None, watchdog_interval=1.0)
    engine = _engine(kind, parts, 2, **kw)
    try:
        want = asyncio.run(_collect(engine, GenRequest(
            prompt_ids=list(survivor), max_new_tokens=6)))
    except BaseException:
        engine.stop()
        raise
    now, stalled, release = _still(monkeypatch)
    prepare, recover = engine._prepare_ragged, engine._ragged_recover
    out, rolled = [], []

    def arm(mask, epoch):
        plan = prepare(mask, epoch)
        if (plan is not None and plan["behind"] and plan["decode_mask"].any()
                and plan["shares"] and not faults.active() and not out):
            faults.configure([
                {"point": "engine.decode.stall", "action": "delay",
                 "delay": 120.0, "times": 1},
            ])
        return plan

    async def recovered(plan):
        out.extend(f.seq for f in engine._ragged_flights)
        job, _take = plan["shares"][0]
        pool = engine.paged_cache.pool if engine.paged_cache is not None else None
        await recover(plan)
        rolled.append((
            int(plan["pre_lens"][job.slot]), job.pos,
            pool.slot_length(job.slot) if pool is not None else None,
        ))

    engine._prepare_ragged, engine._ragged_recover = arm, recovered

    async def run():
        a = GenRequest(prompt_ids=[256, 4, 5], max_new_tokens=200)
        a_task = asyncio.create_task(_collect(engine, a))
        while a.produced < 2:
            await asyncio.sleep(0.002)
        b = GenRequest(prompt_ids=list(survivor), max_new_tokens=6)
        b_task = asyncio.create_task(_collect(engine, b))
        while not stalled.is_set():
            await asyncio.sleep(0.002)
        now[0] += 10.0                      # ten intervals without progress
        while engine.counters["watchdog_trips"] < 1:
            await asyncio.sleep(0.002)
        with pytest.raises(EngineStuckError):
            await a_task
        assert not b_task.done() and not engine.health()["ready"]
        release.set()
        assert await b_task == want
        await engine.wait_drained()

    try:
        asyncio.run(run())
        _clean(engine)
        assert engine.counters["watchdog_trips"] == 1 and engine.is_ready
    finally:
        release.set()
        engine.stop()
    # the older launch and the one behind it
    assert len(out) == 2 and out[1] == out[0] + 1
    pre, pos, length = rolled[0]
    if engine.state_cache is not None:
        assert pos == 0 and length in (None, 0)         # a state starts again
    else:
        assert pos == pre == length                     # the OLDER launch's


def test_a_worker_fault_with_a_launch_out_fails_the_step_not_the_engine(
        kind, parts):
    """The launch behind the one in flight (a decode row beside a prompt's
    chunk in both) raises in its worker, before any device work: the step
    fails as a whole (both requests with a structured error), the launch
    that was out is waited for and dropped, nothing leaks, and the engine
    serves the next request."""
    engine = _engine(kind, parts, 2)
    prepare, seen = engine._prepare_ragged, []

    def arm(mask, epoch):
        plan = prepare(mask, epoch)
        if (plan is not None and plan["behind"] and not seen
                and plan["decode_mask"].any() and plan["shares"]):
            seen.append(len(engine._ragged_flights))
            faults.configure([
                {"point": "engine.decode", "action": "raise", "times": 1},
            ])
        return plan

    engine._prepare_ragged = arm

    async def run():
        outs = await asyncio.gather(
            _collect(engine, GenRequest(prompt_ids=_ids(1, 20), max_new_tokens=60)),
            _collect(engine, GenRequest(prompt_ids=_ids(2, 120), max_new_tokens=4)),
            return_exceptions=True)
        await engine.wait_drained()
        faults.clear()
        again = await _collect(
            engine, GenRequest(prompt_ids=_ids(3, 20), max_new_tokens=4))
        await engine.wait_drained()
        return outs, again

    try:
        outs, again = asyncio.run(run())
        stats = _clean(engine)
    finally:
        engine.stop()
    assert seen == [1]
    assert all(isinstance(o, EngineStepError) for o in outs)
    assert len(again) == 4 and stats["step_failures"] == 1


# -- (5) a verify row keeps the step serial -------------------------------------


def test_a_verify_row_keeps_the_step_serial(parts):
    """Drafts come from the host's token history and the accepted length is
    the device's: with speculation on no launch is planned ahead, however
    deep the backlog."""
    engine = _engine("paged", parts, 2, speculation="ngram", spec_k=2,
                     spec_ngram=2)
    repeat = [TOK.bos_token_id] + [65, 66, 67, 68] * 6

    async def run():
        outs = await asyncio.gather(
            _collect(engine, GenRequest(prompt_ids=repeat, max_new_tokens=24)),
            _collect(engine, GenRequest(prompt_ids=_ids(2, 120), max_new_tokens=4)))
        await engine.wait_drained()
        return outs

    try:
        outs = asyncio.run(run())
        stats = _clean(engine)
    finally:
        engine.stop()
    assert [len(o) for o in outs] == [24, 4]
    assert stats["ragged"]["step_rows"]["spec_verify"] >= 1
    assert stats["ragged"]["launches_behind"] == 0
