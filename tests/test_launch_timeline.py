"""The launch timeline (docs/pipelined_decode.md "Observability"): the dispatch
worker's four stamps come back with its result, the loop thread cuts the
``launch`` phase into five parts that add up to it, takes ``ready`` from the
retire's first device-to-host copy, and observes per launch the stretch in
which the chip had nothing queued (``starve_ms``); a request's ``prefill_ms``
is cut on the same timeline into three stretches that add up to it. Both of
a ragged step's waits are a worker's (the dispatch, then the read of its
results), so the streams an emission wakes are served inside the NEXT launch,
after its ``enqueued`` read and before its ``ready`` read."""

import asyncio
import itertools
import threading

import jax
import numpy as np
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.errors import EngineOverloadedError
from clearml_serving_tpu.llm import engine as engine_mod
from clearml_serving_tpu.llm import faults
from clearml_serving_tpu.llm.engine import (
    GenRequest,
    LLMEngineCore,
    _CycleClock,
)

PARTS = ("hop_out_ms", "upload_ms", "enqueue_ms", "tail_ms", "hop_back_ms")
STRETCHES = ("first_launch_wait_ms", "prefill_span_ms", "first_emit_ms")
PROMPTS = [[90] + [(7 * i + 3 * j) % 80 + 3 for j in range(40)] for i in range(4)]
STATE_CFG = dict(vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                 head_dim=16, ffn_dim=96, scan_layers=True, dtype="float32",
                 attention="power_retention", retention_degree=2, qk_norm=True,
                 norm_eps=1e-6, rope_theta=1e6, max_seq_len=512)
KINDS = {
    "paged": ({"preset": "llama-tiny", "dtype": "float32"},
              dict(cache_mode="paged", page_size=8, num_pages=64)),
    "state": (STATE_CFG, dict(cache_mode="state")),
}


@pytest.fixture(scope="module", params=list(KINDS))
def kind(request):
    cfg, kw = KINDS[request.param]
    bundle = models.build_model("llama", cfg)
    return bundle, bundle.init(jax.random.PRNGKey(0)), kw


def _engine(kind, **kw):
    bundle, params, cache = kind
    args = dict(max_batch=2, max_seq_len=128, eos_token_id=None, decode_steps=4,
                scheduler="ragged", step_token_budget=16, **cache)
    args.update(kw)
    return LLMEngineCore(bundle, params, **args)


def _run(engine, prompts, n=10, **req_kw):
    async def go():
        reqs = [GenRequest(prompt_ids=list(p), max_new_tokens=n, **req_kw)
                for p in prompts]

        async def one(req):
            return [t async for t in engine.generate(req)]

        await asyncio.gather(*(one(r) for r in reqs))
        await engine.wait_drained()
        return reqs

    return asyncio.run(go())


class Tape:
    """What the clock was told, in order: every ``landed`` with its parts and
    the starve it observed (None where it observed none), every ``park``,
    every cycle's six phases as ``top`` observed them, every ``ready``; and
    the thread of every call into the clock."""

    def __init__(self, engine):
        clock = self.clock = engine._cycle
        self.events, self.cycles, self.readbacks, self.threads = [], [], [], set()
        for name in ("top", "mark", "park", "landed", "ready"):
            setattr(clock, name, self._wrap(name, getattr(clock, name)))

    def _wrap(self, name, fn):
        clock = self.clock

        def call(*args, **kw):
            self.threads.add(threading.get_ident())
            before = {h: h.n for h in (clock.starve, clock.cycle, clock.readback)}
            out = fn(*args, **kw)
            if name == "park":
                self.events.append({"park": True})
            if name == "landed":
                seq, launch_at, stamps, now = args
                edges = (launch_at, *stamps, now)
                self.events.append({
                    "seq": seq, "stamps": stamps,
                    "parts": [(b - a) * 1e3 for a, b in zip(edges, edges[1:])],
                    "starve": (clock.starve.last
                               if clock.starve.n > before[clock.starve] else None),
                })
            if clock.cycle.n > before[clock.cycle]:
                self.cycles.append({p: h.last for p, h in clock.phases.items()})
            if clock.readback.n > before[clock.readback]:
                self.readbacks.append(clock.readback.last)
            return out

        return call

    @property
    def launches(self):
        return [e for e in self.events if "seq" in e]


@pytest.fixture(autouse=True)
def keep_last(monkeypatch):
    """A histogram remembers its last observation, for the tape."""
    observe = engine_mod._MsHistogram.observe

    def remember(self, ms):
        self.last = ms
        observe(self, ms)

    monkeypatch.setattr(engine_mod._MsHistogram, "observe", remember)


def _ragged_spy(engine):
    """The seqs of the launches that were ragged steps, as they are made."""
    ragged, dispatch = [], engine._dispatch_ragged_device

    def spy(plan):
        ragged.append(plan["seq"])
        return dispatch(plan)

    engine._dispatch_ragged_device = spy
    return ragged


def _iterations(engine, monkeypatch):
    """One list a loop iteration (opened at the clock's ``top``), which takes
    a "sleep0" where the iteration hands the event loop over with
    ``asyncio.sleep(0)`` and whatever the test's own spies append."""
    iterations = []
    real_sleep, top = asyncio.sleep, engine._cycle.top

    async def sleep(delay, *args, **kw):
        if delay == 0 and iterations:
            iterations[-1].append("sleep0")
        return await real_sleep(delay, *args, **kw)

    def on_top(seq):
        iterations.append([])
        return top(seq)

    monkeypatch.setattr(asyncio, "sleep", sleep)
    engine._cycle.top = on_top
    return iterations


# -- the serial step: five parts = launch, seven stretches = starve -----------


TICK_MS = 0.01


@pytest.fixture
def counted_clock(monkeypatch):
    """The timeline's clock, injected: every read is one tick after the one
    before, whichever thread asks, so the sums below are arithmetic on the
    order of the reads and a loaded machine cannot stretch a part."""
    reads = itertools.count(1)
    monkeypatch.setattr(engine_mod, "_clock",
                        lambda: next(reads) * TICK_MS * 1e-3)


def test_parts_add_up_to_launch_and_stretches_to_starve(kind, counted_clock):
    """Depth 1: every cycle is one serial launch (a ragged step, or on pages
    a decode chunk once the prompts are in), so cycle k is launch k. A ragged
    step's ``launch`` still ends at the dispatch worker's hop back (the loop
    stood still until the ``enqueued`` stamp inside it); the read of the
    results is a second worker's, under ``wait``, and its ``ready`` read
    comes back with the copies."""
    engine = _engine(kind, pipeline_depth=1)
    ragged = _ragged_spy(engine)
    tape = Tape(engine)
    _run(engine, PROMPTS[:3])
    launches = tape.launches
    assert len(launches) == len(tape.cycles) == len(tape.readbacks) >= 6
    assert len(ragged) >= 4
    starved = 0
    for k, (launch, cycle) in enumerate(zip(launches, tape.cycles)):
        assert all(p >= 0 for p in launch["parts"])
        if launch["seq"] in ragged:
            # shared clock reads: equal to rounding
            assert sum(launch["parts"]) == pytest.approx(cycle["launch"], abs=1e-6)
        else:
            # a chunk's entry is queued between the hop back and the wait:
            # the loop's read at the landing, then the read that opens
            # ``wait`` (a few more if a request was stamped in between)
            queued = cycle["launch"] - sum(launch["parts"])
            assert TICK_MS - 1e-6 <= queued <= 8 * TICK_MS + 1e-6
        if launch["starve"] is None:
            continue
        starved += 1
        before = tape.cycles[k - 1]
        hop_out, upload = launch["parts"][:2]
        stretches = (tape.readbacks[k - 1], before["emit"], before["yield"],
                     cycle["admin"], cycle["plan"], hop_out, upload)
        assert launch["starve"] == pytest.approx(sum(stretches), abs=1e-6)
        assert launch["starve"] <= cycle["admin"] + cycle["plan"] + (
            cycle["launch"] + before["wait"] + before["emit"] + before["yield"])
    assert starved >= 4
    if kind[2]["cache_mode"] == "state":
        assert len(ragged) == len(launches)      # the state cache has ONE step
    engine.stop()


# -- the streams are served inside the next launch -----------------------------


def test_a_woken_stream_runs_inside_the_next_launch(kind, counted_clock,
                                                    monkeypatch):
    """One long answer decodes while three prompts take the other slot in
    turn, so ragged steps with a decode row follow one another. A consumer
    reads the injected clock for every token it is handed: between the first
    copy of launch N (its emission follows) and launch N+1's ``enqueued``
    read no consumer runs, although N woke one; it runs before N+1's
    ``ready`` read."""
    # a first-use compile inside the dispatch must not release the loop early
    monkeypatch.setattr(engine_mod, "_ENQUEUED_WAIT_S", 600.0)
    engine = _engine(kind, pipeline_depth=1)
    ragged = _ragged_spy(engine)
    tape = Tape(engine)
    readies = {}
    ready = engine._cycle.ready

    def on_ready(seq, at=None):
        readies[seq] = ready(seq, at)
        return readies[seq]

    engine._cycle.ready = on_ready
    handed = []

    async def go():
        async def one(prompt, n):
            req = GenRequest(prompt_ids=list(prompt), max_new_tokens=n)
            async for _ in engine.generate(req):
                handed.append(engine_mod._clock())

        await asyncio.gather(*(one(p, n) for p, n in zip(PROMPTS, (48, 6, 6, 6))))
        await engine.wait_drained()

    asyncio.run(go())
    stamps = {e["seq"]: e["stamps"] for e in tape.launches}
    order = [e["seq"] for e in tape.launches]
    served = pairs = 0
    for before, seq in zip(order, order[1:]):
        if before not in ragged or seq not in ragged:
            continue
        pairs += 1
        enqueued = stamps[seq][2]
        # the loop stood still from the last emission to this stamp ...
        assert not [t for t in handed if readies[before] < t < enqueued]
        # ... and what that emission woke ran while this launch did
        served += bool([t for t in handed if enqueued < t < readies[seq]])
    assert pairs >= 8 and served >= 6
    stats = engine.lifecycle_stats()["ragged"]
    assert 0 < stats["waits_off_loop"] <= stats["steps"] == len(ragged)
    engine.stop()


def test_a_step_without_a_plan_still_yields(kind, monkeypatch):
    """An iteration whose ragged step launched nothing hands the event loop
    over with ``sleep(0)`` (the loop cannot spin); one that awaited its
    worker does not (the handlers ran inside its launch)."""
    engine = _engine(kind, pipeline_depth=1)
    iterations, prepare = _iterations(engine, monkeypatch), engine._prepare_ragged

    def on_prepare(mask, epoch):
        plan = prepare(mask, epoch)
        iterations[-1].append("none" if plan is None else "plan")
        return plan

    engine._prepare_ragged = on_prepare
    # the lone job is shed as it asks for budget: nothing is left to launch
    faults.configure([{"point": "engine.admit.budget", "action": "raise",
                       "times": 1}])
    try:
        with pytest.raises(EngineOverloadedError):
            _run(engine, PROMPTS[:1])
    finally:
        faults.clear()
    _run(engine, PROMPTS[1:3])
    assert ["none", "sleep0"] in iterations
    planned = [it for it in iterations if "plan" in it]
    assert len(planned) >= 4 and all(it == ["plan"] for it in planned)
    engine.stop()


@pytest.mark.parametrize("armed", [False, True])
def test_a_drain_that_awaited_its_worker_does_not_yield_again(armed, monkeypatch):
    """Pages, depth 2: a prompt that arrives while chunks are in flight makes
    the loop retire them first; such a retire is serial (nothing is queued
    behind it), so where its readback awaited a worker the iteration ends
    without ``sleep(0)``, and where the chunk had already landed it sleeps.
    With any fault armed no readback takes the short cut: every drain awaits."""
    cfg, cache = KINDS["paged"]
    bundle = models.build_model("llama", cfg)
    engine = LLMEngineCore(
        bundle, bundle.init(jax.random.PRNGKey(0)), max_batch=2, max_seq_len=128,
        eos_token_id=None, decode_steps=4, step_token_budget=16,
        pipeline_depth=2, **cache)
    iterations, retire = _iterations(engine, monkeypatch), engine._retire_oldest

    async def on_retire():
        drains = bool(engine._prefill_jobs)     # called from the ragged phase
        awaited = await retire()
        if drains:
            iterations[-1].append(("drain", awaited))
        return awaited

    engine._retire_oldest = on_retire

    async def go():
        async def one(prompt, n):
            req = GenRequest(prompt_ids=list(prompt), max_new_tokens=n)
            return [t async for t in engine.generate(req)]

        await asyncio.gather(*(one(p, n) for p, n in zip(PROMPTS, (64, 6, 6, 6))))
        await engine.wait_drained()

    if armed:       # a seam that fires once the engine has drained: inert here
        faults.configure([{"point": "engine.drain", "action": "delay"}])
    try:
        asyncio.run(go())
    finally:
        faults.clear()
    drained = [it for it in iterations if any(isinstance(e, tuple) for e in it)]
    assert len(drained) >= 2
    for it in drained:
        (_, awaited), = [e for e in it if isinstance(e, tuple)]
        assert ("sleep0" in it) == (not awaited)
        assert awaited or not armed
    engine.stop()


class _Result:
    """A stand-in for a launch's result on the device: says whether it has
    landed and notes the thread that copies it back."""

    def __init__(self, name, landed, log):
        self.name, self.landed, self.log = name, landed, log

    def is_ready(self):
        return self.landed

    def copy_to_host_async(self):
        self.log.append(("ask " + self.name, threading.get_ident()))

    def __array__(self, dtype=None, copy=None):
        self.log.append((self.name, threading.get_ident()))
        return np.zeros(2, np.int32)


@pytest.mark.parametrize("landed", [False, True])
def test_the_readback_leaves_the_loop_thread_unless_the_launch_has_landed(
        kind, landed):
    """One readback for both steps: a worker waits for the first result and
    copies the rest where the launch is still out (the loop awaits it), the
    loop thread copies itself where it has already landed; every copy is
    asked for (``copy_to_host_async``) on the loop thread before the first is
    waited for, so they overlap behind the launch; the pytree of the rest
    comes back in its shape, None where nothing was asked for."""
    engine = _engine(kind)
    log = []
    first = _Result("first", landed, log)
    rest = {"lp": (_Result("a", landed, log), _Result("b", landed, log)),
            "gstate": None}

    async def go():
        engine._cycle.top(1)
        return await engine._read_back(1, first, rest)

    head, tail, ready_at, awaited = asyncio.run(go())
    assert awaited is (not landed)
    assert [name for name, _ in log] == [
        "ask first", "ask a", "ask b", "first", "a", "b"]
    assert {thread for _, thread in log[:3]} == {threading.get_ident()}
    threads = {thread for _, thread in log[3:]}
    assert len(threads) == 1
    assert (threads == {threading.get_ident()}) is landed
    assert isinstance(head, np.ndarray) and tail["gstate"] is None
    assert [type(x) for x in tail["lp"]] == [np.ndarray, np.ndarray]
    assert engine._cycle._ready == (1, ready_at)
    engine.stop()


# -- counts after a drain; a launch after a park is not starved ---------------


@pytest.mark.parametrize("depth", [1, 2])
def test_every_histogram_counts_the_launches(kind, depth):
    engine = _engine(kind, pipeline_depth=depth)
    tape = Tape(engine)
    _run(engine, PROMPTS[:2])
    first = len(tape.launches)
    assert tape.events[-1] == {"park": True}        # drained: the loop left
    _run(engine, PROMPTS[2:], n=6)                  # restarts after the park
    pipe = engine.lifecycle_stats()["pipeline"]
    launches = pipe["dispatch_ms"]["count"]
    assert launches == len(tape.launches) > first >= 3
    assert set(pipe["launch_parts"]) == set(PARTS)
    assert all(pipe["launch_parts"][p]["count"] == launches for p in PARTS)
    assert pipe["readback_ms"]["count"] == pipe["cycle_ms"]["count"] == launches
    # a launch observes a starve unless a park (or nothing) lies before it
    after_park = sum(
        1 for prev, ev in zip([{"park": True}] + tape.events, tape.events)
        if "seq" in ev and "park" in prev)
    assert after_park >= 2
    assert pipe["starve_ms"]["count"] == launches - after_park
    assert tape.launches[first]["starve"] is None
    assert engine.health()["pipeline"]["starve_ms"] == pipe["starve_ms"]
    # the worker parts are dispatch_ms, cut: one measurement, not two
    worker = sum(pipe["launch_parts"][p]["sum_ms"]
                 for p in ("upload_ms", "enqueue_ms", "tail_ms"))
    assert worker == pytest.approx(pipe["dispatch_ms"]["sum_ms"], abs=1e-6)
    for launch in tape.launches:
        assert all(p >= 0 for p in launch["parts"])
        assert launch["starve"] is None or launch["starve"] >= 0
    engine.stop()


# -- the stamps come back with the result; the clock is the loop thread's -----


def test_the_worker_stamps_and_the_loop_thread_accounts(kind):
    engine = _engine(kind, pipeline_depth=2)
    calls = []
    dispatch = engine._dispatch_ragged_device

    def spy(plan):
        result = dispatch(plan)
        calls.append((threading.get_ident(), plan["seq"], result["stamps"]))
        return result

    engine._dispatch_ragged_device = spy
    tape = Tape(engine)
    _run(engine, PROMPTS[:2])
    loop_thread = threading.get_ident()             # asyncio.run ran here
    assert tape.threads == {loop_thread}
    assert calls and all(thread != loop_thread for thread, _, _ in calls)
    landed = {e["seq"]: e["stamps"] for e in tape.launches}
    for _, seq, stamps in calls:
        assert isinstance(stamps, tuple) and len(stamps) == 4
        assert list(stamps) == sorted(stamps)
        assert landed[seq] is stamps
    engine.stop()


# -- a request's prefill on the launch timeline --------------------------------


def test_request_stretches_add_up_to_prefill(kind):
    engine = _engine(kind)
    reqs = _run(engine, PROMPTS[:3])
    stats = engine.lifecycle_stats()["requests"]
    assert all(stats[s]["count"] == stats["prefill_ms"]["count"] == 3
               for s in STRETCHES)
    assert sum(stats[s]["sum_ms"] for s in STRETCHES) == pytest.approx(
        stats["prefill_ms"]["sum_ms"], abs=1e-6)
    for r in reqs:
        assert r._prefill_launches >= 3              # 41 tokens, 16 a launch
        assert r._job_at <= r._enqueue_at < r._ready_at
    engine.stop()


def test_a_preempted_request_observes_its_stretches_once():
    bundle = models.build_model("llama", {"preset": "llama-tiny", "dtype": "float32"})
    engine = LLMEngineCore(
        bundle, bundle.init(jax.random.PRNGKey(0)), max_batch=1, max_seq_len=128,
        eos_token_id=None, decode_steps=2, cache_mode="paged", page_size=16,
        prefix_cache=64, prefix_block=16, preempt_batch=True, preempt_budget=2)

    async def go():
        batch = GenRequest(prompt_ids=[(i * 7 + 3) % 250 + 1 for i in range(17)],
                           max_new_tokens=24, priority="batch")

        async def collect(req):
            return [t async for t in engine.generate(req)]

        task = asyncio.create_task(collect(batch))
        while batch.produced < 6:
            await asyncio.sleep(0.005)
        await asyncio.wait_for(
            collect(GenRequest(prompt_ids=[1, 9, 9], max_new_tokens=2)), 60)
        await asyncio.wait_for(task, 60)
        await engine.wait_drained()
        return batch

    batch = asyncio.run(go())
    assert engine.counters["preemptions"] >= 1
    assert batch._prefill_launches >= 2              # the resume leg rode one too
    stats = engine.lifecycle_stats()["requests"]
    assert stats["queue_wait_ms"]["count"] == 2 + engine.counters["preemptions"]
    assert all(stats[s]["count"] == stats["ttft_ms"]["count"] == 2
               for s in STRETCHES)
    assert sum(stats[s]["sum_ms"] for s in STRETCHES) == pytest.approx(
        stats["prefill_ms"]["sum_ms"], abs=1e-6)
    engine.stop()


# -- the clock alone, on a clock the test turns --------------------------------


@pytest.fixture
def turned(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(engine_mod, "_clock", lambda: now[0])

    def at(t):
        now[0] = 100.0 + t
        return now[0]

    return at


def _launch(clock, at, seq, t, copy=0.002):
    """One serial launch that starts at ``t``: 1 ms a stretch, the device done
    10 ms after the enqueue, ``copy`` s of readback."""
    clock.top(seq)
    at(t)
    clock.mark("plan", seq)
    launch_at = clock.mark("launch", seq)
    stamps = tuple(at(t + ms / 1e3) for ms in (1, 2, 3, 4))
    at(t + 0.005)
    clock.landed(seq, launch_at, stamps, clock.mark("wait", seq))
    at(t + 0.012)
    clock.ready(seq)
    at(t + 0.012 + copy)
    clock.mark("emit", seq)
    at(t + 0.020)
    clock.mark("yield", seq)
    return stamps


def test_starve_is_enqueue_less_the_last_ready_and_a_park_forgets(turned):
    clock = _CycleClock()
    _launch(clock, turned, 1, 0.0)
    assert clock.starve.snapshot()["count"] == 0        # no predecessor
    _launch(clock, turned, 2, 0.030)                    # ready(1) at 0.012
    assert clock.starve.snapshot()["sum_ms"] == pytest.approx(32.0 - 12.0)
    timeline = clock.timeline()
    assert set(timeline) == {"launch_parts", "readback_ms", "starve_ms"}
    assert [timeline["launch_parts"][p]["sum_ms"] for p in PARTS] == pytest.approx(
        [2.0] * 5)
    assert timeline["readback_ms"]["sum_ms"] == pytest.approx(4.0)
    clock.top(3)
    clock.park()                                        # waits for work
    _launch(clock, turned, 3, 5.0)
    assert clock.starve.snapshot()["count"] == 1        # not starved: idle
    _launch(clock, turned, 4, 5.040)
    assert clock.starve.snapshot()["count"] == 2
    clock.top(5)
    assert sum(h["sum_ms"] for h in clock.snapshot().values()) == pytest.approx(
        clock.cycle.snapshot()["sum_ms"])


def test_a_launch_behind_one_in_flight_is_not_starved(turned):
    """The pipelined step: launch 2 is enqueued while launch 1 still runs, so
    its starve is 0 whichever of the two reaches the loop thread first."""
    clock = _CycleClock()
    clock.top(1)
    clock.landed(1, turned(0.0), (0.001, 0.002, 0.003, 0.004), turned(0.005))
    clock.landed(2, turned(0.006), (0.007, 0.008, 0.009, 0.010), turned(0.011))
    assert (clock.starve.n, clock.starve.total_ms) == (1, 0.0)
    clock.mark("wait", 1)
    clock.ready(1, 100.050)                # a readback worker's read
    turned(0.051)
    clock.mark("emit", 1)
    assert clock.readback.total_ms == pytest.approx(1.0)
    # launch 3 is enqueued before launch 2 is back: nothing to observe yet
    clock.landed(3, turned(0.052), (100.053, 100.054, 100.055, 100.056),
                 turned(0.057))
    assert (clock.starve.n, clock.starve.total_ms) == (2, 0.0)
    clock.ready(2, 100.090)
    clock.ready(3, 100.120)
    # launch 4 after launch 3 came back: the chip waited 10 ms
    clock.landed(4, turned(0.125), (100.126, 100.130, 100.131, 100.132),
                 turned(0.133))
    assert clock.starve.n == 3
    assert clock.starve.total_ms == pytest.approx(10.0)
