"""The engine's own spans (docs/pipelined_decode.md "Observability"): the
loop thread's time per scheduling cycle cut into six phases that add up, the
same boundaries as ``engine.*`` annotations on the profiler's host plane, a
request's way to its first token, and the pool's raw occupancy counters."""

import asyncio
import math
import re

import jax
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.llm.engine import (
    GenRequest,
    LLMEngineCore,
    _CycleClock,
)

PHASES = ("admin_ms", "plan_ms", "launch_ms", "wait_ms", "emit_ms", "yield_ms")
PROMPTS = [
    [256] + [(7 * i + 3 * j) % 250 + 1 for j in range(40)] for i in range(4)
]
RAGGED = dict(cache_mode="paged", scheduler="ragged", step_token_budget=16,
              page_size=8, num_pages=64)


@pytest.fixture(scope="module")
def parts():
    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    return bundle, bundle.init(jax.random.PRNGKey(0))


def _engine(parts, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_buckets", [16, 32, 64])
    kw.setdefault("eos_token_id", None)
    kw.setdefault("decode_steps", 4)
    return LLMEngineCore(*parts, **kw)


def _run(engine, prompts, n=12, **req_kw):
    """Every prompt at once; returns the requests after the loop drained."""

    async def go():
        reqs = [GenRequest(prompt_ids=list(p), max_new_tokens=n, **req_kw)
                for p in prompts]

        async def one(req):
            return [t async for t in engine.generate(req)]

        await asyncio.gather(*(one(r) for r in reqs))
        await engine.wait_drained()
        return reqs

    return asyncio.run(go())


# -- (a) the six phases are a partition of the cycle --------------------------


@pytest.mark.parametrize("mode,kw", [
    ("pipelined", {}),
    ("serial", {"pipeline_depth": 1}),
    ("ragged", RAGGED),
])
def test_phases_add_up_to_the_cycle(parts, mode, kw):
    engine = _engine(parts, **kw)
    _run(engine, PROMPTS)
    pipe = engine.lifecycle_stats()["pipeline"]
    launches = pipe["dispatch_ms"]["count"]
    assert launches >= 4
    cycle = pipe["cycle_ms"]
    assert set(pipe["phases"]) == set(PHASES)
    total = sum(pipe["phases"][p]["sum_ms"] for p in PHASES)
    assert total == pytest.approx(cycle["sum_ms"], rel=0.01)
    # a cycle is an iteration that dispatched or retired: once the pipeline
    # has drained there are as many as launches, whatever the depth
    assert cycle["count"] == launches == pipe["retire_ms"]["count"]
    assert all(pipe["phases"][p]["count"] == launches for p in PHASES)
    # retire_ms is the device wait plus the emission, on shared clock reads
    assert pipe["retire_ms"]["sum_ms"] == pytest.approx(
        pipe["phases"]["wait_ms"]["sum_ms"] + pipe["phases"]["emit_ms"]["sum_ms"],
        rel=1e-6,
    )
    if mode == "ragged":
        assert engine.counters["ragged_steps"] > 0
    engine.stop()


def test_a_parked_loop_is_no_cycle():
    clock = _CycleClock()
    clock.top(1)
    clock.mark("plan", 1)
    clock.park()                       # nothing launched: dropped
    clock.top(1)
    clock.mark("yield", 1)             # an iteration without work
    clock.top(2)
    assert clock.cycle.snapshot()["count"] == 0
    clock.mark("plan", 2)
    clock.mark("launch", 2)
    t0 = clock.mark("wait", 2)
    assert clock.mark("wait", 2) >= t0  # re-entering a phase is a no-op
    clock.mark("emit", 2)
    clock.mark("yield", 2)
    clock.top(3)
    clock.park()
    snap = clock.snapshot()
    assert clock.cycle.snapshot()["count"] == 1
    assert sum(snap[p]["sum_ms"] for p in PHASES) == pytest.approx(
        clock.cycle.snapshot()["sum_ms"], rel=1e-9
    )
    assert clock.mark("plan", 3) > 0    # between cycles: a clock read only
    assert clock.snapshot()["plan_ms"]["count"] == 1


# -- (b) a request's way to its first token -----------------------------------


@pytest.mark.parametrize("kw", [{}, RAGGED], ids=["two_dispatch", "ragged"])
def test_request_phases_add_up_to_ttft(parts, kw):
    engine = _engine(parts, **kw)
    reqs = _run(engine, PROMPTS[:3])
    stats = engine.lifecycle_stats()["requests"]
    # prefill_ms cut on the launch timeline (tests/test_launch_timeline.py):
    # observed where the prompt rode launches, so not on the dense path
    cut = {"first_launch_wait_ms", "prefill_span_ms", "first_emit_ms"}
    assert set(stats) == {"queue_wait_ms", "admit_ms", "prefill_ms",
                          "ttft_ms", "prefill_launches"} | cut
    assert all(s["count"] == (3 if kw or k not in cut else 0)
               for k, s in stats.items())
    assert stats["ttft_ms"]["buckets"][-1] == 30000.0
    parts_sum = sum(stats[k]["sum_ms"]
                    for k in ("queue_wait_ms", "admit_ms", "prefill_ms"))
    assert parts_sum == pytest.approx(stats["ttft_ms"]["sum_ms"], abs=3.0)
    for r in reqs:
        assert r._submitted == r._queued <= r._slot_at <= r._job_at
        assert r._prefill_launches >= 1
    engine.stop()


@pytest.mark.parametrize("prompt_len,budget", [(41, 16), (16, 16), (70, 12)])
def test_a_lone_request_counts_its_prefill_launches(parts, prompt_len, budget):
    engine = _engine(parts, **dict(RAGGED, step_token_budget=budget))
    prompt = [(i * 7 + 3) % 250 + 1 for i in range(prompt_len)]
    (req,) = _run(engine, [prompt], n=4)
    chunk = engine.lifecycle_stats()["ragged"]["effective_budget"]
    assert req._prefill_launches == math.ceil(prompt_len / chunk)
    snap = engine.lifecycle_stats()["requests"]["prefill_launches"]
    assert (snap["count"], snap["sum_ms"]) == (1, req._prefill_launches)
    engine.stop()


def test_a_preempted_request_waits_twice_and_has_one_ttft(parts):
    engine = _engine(
        parts, max_batch=1, prefill_buckets=[32, 64], decode_steps=2,
        cache_mode="paged", page_size=16, prefix_cache=64, prefix_block=16,
        preempt_batch=True, preempt_budget=2,
    )

    async def go():
        batch = GenRequest(prompt_ids=[(i * 7 + 3) % 250 + 1 for i in range(17)],
                           max_new_tokens=24, priority="batch")

        async def collect(req):
            return [t async for t in engine.generate(req)]

        task = asyncio.create_task(collect(batch))
        while batch.produced < 6:
            await asyncio.sleep(0.005)
        submitted = batch._queued
        await asyncio.wait_for(
            collect(GenRequest(prompt_ids=[1, 9, 9], max_new_tokens=2)), 60)
        await asyncio.wait_for(task, 60)
        await engine.wait_drained()
        return batch, submitted

    batch, submitted = asyncio.run(go())
    assert engine.counters["preemptions"] >= 1
    stats = engine.lifecycle_stats()["requests"]
    # two requests, one first token each; the resume leg waited anew
    assert stats["ttft_ms"]["count"] == 2
    assert stats["queue_wait_ms"]["count"] == 2 + engine.counters["preemptions"]
    assert batch._queued > submitted == batch._submitted
    engine.stop()


# -- (c) the same boundaries on the profiler's clock --------------------------


def _host_events(trace_dir):
    """(name, seq, start_s, end_s) of the engine's annotations, as the
    benchmark's own reader of the host plane finds them."""
    from benchmark import host_spans, xplane

    path = xplane.find_xplane(trace_dir)
    return [span[:4] for span in host_spans.engine_spans(xplane.load(path))]


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_phases_are_annotations_on_the_host_plane(parts, tmp_path):
    # depth 1: every cycle is serial, ragged step or decode chunk, so each
    # launch seq has each phase once (at depth 2 a chunk retires one cycle
    # after its launch, beside the next dispatch)
    engine = _engine(parts, pipeline_depth=1, **RAGGED)
    _run(engine, PROMPTS[:1], n=4)          # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        _run(engine, PROMPTS[:3], n=6)
    events = _host_events(str(tmp_path))
    names = {name for name, *_ in events}
    assert {"engine.admin", "engine.plan", "engine.launch", "engine.dispatch",
            "engine.wait", "engine.emit", "engine.yield",
            "engine.admit"} <= names
    by_seq = {}
    for name, seq, start, end in events:
        if seq is not None and name != "engine.admin":
            by_seq.setdefault(seq, {})[name] = (start, end)
    # six of the loop and the worker, and the launch timeline's three
    whole = [s for s in by_seq.values() if len(s) == 9]
    assert len(whole) >= 3
    for spans in whole:
        # in time as the phases are: plan, launch (the worker's dispatch
        # inside it), wait, emit, yield; each starts after the last ended
        order = [spans["engine." + p]
                 for p in ("plan", "launch", "wait", "emit", "yield")]
        for (_, end), (start, _) in zip(order, order[1:]):
            assert start >= end
        launch, dispatch = spans["engine.launch"], spans["engine.dispatch"]
        assert launch[0] <= dispatch[0] and dispatch[1] <= launch[1]
        # the worker's uploads, then its jitted call, inside its dispatch;
        # the copies after the first (the read worker's for a ragged step,
        # the retire's for a chunk) inside the loop's wait, which stays
        # open on the loop thread across the await
        upload, enqueue = spans["engine.upload"], spans["engine.enqueue"]
        assert dispatch[0] <= upload[0] <= upload[1] <= enqueue[0]
        assert enqueue[1] <= dispatch[1]
        wait, readback = spans["engine.wait"], spans["engine.readback"]
        assert wait[0] <= readback[0] and readback[1] <= wait[1]
    engine.stop()


# -- the device side: every section of the step is named in its op_name -------


def test_the_steps_operations_carry_their_named_scope(parts):
    """An operation's ``op_name`` is what a chip trace keeps beside it (stat
    ``tf_op`` of the event's metadata; ``benchmark/host_spans.py::
    device_ms_by_scope`` groups by it), so the compiled ragged step must name
    every section: qkv, attn, kv_write, oproj, ffn, logits
    (models/llama.py), sample (llm/sampling.py), logprobs (llm/engine.py)."""
    engine = _engine(parts, **RAGGED)
    step, seen = engine._ragged_paged_jit, []

    def spy(*args, **kw):
        if kw.get("want_lp") and not seen:
            # shapes only: the pools are donated to the call
            seen.append(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                if hasattr(x, "shape") else x, (args, kw)))
        return step(*args, **kw)

    engine._ragged_paged_jit = spy
    _run(engine, PROMPTS[:2], n=4, logprobs=2)
    engine.stop()
    args, kw = seen[0]
    hlo = step.lower(*args, **kw).compile().as_text()
    scoped = {tuple(name.split("/")[:-1])
              for name in re.findall(r'op_name="([^"]*)"', hlo)}
    for scope in ("qkv", "attn", "kv_write", "oproj", "ffn", "logits",
                  "logprobs"):
        assert ("jit(_ragged_paged_step)", scope) in scoped, scope
    assert ("jit(_ragged_paged_step)", "jit(sample_tokens)", "sample") in scoped


# -- the pool's raw high-water mark -------------------------------------------


def test_kv_pool_reports_raw_high_water_mark(parts):
    engine = _engine(parts, cache_mode="paged", page_size=16, num_pages=32,
                     prefix_cache=16, prefix_block=16)
    assert engine.lifecycle_stats()["kv_pool"]["used_pages_peak"] == 0
    _run(engine, PROMPTS[:2], n=8)
    pool = engine.lifecycle_stats()["kv_pool"]
    cached = engine._prefix.cached_pages
    # drained: only the prefix cache still holds pages, and the mark counts
    # them beside what the two live requests held
    assert pool["num_pages"] - 1 - engine.paged_cache.pool.free_pages == cached > 0
    assert pool["used_pages_peak"] >= max(cached, 2 * 3)   # 2 x ceil(41+8 / 16)
    assert "used_pages" not in pool
    assert engine.health()["kv_pool"]["used_pages_peak"] == pool["used_pages_peak"]
    engine.stop()


# -- the stacked pools stay one buffer through a launch (ISSUE 25) ------------

# sizes no other dimension of llama-tiny shares: 2 layers, 2 KV heads x 16,
# 11 pages of 8 tokens
_POOL = dict(layers=2, hkv=2, pages=11, page=8, d=16)


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _sub_jaxprs(eqn):
            yield from _walk(inner)


def _assert_pools_ride_the_carry(jaxpr, quant):
    """No equation yields one layer's pool (a slice of the stack, or a stack
    rebuilt from slices, is a copy of gigabytes on the chip), and every scan
    that touches the stacks has them in its carry, never among the inputs it
    scans over or the outputs it stacks. Returns the scans seen."""
    p = _POOL
    layer_pool = (p["hkv"], p["pages"], p["page"], p["d"])
    stacks = {(p["layers"],) + layer_pool}
    if quant:
        stacks.add((p["layers"],) + layer_pool[:-1])
    banned = {s[1:] for s in stacks}
    scans = 0
    for eqn in _walk(jaxpr):
        for var in eqn.outvars:
            shape = tuple(var.aval.shape)
            while shape[:1] == (1,):
                shape = shape[1:]
            assert shape not in banned, (eqn.primitive.name, var.aval)
        if eqn.primitive.name != "scan":
            continue
        n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        shapes = [tuple(v.aval.shape) for v in eqn.invars]
        carry = shapes[n_consts:n_consts + n_carry]
        scanned = shapes[n_consts + n_carry:]
        stacked = [tuple(v.aval.shape) for v in eqn.outvars[n_carry:]]
        if not stacks & set(shapes):
            continue
        scans += 1
        assert stacks <= set(carry), carry
        assert not stacks & set(shapes[:n_consts]), "a pool closed over"
        # a scanned input or stacked output has the scan's length in front
        for shape in scanned + stacked:
            assert shape not in stacks and shape[1:] not in stacks, shape
    return scans


def _pass_jaxpr(which, quant, scan_layers):
    """The jaxpr of one paged pass of llama-tiny on pools of _POOL's sizes."""
    import jax.numpy as jnp

    cfg = {"preset": "llama-tiny", "dtype": "bfloat16",
           "scan_layers": scan_layers}
    if quant:
        cfg["kv_quant"] = "int8"
    bundle = models.build_model("llama", cfg)
    params = jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0)))
    p = _POOL
    stack = (p["layers"], p["hkv"], p["pages"], p["page"], p["d"])
    pool = jax.ShapeDtypeStruct(stack, jnp.int8 if quant else jnp.bfloat16)
    scales = (
        {n: jax.ShapeDtypeStruct(stack[:-1], jnp.float32)
         for n in ("k_scales", "v_scales")} if quant else {}
    )

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    rows, t = 3, 16
    table, per_row, per_tok = i32(rows, 4), i32(rows), i32(t)
    # the operands in front of and behind (k_pools, v_pools), per signature
    front, back = {
        "decode_paged": ([per_row], [table] + [per_row] * 3),
        "verify_paged": ([i32(rows, 3)], [table, per_row]),
        "forward_ragged": (
            [per_tok] * 3 + [jax.ShapeDtypeStruct((t,), jnp.bool_), per_tok,
                             per_row],
            [table] + [per_row] * 3 + [per_tok] * 2,
        ),
    }[which]

    def run(params, front, k, v, back, scales):
        return getattr(bundle, which)(params, *front, k, v, *back, **scales)

    return jax.make_jaxpr(run)(params, front, pool, pool, back, scales).jaxpr


@pytest.mark.parametrize("scan_layers", [False, True], ids=["unrolled", "scan"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("which", ["decode_paged", "forward_ragged",
                                   "verify_paged"])
def test_a_paged_pass_carries_the_stacked_pools(which, quant, scan_layers):
    jaxpr = _pass_jaxpr(which, quant, scan_layers)
    scans = _assert_pools_ride_the_carry(jaxpr, quant)
    assert scans == (1 if scan_layers else 0)
    # the pass hands the stacks back whole, behind the logits
    p = _POOL
    stack = (p["layers"], p["hkv"], p["pages"], p["page"], p["d"])
    outs = [tuple(v.aval.shape) for v in jaxpr.outvars]
    assert outs.count(stack) == 2
    assert outs.count(stack[:-1]) == (2 if quant else 0)


@pytest.mark.parametrize("scan_layers", [False, True], ids=["unrolled", "scan"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_the_ragged_step_carries_the_stacked_pools(quant, scan_layers):
    """The same on the program the engine launches (the spy of
    test_the_steps_operations_carry_their_named_scope): the ragged pass and
    the decode passes chained behind it, whose scan over steps carries the
    pools too."""
    cfg = {"preset": "llama-tiny", "dtype": "bfloat16",
           "scan_layers": scan_layers}
    if quant:
        cfg["kv_quant"] = "int8"
    bundle = models.build_model("llama", cfg)
    p = _POOL
    engine = _engine((bundle, bundle.init(jax.random.PRNGKey(0))),
                     **dict(RAGGED, page_size=p["page"], num_pages=p["pages"]))
    step, seen = engine._ragged_paged_jit, []

    def spy(*args, **kw):
        if kw.get("chain") is not None and not seen:
            seen.append(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                if hasattr(x, "shape") else x, (args, kw)))
        return step(*args, **kw)

    engine._ragged_paged_jit = spy
    # two prompts at once: the first to finish its prefill decodes (a window
    # of 4 steps, so a chain) beside the other's chunks
    _run(engine, [p[:20] for p in PROMPTS[:2]], n=8)
    engine.stop()
    args, kw = seen[0]
    jaxpr = jax.make_jaxpr(lambda a, k: step.__wrapped__(*a, **k))(
        args, {k: v for k, v in kw.items() if k != "want_lp"}).jaxpr
    scans = _assert_pools_ride_the_carry(jaxpr, quant)
    # the chain's scan over steps, and under scan_layers the layer scan of
    # the ragged pass and the one inside the chain's body
    assert scans == (3 if scan_layers else 1)
