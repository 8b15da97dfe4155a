"""Ragged scheduler engine tests (docs/ragged_attention.md): byte-identity
of the paged cache's token-budget single-launch scheduler against the dense
cache's two-dispatch loop (greedy + seeded, int8 KV, pipeline depths),
prefix cache / speculation composition, and chaos behavior
mid-ragged-dispatch."""

import asyncio

import jax
import numpy as np
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.errors import EngineOverloadedError
from clearml_serving_tpu.llm import faults
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

CFG = {"preset": "llama-tiny", "dtype": "float32"}
QCFG = dict(CFG, kv_quant="int8")

LONG = [(i * 7 + 3) % 250 + 1 for i in range(40)]
SHORT = [5, 9, 2, 17, 33]


@pytest.fixture(scope="module")
def parts():
    bundle = models.build_model("llama", CFG)
    qbundle = models.build_model("llama", QCFG)
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, qbundle, params


def _engine(bundle, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("prefill_buckets", [16, 64])
    kw.setdefault("eos_token_id", None)
    kw.setdefault("decode_steps", 2)
    return LLMEngineCore(bundle, params, **kw)


def _serve(engine, requests):
    """Submit (prompt, max_new_tokens, sampling keywords) requests 50 ms
    apart, so later admissions overlap live decode streams — the mixed
    prefill+decode batch the ragged scheduler exists for — and drain."""

    async def one(i, ids, n, kw):
        if i:
            await asyncio.sleep(0.05 * i)
        req = GenRequest(prompt_ids=list(ids), max_new_tokens=n, **kw)
        return [t async for t in engine.generate(req)]

    async def run():
        outs = await asyncio.gather(
            *(one(i, *r) for i, r in enumerate(requests)))
        await engine.wait_drained()
        return outs

    return asyncio.run(run())


def _staggered(engine, prompts, n=8, seeds=None):
    """:func:`_serve` of ``prompts``; seeded entries sample at temperature
    (deterministic per seed), the others are greedy."""
    seeds = seeds or [None] * len(prompts)
    return _serve(engine, [
        (ids, n, {"temperature": 0.7, "seed": seed} if seed is not None else {})
        for ids, seed in zip(prompts, seeds)
    ])


def _ab(bundle, params, prompts, *, seeds=None, n=8, legacy_kw=None,
        ragged_kw=None, **common):
    """(legacy streams, ragged streams) for the same staggered workload:
    the dense cache's two-dispatch loop against the paged cache's ragged
    step. The legacy arm chunks EVERY prompt (chunk below the shortest
    prompt): under kv_quant, full prefill attends live precision while
    chunked prefill reads back what it quantized — different caches by
    design — and the ragged scheduler is a chunked path by construction."""
    legacy = _engine(bundle, params, cache_mode="dense",
                     chunked_prefill_size=4,
                     **{**common, **(legacy_kw or {})})
    a = _staggered(legacy, prompts, n=n, seeds=seeds)
    legacy.stop()
    ragged = _engine(bundle, params, cache_mode="paged",
                     step_token_budget=12, **{**common, **(ragged_kw or {})})
    b = _staggered(ragged, prompts, n=n, seeds=seeds)
    stats = ragged.lifecycle_stats()
    ragged.stop()
    return a, b, stats


def test_ragged_ab_paged_greedy_seeded_depth2(parts, monkeypatch):
    """One mixed batch carries a GREEDY decode stream (row 0, seed None)
    and a SEEDED temperature>0 admission (row 1). At pipeline depth 2 the
    ragged phases drain the in-flight queue and reset the device chains;
    both streams still replay the dense two-dispatch arm exactly (depth 1
    is covered by the int8 cells below)."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle, _, params = parts
    a, b, stats = _ab(
        bundle, params, [SHORT, LONG], seeds=[None, 22],
        legacy_kw={"pipeline_depth": 2},
        ragged_kw={"pipeline_depth": 2},
    )
    assert a == b
    assert stats["ragged"]["steps"] >= 2           # chunked admission ran
    assert stats["ragged"]["step_rows"]["prefill"] >= 2
    assert stats["ragged"]["step_rows"]["decode"] >= 1  # mixed launches


def test_ragged_ab_int8_kv(parts, monkeypatch):
    """int8 KV through the ragged path: chunk K/V quantize via the same
    _kv_store math and the ragged kernel/reference dequantizes like the
    decode path — streams match the (fully chunked) dense two-dispatch
    arm."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    _, qbundle, params = parts
    a, b, _ = _ab(qbundle, params, [SHORT, LONG])
    assert a == b


def test_ragged_prefix_cache_tail_chunks(parts, monkeypatch):
    """Paged radix hits under the ragged scheduler: the shared run maps
    into the slot's table by reference at job start and only the TAIL
    rides the launches as chunk rows — warm streams replay the cold ones
    exactly, under the armed KV sanitizer, leak-free."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle, _, params = parts
    plain = _engine(bundle, params, cache_mode="dense",
                    chunked_prefill_size=4, max_seq_len=160)
    want = _staggered(plain, [LONG], n=6)
    plain.stop()
    cached = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                     step_token_budget=16, max_seq_len=160,
                     prefix_cache=4, prefix_block=16)
    first = _staggered(cached, [LONG], n=6)
    second = _staggered(cached, [LONG], n=6)
    assert cached._prefix.hits >= 1
    pool = cached.paged_cache.pool
    live = pool.num_pages - 1 - pool.free_pages
    assert live == cached._prefix.cached_pages  # only the cache holds pages
    cached.stop()
    assert first == want
    assert second == want


def test_ragged_speculation_composes(parts):
    """Spec-as-row (ISSUE 13): under the ragged scheduler, speculation is a
    ROW SHAPE — eligible slots ride the mixed launches as q=k+1 verify
    rows instead of draining the pipeline into the legacy serial scan.
    Greedy streams stay identical to the plain ragged engine (the verify
    guarantee), and the launches actually carry spec_verify rows."""
    bundle, _, params = parts
    prompt = [5, 9, 2, 17, 5, 9, 2]
    plain = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                    step_token_budget=12)
    want = _staggered(plain, [prompt], n=8)
    plain.stop()
    spec = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                   step_token_budget=12, speculation="ngram", spec_k=2,
                   spec_ngram=2)
    got = _staggered(spec, [prompt], n=8)
    stats = spec.lifecycle_stats()["ragged"]
    spec.stop()
    assert got == want
    assert stats["step_rows"]["spec_verify"] >= 1
    assert stats["spec_acceptance"]["count"] >= 1


def _overlapped(engine, n_a=24, n_b=8, seed_b=22):
    """A greedy decode stream that is PROVABLY mid-flight when a seeded
    long-prompt admission arrives — the mixed launches carry the decode
    row beside the admission's chunk rows for several steps."""

    async def run():
        a = GenRequest(prompt_ids=list(SHORT), max_new_tokens=n_a)
        a_task = asyncio.create_task(_collect_async(engine, a))
        while a.produced < 2:
            await asyncio.sleep(0.005)
        b = GenRequest(
            prompt_ids=list(LONG), max_new_tokens=n_b,
            temperature=0.7 if seed_b is not None else 0.0, seed=seed_b,
        )
        out_b = [t async for t in engine.generate(b)]
        out_a = await a_task
        await engine.wait_drained()
        return [out_a, out_b]

    return asyncio.run(run())


def test_ragged_multistep_byte_identity(parts, monkeypatch):
    """Multi-step decode rows (ISSUE 13 tentpole): q=decode_steps windows
    chain sampled tokens device-side inside ONE mixed launch. Greedy +
    seeded streams at ragged window ∈ {2, 4} equal the q=1 ragged streams
    AND the dense two-dispatch streams exactly — pipeline depth 2, armed
    sanitizer."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle, _, params = parts
    legacy = _engine(bundle, params, chunked_prefill_size=4,
                     cache_mode="dense", pipeline_depth=2, decode_steps=4)
    want = _overlapped(legacy)
    legacy.stop()
    for q in (1, 2, 4):
        ragged = _engine(bundle, params, step_token_budget=24,
                         cache_mode="paged", pipeline_depth=2,
                         decode_steps=4, ragged_decode_steps=q)
        got = _overlapped(ragged)
        stats = ragged.lifecycle_stats()["ragged"]
        ragged.stop()
        assert got == want, q
        if q > 1:
            # the window actually engaged: some launch advanced a
            # decode row by more than one token
            snap = stats["tokens_per_launch"]
            assert snap["count"] >= 1, q
            assert snap["sum_ms"] > snap["count"], q


def test_ragged_multistep_int8_kv(parts, monkeypatch):
    """int8 KV through multi-step windows: the chained steps quantize each
    token's K/V via the same _kv_store math as the q=1 path — streams
    match the fully-chunked dense two-dispatch arm."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    _, qbundle, params = parts
    a, b, _ = _ab(
        qbundle, params, [SHORT, LONG],
        legacy_kw={"decode_steps": 4},
        ragged_kw={"decode_steps": 4, "ragged_decode_steps": 4},
    )
    assert a == b


def test_ragged_multistep_logprobs(parts):
    """Per-step logprob entries through a q=4 window equal the q=1 ones
    (the lp triple is chained step-major through the in-launch scan)."""
    bundle, _, params = parts

    def run(q):
        engine = _engine(bundle, params, cache_mode="paged",
                         scheduler="ragged", step_token_budget=24,
                         decode_steps=4, ragged_decode_steps=q)

        async def go():
            a = GenRequest(prompt_ids=list(SHORT), max_new_tokens=6,
                           logprobs=2)
            b = GenRequest(prompt_ids=list(LONG), max_new_tokens=4)

            async def one(req, delay):
                if delay:
                    await asyncio.sleep(delay)
                return [t async for t in engine.generate(req)]

            outs = await asyncio.gather(one(a, 0), one(b, 0.05))
            await engine.wait_drained()
            return outs, list(a.logprob_entries)

        outs, entries = asyncio.run(go())
        engine.stop()
        return outs, entries

    outs1, entries1 = run(1)
    outs4, entries4 = run(4)
    assert outs1 == outs4
    assert entries1 == entries4
    assert len(entries1) == 6


def test_spec_as_row_matches_legacy_spec(parts):
    """Spec-as-row reproduces the serial spec path's accepted streams
    (greedy): the dense engine's draft-verify scan and the paged engine's
    in-launch verify rows emit identical tokens, and the paged engine
    never touches the serial scan path."""
    bundle, _, params = parts
    prompts = [[5, 9, 2, 17, 5, 9, 2], [3, 3, 7, 3, 3, 7, 3]]
    legacy = _engine(bundle, params, cache_mode="dense",
                     chunked_prefill_size=4, speculation="ngram",
                     spec_k=2, spec_ngram=2)
    want = _staggered(legacy, prompts, n=10)
    legacy.stop()
    ragged = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                     step_token_budget=12, speculation="ngram", spec_k=2,
                     spec_ngram=2)

    def boom(*a, **k):  # the drain-and-scan path must be dead here
        raise AssertionError(
            "legacy serial spec scan ran under the ragged scheduler"
        )

    ragged._dispatch_spec_chunk = boom
    got = _staggered(ragged, prompts, n=10)
    stats = ragged.lifecycle_stats()["ragged"]
    ragged.stop()
    assert got == want
    assert stats["step_rows"]["spec_verify"] >= 1


def test_ragged_decode_steps_validation(parts):
    bundle, _, params = parts
    with pytest.raises(ValueError, match="ragged_decode_steps"):
        _engine(bundle, params, cache_mode="paged", step_token_budget=16,
                decode_steps=2, ragged_decode_steps=8)


def test_ragged_budget_validation(parts):
    bundle, _, params = parts
    with pytest.raises(ValueError, match="step_token_budget"):
        _engine(bundle, params, cache_mode="paged", step_token_budget=2)
    with pytest.raises(ValueError, match="scheduler"):
        _engine(bundle, params, scheduler="nope")


def test_ragged_health_and_stats_blocks(parts):
    bundle, _, params = parts
    engine = _engine(bundle, params, cache_mode="paged", step_token_budget=16)
    try:
        assert engine._prefill_gate is None  # the gate is REPLACED
        h = engine.health()
        assert h["scheduler"] == "ragged"
        assert h["ragged"]["step_token_budget"] == 16
        s = engine.lifecycle_stats()["ragged"]
        assert s["budget_utilization"]["count"] == 0
        assert s["step_rows"] == {
            "prefill": 0, "decode": 0, "spec_verify": 0,
        }
        assert s["decode_steps"] == 2        # inherited from decode_steps
        assert s["decode_tokens"] == 0
        assert s["tokens_per_launch"]["count"] == 0
        assert s["spec_acceptance"]["count"] == 0
        # the mixed pass's two token axes: the budget on the compact one;
        # the XLA twin needs no alignment, so the view is the same axis
        assert (s["dense_axis"], s["layout_axis"], s["dense_rows"]) == (16, 16, 0)
        assert (h["ragged"]["dense_axis"], h["ragged"]["layout_axis"]) == (16, 16)
        _staggered(engine, [LONG, SHORT], n=4)
        s = engine.lifecycle_stats()["ragged"]
        # the dense layers multiplied the compact axis, whole, every launch
        assert s["steps"] > 0 and s["dense_rows"] == 16 * s["steps"]
    finally:
        engine.stop()
    legacy = _engine(bundle, params)
    try:
        assert legacy.lifecycle_stats()["ragged"] is None
        assert legacy.health()["scheduler"] == "two_dispatch"
    finally:
        legacy.stop()


# -- chaos ------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_fault_mid_ragged_dispatch_isolates_job(parts, monkeypatch):
    """A poison attributed to the ADMISSION row of a mixed launch (fault at
    the dispatch seam, before device work) fails that request structurally;
    the decode rows keep streaming to completion with the exact tokens an
    undisturbed run produces."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle, _, params = parts
    marker = 251  # only in the admitted prompt
    poisoned = list(LONG)
    poisoned[7] = marker

    clean = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                    step_token_budget=12)
    want = _staggered(clean, [SHORT], n=8)[0]
    clean.stop()

    engine = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                     step_token_budget=12)
    faults.configure([
        {"point": "engine.decode", "action": "raise",
         "match_token": marker, "times": 1},
    ])
    try:

        async def run():
            a = GenRequest(prompt_ids=list(SHORT), max_new_tokens=8)
            a_task = asyncio.create_task(
                _collect_async(engine, a)
            )
            # wait for the decode stream to be live, then admit the poison
            while a.produced < 2:
                await asyncio.sleep(0.005)
            b = GenRequest(prompt_ids=poisoned, max_new_tokens=4)
            b_err = None
            try:
                async for _ in engine.generate(b):
                    pass
            except Exception as ex:
                b_err = ex
            out_a = await asyncio.wait_for(a_task, 60)
            await engine.wait_drained()
            return out_a, b_err

        out_a, b_err = asyncio.run(run())
        assert b_err is not None          # job failed structurally
        assert out_a == want              # decode rows survived, exactly
        pool = engine.paged_cache.pool
        assert pool.free_pages == pool.num_pages - 1  # nothing leaked
    finally:
        faults.clear()
        engine.stop()


async def _collect_async(engine, req):
    return [t async for t in engine.generate(req)]


@pytest.mark.chaos
def test_chaos_retire_fault_on_spec_row_stays_per_request(parts, monkeypatch):
    """A per-request ``engine.decode.retire`` fault landing on a SPEC
    verify row fails only that request — including the zero-accepted case
    (window == 1, immediate fail): the failed slot's pages free wholesale
    and the retire's truncate pass must skip it instead of raising out of
    the step and failing the whole batch. The sibling stream completes
    byte-identically and nothing leaks."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle, _, params = parts
    marker = 201
    # draft-hostile prompt (no n-gram repeats): acceptance ~1/vocab, so
    # the faulted verify row's window is (almost surely) a single token
    hostile = [marker, 7, 31, 5, 47, 13]
    sibling = [3, 3, 7, 3, 3, 7, 3]

    clean = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                    step_token_budget=16, speculation="ngram", spec_k=2,
                    spec_ngram=2)
    want = _staggered(clean, [sibling], n=10)[0]
    clean.stop()

    engine = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                     step_token_budget=16, speculation="ngram", spec_k=2,
                     spec_ngram=2)
    try:

        async def tolerant(req):
            out = []
            try:
                async for t in engine.generate(req):
                    out.append(t)
            except Exception as ex:
                return out, ex
            return out, None

        async def run():
            a = GenRequest(prompt_ids=list(hostile), max_new_tokens=10)
            b = GenRequest(prompt_ids=list(sibling), max_new_tokens=10)
            a_task = asyncio.create_task(tolerant(a))
            b_task = asyncio.create_task(tolerant(b))
            while a.produced < 1 or b.produced < 1:
                await asyncio.sleep(0.005)
            faults.configure([
                {"point": "engine.decode.retire", "action": "raise",
                 "match_token": marker, "times": 1},
            ])
            out_a, a_err = await asyncio.wait_for(a_task, 60)
            out_b, b_err = await asyncio.wait_for(b_task, 60)
            await engine.wait_drained()
            return out_a, a_err, out_b, b_err

        out_a, a_err, out_b, b_err = asyncio.run(run())
        from clearml_serving_tpu.errors import EngineStepError

        assert isinstance(a_err, EngineStepError)   # only the matched row
        assert b_err is None
        assert out_b == want                        # sibling untouched
        assert engine.counters["step_failures"] == 1
        pool = engine.paged_cache.pool
        assert pool.free_pages == pool.num_pages - 1  # nothing leaked
    finally:
        faults.clear()
        engine.stop()


@pytest.mark.chaos
def test_chaos_retire_fault_mid_multistep_window(parts, monkeypatch):
    """A per-request ``engine.decode.retire`` fault landing on a q>1 decode
    row fails ONLY that request, with its PARTIAL window delivered (all
    but the last token — the tokens were already sampled device-side; the
    failure is a host-emission failure): the delivered stream is a strict
    prefix of the undisturbed run, the concurrent admission completes
    untouched, and no pages leak under the armed sanitizer."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle, _, params = parts
    marker = SHORT[0]  # matches the DECODING request

    clean = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                    step_token_budget=64, decode_steps=4,
                    ragged_decode_steps=4, max_seq_len=160)
    want = _overlapped(clean, n_a=48, n_b=12, seed_b=None)
    clean.stop()

    engine = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                     step_token_budget=64, decode_steps=4,
                     ragged_decode_steps=4, max_seq_len=160)
    # deterministic window accounting: the poison is armed INSIDE the first
    # retire whose plan carries the decoding request as a q>1 row (arming it
    # from the test's coroutine raced the loop: the fault could land on a
    # launch already in flight with a q=1 row, on the parent commit as often
    # as not), and the row's produced count and window size are recorded there
    seen = {}
    real_retire = engine._retire_ragged

    def spy(plan, result):
        # the retire is handed HOST copies: the step's read worker waited
        # for the launch and copied its results off the loop thread
        assert isinstance(result["sampled"], np.ndarray)
        assert result["ready_at"] >= result["stamps"][2]
        if not seen:
            for slot, request in enumerate(engine._slot_req):
                if request is not None and marker in request.prompt_ids:
                    if plan["row_steps"][slot] > 1:
                        seen["produced"] = request.produced
                        seen["steps"] = int(plan["row_steps"][slot])
                        faults.configure([
                            {"point": "engine.decode.retire",
                             "action": "raise", "match_token": marker,
                             "times": 1},
                        ])
        return real_retire(plan, result)

    engine._retire_ragged = spy
    try:

        async def tolerant(req):
            out = []
            try:
                async for t in engine.generate(req):
                    out.append(t)
            except Exception as ex:
                return out, ex
            return out, None

        async def run():
            a = GenRequest(prompt_ids=list(SHORT), max_new_tokens=48)
            a_task = asyncio.create_task(tolerant(a))
            while a.produced < 2:
                await asyncio.sleep(0.005)
            # the admission makes the loop take ragged steps; with this
            # much budget the decode row rides them as a q=4 window, and a
            # outlives the admission (48 tokens): a retire that carries its
            # decode row as a window is guaranteed, and the spy poisons it
            b_task = asyncio.create_task(tolerant(
                GenRequest(prompt_ids=list(LONG), max_new_tokens=12)
            ))
            out_a, a_err = await asyncio.wait_for(a_task, 60)
            out_b, b_err = await asyncio.wait_for(b_task, 60)
            await engine.wait_drained()
            return out_a, a_err, out_b, b_err

        out_a, a_err, out_b, b_err = asyncio.run(run())
        from clearml_serving_tpu.errors import EngineStepError

        assert isinstance(a_err, EngineStepError)
        assert b_err is None
        # partial window: tokens before the poisoned launch plus all but
        # the last token of its window, a strict prefix of the clean run
        assert seen, "fault never landed on a q>1 window"
        assert out_a == want[0][: seen["produced"] + seen["steps"] - 1]
        assert seen["steps"] > 1
        assert out_b == want[1]       # the admission was untouched
        pool = engine.paged_cache.pool
        assert pool.free_pages == pool.num_pages - 1  # nothing leaked
    finally:
        faults.clear()
        engine.stop()


@pytest.mark.chaos
def test_chaos_budget_admission_shed(parts, monkeypatch):
    """``engine.admit.budget`` (faults.KNOWN_POINTS): an injected raise as
    a job's chunk is admitted into a step's budget sheds that admission
    with a structured 429; the shed books under reason="budget"."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle, _, params = parts
    engine = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                     step_token_budget=12)
    faults.configure([
        {"point": "engine.admit.budget", "action": "raise", "times": 1},
    ])
    try:

        async def run():
            req = GenRequest(prompt_ids=list(LONG), max_new_tokens=4)
            try:
                async for _ in engine.generate(req):
                    pass
            except EngineOverloadedError as ex:
                return ex
            return None

        err = asyncio.run(run())
        assert err is not None and err.retry_after is not None
        assert engine._class_sheds.get("budget", {}).get("interactive") == 1
        pool = engine.paged_cache.pool
        assert pool.free_pages == pool.num_pages - 1
        # the engine keeps serving afterwards
        out = _staggered(engine, [SHORT], n=4)
        assert len(out[0]) == 4
    finally:
        faults.clear()
        engine.stop()


def test_ragged_cancel_mid_admission_reclaims(parts, monkeypatch):
    """Client disconnect while the prompt is mid-chunking: the job aborts
    at the next step boundary and the slot's pages free (sanitizer-armed)."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle, _, params = parts
    engine = _engine(bundle, params, cache_mode="paged", scheduler="ragged",
                     step_token_budget=8, max_seq_len=160)

    async def run():
        req = GenRequest(prompt_ids=list(LONG), max_new_tokens=4)
        agen = engine.generate(req)
        task = asyncio.ensure_future(agen.__anext__())
        await asyncio.sleep(0.05)
        req.cancel()
        try:
            await asyncio.wait_for(task, 30)
        except BaseException:
            pass
        await agen.aclose()
        await engine.wait_drained()

    try:
        asyncio.run(run())
        pool = engine.paged_cache.pool
        assert pool.free_pages == pool.num_pages - 1
    finally:
        engine.stop()


def test_ragged_retire_reads_back_only_finishing_rows(parts, monkeypatch):
    """ISSUE-10 satellite: the retire stage must never read back the full
    [R, vocab] logits, and the streams stay byte-identical to the
    two-dispatch arm. Since ISSUE 49 not even the finishing rows cross:
    the dispatch worker samples each finishing prompt's first token from
    its row ON the device, behind the launch (nothing when no job
    finishes), and the result carries the ids and logprob triples alone."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle, _, params = parts
    finished, crossed = [], []
    orig = LLMEngineCore._dispatch_ragged_device

    def spy(self, plan):
        result = orig(self, plan)
        assert "logits" not in result
        assert len(result["first"]) == len(result["finish_rows"])
        finished.append(len(result["first"]))
        crossed.extend(
            tuple(leaf.shape) for leaf in jax.tree.leaves(result["first"])
        )
        return result

    monkeypatch.setattr(LLMEngineCore, "_dispatch_ragged_device", spy)
    a, b, stats = _ab(bundle, params, [SHORT, LONG], seeds=[None, 22],
                      legacy_kw={"pipeline_depth": 1},
                      ragged_kw={"pipeline_depth": 1})
    assert a == b, "streams must stay byte-identical under the gather"
    assert stats["ragged"]["steps"] >= 2
    assert finished, "spy never saw a ragged step"
    vocab = bundle.config["vocab_size"]
    # most steps finish no job: nothing is sampled or read back for them
    assert 0 in finished
    assert sum(finished) == 2, "both admissions must complete in a step"
    # what a finishing launch hands back: an id [1] and the logprob triple
    # ([1], [1, K], [1, K]) a prompt, never a row of the vocabulary's size
    assert crossed and all(vocab not in shape for shape in crossed)


# -- the sampler does what the launch's live rows asked for (ISSUE 34) --------


def test_sampler_counters_follow_the_live_rows(parts, monkeypatch):
    """``lifecycle_stats()["sampler"]``: counted on the host from the host
    rows and the launch's own row windows, and equal, call for call, to the
    predicates the two conds see on the device (a spy evaluates
    ``row_needs`` inside every launch's sampler calls). Greedy traffic
    moves neither ``filtered_passes`` nor ``drawn_passes``; one
    ``temperature=0.7, top_p=0.9`` request moves both for exactly the
    passes it is live in; they stop when it finishes, although its freed
    slot keeps its settings in the host rows."""
    import jax.numpy as jnp
    from prometheus_client import CollectorRegistry

    from clearml_serving_tpu.llm import engine as engine_mod
    from clearml_serving_tpu.llm.sampling import row_needs
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    seen = []                    # (filtered, drawn) per in-launch sampler call
    real = engine_mod.sample_tokens

    def spy(logits, params, rng, *extras, live=None):
        if live is not None:     # a first token is sampled alone, uncounted
            filters, draws = row_needs(*params, live)
            jax.debug.callback(
                lambda f, d: seen.append((bool(f), bool(d))),
                jnp.any(filters), jnp.any(draws))
        return real(logits, params, rng, *extras, live=live)

    monkeypatch.setattr(engine_mod, "sample_tokens", spy)
    bundle, _, params = parts
    engine = _engine(bundle, params, cache_mode="paged", max_batch=4,
                     step_token_budget=12, decode_steps=2)

    def stats():
        jax.effects_barrier()
        s = engine.lifecycle_stats()["sampler"]
        assert s == engine.health()["sampler"]
        assert s["passes"] == len(seen)
        assert s["filtered_passes"] == sum(f for f, _ in seen)
        assert s["drawn_passes"] == sum(d for _, d in seen)
        return s

    try:
        _serve(engine, [(SHORT, 6, {}), (LONG, 8, {})])
        greedy = stats()
        assert greedy["passes"] > 0
        assert greedy["filtered_passes"] == greedy["drawn_passes"] == 0

        sampled = {"temperature": 0.7, "top_p": 0.9, "seed": 5}
        _serve(engine, [(LONG, 30, {}), (SHORT, 6, sampled), (SHORT, 4, {})])
        mixed = stats()
        moved = mixed["filtered_passes"]
        assert 0 < moved == mixed["drawn_passes"]
        # the greedy stream outlives it: launches after it left sort nothing
        assert moved < mixed["passes"] - greedy["passes"]
        assert (engine._temperature > 0).any()     # the freed slot's stale row

        _serve(engine, [(SHORT, 6, {}), (LONG, 8, {"temperature": 0.6})])
        after = stats()
        assert after["filtered_passes"] == moved   # temperature only: no sort
        assert after["drawn_passes"] > moved
        registry = CollectorRegistry()
        register_engine_lifecycle(
            engine.lifecycle_stats, registry=registry, key="m")
        for kind, name in (("all", "passes"), ("filtered", "filtered_passes"),
                           ("drawn", "drawn_passes")):
            assert registry.get_sample_value(
                "engine_sampler_passes_total", {"model": "m", "kind": kind}
            ) == after[name]
    finally:
        engine.stop()


def _primitives(jaxpr, names, under_cond=False):
    """(primitive, whether a ``cond`` encloses it) of every equation of
    ``jaxpr``, and of every jaxpr nested in it, whose primitive is in
    ``names``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            found.append((eqn.primitive.name, under_cond))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _primitives(
                sub, names, under_cond or eqn.primitive.name == "cond")
    return found


def test_launch_programs_sort_and_draw_only_under_a_cond(parts):
    """The two launch programs of a paged engine, traced with the arguments
    the engine itself hands them while serving: every whole-vocabulary
    ``sort`` and every random-bits primitive sits under a ``cond``, in the
    mixed pass, the chained steps and the decode chunk's scan alike."""
    bundle, _, params = parts
    engine = _engine(bundle, params, cache_mode="paged", max_batch=4,
                     step_token_budget=12, decode_steps=2)
    programs = {}

    def record(name):
        jitted = getattr(engine, name)

        def call(*args, **kw):
            if name not in programs:
                shapes = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
                    if hasattr(a, "shape") else a, (args, kw))
                programs[name] = jitted.trace(*shapes[0], **shapes[1]).jaxpr
            return jitted(*args, **kw)

        setattr(engine, name, call)

    try:
        record("_ragged_paged_jit")
        record("_decode_paged_chunk_jit")
        _serve(engine, [(LONG, 12, {}),
                        (SHORT, 8, {"temperature": 0.7, "top_p": 0.9})])
    finally:
        engine.stop()
    assert set(programs) == {"_ragged_paged_jit", "_decode_paged_chunk_jit"}
    costly = {"sort", "random_bits", "threefry2x32"}
    for name, jaxpr in programs.items():
        found = _primitives(jaxpr.jaxpr, costly)
        assert ("sort", True) in found and len(found) > 1, name
        assert all(under_cond for _, under_cond in found), (name, found)
