"""Runtime compile-sentry suite (llm/compile_sentry.py + llm/warmup.py;
docs/static_analysis.md TPU6xx).

Proves the dynamic half of the compile-surface discipline end to end:

- the sentry's hook counts real XLA compilations, attributes them to the
  thread context, splits them at the warmup fence, and raises in strict
  mode through the engine's loop-boundary check;
- the shared warmup registry (llm/warmup.py) drives a real engine to ZERO
  post-fence compiles over novel in-class traffic (the full paged sweep is
  `slow`; a reduced dense sweep runs in tier-1);
- the SEEDED SHAPE-DRIFT DEFECT — `engine.compile.bucket` makes the
  prefill bucket picker return raw request lengths — is proven caught:
  post-fence compiles appear, the strict check raises naming the function,
  and the attribution carries the prefill context (acceptance criterion).
"""

import asyncio
import threading

import jax
import jax.numpy as jnp
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.llm import compile_sentry, faults
from clearml_serving_tpu.llm.compile_sentry import (
    CompileSentry,
    CompileSentryError,
)
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore


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
    yield
    faults.clear()
    # the singleton is process-wide: never leave a fence (or strictness)
    # behind for unrelated suites — post-fence state would misattribute
    # THEIR legitimate first-use compiles as violations
    if compile_sentry._sentry is not None:
        compile_sentry._sentry.reset(strict=False)


async def _collect(engine, req):
    out = []
    async for token in engine.generate(req):
        out.append(token)
    return out


# -- sentry unit behavior (private instance, no singleton) --------------------


def test_sentry_counts_fence_and_strict_raise():
    sentry = CompileSentry(strict=True).install()
    try:
        jax.jit(lambda x: x * 2)(jnp.ones((3,)))  # fresh lambda: compiles
        assert sentry.counts["warmup"] >= 1
        assert sentry.counts["serve"] == 0
        sentry.check()  # pre-fence: nothing to raise
        x5 = jnp.ones((5,))  # its eager broadcast compiles pre-fence
        sentry.fence()

        def times_three(x):
            return x * 3

        jax.jit(times_three)(x5)
        assert sentry.post_fence_compiles >= 1
        # a compile after the fence is counted with its fun_name (the
        # public jax.monitoring event carries it)
        served = [e for e in sentry.stats()["events"] if e["phase"] == "serve"]
        assert any(e["fn"] == "jit(times_three)" for e in served), served
        with pytest.raises(CompileSentryError) as exc:
            sentry.check(where="unit")
        assert "AFTER the warmup fence" in str(exc.value)
        assert "times_three" in str(exc.value)
    finally:
        sentry.uninstall()
    # uninstalled: further compiles are invisible
    before = dict(sentry.counts)
    jax.jit(lambda x: x * 5)(jnp.ones((7,)))
    assert sentry.counts == before


def test_sentry_nonstrict_counts_without_raising():
    sentry = CompileSentry(strict=False).install()
    try:
        sentry.fence()
        jax.jit(lambda x: x * 7)(jnp.ones((2,)))
        assert sentry.post_fence_compiles >= 1
        sentry.check()  # counts, never raises
    finally:
        sentry.uninstall()


def test_sentry_thread_context_attribution_and_durations():
    sentry = CompileSentry(strict=False).install()
    try:
        def worker():
            with sentry.context(phase="decode", seq=41):
                jax.jit(lambda x: x * 11)(jnp.ones((9,)))

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        tagged = [
            e for e in sentry.stats()["events"]
            if e["context"].get("phase") == "decode"
        ]
        assert tagged and tagged[0]["context"]["seq"] == 41
        # the Finished-compilation lines attach per-compile durations,
        # which feed the ms histogram
        assert any(e["duration_ms"] is not None for e in sentry.stats()["events"])
        snap = sentry.hist_snapshot()
        assert sum(snap["counts"]) >= 1 and snap["sum_ms"] > 0
    finally:
        sentry.uninstall()


def test_sentry_lazy_context_is_counted_not_violated():
    # __compile_keys__ "lazy"-role entries (e.g. _score_prompt_jit) are
    # one-bounded-compile-per-variant BY DESIGN: post-fence they count
    # into serve (observable) but never trip strict
    sentry = CompileSentry(strict=True).install()
    try:
        sentry.fence()
        with sentry.context(phase="score", lazy=True):
            jax.jit(lambda x: x * 19)(jnp.ones((6,)))
        assert sentry.post_fence_compiles >= 1
        sentry.check()  # no violation recorded
        jax.jit(lambda x: x * 23)(jnp.ones((11,)))  # outside: violation
        with pytest.raises(CompileSentryError):
            sentry.check()
    finally:
        sentry.uninstall()


def test_sentry_reset_clears_fence_and_counts():
    sentry = CompileSentry(strict=True).install()
    try:
        sentry.fence()
        jax.jit(lambda x: x * 13)(jnp.ones((4,)))
        assert sentry.post_fence_compiles >= 1
        sentry.reset(strict=False)
        assert sentry.post_fence_compiles == 0
        assert not sentry.stats()["fenced"]
        sentry.check()  # no pending violation survives a reset
    finally:
        sentry.uninstall()


# -- warmup plan enumeration (no engine needed) -------------------------------


class _StubPool:
    page_size = 16

    def pages_needed(self, tokens):
        return -(-tokens // self.page_size)


class _StubPaged:
    pool = _StubPool()


class _StubPrefix:
    block = 16


class _StubEngine:
    _vocab = 300
    _buckets = [32, 64]
    max_seq_len = 128
    max_batch = 2
    decode_steps = 1
    _prefix = _StubPrefix()
    paged_cache = _StubPaged()
    _speculation = None
    _spec_k = 4
    _ragged = False


def test_warmup_plan_covers_the_key_space():
    from clearml_serving_tpu.llm.warmup import warmup_plan

    plan = warmup_plan(_StubEngine())
    lens = {len(p["prompt_ids"]) for p in plan}
    # every prompt admissible
    assert all(0 < n < _StubEngine.max_seq_len for n in lens)
    # the implicit max_seq_len fallback bucket is part of the surface
    assert any(n > 64 for n in lens)
    # single-page resume tails sweep every final-segment length at a
    # hit bucket (prefix 48 + tails 1..16 -> 49..64)
    assert set(range(49, 65)) <= lens
    # multi-page tails reach the larger buckets (2b: e.g. a 2-page tail
    # riding a shortened prefix)
    assert len(plan) > 40
    # the cheap startup subset stays cheap
    small = warmup_plan(_StubEngine(), full=False)
    assert 0 < len(small) <= 8


def test_warmup_plan_without_prefix_cache():
    class _NoPrefix(_StubEngine):
        _prefix = None
        paged_cache = None

    from clearml_serving_tpu.llm.warmup import warmup_plan

    plan = warmup_plan(_NoPrefix())
    assert plan, "cold per-bucket pass must survive prefix-less configs"
    assert all(
        0 < len(p["prompt_ids"]) < _NoPrefix.max_seq_len for p in plan
    )


# -- engine integration: warmed serve + the seeded defect ---------------------


def test_engine_warmup_fence_and_seeded_shape_drift(parts, monkeypatch):
    """Tier-1 acceptance path on a cheap dense engine: after the reduced
    warmup + fence, in-class traffic compiles NOTHING; then the seeded
    shape-drift defect (engine.compile.bucket skips the bucketizer) makes
    a novel length mint a fresh XLA program — the sentry counts it with
    prefill attribution and the strict check kills the request through
    the loop boundary."""
    monkeypatch.setenv("TPUSERVE_COMPILE_SENTRY", "strict")
    sentry = compile_sentry.get()
    sentry.reset(strict=True)
    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64,
        prefill_buckets=[16, 32], eos_token_id=None, decode_steps=1,
    )
    assert engine._compile_sentry is sentry

    async def run():
        # reduced warmup: one pass per bucket (incl. the fallback). A
        # partial sweep must NOT self-certify (only full=True fences);
        # this test fences explicitly to exercise the machinery on a
        # cheap engine whose traffic stays inside the reduced surface.
        stats = await engine.warmup(full=False)
        assert stats["fenced"] is False
        sentry.fence()
        block = engine.lifecycle_stats()["compile"]
        assert block["fenced"] and block["warmup"] > 0
        assert block["serve"] == 0
        assert engine.health()["compile"]["warmup"] == block["warmup"]

        # in-class traffic (warmed buckets, varied content): zero compiles
        for ids in ([7, 8, 9], [5] * 14, [9] * 29, [3] * 50):
            await _collect(engine, GenRequest(
                prompt_ids=list(ids), max_new_tokens=2
            ))
        await engine.wait_drained()
        assert sentry.post_fence_compiles == 0

        # seeded defect: skip the bucketizer for one admission
        faults.configure([
            {"point": "engine.compile.bucket", "action": "raise",
             "times": 1, "message": "shape drift"},
        ])
        with pytest.raises(CompileSentryError):
            await _collect(engine, GenRequest(
                prompt_ids=[4] * 23, max_new_tokens=4
            ))
        assert sentry.post_fence_compiles > 0
        prefill_tagged = [
            e for e in sentry.stats()["events"]
            if e["phase"] == "serve"
            and e["context"].get("phase") == "prefill"
        ]
        assert prefill_tagged, "drift compile must carry prefill attribution"
        return engine.lifecycle_stats()["compile"]

    try:
        block = asyncio.run(run())
        assert block["violations"] >= 1
        assert block["serve"] >= 1
    finally:
        engine.stop()
        sentry.reset(strict=False)


def test_warmup_covers_ragged_multistep_and_spec_rows(parts, monkeypatch):
    """Multi-step / spec-as-row compile surface (docs/ragged_attention.md):
    a ragged paged engine with speculation warms every (decode window,
    spec-row) launch variant through warmup.warm_ragged_variants — novel
    OVERLAPPING traffic (q=4 windows beside admission chunk rows, spec
    verify rows in pure-decode phases) then compiles NOTHING under the
    strict fence."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    monkeypatch.setenv("TPUSERVE_COMPILE_SENTRY", "strict")
    sentry = compile_sentry.get()
    sentry.reset(strict=True)
    bundle, params = parts
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=128,
        prefill_buckets=[32, 64], eos_token_id=None, decode_steps=4,
        ragged_decode_steps=4, cache_mode="paged", page_size=16,
        scheduler="ragged", step_token_budget=32,
        speculation="ngram", spec_k=2, spec_ngram=2, pipeline_depth=1,
    )

    async def run():
        stats = await engine.warmup(full=True)
        assert stats["fenced"]
        # overlapped: a live decode stream rides q>1 windows while the
        # long prompt admits as chunk rows of the same launches
        a = GenRequest(
            prompt_ids=[5, 9, 2, 17, 5, 9, 2], max_new_tokens=24
        )
        a_task = asyncio.get_running_loop().create_task(_collect(engine, a))
        while a.produced < 2:
            await asyncio.sleep(0.005)
        await _collect(engine, GenRequest(
            prompt_ids=[(i * 7 + 3) % 250 + 1 for i in range(40)],
            max_new_tokens=6,
        ))
        await a_task
        await engine.wait_drained()
        ragged = engine.lifecycle_stats()["ragged"]
        assert ragged["step_rows"]["spec_verify"] >= 1
        assert ragged["tokens_per_launch"]["count"] >= 1
        assert sentry.post_fence_compiles == 0, sentry.stats()["events"][-5:]

    try:
        asyncio.run(run())
    finally:
        engine.stop()
        sentry.reset(strict=False)


@pytest.mark.parametrize("fleet", ["one", "disagg"])
def test_warmup_registry_covers_all_dispatch_paths_paged(
    parts, monkeypatch, fleet
):
    """Full coverage certification: a paged+prefix-cache engine, the FULL
    warmup sweep, then novel random-length traffic with shared prefixes
    under the STRICT fence — zero post-fence compiles, proving
    WARMUP_COVERED means covered. ``disagg``: the same behind a
    prefill/decode pair (docs/disaggregation.md): the sweep's transport
    block has warmed the ship export and the receive import, so shipping
    KV between the replicas compiles nothing either."""
    import random

    from clearml_serving_tpu.llm.replica import ReplicaGroup

    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    monkeypatch.setenv("TPUSERVE_COMPILE_SENTRY", "strict")
    sentry = compile_sentry.get()
    sentry.reset(strict=True)
    bundle, params = parts
    engines = [
        LLMEngineCore(
            bundle, params, replica="r{}".format(i), max_batch=2,
            max_seq_len=128,
            prefill_buckets=[32, 64], eos_token_id=None, decode_steps=1,
            cache_mode="paged", page_size=16, chunked_prefill_size=16,
            prefix_cache=64, prefix_block=16, num_pages=49,
            prefix_cache_pages=16, pipeline_depth=1,
        )
        for i in range(2 if fleet == "disagg" else 1)
    ]
    served = engines[0]
    if fleet == "disagg":
        served = ReplicaGroup(engines, roles=["prefill", "decode"])

    async def run():
        stats = await served.warmup(full=True)
        assert stats["fenced"]
        rng = random.Random(9)
        shared = [(5 * i + 3) % 250 + 1 for i in range(48)]
        for i in range(14):
            n = rng.randrange(1, 120)
            ids = [rng.randrange(1, 251) for _ in range(n)]
            if i % 3 == 0:
                ids = (shared + ids[:10])[:120]
            await _collect(served, GenRequest(
                prompt_ids=ids, max_new_tokens=3
            ))
        await served.wait_drained()
        assert sentry.post_fence_compiles == 0, sentry.stats()["events"][-5:]
        if fleet == "disagg":
            ship = engines[1]._kv_ship_snapshot()
            assert ship["receives"] >= 1 and ship["hits"] >= 1, ship

    try:
        asyncio.run(run())
    finally:
        served.stop()
        sentry.reset(strict=False)
