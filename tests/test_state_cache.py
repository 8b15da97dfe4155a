"""The state cache (docs/state_cache.md): LLMEngineCore with ``cache_mode=
"state"`` serving a power-retention model at tiny widths on the CPU — the
served path against the plain reference, slots that change hands, windows
that close early, preemption by recompute, and every refusal by name."""

import asyncio
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from clearml_serving_tpu import models  # noqa: E402
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore  # noqa: E402
from clearml_serving_tpu.llm.kv_cache import StateCache  # noqa: E402

CFG = dict(vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
           head_dim=16, ffn_dim=96, scan_layers=True, dtype="float32",
           attention="power_retention", retention_degree=2, qk_norm=True,
           norm_eps=1e-6, rope_theta=1e6, max_seq_len=512)
MODEL = dict(CFG)    # what the reference is given


@pytest.fixture(scope="module")
def parts():
    bundle = models.build_model("llama", CFG)
    return bundle, bundle.init(jax.random.PRNGKey(3))


def make_engine(parts, **kw):
    bundle, params = parts
    args = dict(max_batch=3, max_seq_len=256, cache_mode="state",
                scheduler="ragged", step_token_budget=16, decode_steps=4,
                eos_token_id=None)
    args.update(kw)
    return LLMEngineCore(bundle, params, **args)


async def collect(engine, request):
    return [t async for t in engine.generate(request)]


def prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 97, size=n).tolist() for n in lengths]


def greedy(parts, prompt, n):
    """n greedy tokens by the model's full causal forward (attention form)."""
    bundle, params = parts
    ids, out = list(prompt), []
    for _ in range(n):
        nxt = int(jnp.argmax(bundle.apply(params, jnp.asarray([ids]))[0, -1]))
        out.append(nxt)
        ids.append(nxt)
    return out


# ------------------------------------------------------------- the slots

def test_state_cache_slot_contract(parts):
    cache = StateCache(parts[0].init_state, 3)
    assert cache.s.shape == (2, 3, 2, 16, 144) and cache.z.shape == (2, 3, 2, 16, 16)
    assert cache.s.dtype == jnp.float32 and cache.in_use == 0
    cache.allocate(1)
    with pytest.raises(RuntimeError, match="already owned"):
        cache.allocate(1)
    assert cache.length(1) == 0 and cache.owned(1) and not cache.owned(0)
    cache.advance(1, 16)        # the launch that carried position 0: a reset
    cache.advance(1, 4)
    assert (cache.length(1), cache.resets) == (20, 1)
    cache.rewind(1)             # recompute: the next launch resets again
    cache.advance(1, 20)
    assert (cache.length(1), cache.resets, cache.rewinds) == (20, 2, 1)
    cache.free(1)
    cache.free(1)               # idempotent
    snap = cache.snapshot()
    assert snap["in_use"] == 0 and snap["in_use_peak"] == 1 and snap["slots"] == 3
    assert snap["bytes"] == 3 * snap["bytes_per_slot"] == cache.s.nbytes + cache.z.nbytes


# ------------------------------------------- served path vs the reference

def test_served_logprobs_agree_with_the_plain_reference(parts):
    """Prefill in chunks through the ragged step, then decode through the
    state pool, against benchmark/reference/brumby.py (the attention form) on
    log-probabilities: float32 on both sides."""
    from benchmark import correctness as cx

    engine = make_engine(parts)
    prompt = prompts([70])[0]           # five chunks of the 16-token budget
    request = GenRequest(prompt_ids=list(prompt), max_new_tokens=9,
                         temperature=0.0, logprobs=5)
    got = asyncio.run(collect(engine, request))
    assert len(got) == 9 and len(request.logprob_entries) == 9
    ref = np.asarray(cx.reference_logprobs(
        "brumby", MODEL, cx.ServedWeights(parts[1]), prompt, got))
    worst = 0.0
    for pos, entry in enumerate(request.logprob_entries):
        assert entry["id"] == got[pos]
        for tok, lp in zip(entry["top_ids"], entry["top_logprobs"]):
            worst = max(worst, abs(lp - float(ref[pos, tok])))
    assert worst < 5e-4, worst
    assert engine.counters["ragged_prefill_tokens"] == 70
    engine.stop()


@pytest.mark.parametrize("quant", [None, "int8"])
def test_reference_agrees_with_the_models_full_forward(quant):
    from benchmark import correctness as cx

    bundle = models.build_model("llama", CFG)
    params = (bundle.init(jax.random.PRNGKey(5), weight_quant=quant) if quant
              else bundle.init(jax.random.PRNGKey(5)))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 97, 40), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = jax.nn.log_softmax(bundle.apply(params, tokens[None])[0, 35:40], -1)
    ref = cx.reference_logprobs("brumby", MODEL, cx.ServedWeights(params),
                                list(map(int, tokens[:36])),
                                list(map(int, tokens[36:])) + [0])
    assert np.max(np.abs(np.asarray(ref) - np.asarray(want))) < 2e-4


def test_reference_query_blocks_do_not_change_the_answer(monkeypatch):
    from benchmark import correctness as cx
    from benchmark.reference import brumby

    bundle = models.build_model("llama", CFG)
    params = bundle.init(jax.random.PRNGKey(6))
    tokens = list(map(int, np.random.default_rng(2).integers(0, 97, 50)))
    whole = cx.reference_logprobs("brumby", MODEL, cx.ServedWeights(params),
                                  tokens[:45], tokens[45:] + [0])
    monkeypatch.setattr(brumby, "Q_BLOCK", 16)      # 50 tokens: 4 blocks, the last moved back
    monkeypatch.setattr(brumby, "VOCAB_BLOCK", 40)
    blocks = cx.reference_logprobs("brumby", MODEL, cx.ServedWeights(params),
                                   tokens[:45], tokens[45:] + [0])
    assert np.max(np.abs(np.asarray(whole) - np.asarray(blocks))) < 1e-5


# ------------------------------------- chunking, windows, slots, recompute

@pytest.mark.parametrize("budget", [8, 16, 200])
def test_however_the_prompt_is_chunked_the_stream_is_the_same(parts, budget):
    """A 150-token prompt in one chunk (budget 200), by 16 and by 8 (with
    three rows, a chunk is what the budget leaves): the greedy stream is the
    attention form's."""
    engine = make_engine(parts, step_token_budget=budget)
    prompt = prompts([150], seed=4)[0]
    got = asyncio.run(collect(engine, GenRequest(
        prompt_ids=list(prompt), max_new_tokens=8, temperature=0.0)))
    assert got == greedy(parts, prompt, 8)
    engine.stop()


def test_chunking_invariance_of_the_logits(parts):
    """The model step itself: one prompt as 1 x N, by 128 and token by token
    gives the same last-token logits within float32 rounding."""
    bundle, params = parts
    ids = np.random.default_rng(7).integers(0, 97, 200).astype(np.int32)

    def run(cuts):
        s_pool, z_pool = bundle.init_state(1)
        step = jax.jit(bundle.forward_ragged_state)
        pos, logits = 0, None
        for n in cuts:
            t = -(-n // 8) * 8 + 8
            tok, tp, tv = np.zeros(t, np.int32), np.zeros(t, np.int32), np.zeros(t, bool)
            tok[:n], tp[:n], tv[:n] = ids[pos:pos + n], pos + np.arange(n), True
            logits, s_pool, z_pool = step(
                params, *map(jnp.asarray, (tok, tp, np.zeros(t, np.int32), tv)),
                jnp.asarray([n - 1]), s_pool, z_pool, jnp.asarray([0]),
                jnp.asarray([n]), jnp.asarray([pos == 0]))
            pos += n
        return np.asarray(logits[0])

    whole, by128, single = run([200]), run([128, 72]), run([1] * 200)
    assert np.abs(whole - by128).max() < 2e-4 and np.abs(whole - single).max() < 2e-4
    want = np.asarray(bundle.apply(params, jnp.asarray(ids)[None])[0, -1])
    assert np.abs(whole - want).max() < 2e-4


def test_slots_change_hands_and_windows_close_early(parts):
    """Seven requests through three slots, answers of unequal length under
    4-step decode windows: a window that closes early (a row out of tokens)
    must not move its slot, and a reused slot must carry nothing over. Every
    stream is the attention form's."""
    engine = make_engine(parts)
    lens = [5, 40, 23, 9, 31, 17, 2]
    news = [12, 3, 7, 10, 1, 6, 9]
    ps = prompts(lens)

    async def main():
        return await asyncio.gather(*[
            collect(engine, GenRequest(prompt_ids=list(p), max_new_tokens=n,
                                       temperature=0.0))
            for p, n in zip(ps, news)])

    got = asyncio.run(main())
    for p, n, out in zip(ps, news, got):
        assert out == greedy(parts, p, n)
    pool = engine.lifecycle_stats()["state_pool"]
    assert pool["in_use"] == 0 and pool["in_use_peak"] == 3 and pool["resets"] == 7
    assert engine.health()["state_pool"]["slots"] == 3
    assert engine.lifecycle_stats()["kv_pool"] is None
    ragged = engine.lifecycle_stats()["ragged"]
    assert ragged["prefill_tokens"] == sum(lens)
    assert ragged["passes"] >= ragged["steps"] > 0
    engine.stop()


def test_pad_positions_and_idle_rows_leave_a_slot_bit_identical(parts):
    """decode_state with a row masked out (a closed window's pad position, a
    row in prefill, an idle slot) returns that slot bit for bit."""
    bundle, params = parts
    rng = np.random.default_rng(3)
    s_pool, z_pool = bundle.init_state(3)
    s_pool = jnp.asarray(rng.normal(size=s_pool.shape), jnp.float32)
    z_pool = jnp.asarray(rng.normal(size=z_pool.shape) + 3.0, jnp.float32)
    _, s_new, z_new = jax.jit(bundle.decode_state)(
        params, jnp.asarray([5, 6, 7]), s_pool, z_pool, jnp.asarray([4, 9, 2]),
        jnp.asarray([True, False, True]))
    assert np.array_equal(np.asarray(s_new[:, 1]), np.asarray(s_pool[:, 1]))
    assert np.array_equal(np.asarray(z_new[:, 1]), np.asarray(z_pool[:, 1]))
    assert not np.array_equal(np.asarray(s_new[:, 0]), np.asarray(s_pool[:, 0]))


def test_preemption_frees_the_slot_and_recompute_reproduces_the_stream(parts):
    prompt = prompts([17], seed=9)[0]
    n_new = 24

    async def contended():
        engine = make_engine(parts, max_batch=1, preempt_batch=True,
                             preempt_budget=2, step_token_budget=8)
        batch = GenRequest(prompt_ids=list(prompt), max_new_tokens=n_new,
                           temperature=0.0, priority="batch")
        task = asyncio.create_task(collect(engine, batch))
        while batch.produced < 6:
            await asyncio.sleep(0.005)
        hi = GenRequest(prompt_ids=[1, 9, 9], max_new_tokens=2, temperature=0.0)
        assert len(await asyncio.wait_for(collect(engine, hi), 60)) == 2
        out = await asyncio.wait_for(task, 60)
        await engine.wait_drained()
        return engine, out

    engine, got = asyncio.run(contended())
    assert engine.counters["preemptions"] >= 1, "no preemption happened"
    assert got == greedy(parts, prompt, n_new)
    pool = engine.lifecycle_stats()["state_pool"]
    assert pool["in_use"] == 0 and pool["resets"] >= 3    # batch, hi, batch again
    engine.stop()


# ---------------------------------------------------------------- refusals

@pytest.mark.parametrize("kw,reason", [
    (dict(prefix_cache=64), "prefix_cache cannot serve engine.cache=state"),
    (dict(prefix_cache_host_pages=8), "HostKVTier"),
    (dict(speculation="ngram"), "speculation cannot serve engine.cache=state"),
    (dict(scheduler="two_dispatch"),
     "cache=state runs the ragged scheduler; scheduler='two_dispatch' exists "
     "only on cache=dense"),
    (dict(lora_adapters={"a": {}}), "lora_adapters are not served"),
    (dict(cache_mode="paged"), "serve it with engine.cache=state"),
    (dict(cache_mode="dense"), "serve it with engine.cache=state"),
])
def test_what_assumes_pages_refuses_the_state_cache_by_name(parts, kw, reason):
    with pytest.raises(ValueError, match=reason):
        make_engine(parts, **kw)


def test_kv_quant_shipment_and_softmax_models_say_no_too(parts):
    with pytest.raises(ValueError, match="kv_quant"):
        models.build_model("llama", dict(CFG, kv_quant="int8"))
    engine = make_engine(parts)
    with pytest.raises(ValueError, match="KVShipment"):
        engine.attach_kv_transport(object(), role="prefill")
    engine.stop()
    softmax = models.build_model("llama", {k: v for k, v in CFG.items()
                                           if k != "attention"})
    with pytest.raises(ValueError, match="attends over keys and values"):
        LLMEngineCore(softmax, softmax.init(jax.random.PRNGKey(0)),
                      cache_mode="state", scheduler="ragged")
    with pytest.raises(ValueError, match="degree 2 only"):
        models.build_model("llama", dict(CFG, retention_degree=3))


def test_kernels_block_and_prometheus_families(parts):
    from prometheus_client import CollectorRegistry

    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    engine = make_engine(parts)
    kernels = engine.health()["kernels"]
    assert kernels["decode"] == kernels["ragged"] == "xla"
    assert "TPU only" in kernels["reason"]["decode"]
    asyncio.run(collect(engine, GenRequest(prompt_ids=[4, 5, 6], max_new_tokens=2,
                                           temperature=0.0)))
    registry = CollectorRegistry()
    register_engine_lifecycle(engine.lifecycle_stats, registry=registry, key="m")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m", **labels})

    assert val("engine_state_pool_slots", state="total") == 3
    assert val("engine_state_pool_slots", state="in_use_peak") == 1
    assert val("engine_state_pool_resets_total") == 1
    per_slot = val("engine_state_pool_bytes", kind="slot")
    assert val("engine_state_pool_bytes", kind="pool") == 3 * per_slot
    assert val("engine_kv_pool_bytes", kind="kv") is None
    engine.stop()


def test_the_warmup_sweep_compiles_every_window_of_the_state_step(parts):
    from clearml_serving_tpu.llm import warmup

    engine = make_engine(parts)
    before = np.asarray(engine.state_cache.s).copy()

    async def main():
        out = await warmup.run_warmup(engine, full=True, fence=False)
        await engine.wait_drained()
        return out

    out = asyncio.run(main())
    assert out["requests"] > 0
    assert warmup.warm_ragged_variants(engine) == 3        # windows 1, 2, 4
    # null rows moved nothing; the sweep's own requests freed their slots
    assert engine.lifecycle_stats()["state_pool"]["in_use"] == 0
    assert "_ragged_state_jit" in warmup.WARMUP_COVERED
    assert before.shape == np.asarray(engine.state_cache.s).shape
    engine.stop()


def test_a_bfloat16_state_is_told_apart_from_the_float32_one(parts):
    """``state_round="bfloat16"`` (the measuring device behind the cell's
    tolerance): the same weights with the state rounded after every update
    move the logits by far more than float32 rounding does."""
    bundle, params = parts
    rounded = models.build_model("llama", dict(CFG, state_round="bfloat16"))
    ids = np.random.default_rng(8).integers(0, 97, 64).astype(np.int32)

    def two_chunks(b):
        s_pool, z_pool = b.init_state(1)
        step = jax.jit(b.forward_ragged_state)
        for start in (0, 32):
            tok = jnp.asarray(ids[start:start + 32])
            logits, s_pool, z_pool = step(
                params, tok, start + jnp.arange(32, dtype=jnp.int32),
                jnp.zeros(32, jnp.int32), jnp.ones(32, bool),
                jnp.asarray([0 if start else 31]),
                s_pool, z_pool, jnp.asarray([0]), jnp.asarray([32]),
                jnp.asarray([start == 0]))
        # the second chunk's FIRST token: with gates near one half nothing
        # of the stored state reaches a token 32 places on
        return np.asarray(logits[0])

    exact, coarse = two_chunks(bundle), two_chunks(rounded)
    want = np.asarray(bundle.apply(params, jnp.asarray(ids)[None])[0, 32])
    assert np.abs(exact - want).max() < 2e-4
    assert np.abs(coarse - want).max() > 10 * np.abs(exact - want).max()
    with pytest.raises(ValueError, match="state_round"):
        models.build_model("llama", dict(CFG, state_round="float16"))
