"""OpenAI sampling-parameter parity tests: presence/frequency/repetition
penalties, logit_bias, per-request seeds (llm/sampling.py extras + engine
threading)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.llm import sampling
from clearml_serving_tpu.llm.sampling import (
    SamplingExtras,
    SamplingParams,
    _row_keys,
    make_sampling_params,
    penalize_logits,
    row_needs,
    sample_tokens,
    warp_logits,
)

CFG = {"preset": "llama-tiny", "dtype": "float32"}


def _extras(b, v, presence=0.0, frequency=0.0, repetition=1.0, bias=None,
            seeds=None, counters=None):
    return SamplingExtras(
        presence=jnp.full((b,), presence, jnp.float32),
        frequency=jnp.full((b,), frequency, jnp.float32),
        repetition=jnp.full((b,), repetition, jnp.float32),
        bias=jnp.zeros((b, v), jnp.float32) if bias is None else jnp.asarray(bias),
        seeds=jnp.full((b,), -1, jnp.int32) if seeds is None else jnp.asarray(seeds),
        counters=jnp.zeros((b,), jnp.int32) if counters is None else jnp.asarray(counters),
    )


# -- unit: penalty math -------------------------------------------------------


def test_frequency_and_presence_math():
    logits = jnp.zeros((1, 4), jnp.float32)
    counts = jnp.asarray([[0, 1, 3, 0]], jnp.int32)
    ex = _extras(1, 4, presence=0.5, frequency=0.25)
    out = np.asarray(penalize_logits(logits, ex, counts, None))
    # token1: -0.25*1 - 0.5 = -0.75 ; token2: -0.25*3 - 0.5 = -1.25
    np.testing.assert_allclose(out[0], [0.0, -0.75, -1.25, 0.0], atol=1e-6)


def test_repetition_penalty_math():
    logits = jnp.asarray([[2.0, -2.0, 2.0, -2.0]], jnp.float32)
    counts = jnp.asarray([[1, 1, 0, 0]], jnp.int32)
    pmask = jnp.asarray([[False, False, True, True]])
    ex = _extras(1, 4, repetition=2.0)
    out = np.asarray(penalize_logits(logits, ex, counts, pmask))
    # seen positive -> /2 ; seen negative -> *2 (both output and prompt hits)
    np.testing.assert_allclose(out[0], [1.0, -4.0, 1.0, -4.0], atol=1e-6)


def test_logit_bias_forces_greedy():
    logits = jnp.zeros((2, 8), jnp.float32)
    bias = np.zeros((2, 8), np.float32)
    bias[0, 5] = 50.0
    bias[1, 2] = 50.0
    ex = _extras(2, 8, bias=bias)
    toks = sample_tokens(
        logits, make_sampling_params(2), jax.random.PRNGKey(0), ex,
        jnp.zeros((2, 8), jnp.int32), jnp.zeros((2, 8), bool),
    )
    assert list(np.asarray(toks)) == [5, 2]


def test_seeded_rows_reproducible_and_batch_independent():
    v = 64
    row = jax.random.normal(jax.random.PRNGKey(1), (1, v)) * 2.0
    logits = jnp.tile(row, (3, 1))  # identical rows: only seeds may differ
    sp = make_sampling_params(3, temperature=1.0)
    ex1 = _extras(3, v, seeds=[7, 7, -1], counters=[4, 4, 0])
    t1 = np.asarray(sample_tokens(logits, sp, jax.random.PRNGKey(0), ex1))
    t2 = np.asarray(sample_tokens(logits, sp, jax.random.PRNGKey(99), ex1))
    # rows 0/1: same seed+counter+logits -> identical regardless of the
    # shared rng; row 2 is unseeded and follows the shared stream
    assert t1[0] == t1[1] == t2[0] == t2[1]
    ex3 = _extras(3, v, seeds=[7, 8, -1], counters=[4, 4, 0])
    t3 = np.asarray(sample_tokens(logits, sp, jax.random.PRNGKey(0), ex3))
    assert t3[0] == t1[0]  # seed 7 unchanged


# -- engine-level -------------------------------------------------------------


def _engine(bundle, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("prefill_buckets", [16])
    kw.setdefault("eos_token_id", None)
    kw.setdefault("decode_steps", 2)
    return LLMEngineCore(bundle, params, **kw)


@pytest.fixture(scope="module")
def parts():
    bundle = models.build_model("llama", CFG)
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


def _gen(engine, **req_kw):
    async def run():
        req = GenRequest(**req_kw)
        return [t async for t in engine.generate(req)]

    return asyncio.run(run())


def test_presence_penalty_prevents_repeats(parts):
    bundle, params = parts
    prompt = [5, 9, 2, 17]
    engine = _engine(bundle, params)
    toks = _gen(
        engine, prompt_ids=prompt, max_new_tokens=10, presence_penalty=100.0
    )
    engine.stop()
    assert len(toks) == 10
    assert len(set(toks)) == len(toks)  # a 100-point penalty forbids repeats


def test_logit_bias_dominates_generation(parts):
    bundle, params = parts
    engine = _engine(bundle, params)
    toks = _gen(
        engine,
        prompt_ids=[5, 9, 2],
        max_new_tokens=4,
        logit_bias={42: 100.0},
    )
    engine.stop()
    assert toks == [42, 42, 42, 42]


def test_bias_plus_presence_walks_vocab(parts):
    """Bias and penalties compose: +100 bias on two tokens with a forbidding
    presence penalty alternates between exactly those two."""
    bundle, params = parts
    engine = _engine(bundle, params)
    toks = _gen(
        engine,
        prompt_ids=[5, 9, 2],
        max_new_tokens=2,
        logit_bias={42: 200.0, 43: 100.0},
        presence_penalty=150.0,
    )
    engine.stop()
    assert toks == [42, 43]


def test_seed_reproducible_across_batch_composition(parts):
    bundle, params = parts
    prompt = [5, 9, 2, 17, 33]

    engine = _engine(bundle, params)
    solo = _gen(
        engine, prompt_ids=prompt, max_new_tokens=6, temperature=1.0, seed=1234
    )
    engine.stop()

    engine2 = _engine(bundle, params)

    async def pair():
        r1 = GenRequest(
            prompt_ids=list(prompt), max_new_tokens=6, temperature=1.0, seed=1234
        )
        r2 = GenRequest(prompt_ids=[7, 7, 7], max_new_tokens=6, temperature=0.9)

        async def collect(r):
            return [t async for t in engine2.generate(r)]

        return await asyncio.gather(collect(r1), collect(r2))

    with_neighbor, _ = asyncio.run(pair())
    engine2.stop()
    assert with_neighbor == solo  # same seed -> same stream, any batch mix


def test_unseeded_sampling_still_varies(parts):
    bundle, params = parts
    engine = _engine(bundle, params, rng_seed=0)
    a = _gen(engine, prompt_ids=[5, 9, 2], max_new_tokens=8, temperature=1.0)
    engine.stop()
    engine2 = _engine(bundle, params, rng_seed=123)
    b = _gen(engine2, prompt_ids=[5, 9, 2], max_new_tokens=8, temperature=1.0)
    engine2.stop()
    assert a != b


def test_extras_disable_speculation_but_match_plain(parts):
    """Greedy + penalties on a spec-enabled engine must fall back to the
    plain chunk and match a never-speculating engine exactly."""
    bundle, params = parts
    prompt = [5, 9, 2, 17, 5, 9, 2]
    kw = dict(prompt_ids=prompt, max_new_tokens=8, presence_penalty=10.0)

    plain = _engine(bundle, params)
    want = _gen(plain, **kw)
    plain.stop()

    spec = _engine(bundle, params, speculation="ngram", spec_k=2, spec_ngram=2)
    got = _gen(spec, **kw)
    spec.stop()
    assert got == want


def test_invalid_logit_bias_rejected(parts):
    bundle, params = parts
    engine = _engine(bundle, params)

    async def run():
        req = GenRequest(
            prompt_ids=[1, 2], max_new_tokens=2, logit_bias={999999: 1.0}
        )
        with pytest.raises(ValueError):
            async for _ in engine.generate(req):
                pass

    try:
        asyncio.run(run())
    finally:
        engine.stop()


def test_min_tokens_math():
    # eos (col 3) carries the top logit but is suppressed until counters
    # reach min_new; stop sets are [B, K] -1-padded
    logits = jnp.asarray([[0.0, 0.0, 0.0, 5.0]] * 2, jnp.float32)
    ex = _extras(2, 4, counters=jnp.asarray([1, 4], jnp.int32))._replace(
        min_new=jnp.asarray([3, 3], jnp.int32),
        stop=jnp.asarray([[3, -1], [3, -1]], jnp.int32),
    )
    out = np.asarray(penalize_logits(logits, ex, None, None))
    assert out[0, 3] < -1e29          # row 0: 1 < 3 -> suppressed
    assert out[1, 3] == 5.0           # row 1: 4 >= 3 -> allowed


def test_min_tokens_suppresses_custom_stop_ids():
    # both stop tokens (cols 1 and 3) blocked until the floor
    logits = jnp.zeros((1, 4), jnp.float32)
    ex = _extras(1, 4, counters=jnp.asarray([0], jnp.int32))._replace(
        min_new=jnp.asarray([2], jnp.int32),
        stop=jnp.asarray([[1, 3]], jnp.int32),
    )
    out = np.asarray(penalize_logits(logits, ex, None, None))
    assert out[0, 1] < -1e29 and out[0, 3] < -1e29
    assert out[0, 0] == 0.0 and out[0, 2] == 0.0


def test_min_tokens_never_blanks_constrained_row():
    """When an upstream constraint (guided grammar at accept) leaves ONLY
    stop tokens admissible, the floor must yield instead of blanking the
    row (grammar wins — a blank row would sample a violating token)."""
    logits = jnp.full((1, 4), -1e30, jnp.float32).at[0, 3].set(1.0)
    ex = _extras(1, 4, counters=jnp.asarray([0], jnp.int32))._replace(
        min_new=jnp.asarray([5], jnp.int32),
        stop=jnp.asarray([[3, -1]], jnp.int32),
    )
    out = np.asarray(penalize_logits(logits, ex, None, None))
    assert out[0, 3] == 1.0  # eos stays available: nothing else is


def test_min_tokens_engine_defers_eos(parts):
    """A logit_bias that makes EOS the greedy pick must not end generation
    before min_tokens tokens were produced (vLLM min_tokens semantics)."""
    bundle, params = parts
    engine = _engine(bundle, params, eos_token_id=257)
    toks = _gen(
        engine,
        prompt_ids=[5, 9, 2],
        max_new_tokens=8,
        logit_bias={257: 100.0},       # EOS wins whenever it is allowed
        min_tokens=4,
    )
    engine.stop()
    # exactly: 4 forced non-eos tokens, then the biased EOS fires
    assert len(toks) == 5 and toks[-1] == 257
    assert all(t != 257 for t in toks[:4])


def test_min_tokens_suppresses_request_stop_tokens(parts):
    """Custom stop_token_ids must also respect the floor (vLLM semantics:
    min_tokens suppresses eos AND stop ids)."""
    bundle, params = parts
    engine = _engine(bundle, params, eos_token_id=257)
    toks = _gen(
        engine,
        prompt_ids=[5, 9, 2],
        max_new_tokens=8,
        stop_token_ids=[42],
        logit_bias={42: 100.0},
        min_tokens=4,
    )
    engine.stop()
    assert len(toks) == 5 and toks[-1] == 42
    assert all(t != 42 for t in toks[:4])


def test_min_tokens_exceeding_max_tokens_rejected(parts):
    bundle, params = parts
    engine = _engine(bundle, params, eos_token_id=257)
    with pytest.raises(ValueError):
        engine.validate(GenRequest(prompt_ids=[1], max_new_tokens=4, min_tokens=9))
    engine.stop()


def test_min_tokens_with_too_many_stop_ids_rejected(parts):
    """ADVICE r3: suppression rows hold _STOP_SLOTS ids; rather than
    silently under-enforcing the floor on the overflow ids, validate()
    rejects the combination up front."""
    bundle, params = parts
    engine = _engine(bundle, params, eos_token_id=257)
    many = list(range(100, 109))  # 9 > _STOP_SLOTS (8)
    with pytest.raises(ValueError):
        engine.validate(
            GenRequest(
                prompt_ids=[1], max_new_tokens=8, min_tokens=2,
                stop_token_ids=many,
            )
        )
    # without a floor the same stop set remains fine
    engine.validate(
        GenRequest(prompt_ids=[1], max_new_tokens=8, stop_token_ids=many)
    )
    engine.stop()


def test_paged_cache_with_penalties(parts):
    bundle, params = parts
    engine = _engine(bundle, params, cache_mode="paged", page_size=16)
    toks = _gen(
        engine,
        prompt_ids=[5, 9, 2],
        max_new_tokens=4,
        logit_bias={42: 100.0},
    )
    engine.stop()
    assert toks == [42, 42, 42, 42]

# -- the sampler does what the launch's rows asked for (PR 34) ----------------
# Plain reference: warp_logits / sample_tokens as they stood before the two
# conds (two whole-vocabulary sorts, softmax, cumsum and a draw in every
# call), copied here so the rewritten module is held to the old tokens.


def _ref_warp_logits(logits, temperature, top_k, top_p):
    n, v = logits.shape
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k = jnp.where(top_k > 0, top_k, v)
    kth = jnp.take_along_axis(
        sorted_desc, jnp.minimum(k - 1, v - 1)[:, None], axis=-1
    )
    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    sorted_scaled = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs_sorted = jax.nn.softmax(sorted_scaled, axis=-1)
    cumulative = jnp.cumsum(probs_sorted, axis=-1)
    keep_sorted = (cumulative - probs_sorted) < top_p[:, None]
    cutoff = jnp.where(
        keep_sorted, sorted_scaled, jnp.inf
    ).min(axis=-1, keepdims=True)
    return jnp.where(scaled < cutoff, -jnp.inf, scaled)


@jax.jit
def _ref_sample_tokens(logits, params, rng, extras=None, counts=None,
                       prompt_mask=None):
    b, v = logits.shape
    if extras is not None:
        logits = penalize_logits(logits, extras, counts, prompt_mask)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = _ref_warp_logits(
        logits, params.temperature, params.top_k, params.top_p
    )
    if extras is None:
        sampled = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
    else:
        keys = _row_keys(rng, extras, b)
        sampled = jax.vmap(
            lambda key, row: jax.random.categorical(key, row)
        )(keys, scaled).astype(jnp.int32)
    return jnp.where(params.temperature <= 0.0, greedy, sampled)


# (temperature, top_k, top_p) a row can ask for. "greedy_k" is a greedy row
# that also sent filter settings: still the argmax, and no sort for it.
_KINDS = {
    "greedy": (0.0, 0, 1.0),
    "greedy_k": (0.0, 7, 0.5),
    "temp": (0.8, 0, 1.0),
    "topk": (0.8, 5, 1.0),
    "topp": (0.7, 0, 0.9),
    "both": (1.3, 40, 0.95),
}
_FILTERED = ("topk", "topp", "both")


def _params_of(kinds):
    t, k, p = zip(*(_KINDS[kind] for kind in kinds))
    return SamplingParams(
        temperature=jnp.asarray(t, jnp.float32),
        top_k=jnp.asarray(k, jnp.int32),
        top_p=jnp.asarray(p, jnp.float32),
    )


def _compositions(batch):
    """Launches that cross both branches of both conds: every kind beside
    every class of neighbour (all greedy / sampling without a filter /
    filtering), and one launch of all kinds."""
    names = list(_KINDS)
    out = [[names[(r + shift) % len(names)] for r in range(batch)]
           for shift in range(len(names))]
    for others in ("greedy", "temp", "topp"):
        for first in names:
            out.append([first] + [others] * (batch - 1))
    return out


@pytest.mark.parametrize("with_extras", [False, True], ids=["plain", "extras"])
@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("vocab", [257, 32000])
def test_sampler_rows_match_reference_in_any_launch(vocab, batch, with_extras):
    rng = np.random.default_rng(vocab + batch)
    logits = jnp.asarray(rng.normal(0.0, 3.0, (batch, vocab)), jnp.float32)
    key = jax.random.PRNGKey(34)
    tail = ()
    if with_extras:
        seeds = np.where(np.arange(batch) % 2 == 0, 11 + np.arange(batch), -1)
        extras = _extras(
            batch, vocab, presence=0.4, frequency=0.1, repetition=1.2,
            bias=rng.normal(0.0, 1.0, (batch, vocab)).astype(np.float32),
            seeds=seeds.astype(np.int32),
            counters=np.arange(batch, dtype=np.int32),
        )
        counts = jnp.asarray(rng.integers(0, 3, (batch, vocab)), jnp.int32)
        pmask = jnp.asarray(rng.random((batch, vocab)) < 0.1)
        tail = (extras, counts, pmask)
        penalized = penalize_logits(logits, extras, counts, pmask)
        row_keys = _row_keys(key, extras, batch)
    else:
        penalized = logits
    greedy = np.asarray(jnp.argmax(penalized, axis=-1))

    seen = {}
    cache0 = sample_tokens._cache_size()
    for kinds in _compositions(batch):
        params = _params_of(kinds)
        got = np.asarray(sample_tokens(logits, params, key, *tail))
        ref = np.asarray(_ref_sample_tokens(logits, params, key, *tail))
        warped = np.asarray(warp_logits(penalized, *params))
        ref_warped = np.asarray(_ref_warp_logits(penalized, *params))
        scaled = penalized / jnp.maximum(params.temperature, 1e-6)[:, None]
        if with_extras:
            plain = jax.vmap(jax.random.categorical)(row_keys, scaled)
        else:
            plain = jax.random.categorical(key, scaled, axis=-1)
        plain = np.asarray(plain)
        for r, kind in enumerate(kinds):
            if kind.startswith("greedy"):
                assert got[r] == greedy[r] == ref[r], (kinds, r)
            elif kind in _FILTERED:
                # bit for bit the old masked logits, so the old token
                assert got[r] == ref[r], (kinds, r)
                np.testing.assert_array_equal(warped[r], ref_warped[r])
                assert np.isinf(warped[r]).any()
            else:
                # no filter asked for, none applied, in either branch
                assert got[r] == plain[r], (kinds, r)
                np.testing.assert_array_equal(warped[r], np.asarray(scaled[r]))
            # the same row with the same settings draws the same token
            # whoever shares its launch
            assert seen.setdefault((r, kind), got[r]) == got[r], (kinds, r)
    # the settings are data: every mix above ran one executable
    assert sample_tokens._cache_size() - cache0 <= 1


def test_row_needs_is_the_predicate_of_both_conds():
    t = np.asarray([0.0, 0.0, 0.8, 0.8, 0.7, 0.7], np.float32)
    k = np.asarray([0, 7, 0, 5, 0, 0], np.int32)
    p = np.asarray([1.0, 0.5, 1.0, 1.0, 0.9, 0.9], np.float32)
    live = np.asarray([True, True, True, True, True, False])
    filters, draws = row_needs(t, k, p, live)
    assert filters.tolist() == [False, False, False, True, True, False]
    assert draws.tolist() == [False, False, True, True, True, False]
    filters, draws = row_needs(jnp.asarray(t), jnp.asarray(k), jnp.asarray(p))
    assert np.asarray(filters).tolist() == [False, False, False, True, True, True]
    assert np.asarray(draws).tolist() == [False, False, True, True, True, True]


@pytest.mark.parametrize(
    "kinds, live, sorts, draws",
    [
        (["greedy", "greedy_k", "greedy", "greedy"], None, 0, 0),
        (["greedy", "temp", "greedy", "greedy"], None, 0, 1),
        (["greedy", "temp", "topp", "greedy"], None, 1, 1),
        (["topk", "both", "topp", "topp"], None, 1, 1),
        # a freed slot keeps its last request's settings: neither branch
        (["greedy", "greedy", "topp", "temp"], [True, True, False, False], 0, 0),
        (["greedy", "temp", "topp", "greedy"], [True, True, False, True], 0, 1),
        (["greedy", "temp", "topp", "greedy"], [True, False, True, True], 1, 1),
    ],
)
def test_sampler_runs_only_the_branch_its_live_rows_need(
    monkeypatch, kinds, live, sorts, draws
):
    """Eagerly (no jit) a cond runs the branch its predicate picks and
    nothing of the other, so spies on the sort and the draw see exactly
    what a launch of these rows costs."""
    calls = {"sort": 0, "draw": 0}
    real_sort, real_draw = jnp.sort, jax.random.categorical

    def sort(*a, **kw):
        calls["sort"] += 1
        return real_sort(*a, **kw)

    def draw(*a, **kw):
        calls["draw"] += 1
        return real_draw(*a, **kw)

    monkeypatch.setattr(sampling.jnp, "sort", sort)
    monkeypatch.setattr(sampling.jax.random, "categorical", draw)
    logits = jnp.asarray(
        np.random.default_rng(5).normal(0.0, 3.0, (4, 257)), jnp.float32
    )
    mask = None if live is None else jnp.asarray(live)
    with jax.disable_jit():
        got = np.asarray(sample_tokens(
            logits, _params_of(kinds), jax.random.PRNGKey(2), live=mask
        ))
    assert (calls["sort"], calls["draw"]) == (sorts, draws)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    for r, kind in enumerate(kinds):
        if kind.startswith("greedy") or (live is not None and not live[r]):
            assert got[r] == greedy[r]
