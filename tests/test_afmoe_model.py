"""arch afmoe end to end at tiny widths on the CPU (window 9 on pages of 8,
16 experts top-2 + 1 shared, two leading dense layers): the served path
(standard paged pools, the XLA twins of the two windowed kernels) against
the plain reference's full forward pass, each control told apart, the
experts' shares, int8 against float weights, the engine (a prefix-cache hit,
a preempted and resumed row, the counters by layer kind) and every refusal
by name."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.correctness import ServedWeights
from benchmark.reference import trinity_mini as ref
from clearml_serving_tpu import models
from clearml_serving_tpu.llm.engine import (
    GenRequest, LLMEngineCore, _window_pass_work,
)

F, S = "full_attention", "sliding_attention"
TINY = dict(
    vocab_size=304, dim=64, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=16,
    ffn_dim=96, rope_theta=10000.0, norm_eps=1e-5, moe_top_k=2,
    sliding_window=9, dtype="float32",
    layer_types=[S, S, S, F, S, S, S, F], router_experts=16,
    moe_intermediate_size=32, n_shared_experts=1, num_dense_layers=2,
    route_scale=2.826, route_norm=True, scoring_func="sigmoid",
    embed_scale=True, scan_layers=True,
)
PAGE, PAGES_PER_SEQ = 8, 16


def tiny(quant=None, **over):
    cfg = dict(TINY, **over)
    cfg["n_layers"] = len(cfg["layer_types"])
    bundle = models.build_model("afmoe", cfg)
    return cfg, bundle, bundle.init(jax.random.PRNGKey(1), weight_quant=quant)


def serve(bundle, params, prompt, n_new, chunk=24, probe=False,
          controls=None, counters=False):
    """One sequence as the engine drives it: the prompt in ragged chunks of
    ``chunk`` tokens through the standard pools (row 0 of two), then greedy
    decode steps. Returns (logits at the positions that produce the new
    tokens, the whole sequence, the probes of the prefill chunks, v_pools as
    the last launch returned it)."""
    shape = (bundle.n_layers, bundle.n_kv_heads, PAGES_PER_SEQ + 4, PAGE,
             bundle.head_dim)
    k = jnp.zeros(shape, jnp.float32)
    v = jnp.zeros(shape, jnp.float32)
    if counters:
        v = (v, jnp.zeros((bundle.paged_window.counters,), jnp.int32))
    table = jnp.stack([jnp.arange(1, PAGES_PER_SEQ + 1),
                       jnp.zeros(PAGES_PER_SEQ, jnp.int32)]).astype(jnp.int32)
    ragged = jax.jit(
        lambda *a, **kw: bundle.forward_ragged(*a, controls=controls, **kw),
        static_argnames=("probe",))
    decode = jax.jit(bundle.decode_paged)
    done, probes = 0, []
    while done < len(prompt):
        n = min(chunk, len(prompt) - done)
        valid = jnp.arange(chunk) < n
        pos = (done + jnp.arange(chunk)).astype(jnp.int32)
        toks = jnp.asarray(list(prompt[done:done + n]) + [0] * (chunk - n),
                           jnp.int32)
        page = table[0][jnp.minimum(pos // PAGE, PAGES_PER_SEQ - 1)]
        out = ragged(
            params, toks, pos, jnp.zeros(chunk, jnp.int32), valid,
            jnp.where(valid, jnp.arange(chunk), chunk), jnp.array([n - 1, 0]),
            k, v, table, jnp.array([done + n, 0]), jnp.array([0, 0]),
            jnp.array([n, 0]), jnp.where(valid, page, 0),
            jnp.where(valid, pos % PAGE, 0), probe=probe)
        logits, k, v = out[:3]
        if probe:
            probes.append((n, out[3]))
        done += n
    got, seq = [np.asarray(logits[0])], list(prompt)
    for _ in range(n_new - 1):
        nxt, length = int(np.argmax(got[-1])), len(seq)
        seq.append(nxt)
        logits, k, v = decode(
            params, jnp.array([nxt, 0]), k, v, table, jnp.array([length, 0]),
            jnp.array([int(table[0][length // PAGE]), 0]),
            jnp.array([length % PAGE, 0]), active=jnp.array([True, False]))
        got.append(np.asarray(logits[0]))
    return np.stack(got), seq, probes, v


def prompt_of(n, seed=0):
    return list(np.random.RandomState(seed).randint(0, 300, size=n))


def reference(cfg, params, seq, n_prompt, **controls):
    return np.asarray(ref.forward(
        cfg, ServedWeights(params), jnp.asarray(seq, jnp.int32),
        jnp.arange(n_prompt - 1, len(seq)), **controls))


# ------------------------------------------- served path against reference

@pytest.mark.parametrize("n_prompt, chunk", [
    (6, 24),     # shorter than the window: every layer sees everything
    (9, 24),     # exactly the window
    (90, 24),    # ten windows; chunks end at 24, 48, 72: off the pages' edge
    (90, 7),     # chunks shorter than the window: each straddles its edge
    (41, 16),    # chunk boundaries on the pages' edges
], ids=["shorter", "window", "longer", "small_chunks", "page_chunks"])
def test_prefill_then_decode_gives_the_references_logits(n_prompt, chunk):
    cfg, bundle, params = tiny()
    prompt = prompt_of(n_prompt)
    got, seq, _, _ = serve(bundle, params, prompt, 5, chunk=chunk)
    np.testing.assert_allclose(
        got, reference(cfg, params, seq, n_prompt), atol=2e-4)


@pytest.mark.parametrize("layer_types, scan, plan", [
    ([S, S, S, F, S, S, S, F], True, (8, 0)),
    ([S, S, S, F] * 3, True, (4, 4)),
    ([F, F, F, F], True, (2, 1)),
], ids=["eight_unrolled", "twelve_scanned", "all_full"])
def test_the_layer_plan_scans_a_tail_that_repeats(layer_types, scan, plan):
    """Eight layers have no tail that repeats twice (a lead of four, then
    one period): unrolled; twelve scan the period. Either way the served
    logits are the reference's."""
    cfg, bundle, params = tiny(layer_types=layer_types, scan_layers=scan)
    assert bundle.layer_plan == plan
    prompt = prompt_of(40, seed=2)
    got, seq, _, _ = serve(bundle, params, prompt, 3)
    np.testing.assert_allclose(
        got, reference(cfg, params, seq, len(prompt)), atol=2e-4)


CONTROLS = {
    "window_off": dict(windowed=False), "gate_off": dict(gated=False),
    "bias_off": dict(bias=False), "rope_on_full": dict(rope_full=True),
    "norms_dropped": dict(post_norms=False),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_fails_the_comparison_on_both_sides(control):
    """A mechanism switched in the reference alone, or in the served path
    alone, is far from the other; switched in both, they agree again."""
    cfg, bundle, params = tiny()
    prompt = prompt_of(60, seed=4)
    got, seq, _, _ = serve(bundle, params, prompt, 3)
    changed = reference(cfg, params, seq, len(prompt), **CONTROLS[control])
    assert np.abs(got - changed).max() > 0.02
    both, seq2, _, _ = serve(bundle, params, prompt, 1,
                             controls=CONTROLS[control])
    np.testing.assert_allclose(
        both, reference(cfg, params, seq2, len(prompt), **CONTROLS[control]),
        atol=2e-4)
    assert np.abs(both[0] - got[0]).max() > 0.02


def test_k_v_rows_rounded_one_precision_below_are_told_apart():
    cfg, bundle, params = tiny()
    prompt = prompt_of(60, seed=5)
    got, seq, _, _ = serve(bundle, params, prompt, 3)
    exact = reference(cfg, params, seq, len(prompt))
    rounded = reference(cfg, params, seq, len(prompt),
                        row_dtype=jnp.float8_e4m3fn)
    assert np.abs(got - exact).max() < 2e-4 < 0.01 < np.abs(
        got - rounded).max()


def test_each_layers_outputs_are_the_references():
    cfg, bundle, params = tiny(scan_layers=False)
    prompt = prompt_of(72, seed=3)
    _, _, probes, _ = serve(bundle, params, prompt, 1, chunk=24, probe=True)
    trace = []
    ref.forward(cfg, ServedWeights(params), jnp.asarray(prompt, jnp.int32),
                jnp.asarray([len(prompt) - 1]), trace=trace)
    for layer in range(8):
        for part in (0, 1):
            served = np.concatenate(
                [np.asarray(p[layer][part])[:n] for n, p in probes])
            np.testing.assert_allclose(
                served, np.asarray(trace[layer][part]), atol=2e-4)


# ------------------------------------------------------ the experts' shares

def _expert_layer(bundle, params, cfg, m, held=None):
    """(program, reference) output of layer 2's routed feed-forward on
    ``m``, for a chip that holds ``held`` = [first, count]."""
    over = {} if held is None else {"experts_held": list(held)}
    cfg = dict(cfg, **over)
    part = models.build_model("afmoe", cfg)
    layer = dict(params["layers"][2])
    if held is not None:
        first, count = held
        for name in ("w_gate_e", "w_up_e", "w_down_e"):
            layer[name] = layer[name][first:first + count]
    got, _ = part.ffn(layer, "moe", m, jnp.ones(m.shape[0], bool), None)
    want = ref.moe_feed_forward(
        cfg, ServedWeights.f32, ServedWeights.view(layer, None), m)
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("rank", range(8))
def test_an_expert_layers_share_is_the_references(rank):
    cfg, bundle, params = tiny()
    m = jnp.asarray(np.random.RandomState(7).randn(40, 64), jnp.float32)
    got, want = _expert_layer(bundle, params, cfg, m, (2 * rank, 2))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips of two experts each; the shared expert, which every chip
    computes alike, counted once."""
    cfg, bundle, params = tiny()
    m = jnp.asarray(np.random.RandomState(8).randn(40, 64), jnp.float32)
    layer = params["layers"][2]
    shared = np.asarray(ref.swiglu(
        ServedWeights.f32, ServedWeights.view(layer, None), m))
    parts = [_expert_layer(bundle, params, cfg, m, (2 * r, 2))[0] - shared
             for r in range(8)]
    whole, want = _expert_layer(bundle, params, cfg, m)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=1e-4)
    np.testing.assert_allclose(whole, want, atol=2e-5)


def test_the_counters_ride_beside_the_v_pool():
    """Handed ``(pool, counters)`` the passes count: an expert layer's hit
    experts (at most tokens x top-k, at most the held ones), the
    assignments (every valid token's top-k: all experts are held) and the
    expert layers run; handed the pool alone they return the pool alone."""
    cfg, bundle, params = tiny()
    prompt = prompt_of(24, seed=6)
    *_, v = serve(bundle, params, prompt, 3, counters=True)
    pool, counters = v
    hit, local, layers = (int(x) for x in np.asarray(counters)[:3])
    assert layers == 6 * 3                       # one mixed pass, two decodes
    assert local == 6 * 2 * (24 + 2)
    assert 6 * 2 <= hit <= 6 * (16 + 2 + 2)
    *_, v = serve(bundle, params, prompt, 1)
    assert not isinstance(v, tuple)


# ----------------------------------------------------------- int8 weights

def test_int8_weights_stay_close_to_the_float_ones():
    """Stated tolerance: the top-20 log-probabilities of the int8 tree lie
    within 0.3 rms of the float tree's (0.19 measured: per output channel,
    8 layers at width 64, where one weight in 64 sets a column's scale), and
    the reference on the int8 leaves agrees with the served int8 path to
    float32 rounding."""
    cfg, bundle, params = tiny()
    _, _, packed = tiny(quant="int8")
    assert set(packed["layers"][2]["wq"]) == {"_q8", "_scale"}
    assert set(packed["layers"][2]["w_attn_gate"]) == {"_q8", "_scale"}
    assert packed["layers"][2]["w_router"].dtype == jnp.float32
    prompt = prompt_of(50, seed=10)
    got, seq, _, _ = serve(bundle, packed, prompt, 2)
    np.testing.assert_allclose(
        got, reference(cfg, packed, seq, len(prompt)), atol=5e-4)
    exact = reference(cfg, params, seq, len(prompt))
    lp = lambda x: np.asarray(jax.nn.log_softmax(x, -1))  # noqa: E731
    top = np.argsort(-exact, -1)[:, :20]
    diff = np.take_along_axis(lp(got) - lp(exact), top, -1)
    assert 1e-4 < np.sqrt(np.mean(diff ** 2)) < 0.3


# ------------------------------------------------------------- the engine

@pytest.fixture(scope="module")
def engine():
    cfg, bundle, params = tiny()
    eng = LLMEngineCore(bundle, params, max_batch=2, max_seq_len=160,
                        cache_mode="paged", page_size=8, prefix_cache=8,
                        prefix_block=8, step_token_budget=32)
    yield cfg, params, eng
    eng.stop()


async def _one(eng, prompt, n_new=6, priority=None):
    kw = {} if priority is None else {"priority": priority}
    req = GenRequest(prompt_ids=list(prompt), max_new_tokens=n_new,
                     temperature=0.0, **kw)
    return [t async for t in eng.generate(req)]


def _greedy(cfg, params, prompt, out):
    seq = list(prompt) + list(out)
    want = ref.forward(cfg, ServedWeights(params),
                       jnp.asarray(seq[:-1], jnp.int32),
                       jnp.arange(len(prompt) - 1, len(seq) - 1))
    return np.argmax(np.asarray(want), -1).tolist()


def test_a_prefix_cache_hit_gives_the_references_tokens(engine):
    cfg, params, eng = engine
    doc = prompt_of(64, seed=9)
    prompts = [doc + [5, 6, 7, 8, 9, 10, 11], doc + [9, 9, 9, 1, 2, 3]]

    async def run():
        outs = [await _one(eng, prompts[0]), await _one(eng, prompts[1])]
        await eng.wait_drained()
        return outs

    outs = asyncio.run(run())
    assert eng._prefix.stats()["hit_tokens"] >= 64
    for prompt, out in zip(prompts, outs):
        assert out == _greedy(cfg, params, prompt, out)
    stats = eng.lifecycle_stats()
    assert stats["kernels"]["decode"] == stats["kernels"]["ragged"]
    assert set(stats["kv_pool"]) == {"kv", "scale", "dtype", "num_pages",
                                     "page_size", "used_pages_peak"}
    assert eng.paged_cache.v.ndim == 5           # the standard stack
    win, moe = stats["window"], stats["moe"]
    assert win["rows_window"] == 3 * win["rows_full"] > 0   # 6 window, 2 full
    assert win["decode_keys_window"] + win["mixed_keys_window"] \
        < win["window_keys_unbounded"]
    assert win["decode_keys_full"] * 3 > win["decode_keys_window"]
    assert moe["experts_held"] == 16 and moe["layer_passes"] % 6 == 0
    assert 0 < moe["experts_hit"] <= moe["layer_passes"] * 16
    assert moe["local_assignments"] == 2 * win["rows_full"] // 2 * 6
    assert "latent" not in stats


def test_a_preempted_and_resumed_row_gives_the_references_tokens(engine):
    """max_batch 2: two batch rows run, an interactive request takes a
    slot, the victim is resumed; every stream is the reference's greedy
    one, through windows that the resumed prefill crosses again."""
    cfg, params, eng = engine
    before = eng.counters["preemptions"]
    victims = [prompt_of(40, seed=20), prompt_of(44, seed=21)]
    urgent = prompt_of(30, seed=22)

    async def run():
        jobs = [asyncio.ensure_future(_one(eng, p, 40, "batch"))
                for p in victims]
        await asyncio.sleep(0.5)
        fast = await _one(eng, urgent, 4, "interactive")
        outs = [await j for j in jobs]
        await eng.wait_drained()
        return outs, fast

    outs, fast = asyncio.run(run())
    assert eng.counters["preemptions"] > before
    assert fast == _greedy(cfg, params, urgent, fast)
    for prompt, out in zip(victims, outs):
        assert len(out) == 40 and out == _greedy(cfg, params, prompt, out)


def test_window_pass_work_counts_what_a_launch_reads():
    kinds = tiny()[1].paged_window
    assert (kinds.window, kinds.n_full, kinds.n_window) == (9, 2, 6)
    # a mixed pass: a chunk of 4 queries on 20 keys and a decode row on 5;
    # the chained passes: that row twice more (6 and 7 visible keys)
    got = _window_pass_work(
        kinds, mixed_visible=[17, 18, 19, 20, 5], chain_first=[6],
        chain_passes=[2], row_lens=[4, 1, 0], kv_lens=[20, 5, 0])
    assert got == {
        "rows_full": 7 * 2, "rows_window": 7 * 6,
        "decode_keys_full": (6 + 7) * 2, "decode_keys_window": (6 + 7) * 6,
        "mixed_keys_full": (20 + 5) * 2,
        "mixed_keys_window": (min(20, 9 + 3) + 5) * 6,
        "mixed_pairs_full": (17 + 18 + 19 + 20 + 5) * 2,
        "mixed_pairs_window": (9 * 4 + 5) * 6,
        "window_keys_unbounded": (6 + 7 + 20 + 5) * 6,
    }
    long_row = _window_pass_work(kinds, chain_first=[100], chain_passes=[3])
    assert long_row["decode_keys_window"] == 3 * 9 * 6
    assert long_row["decode_keys_full"] == (100 + 101 + 102) * 2


# --------------------------------------------------------------- refusals

REFUSALS = {
    "kv_quant": (dict(kv_quant="int8"), "kv_quant cannot serve a windowed"),
    "lora": (dict(lora_rank=8), "lora adapters are not served"),
    "softmax_router": (dict(scoring_func="softmax"), "must be 'sigmoid'"),
    "layer_types": (dict(layer_types=[S, "other"]), "layer_types must name"),
    "no_window": (dict(sliding_window=0), "need a positive sliding_window"),
    "experts_held": (dict(experts_held=[12, 8]), "must lie inside"),
    "tied": (dict(tie_embeddings=True), "untied"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_what_the_model_cannot_do_is_refused_by_name(case):
    over, message = REFUSALS[case]
    with pytest.raises(ValueError, match=message):
        tiny(**over)


ENGINE_REFUSALS = {
    "dense_cache": (dict(cache_mode="dense"), "serve it with engine.cache=paged"),
    "speculation": (dict(cache_mode="paged", speculation="ngram"),
                    "speculation cannot serve a windowed paged path"),
    "lora": (dict(cache_mode="paged", lora_adapters={"a": object()}),
             "lora adapters are not served by this model"),
}


@pytest.mark.parametrize("case", sorted(ENGINE_REFUSALS))
def test_what_the_engine_cannot_do_with_it_is_refused_by_name(case):
    kw, message = ENGINE_REFUSALS[case]
    cfg, bundle, params = tiny()
    with pytest.raises(ValueError, match=message):
        LLMEngineCore(bundle, params, max_batch=2, max_seq_len=64, **kw)


def test_a_mesh_is_refused_by_name():
    from jax.sharding import Mesh

    cfg, bundle, params = tiny()
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    with pytest.raises(ValueError, match="2-device mesh cannot serve"):
        LLMEngineCore(bundle, params, max_batch=2, max_seq_len=64,
                      cache_mode="paged", mesh=mesh)


def test_verify_rows_and_the_dense_surface_are_refused_by_name():
    cfg, bundle, params = tiny()
    with pytest.raises(ValueError, match="served from engine.cache=paged"):
        bundle.prefill(params, None, None, None)
    with pytest.raises(ValueError, match="row_logit_idx"):
        bundle.forward_ragged(
            params, *([None] * 16), row_logit_idx=jnp.zeros((1, 2), jnp.int32))
    with pytest.raises(ValueError, match="k_scales"):
        bundle.decode_paged(params, *([None] * 7), k_scales=jnp.zeros(1))


def test_llama_with_a_window_is_still_refused_on_pages_by_name():
    from clearml_serving_tpu.models import llama  # noqa: F401

    bundle = models.build_model("llama", dict(
        vocab_size=300, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=96, sliding_window=16, dtype="float32", max_seq_len=128))
    params = bundle.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="arch llama need engine.cache=dense"):
        LLMEngineCore(bundle, params, max_batch=2, max_seq_len=64,
                      cache_mode="paged")
