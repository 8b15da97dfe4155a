"""The latent page layout end to end at tiny widths on the CPU (top-16
selection on contexts to 96, window 9, 16 experts of which 4 held): the
served path (absorbed form, paged planes, the XLA twins) against the plain
reference's full forward pass, the kernels in interpret mode against their
twins, the engine's prefix cache on latent pages, and every refusal by name.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.correctness import ServedWeights
from benchmark.reference import dots3_note as ref
from clearml_serving_tpu import models
from clearml_serving_tpu.llm.engine import (
    GenRequest, LLMEngineCore, _latent_pass_work,
)
from clearml_serving_tpu.models import dots3_note, llama
from clearml_serving_tpu.ops import latent_attention as la
from clearml_serving_tpu.ops.paged_attention import (
    paged_kernel_unsupported_reason, ragged_layout, ragged_view_tokens,
    ragged_work_items,
)

F, S = "full_attention", "sliding_attention"
TINY = dict(
    vocab_size=300, dim=64, n_layers=6, n_heads=4, ffn_dim=96,
    rope_theta=8e7, norm_eps=1e-5, moe_top_k=2, dtype="float32",
    layer_types=[F, F, S, F, S, F],
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
    swa_num_attention_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=48,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
    swa_rope_theta=50000.0, sliding_window_size=9, index_topk=16,
    index_n_heads=4, index_head_dim=16, moe_intermediate_size=32,
    router_experts=16, experts_held=[4, 4], n_shared_experts=1,
    first_k_dense_replace=1, scan_layers=True,
)
PAGE, PAGES_PER_SEQ, CHUNK = 8, 16, 24


def tiny(**over):
    cfg = dict(TINY, **over)
    cfg["n_layers"] = len(cfg["layer_types"])
    bundle = models.build_model("dots3_note", cfg)
    return cfg, bundle, bundle.init(jax.random.PRNGKey(1))


def serve(bundle, params, prompt, n_new, probe=False):
    """One sequence as the engine drives it: the prompt in ragged chunks
    through the paged planes (row 0 of two), then greedy decode steps.
    Returns (logits at the positions that produce the new tokens, the whole
    sequence, the probes of the prefill chunks)."""
    k, v = bundle.paged_layout.init_pools(PAGES_PER_SEQ + 4, PAGE)
    table = jnp.stack([jnp.arange(1, PAGES_PER_SEQ + 1),
                       jnp.zeros(PAGES_PER_SEQ, jnp.int32)]).astype(jnp.int32)
    ragged = jax.jit(bundle.forward_ragged, static_argnames=("probe",))
    decode = jax.jit(bundle.decode_paged)
    done, probes = 0, []
    while done < len(prompt):
        n = min(CHUNK, len(prompt) - done)
        valid = jnp.arange(CHUNK) < n
        pos = (done + jnp.arange(CHUNK)).astype(jnp.int32)
        toks = jnp.asarray(list(prompt[done:done + n]) + [0] * (CHUNK - n),
                           jnp.int32)
        page = table[0][jnp.minimum(pos // PAGE, PAGES_PER_SEQ - 1)]
        out = ragged(
            params, toks, pos, jnp.zeros(CHUNK, jnp.int32), valid,
            jnp.where(valid, jnp.arange(CHUNK), CHUNK), jnp.array([n - 1, 0]),
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
    return np.stack(got), seq, probes


def prompt_of(n, seed=0):
    return list(np.random.RandomState(seed).randint(0, 300, size=n))


# ------------------------------------------- served path against reference

@pytest.mark.parametrize("layer_types, scan", [
    ([F, F], False), ([S, S], False), ([F, F, S, F, S, F], True),
    ([F, S, S, F], False),
], ids=["full_layers", "window_layers", "mixed_scanned", "mixed_unrolled"])
def test_prefill_then_decode_gives_the_references_logits(layer_types, scan):
    cfg, bundle, params = tiny(layer_types=layer_types, scan_layers=scan)
    prompt = prompt_of(90)
    got, seq, _ = serve(bundle, params, prompt, 5)
    want = ref.forward(cfg, ServedWeights(params), jnp.asarray(seq, jnp.int32),
                       jnp.arange(len(prompt) - 1, len(seq)))
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4)


def test_the_scan_covers_the_repeating_tail_and_unrolls_the_rest():
    assert tiny()[1].layer_plan == (2, 2)


@pytest.mark.parametrize("kinds, want", [
    ("FFSSSFSSSF", (2, 4)), ("FSSSFSSS", (0, 4)), ("FFS", (3, 0)),
    ("FSFS", (0, 2)), ("FFFF", (1, 1)), ("SSSS", (0, 1)),
])
def test_layer_plan(kinds, want):
    table = [(c, "dense" if i == 0 and kinds.startswith("FF") else "moe")
             for i, c in enumerate(kinds)]
    assert dots3_note.layer_plan(table, True) == want
    assert ref.layer_plan(table, True) == want
    assert dots3_note.layer_plan(table, False) == (len(kinds), 0)


@pytest.fixture(scope="module")
def probed():
    cfg, bundle, params = tiny(layer_types=[F, S, F], scan_layers=False)
    prompt = prompt_of(72, seed=3)
    _, _, probes = serve(bundle, params, prompt, 1, probe=True)
    trace = []
    ref.forward(cfg, ServedWeights(params), jnp.asarray(prompt, jnp.int32),
                jnp.asarray([len(prompt) - 1]), trace=trace)
    return probes, trace


@pytest.mark.parametrize("layer", [0, 2])
def test_the_selected_sets_are_the_references(probed, layer):
    probes, trace = probed
    visible = np.asarray(trace[layer][1])
    t = 0
    for n, chunk in probes:
        _, pos, count, _ = chunk[layer]
        for i in range(n):
            chosen = set(np.asarray(pos[i])[:int(count[i])].tolist())
            assert chosen == set(np.nonzero(visible[t])[0].tolist()), t
            assert len(chosen) == min(t + 1, TINY["index_topk"])
            t += 1


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_the_absorbed_attention_is_the_expanded_one(probed, layer):
    probes, trace = probed
    got = np.concatenate([np.asarray(c[layer][0])[:n] for n, c in probes])
    np.testing.assert_allclose(got, np.asarray(trace[layer][0]), atol=1e-4)


def test_the_feed_forwards_output_is_the_references(probed):
    probes, trace = probed
    for layer in range(3):
        got = np.concatenate([np.asarray(c[layer][3])[:n] for n, c in probes])
        np.testing.assert_allclose(got, np.asarray(trace[layer][2]), atol=1e-4)


def test_rows_rounded_one_precision_below_are_told_apart():
    cfg, bundle, params = tiny(layer_types=[F, S])
    prompt = prompt_of(60, seed=6)
    got, seq, _ = serve(bundle, params, prompt, 2)
    args = (cfg, ServedWeights(params), jnp.asarray(seq, jnp.int32),
            jnp.arange(len(prompt) - 1, len(seq)))
    low = np.asarray(ref.forward(*args, row_dtype=jnp.float8_e4m3fn))
    assert np.abs(got - low).max() > 50 * np.abs(
        got - np.asarray(ref.forward(*args))).max()


@pytest.mark.parametrize("control", ["indexer", "windowed", "bias"])
def test_a_control_that_switches_a_mechanism_off_is_told_apart(control):
    cfg, bundle, params = tiny(layer_types=[F, S], first_k_dense_replace=0)
    prompt = prompt_of(80, seed=5)
    got, seq, _ = serve(bundle, params, prompt, 2)
    args = (cfg, ServedWeights(params), jnp.asarray(seq, jnp.int32),
            jnp.arange(len(prompt) - 1, len(seq)))
    np.testing.assert_allclose(got, np.asarray(ref.forward(*args)), atol=2e-4)
    off = np.asarray(ref.forward(*args, **{control: False}))
    assert np.abs(got - off).max() > 1e-2


# ------------------------------------------------------------ the experts

@pytest.mark.parametrize("rank", range(8))
def test_an_expert_layers_share_is_the_references(rank):
    """The served FFN of rank r of eight holds experts [2r, 2r + 2) and gives
    the reference's share; the eight shares with the shared expert counted
    once add up to the uncut layer."""
    cfg, bundle, params = tiny(layer_types=[S], first_k_dense_replace=0,
                               experts_held=[2 * rank, 2])
    h = jax.random.normal(jax.random.PRNGKey(7), (12, 64), jnp.float32)
    layer = params["layers"][0]
    got, _ = bundle.ffn(layer, "moe", h, jnp.ones(12, bool),
                        jnp.zeros(8, jnp.int32))
    view = ServedWeights.view(layer, None)
    want = ref.moe_feed_forward(cfg, ServedWeights.f32, view, h)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # the uncut layer from the same router and shared expert: every expert's
    # own weights drawn as this rank's are, so only the routed sum is cut
    routed = ref.moe_feed_forward(cfg, ServedWeights.f32, view, h, shared=False)
    shared = ref.swiglu(ServedWeights.f32, view, h)
    np.testing.assert_allclose(want, routed + shared, atol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    cfg, _, params = tiny(layer_types=[S], first_k_dense_replace=0,
                          experts_held=[0, 16])
    layer = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(8), (12, 64), jnp.float32)
    view = ServedWeights.view(layer, None)
    whole = ref.moe_feed_forward(cfg, ServedWeights.f32, view, h)
    total = ref.swiglu(ServedWeights.f32, view, h)
    for rank in range(8):
        held = dict(layer, **{
            k: layer[k][2 * rank:2 * rank + 2]
            for k in ("w_gate_e", "w_up_e", "w_down_e")})
        total = total + ref.moe_feed_forward(
            dict(cfg, experts_held=[2 * rank, 2]), ServedWeights.f32,
            ServedWeights.view(held, None), h, shared=False)
    np.testing.assert_allclose(total, whole, atol=1e-5)


def test_mixtrals_routing_is_unchanged_bit_for_bit():
    logits = jax.random.normal(jax.random.PRNGKey(0), (33, 8), jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, 2)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    got_p, got_e = llama.moe_route(logits, 2)
    assert (np.asarray(got_p) == np.asarray(top_p)).all()
    assert (np.asarray(got_e) == np.asarray(top_e)).all()


def test_mixtrals_dropless_ffn_is_unchanged_bit_for_bit():
    bundle = models.build_model("llama", {
        "preset": "llama-tiny", "dtype": "float32", "n_experts": 4})
    params = bundle.init(jax.random.PRNGKey(0))
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 1, bundle.config["dim"]))
    tokens = x.reshape(3, -1)
    probs = jax.nn.softmax(tokens @ layer["w_router"].astype(jnp.float32), -1)
    top_p, top_e = jax.lax.top_k(probs, 2)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    weights = jnp.zeros((3, 4), jnp.float32).at[
        jnp.arange(3)[:, None], top_e].add(top_p)
    per = jnp.broadcast_to(tokens[None], (4,) + tokens.shape)
    hid = jax.nn.silu(jnp.einsum("etd,edf->etf", per, layer["w_gate_e"])) \
        * jnp.einsum("etd,edf->etf", per, layer["w_up_e"])
    out = jnp.einsum("te,etd->td", weights,
                     jnp.einsum("etf,efd->etd", hid, layer["w_down_e"]))
    got = bundle.ffn(layer, x, dropless=True)
    assert (np.asarray(got) == np.asarray(out.reshape(x.shape))).all()


def test_sigmoid_routing_selects_by_the_bias_and_weighs_without_it():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([0.0, 0.0, 5.0, 0.0])
    p, e = llama.moe_route(logits, 2, scoring="sigmoid", bias=bias, scale=2.0)
    assert sorted(np.asarray(e)[0].tolist()) == [0, 2]
    s = jax.nn.sigmoid(logits)[0]
    want = {0: float(s[0] / (s[0] + s[2]) * 2), 2: float(s[2] / (s[0] + s[2]) * 2)}
    for gate, expert in zip(np.asarray(p)[0], np.asarray(e)[0]):
        assert gate == pytest.approx(want[int(expert)], rel=1e-6)


# ----------------------------------------------- kernels against the twins

L, N, W, H, V, R = 2, 40, 256, 4, 128, 3
RNG = np.random.RandomState(0)
POOL = jnp.asarray(RNG.randn(L, 1, N, PAGE, W), jnp.float32)
TABLE = jnp.asarray(RNG.permutation(np.arange(1, N))[:R * 12].reshape(R, 12),
                    jnp.int32)
LENGTHS = jnp.asarray([37, 0, 90], jnp.int32)


def _selection(rows, pos, valid, k=16):
    chosen = jnp.asarray(np.stack(
        [RNG.permutation(96)[:k] for _ in range(len(rows))]), jnp.int32)
    n = jnp.where(jnp.asarray(valid), jnp.minimum(jnp.asarray(pos) + 1, k), 0)
    return (TABLE[jnp.asarray(rows)[:, None], chosen // PAGE], chosen % PAGE,
            n.astype(jnp.int32))


@pytest.mark.parametrize("window, selected", [
    (0, False), (9, False), (30, False), (0, True)],
    ids=["causal", "window9", "window30", "selected"])
def test_the_decode_kernel_is_its_twin(window, selected):
    q = jnp.asarray(RNG.randn(R, H, W), jnp.float32) * 0.1
    rows = np.arange(R)
    sel = _selection(rows, LENGTHS - 1, LENGTHS > 0) if selected else None
    got = la.latent_attention_decode(
        q, POOL, TABLE, LENGTHS, layer=1, v_width=V, window=window,
        selected=sel, interpret=True)
    want = la.latent_attention_xla(
        q, POOL, TABLE, jnp.asarray(rows), LENGTHS - 1, LENGTHS > 0, layer=1,
        window=window, selected=sel, v_width=V)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert not np.asarray(got[1]).any()        # a dead row reads zeros


def _ragged_batch():
    """A 13-token chunk on 30 cached tokens (its keys cross pages and the
    window's edge), a decode row and an idle row, on a compact axis of 24."""
    row_lens, kv_lens, c = np.array([13, 1, 0]), np.array([43, 20, 0]), 24
    starts, _ = ragged_layout(row_lens, 8)
    view = ragged_view_tokens(c, R, 8)
    pad = c - row_lens.sum()
    per = lambda f: np.concatenate(  # noqa: E731
        [f(r, n) for r, n in enumerate(row_lens)])
    tok_row = np.concatenate([per(lambda r, n: np.full(n, r)), np.zeros(pad, int)])
    tok_pos = np.concatenate(
        [per(lambda r, n: kv_lens[r] - n + np.arange(n)), np.zeros(pad, int)])
    tok_slot = np.concatenate(
        [per(lambda r, n: starts[r] + np.arange(n)), np.full(pad, view)])
    return dict(row_lens=row_lens, kv_lens=kv_lens, starts=starts, view=view,
                tok_row=tok_row, tok_pos=tok_pos, tok_slot=tok_slot,
                valid=np.arange(c) < row_lens.sum(), c=c)


@pytest.mark.parametrize("window, selected", [(9, False), (0, False), (0, True)],
                         ids=["window9", "causal", "selected"])
def test_the_ragged_kernel_is_its_twin(window, selected):
    b = _ragged_batch()
    q = jnp.asarray(RNG.randn(b["c"], H, W), jnp.float32) * 0.1
    slot = jnp.asarray(b["tok_slot"])
    want_kw = dict(layer=1, v_width=V)
    if selected:
        sel = _selection(b["tok_row"], b["tok_pos"], b["valid"])
        got = la.latent_ragged_attention(
            q, POOL, None, None, None, None, None, None, tile=1, selected=sel,
            tok_valid=jnp.asarray(b["valid"]), interpret=True, **want_kw)
    else:
        sel = None
        items = ragged_work_items(b["row_lens"], 8, total=R + b["view"] // 8)
        slot_tok = jnp.full((b["view"],), b["c"], jnp.int32).at[slot].set(
            jnp.arange(b["c"]), mode="drop")
        got = la.latent_ragged_attention(
            q.at[slot_tok].get(mode="fill", fill_value=0), POOL, TABLE,
            jnp.asarray(b["kv_lens"], jnp.int32), jnp.asarray(b["starts"]),
            jnp.asarray(b["row_lens"], jnp.int32), jnp.asarray(items[0]),
            jnp.asarray(items[1]), tile=8, window=window, interpret=True,
            **want_kw).at[slot].get(mode="fill", fill_value=0)
    want = la.latent_attention_xla(
        q, POOL, TABLE, jnp.asarray(b["tok_row"]), jnp.asarray(b["tok_pos"]),
        jnp.asarray(b["valid"]), window=window, selected=sel, **want_kw)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_write_kernel_is_its_twin():
    b = _ragged_batch()
    rows = jnp.asarray(RNG.randn(b["c"], W), jnp.float32)
    page = np.where(b["valid"], np.asarray(TABLE)[b["tok_row"], b["tok_pos"] // PAGE], 0)
    off = np.where(b["valid"], b["tok_pos"] % PAGE, 0)
    args = (POOL, rows, jnp.asarray(page, jnp.int32), jnp.asarray(off, jnp.int32))
    got = la.latent_kv_write(*args, layer=1, interpret=True)
    want = la.latent_kv_write_xla(*args, layer=1)
    # page 0 is the null page: the pads' duplicate writes leave it open
    np.testing.assert_array_equal(got[:, :, 1:], want[:, :, 1:])
    assert (np.asarray(got[0]) == np.asarray(POOL[0])).all()


@pytest.mark.parametrize("widths, reason", [
    ((640, 1152, 128), None), (128, None), ((576, 1152), "row width 576"),
    (64, "head_dim 64"),
])
def test_the_kernel_gate_takes_the_row_widths_of_a_layout(widths, reason):
    got = paged_kernel_unsupported_reason(widths, 16, jnp.bfloat16, platform="tpu")
    assert (got is None) if reason is None else (reason in got)


# ------------------------------------------------------ engine: the layout

@pytest.fixture(scope="module")
def engine():
    cfg, bundle, params = tiny()
    eng = LLMEngineCore(bundle, params, max_batch=4, max_seq_len=160,
                        cache_mode="paged", page_size=8, prefix_cache=8,
                        prefix_block=8, step_token_budget=32)
    yield cfg, params, eng
    eng.stop()


def test_the_prefix_cache_reuses_latent_pages(engine):
    cfg, params, eng = engine
    doc = prompt_of(64, seed=9)
    prompts = [doc + [5, 6, 7, 8, 9, 10, 11], doc + [9, 9, 9, 1, 2, 3]]

    async def one(prompt):
        req = GenRequest(prompt_ids=list(prompt), max_new_tokens=6,
                         temperature=0.0)
        return [t async for t in eng.generate(req)]

    async def run():
        first = await one(prompts[0])
        second = await one(prompts[1])
        await eng.wait_drained()
        return first, second

    outs = asyncio.run(run())
    assert eng._prefix.stats()["hit_tokens"] >= 64
    weights = ServedWeights(params)
    for prompt, out in zip(prompts, outs):
        seq = prompt + out
        want = ref.forward(cfg, weights, jnp.asarray(seq[:-1], jnp.int32),
                           jnp.arange(len(prompt) - 1, len(seq) - 1))
        assert out == np.argmax(np.asarray(want), -1).tolist()
    stats = eng.lifecycle_stats()
    assert set(stats["kv_pool"]) == {"kv", "scale", "dtype", "num_pages",
                                     "page_size", "used_pages_peak"}
    pool = eng.paged_cache
    assert stats["kv_pool"]["kv"] == sum(
        int(x.nbytes) for x in jax.tree.leaves((pool.k, pool.v)) if x.ndim == 5)
    lat, moe = stats["latent"], stats["moe"]
    assert lat["rows_full"] == 2 * lat["rows_window"] > 0     # 4 full, 2 window
    assert 0 < lat["index_keys_kept"] < lat["index_keys_scored"]
    assert lat["decode_latent_tokens"] == \
        lat["decode_keys_full"] + lat["decode_keys_window"] > 0
    assert moe["experts_held"] == 4 and 0 < moe["experts_hit"] <= \
        4 * moe["layer_passes"] and moe["local_assignments"] > 0
    assert eng.health()["kernels"]["decode"] == "xla"          # the CPU's twin


def test_latent_pass_work_counts_what_a_launch_reads():
    layout = tiny()[1].paged_layout                # 4 full, 2 window layers
    got = _latent_pass_work(
        layout, mixed_visible=[31, 32, 33, 8], chain_first=[32, 9],
        chain_passes=[0, 2], row_lens=[3, 1], kv_lens=[33, 8])
    seen = [31, 32, 33, 8, 9, 10]                  # mixed, then row 1's chain
    assert got["rows_full"] == 4 * 6 and got["rows_window"] == 2 * 6
    assert got["index_keys_scored"] == 4 * sum(seen)
    assert got["index_keys_kept"] == 4 * sum(min(v, 16) for v in seen)
    assert got["window_keys"] == 2 * sum(min(v, 9) for v in seen)
    assert got["decode_keys_full"] == 4 * (9 + 10)
    assert got["decode_keys_window"] == 2 * (9 + 9)
    assert got["mixed_keys_full"] == 4 * (16 + 8)
    assert got["mixed_keys_window"] == 2 * (9 + 3 - 1 + 8)
    assert got["decode_latent_tokens"] == 4 * 19 + 2 * 18


REFUSALS = {
    "cache_dense": (dict(cache_mode="dense"), "engine.cache=paged"),
    "kv_quant": (dict(config={"kv_quant": "int8"}), "kv_quant cannot serve"),
    "host_tier": (dict(prefix_cache=8, prefix_cache_host_pages=4),
                  "HostKVTier"),
    "host_tier_mb": (dict(prefix_cache=8, prefix_cache_host_bytes=1 << 20),
                     "HostKVTier"),
    "speculation": (dict(speculation="ngram"), "speculation cannot serve"),
    "spec_tree": (dict(spec_tree=True), "speculation cannot serve"),
    "lora": (dict(lora_adapters={"a": {}}), "lora_adapters are not served"),
    "int4_weights": (dict(weight_quant_init="int4"), "'int8' or none"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_what_the_layout_cannot_do_is_refused_by_name(case):
    kw, reason = REFUSALS[case]
    kw = dict(kw)
    cfg = dict(TINY, **kw.pop("config", {}))
    with pytest.raises(ValueError, match=reason):
        bundle = models.build_model("dots3_note", cfg)
        quant = kw.pop("weight_quant_init", None)
        params = bundle.init(jax.random.PRNGKey(0), weight_quant=quant)
        kw.setdefault("cache_mode", "paged")
        LLMEngineCore(bundle, params, max_batch=2, max_seq_len=64,
                      page_size=8, **kw)


def test_a_mesh_is_refused_by_name():
    from clearml_serving_tpu.llm.engine import _latent_cache_refusal

    class Mesh:
        size = 4

    _, bundle, _ = tiny()
    got = _latent_cache_refusal(
        bundle, cache_mode="paged", mesh=Mesh(), prefix_cache_host_pages=None,
        prefix_cache_host_bytes=None, speculation=None, spec_tree=False,
        lora_adapters=None)
    assert "4-device mesh" in got and "exchange" in got


def test_page_export_and_the_dense_cache_are_refused_by_name(engine):
    _, _, eng = engine
    with pytest.raises(ValueError, match="latent page layout"):
        eng.paged_cache.export_pages([1])
    with pytest.raises(ValueError, match="engine.cache=paged only"):
        eng.bundle.init_cache(1, 8)


def test_a_windowed_kv_model_is_still_refused_on_pages_by_layer_kind():
    bundle = models.build_model("llama", {
        "preset": "llama-tiny", "dtype": "float32", "sliding_window": 8})
    with pytest.raises(ValueError, match="arch llama need engine.cache=dense"):
        LLMEngineCore(bundle, bundle.init(jax.random.PRNGKey(0)),
                      max_batch=2, max_seq_len=64, cache_mode="paged")
