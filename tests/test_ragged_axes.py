"""The two token axes of a ragged pass (docs/ragged_attention.md, "Two
axes"): everything per token runs on the COMPACT axis (the launch's tokens
packed row after row), the token-mixing kernels read an ALIGNED VIEW whose
rows start on the kernel's 8-token copies, and ``tok_slot`` takes a token
from the one to the other.

- ``forward_ragged`` with an 8-aligned view gives the logits and the pools of
  the same launch whose view is the compact axis itself (``q_block`` 1: the
  XLA twin's layout), for decode rows with reserved window positions, a
  prompt chunk, a verify row read at ``row_logit_idx``, a draft tree, an
  expert FFN, a LoRA row and int8 pools; what a pad holds reaches no logit
  and no page but the null one;
- ``forward_ragged_state`` keeps ONE axis, the kernels' view (PERF.md, PR 42:
  packing it was measured and not taken), and gives what ``4fb11cc`` gave for
  the same launches (tests/data/ragged_state_pr41.json);
- an engine on ``cache=state`` streams the attention form's tokens and counts
  the rows its dense layers multiplied."""

import asyncio
import json
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
from clearml_serving_tpu.ops import paged_attention as pa  # noqa: E402

BASE = dict(vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            head_dim=16, ffn_dim=96, scan_layers=True, dtype="float32",
            max_seq_len=128)
ROWS, DENSE, PAGE, PAGES_PER_SEQ = 5, 40, 8, 8
K = 3                                   # drafts of a verify row

# (query tokens, tokens in the cache before, reserved window positions)
PLAIN = [(1, 13, 3), (1, 30, 0), (0, 0, 0), (19, 9, 0), (1, 2, 1)]
# row 1 verifies a chain of K drafts, row 3 is a prompt's first chunk
VERIFY = [(1, 13, 1), (K + 1, 30, 0), (0, 0, 0), (11, 0, 0), (1, 2, 0)]


def _build(**more):
    cfg = dict(BASE, **more)
    bundle = models.build_model("llama", cfg)
    params = bundle.init(jax.random.PRNGKey(5))
    if more.get("lora_rank"):
        # adapter 1 carries weight: a row on it must differ from the base
        keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))
        params["layers"] = {
            name: (leaf.at[:, 1].set(
                0.05 * jax.random.normal(next(keys), leaf[:, 1].shape))
                if name.startswith("lora_") else leaf)
            for name, leaf in params["layers"].items()
        }
    return bundle, params


def _pools(bundle, quant):
    """Stacked pools with a random context in every page (the null page 0
    included: what lands there is compared apart)."""
    rng = np.random.default_rng(11)
    shape = (BASE["n_layers"], BASE["n_kv_heads"], 1 + ROWS * PAGES_PER_SEQ,
             PAGE, BASE["head_dim"])
    if quant:
        pools = [jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                 for _ in range(2)]
        pools += [jnp.asarray(rng.uniform(0.005, 0.02, shape[:-1]), jnp.float32)
                  for _ in range(2)]
        return pools
    return [jnp.asarray(rng.normal(0, 0.5, shape), jnp.float32)
            for _ in range(2)]


def _launch(rows, q_block, *, pad_token=0, verify=False, tree=None):
    """A launch's operands as the engine's planner lays them out: the compact
    axis packs every row's span (query tokens and reserved positions), the
    view aligns the same spans to ``q_block``."""
    rng = np.random.default_rng(3)
    spans = np.asarray([n + r for n, _, r in rows], np.int32)
    packed, _ = pa.ragged_layout(spans, 1, total=DENSE)
    view = pa.ragged_view_tokens(DENSE, ROWS, q_block)
    starts, _ = pa.ragged_layout(spans, q_block, total=view)
    tokens = np.full(DENSE, pad_token, np.int32)
    tok_pos, tok_row = np.zeros(DENSE, np.int32), np.zeros(DENSE, np.int32)
    tok_valid = np.zeros(DENSE, bool)
    tok_slot = np.full(DENSE, view, np.int32)
    write_page, write_offset = np.zeros(DENSE, np.int32), np.zeros(DENSE, np.int32)
    row_last, row_lens = np.zeros(ROWS, np.int32), np.zeros(ROWS, np.int32)
    kv_lens = np.zeros(ROWS, np.int32)
    table = 1 + np.arange(ROWS * PAGES_PER_SEQ, dtype=np.int32).reshape(
        ROWS, PAGES_PER_SEQ)
    logit_idx = np.zeros((ROWS, K + 1), np.int32)
    anc = np.full((view, K + 1), -1, np.int32)
    anc[:, 0] = -2
    for r, (n, pre, _) in enumerate(rows):
        ids = rng.integers(3, 97, size=n)           # the same in every layout
        if n == 0:
            continue
        s = int(packed[r])
        pos = pre + np.arange(n)
        if tree is not None and r == 1:
            pos = pre + np.asarray(tree["depths"])
            anc[starts[r]: starts[r] + n] = pa.tree_ancestors(
                np.asarray(tree["parents"], np.int32), n, width=K + 1)
        tokens[s: s + n], tok_pos[s: s + n] = ids, pos
        tok_row[s: s + n], tok_valid[s: s + n] = r, True
        tok_slot[s: s + n] = starts[r] + np.arange(n)
        where = pre + np.arange(n)                  # a node's own slot
        write_page[s: s + n] = table[r, where // PAGE]
        write_offset[s: s + n] = where % PAGE
        row_last[r], row_lens[r], kv_lens[r] = s + n - 1, n, pre + n
        logit_idx[r] = s + np.minimum(np.arange(K + 1), n - 1)
    per_tok = (tokens, tok_pos, tok_row, tok_valid, tok_slot)
    kw = {}
    if verify:
        kw["row_logit_idx"] = jnp.asarray(logit_idx)
    if tree is not None:
        kw["tree_anc"] = jnp.asarray(anc)
    return dict(
        per_tok=tuple(map(jnp.asarray, per_tok)), row_last=jnp.asarray(row_last),
        table=jnp.asarray(table), kv_lens=jnp.asarray(kv_lens),
        row_starts=jnp.asarray(starts), row_lens=jnp.asarray(row_lens),
        write=(jnp.asarray(write_page), jnp.asarray(write_offset)), kw=kw,
        live=[r for r, (n, _, _) in enumerate(rows) if n], view=view)


def _run(bundle, params, pools, launch, lora=None):
    scales = dict(zip(("k_scales", "v_scales"), pools[2:]))
    out = jax.jit(bundle.forward_ragged)(
        params, *launch["per_tok"], launch["row_last"], pools[0], pools[1],
        launch["table"], launch["kv_lens"], launch["row_starts"],
        launch["row_lens"], *launch["write"], None, None, lora,
        **scales, **launch["kw"])
    return out[0], [np.asarray(p) for p in out[1:]]


@pytest.fixture
def aligned_view(monkeypatch):
    """The kernel's routing on the CPU: ``forward_ragged`` lays q out in the
    8-aligned view and calls the "kernel", which is the XLA twin here (the
    twin takes any row map; the Pallas kernel under the interpreter runs this
    layout in tests/test_bringup.py)."""
    monkeypatch.setattr(pa, "paged_kernel_unsupported_reason",
                        lambda *a, **k: None)
    monkeypatch.setattr(
        pa, "ragged_paged_attention",
        lambda *a, item_rows=None, item_q0=None, **k:
            pa.ragged_paged_attention_xla(*a, **k))
    monkeypatch.setattr(pa, "paged_kv_write", pa.paged_kv_write_xla)


CASES = {
    "decode_rows_and_a_chunk": dict(rows=PLAIN),
    "verify_row": dict(rows=VERIFY, verify=True),
    "draft_tree": dict(rows=VERIFY, verify=True,
                       tree=dict(parents=[-1, 0, 0, 1], depths=[0, 1, 1, 2])),
    "expert_ffn": dict(rows=PLAIN, cfg=dict(n_experts=4, moe_top_k=2)),
    "lora_row": dict(rows=PLAIN, cfg=dict(lora_rank=4, max_loras=2),
                     lora=[0, 0, 0, 1, 1]),
    "int8_pools": dict(rows=PLAIN, cfg=dict(kv_quant="int8")),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_aligned_view_gives_what_the_compact_layout_gives(
        case, aligned_view, monkeypatch):
    spec = CASES[case]
    bundle, params = _build(**spec.get("cfg", {}))
    quant = "kv_quant" in spec.get("cfg", {})
    lora = jnp.asarray(spec["lora"], jnp.int32) if "lora" in spec else None
    layout = dict(verify=spec.get("verify", False), tree=spec.get("tree"))
    wide = _launch(spec["rows"], pa._RAGGED_QB, **layout)
    assert wide["view"] == 80 and int(wide["row_starts"][3]) % 8 == 0
    got, got_pools = _run(bundle, params, _pools(bundle, quant), wide, lora)
    # the same launch where the view IS the compact axis: the twin's routing
    monkeypatch.undo()
    flat = _launch(spec["rows"], 1, **layout)
    assert flat["view"] == DENSE
    valid, slot = np.asarray(flat["per_tok"][3]), np.asarray(flat["per_tok"][4])
    np.testing.assert_array_equal(slot[valid], np.arange(DENSE)[valid])
    assert (slot[~valid] == DENSE).all()        # pads: out of range
    want, want_pools = _run(bundle, params, _pools(bundle, quant), flat, lora)
    live = wide["live"]
    if layout["verify"]:
        (got, got_at), (want, want_at) = got, want
        assert got_at.shape == (ROWS, K + 1, BASE["vocab_size"])
        np.testing.assert_allclose(np.asarray(got_at)[live],
                                   np.asarray(want_at)[live], atol=2e-5)
        # the chain's last node is the row's last token
        np.testing.assert_allclose(got_at[1, K], got[1], atol=2e-5)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)
    for a, b in zip(got_pools, want_pools):
        np.testing.assert_array_equal(a, b)
    if lora is not None:
        base, _ = _run(bundle, params, _pools(bundle, quant), flat,
                       jnp.zeros(ROWS, jnp.int32))
        assert np.abs(np.asarray(base)[3] - np.asarray(want)[3]).max() > 1e-3
        np.testing.assert_allclose(np.asarray(base)[0], np.asarray(want)[0],
                                   atol=2e-5)


@pytest.mark.parametrize("cfg", [{}, dict(n_experts=4, moe_top_k=2)],
                         ids=["dense_ffn", "expert_ffn"])
def test_what_a_pad_holds_reaches_no_logit_and_no_page(cfg, aligned_view):
    """Pads of the compact axis (its tail, and a decode row's reserved window
    positions) are multiplied by the dense layers and routed by no one else's
    arithmetic: other tokens there move nothing but the null page."""
    bundle, params = _build(**cfg)
    outs = []
    for pad_token in (0, 61):
        launch = _launch(PLAIN, pa._RAGGED_QB, pad_token=pad_token)
        outs.append(_run(bundle, params, _pools(bundle, False), launch))
    (a, a_pools), (b, b_pools) = outs
    live = [0, 1, 3, 4]
    np.testing.assert_array_equal(np.asarray(a)[live], np.asarray(b)[live])
    for x, y in zip(a_pools, b_pools):
        np.testing.assert_array_equal(x[:, :, 1:], y[:, :, 1:])
        assert not np.array_equal(x[:, :, 0], y[:, :, 0])    # the null page


@pytest.mark.parametrize("quant", [False, True], ids=["float32", "int8"])
def test_the_expert_batch_axis_is_the_product_over_free_experts(quant):
    """The dropless expert FFN broadcasts its tokens per expert so that the
    expert axis is a batch axis of every product (models/llama.py): that is
    ``td,edf->etf`` with the experts left free on the weights, to rounding."""
    from clearml_serving_tpu.ops.quant import dequantize, quantize_llama_params

    bundle, params = _build(n_experts=4, moe_top_k=2)
    if quant:
        params = quantize_llama_params(params)
        assert "_q8" in params["layers"]["w_gate_e"]
    layer = jax.tree.map(lambda leaf: leaf[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(7), (DENSE, 1, BASE["dim"]))
    got = bundle.ffn(layer, x, dropless=True)[:, 0]

    def weight(name):
        leaf = layer[name]
        if isinstance(leaf, dict):
            return dequantize(leaf["_q8"], leaf["_scale"], jnp.float32)
        return leaf

    tokens = x[:, 0]
    probs = jax.nn.softmax(tokens @ weight("w_router"), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, 2)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(DENSE)[:, None], top_e
    ].add(top_p / top_p.sum(-1, keepdims=True))
    h = jax.nn.silu(jnp.einsum("td,edf->etf", tokens, weight("w_gate_e"))
                    ) * jnp.einsum("td,edf->etf", tokens, weight("w_up_e"))
    want = jnp.einsum("te,etd->td", gates,
                      jnp.einsum("etf,efd->etd", h, weight("w_down_e")))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_a_tree_of_another_view_is_refused(aligned_view):
    bundle, params = _build()
    launch = _launch(VERIFY, pa._RAGGED_QB, verify=True,
                     tree=dict(parents=[-1, 0, 0, 1], depths=[0, 1, 1, 2]))
    launch["kw"]["tree_anc"] = launch["kw"]["tree_anc"][:DENSE]
    with pytest.raises(ValueError, match="aligned view"):
        _run(bundle, params, _pools(bundle, False), launch)


@pytest.mark.parametrize("tokens,rows,q_block,want", [
    (128, 32, 8, 352),       # the K/V cells
    (256, 16, 8, 368),       # brumby14b.long_decode
    (128, 32, 1, 128),       # the XLA twin: the compact axis itself
    (16, 2, 8, 32),          # tests/test_bringup.py's engine
    (24, 3, 8, 48),
])
def test_the_views_size_follows_from_the_shapes(tokens, rows, q_block, want):
    assert pa.ragged_view_tokens(tokens, rows, q_block) == want
    # any layout of that many tokens in that many rows fits
    worst = [1] * (rows - 1) + [tokens - (rows - 1)]
    _, t_pad = pa.ragged_layout(worst, q_block, total=want)
    assert t_pad == want


# ------------------------------------------------------ the state cache

STATE_CFG = dict(vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                 head_dim=16, ffn_dim=96, scan_layers=True, dtype="float32",
                 attention="power_retention", retention_degree=2, qk_norm=True,
                 norm_eps=1e-6, rope_theta=1e6, max_seq_len=512)


@pytest.fixture(scope="module")
def state_parts():
    bundle = models.build_model("llama", STATE_CFG)
    return bundle, bundle.init(jax.random.PRNGKey(3))


def test_the_state_pass_keeps_its_one_axis(state_parts):
    """Two launches over four slots (two prompts' first chunks; then a decode
    row, a chunk that continues, an idle row and a prompt that resets its
    slot) on the kernels' aligned view, the state pass's one token axis: the
    logits and the slots' sums recorded from ``4fb11cc``. (PR 42 measured the
    pass on a packed axis against this record and did not take it: PERF.md.)"""
    bundle, params = state_parts
    golden = json.loads(
        (ROOT / "tests" / "data" / "ragged_state_pr41.json").read_text())
    ids, rows = golden["ids"], 4
    step = jax.jit(bundle.forward_ragged_state)
    s_pool, z_pool = bundle.init_state(rows)
    for want in golden["launches"]:
        lens, pos = want["lens"], want["pos"]
        starts, view = pa.ragged_layout(lens, 8, total=96)
        tok, tp = np.zeros(view, np.int32), np.zeros(view, np.int32)
        tr, tv = np.zeros(view, np.int32), np.zeros(view, bool)
        last = np.zeros(rows, np.int32)
        for r, (n, p) in enumerate(zip(lens, pos)):
            if n == 0:
                continue
            s = int(starts[r])
            tok[s: s + n], tp[s: s + n] = ids[r][p: p + n], p + np.arange(n)
            tr[s: s + n], tv[s: s + n] = r, True
            last[r] = s + n - 1
        reset = (np.asarray(pos) == 0) & (np.asarray(lens) > 0)
        logits, s_pool, z_pool = step(
            params, *map(jnp.asarray, (tok, tp, tr, tv, last)),
            s_pool, z_pool, jnp.asarray(starts),
            jnp.asarray(np.asarray(lens, np.int32)), jnp.asarray(reset))
        for r, row in want["logits"].items():
            np.testing.assert_allclose(
                np.asarray(logits[int(r)]), np.asarray(row), atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(jnp.sum(jnp.abs(s_pool), axis=(0, 2, 3, 4))),
            want["s_sum"], rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(jnp.sum(jnp.abs(z_pool), axis=(0, 2, 3, 4))),
            want["z_sum"], rtol=1e-5)
    assert want["s_sum"][2] == 0.0          # the idle row's slot: untouched


def test_a_state_engine_streams_the_attention_forms_tokens(state_parts):
    """Five requests through three slots under a budget of 20 tokens: the
    state pass's one axis is the kernels' view (24 tokens in whole 8-row
    blocks + seven rows of waste a slot = 48), and every stream is what the
    model's full causal forward samples."""
    bundle, params = state_parts
    engine = LLMEngineCore(
        bundle, params, max_batch=3, max_seq_len=256, cache_mode="state",
        scheduler="ragged", step_token_budget=20, decode_steps=4,
        eos_token_id=None)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(3, 97, size=n).tolist() for n in (37, 5, 22, 9, 50)]
    news = [6, 9, 3, 7, 5]

    async def one(ids, n):
        return [t async for t in engine.generate(
            GenRequest(prompt_ids=list(ids), max_new_tokens=n))]

    async def main():
        outs = await asyncio.gather(*map(one, prompts, news))
        await engine.wait_drained()
        return outs

    try:
        outs = asyncio.run(main())
        stats = engine.lifecycle_stats()["ragged"]
        health = engine.health()["ragged"]
    finally:
        engine.stop()
    for ids, n, got in zip(prompts, news, outs):
        seq, want = list(ids), []
        for _ in range(n):
            want.append(int(jnp.argmax(
                bundle.apply(params, jnp.asarray([seq]))[0, -1])))
            seq.append(want[-1])
        assert got == want
    assert (stats["dense_axis"], stats["layout_axis"]) == (48, 48)
    assert (health["dense_axis"], health["layout_axis"]) == (48, 48)
    assert stats["steps"] > 0 and stats["dense_rows"] == 48 * stats["steps"]
