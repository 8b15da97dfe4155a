"""The routed feed-forward's kernel (ops/moe_experts.py) in interpret mode on
the CPU, at both expert configurations' published widths cut in expert count
alone (d 2048 / width 1024 of trinity-mini-d8, d 5120 / width 1536 of
dots3-note-prev-ep8), float and int8 stacks: against ``moe_dropless``; a row
alone against the same row beside the others, bit for bit; what forces no
visit; the visited set against what ``moe.experts_hit`` counts; and both model
files with the kernel routed in (unrolled layers and a scanned period, where
the kernel takes the stacked operand whole)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from clearml_serving_tpu.models import llama
from clearml_serving_tpu.ops import moe_experts as me
from clearml_serving_tpu.ops.quant import dequantize, quantize_int8

# (dim, width, held experts, router outputs, first held, tokens)
WIDTHS = {
    "trinity": (2048, 1024, 6, 8, 0, 32),
    "dots3": (5120, 1536, 4, 16, 4, 16),
}
TOP_K = 2


def _case(widths, quant, seed=0, tokens=None):
    """One expert layer at a configuration's widths, routed: the tokens,
    the three stacks, the router's choice as indices into the held stacks
    (``here``: held on this chip), the valid rows."""
    dim, width, n_held, n_router, first, t = WIDTHS[widths]
    t = tokens or t
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    dtype = jnp.float32

    def stack(key, a, b):
        w = jax.random.normal(key, (n_held, a, b), dtype) * a ** -0.5
        if quant:
            return dict(zip(("_q8", "_scale"), quantize_int8(w, axis=-2)))
        return w

    stacks = [stack(keys[0], dim, width), stack(keys[1], dim, width),
              stack(keys[2], width, dim)]
    x = jax.random.normal(keys[3], (t, dim), dtype)
    top_p, top_e = llama.moe_route(
        jax.random.normal(keys[4], (t, n_router)), TOP_K, scoring="sigmoid",
        bias=0.0)
    valid = jnp.arange(t) < t - 3                  # a padded tail
    local = top_e - first
    here = (local >= 0) & (local < n_held)
    return dict(x=x, stacks=stacks, top_p=top_p, local=local, here=here,
                valid=valid, n_held=n_held)


def _kernel(c, took, **kw):
    gates, hit = me.expert_gates(c["top_p"], c["local"], took, c["n_held"])
    order, count = me.visit_order(hit)
    y = me.moe_experts(c["x"], gates, order, count, *c["stacks"],
                       interpret=True, **kw)
    return np.asarray(y), np.asarray(hit), int(count)


def _twin(c):
    dense = [dequantize(w["_q8"], w["_scale"], jnp.float32)
             if isinstance(w, dict) else w for w in c["stacks"]]
    return np.asarray(llama.moe_dropless(
        c["x"], c["top_p"], jnp.where(c["here"], c["local"], c["n_held"]),
        *dense))


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("widths", ["trinity", "dots3"])
def test_the_kernel_is_moe_dropless_on_the_valid_rows(widths, quant):
    c = _case(widths, quant)
    took = c["here"] & c["valid"][:, None]
    got, hit, count = _kernel(c, took)
    want = _twin(c)
    live = np.asarray(c["valid"])
    np.testing.assert_allclose(got[live], want[live], rtol=2e-4, atol=2e-4)
    # a padding row adds nothing, whatever it chose
    assert not got[~live].any()
    assert 0 < count == hit.sum() <= c["n_held"]


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("widths", ["trinity", "dots3"])
def test_a_row_alone_answers_bit_for_bit_as_beside_the_others(widths, quant):
    """128 rows of one launch; row 5 alone (the other 127 padding) visits
    its own experts only and reads the same 32 bits a value."""
    c = _case(widths, quant, seed=1, tokens=128)
    took = c["here"] & c["valid"][:, None]
    beside, _, n_all = _kernel(c, took)
    alone, _, n_own = _kernel(c, took & (jnp.arange(128) == 5)[:, None])
    assert n_own == int(np.asarray(took[5]).sum()) < n_all
    assert np.array_equal(alone[5], beside[5])
    assert not np.delete(alone, 5, axis=0).any()


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_the_tile_walk_of_the_width_changes_no_visit(quant):
    """The width in two tiles against one: the same visits, the same sum to
    float32 rounding (a tile is a partial sum of the down projection)."""
    c = _case("trinity", quant)
    took = c["here"] & c["valid"][:, None]
    whole, _, count = _kernel(c, took)
    halves, _, count2 = _kernel(c, took, tile=512)
    assert count == count2
    np.testing.assert_allclose(halves, whole, rtol=1e-5, atol=1e-5)
    assert me.width_tile(2048, 1024) == 1024
    assert me.width_tile(5120, 1536) == 512
    assert me.width_tile(4096, 14336) == 512      # Mixtral's, for later


@pytest.mark.parametrize("widths", ["trinity", "dots3"])
def test_what_forces_no_visit(widths):
    """An expert this chip does not hold (the sentinel index), a padding
    row and an expert nobody chose: no visit, nothing added; no hit at all
    gives zeros."""
    c = _case(widths, True, seed=2)
    took = c["here"] & c["valid"][:, None]
    _, hit, count = _kernel(c, took)
    # the visited set is what moe.experts_hit counts (models/afmoe._ffn of
    # the parent: a scatter-max over the assignments that stayed here)
    idx = jnp.where(took, c["local"], c["n_held"])
    counted = jnp.zeros((c["n_held"] + 1,), jnp.int32).at[idx].max(1)
    assert np.array_equal(hit, np.asarray(counted[:c["n_held"]]))
    gates, _ = me.expert_gates(c["top_p"], c["local"], took, c["n_held"])
    order, _ = me.visit_order(jnp.asarray(hit))
    assert sorted(set(np.asarray(order[:count]).tolist())) == \
        np.flatnonzero(hit).tolist()
    assert np.array_equal(np.asarray(order[count:]),
                          np.full(c["n_held"] - count, order[count - 1]))
    # a gate is non-zero exactly where a counted choice named the expert
    assert np.array_equal(np.asarray(gates != 0).any(0), hit.astype(bool))
    # nobody valid, or nobody held: zeros, and no NaN from an unvisited stack
    for none in (took & False, c["here"] & False):
        y, hit0, count0 = _kernel(c, none)
        assert count0 == 0 and not hit0.any() and not y.any()


def test_an_unchosen_experts_overflow_adds_an_exact_zero():
    """Expert 0's hidden overflows for every row (a huge gate stack); a row
    that did not choose it is untouched: its term is selected to zero, not
    multiplied by it."""
    c = _case("trinity", False, seed=3)
    c["stacks"][0] = c["stacks"][0].at[0].set(1e30)
    c["stacks"][1] = c["stacks"][1].at[0].set(1e30)
    took = c["here"] & c["valid"][:, None]
    got, hit, _ = _kernel(c, took)
    chose0 = np.asarray((took & (c["local"] == 0)).any(1))
    assert hit[0] and chose0.any() and not chose0.all()
    assert np.isfinite(got[~chose0]).all()
    assert not np.isfinite(got[chose0]).all()


def test_visit_order_compacts_ascending():
    for hit, want, count in (
        ([0, 0, 1, 0, 1, 1, 0, 0], [2, 4, 5, 5, 5, 5, 5, 5], 3),
        ([1] * 8, list(range(8)), 8),
        ([0] * 8, [0] * 8, 0),
        ([0, 0, 0, 0, 0, 0, 0, 1], [7] * 8, 1),
    ):
        order, n = me.visit_order(jnp.asarray(hit))
        assert (np.asarray(order).tolist(), int(n)) == (want, count)


def test_the_stacked_operand_takes_a_layer_index():
    c = _case("trinity", True)
    took = c["here"] & c["valid"][:, None]
    gates, hit = me.expert_gates(c["top_p"], c["local"], took, c["n_held"])
    order, count = me.visit_order(hit)
    one = me.moe_experts(c["x"], gates, order, count, *c["stacks"],
                         interpret=True)
    stacked = [{k: jnp.stack([jnp.zeros_like(v), v]) for k, v in w.items()}
               for w in c["stacks"]]
    two = jax.jit(lambda r: me.moe_experts(
        c["x"], gates, order, count, *stacked, layer=r, interpret=True))(
            jnp.int32(1))
    assert np.array_equal(np.asarray(one), np.asarray(two))


@pytest.mark.parametrize("case, reason", [
    (dict(), None),
    (dict(platform="cpu"), "platform cpu"),
    (dict(tokens=12), "12 tokens"),
    (dict(w=jax.ShapeDtypeStruct((8, 2048, 1024), jnp.float32)),
     "float32 beside bfloat16"),
    (dict(w={"_q4": None, "_scale4": None}), "widens int8 only"),
    (dict(w=jax.ShapeDtypeStruct((8, 2000, 1024), jnp.bfloat16)), "dim 2000"),
    (dict(w=jax.ShapeDtypeStruct((8, 2048, 1000), jnp.bfloat16)),
     "expert width 1000"),
])
def test_the_route_is_decided_from_shapes_and_dtype(case, reason):
    int8 = {"_q8": jax.ShapeDtypeStruct((2, 128, 2048, 1024), jnp.int8),
            "_scale": jax.ShapeDtypeStruct((2, 128, 1, 1024), jnp.float32)}
    why = me.moe_kernel_unsupported_reason(
        case.get("tokens", 128), jnp.bfloat16, case.get("w", int8),
        platform=case.get("platform", "tpu"))
    assert (why is None) if reason is None else (reason in why)


# ------------------------------------------ both model files, the kernel in

@pytest.fixture
def kernel_routed(monkeypatch):
    """The TPU's route on the CPU: the reason function answers for a TPU
    and the kernel is interpreted (tests/test_bringup.py's way)."""
    reason = me.moe_kernel_unsupported_reason
    calls = []

    def experts(*a, **kw):
        calls.append(kw.get("layer"))
        return me_experts(*a, **dict(kw, interpret=True))

    me_experts = me.moe_experts
    monkeypatch.setattr(me, "moe_kernel_unsupported_reason",
                        functools.partial(reason, platform="tpu"))
    monkeypatch.setattr(me, "moe_experts", experts)
    return calls


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scanned"])
@pytest.mark.parametrize("quant", [None, "int8"], ids=["float", "int8"])
def test_afmoe_serves_the_twins_logits_through_the_kernel(
        kernel_routed, monkeypatch, quant, scan):
    import test_afmoe_model as t

    # widths the route takes (dim and expert width whole 128-lane tiles);
    # S F S F repeats, so scan_layers scans a period of two
    over = dict(dim=128, moe_intermediate_size=128, n_heads=8, scan_layers=scan,
                layer_types=[t.S, t.F, t.S, t.F], num_dense_layers=0)
    cfg, bundle, params = t.tiny(quant, **over)
    assert bundle.layer_plan == ((0, 2) if scan else (4, 0))
    prompt = t.prompt_of(40)
    got, seq, _, v = t.serve(bundle, params, prompt, 3, chunk=24,
                             counters=True)
    # a launch of 24 tokens takes the kernel (whole 8-row float32 tiles;
    # traced once a layer, or once a position of the scanned period), a
    # decode pass of 2 rows the twin
    assert len(kernel_routed) == (2 if scan else 4)
    assert all((layer is not None) == scan for layer in kernel_routed)
    monkeypatch.undo()
    want, seq2, _, v2 = t.serve(bundle, params, prompt, 3, chunk=24,
                                counters=True)
    assert seq == seq2
    np.testing.assert_allclose(got, want, atol=5e-4)
    # the counters do not know which route ran
    assert np.array_equal(np.asarray(v[1]), np.asarray(v2[1]))


def test_dots3_serves_the_twins_logits_through_the_kernel(
        kernel_routed, monkeypatch):
    import test_latent_model as t

    over = dict(dim=128, moe_intermediate_size=128)
    cfg, bundle, params = t.tiny(**over)
    lead, period = bundle.layer_plan
    assert period
    prompt = t.prompt_of(24)
    got, seq = t.serve(bundle, params, prompt, 2)[:2]
    assert {layer is not None for layer in kernel_routed} == {False, True}
    monkeypatch.undo()
    want, seq2 = t.serve(bundle, params, prompt, 2)[:2]
    assert seq == seq2
    np.testing.assert_allclose(got, want, atol=5e-4)


# ---------------------------------- the engine's report of the route (PR 53)

@pytest.mark.parametrize("arch", ["afmoe", "dots3_note"])
def test_the_engine_names_the_experts_route_and_its_reason(arch, monkeypatch):
    """``health()["kernels"]["moe"]`` beside ``decode`` and ``ragged``: on
    the CPU the twin, with the platform as its reason; with the reason
    function answering for a TPU, the reason of the engine's own shapes (a
    decode pass of 2 float32 rows is no whole tile), then ``pallas``."""
    from clearml_serving_tpu.llm.engine import LLMEngineCore

    t = __import__("test_afmoe_model" if arch == "afmoe"
                   else "test_latent_model")
    bundle, params = t.tiny(dim=128, moe_intermediate_size=128)[1:]

    def kernels(**kw):
        eng = LLMEngineCore(bundle, params, max_seq_len=160, page_size=8,
                            cache_mode="paged", step_token_budget=32, **kw)
        try:
            assert eng.lifecycle_stats()["kernels"] == eng.health()["kernels"]
            return eng.health()["kernels"]
        finally:
            eng.stop()

    got = kernels(max_batch=2)
    assert got["moe"] == "xla" and "platform cpu" in got["reason"]["moe"]
    monkeypatch.setattr(
        me, "moe_kernel_unsupported_reason",
        functools.partial(me.moe_kernel_unsupported_reason, platform="tpu"))
    got = kernels(max_batch=2)
    assert got["moe"] == "xla" and "2 tokens a launch" in got["reason"]["moe"]
    got = kernels(max_batch=8)
    assert got["moe"] == "pallas" and "moe" not in (got["reason"] or {})


def test_a_model_without_held_experts_reports_no_experts_route():
    """arch llama (Mixtral's file too: its experts are not a held slice of a
    large set, ISSUE 53) keeps the kernels block it had."""
    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import LLMEngineCore

    cfg = dict(vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
               ffn_dim=64, max_seq_len=64, dtype="float32", n_experts=4,
               moe_top_k=2)
    bundle = models.build_model("llama", cfg)
    eng = LLMEngineCore(bundle, bundle.init(jax.random.PRNGKey(0)),
                        max_batch=2, max_seq_len=64, cache_mode="paged",
                        page_size=8, step_token_budget=16)
    try:
        assert set(eng.health()["kernels"]) == {
            "decode", "ragged", "int4", "reason"}
    finally:
        eng.stop()
