"""w4a16 fused dequant-matmul tests (ops/fused_matmul.py, docs/w4a16.md):
interpret-mode kernel parity against the XLA ``dequantize_int4`` reference
across group sizes / K paddings / stacked trees, fallback routing for
ineligible shapes, int4 TP sharding guards, the offline checkpoint
quantizer, and end-to-end engine byte-identity under the armed sanitizer."""

import asyncio
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.ops.fused_matmul import (
    MAX_FUSED_ROWS,
    fused_int4_matmul,
    int4_kernel_unsupported_reason,
    int4_matmul_xla,
)
from clearml_serving_tpu.ops.quant import (
    detect_weight_quant,
    quantize_int4,
    quantize_llama_params,
)

REPO = Path(__file__).resolve().parent.parent


def _rand_wx(m, k, n, seed=0, scale=True):
    """Activation + weight at production-like magnitudes (dense init is
    normal * fan_in**-0.5), so the <=1e-5 absolute parity bound is measured
    on realistically scaled outputs."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(k, n)).astype(np.float32)
    if scale:
        w *= k ** -0.5
    x = rng.normal(size=(m, k)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(w)


# -- kernel parity (interpret mode runs the Pallas path on any backend) ------

PARITY_GRID = [
    # (m, k, n, group): single group, exact multiples, coarse/fine groups,
    # K below the group size (per-channel fallback grouping), non-128 N,
    # and the 3-D activation case
    (1, 128, 128, 128),
    (2, 256, 256, 128),
    (3, 256, 384, 64),
    (8, 512, 1024, 128),
    (4, 96, 128, 128),     # K % group != 0 -> one per-channel group
    (5, 64, 130, 64),      # N not lane-aligned (interpret-only shape)
    (16, 384, 512, 192),
]


@pytest.mark.parametrize("m,k,n,group", PARITY_GRID)
def test_kernel_interpret_parity(m, k, n, group):
    x, w = _rand_wx(m, k, n, seed=m + k + n)
    q, s = quantize_int4(w, group=group)
    assert int4_kernel_unsupported_reason(x, q, s, interpret=True) is None
    ref = int4_matmul_xla(x, q, s, jnp.float32)
    out = fused_int4_matmul(x, q, s, interpret=True)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5


def test_kernel_interpret_parity_3d_activations():
    """[B, S, K] activations (speculative-verify shape) flatten to rows and
    reshape back."""
    x, w = _rand_wx(6, 256, 256, seed=7)
    x3 = x.reshape(2, 3, 256)
    q, s = quantize_int4(w, group=128)
    ref = int4_matmul_xla(x3, q, s, jnp.float32)
    out = fused_int4_matmul(x3, q, s, interpret=True)
    assert out.shape == ref.shape
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5


def test_kernel_interpret_parity_bf16():
    x, w = _rand_wx(4, 256, 256, seed=11)
    x = x.astype(jnp.bfloat16)
    q, s = quantize_int4(w, group=128)
    ref = int4_matmul_xla(x, q, s, jnp.bfloat16)
    out = fused_int4_matmul(x, q, s, interpret=True)
    assert out.dtype == jnp.bfloat16
    # bf16 epsilon-scale agreement (both paths accumulate in f32; the
    # operand rounding differs)
    assert float(jnp.max(jnp.abs(
        out.astype(jnp.float32) - ref.astype(jnp.float32)
    ))) <= 0.05


def test_kernel_parity_stacked_tree_slices():
    """Scanned trees hit the kernel one layer at a time ([L, K//2, N]
    sliced inside lax.scan): each slice must match the reference dequant of
    the stacked quantization."""
    rng = np.random.default_rng(3)
    L, k, n = 3, 256, 256
    w = jnp.asarray(rng.normal(size=(L, k, n)).astype(np.float32) * k ** -0.5)
    q, s = quantize_int4(w, group=128)
    x = jnp.asarray(rng.normal(size=(2, k)).astype(np.float32))
    from clearml_serving_tpu.ops.quant import dequantize_int4

    dense = dequantize_int4(q, s, jnp.float32)          # [L, K, N]
    for layer in range(L):
        out = fused_int4_matmul(x, q[layer], s[layer], interpret=True)
        ref = x @ dense[layer]
        assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5


# -- routing matrix ----------------------------------------------------------

def test_unsupported_reason_matrix():
    """The ONE routing decision (models/llama _mm and the engine's health
    block both call it): shapes, then — without interpret — platform and
    the hardware tiling gates."""
    x, w = _rand_wx(2, 256, 256)
    q, s = quantize_int4(w, group=128)
    ok = lambda *a, **kw: int4_kernel_unsupported_reason(*a, **kw)

    assert ok(x, q, s, interpret=True) is None
    # hardware-aligned: 2 groups of 128, N=256 — the kernel, on a TPU
    assert ok(x, q, s, platform="tpu") is None
    # and the reference everywhere else; the default platform is the
    # backend the program compiles for (tier-1 runs on CPU)
    assert "platform cpu" in ok(x, q, s, platform="cpu")
    assert "platform cpu" in ok(x, q, s)

    # stacked (3-D) weights route per layer, never whole
    q3, s3 = quantize_int4(jnp.stack([w, w]), group=128)
    assert "2-D" in ok(x, q3, s3, interpret=True)

    # odd group size: nibble pairs straddle the group boundary
    q_odd, s_odd = quantize_int4(
        jnp.asarray(np.random.default_rng(0).normal(size=(6, 128)).astype(np.float32)),
        group=3,
    )
    x6 = jnp.ones((2, 6), jnp.float32)
    assert "odd group" in ok(x6, q_odd, s_odd, interpret=True)

    # M > cap: prefill-shaped matmuls take the XLA path
    big = jnp.ones((MAX_FUSED_ROWS + 1, 256), jnp.float32)
    assert "rows exceed" in ok(big, q, s, interpret=True)
    at_cap = jnp.ones((MAX_FUSED_ROWS, 256), jnp.float32)
    assert ok(at_cap, q, s, platform="tpu") is None

    # hardware-only gates: lane misalignment (fine in interpret)
    xs, ws = _rand_wx(2, 256, 130)
    qs, ss = quantize_int4(ws, group=128)
    assert ok(xs, qs, ss, interpret=True) is None
    assert "lane-tileable" in ok(xs, qs, ss, platform="tpu")
    xg, wg = _rand_wx(2, 96, 128)   # one 96-row group: not a 128-lane window
    qg, sg = quantize_int4(wg, group=96)
    assert ok(xg, qg, sg, interpret=True) is None
    assert "lane-aligned" in ok(xg, qg, sg, platform="tpu")

    # int-typed activations are rejected outright
    assert "floating" in ok(x.astype(jnp.int32), q, s, interpret=True)


def test_kernel_never_returns_the_reference():
    """fused_int4_matmul only ever runs the kernel: operands the routing
    function rejects raise with its reason — picking int4_matmul_xla is
    the caller's decision (models/llama _mm), never a silent one."""
    x, w = _rand_wx(2, 6, 10)
    q, s = quantize_int4(w, group=3)  # odd group: rejected even in interpret
    with pytest.raises(ValueError, match="odd group"):
        fused_int4_matmul(x, q, s, interpret=True)
    # misaligned for the hardware, and not interpreted: refused, not rerouted
    xs, ws = _rand_wx(2, 256, 130)
    qs, ss = quantize_int4(ws, group=128)
    with pytest.raises(ValueError, match="lane-tileable"):
        fused_int4_matmul(xs, qs, ss)
    big = jnp.ones((MAX_FUSED_ROWS + 8, 256), jnp.float32)
    with pytest.raises(ValueError, match="rows exceed"):
        fused_int4_matmul(big, qs, ss, interpret=True)


# -- model-level routing -----------------------------------------------------

CFG = {"preset": "llama-tiny", "dtype": "float32"}


def test_scanned_vs_unscanned_int4_logits_match():
    """The _mm routing serves both tree layouts: a scanned [L, ...] int4
    tree and the per-layer list layout produce matching logits (the fused
    wrapper sees identical per-layer 2-D slices either way)."""
    bundle_scan = models.build_model("llama", dict(CFG, scan_layers=True))
    bundle_list = models.build_model("llama", CFG)
    params = bundle_list.init(jax.random.PRNGKey(0))
    q_list = quantize_llama_params(params, bits=4)
    q_scan = bundle_scan.prepare_params(q_list)
    tokens = jnp.asarray([[5, 9, 2, 17]], jnp.int32)
    a = bundle_scan.apply(q_scan, tokens)
    b = bundle_list.apply(q_list, tokens)
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
    )


def test_int4_fused_flag_streams_byte_identical():
    """cfg int4_fused=False (the XLA inline-dequant arm) and the default routing
    produce byte-identical greedy streams off-TPU: the wrapper's fallback
    IS the historical expression."""
    bundle = models.build_model("llama", CFG)
    bundle_off = models.build_model("llama", dict(CFG, int4_fused=False))
    params = bundle.init(jax.random.PRNGKey(0))
    qparams = quantize_llama_params(params, bits=4)

    def gen(b):
        engine = LLMEngineCore(
            b, qparams, max_batch=2, max_seq_len=96,
            prefill_buckets=[16, 32], eos_token_id=None, decode_steps=2,
        )

        async def run():
            req = GenRequest(prompt_ids=[256, 5, 6, 7], max_new_tokens=8)
            out = [t async for t in engine.generate(req)]
            await engine.wait_drained()
            return out

        out = asyncio.run(run())
        engine.stop()
        return out

    assert gen(bundle) == gen(bundle_off)


def test_paged_int4_engine_byte_identical_to_dense_under_sanitizer(monkeypatch):
    """End-to-end: the paged int4 engine streams byte-identically to the
    dense int4 engine under the armed KV sanitizer — weight quantization is
    orthogonal to the KV backend, and the fused-route gate must not perturb
    either path."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle = models.build_model("llama", CFG)
    params = bundle.init(jax.random.PRNGKey(0))

    def gen(cache_mode):
        engine = LLMEngineCore(
            bundle, params, max_batch=2, max_seq_len=96,
            prefill_buckets=[16, 32], eos_token_id=None, decode_steps=2,
            weight_quant="int4", cache_mode=cache_mode,
        )

        async def run():
            req = GenRequest(prompt_ids=[5, 9, 2, 17, 33], max_new_tokens=8)
            out = [t async for t in engine.generate(req)]
            await engine.wait_drained()
            return out

        out = asyncio.run(run())
        if cache_mode == "paged":
            pool = engine.paged_cache.pool
            assert pool.free_pages == pool.num_pages - 1  # no leaked pages
        engine.stop()
        return out

    dense = gen("dense")
    paged = gen("paged")
    assert dense == paged and len(dense) == 8


def test_engine_weight_quant_alias_and_conflict():
    bundle = models.build_model("llama", CFG)
    params = bundle.init(jax.random.PRNGKey(0))
    kw = dict(max_batch=1, max_seq_len=64, prefill_buckets=[16],
              eos_token_id=None)
    with pytest.raises(ValueError, match="conflicts"):
        LLMEngineCore(bundle, params, weight_quant="int4", quantize="int8",
                      **kw)
    with pytest.raises(ValueError, match="weight_quant"):
        LLMEngineCore(bundle, params, weight_quant="int3", **kw)
    # an already-packed tree + a redundant matching knob is a no-op; a
    # MISMATCHED knob is a clear error, not an AttributeError deep in
    # quantize_int4 (the offline bundle keeps its format either way)
    packed = quantize_llama_params(params, bits=4)
    redundant = LLMEngineCore(bundle, packed, weight_quant="int4", **kw)
    assert redundant.weight_quant == "int4"
    redundant.stop()
    with pytest.raises(ValueError, match="already int4-quantized"):
        LLMEngineCore(bundle, packed, weight_quant="int8", **kw)
    engine = LLMEngineCore(bundle, params, weight_quant="int4", **kw)
    assert engine.weight_quant == "int4"
    stats = engine.lifecycle_stats()["weights"]
    assert stats["quant"] == "int4"
    # packed tree is smaller than the f32 source
    assert 0 < stats["bytes"] < sum(
        leaf.nbytes for leaf in jax.tree.leaves(params)
    )
    engine.stop()


# -- TP sharding guard -------------------------------------------------------

def test_sharding_rejects_tp_that_splits_int4_groups():
    """parallel/sharding.py: a TP degree whose shard boundary lands inside
    a quantization group must raise naming the knob, not silently shard
    _q4 against replicated (wrong) scale rows."""
    from clearml_serving_tpu.parallel import (
        llama_quantized_param_sharding, make_mesh,
    )

    mesh = make_mesh({"tp": 4, "dp": 2})
    # w_down: ffn_dim=384 input rows -> 3 groups of 128; tp=4 splits them
    cfg = dict(CFG, dim=128, ffn_dim=384, n_heads=4, n_kv_heads=2,
               vocab_size=256)
    bundle = models.build_model("llama", cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    qparams = quantize_llama_params(params, bits=4)
    with pytest.raises(ValueError) as err:
        llama_quantized_param_sharding(
            mesh, qparams, n_kv_heads=2, n_heads=4
        )
    msg = str(err.value)
    assert "quantization groups" in msg and "mesh.tp" in msg

    # aligned degrees still shard: ffn 512 -> 4 groups, tp=2 divides all
    cfg_ok = dict(cfg, ffn_dim=512)
    bundle_ok = models.build_model("llama", cfg_ok)
    q_ok = quantize_llama_params(
        bundle_ok.init(jax.random.PRNGKey(0)), bits=4
    )
    mesh2 = make_mesh({"tp": 2, "dp": 4})
    specs = llama_quantized_param_sharding(
        mesh2, q_ok, n_kv_heads=2, n_heads=4
    )
    leaf = specs["layers"][0]["w_down"]
    assert set(leaf) == {"_q4", "_scale4"}
    down_spec = list(leaf["_scale4"].spec)
    down_spec += [None] * (2 - len(down_spec))
    assert down_spec[-2] == "tp"  # group axis sharded WITH the weight rows

    # the single-group (K < group) fallback replicates the scale row
    # instead of raising — one per-channel row serves every shard exactly
    tiny = models.build_model("llama", CFG)  # dim 64 -> 1 group everywhere
    tq = quantize_llama_params(tiny.init(jax.random.PRNGKey(0)), bits=4)
    specs = llama_quantized_param_sharding(
        make_mesh({"tp": 4, "dp": 2}), tq, n_kv_heads=2, n_heads=4
    )
    scale_spec = specs["layers"][0]["w_gate"]["_scale4"].spec
    padded = list(scale_spec) + [None] * (2 - len(scale_spec))
    assert padded[-2] is None  # input (group) axis replicated


# -- offline checkpoint quantizer --------------------------------------------

def test_quantize_ckpt_roundtrip(tmp_path):
    """scripts/quantize_ckpt.py converts a bf16 bundle offline; loading the
    output serves byte-identically to quantize-at-load (quantize_int4 is
    deterministic), the engine detects the packed tree, and re-quantizing
    is refused."""
    from clearml_serving_tpu.engines.jax_engine import load_bundle, save_bundle

    bundle = models.build_model("llama", CFG)
    params = bundle.init(jax.random.PRNGKey(0))
    src, dst = tmp_path / "src", tmp_path / "dst"
    save_bundle(src, "llama", CFG, params)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "quantize_ckpt.py"),
         str(src), str(dst), "--bits", "4"],
        capture_output=True, text=True, env=env, cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-1000:]

    qbundle, qparams = load_bundle(dst)
    assert detect_weight_quant(qparams) == "int4"

    def gen(b, p, **kw):
        engine = LLMEngineCore(
            b, p, max_batch=2, max_seq_len=96, prefill_buckets=[16, 32],
            eos_token_id=None, decode_steps=2, **kw,
        )

        async def run():
            req = GenRequest(prompt_ids=[256, 5, 6, 7], max_new_tokens=6)
            res = [t async for t in engine.generate(req)]
            await engine.wait_drained()
            return res

        res = asyncio.run(run())
        offline_quant = engine.weight_quant
        engine.stop()
        return res, offline_quant

    offline, wq = gen(qbundle, qparams)
    assert wq == "int4"  # detected from the packed tree, no knob needed
    online, _ = gen(bundle, params, weight_quant="int4")
    assert offline == online

    # double quantization refused with a clear message
    out2 = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "quantize_ckpt.py"),
         str(dst), str(tmp_path / "dst2")],
        capture_output=True, text=True, env=env, cwd=str(REPO),
    )
    assert out2.returncode != 0 and "already" in out2.stderr
