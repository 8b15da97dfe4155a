"""Bring-up invariants (PR 21): no fallback that hides the device.

- the engine's ``health()`` names the device and which implementation each
  kernel's call sites take, with the routing functions' own reasons;
- the TPU-only ragged layout (rows aligned to the kernel's 8-token copies,
  the work plan, the ``_ragged_tpad`` rows of the kernel's view around
  ``_ragged_dense`` packed tokens, null-row warmup operands)
  EXECUTES in tier-1: the
  routing function is patched to answer "kernel" and the kernels run in
  the Pallas interpreter, so the branch no CPU test used to reach serves
  real requests and must agree with the XLA path token for token;
- limits the chip's compiler enforces are load-time errors;
- ``engine.preset`` + ``engine.weight_quant`` builds the packed tree
  directly; a failed warmup fails the endpoint; a mesh the host cannot
  build is an error naming ``mesh``.
"""

import asyncio
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from clearml_serving_tpu import models
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.ops import paged_attention as pa
from clearml_serving_tpu.ops.quant import (
    dequant_llama_params,
    detect_weight_quant,
    quantize_llama_params,
)
from clearml_serving_tpu.serving.endpoints import ModelEndpoint
from clearml_serving_tpu.serving.main import build_app
from clearml_serving_tpu.serving.model_request_processor import (
    FastSimpleQueue,
    ModelRequestProcessor,
)


@pytest.fixture(scope="module")
def parts():
    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    return bundle, bundle.init(jax.random.PRNGKey(0))


async def _collect(engine, prompt, n):
    out = []
    async for tok in engine.generate(
        GenRequest(prompt_ids=list(prompt), max_new_tokens=n)
    ):
        out.append(tok)
    return out


def _serve(engine, prompts, n=6):
    async def run():
        try:
            return await asyncio.gather(
                *[_collect(engine, p, n) for p in prompts]
            )
        finally:
            engine.stop()

    return asyncio.run(run())


# -- health(): device + kernels ----------------------------------------------


def test_health_names_device_and_kernel_routes(parts):
    bundle, params = parts
    dense = LLMEngineCore(bundle, params, max_batch=2, max_seq_len=64)
    h = dense.health()
    assert h["device"]["platform"] == "cpu"
    assert h["device"]["device_count"] == jax.device_count()
    assert h["device"]["peak_bytes_in_use"] is None  # CPU reports none
    assert h["kernels"]["decode"] == "xla"
    assert h["kernels"]["ragged"] is None and h["kernels"]["int4"] is None
    # dense never reaches the paged kernels, and says whether paged would
    assert "engine.cache=dense" in h["kernels"]["reason"]["decode"]
    assert "platform cpu" in h["kernels"]["reason"]["decode"]
    assert dense.lifecycle_stats()["kernels"] == h["kernels"]

    paged = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64, cache_mode="paged",
        scheduler="ragged",
    )
    k = paged.health()["kernels"]
    assert k["decode"] == k["ragged"] == "xla"
    assert "platform cpu" in k["reason"]["ragged"]
    assert not paged._ragged_kernel and paged._ragged_qb == 1


def test_int8_pools_on_16_token_pages_are_routed_with_a_reason(
    parts, monkeypatch
):
    """The old warnings.warn for int8 + 16-token pages is the routing
    reason now: on a TPU this engine's health says xla and why."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bundle = models.build_model(
        "llama",
        {"preset": "llama-tiny", "dtype": "float32", "kv_quant": "int8",
         "dim": 256, "n_heads": 2, "n_kv_heads": 1},  # head_dim 128
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64, cache_mode="paged",
        page_size=16,
    )
    k = engine.health()["kernels"]
    assert k["decode"] == "xla"
    assert "page_size 16" in k["reason"]["decode"]
    assert "int8" in k["reason"]["decode"]


def test_int4_route_reported_for_the_decode_shape(monkeypatch):
    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32", "dim": 256,
                  "ffn_dim": 256, "n_heads": 4, "n_kv_heads": 2},
    )
    params = bundle.init(jax.random.PRNGKey(0), weight_quant="int4")
    engine = LLMEngineCore(bundle, params, max_batch=2, max_seq_len=64)
    k = engine.health()["kernels"]
    assert k["int4"] == "xla" and "platform cpu" in k["reason"]["int4"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # K=256 spans two 128-row groups, every N is a multiple of 128
    assert engine._int4_kernel_reason() is None


# -- the TPU ragged layout, executed ------------------------------------------


def test_kernel_layout_serves_and_matches_the_xla_path(parts, monkeypatch):
    """Route the paged engine to the kernels (interpreted): rows align to
    the kernel's 8-token copies, the work plan rides every launch, the
    null-row warmup launch uses the same operands — and the greedy streams
    equal the XLA path's."""
    bundle, params = parts
    kw = dict(
        max_batch=2, max_seq_len=64, cache_mode="paged", page_size=16,
        scheduler="ragged", step_token_budget=16, ragged_decode_steps=1,
        eos_token_id=None,
    )
    prompts = [list(range(3, 3 + 21)), [7, 8, 9]]  # 3 copies + a short row
    want = _serve(LLMEngineCore(bundle, params, **kw), prompts)

    monkeypatch.setattr(
        pa, "paged_kernel_unsupported_reason", lambda *a, **k: None
    )
    monkeypatch.setattr(
        pa, "paged_attention",
        functools.partial(pa.paged_attention, interpret=True),
    )
    monkeypatch.setattr(
        pa, "ragged_paged_attention",
        functools.partial(pa.ragged_paged_attention, interpret=True),
    )
    monkeypatch.setattr(
        pa, "paged_kv_write",
        functools.partial(pa.paged_kv_write, interpret=True),
    )
    engine = LLMEngineCore(bundle, params, **kw)
    assert engine._ragged_kernel and engine._ragged_qb == pa._RAGGED_QB
    # the dense layers' axis is the budget; the kernel's view adds one copy
    # less a token of alignment waste per row, aligned to 8
    assert (engine._ragged_dense, engine._ragged_tpad) == (16, 32)
    # a tile holds any chunk of this model: an item a row, none further
    assert (engine._ragged_tile, engine._ragged_items) == (128, 2)
    assert engine.health()["kernels"] == {
        "decode": "pallas", "ragged": "pallas", "int4": None, "reason": None,
    }
    from clearml_serving_tpu.llm.warmup import warm_ragged_variants

    assert warm_ragged_variants(engine) >= 1  # null rows, item_rows == -1
    got = _serve(engine, prompts)
    assert got == want
    assert engine.counters["ragged_steps"] > 0


def _serve_staggered(engine, prompts, answers):
    """The first request is decoding when the others arrive together."""
    async def run():
        first = asyncio.Event()

        async def collect(prompt, n):
            out = []
            async for tok in engine.generate(
                GenRequest(prompt_ids=list(prompt), max_new_tokens=n)
            ):
                out.append(tok)
                first.set()
            return out

        try:
            head = asyncio.ensure_future(collect(prompts[0], answers[0]))
            await first.wait()
            return await asyncio.gather(head, *[
                collect(p, n) for p, n in zip(prompts[1:], answers[1:])
            ])
        finally:
            engine.stop()

    return asyncio.run(run())


def test_decode_passes_hand_the_kernel_nothing_for_rows_that_do_not_decode(
    parts, monkeypatch
):
    """Every decode pass over paged KV (the chained passes of a ragged
    launch, the passes of a decode chunk) gives the kernel length 0 for an
    empty slot, a prefill row and a row whose window has closed, and the
    tokens it attends for the others; the streams are the XLA path's, and
    ``ragged.decode_chain_rows`` / ``decode_chain_kv_tokens`` are the sums
    of what the kernel was handed."""
    bundle, params = parts
    kw = dict(
        max_batch=4, max_seq_len=96, cache_mode="paged", page_size=16,
        scheduler="ragged", step_token_budget=24, ragged_decode_steps=4,
        decode_steps=4, eos_token_id=None,
    )
    # the first request is decoding (and goes on for 40 tokens) when the
    # other two arrive: their prompts ride ragged launches whose leftover
    # budget gives the decode rows windows of 4, i.e. chained passes with
    # prefill rows in them; three requests never fill the fourth slot
    prompts = [[5, 6, 7, 8, 9], [7, 8, 9], list(range(3, 3 + 30))]
    answers = [40, 3, 6]

    serve = functools.partial(
        _serve_staggered, prompts=prompts, answers=answers)
    want = serve(LLMEngineCore(bundle, params, **kw))

    handed = []

    def spy(q, k_pool, v_pool, page_table, lengths, **kwargs):
        jax.debug.callback(lambda l: handed.append(tuple(int(x) for x in l)),
                           lengths)
        return kernel(q, k_pool, v_pool, page_table, lengths, **kwargs)

    kernel = functools.partial(pa.paged_attention, interpret=True)
    monkeypatch.setattr(
        pa, "paged_kernel_unsupported_reason", lambda *a, **k: None
    )
    monkeypatch.setattr(pa, "paged_attention", spy)
    monkeypatch.setattr(
        pa, "ragged_paged_attention",
        functools.partial(pa.ragged_paged_attention, interpret=True),
    )
    monkeypatch.setattr(
        pa, "paged_kv_write",
        functools.partial(pa.paged_kv_write, interpret=True),
    )
    engine = LLMEngineCore(bundle, params, **kw)
    chained, chunked = [], []     # one vector of lengths per decode pass
    prefill_rows_masked = []
    retire, dispatch = engine._retire_ragged, engine._dispatch_paged

    def retire_ragged(plan, result):
        for i in range(1, int(plan["launch_steps"])):
            alive = plan["chain_mask"][i - 1]
            chained.append(tuple(int(x) for x in np.where(
                alive, plan["pre_lens"] + 1 + i, 0)))
            prefill_rows_masked.extend(
                not alive[job.slot] for job, _ in plan["shares"])
        return retire(plan, result)

    def dispatch_paged(prep, exhausted):
        held = engine.paged_cache.pool.lengths().copy()
        for s in range(engine.decode_steps):
            chunked.append(tuple(
                int(x) for x in np.where(held > 0, held + s + 1, 0)))
        return dispatch(prep, exhausted)

    monkeypatch.setattr(engine, "_retire_ragged", retire_ragged)
    monkeypatch.setattr(engine, "_dispatch_paged", dispatch_paged)
    got = serve(engine)
    jax.effects_barrier()
    assert got == want
    expected = chained + chunked
    assert sorted(handed) == sorted(expected * len(params["layers"]))
    # both kinds of launch ran; a prefill row is dead in every chained pass
    # it rides, and some slot is empty in every pass
    assert chained and chunked
    assert prefill_rows_masked and all(prefill_rows_masked)
    assert all(0 in v and any(v) for v in expected)
    stats = engine.lifecycle_stats()["ragged"]
    assert stats["decode_chain_rows"] == sum(
        sum(1 for x in v if x) for v in expected)
    assert stats["decode_chain_kv_tokens"] == sum(map(sum, expected))


def test_mixed_passes_hand_the_kernel_the_plans_work_items(parts, monkeypatch):
    """Two prompts arrive under a decoding row: every mixed pass hands the
    ragged kernel exactly its plan's items — a decode row one item, a prompt
    chunk one per query tile (8 here, so a chunk of 20 is three), the list
    padded to its one static length — with the plan's ``row_lens`` /
    ``kv_lens``; the streams are the XLA path's, and ``ragged.mixed_rows`` /
    ``mixed_kv_tokens`` / ``mixed_qk_pairs`` are the sums over the plans."""
    bundle, params = parts
    kw = dict(
        max_batch=4, max_seq_len=96, cache_mode="paged", page_size=16,
        scheduler="ragged", step_token_budget=24, ragged_decode_steps=4,
        decode_steps=4, eos_token_id=None,
    )
    prompts = [[5, 6, 7, 8, 9], list(range(40, 40 + 26)), list(range(3, 3 + 30))]
    answers = [40, 3, 6]
    want = _serve_staggered(LLMEngineCore(bundle, params, **kw), prompts, answers)

    handed, tiles = [], set()

    def spy(q, k_pool, v_pool, page_table, kv_lens, row_starts, row_lens, *,
            item_rows, item_q0, **kwargs):
        # the kernel cuts an item at the tile of ITS operands: the engine's
        # plan has to be built at that tile, for the dtype the model passes
        tiles.add(pa.ragged_query_tile(*q.shape[1:], q.dtype))
        jax.debug.callback(
            lambda *a: handed.append(tuple(tuple(int(x) for x in v) for v in a)),
            item_rows, item_q0, row_lens, kv_lens)
        return kernel(q, k_pool, v_pool, page_table, kv_lens, row_starts,
                      row_lens, item_rows=item_rows, item_q0=item_q0, **kwargs)

    kernel = functools.partial(pa.ragged_paged_attention, interpret=True)
    monkeypatch.setattr(pa, "_RAGGED_TILE_QUERIES", 8)
    monkeypatch.setattr(pa, "_RAGGED_SUB_ROWS", 16)
    monkeypatch.setattr(
        pa, "paged_kernel_unsupported_reason", lambda *a, **k: None
    )
    monkeypatch.setattr(pa, "ragged_paged_attention", spy)
    monkeypatch.setattr(
        pa, "paged_attention",
        functools.partial(pa.paged_attention, interpret=True),
    )
    monkeypatch.setattr(
        pa, "paged_kv_write",
        functools.partial(pa.paged_kv_write, interpret=True),
    )
    engine = LLMEngineCore(bundle, params, **kw)
    # 4 rows on 24 + 4 x 7 = 52 -> 56 flat tokens: 4 + 56 // 8 items
    assert (engine._ragged_tile, engine._ragged_items) == (8, 11)
    expected, work = [], np.zeros(3, np.int64)
    retire = engine._retire_ragged

    def retire_ragged(plan, result):
        row_lens, kv_lens = plan["row_lens"], plan["kv_lens"]
        items = pa.ragged_work_items(row_lens, 8, total=11)
        expected.append(tuple(
            tuple(int(x) for x in v) for v in (*items, row_lens, kv_lens)))
        for n, kv in zip(row_lens, kv_lens):
            if n:
                hist = int(kv) - int(n)
                work[:] += (1, kv, sum(hist + i + 1 for i in range(int(n))))
        return retire(plan, result)

    monkeypatch.setattr(engine, "_retire_ragged", retire_ragged)
    got = _serve_staggered(engine, prompts, answers)
    jax.effects_barrier()
    assert got == want
    assert tiles == {engine._ragged_tile}
    assert sorted(handed) == sorted(expected * len(params["layers"]))
    # prompt chunks of several tiles rode beside a decode row's one item
    plans = [dict(zip(("rows", "q0", "row_lens", "kv_lens"), e)) for e in expected]
    assert any(
        max(p["q0"]) >= 16 and 1 in p["row_lens"] for p in plans)
    assert all(p["rows"].count(-1) >= 11 - 4 - 2 for p in plans)
    stats = engine.lifecycle_stats()["ragged"]
    assert [stats[k] for k in ("mixed_rows", "mixed_kv_tokens", "mixed_qk_pairs")
            ] == [int(x) for x in work]
    assert work[0] > len(expected) and work[2] > work[1]


def test_decode_paged_masks_the_rows_a_pass_does_not_advance(parts, monkeypatch):
    """``decode_paged(..., active=)``: the attention is handed length 0 for
    exactly the masked rows (an empty slot, a prefill row, a closed window:
    the engine's ``chain_mask`` row) and ``lengths + 1`` for the others,
    whose logits are bit for bit those of the unmasked step."""
    bundle, params = parts
    rows, page, pages = 4, 16, 2
    shape = (bundle.n_layers, bundle.n_kv_heads, 1 + rows * pages, page,
             bundle.head_dim)
    k = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)
    table = jnp.arange(1, 1 + rows * pages, dtype=jnp.int32).reshape(rows, pages)
    lengths = jnp.asarray([20, 7, 0, 31], jnp.int32)
    active = jnp.asarray([True, False, False, True])
    tokens = jnp.asarray([3, 4, 5, 6], jnp.int32)
    coords = jnp.zeros(rows, jnp.int32)               # the null page
    handed, reference = [], pa.paged_attention_xla

    def spy(q, k_pool, v_pool, page_table, lens, **kwargs):
        handed.append(np.asarray(lens))
        return reference(q, k_pool, v_pool, page_table, lens, **kwargs)

    monkeypatch.setattr(pa, "paged_attention_xla", spy)
    masked = bundle.decode_paged(params, tokens, k, v, table, lengths,
                                 coords, coords, active=active)[0]
    assert all((h == [21, 0, 0, 32]).all() for h in handed) and handed
    del handed[:]
    plain = bundle.decode_paged(params, tokens, k, v, table, lengths,
                                coords, coords)[0]
    assert all((h == [21, 8, 1, 32]).all() for h in handed) and handed
    alive = np.asarray(active)
    assert np.asarray(masked)[alive].tobytes() == np.asarray(plain)[alive].tobytes()


# -- limits the compiler enforces are load-time errors --------------------------


def test_smem_overflow_is_a_load_time_error():
    stub = types.SimpleNamespace(
        max_batch=256, _pages_per_seq=1025, max_seq_len=16384
    )
    with pytest.raises(ValueError, match="max_seq_len") as exc:
        LLMEngineCore._check_kernel_smem(stub, tokens=2048)
    assert "scalar memory" in str(exc.value)
    # the smoke's configuration is far inside the limit
    ok = types.SimpleNamespace(max_batch=8, _pages_per_seq=129, max_seq_len=2048)
    LLMEngineCore._check_kernel_smem(ok, tokens=184, tree_width=5)


def test_paged_cache_under_a_mesh_is_refused_where_the_kernels_would_run(
    parts, monkeypatch
):
    from clearml_serving_tpu.parallel import make_mesh

    bundle, params = parts
    mesh = make_mesh({"tp": 2, "dp": -1})
    monkeypatch.setattr(
        pa, "paged_kernel_unsupported_reason", lambda *a, **k: None
    )
    with pytest.raises(ValueError, match="mesh") as exc:
        LLMEngineCore(
            bundle, params, max_batch=2, max_seq_len=64, cache_mode="paged",
            mesh=mesh,
        )
    assert "automatically partitioned" in str(exc.value)


# -- endpoint load --------------------------------------------------------------


def test_init_with_weight_quant_builds_the_packed_tree_directly():
    # the scanned build (chip_smoke's); the unscanned one loads through the
    # endpoint in test_preset_with_weight_quant_never_holds_full_precision
    key = jax.random.PRNGKey(3)
    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "scan_layers": True}
    )
    direct = bundle.init(key, weight_quant="int8")
    assert detect_weight_quant(direct) == "int8"
    ref = quantize_llama_params(bundle.init(key), bits=8)
    assert jax.tree.structure(direct) == jax.tree.structure(ref)
    # same weights up to one int8 level (jit fuses the bf16 cast)
    a = dequant_llama_params(direct, jnp.float32)["layers"]["w_down"]
    b = dequant_llama_params(ref, jnp.float32)["layers"]["w_down"]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0.01)
    with pytest.raises(ValueError, match="weight_quant"):
        bundle.init(key, weight_quant="int3")


def _endpoint(tmp_path, url, aux):
    mrp = ModelRequestProcessor(
        state_root=str(tmp_path), force_create=True, name=url
    )
    mrp.add_endpoint(
        ModelEndpoint(engine_type="llm", serving_url=url, auxiliary_cfg=aux)
    )
    mrp.serialize()
    mrp.deserialize(skip_sync=True)
    return mrp


def _run(mrp, fn):
    async def runner():
        client = TestClient(TestServer(build_app(mrp)))
        await client.start_server()
        try:
            return await fn(client)
        finally:
            await client.close()

    return asyncio.run(runner())


_TINY = {"preset": "llama-tiny", "config": {"dtype": "float32"},
         "max_batch": 1, "max_seq_len": 64, "prefill_buckets": [16]}


def test_preset_with_weight_quant_never_holds_full_precision(
    tmp_path, monkeypatch
):
    """The serving entry point asks init for the packed tree; the engine
    finds it quantized and quantizes nothing."""
    from clearml_serving_tpu.ops import quant

    def boom(*a, **k):
        raise AssertionError("the engine quantized a full-precision tree")

    mrp = _endpoint(tmp_path, "q8", {"engine": dict(_TINY, weight_quant="int8")})

    async def fn(client):
        # patched only around the load: init itself quantizes per layer
        # through the same function, which is bound before the patch
        r = await client.post(
            "/serve/openai/v1/completions",
            json={"model": "q8", "prompt": [1, 2, 3], "max_tokens": 3},
        )
        ready = await client.get("/ready")
        return r.status, await r.json(), await ready.json()

    seen = {}
    real_init = LLMEngineCore.__init__

    def spy(self, bundle, params, **kw):
        seen["pre"] = detect_weight_quant(params)
        monkeypatch.setattr(quant, "quantize_llama_params", boom)
        try:
            real_init(self, bundle, params, **kw)
        finally:
            monkeypatch.undo()

    monkeypatch.setattr(LLMEngineCore, "__init__", spy)
    status, body, ready = _run(mrp, fn)
    assert status == 200, body
    assert seen["pre"] == "int8"
    engine = ready["engines"]["q8"]
    assert engine["weights"]["quant"] == "int8"
    assert engine["device"]["platform"] == "cpu"
    assert ready["stats_queue"] == "python"


def test_failed_warmup_fails_the_endpoint_not_the_first_user(
    tmp_path, monkeypatch
):
    async def broken(self, full=True):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(LLMEngineCore, "warmup", broken)
    mrp = _endpoint(tmp_path, "cold", {"engine": dict(_TINY, warmup="startup")})

    async def fn(client):
        out = []
        for _ in range(2):  # every request, not only the first
            r = await client.post(
                "/serve/openai/v1/completions",
                json={"model": "cold", "prompt": [1, 2], "max_tokens": 2},
            )
            out.append((r.status, await r.text()))
        ready = await client.get("/ready")
        return out, ready.status, await ready.json()

    answers, ready_status, ready = _run(mrp, fn)
    for status, text in answers:
        assert status == 422 and "warmup" in text and "Mosaic" in text
    assert ready_status == 503 and ready["not_ready"] == ["cold"]
    assert ready["engines"]["cold"]["warmup"].startswith("failed")


def test_mesh_the_host_cannot_build_is_an_endpoint_load_error(tmp_path):
    mrp = _endpoint(tmp_path, "meshy", {"engine": dict(_TINY), "mesh": {"tp": 3}})

    async def fn(client):
        r = await client.post(
            "/serve/openai/v1/completions",
            json={"model": "meshy", "prompt": [1, 2], "max_tokens": 2},
        )
        return r.status, await r.text()

    status, text = _run(mrp, fn)
    assert status == 422 and "mesh" in text and "device" in text, text
    assert "meshy" not in mrp._engine_processor_lookup


def test_stats_queue_says_which_backend_serves(monkeypatch):
    monkeypatch.delenv("TPUSERVE_NATIVE_QUEUE", raising=False)
    assert FastSimpleQueue().backend == "python"
