"""One ragged engine that holds a page pool AND a slot pool
(docs/hybrid_cache.md), at tiny widths on the CPU: a row's life in the two
pools (admit, a prompt in several launches, decode, preempt, come back by
recompute, free), slots that change hands, the recovery that starts a prompt
again, every refusal by name, the ``ssm`` block of lifecycle_stats, and what
a model WITHOUT a row state gets: a plan and a carry with no state plane and
no reset flag."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.llm.kv_cache import PagedKVCache, StateCache

from test_falcon_h1_model import prompt_of, reference, tiny


def make_engine(parts, **kw):
    _cfg, bundle, params = parts
    args = dict(max_batch=3, max_seq_len=200, cache_mode="paged", page_size=8,
                prefix_cache=0, step_token_budget=32, decode_steps=4,
                eos_token_id=None)
    args.update(kw)
    return LLMEngineCore(bundle, params, **args)


@pytest.fixture(scope="module")
def parts():
    return tiny()


async def collect(engine, request):
    return [t async for t in engine.generate(request)]


def greedy(parts, prompt, out):
    cfg, _bundle, params = parts
    seq = list(prompt) + list(out)
    want = reference(cfg, params, seq[:-1], len(prompt))
    return np.argmax(want, -1).tolist()


def request(prompt, n, **kw):
    return GenRequest(prompt_ids=list(prompt), max_new_tokens=n,
                      temperature=0.0, **kw)


# ----------------------------------------------------- a row's life, in two

def test_rows_are_admitted_chunked_decoded_and_freed_in_both_pools(parts):
    """Five requests over three rows: prompts of 5 to 90 tokens against a
    32-token budget (several launches each, beside decoding rows), every
    stream the reference's greedy one; afterwards no page and no slot is
    owned, and the slots that changed hands were counted as zero."""
    engine = make_engine(parts)
    prompts = [prompt_of(n, seed=n) for n in (40, 90, 5, 33, 64)]

    async def run():
        outs = await asyncio.gather(
            *[collect(engine, request(p, 10)) for p in prompts])
        await engine.wait_drained()
        return outs

    outs = asyncio.run(run())
    for prompt, out in zip(prompts, outs):
        assert out == greedy(parts, prompt, out)
    stats = engine.lifecycle_stats()
    ssm, pool = stats["ssm"], stats["state_pool"]
    assert pool == ssm["state_pool"]
    assert pool["slots"] == 3 and pool["in_use"] == 0
    assert pool["in_use_peak"] == 3 and pool["resets"] == 5 == ssm["resets"]
    assert pool["dtype"] == "float32" and set(pool["planes"]) == {"h", "conv"}
    assert pool["planes"]["h"][1] == 4          # three rows and the null slot
    assert engine.paged_cache.pool.free_pages == \
        engine.paged_cache.pool.num_pages - 1   # all but the null page
    assert all(engine.paged_cache.pool.slot_length(s) == 0 for s in range(3))
    # the mixer's work: every prompt token but the one-token tails rode a
    # chunk, every decode token an update
    total_prompt = sum(len(p) for p in prompts)
    assert ssm["chunk_tokens"] + ssm["update_rows"] >= total_prompt + 5 * 9
    assert 0 < ssm["chunk_rows"] <= ssm["chunk_tokens"]
    assert ssm["passes"] == stats["sampler"]["passes"] > 0
    assert ssm["layers"] == 3 and ssm["rewinds"] == 0
    kernels = stats["kernels"]
    assert kernels["ssd_update"] == kernels["ssd_chunk"] == "xla"
    assert "platform cpu" in kernels["reason"]["ssd_chunk"]
    engine.stop()


def test_a_preempted_row_gives_up_pages_and_slot_and_comes_back(parts):
    """One row: a batch request is preempted by an interactive one, which
    takes the row (its slot counted as zero), and comes back by recompute of
    its history: both streams are the reference's."""
    prompt = prompt_of(17, seed=9)

    async def contended():
        engine = make_engine(parts, max_batch=1, preempt_batch=True,
                             preempt_budget=2, step_token_budget=8)
        batch = request(prompt, 24, priority="batch")
        task = asyncio.create_task(collect(engine, batch))
        while batch.produced < 6:
            await asyncio.sleep(0.005)
        hi = request([1, 9, 9], 3)
        fast = await asyncio.wait_for(collect(engine, hi), 120)
        out = await asyncio.wait_for(task, 120)
        await engine.wait_drained()
        return engine, out, fast

    engine, got, fast = asyncio.run(contended())
    assert engine.counters["preemptions"] >= 1, "no preemption happened"
    assert got == greedy(parts, prompt, got) and len(got) == 24
    assert fast == greedy(parts, [1, 9, 9], fast)
    pool = engine.lifecycle_stats()["state_pool"]
    assert pool["in_use"] == 0 and pool["resets"] >= 3   # batch, hi, batch again
    assert engine.paged_cache.pool.slot_length(0) == 0
    engine.stop()


def test_pages_and_slot_never_outlive_each_other(parts):
    """Whatever frees a row frees both: at every point of a run a slot is
    owned exactly where its row holds pages or is being admitted."""
    engine = make_engine(parts, max_batch=2)
    seen = []
    release = engine._release_cache_slot

    def checked(slot):
        release(slot)
        seen.append((engine.paged_cache.pool.slot_length(slot),
                     engine.state_cache.owned(slot)))

    engine._release_cache_slot = checked

    async def run():
        outs = await asyncio.gather(*[
            collect(engine, request(prompt_of(n, seed=n), 6))
            for n in (20, 45, 12)])
        await engine.wait_drained()
        return outs

    asyncio.run(run())
    assert len(seen) >= 3 and all(s == (0, False) for s in seen)
    engine.stop()


def test_a_recovery_starts_the_prompt_again_in_both_pools(parts):
    """A watchdog trip mid-step: the state took the chunk and cannot give it
    back, so the surviving job's pages go too and its prompt starts again."""
    from clearml_serving_tpu.llm.engine import _RaggedJob

    engine = make_engine(parts)
    req = request(prompt_of(40, seed=3), 4)
    pool = engine.paged_cache.pool
    pool.allocate(1, 24)
    engine.state_cache.allocate(1)
    engine.state_cache.advance(1, 24)
    job = _RaggedJob(request=req, slot=1, pos=24)
    engine._prefill_jobs.append(job)
    plan = {"shares": [(job, 16)], "pre_lens": np.array([0, 24, 0])}

    async def recover():
        engine._finish_recovery = lambda: asyncio.sleep(0)
        await engine._ragged_recover(plan)

    asyncio.run(recover())
    assert job.pos == 0 and pool.slot_length(1) == 0
    assert engine.state_cache.length(1) == 0
    assert engine.state_cache.rewinds == 1
    engine._prefill_jobs.clear()
    engine._release_cache_slot(1)
    engine.stop()


def test_the_state_cache_holds_whatever_planes_a_model_declares(parts):
    _cfg, bundle, _params = parts
    cache = StateCache(bundle.init_state, 3)
    assert set(cache.planes) == {"h", "conv"}
    assert cache.planes["h"].shape == (3, 4, 4, 16, 16)
    assert cache.planes["conv"].shape == (3, 4, 3, 128)
    per_slot = 3 * (4 * 16 * 16 + 3 * 128) * 4
    assert cache.bytes_per_slot == per_slot
    assert cache.pool_bytes() == 4 * per_slot       # the null slot is held too
    cache.allocate(2)
    cache.advance(2, 9)
    snap = cache.snapshot()
    assert (snap["in_use"], snap["resets"]) == (1, 1)
    assert "s_shape" not in snap and snap["planes"]["conv"] == [3, 4, 3, 128]
    # beside the pages the planes ride the carry, and come back through it
    paged = PagedKVCache(3, 2, 16, num_pages=9, page_size=8, max_slots=3,
                         dtype="float32", row_state=cache)
    v, planes = paged.v_carry
    assert planes is cache.planes and v is paged.v
    paged.v_carry = (v, {k: a + 1 for k, a in planes.items()})
    assert float(cache.planes["h"][0, 0, 0, 0, 0]) == 1.0
    with pytest.raises(ValueError, match="STANDARD pools alone"):
        PagedKVCache(3, 2, 16, num_pages=9, counters=8, row_state=cache)


# --------------------------------------------------------------- refusals

ENGINE_REFUSALS = {
    "prefix_cache": (dict(prefix_cache=8, prefix_block=8),
                     "prefix_cache cannot serve a model that keeps a "
                     "recurrent state beside its pages"),
    "host_tier": (dict(prefix_cache_host_pages=8), "HostKVTier"),
    "speculation": (dict(speculation="ngram"),
                    "speculation cannot serve a model that keeps a recurrent "
                    "state"),
    "lora": (dict(lora_adapters={"a": {}}),
             "lora_adapters are not served by a model that keeps"),
    "dense": (dict(cache_mode="dense"),
              "own K/V pages AND a recurrent state slot: serve it with "
              "engine.cache=paged"),
    "state": (dict(cache_mode="state", scheduler="ragged"),
              "engine.cache=state is for models with no keys and values"),
}


@pytest.mark.parametrize("case", sorted(ENGINE_REFUSALS))
def test_what_assumes_a_row_is_its_pages_is_refused_by_name(parts, case):
    kw, message = ENGINE_REFUSALS[case]
    with pytest.raises(ValueError, match=message):
        make_engine(parts, **kw)


def test_a_mesh_shipment_and_kv_quant_are_refused_by_name(parts):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    with pytest.raises(ValueError, match="2-device mesh cannot serve a model "
                                         "that keeps a recurrent state"):
        make_engine(parts, mesh=mesh)
    engine = make_engine(parts)
    with pytest.raises(ValueError, match="KVShipment.*recurrent state beside"):
        engine.attach_kv_transport(object(), role="prefill")
    engine.stop()
    with pytest.raises(ValueError, match="kv_quant cannot serve arch falcon_h1"):
        tiny(kv_quant="int8")


# ------------------------------ a model that declares no row state: as before

def test_a_model_without_a_row_state_gets_no_plane_and_no_flag():
    """arch llama on pages: the carry is the bare V pool, the launch's staged
    operands hold no ``row_reset``, lifecycle_stats no ``ssm`` block, the
    kernels block no SSD entry; a state-only model keeps its flag."""
    cfg = dict(vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
               ffn_dim=64, max_seq_len=64, dtype="float32")
    bundle = models.build_model("llama", cfg)
    engine = LLMEngineCore(bundle, bundle.init(jax.random.PRNGKey(0)),
                           max_batch=2, max_seq_len=64, cache_mode="paged",
                           page_size=8, step_token_budget=16)
    assert engine.state_cache is None and engine._row_state is None
    assert engine.paged_cache.row_state is None
    assert engine.paged_cache.v_carry is engine.paged_cache.v
    names = {name for layout, _ in engine._ragged_layouts.values()
             for name, *_ in layout}
    assert "row_reset" not in names and "page_table" in names
    stats = engine.lifecycle_stats()
    assert "ssm" not in stats and stats["state_pool"] is None
    assert not {"ssd_update", "ssd_chunk"} & set(stats["kernels"])
    engine.stop()


def test_the_hybrid_plan_is_the_paged_plan(parts):
    """The model reads a starting row off the launch's own positions: the
    staged operands of a hybrid engine are those of any paged engine."""
    engine = make_engine(parts)
    names = {name for layout, _ in engine._ragged_layouts.values()
             for name, *_ in layout}
    assert "row_reset" not in names
    assert {"page_table", "write_page", "kv_lens", "row_lens"} <= names
    v, planes = engine.paged_cache.v_carry
    assert set(planes) == {"h", "conv"} and v.ndim == 5
    engine.stop()
