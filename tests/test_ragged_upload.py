"""A ragged launch's host operands cross to the device in ONE transfer
(docs/ragged_attention.md, "The launch's operands"): the dispatch worker
fills one staging buffer from the plan's host vectors, it crosses as the
one argument of one unpack program, which hands back exactly the operands the
per-array uploads gave, by name, shape, dtype and value, for every step
variant and after a pool-exhaustion drop; the buffer aliases nothing the
host writes later, and each variant's unpack compiles once."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.llm import compile_sentry
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

LONG = [(i * 7 + 3) % 250 + 1 for i in range(40)]
SHORT = [5, 9, 2, 17, 33]
REPEAT = [5, 9, 2, 17, 5, 9, 2]
STATE_CFG = dict(vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                 head_dim=16, ffn_dim=96, scan_layers=True, dtype="float32",
                 attention="power_retention", retention_degree=2, qk_norm=True,
                 norm_eps=1e-6, rope_theta=1e6, max_seq_len=512)

# what every cache kind's step takes, and what each kind and variant adds:
# the names of the per-array uploads this buffer replaced
COMMON = {"tokens", "tok_pos", "tok_row", "tok_valid", "row_last", "kv_lens",
          "row_starts", "row_lens", "decode_mask"}
PAGED = {"tok_slot", "page_table", "write_page", "write_offset"}
SPEC = {"spec_mask", "sspec_mask", "drafts", "row_logit_idx"}
TREE = {"tree_tokens", "tree_parents", "tree_n", "tree_anc"}


@pytest.fixture(scope="module")
def paged_parts():
    bundle = models.build_model("llama", {"preset": "llama-tiny", "dtype": "float32"})
    return bundle, bundle.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def state_parts():
    bundle = models.build_model("llama", STATE_CFG)
    return bundle, bundle.init(jax.random.PRNGKey(3))


def _paged(parts, **kw):
    args = dict(max_batch=2, max_seq_len=128, eos_token_id=None, decode_steps=4,
                ragged_decode_steps=4, cache_mode="paged", page_size=8,
                num_pages=64, step_token_budget=16, pipeline_depth=1)
    args.update(kw)
    return LLMEngineCore(*parts, **args)


def _state(parts, **kw):
    args = dict(max_batch=3, max_seq_len=256, cache_mode="state",
                step_token_budget=16, decode_steps=4, eos_token_id=None)
    args.update(kw)
    return LLMEngineCore(*parts, **args)


def _serve(engine, requests, gap=0.05):
    """(prompt, max_new_tokens, request keywords) requests ``gap`` apart,
    so admissions ride launches beside live decode rows; a request that
    fails yields its exception."""

    async def one(i, ids, n, kw):
        await asyncio.sleep(gap * i)
        req = GenRequest(prompt_ids=list(ids), max_new_tokens=n, **kw)
        try:
            return [t async for t in engine.generate(req)]
        except MemoryError as ex:
            return ex

    async def run():
        outs = await asyncio.gather(*(one(i, *r) for i, r in enumerate(requests)))
        await engine.wait_drained()
        return outs

    return asyncio.run(run())


class Uploads:
    """Every upload of the engine's ragged launches: the layout's key, a
    copy of the plan's host vectors as the worker staged them, the device
    operands the unpack gave back, and the live plan."""

    def __init__(self, engine):
        self.engine, self.seen = engine, []
        upload = engine._upload_ragged_operands

        def spy(plan):
            key = (plan["launch_steps"], plan["use_extras"],
                   plan["row_logit_idx"] is not None)
            layout, _total = engine._ragged_layouts[key]
            host = {name: np.array(plan[name]) for name, *_ in layout}
            sizes = {name: v.size for name, v in host.items()}
            # the unpack program consumes ``chain_at``: a row the launch in
            # flight carried takes its pending token from the device's
            # chain there, every other row points past the end
            at = host.pop("chain_at")
            carried = at < host["tokens"].size
            assert plan["behind"] or not carried.any()
            if carried.any():
                chain = np.asarray(engine._next_token_dev)
                host["tokens"][at[carried]] = chain[carried]
            dev = upload(plan)
            self.seen.append({"key": key, "host": host, "dev": dev, "plan": plan,
                              "sizes": sizes,
                              "dropped": bool(plan["exhausted"] or plan["failed_jobs"])})
            return dev

        engine._upload_ragged_operands = spy

    def of(self, pick):
        found = [u for u in self.seen if pick(u)]
        assert found, "no launch of that kind among {}".format(
            sorted({u["key"] for u in self.seen}))
        return found


def _check(upload, names):
    """The unpacked operands are what ``jnp.asarray`` of each host vector
    gave: same names, and per name the same dtype, shape and values."""
    assert set(upload["dev"]) == set(upload["host"]) == names
    for name, host in upload["host"].items():
        want, got = jnp.asarray(host), upload["dev"][name]
        assert got.dtype == want.dtype, name
        assert got.shape == want.shape, name
        assert got.sharding == want.sharding and not got._committed, name
        np.testing.assert_array_equal(np.asarray(got), host, err_msg=name)


# -- (1) the unpacked operands are the per-array uploads' ----------------------


def _case_paged(parts, steps):
    engine = _paged(parts)
    seen = Uploads(engine)
    _serve(engine, [(SHORT, 12, {}), (LONG, 6, {})])
    chain = {"chain_mask", "chain_wp", "chain_wo"} if steps > 1 else set()
    return engine, seen.of(lambda u: u["key"] == (steps, False, False)), (
        COMMON | PAGED | chain)


def _case_state(parts):
    engine = _state(parts)
    seen = Uploads(engine)
    _serve(engine, [(SHORT, 12, {}), (LONG, 6, {})])
    one = seen.of(lambda u: u["key"] == (1, False, False))
    _check(one[0], COMMON | {"row_reset"})
    return engine, seen.of(lambda u: u["key"][0] > 1), (
        COMMON | {"row_reset", "chain_mask"})


def _case_dropped(parts):
    """A decode row and a prompt chunk ride one launch and the pool refuses
    the chunk's pages: ``_ragged_drop_row`` edits the host vectors in place
    BEFORE they are staged."""
    engine = _paged(parts, ragged_decode_steps=1)
    seen = Uploads(engine)
    pool, dispatch = engine.paged_cache.pool, engine._dispatch_ragged_device
    extend, refused, chunk_rows = pool.extend, [], []

    def mixed(plan):
        # the first launch that carries a prompt chunk beside a decode row
        if plan["decode_mask"].any() and plan["shares"] and not refused:
            chunk_rows.append(plan["shares"][0][0].slot)
        try:
            return dispatch(plan)
        finally:
            chunk_rows.clear()

    def grudging(slot, n):
        if slot in chunk_rows:
            refused.append(slot)
            raise MemoryError("kv page pool exhausted (the test's)")
        return extend(slot, n)

    engine._dispatch_ragged_device, pool.extend = mixed, grudging
    outs = _serve(engine, [(SHORT, 12, {}), (LONG, 6, {})])
    assert refused and isinstance(outs[1], MemoryError) and len(outs[0]) == 12
    dropped = seen.of(lambda u: u["dropped"])
    slot = refused[0]
    # the dropped row's tokens are pads in what crossed
    host = dropped[0]["host"]
    assert host["row_lens"][slot] == 0 and not host["tok_valid"][
        host["tok_row"] == slot].any()
    return engine, dropped, COMMON | PAGED


def _case_extras(parts):
    engine = _paged(parts)
    seen = Uploads(engine)
    _serve(engine, [(SHORT, 12, {"logit_bias": {7: 4.0}}), (LONG, 6, {})])
    found = seen.of(lambda u: u["key"][1])
    assert any(u["host"]["counters"].any() for u in found)
    names = COMMON | PAGED | {"counters"}
    return engine, found, lambda u: names | (
        {"chain_mask", "chain_wp", "chain_wo"} if u["key"][0] > 1 else set())


def _case_spec(parts, tree):
    engine = _paged(parts, speculation="ngram", spec_k=2, spec_ngram=2,
                    spec_tree=tree, **({"spec_branch": 2} if tree else {}))
    seen = Uploads(engine)
    _serve(engine, [(REPEAT, 24, {}), (LONG, 6, {})])
    found = seen.of(lambda u: u["key"][2])
    assert any(u["host"]["spec_mask"].any() for u in found)
    names = COMMON | PAGED | SPEC | (TREE if tree else set())
    return engine, found, lambda u: names | (
        {"chain_mask", "chain_wp", "chain_wo"} if u["key"][0] > 1 else set())


CASES = {
    "paged-window-1": lambda p, s: _case_paged(p, 1),
    "paged-window-4": lambda p, s: _case_paged(p, 4),
    "state": lambda p, s: _case_state(s),
    "paged-after-a-drop": lambda p, s: _case_dropped(p),
    "paged-extras": lambda p, s: _case_extras(p),
    "paged-verify-rows": lambda p, s: _case_spec(p, False),
    "paged-tree-rows": lambda p, s: _case_spec(p, True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_unpacked_operands_are_the_per_array_uploads(case, paged_parts, state_parts):
    engine, uploads, names = CASES[case](paged_parts, state_parts)
    try:
        for upload in uploads:
            _check(upload, names(upload) if callable(names) else names)
        # one layout a variant, no wider than its operands, never rebuilt
        for upload in uploads:
            layout, total = engine._ragged_layouts[upload["key"]]
            assert total == sum(upload["sizes"].values())
            assert [e[1] for e in layout] == list(np.cumsum(
                [0] + [upload["sizes"][e[0]] for e in layout])[:-1])
    finally:
        engine.stop()


# -- (2) the counter that says it engaged --------------------------------------


@pytest.mark.parametrize("kind", ["paged", "state"])
def test_one_transfer_a_launch(kind, paged_parts, state_parts):
    engine = _paged(paged_parts) if kind == "paged" else _state(state_parts)
    try:
        before = engine.lifecycle_stats()["ragged"]
        assert before["h2d_transfers"] == before["steps"] == 0
        _serve(engine, [(SHORT, 12, {}), (LONG, 6, {})])
        ragged = engine.lifecycle_stats()["ragged"]
        assert ragged["steps"] >= 4
        assert ragged["h2d_transfers"] / ragged["steps"] == 1
        assert engine.health()["ragged"]["h2d_transfers"] == ragged["h2d_transfers"]
    finally:
        engine.stop()


def test_the_worker_uploads_nothing_else_before_the_call(paged_parts, monkeypatch):
    """Counted where it happens: between ``worker_in`` and the jitted call
    the worker asks jax for no ``asarray`` and no ``device_put``: the one
    host array that crosses is the unpack program's argument."""
    from clearml_serving_tpu.llm import engine as engine_mod

    engine = _paged(paged_parts)
    calls, inside = [], []
    dispatch, step = engine._dispatch_ragged_device, engine._ragged_paged_jit
    unpack = engine._ragged_unpack_jit

    def worker(plan):
        inside.append(True)
        try:
            return dispatch(plan)
        finally:
            inside.clear()

    def called(*args, **kw):
        inside.clear()                   # the stretch ends at the call
        return step(*args, **kw)

    def counting(name, fn):
        def call(*args, **kw):
            if inside:
                calls.append(name)
            return fn(*args, **kw)
        return call

    def unpacking(staged, layout, chain):
        assert inside and type(staged) is np.ndarray
        calls.append("unpack")
        return unpack(staged, layout, chain)

    engine._dispatch_ragged_device, engine._ragged_paged_jit = worker, called
    engine._ragged_unpack_jit = unpacking
    monkeypatch.setattr(engine_mod.jnp, "asarray",
                        counting("asarray", jnp.asarray))
    monkeypatch.setattr(engine_mod.jax, "device_put",
                        counting("device_put", jax.device_put))
    try:
        _serve(engine, [(SHORT, 12, {}), (LONG, 6, {})])
        steps = engine.lifecycle_stats()["ragged"]["steps"]
        assert steps >= 4 and calls == ["unpack"] * steps
    finally:
        engine.stop()


# -- (3) nothing the host writes later reaches the device ----------------------


def test_writing_the_plan_after_the_dispatch_changes_no_operand(paged_parts):
    """On the CPU backend a device array may alias the host memory it was
    put from (the hazard ``_chain_input`` records): the staging buffer is
    fresh a launch and the plan's own vectors never cross, so scribbling
    over every one of them the moment the worker returns (the step may not
    have run yet) moves neither an operand nor a token."""
    quiet = _paged(paged_parts)
    want = _serve(quiet, [(SHORT, 12, {}), (LONG, 6, {})])
    quiet.stop()

    engine = _paged(paged_parts)
    seen = Uploads(engine)
    dispatch = engine._dispatch_ragged_device
    checked = []

    def scribbling(plan):
        result = dispatch(plan)
        upload = seen.seen[-1]
        assert upload["plan"] is plan
        for name, host in upload["host"].items():
            live = plan[name]
            live[...] = ~live if live.dtype == bool else live + 77
        for name, host in upload["host"].items():
            np.testing.assert_array_equal(
                np.asarray(upload["dev"][name]), host, err_msg=name)
            plan[name][...] = host       # the retire reads the plan too
        checked.append(plan["seq"])
        return result

    engine._dispatch_ragged_device = scribbling
    try:
        got = _serve(engine, [(SHORT, 12, {}), (LONG, 6, {})])
        assert len(checked) >= 4 and got == want
    finally:
        engine.stop()


# -- (4) each variant's unpack compiles once ------------------------------------


@pytest.mark.parametrize("kind", ["paged", "state"])
def test_a_second_pass_compiles_nothing(kind, paged_parts, state_parts, monkeypatch):
    """No warm-up sweep: the first pass of a stream that meets both decode
    windows and ends two prompts builds each window's step, its unpack
    and the first-token program behind a finishing launch; the same stream
    again compiles nothing (the compile sentry's count after the fence).
    Nothing here counts compile EVENTS of the first pass: a program that an
    earlier test of this process left in jax's caches (a function's
    programs outlive a ``jax.jit`` wrapper), or a checkout's persisted
    ``.jax_cache`` served, is no event. What is held instead: one static
    layout a variant (the unpack's only compile key beside the buffer's
    length, which follows from it), and no compile of anything after the
    fence, with the persistent cache off so that none can hide as a load."""
    monkeypatch.setenv("TPUSERVE_COMPILE_SENTRY", "1")
    sentry = compile_sentry.get()
    sentry.reset(strict=False)
    persisted = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    engine = (_paged(paged_parts, max_batch=3, step_token_budget=24)
              if kind == "paged" else _state(state_parts, max_batch=2))
    seen = Uploads(engine)
    layouts, firsts = [], []
    unpack, first_token = engine._ragged_unpack_jit, engine._first_token_jit

    def unpacking(staged, layout, chain):
        layouts.append((staged.shape, layout))
        return unpack(staged, layout, chain)

    def sampling(logits, staged, layout, key, state, keyed):
        firsts.append((tuple(logits.shape), staged.shape, layout, keyed))
        return first_token(logits, staged, layout, key, state, keyed)

    engine._ragged_unpack_jit, engine._first_token_jit = unpacking, sampling
    traffic = [(SHORT, 12, {}), (LONG, 6, {})]
    try:
        first = _serve(engine, traffic)
        assert {u["key"][0] for u in seen.seen} == {1, 4}
        # two variants, two unpack programs: each launch handed the unpack
        # its variant's ONE layout object and a buffer of that length
        assert len(set(layouts)) == 2
        for (shape, layout), upload in zip(layouts, seen.seen):
            want, total = engine._ragged_layouts[upload["key"]]
            assert layout is want and shape == (total,)
        # both prompts ended in a launch, through one first-token program
        assert len(firsts) == 2 and len(set(firsts)) == 1
        assert all(e["context"]["phase"] == "ragged"
                   for e in sentry.stats()["events"]
                   if "unpack_ragged_operands" in e["fn"]
                   or "first_tokens" in e["fn"])
        sentry.fence()
        again = _serve(engine, traffic)
        assert again == first
        assert len(firsts) == 4 and len(set(firsts)) == 1
        assert len(set(layouts)) == 2
        assert sentry.post_fence_compiles == 0, sentry.stats()["events"][-5:]
    finally:
        engine.stop()
        sentry.reset(strict=False)
        jax.config.update("jax_enable_compilation_cache", persisted)
