"""A prompt's first token is sampled BEHIND the launch that finished its
prompt (docs/ragged_attention.md, "The step's order"): the dispatch worker
enqueues one program over the finishing rows' logits right after the launch,
its results come back with the launch's own copies, and the loop thread's
commit is host bookkeeping.

(1) what is served is what the parent served: token ids, log-probability
    entries and endings of a mixed traffic, recorded on ``0c5390e`` (PR 48)
    BEFORE the commit moved (``tests/data/first_token_pr48.json``; floats
    compare by ``float.hex``; re-record only for a change meant to move
    numerics: ``JAX_PLATFORMS=cpu PYTHONPATH=. python
    tests/test_first_token_commit.py --record``). PR 54 re-recorded four
    entries' floats at depth 1 (every id and ending is PR 48's): the MIXED
    requests are now submitted each when the one before it has been
    admitted, not 30 ms apart, so a prompt's chunk cuts, which a state's
    and a page's floats follow, are the same on a fast and on a loaded host
    (ROADMAP D10 (a)); both engines run at depth 1 here, and
    ``tests/test_launch_in_flight.py`` holds depth 2 to the same ids;
(2) between a launch's ``ready`` and the first ``_emit`` the loop thread
    makes no device call;
(3) the counters that say it engaged;
(4) the legacy dense admission samples through the same program;
(5) a finishing row cancelled, or past its deadline, between plan and
    retire emits nothing and leaks no page."""

import asyncio
import json
import pathlib
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.llm import faults
from clearml_serving_tpu.llm.engine import (
    DeadlineExceededError, GenRequest, LLMEngineCore,
)
from clearml_serving_tpu.llm.guided import GuidedSpec
from clearml_serving_tpu.llm.tokenizer import ByteTokenizer

GOLDEN = pathlib.Path(__file__).parent / "data" / "first_token_pr48.json"
TOK = ByteTokenizer(512)
STATE_CFG = dict(vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                 head_dim=16, ffn_dim=96, scan_layers=True, dtype="float32",
                 attention="power_retention", retention_degree=2, qk_norm=True,
                 norm_eps=1e-6, rope_theta=1e6, max_seq_len=512)


def _ids(seed, n):
    return [TOK.bos_token_id] + [(seed * 31 + i * 7) % 90 + 33 for i in range(n)]


BIAS = {65 + i: 2.5 - 0.1 * i for i in range(20)}
PENALTIES = dict(presence_penalty=0.5, frequency_penalty=0.3,
                 repetition_penalty=1.2)

# alone on a fresh engine, one after the other: the shared rng stream's order
# (a launch's key, its finishing rows' keys, the next launch's) decides what
# an UNSEEDED sampling row draws
ALONE = [
    ("unseeded_bias", _ids(1, 21), 6,
     dict(temperature=0.8, top_p=0.9, logit_bias=BIAS, logprobs=2)),
    ("unseeded_plain", _ids(2, 9), 6, dict(temperature=0.7, logprobs=1)),
]
# together, each submitted when the one before it has been admitted: greedy and
# seeded rows, whose tokens do not depend on which launch carried them
MIXED = [
    ("greedy_long", _ids(3, 40), 8, {}),
    ("greedy_logprobs", _ids(4, 12), 8, dict(logprobs=3)),
    ("seeded_topk", _ids(5, 18), 8,
     dict(temperature=0.9, top_k=20, seed=11, logprobs=2)),
    ("bias_min_penalties", _ids(6, 15), 8,
     dict(logit_bias=BIAS, min_tokens=4, logprobs=2, **PENALTIES)),
    ("guided_seeded", TOK.encode("Q:"), 12,
     dict(temperature=0.9, seed=5, logprobs=1,
          guided=GuidedSpec("regex", "(yes|no|maybe)"))),
    ("guided_greedy_min", TOK.encode("R:"), 12,
     dict(min_tokens=2, guided=GuidedSpec("regex", "(alpha|beta|gamma)"))),
]
# two short prompts queued behind a prompt of several budgets end their
# prefill in ONE launch
BLOCKER = ("blocker", _ids(7, 70), 3, {})
PAIR = [
    ("pair_seeded_bias", _ids(8, 3), 6,
     dict(temperature=1.0, top_p=0.8, seed=23, logit_bias=BIAS, logprobs=2)),
    ("pair_greedy", _ids(9, 4), 6, dict(logprobs=0)),
]


@pytest.fixture(scope="module")
def paged_parts():
    bundle = models.build_model("llama", {"preset": "llama-tiny", "dtype": "float32"})
    return bundle, bundle.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def state_parts():
    bundle = models.build_model("llama", STATE_CFG)
    return bundle, bundle.init(jax.random.PRNGKey(3))


def _engine(kind, parts, **kw):
    args = dict(max_batch=4, max_seq_len=128, eos_token_id=TOK.eos_token_id,
                tokenizer=TOK, decode_steps=4, step_token_budget=16)
    if kind == "paged":
        args.update(cache_mode="paged", page_size=8, num_pages=96,
                    ragged_decode_steps=4, pipeline_depth=1)
    elif kind == "state":
        # depth 1, like the pages: the record's floats follow the prompts'
        # chunk cuts, and a launch in flight may cut a prompt elsewhere
        # (the ids it serves are the same: tests/test_launch_in_flight.py)
        args.update(cache_mode="state", pipeline_depth=1)
    else:
        args.pop("step_token_budget")
        args.update(prefill_buckets=[16, 32, 64, 128])
    args.update(kw)
    return LLMEngineCore(*parts, **args)


def _hex(entry):
    return None if entry is None else {
        "id": entry["id"], "logprob": float(entry["logprob"]).hex(),
        "top_ids": list(entry["top_ids"]),
        "top_logprobs": [float(x).hex() for x in entry["top_logprobs"]],
    }


async def _one(engine, spec, after=None, mine=None, opened=False):
    """``after``: the request before this one, once submitted (a future):
    this one is submitted when that one has been ADMITTED, i.e. its prompt
    is in and its first token out (``opened``: as soon as its job is open
    and its prompt rides the launches), so the prompts' chunk cuts do not
    depend on how long a launch takes on this host (ROADMAP D10 (a))."""
    name, ids, n, kw = spec
    if after is not None:
        before = await after
        while before.error is None and not (
                before._job_at if opened else before.first_token_at):
            await asyncio.sleep(0.001)
    req = GenRequest(prompt_ids=list(ids), max_new_tokens=n, **kw)
    if mine is not None:
        mine.set_result(req)
    try:
        toks = [t async for t in engine.generate(req)]
        error = None
    except Exception as ex:  # the ending is part of the record
        toks, error = [], type(ex).__name__
    wanted = req.logprobs is not None
    return name, {
        "ids": toks,
        "logprobs": [_hex(e) for e in req.logprob_entries] if wanted else None,
        "ending": error or ("length" if len(toks) == n else "stop"),
    }


def _serve(engine):
    """The whole traffic on one engine, in its three situations."""

    async def run():
        out = {}
        for spec in ALONE:
            out.update([await _one(engine, spec)])
        loop = asyncio.get_running_loop()
        sent = [None] + [loop.create_future() for _ in MIXED]
        out.update(await asyncio.gather(*(
            _one(engine, spec, after=sent[i], mine=sent[i + 1])
            for i, spec in enumerate(MIXED))))
        await engine.wait_drained()
        blocker = loop.create_future()
        got = await asyncio.gather(
            _one(engine, BLOCKER, mine=blocker),
            *(_one(engine, spec, after=blocker, opened=True) for spec in PAIR))
        out.update(got)
        await engine.wait_drained()
        return out

    return asyncio.run(run())


class FinishRows:
    """How many prompts each ragged launch finished."""

    def __init__(self, engine):
        self.counts = []
        dispatch = engine._dispatch_ragged_device

        def spy(plan):
            result = dispatch(plan)
            self.counts.append(len(result["finish_rows"]))
            return result

        engine._dispatch_ragged_device = spy


# -- (1) the served streams are the parent's ----------------------------------


@pytest.mark.parametrize("kind", ["paged", "state"])
def test_the_streams_are_the_recorded_ones(kind, paged_parts, state_parts):
    engine = _engine(kind, paged_parts if kind == "paged" else state_parts)
    rows = FinishRows(engine)
    try:
        got = _serve(engine)
        ragged = engine.lifecycle_stats()["ragged"]
    finally:
        engine.stop()
    want = json.loads(GOLDEN.read_text())[kind]
    assert set(got) == set(want)
    for name in want:
        assert got[name] == want[name], name
    # the guided rows ended inside their grammars
    assert TOK.decode(got["guided_seeded"]["ids"]) in ("yes", "no", "maybe")
    assert TOK.decode(got["guided_greedy_min"]["ids"]) in ("alpha", "beta", "gamma")
    # the pair did end its prefill in one launch (with the blocker's last
    # chunk, where that left them room)
    assert max(rows.counts) >= 2, rows.counts
    # (3) every first token came back with its launch
    assert ragged["first_tokens"] == len(want)
    assert ragged["first_tokens_behind_launch"] == ragged["first_tokens"]


# -- (2) the loop thread's commit makes no device call --------------------------


class DeviceCalls:
    """Every ``jnp.asarray`` / ``jax.device_put`` of the engine's module,
    every call of a jitted member and every key taken from the shared
    stream, with the thread that made it and whether it fell between a
    launch's ``ready`` (``_read_back`` returned) and the END of that
    launch's retire: every ``_emit`` of the launch, the decode rows' (which
    come first) and the finishing prompts' first tokens, lies inside."""

    def __init__(self, engine, monkeypatch):
        from clearml_serving_tpu.llm import engine as engine_mod

        self.loop_thread = None
        self.window = False            # ready .. the retire's end, loop thread
        self.in_window, self.commits, self.by_name = [], 0, {}

        def spy(name, fn):
            def call(*args, **kw):
                here = threading.current_thread() is self.loop_thread
                self.by_name.setdefault(name, set()).add(here)
                if self.window and here:
                    self.in_window.append(name)
                return fn(*args, **kw)
            return call

        monkeypatch.setattr(engine_mod.jnp, "asarray", spy("asarray", jnp.asarray))
        monkeypatch.setattr(engine_mod.jax, "device_put",
                            spy("device_put", jax.device_put))
        for name in type(engine).__compile_keys__["serve"]:
            if getattr(engine, name, None) is not None:
                setattr(engine, name, spy(name, getattr(engine, name)))
        engine._next_rng = spy("_next_rng", engine._next_rng)
        read_back, retire = engine._read_back, engine._retire_ragged

        async def reading(seq, first, rest):
            out = await read_back(seq, first, rest)
            self.loop_thread = threading.current_thread()
            # a ragged step's read (a decode chunk's retire reads through
            # the same worker)
            self.window = isinstance(rest, dict) and "first" in rest
            return out

        def retiring(plan, result):
            # what the retire is handed lives on the host
            assert not [leaf for leaf in jax.tree.leaves(result)
                        if isinstance(leaf, jax.Array)]
            finishing = [j for j, _ in plan["shares"]
                         if j.slot in result["finish_rows"]]
            self.commits += len(finishing)
            try:
                return retire(plan, result)
            finally:
                self.window = False

        engine._read_back, engine._retire_ragged = reading, retiring


@pytest.mark.parametrize("kind", ["paged", "state"])
def test_the_commit_on_the_loop_thread_calls_no_device(
        kind, paged_parts, state_parts, monkeypatch):
    engine = _engine(kind, paged_parts if kind == "paged" else state_parts)
    calls = DeviceCalls(engine, monkeypatch)
    try:
        got = _serve(engine)
        ragged = engine.lifecycle_stats()["ragged"]
    finally:
        engine.stop()
    assert got == json.loads(GOLDEN.read_text())[kind]    # the spies are inert
    assert calls.commits == ragged["first_tokens"] == len(got)
    assert calls.in_window == []
    # the program ran, and in the dispatch worker alone; the launch's own
    # row reset (a legacy commit's) never did
    assert calls.by_name["_first_token_jit"] == {False}
    assert "_set_sampling_row_jit" not in calls.by_name
    # (3) the counters that say so
    assert ragged["first_tokens_behind_launch"] == ragged["first_tokens"] > 0


# -- (4) one program for both admission paths -----------------------------------


FIRST = [
    ("greedy", _ids(11, 10), dict(logprobs=3)),
    ("seeded_bias", _ids(12, 14),
     dict(temperature=0.9, top_p=0.9, seed=3, logit_bias=BIAS, logprobs=2,
          **PENALTIES)),
    ("guided", TOK.encode("Q:"),
     dict(temperature=0.9, seed=9, logprobs=1, min_tokens=1,
          guided=GuidedSpec("regex", "(yes|no|maybe)"))),
]


def _first_entries(engine):
    async def run():
        out = {}
        for name, ids, kw in FIRST:
            req = GenRequest(prompt_ids=list(ids), max_new_tokens=3, **kw)
            toks = [t async for t in engine.generate(req)]
            out[name] = (toks[0], req.logprob_entries[0])
        await engine.wait_drained()
        return out

    try:
        return asyncio.run(run())
    finally:
        engine.stop()


def test_the_legacy_admission_samples_through_the_same_program(paged_parts):
    """The dense engine's admission worker and the ragged retire hand out
    the same first token and logprob entry (the prefill logits come from
    two model passes: ids and top ids exact, log-probabilities to float32
    rounding), and the legacy worker's one row went through
    ``_first_token_jit`` (its eager second ``penalize_logits`` is gone)."""
    dense = _engine("dense", paged_parts)
    seen = []
    program = dense._first_token_jit

    def spy(logits, staged, layout, key, state, keyed):
        seen.append((tuple(logits.shape), state, keyed))
        return program(logits, staged, layout, key, state, keyed)

    dense._first_token_jit = spy
    legacy = _first_entries(dense)
    ragged = _first_entries(_engine("paged", paged_parts))
    # its one row, no slot state, and the trace that fits the request's
    # extras (the greedy request has none)
    assert seen == [((1, 512), None, keyed) for keyed in (False, True, True)]
    for name in legacy:
        (tok_a, lp_a), (tok_b, lp_b) = legacy[name], ragged[name]
        assert tok_a == tok_b and lp_a["id"] == lp_b["id"] == tok_a, name
        assert lp_a["top_ids"] == lp_b["top_ids"], name
        np.testing.assert_allclose(lp_a["logprob"], lp_b["logprob"], atol=1e-5)
        np.testing.assert_allclose(
            lp_a["top_logprobs"], lp_b["top_logprobs"], atol=1e-5)


def test_the_program_picks_its_row_and_resets_its_slot(paged_parts):
    """Byte-identical by construction: over a launch's [R, V] logits the
    program takes its slot's row on the device and gives the id and the
    logprob triple that the legacy form gives (that row alone, no state)
    under the same key; the slots' device rows take the sampled id, the
    bias row and the prompt mask of the slot the call names, and of no
    other."""
    engine = _engine("paged", paged_parts)
    reqs = [GenRequest(prompt_ids=list(ids), max_new_tokens=2, **kw)
            for _, ids, kw in FIRST]
    try:
        rng = np.random.default_rng(49)
        logits = jnp.asarray(rng.normal(size=(4, 512)).astype(np.float32) * 3)
        for req in reqs:
            engine.validate(req)
        ops = [engine._first_token_ops(req) for req in reqs]
        slots = [2, 0, 3]
        engine._ensure_extras_state()
        state = tuple(jnp.ones_like(a) for a in (
            engine._counts_dev, engine._bias_dev, engine._pmask_dev))
        firsts, dense = [], []
        for op, slot in zip(ops, slots):
            (ids, lp, state), rows = engine._sample_first_token(
                op, slot, logits, state)
            (one, one_lp, none), _ = engine._sample_first_token(
                op, 0, logits[slot : slot + 1], None)
            assert none is None and int(one[0]) == int(ids[0])
            for a, b in zip(one_lp, lp):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            firsts.append(int(ids[0]))
            dense.append(rows)
        counts, bias, pmask = map(np.asarray, state)
        for first, slot, (bias_row, pmask_row) in zip(firsts, slots, dense):
            want = np.zeros(512, np.int32)
            want[first] = 1
            np.testing.assert_array_equal(counts[slot], want)
            np.testing.assert_array_equal(bias[slot], bias_row)
            np.testing.assert_array_equal(pmask[slot], pmask_row)
        # slot 1 was named by no call
        assert counts[1].all() and bias[1].all() and pmask[1].all()
        # the guided row was held to its grammar's first bytes
        assert TOK.decode([firsts[2]]) in ("y", "n", "m")
    finally:
        for req in reqs:
            engine._deref_guided_request(req)
        engine.stop()


def test_the_bias_and_prompt_rows_are_the_loops(paged_parts):
    """``_bias_pmask_rows`` is built with numpy where a Python loop stood:
    the loop is the reference, ids outside the vocabulary and JSON's string
    keys included, bit for bit."""
    engine = _engine("paged", paged_parts)
    try:
        req = GenRequest(
            prompt_ids=[256, 5, 511, 512, -3, 5, 70000, 0], max_new_tokens=2,
            logit_bias={"65": 2.5, 66: -100, 511: 0.1, 512: 9.0, -1: 4.0,
                        "70000": 1.0, 0: 1e-3})
        bias, pmask = engine._bias_pmask_rows(req)
        want_bias = np.zeros(512, np.float32)
        for tok, value in req.logit_bias.items():
            if 0 <= int(tok) < 512:
                want_bias[int(tok)] = float(value)
        want_mask = np.zeros(512, bool)
        want_mask[[t for t in req.prompt_ids if 0 <= t < 512]] = True
        assert bias.dtype == np.float32 and pmask.dtype == bool
        np.testing.assert_array_equal(bias, want_bias)
        np.testing.assert_array_equal(pmask, want_mask)
        none = engine._bias_pmask_rows(GenRequest(prompt_ids=[], max_new_tokens=1))
        assert not none[0].any() and not none[1].any()
    finally:
        engine.stop()


# -- (5) a finishing row that is dropped between plan and retire ---------------


MARK = 301     # a token only the dropped request's prompt holds


@pytest.fixture
def chaos(monkeypatch):
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    faults.clear()
    yield
    faults.clear()


def _drop(kind, parts, how):
    """One prompt whose only launch is held in the dispatch worker (the
    ``engine.decode`` seam's delay) while ``how`` ends the request; then a
    plain request takes the slot. Returns what both streams gave."""
    engine = _engine(kind, parts, ttft_timeout=None)
    planned = threading.Event()
    dispatch = engine._dispatch_ragged_device

    def worker(plan):
        if any(MARK in r.prompt_ids for r in plan["requests"]):
            assert plan["finish_slots"]          # its one launch ends its prompt
            planned.set()
        return dispatch(plan)

    engine._dispatch_ragged_device = worker
    victim = GenRequest(
        prompt_ids=_ids(13, 6) + [MARK], max_new_tokens=5,
        logit_bias=BIAS, logprobs=1, temperature=0.8, **how.get("request", {}))
    after = GenRequest(prompt_ids=_ids(14, 9), max_new_tokens=5, logprobs=1)

    async def run():
        if "configure" in how:
            faults.configure(how["configure"])
        else:
            faults.configure([{"point": "engine.decode", "action": "delay",
                               "delay": 0.6, "match_token": MARK, "times": 1}])
        got = []

        async def consume():
            async for t in engine.generate(victim):
                got.append(t)

        task = asyncio.ensure_future(consume())
        while not planned.is_set():
            await asyncio.sleep(0.005)
        if how.get("cancel"):
            victim.cancel()
        try:
            await asyncio.wait_for(task, 30)
            error = None
        except Exception as ex:
            error = ex
        await engine.wait_drained()
        faults.clear()
        rest = [t async for t in engine.generate(after)]
        await engine.wait_drained()
        return got, error, rest

    try:
        got, error, rest = asyncio.run(run())
        stats = engine.lifecycle_stats()
        if kind == "paged":
            pool = engine.paged_cache.pool
            assert pool.free_pages == pool.num_pages - 1
            assert engine._sanitizer.stats()["failures"] == 0
        else:
            assert stats["state_pool"]["in_use"] == 0
        return got, error, rest, after, stats
    finally:
        engine.stop()


DROPS = {
    "cancelled": dict(cancel=True),
    "past-its-deadline": dict(request=dict(total_timeout=0.3)),
    "failed-at-retire": dict(configure=[
        {"point": "engine.decode.retire", "match_token": MARK, "times": 1,
         "message": "retire blew up"}]),
}


@pytest.mark.parametrize("how", list(DROPS))
@pytest.mark.parametrize("kind", ["paged", "state"])
def test_a_dropped_finishing_row_emits_nothing_and_leaks_nothing(
        kind, how, paged_parts, state_parts, chaos):
    parts = paged_parts if kind == "paged" else state_parts
    got, error, rest, after, stats = _drop(kind, parts, DROPS[how])
    assert got == [], "the dropped row's first token reached its stream"
    if how == "past-its-deadline":
        assert isinstance(error, DeadlineExceededError)
    elif how == "failed-at-retire":
        assert type(error).__name__ == "EngineStepError"
    # its sampled id was dropped with it: one commit, the later request's
    assert stats["ragged"]["first_tokens"] == 1
    assert stats["ragged"]["first_tokens_behind_launch"] == 1
    # the slot's device rows were reset again at its next admission: the
    # later request reads what it reads on an engine that never saw the bias
    fresh = _engine(kind, parts)
    try:
        async def alone():
            req = GenRequest(prompt_ids=list(after.prompt_ids),
                             max_new_tokens=5, logprobs=1)
            return [t async for t in fresh.generate(req)], req.logprob_entries

        want, want_lp = asyncio.run(alone())
    finally:
        fresh.stop()
    assert rest == want and after.logprob_entries == want_lp


if __name__ == "__main__":
    if sys.argv[1:] != ["--record"]:
        sys.exit("usage: test_first_token_commit.py --record")
    record = {}
    for kind, cfg, key in (("paged", {"preset": "llama-tiny", "dtype": "float32"}, 0),
                           ("state", STATE_CFG, 3)):
        bundle = models.build_model("llama", cfg)
        parts = bundle, bundle.init(jax.random.PRNGKey(key))
        runs = []
        for _ in range(2):
            engine = _engine(kind, parts)
            runs.append(_serve(engine))
            engine.stop()
        assert runs[0] == runs[1], "the traffic is not reproducible on this tree"
        record[kind] = runs[0]
    GOLDEN.write_text(json.dumps(record, indent=1, sort_keys=True) + "\n")
    print("recorded", {k: {n: len(v["ids"]) for n, v in r.items()}
                       for k, r in record.items()})
