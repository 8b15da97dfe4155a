"""The traffic generator: a plan is a pure function of (mix, config, seed,
seconds); the judged population is the same ids in every run of a seed; every
seed offers the same multiset of sizes and gaps; a closed loop's plan is the
plan it was before it had blocks, and then outlasts the chip's roofline."""

import hashlib
import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import traffic  # noqa: E402

TINY = Path(__file__).resolve().parent / "tiny" / "traffic"
CELLS = [
    ("chat_sessions", "mistral-7b-v0.3", None),
    ("chat_sessions", "mixtral-8x7b-d6", None),
    ("offline_decode", "mistral-7b-v0.3", None),
    ("offline_prefill", "mixtral-8x7b-d6", None),
    ("offline_long_decode", "brumby-14b-d12", None),
    ("tiny_sessions", "tiny-dense", TINY),
    ("tiny_closed", "tiny-moe", TINY),
]
BIG_SEED = 2 ** 31 + 12345
STOCK_CLOSED = [c for c in CELLS if c[0].startswith("offline_")]
# sha256 of json.dumps(plan["clients"], sort_keys=True) as make_plan(mix, config,
# seed, 51) gave it on commit 6e992a5, before a plan had blocks: seeds 1, BIG_SEED
PARENT_PLANS = {
    "offline_decode": (
        10, "0d51c1ae62772fbdd13ad7807b1f824ca177a97347bc679e30180493d14ebc38",
        "7e6a77ecf7dfab103196f2e33c65ecadd0a0329d0d63d087aa5d32d8ded4a1b5"),
    "offline_prefill": (
        13, "e2f8a45f72a7cb52adb003661cb8f4995d5a200f07e48d5a7097e2933f514828",
        "ec36dc204cc89f3b6ecdc2c59f46de96e39b75845cc28f4de41721081f38cfb1"),
    "offline_long_decode": (
        8, "11f6cc6bbe619f0ebb17125cb8e31879baba221c567821b179df893d8a8d65aa",
        "8bb55448a98d5869df36002bb3560dc9879145d227a766efc37a4da9d197acdc"),
}


def _requests(plan):
    if plan["loop"] == "open":
        return plan["requests"]
    return [r for c in plan["clients"] for r in c]


@pytest.mark.parametrize("mix,config,directory", CELLS)
def test_plan_is_a_pure_function_of_the_seed(mix, config, directory):
    a = traffic.make_plan(mix, config, BIG_SEED, 20, directory)
    b = traffic.make_plan(mix, config, BIG_SEED, 20, directory)
    c = traffic.make_plan(mix, config, BIG_SEED + 1, 20, directory)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert json.dumps(a, sort_keys=True) != json.dumps(c, sort_keys=True)


@pytest.mark.parametrize("mix,config,directory", CELLS)
def test_every_seed_offers_the_same_sizes(mix, config, directory):
    a = traffic.make_plan(mix, config, 1, 20, directory)
    b = traffic.make_plan(mix, config, BIG_SEED, 20, directory)
    if a["loop"] == "closed":
        # the whole list is one multiset; the first requests are cut short
        la = sorted(r["max_tokens"] for c in a["clients"] for r in c[1:])
        lb = sorted(r["max_tokens"] for c in b["clients"] for r in c[1:])
        assert len(_requests(a)) == len(_requests(b))
        assert abs(sum(la) - sum(lb)) <= 0.15 * sum(la)
    else:
        # the schedule is periodic with the window: every seed's judged
        # population is the same count and the same sizes, in another order
        ja = [r for r in a["requests"] if r["judged"]]
        jb = [r for r in b["requests"] if r["judged"]]
        assert len(ja) == len(jb) > 0
        assert sorted(r["max_tokens"] for r in ja) == sorted(r["max_tokens"] for r in jb)
        assert [r["max_tokens"] for r in ja] != [r["max_tokens"] for r in jb]
        turn = lambda r: int(r["id"].rsplit("t", 1)[1])  # noqa: E731
        assert sorted(map(turn, ja)) == sorted(map(turn, jb))


@pytest.mark.parametrize("mix,config,directory",
                         [c for c in CELLS if "sessions" in c[0]])
def test_open_loop_population_and_chains(mix, config, directory):
    plan = traffic.make_plan(mix, config, 7, 20, directory)
    again = traffic.make_plan(mix, config, 7, 20, directory)
    judged = [r["id"] for r in plan["requests"] if r["judged"]]
    assert judged and judged == [r["id"] for r in again["requests"] if r["judged"]]
    ramp, end = plan["ramp_s"], plan["ramp_s"] + plan["window_s"]
    margin = traffic.load_mix(mix, directory).get("margin_s", 0.0)
    by_id = {r["id"]: r for r in plan["requests"]}
    assert any(r["due"] >= end for r in plan["requests"])   # through the drain
    for r in plan["requests"]:
        assert 0 <= r["due"] < end + plan["drain_s"]
        assert r["judged"] == (ramp <= r["due"] < end - margin)
        if r["after"]:
            # a later turn is due after its predecessor and carries only
            # its own user message
            assert by_id[r["after"]]["due"] < r["due"]
            assert [m["role"] for m in r["messages"]] == ["user"]
        else:
            assert r["messages"][0]["role"] == "system"
            assert r["messages"][-1]["role"] == "user"
    # sessions in progress at t=0 bring a history of whole turns
    if mix == "chat_sessions":
        assert any(len(r["messages"]) > 2 for r in plan["requests"] if not r["after"])


@pytest.mark.parametrize("mix,config,directory",
                         [c for c in CELLS if "sessions" not in c[0]])
def test_closed_loop_clients(mix, config, directory):
    spec = traffic.load_mix(mix, directory)
    plan = traffic.make_plan(mix, config, 3, 20, directory)
    assert len(plan["clients"]) == spec["clients"]
    ids = [r["id"] for r in _requests(plan)]
    assert len(ids) == len(set(ids))
    lo, hi = spec["answer_tokens"].get("min"), spec["answer_tokens"].get("max")
    for client in plan["clients"]:
        assert len(client) >= 3
        for r in client[1:]:
            if lo is not None:
                assert lo <= r["max_tokens"] <= hi
    firsts = [c[0] for c in plan["clients"]]
    key = "max_tokens" if spec["stagger_first"] == "answer" else None
    if key:
        assert len({r[key] for r in firsts}) > 1


def _digest(clients):
    return hashlib.sha256(json.dumps(clients, sort_keys=True).encode()).hexdigest()


def _without_floor(mix, tmp_path):
    """A copy of the stock mix as it was: no ``floor_request_s``."""
    spec = traffic.load_mix(mix)
    del spec["floor_request_s"]
    (tmp_path / (mix + ".json")).write_text(json.dumps(spec))
    return tmp_path


@pytest.mark.parametrize("which,seed", [(1, 1), (2, BIG_SEED)])
@pytest.mark.parametrize("mix,config,_", STOCK_CLOSED)
def test_block_0_is_the_parents_plan_byte_for_byte(mix, config, _, which, seed):
    plan = traffic.make_plan(mix, config, seed, 51)
    count = PARENT_PLANS[mix][0]
    assert plan["block0_per_client"] == count < plan["per_client"]
    assert _digest([c[:count] for c in plan["clients"]]) == PARENT_PLANS[mix][which]


@pytest.mark.parametrize("mix,config,_", STOCK_CLOSED)
def test_a_plan_with_blocks_extends_the_plan_without(mix, config, _, tmp_path):
    bare = traffic.make_plan(mix, config, BIG_SEED, 51, _without_floor(mix, tmp_path))
    plan = traffic.make_plan(mix, config, BIG_SEED, 51)
    count = bare["per_client"]
    # a mix without the key plans as before: block 0 alone
    assert count == bare["block0_per_client"] == PARENT_PLANS[mix][0]
    assert bare["floor_request_s"] is None
    assert _digest(bare["clients"]) == PARENT_PLANS[mix][2]
    assert [c[:count] for c in plan["clients"]] == bare["clients"]
    assert all(len(c) == plan["per_client"] for c in plan["clients"])
    assert plan["per_client"] % count == 0            # whole blocks
    for key in ("ramp_s", "window_s", "drain_s", "probe_interval_s", "seed"):
        assert plan[key] == bare[key]


@pytest.mark.parametrize("mix,config,_", STOCK_CLOSED)
def test_the_plan_outlasts_the_floor(mix, config, _):
    """At ``floor_request_s`` a request, the least the chip's peaks allow, the
    caller with the shortest list is still sending when the window closes."""
    spec = traffic.load_mix(mix)
    plan = traffic.make_plan(mix, config, 3, 51)
    horizon = plan["ramp_s"] + plan["window_s"]
    assert plan["floor_request_s"] == spec["floor_request_s"] < spec["nominal_request_s"]
    assert len(spec["floor_why"]) > 100 and "peaks.json" in spec["floor_why"]
    shortest = min(len(c) for c in plan["clients"])
    assert shortest >= math.ceil(horizon / spec["floor_request_s"]) + 2
    assert (shortest - 1) * spec["floor_request_s"] >= horizon
    times = {"offline_decode": 3, "offline_prefill": 4, "offline_long_decode": 3}
    assert shortest >= times[mix] * plan["block0_per_client"]
    ids = [r["id"] for r in _requests(plan)]
    assert len(ids) == len(set(ids))
    for c, client in enumerate(plan["clients"]):      # the ids go on counting
        assert [r["id"] for r in client] == [
            "c{}r{}".format(c, j) for j in range(len(client))]


@pytest.mark.parametrize("mix,config,_", STOCK_CLOSED)
def test_every_block_offers_the_same_lengths_under_every_seed(mix, config, _):
    """The multiset rule, block by block: whatever block a fast program
    reaches, it finds there the work every other seed has there, in another
    order; only block 0 cuts its first round short (by fractions that are
    themselves the same set under every seed)."""
    def blocks(seed):
        plan = traffic.make_plan(mix, config, seed, 51)
        size = plan["block0_per_client"]
        out = []
        for b in range(plan["per_client"] // size):
            rows = [c[b * size + (1 if b == 0 else 0):(b + 1) * size]
                    for c in plan["clients"]]
            out.append((
                sorted(traffic.prompt_tokens(r["messages"]) for c in rows for r in c),
                sorted(r["max_tokens"] for c in rows for r in c),
                [(len(r["messages"][0]["content"]), r["max_tokens"])
                 for c in rows for r in c]))
        return out
    a, b, c = blocks(1), blocks(BIG_SEED), blocks(77)
    assert len(a) == len(b) == len(c) >= 3
    stagger = traffic.load_mix(mix)["stagger_first"]
    for k, (x, y, z) in enumerate(zip(a, b, c)):
        if k or stagger != "prompt":
            assert x[0] == y[0] == z[0]               # prompts
        if k or stagger != "answer":
            assert x[1] == y[1] == z[1]               # answers
        assert x[2] != y[2] != z[2]                   # in another order
    # a later block is a block like the first, not a copy of it
    assert a[1][2] != a[2][2]
    assert a[1][1] == a[2][1] and a[1][0] == a[2][0]


@pytest.mark.parametrize("spec,n,expect", [
    ({"dist": "fixed", "value": 16}, 3, [16, 16, 16]),
    ({"dist": "uniform", "min": 0.0, "max": 1.0}, 4, [0.125, 0.375, 0.625, 0.875]),
    ({"dist": "choice", "values": [1, 2, 3]}, 5, [1, 2, 3, 1, 2]),
    ({"dist": "zipf", "values": [0, 1], "s": 1.0}, 6, [0, 0, 0, 0, 1, 1]),
])
def test_strata(spec, n, expect):
    assert traffic.strata(spec, n) == pytest.approx(expect)


def test_lognormal_strata_are_clipped_and_centred():
    vals = traffic.strata(
        {"dist": "lognormal", "median": 64, "sigma": 0.45, "min": 32, "max": 192}, 101)
    assert min(vals) >= 32 and max(vals) <= 192
    assert vals[50] == 64 and vals == sorted(vals)


def test_prompt_tokens_counts_the_byte_template():
    messages = [{"role": "user", "content": "abc"}]
    # BOS + "<|user|>\nabc\n" + "<|assistant|>\n"
    assert traffic.prompt_tokens(messages) == 1 + len("<|user|>\nabc\n<|assistant|>\n")


def test_unknown_config_has_no_rate():
    with pytest.raises(KeyError):
        traffic.make_plan("chat_sessions", "no-such-config", 1, 10)


def test_dealt_blocks_hold_a_like_spread():
    import random

    vals = traffic.dealt(list(range(100)), 10, random.Random(1))
    sums = [sum(vals[i:i + 10]) for i in range(0, 100, 10)]
    assert sorted(vals) == list(range(100))
    assert max(sums) - min(sums) <= 100      # of a mean of 495
    assert vals != traffic.dealt(list(range(100)), 10, random.Random(2))
