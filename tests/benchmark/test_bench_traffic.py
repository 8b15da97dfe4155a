"""The traffic generator: a plan is a pure function of (mix, config, seed,
seconds); the judged population is the same ids in every run of a seed; every
seed offers the same multiset of sizes and gaps."""

import json
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
    ("tiny_sessions", "tiny-dense", TINY),
    ("tiny_closed", "tiny-moe", TINY),
]
BIG_SEED = 2 ** 31 + 12345


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
