"""The closed loop against a stub server that streams each answer in a fixed
time: at 1.1 x the mix's ``floor_request_s`` a request the generator ends with
``deepest_request`` beyond block 0 and no error; faster than the floor, a
caller ends its list and the error names it, its count and the floor."""

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

import pytest
from aiohttp import web

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import loadgen, reduce as rd, traffic  # noqa: E402

MIX = {
    "loop": "closed", "clients": 2, "ramp_s": 0.5, "drain_s": 0,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.3, "min": 20, "max": 80},
    "answer_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.3, "min": 3, "max": 12},
    "stagger_first": "answer", "nominal_request_s": 1.0, "floor_request_s": 0.25,
    "front_probe_interval_s": 0.5,
}
WINDOW_S = 2.0


def stub_app(request_s: float) -> web.Application:
    """Answers ``max_tokens`` tokens, one chunk each, over ``request_s``."""
    async def chat(request):
        body = await request.json()
        n = body["max_tokens"]
        resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        for _ in range(n):
            await asyncio.sleep(request_s / n)
            chunk = {"choices": [{"delta": {"content": "a"}, "logprobs": {
                "content": [{"token": "a", "logprob": -0.5}]}}]}
            await resp.write(b"data: " + json.dumps(chunk).encode() + b"\n\n")
        usage = {"choices": [], "usage": {"prompt_tokens": 1, "completion_tokens": n}}
        await resp.write(b"data: " + json.dumps(usage).encode() + b"\n\n")
        await resp.write(b"data: [DONE]\n\n")
        return resp

    async def tokenize(request):
        return web.json_response({"tokens": []})

    app = web.Application()
    app.router.add_post("/serve/openai/v1/chat/completions", chat)
    app.router.add_post("/serve/openai/v1/tokenize", tokenize)
    return app


async def offer(tmp_path, request_s: float, plan: dict = None) -> dict:
    """The generator's summary after ``plan`` (default: MIX's) was offered to
    the stub."""
    if plan is None:
        (tmp_path / "tiny_floor.json").write_text(json.dumps(MIX))
        plan = traffic.make_plan("tiny_floor", "none", 2 ** 31 + 5, WINDOW_S, tmp_path)
    (tmp_path / "plan.json").write_text(json.dumps(plan))
    runner = web.AppRunner(stub_app(request_s))
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    try:
        summary = await loadgen.main_async(argparse.Namespace(
            plan=str(tmp_path / "plan.json"), records=str(tmp_path / "records.jsonl"),
            base="http://127.0.0.1:{}".format(port), model="stub",
            t0=time.monotonic() + 0.2))
    finally:
        await runner.cleanup()
    return dict(summary, plan=plan)


def test_at_the_floor_the_callers_go_beyond_block_0_and_do_not_run_out(tmp_path):
    got = asyncio.run(offer(tmp_path, 1.1 * MIX["floor_request_s"]))
    plan = got["plan"]
    assert (plan["block0_per_client"], plan["per_client"]) == (5, 15)
    # 2.5 s of traffic at 0.275 s a request: block 0's five are long sent
    assert plan["block0_per_client"] <= got["deepest_request"] < plan["per_client"] - 1
    records = rd.load_records(tmp_path / "records.jsonl")
    assert got["records"] == len(records) >= 2 * 7
    judged = [r for r in records if r["judged"]]
    assert judged and all(rd.request_ok(r) for r in judged)
    beyond = [r for r in judged if int(r["id"].split("r")[1]) >= plan["block0_per_client"]]
    assert beyond and all(r["n_out"] == r["want"] for r in beyond)


def test_faster_than_the_floor_a_caller_runs_out_and_is_named(tmp_path):
    with pytest.raises(RuntimeError) as err:
        asyncio.run(offer(tmp_path, 0.3 * MIX["floor_request_s"]))
    said = str(err.value)
    assert "ran out of requests before the window closed" in said
    assert "client 0 " in said or "client 1 " in said
    assert "all 15 of its list" in said and "block 0 held 5" in said
    assert "floor_request_s = 0.25" in said


def test_an_open_loop_has_no_blocks_and_no_deepest_request(tmp_path):
    tiny = ROOT / "tests" / "benchmark" / "tiny" / "traffic"
    plan = traffic.make_plan("tiny_sessions", "tiny-dense", 3, 2.0, tiny)
    assert "per_client" not in plan and "block0_per_client" not in plan
    got = asyncio.run(offer(tmp_path, 0.05, plan))
    assert got["deepest_request"] is None and got["records"] > 0
