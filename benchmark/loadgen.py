"""The load generator: a child process that never imports jax.

    python3 benchmark/loadgen.py --plan plan.json --records out.jsonl \
        --base http://127.0.0.1:PORT --model NAME --t0 <time.monotonic()>

The parent (run.py) holds the chip and serves the app; this process offers the
plan that ``traffic.make_plan`` drew from the seed, over real sockets, from one
thread, and writes one record per request. Times are ``time.monotonic()``,
which is one clock for every process of the machine. ``--t0`` is when traffic
starts; the window is [t0 + ramp_s, t0 + ramp_s + window_s).

Open loop: a request is sent when it is due (a session's later turn when its
predecessor has ended, if that is later) and its latency is timed from when
it was due. The schedule goes on through the drain after the window, so the
last judged requests end under the same load as the first; the run ends when
the last of them has. Closed loop: each client sends its next request when
its last one ends, and latency is timed from the send; the summary's
``deepest_request`` is the highest index of its list that any client reached
(an open loop has none).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import time

import aiohttp

# answers are held to printable ASCII: one token is one byte of streamed text
# (the byte tokenizer decodes ids >= 256 to nothing, and such a token would
# reach the client in no chunk at all)
ASCII_BIAS = {str(i): 100 for i in range(32, 127)}

now = time.monotonic


def request_body(model: str, messages: list, max_tokens: int) -> dict:
    return {
        "model": model, "messages": messages,
        "max_tokens": max_tokens, "min_tokens": max_tokens,
        "temperature": 0, "logit_bias": ASCII_BIAS,
        "logprobs": True, "top_logprobs": 0,
        "stream": True, "stream_options": {"include_usage": True},
    }


async def stream_chat(session, url, body, rec, w0, w1):
    """POST one streaming chat completion and fill ``rec``; returns the text
    received. Every content chunk carries the logprob entries of its tokens,
    which is how tokens are counted."""
    rec["sent"] = now()
    text = []
    async with session.post(url, json=body) as resp:
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = (await resp.text())[:300]
            return ""
        async for raw in resp.content:
            line = raw.strip()
            if not line.startswith(b"data:"):
                continue
            payload = line[5:].strip()
            if payload == b"[DONE]":
                rec["done"] = True
                break
            t = now()
            chunk = json.loads(payload)
            if chunk.get("error"):
                rec["error"] = json.dumps(chunk["error"])[:300]
                break
            if chunk.get("usage"):
                rec["prompt_tokens"] = chunk["usage"]["prompt_tokens"]
                rec["completion_tokens"] = chunk["usage"]["completion_tokens"]
            for choice in chunk.get("choices") or []:
                content = (choice.get("delta") or {}).get("content")
                entries = (choice.get("logprobs") or {}).get("content") or []
                if content:
                    text.append(content)
                n = len(entries)
                if n:
                    if rec["first"] is None:
                        rec["first"] = t
                    rec["last"] = t
                    rec["n_out"] += n
                    if w0 <= t < w1:
                        rec["tok_in_window"] += n
                    for e in entries:
                        lp = e.get("logprob")
                        if not (isinstance(lp, (int, float))
                                and math.isfinite(lp)):
                            rec["lp_finite"] = False
                if choice.get("finish_reason"):
                    rec["finish"] = choice["finish_reason"]
    rec["end"] = now()
    return "".join(text)


def new_record(req: dict, **extra) -> dict:
    rec = {
        "id": req["id"], "want": req["max_tokens"], "judged": False,
        "due": None, "sent": None, "first": None, "last": None, "end": None,
        "n_out": 0, "tok_in_window": 0, "lp_finite": True, "done": False,
        "status": None, "finish": None, "error": None, "late": None,
        "prompt_tokens": None, "completion_tokens": None,
    }
    rec.update(extra)
    return rec


async def run_open(plan, session, url, model, t0, w0, w1, stop_at, records):
    """Every request is a task that sleeps until it is due."""
    done = {}  # id -> future of (messages sent, answer text)
    loop = asyncio.get_running_loop()
    for r in plan["requests"]:
        done[r["id"]] = loop.create_future()

    async def one(r):
        rec = new_record(r, judged=bool(r.get("judged")), due=t0 + r["due"])
        records.append(rec)
        fut = done[r["id"]]
        try:
            await asyncio.sleep(max(0.0, rec["due"] - now()))
            messages = r["messages"]
            intended = rec["due"]
            if r.get("after"):
                prev_messages, answer = await done[r["after"]]
                intended = max(intended, now())
                messages = prev_messages + [
                    {"role": "assistant", "content": answer}
                ] + messages
            if now() >= stop_at:
                rec["error"] = "not sent: its turn came after the run's end"
                fut.set_result((messages, ""))
                return
            rec["late"] = now() - intended
            answer = await stream_chat(
                session, url, request_body(model, messages, r["max_tokens"]),
                rec, w0, w1,
            )
            fut.set_result((messages, answer))
        except asyncio.CancelledError:
            rec["error"] = rec["error"] or "cancelled at the end of the run"
            if not fut.done():
                fut.cancel()
            raise
        except (aiohttp.ClientError, OSError, ValueError) as ex:
            rec["error"] = "{}: {}".format(type(ex).__name__, ex)[:300]
            if not fut.done():
                fut.set_result((r["messages"], ""))

    tasks = [asyncio.create_task(one(r)) for r in plan["requests"]]
    judged = [t for t, r in zip(tasks, plan["requests"]) if r.get("judged")]
    await asyncio.sleep(max(0.0, w1 - now()))
    if judged:   # through the drain, until the last judged request has ended
        await asyncio.wait(judged, timeout=max(0.0, stop_at - now()))
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def run_closed(plan, session, url, model, t0, w0, w1, stop_at, records):
    """Each client sends its next request when its last one ends; none is
    started after the window has closed. Returns the highest index in its
    list that any client reached: how far from the plan's end the run was."""
    reached = [-1] * len(plan["clients"])

    async def client(c, seq):
        await asyncio.sleep(max(0.0, t0 - now()))
        for j, r in enumerate(seq):
            if now() >= w1:
                return
            reached[c] = j
            rec = new_record(r, late=0.0)
            records.append(rec)
            try:
                await stream_chat(
                    session, url,
                    request_body(model, r["messages"], r["max_tokens"]),
                    rec, w0, w1,
                )
            except asyncio.CancelledError:
                rec["error"] = rec["error"] or "cancelled at the end of the run"
                raise
            except (aiohttp.ClientError, OSError, ValueError) as ex:
                rec["error"] = "{}: {}".format(type(ex).__name__, ex)[:300]
                return
            rec["due"] = rec["sent"]
            # judged: the requests that ended inside the window
            rec["judged"] = rec["end"] is not None and w0 <= rec["end"] < w1
        floor = plan.get("floor_request_s")
        raise RuntimeError(
            "client {} ran out of requests before the window closed: all {} "
            "of its list ended {:.1f} s before the close (block 0 held {}); "
            "{}".format(
                c, len(seq), w1 - now(), plan.get("block0_per_client"),
                "the mix has no floor_request_s: give it one" if not floor else
                "a request took under the mix's floor_request_s = {}: the "
                "floor's arithmetic in the mix file is at fault, not the "
                "program".format(floor)))

    tasks = [asyncio.create_task(client(c, seq))
             for c, seq in enumerate(plan["clients"])]
    await asyncio.sleep(max(0.0, stop_at - now()))
    for t in tasks:
        t.cancel()
    results = await asyncio.gather(*tasks, return_exceptions=True)
    for res in results:
        if isinstance(res, RuntimeError):
            raise res
    return max(reached)


async def front_probe(session, base, model, w0, w1, interval, out):
    """The round trip of a request that does all of the front's work (socket,
    router, request processor, endpoint, tokenizer) and none of the engine's,
    taken inside the window: what the front adds under this load."""
    url = base + "/serve/openai/v1/tokenize"
    body = {"model": model, "prompt": "front probe " * 20}
    await asyncio.sleep(max(0.0, w0 - now()))
    while now() < w1:
        t = now()
        try:
            async with session.post(url, json=body) as resp:
                await resp.read()
                if resp.status == 200:
                    out.append((now() - t) * 1000.0)
        except (aiohttp.ClientError, OSError):
            pass
        await asyncio.sleep(max(0.0, t + interval - now()))


async def main_async(args) -> dict:
    with open(args.plan) as f:
        plan = json.load(f)
    t0 = args.t0
    w0 = t0 + plan["ramp_s"]
    w1 = w0 + plan["window_s"]
    stop_at = w1 + plan["drain_s"]
    url = args.base + "/serve/openai/v1/chat/completions"
    records, probes = [], []
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        probe = asyncio.create_task(front_probe(
            session, args.base, args.model, w0, w1,
            plan["probe_interval_s"], probes,
        ))
        run = run_open if plan["loop"] == "open" else run_closed
        try:
            deepest = await run(plan, session, url, args.model, t0, w0, w1,
                                stop_at, records)
        finally:
            probe.cancel()
            await asyncio.gather(probe, return_exceptions=True)
    with open(args.records, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return {"t0": t0, "w0": w0, "w1": w1, "records": len(records),
            "deepest_request": deepest, "front_probe_ms": probes}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plan", required=True)
    ap.add_argument("--records", required=True)
    ap.add_argument("--base", required=True)
    ap.add_argument("--model", required=True)
    ap.add_argument("--t0", type=float, required=True)
    args = ap.parse_args()
    if "jax" in sys.modules:
        raise RuntimeError("the load generator must not import jax")
    summary = asyncio.run(main_async(args))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
