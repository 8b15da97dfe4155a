#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the machine this is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration file (``benchmark/configs/``) under a traffic mix
(``benchmark/traffic/``), both named in ``BENCHMARK.json``. This process holds
the chip: it builds the endpoint through the control plane, serves the real
router on a socket, proves the served path against the plain reference,
primes every program the traffic will use, and then lets a child process
that never imports jax (``loadgen.py``) offer the load. Set-up ends, and the
measured window opens, after the mix's ramp: on an engine already in steady
state. With ``--trace 1`` the last seconds of the window are profiled.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` when traced, and for a
closed loop ``closed_loop``: the deepest request any caller reached beside the
sizes of its plan). Without a
TPU, or with fewer chips than the cell asks, it exits 2 and prints no result.
``--rehearse`` walks every phase on whatever device there is, says so in
``device``, and exits 3: a proof of control flow, never a measurement.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TRACE_SECONDS = 5.0      # the traced tail of the window
SAMPLE_SECONDS = 0.5     # health samples inside the window


def log(msg: str) -> None:
    print("[benchmark {:7.1f}s] {}".format(time.monotonic() - T_PROCESS, msg),
          file=sys.stderr, flush=True)


def load_manifest(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str):
    for w in manifest["workloads"]:
        if w["name"] == name:
            cfg = next(c for c in manifest["configs"] if c["name"] == w["config"])
            return w, cfg
    raise SystemExit("no workload {!r} in the manifest".format(name))


def metrics_of(manifest: dict, group: str, workload: str) -> list:
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


# ------------------------------------------------------------------ phases

async def post_json(session, url, body, timeout=900):
    import aiohttp

    async with session.post(
        url, json=body, timeout=aiohttp.ClientTimeout(total=timeout)
    ) as resp:
        payload = await resp.json(content_type=None)
        if resp.status != 200:
            raise RuntimeError("HTTP {} from {}: {}".format(
                resp.status, url, json.dumps(payload)[:600]))
        return payload


async def wait_warm(svc, timeout=1500.0):
    t0 = time.monotonic()
    while True:
        state = svc.warmup_state()
        if state in (None, "done"):
            return
        if str(state).startswith("failed"):
            raise RuntimeError("engine.warmup " + str(state))
        if time.monotonic() - t0 > timeout:
            raise RuntimeError("engine.warmup still {} after {:.0f} s".format(
                state, timeout))
        await asyncio.sleep(0.25)


async def reference_phase(svc, session, cfg, seed, traced: bool) -> dict:
    """Probes alone in the engine, twice; identical ids; logprobs against
    the plain float32 reference."""
    from benchmark import correctness as cx
    from benchmark.sut import model_block

    model = model_block(cfg)
    spec = cfg.get("probes") or {}
    sizes = spec.get("traced_prompt_tokens" if traced else "prompt_tokens",
                     [40, 200, 600])
    n_new = int(spec.get("traced_new_tokens" if traced else "new_tokens", 6))
    prompts = cx.probe_set(seed, int(model["vocab_size"]), sizes)
    url = svc.base + "/serve/openai/v1/completions"
    rounds = []
    for _ in range(2):
        got = []
        for ids in prompts:
            got.append(cx.parse_probe(await post_json(
                session, url, cx.probe_body(svc.name, ids, n_new))))
        rounds.append(got)
    same = all(a["ids"] == b["ids"] and len(a["ids"]) > 0
               for a, b in zip(*rounds))
    t0 = time.monotonic()
    weights = cx.ServedWeights(svc.engine.params)
    positions = []
    for ids, probe in zip(prompts, rounds[0]):
        ref = cx.reference_logprobs(cfg["reference"], model, weights, ids,
                                    probe["ids"])
        positions += cx.compare_probe(ref, probe)
    del weights
    return dict(cx.verdict(positions, spec["tolerance"]), repeat_identical=same,
                position_rms=[round(x, 5) for x in positions],
                probe_prompt_tokens=list(sizes), new_tokens=n_new,
                reference_seconds=time.monotonic() - t0)


async def prime_phase(svc, session, seed) -> dict:
    """Run, before the window, every program the population's requests use.
    The program's own sweep (aux engine.warmup) warms plain sampling; the
    population samples with ``min_tokens`` / ``logit_bias`` / ``logprobs``,
    whose launch variants trace on first use (llm/warmup.py says so). The
    launch program is keyed by the decode window (1, 2, 4 ... chained steps),
    picked from the token budget left after the prefill shares, so three
    deterministic situations reach all of them: a request decoding alone
    (widest window), one decoding while a long prompt takes the whole budget
    (window 1), and one decoding while a prompt's last chunk leaves 1-2
    tokens of budget per decode row (window 2; wider engines: each power of
    two up to the cap the same way). Then bursts of 2..max_batch two-token
    prompts queued behind a long prefill end their prefill in one launch, for
    the finish-row gather at every padded size."""
    import random

    from benchmark import loadgen, traffic

    h = svc.engine.health()
    ragged = h.get("ragged") or {}
    budget = int(ragged.get("step_token_budget") or 0)
    cap = int(ragged.get("decode_steps") or 1)
    max_batch = int(svc.cfg["engine"]["max_batch"])
    chat = svc.base + "/serve/openai/v1/chat/completions"
    rng = random.Random(seed * 31 + 5)
    done = {"launch_windows": [], "gather_bursts": []}

    def chat_body(n_prompt_tokens: int, n_out: int) -> dict:
        overhead = traffic.prompt_tokens([{"role": "user", "content": ""}])
        content = traffic.text(rng, max(1, n_prompt_tokens - overhead))
        return loadgen.request_body(
            svc.name, [{"role": "user", "content": content}], n_out)

    async def stream(body, first_token: asyncio.Event = None):
        rec = loadgen.new_record({"id": "prime", "max_tokens": body["max_tokens"]})
        task = asyncio.create_task(
            loadgen.stream_chat(session, chat, body, rec, 0.0, 0.0))
        if first_token is not None:
            while rec["first"] is None and not task.done():
                await asyncio.sleep(0.005)
            first_token.set()
        await task
        if rec["n_out"] != body["max_tokens"]:
            raise RuntimeError("priming request came back short: {}".format(rec))

    # alone: the widest window
    t_prime = time.monotonic()
    await stream(chat_body(40, 3 * cap + 2))
    done["launch_windows"].append(cap)
    done["seconds"] = [round(time.monotonic() - t_prime, 2)]
    if budget:
        # per decode row the window is the power of two under 1 + left:
        # left = budget - 1 - (the prompt's last chunk)
        windows = []
        w = 1
        while w < cap:
            windows.append(w)
            w *= 2
        for w in windows:
            left = 0 if w == 1 else w  # w <= 1 + left < 2w
            long_prompt = 3 * (budget - 1) + (budget - 1 - left)
            ev = asyncio.Event()
            a = asyncio.create_task(stream(chat_body(40, 6 * cap + 24), ev))
            await ev.wait()
            await stream(chat_body(long_prompt, 2))
            await a
            done["launch_windows"].append(w)
            done["seconds"].append(round(time.monotonic() - t_prime, 2))
        # finish-row gathers: k two-token prompts queued behind a prefill
        # of whole budgets end their own prefill together in the next launch
        comp = svc.base + "/serve/openai/v1/completions"
        k = 2
        while k <= max_batch and 2 * k <= budget - 1:
            ev = asyncio.Event()
            blocker = asyncio.create_task(stream(chat_body(6 * budget, 2), ev))
            await asyncio.sleep(0.05)
            bodies = [{
                "model": svc.name, "prompt": [40 + i % 80, 65 + i % 26],
                "max_tokens": 2, "min_tokens": 2, "temperature": 0,
                "logit_bias": loadgen.ASCII_BIAS, "logprobs": 0,
            } for i in range(k)]
            await asyncio.gather(*[post_json(session, comp, b) for b in bodies])
            await blocker
            done["gather_bursts"].append(k)
            done["seconds"].append(round(time.monotonic() - t_prime, 2))
            k *= 2
    await svc.engine.wait_drained()
    return done


async def fill_phase(svc, session, plan) -> dict:
    """An open loop starts with sessions in progress. In a server that has
    been up for a while their histories are in the prefix cache, so set-up
    puts them there: every such history is sent once as a prompt (one token
    asked for), a few at a time. Without it the first turns of the ramp
    prefill whole histories, and that backlog reaches into the window."""
    from benchmark import loadgen, traffic

    if plan["loop"] != "open":
        return {"histories": 0}
    t0 = time.monotonic()
    histories = [r["messages"][:-1] for r in plan["requests"]
                 if not r["after"] and len(r["messages"]) > 2]
    # the shared system prompts first, once each
    systems = {}
    for r in plan["requests"]:
        if not r["after"]:
            systems.setdefault(r["messages"][0]["content"], r["messages"][:1])
    url = svc.base + "/serve/openai/v1/chat/completions"
    sem = asyncio.Semaphore(4)

    async def one(messages):
        async with sem:
            rec = loadgen.new_record({"id": "fill", "max_tokens": 1})
            await loadgen.stream_chat(
                session, url, loadgen.request_body(svc.name, messages, 1),
                rec, 0.0, 0.0)
            if rec["n_out"] != 1:
                raise RuntimeError("cache fill request failed: {}".format(rec))

    await asyncio.gather(*[one(m) for m in systems.values()])
    await asyncio.gather(*[one(m) for m in histories])
    await svc.engine.wait_drained()
    return {"histories": len(histories), "systems": len(systems),
            "tokens": sum(traffic.prompt_tokens(m) for m in histories),
            "seconds": round(time.monotonic() - t0, 2)}


def start_loadgen(out_dir: Path, svc, plan: dict, t0: float):
    plan_path = out_dir / "plan.json"
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    return subprocess.Popen(
        [sys.executable, str(HERE / "loadgen.py"), "--plan", str(plan_path),
         "--records", str(out_dir / "records.jsonl"), "--base", svc.base,
         "--model", svc.name, "--t0", repr(t0)],
        stdout=subprocess.PIPE, stderr=open(out_dir / "loadgen.err", "w"),
        env=env, cwd=str(ROOT), text=True,
    )


async def window_phase(svc, plan, t0, trace_dir):
    """Snapshots at the window's edges, light samples inside it, and with
    ``trace_dir`` a profile of its last seconds."""
    import jax

    w0 = t0 + plan["ramp_s"]
    w1 = w0 + plan["window_s"]
    await asyncio.sleep(max(0.0, w0 - time.monotonic()))
    out = {"t_open": time.monotonic(), "health_before": svc.engine.health(),
           "before": svc.snapshot(), "samples": []}
    trace_at = w1 - min(TRACE_SECONDS, plan["window_s"] / 2.0)
    tracing = False
    while True:
        now = time.monotonic()
        if now >= w1:
            break
        if trace_dir is not None and not tracing and now >= trace_at:
            out["trace_before"] = svc.snapshot()
            out["trace_t0"] = time.monotonic()
            jax.profiler.start_trace(str(trace_dir))
            tracing = True
        out["samples"].append(dict(svc.light_sample(), t=now - w0))
        await asyncio.sleep(min(SAMPLE_SECONDS, max(0.0, w1 - time.monotonic())))
    out["after"] = svc.snapshot()
    out["health_after"] = svc.engine.health()
    out["t_close"] = time.monotonic()
    if tracing:
        out["trace_after"] = out["after"]
        out["trace_t1"] = time.monotonic()
        # writing the trace out takes seconds: after the window, off the loop
        await asyncio.to_thread(jax.profiler.stop_trace)
    return out


async def offer(svc, plan, out_dir: Path, trace_dir=None):
    """Start the load generator, watch the window, wait for the generator."""
    t0 = time.monotonic() + 2.0
    child = start_loadgen(out_dir, svc, plan, t0)
    try:
        win = await window_phase(svc, plan, t0, trace_dir)
        stdout, _ = await asyncio.to_thread(
            child.communicate, None, plan["drain_s"] + 60.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise RuntimeError("the load generator exited {}: {}".format(
            child.returncode, (out_dir / "loadgen.err").read_text()[-2000:]))
    return win, stdout


async def sweep(args, svc, cell, cfg, out_dir: Path) -> list:
    """Find the knee of an open-loop mix: the same schedule at several session
    rates, one window each on one warm engine. A rate is sustained when the
    completions of the window keep up with its arrivals and neither the
    engine's queue nor TTFT grows from the window's first half to its second."""
    import aiohttp

    from benchmark import reduce as rd
    from benchmark import traffic

    table = []
    for rate in [float(x) for x in args.sweep.split(",")]:
        plan = traffic.make_plan(cell["traffic"], cfg["name"], args.seed,
                                 args.seconds, args.traffic_dir, rate=rate)
        async with aiohttp.ClientSession() as session:
            await fill_phase(svc, session, plan)
        win, _ = await offer(svc, plan, out_dir)
        records = rd.load_records(out_dir / "records.jsonl")
        s = rd.summarise(records, plan["window_s"])
        w0, w1 = win["t_open"], win["t_close"]
        mid = (w0 + w1) / 2
        ok = [r for r in records if rd.request_ok(r) and r["due"] is not None]

        def half(lo, hi, fn):
            xs = [fn(r) for r in ok if lo <= r["due"] < hi and fn(r) is not None]
            return rd.percentile(xs, 0.5)

        qd = [x["queue_depth"] or 0 for x in win["samples"]]
        act = [x["active_slots"] or 0 for x in win["samples"]]
        n = max(1, len(qd) // 2)
        row = {
            "session_rate": rate,
            "arrivals": sum(1 for r in records if r["due"] and w0 <= r["due"] < w1),
            "completions": sum(1 for r in records if r["end"] and w0 <= r["end"] < w1),
            "failed": s["failed"], "attempted": s["attempted"],
            "ttft_p50_first_half": half(w0, mid, rd.ttft_ms),
            "ttft_p50_second_half": half(mid, w1, rd.ttft_ms),
            "tpot_p50_ms": rd.percentile([x for x in map(rd.tpot_ms, ok) if x], 0.5),
            "ttft_p50_ms": rd.percentile([x for x in map(rd.ttft_ms, ok) if x is not None], 0.5),
            "ttft_p90_ms": rd.percentile([x for x in map(rd.ttft_ms, ok) if x is not None], 0.9),
            "queue_depth_first_half": sum(qd[:n]) / n,
            "queue_depth_second_half": sum(qd[n:]) / max(1, len(qd) - n),
            "active_slots_mean": sum(act) / max(1, len(act)),
            "out_tok_s": s["out_tok_s"], "gen_late_p95_ms": s["gen_late_p95_ms"],
        }
        log("sweep: {}".format(row))
        table.append(row)
        await svc.engine.wait_drained()
    with open(out_dir / "sweep.json", "w") as f:
        json.dump(table, f, indent=1)
    return table


# -------------------------------------------------------------------- main

async def run_cell(args, manifest, cell, cfg_entry, out_dir: Path) -> dict:
    import aiohttp

    from benchmark import correctness as cx
    from benchmark import reduce as rd
    from benchmark import traffic
    from benchmark.sut import Service, device_block, load_config

    cfg = load_config(ROOT / cfg_entry["file"])
    cfg.setdefault("name", cfg_entry["name"])
    traced = bool(args.trace)
    mix = traffic.load_mix(cell["traffic"], args.traffic_dir)
    plan = traffic.make_plan(cell["traffic"], cfg["name"], args.seed,
                             args.seconds, args.traffic_dir)
    log("plan: {} loop, {} requests".format(
        plan["loop"], len(plan.get("requests") or
                          [r for c in plan["clients"] for r in c])))

    svc = Service(cfg, args.seed, out_dir)
    await svc.start()
    await wait_warm(svc)
    log("engine ready")
    phases = {"ready_s": time.monotonic() - T_PROCESS}
    async with aiohttp.ClientSession() as session:
        ref = await reference_phase(svc, session, cfg, args.seed, traced)
        phases["reference_s"] = time.monotonic() - T_PROCESS
        log("reference: {}".format(
            {k: v for k, v in ref.items() if k != "position_rms"}))
        primed = await prime_phase(svc, session, args.seed)
        phases["primed_s"] = time.monotonic() - T_PROCESS
        log("primed: {}".format(primed))
        if not args.sweep:
            primed["fill"] = await fill_phase(svc, session, plan)
            phases["filled_s"] = time.monotonic() - T_PROCESS
            log("cache filled: {}".format(primed["fill"]))

    if args.sweep:
        table = await sweep(args, svc, cell, cfg, out_dir)
        await svc.stop()
        return {"sweep": table, "device": device_block()}

    trace_dir = None
    if traced:
        trace_dir = ROOT / ".bench_tmp" / "trace" / "{}_{}".format(
            cell["name"], os.getpid())
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    win, stdout = await offer(svc, plan, out_dir, trace_dir)
    setup_s = win["t_open"] - T_PROCESS
    log("window closed; set-up was {:.1f} s".format(setup_s))
    gen = json.loads(stdout.strip().splitlines()[-1])
    records = rd.load_records(out_dir / "records.jsonl")
    limits = (mix.get("limits") or {}).get(cfg["name"])
    summary = rd.summarise(records, plan["window_s"], limits)
    device = device_block()
    sentry_events = []
    try:
        from clearml_serving_tpu.llm import compile_sentry

        sentry_events = [
            e for e in compile_sentry.get().stats()["events"]
            if e["t"] >= time.time() - (time.monotonic() - win["t_open"])
        ]
    except Exception as ex:  # diagnosis only
        sentry_events = [{"error": str(ex)}]
    await svc.stop()

    want_tpu = device["platform"] == "tpu"
    faults = cx.health_checks(win["health_before"], win["health_after"], want_tpu)
    if not ref["repeat_identical"]:
        faults.append("a probe sent twice answered with different token ids")
    if not ref["within"]:
        faults.append("served logprobs differ from the reference: {}".format(
            {k: v for k, v in ref.items() if k != "position_rms"}))
    if summary["failed"]:
        faults.append("{} of {} judged requests failed".format(
            summary["failed"], summary["attempted"]))
    if summary["attempted"] == 0:
        faults.append("no request was judged")

    ctx = {
        "summary": summary, "records": records, "window": win,
        "before": win["before"], "after": win["after"],
        "samples": win["samples"], "cfg": cfg, "mix": mix, "plan": plan,
        "device": device, "front_probe_ms": gen.get("front_probe_ms") or [],
        "window_s": plan["window_s"], "setup_s": setup_s, "trace": None,
    }
    if traced:
        from benchmark import xplane

        path = xplane.find_xplane(str(trace_dir))
        if path is None:
            faults.append("the profiler wrote no trace")
        else:
            ctx["trace"] = xplane.reduce_trace(path)
            ctx["trace_path"] = path
            ctx["trace_counters"] = (win["trace_before"], win["trace_after"])

    metrics = {}
    if traced:
        for m in metrics_of(manifest, "per_layer", cell["name"]):
            reader = importlib.import_module("benchmark.layer_metrics." + m["name"])
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            value = setup_s if m["name"] == "setup_s" else summary.get(m["name"])
            if value is None:
                faults.append("end-to-end metric {} has too few samples "
                              "({} judged)".format(m["name"], summary["attempted"]))
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    result = {
        "correct": not faults, "attempted": summary["attempted"],
        "failed": summary["failed"], "metrics": metrics, "device": device,
    }
    if traced and ctx["trace"] and ctx["trace"].get("devices"):
        from benchmark.layer_metrics import _common

        result["device"]["busy_s"] = ctx["trace"]["busy_s"]
        result["device"]["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = _common.breakdown(ctx)
    if traced and not args.keep_trace:   # every reader of the trace has run
        shutil.rmtree(trace_dir, ignore_errors=True)
    if plan["loop"] == "closed":
        # how far from its plan's end the run was (traffic._closed_plan)
        result["closed_loop"] = {
            "deepest_request": gen.get("deepest_request"),
            "block0_per_client": plan["block0_per_client"],
            "per_client": plan["per_client"]}
    detail = {
        "workload": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "faults": faults, "phases": phases,
        "reference": ref, "primed": primed, "summary": summary,
        "generator": {k: v for k, v in gen.items() if k != "front_probe_ms"},
        "compiles_in_window": sentry_events,
        "samples": win["samples"], "result": result,
        "counters": {"before": win["before"], "after": win["after"]},
    }
    with open(out_dir / "detail.json", "w") as f:
        json.dump(detail, f, indent=1, default=str)
    if faults:
        log("NOT CORRECT: " + "; ".join(faults))
    # every number compared, beside its limit: the last lines of stderr and
    # the last key of the line
    tol = ref.get("tolerance") or {}
    result["checks"] = {
        "typical_position_rms": [ref.get("typical_position_rms"), tol.get("typical")],
        "outlier_share": [ref.get("outlier_share"), tol.get("outlier_share")],
        "failed_requests": [summary["failed"], 0],
        "compiles_in_window": [cx.compiles(win["health_after"])
                               - cx.compiles(win["health_before"]), 0],
    }
    for name, (value, limit) in result["checks"].items():
        log("compared: {} = {} (limit {})".format(name, value, limit))
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--out", default=None,
                    help="directory for records and detail (default "
                         "chiprun_out/benchmark/<workload>/s<seed>t<trace>)")
    ap.add_argument("--traffic-dir", default=None,
                    help="where the mix files are (default benchmark/traffic)")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated session rates: find the knee of an "
                         "open-loop mix instead of measuring (prints a table)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the traced run's .xplane.pb under "
                         ".bench_tmp/trace/ (for benchmark/host_spans.py)")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk every phase without a TPU; exits 3")
    args = ap.parse_args()

    manifest = load_manifest(Path(args.manifest))
    cell, cfg_entry = find_cell(manifest, args.workload)
    try:
        importlib.import_module("clearml_serving_tpu")
    except ImportError as ex:
        log("the program is not in this checkout: {}".format(ex))
        return 2

    from benchmark.sut import place_caches

    place_caches(ROOT)
    import jax

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu" and len(devices) >= int(cell["chips"])
    if not on_tpu and not args.rehearse:
        log("needs {} TPU chip(s); jax reports {} x {}: nothing was run".format(
            cell["chips"], len(devices), devices[0].platform))
        return 2

    out_dir = Path(args.out) if args.out else (
        ROOT / "chiprun_out" / "benchmark" / cell["name"]
        / "s{}t{}".format(args.seed, args.trace))
    out_dir.mkdir(parents=True, exist_ok=True)
    result = asyncio.run(run_cell(args, manifest, cell, cfg_entry, out_dir))
    print(json.dumps(result), flush=True)
    if args.sweep:
        return 0
    return 0 if on_tpu else 3


if __name__ == "__main__":
    sys.exit(main())
