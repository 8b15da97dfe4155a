"""Disaggregated prefill/decode loadtest (docs/disaggregation.md).

Replays a repeated-conversation + batch trace against three arms —

1. ``mono``:   ONE replica doing both jobs (the byte-identity baseline),
2. ``hybrid``: two hybrid replicas behind the prefix-affine router (the
               PR-12 fleet: both still do both jobs),
3. ``disagg``: two replicas split ``prefill`` / ``decode`` with the KV
               transport shipping every admission's prefix between them —

and certifies the ISSUE-14 acceptance criteria on the committed artifact
(``benchmarks/DISAGG_AB_cpu.json``, asserted by
tests/test_loadtest_artifact.py in tier-1):

- ship hit rate >= 0.9 on the clean path: the decode replica's
  admissions find the shipped prefix resident and recompute NONE of the
  shipped KV (engine ``kv_ship`` counters, not harness bookkeeping);
- every arm's streams byte-identical to the mono arm's (greedy, int8
  paged KV, radix caching and shipping never change tokens);
- 0 KV-sanitizer violations, 0 post-warmup XLA compiles (STRICT compile
  sentry — completing at all is the zero-recompile certificate).

Measurement model, stated plainly: unlike the PR-12 router loadtest's
isolated-substream estimate, every arm here runs CO-SCHEDULED through
the live group (a disaggregated request's prefill and decode legs are
inherently sequential across replicas — there is no honest way to
isolate them). On this one-core container the goodput columns therefore
carry scheduler interference no real fleet has and are reported for
SHAPE only; the committed headline certifies correctness, ship hit
rate, and the zero-recompile/zero-leak certificates, not fleet
throughput. Disaggregation on the chip: not measured (ROADMAP Reach
item 5).

    python bench.py --loadtest --replicas 2 --disaggregated --smoke
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

REPO = Path(__file__).resolve().parent.parent
ARTIFACT = REPO / "benchmarks" / "DISAGG_AB_cpu.json"

# artifact schema (asserted by tests/test_loadtest_artifact.py in tier-1)
SCHEMA_KEYS = {
    "metric", "platform", "smoke", "replicas", "engine", "trace", "arms",
    "headline",
}
ARM_KEYS = {
    "name", "replicas", "roles", "requests", "completed", "shed", "errors",
    "duration_s", "goodput_tok_s", "interactive_ttft_p50_ms",
    "interactive_ttft_p99_ms", "streams_identical_to_mono",
    "post_warmup_compiles", "warmup_requests", "sanitizer_checks",
    "sanitizer_violations", "kv_ship", "disaggregation",
}
HEADLINE_KEYS = {
    "ship_hit_rate", "ship_hit_bound", "ship_ok", "ship_legs",
    "ship_drops", "ship_warm_skips", "receive_reroutes",
    "streams_identical", "goodput_tok_s_mono", "goodput_tok_s_hybrid",
    "goodput_tok_s_disagg", "goodput_note", "post_warmup_compiles",
    "compile_sentry_mode", "sanitizer_checks", "sanitizer_violations",
}

# the trace: repeated conversations (each turn extends the last — the
# prefix workload shipping exists for) + batch one-shots
N_CONVERSATIONS = 10
N_TURNS = 4
CONV_BASE = 96           # tokens of history at turn 0
TURN_STEP = 16           # tokens appended per turn
CONV_MAX_NEW = 6
N_BATCH = 8
BATCH_WORKERS = 2
BATCH_PROMPT = 48
BATCH_MAX_NEW = 12


def _percentile(xs: List[float], q: float) -> Optional[float]:
    if not xs:
        return None
    xs = sorted(xs)
    idx = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
    return xs[idx]


def conv_prompt(conv: int, turn: int) -> List[int]:
    n = CONV_BASE + TURN_STEP * turn
    return [(conv * 67 + i * 13) % 239 + 1 for i in range(n)]


def batch_prompt(i: int) -> List[int]:
    return [(i * 101 + j * 17) % 239 + 1 for j in range(BATCH_PROMPT)]


def engine_cfg() -> Dict[str, Any]:
    """One replica's budget. int8 paged KV (the transport payload the
    tiering/demote path defined: int8 pages + f32 scale rows); page_size
    32 keeps the int8 kernel gate clean on TPU re-runs."""
    return dict(
        max_batch=4,
        max_seq_len=256,
        prefill_buckets=[32, 64, 96, 128, 160, 192],
        eos_token_id=None,          # fixed work per request
        decode_steps=1,
        cache_mode="paged",
        page_size=32,
        chunked_prefill_size=32,
        prefix_cache=384,
        prefix_block=32,
        num_pages=161,              # 160 usable (page 0 is the null page)
        prefix_cache_pages=96,      # whole trace working set stays resident
        max_pending=32,
        brownout=True,
        watchdog_interval=5.0,
        pipeline_depth=1 if (os.cpu_count() or 1) == 1 else None,
    )


def build_group(n_replicas: int, roles: Optional[List[str]]):
    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import LLMEngineCore
    from clearml_serving_tpu.llm.replica import ReplicaGroup

    bundle = models.build_model(
        "llama",
        {"preset": "llama-tiny", "dtype": "float32", "kv_quant": "int8"},
    )
    params = bundle.init(jax.random.PRNGKey(0))
    cfg = engine_cfg()
    engines = [
        LLMEngineCore(bundle, params, replica="r{}".format(i), **cfg)
        for i in range(n_replicas)
    ]
    return ReplicaGroup(engines, warmup_mode="startup", roles=roles), cfg


async def _consume(group, request, rec: dict, records: List[dict]) -> None:
    from clearml_serving_tpu.errors import (
        EngineOverloadedError,
        RequestError,
    )

    try:
        toks: List[int] = []
        async for token in group.generate(request):
            toks.append(int(token))
        rec["status"] = "ok"
        rec["tokens"] = toks
        if request.first_token_at is not None:
            rec["ttft_ms"] = (
                request.first_token_at - request.submitted_at
            ) * 1e3
        rec["t_done"] = time.perf_counter()
    except EngineOverloadedError:
        rec["status"] = "shed"
    except RequestError as ex:
        rec["status"] = "error"
        rec["error"] = repr(ex)[:200]
    except asyncio.CancelledError:
        rec["status"] = "cancelled"
        raise
    except Exception as ex:  # noqa: BLE001 - harness must keep counting
        rec["status"] = "error"
        rec["error"] = repr(ex)[:200]
    finally:
        records.append(rec)


async def _run_trace(group, seed: int) -> dict:
    """Co-scheduled open sessions through the live group (module
    docstring defends the model): conversation sessions run turns in
    order with think times, batch workers run closed-loop."""
    from clearml_serving_tpu.llm.engine import GenRequest

    rng = random.Random(seed)
    records: List[dict] = []

    async def session(conv: int) -> None:
        await asyncio.sleep(0.02 * (conv % 5))
        for turn in range(N_TURNS):
            request = GenRequest(
                prompt_ids=conv_prompt(conv, turn),
                max_new_tokens=CONV_MAX_NEW, priority="interactive",
            )
            rec = {"cls": "interactive", "conv": conv, "turn": turn}
            await _consume(group, request, rec, records)
            await asyncio.sleep(rng.uniform(0.005, 0.03))

    async def batch_worker(wid: int) -> None:
        for i in range(wid, N_BATCH, BATCH_WORKERS):
            request = GenRequest(
                prompt_ids=batch_prompt(i), max_new_tokens=BATCH_MAX_NEW,
                priority="batch",
            )
            rec = {"cls": "batch", "idx": i}
            await _consume(group, request, rec, records)

    t0 = time.perf_counter()
    await asyncio.gather(
        *[session(c) for c in range(N_CONVERSATIONS)],
        *[batch_worker(w) for w in range(BATCH_WORKERS)],
    )
    await group.wait_drained()
    done_times = [r["t_done"] for r in records if "t_done" in r]
    duration = (max(done_times) if done_times else time.perf_counter()) - t0
    done = [r for r in records if r["status"] == "ok"]
    ttfts = [
        r["ttft_ms"] for r in done
        if r["cls"] == "interactive" and r.get("ttft_ms") is not None
    ]
    return {
        "records": records,
        "requests": len(records),
        "completed": len(done),
        "shed": sum(1 for r in records if r["status"] == "shed"),
        "errors": sum(
            1 for r in records if r["status"] not in ("ok", "shed")
        ),
        "duration_s": round(duration, 2),
        "goodput_tok_s": round(
            sum(len(r.get("tokens", [])) for r in done)
            / max(1e-6, duration), 2,
        ),
        "interactive_ttft_p50_ms": round(_percentile(ttfts, 0.5) or 0.0, 2),
        "interactive_ttft_p99_ms": round(_percentile(ttfts, 0.99) or 0.0, 2),
    }


def _sentry_serve_count() -> int:
    from clearml_serving_tpu.llm import compile_sentry

    if not compile_sentry.enabled():
        return -1
    return int(compile_sentry.get().stats_brief().get("serve", -1))


def _merge_ship(group) -> Optional[dict]:
    """Fleet-wide kv_ship counters: sums over replicas, with the hit rate
    re-derived from the summed hit/recompute counts."""
    blocks = [
        r.engine._kv_ship_snapshot() for r in group.replicas
    ]
    blocks = [b for b in blocks if b]
    if not blocks:
        return None
    out = {
        k: sum(b[k] for b in blocks)
        for k in ("ships", "ship_pages", "ship_drops", "receives",
                  "receive_pages", "receive_empty", "receive_failures",
                  "hits", "recomputes")
    }
    judged = out["hits"] + out["recomputes"]
    out["hit_rate"] = round(out["hits"] / judged, 4) if judged else None
    return out


async def _run_arm(name: str, n_replicas: int,
                   roles: Optional[List[str]],
                   expected: Optional[Dict[tuple, List[int]]]) -> dict:
    from clearml_serving_tpu.llm import compile_sentry

    group, cfg = build_group(n_replicas, roles)
    try:
        if compile_sentry.enabled():
            # fresh fence per arm: the next arm's engines re-warm their
            # own jit caches and those compiles must count as warmup
            compile_sentry.get().reset(
                strict=compile_sentry.strict_enabled()
            )
        warm = await group.warmup(full=True)
        trace = await _run_trace(group, seed=11 + n_replicas)
        identical = None
        streams = {}
        for rec in trace.pop("records"):
            if rec["status"] != "ok":
                continue
            key = (
                ("c", rec["conv"], rec["turn"])
                if rec["cls"] == "interactive"
                else ("b", rec["idx"])
            )
            streams[key] = rec["tokens"]
        if expected is not None:
            identical = bool(streams) and all(
                streams.get(k) == v for k, v in expected.items()
            )
        sanitizer_checks = 0
        sanitizer_failures = 0
        for replica in group.replicas:
            sanitizer = replica.engine._sanitizer
            if sanitizer is None:
                sanitizer_failures = -1
                continue
            s = sanitizer.stats()
            sanitizer_checks += s.get("checks", 0)
            sanitizer_failures += s.get("failures", 0)
        arm = dict(
            trace,
            name=name,
            replicas=n_replicas,
            roles=roles or ["hybrid"] * n_replicas,
            streams_identical_to_mono=identical,
            warmup_requests=warm["requests"],
            post_warmup_compiles=_sentry_serve_count(),
            sanitizer_checks=sanitizer_checks,
            sanitizer_violations=sanitizer_failures,
            kv_ship=_merge_ship(group),
            disaggregation=group._disagg_snapshot(),
        )
        return {"arm": arm, "streams": streams, "cfg": cfg}
    finally:
        group.stop()


async def _run_async(smoke: bool, replicas: int) -> dict:
    from clearml_serving_tpu.llm import compile_sentry

    mono = await _run_arm("mono", 1, None, None)
    hybrid = await _run_arm(
        "hybrid", replicas, None, mono["streams"]
    )
    roles = ["prefill"] * (replicas - 1) + ["decode"]
    disagg = await _run_arm(
        "disagg", replicas, roles, mono["streams"]
    )
    a1, a2, a3 = mono["arm"], hybrid["arm"], disagg["arm"]
    ship = a3["kv_ship"] or {}
    dis = a3["disaggregation"] or {}
    sentry_mode = (
        compile_sentry.get().stats_brief().get("mode", "off")
        if compile_sentry.enabled() else "off"
    )
    streams_identical = bool(
        a2["streams_identical_to_mono"] and a3["streams_identical_to_mono"]
    )
    return {
        "metric": "llm_disagg_loadtest" + ("_cpusmoke" if smoke else ""),
        "platform": "cpu",
        "smoke": smoke,
        "replicas": replicas,
        "engine": {
            k: v for k, v in disagg["cfg"].items() if k != "prefill_buckets"
        },
        "trace": {
            "conversations": N_CONVERSATIONS,
            "turns": N_TURNS,
            "conv_base_tokens": CONV_BASE,
            "turn_step_tokens": TURN_STEP,
            "conv_max_new": CONV_MAX_NEW,
            "batch_requests": N_BATCH,
            "batch_prompt_tokens": BATCH_PROMPT,
            "batch_max_new": BATCH_MAX_NEW,
        },
        "arms": [a1, a2, a3],
        "headline": {
            "ship_hit_rate": ship.get("hit_rate"),
            "ship_hit_bound": 0.9,
            "ship_ok": bool(
                ship.get("hit_rate") is not None
                and ship["hit_rate"] >= 0.9
            ),
            "ship_legs": dis.get("ship_legs", 0),
            "ship_drops": ship.get("ship_drops", 0),
            "ship_warm_skips": dis.get("ship_warm_skips", 0),
            "receive_reroutes": dis.get("receive_reroutes", 0),
            "streams_identical": streams_identical,
            "goodput_tok_s_mono": a1["goodput_tok_s"],
            "goodput_tok_s_hybrid": a2["goodput_tok_s"],
            "goodput_tok_s_disagg": a3["goodput_tok_s"],
            "goodput_note": (
                "co-scheduled on one core: goodput columns carry "
                "scheduler interference no real fleet has; this artifact "
                "certifies correctness + ship hit rate, not throughput"
            ),
            "post_warmup_compiles": max(
                a1["post_warmup_compiles"], a2["post_warmup_compiles"],
                a3["post_warmup_compiles"],
            ),
            "compile_sentry_mode": sentry_mode,
            "sanitizer_checks": a1["sanitizer_checks"]
            + a2["sanitizer_checks"] + a3["sanitizer_checks"],
            "sanitizer_violations": max(
                a1["sanitizer_violations"], a2["sanitizer_violations"],
                a3["sanitizer_violations"],
            ),
        },
    }


def run(smoke: bool = True, replicas: int = 2,
        write_artifact: bool = True) -> dict:
    """Entry point for ``bench.py --loadtest --replicas N
    --disaggregated``. Forces the CPU backend, arms the KV sanitizer AND
    the strict compile sentry BEFORE any engine exists (completing at all
    is the zero-recompile certificate), runs the three arms, optionally
    updates the committed artifact."""
    if replicas < 2:
        raise ValueError("the disaggregated loadtest needs --replicas >= 2")
    os.environ["TPUSERVE_SANITIZE"] = "1"
    # forced, not defaulted: a pre-exported "1" must not silently
    # downgrade the certification run to count-only mode
    os.environ["TPUSERVE_COMPILE_SENTRY"] = "strict"
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    from clearml_serving_tpu.engines.jax_engine import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    row = asyncio.run(_run_async(smoke, replicas))
    if write_artifact:
        ARTIFACT.write_text(json.dumps(row, indent=2) + "\n")
    return row


def main() -> None:
    import sys

    smoke = "--smoke" in sys.argv
    row = run(smoke=smoke)
    print(json.dumps(row))


if __name__ == "__main__":
    main()
