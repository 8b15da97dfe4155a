"""Decode-chunk timing on the chip, plus the CPU certificate modes.

``python bench.py`` times the fused decode+sample chunk — a hand-written
`lax.scan` over `bundle.decode`, the same shape of executable the
continuous-batching engine dispatches, NOT `LLMEngineCore` itself — on a
Llama-3-8B-shaped decoder (int8 weights, scan_layers, random weights) and
prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "tok/s/chip", "vs_baseline": N,
     "platform": "tpu", "backend": "tpu:<device_kind>", ...}

It measures in this one process and on a TPU only: where jax reports another
platform it exits non-zero and prints no number (utils/tpu.require_tpu) — a
CPU timing is never written under a per-chip unit. vs_baseline is the ratio
against the BASELINE.md target of 1500 tok/s/chip (Llama-8B class on v5e).
ROADMAP Speed item 1 replaces this entry with cells that drive the engine.

The ``--*-ab`` / ``--loadtest`` modes below are CPU certificates (identical
streams, hit rates, zero violations); they name themselves ``_cpu`` and
their tok/s and ms fields are CPU timings that say nothing about the chip.

NOTE on timing: every timed section ends with np.asarray of a value that
data-depends on the full computation, so the host clock stops after the
device finished, not after the enqueue.
"""

from __future__ import annotations

import json
import os
import sys
import time

TARGET_TOK_S = 1500.0  # BASELINE.md: Llama-3-8B class, tok/s/chip on v5e


def _measure(cfg, batch, seq_len, chunk, rounds, quantize):
    """Run the decode-throughput measurement on the current jax backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from clearml_serving_tpu import models
    from clearml_serving_tpu.engines.jax_engine import (
        enable_persistent_compilation_cache,
    )
    from clearml_serving_tpu.llm.sampling import SamplingParams, sample_tokens

    enable_persistent_compilation_cache()
    if quantize in ("int8", "int4"):
        # quantized tree built directly (never materializes full-precision
        # 8B); the model's weight accessor dequantizes per layer in the scan
        from clearml_serving_tpu.ops.quant import random_quantized_llama

        bundle, params = random_quantized_llama(
            cfg, seed=0, bits=4 if quantize == "int4" else 8
        )
    else:
        bundle = models.build_model("llama", cfg)
        params = bundle.init(jax.random.PRNGKey(0))
    cache = bundle.init_cache(batch, seq_len)
    # mid-sequence state: decode cost grows with cache occupancy; measure at
    # half-full for a steady-state figure
    cache["length"] = jnp.full((batch,), seq_len // 2, jnp.int32)

    sampling = SamplingParams(
        temperature=jnp.zeros((batch,), jnp.float32),
        top_k=jnp.zeros((batch,), jnp.int32),
        top_p=jnp.ones((batch,), jnp.float32),
    )

    def decode_chunk(params, tokens, cache, rng):
        def body(carry, step_rng):
            tokens, cache = carry
            logits, cache = bundle.decode(params, tokens, cache)
            sampled = sample_tokens(logits.astype(jnp.float32), sampling, step_rng)
            return (sampled, cache), sampled

        (tokens, cache), _ = jax.lax.scan(
            body, (tokens, cache), jax.random.split(rng, chunk)
        )
        return tokens, cache

    # TTFT: one prompt prefill + first greedy token, batch 1 (the
    # BASELINE.md target is p50 TTFT < 200 ms at prompt ~512)
    p_len = min(512, seq_len)
    ptokens = jnp.zeros((1, p_len), jnp.int32)
    pcache = bundle.init_cache(1, seq_len)
    prefill = jax.jit(bundle.prefill)
    plogits, _ = prefill(params, ptokens, jnp.asarray([p_len], jnp.int32), pcache)
    np.asarray(jnp.argmax(plogits))  # compile prefill AND argmax, readback-synced
    t0 = time.perf_counter()
    plogits, _ = prefill(params, ptokens, jnp.asarray([p_len], jnp.int32), pcache)
    first = jnp.argmax(plogits)
    np.asarray(first)
    ttft_ms = (time.perf_counter() - t0) * 1e3
    del pcache, plogits

    step = jax.jit(decode_chunk, donate_argnums=(2,))
    tokens = jnp.zeros((batch,), jnp.int32)
    rng = jax.random.PRNGKey(1)

    # warmup (compile + first execution), synced via readback
    tokens, cache = step(params, tokens, cache, rng)
    np.asarray(tokens)

    t0 = time.perf_counter()
    for _ in range(rounds):
        tokens, cache = step(params, tokens, cache, rng)
    np.asarray(tokens)  # data-dependent readback = true completion
    dt = time.perf_counter() - t0
    return batch * chunk * rounds / dt, ttft_ms


def main() -> None:
    """The default entry: one process, the chip or nothing."""
    from clearml_serving_tpu.utils.tpu import require_tpu

    ident = require_tpu()
    cfg = {
        "preset": os.environ.get("BENCH_PRESET", "llama3-8b"),
        "dtype": "bfloat16",
        "scan_layers": os.environ.get("BENCH_SCAN_LAYERS", "1").lower()
        in ("1", "true", "yes"),
    }
    kv_quant = os.environ.get("BENCH_KV_QUANT", "int8")
    if kv_quant and kv_quant != "none":
        cfg["kv_quant"] = kv_quant
    quantize = os.environ.get("BENCH_QUANTIZE", "int8")
    batch = int(os.environ.get("BENCH_BATCH", 32))
    seq_len = int(os.environ.get("BENCH_SEQ", 1024))
    chunk = int(os.environ.get("BENCH_CHUNK", 25))
    rounds = int(os.environ.get("BENCH_ROUNDS", 4))
    tok_s, ttft_ms = _measure(cfg, batch, seq_len, chunk, rounds, quantize)
    print(json.dumps({
        "metric": "llm_decode_throughput_{}{}{}_b{}".format(
            cfg["preset"],
            "-{}".format(quantize) if quantize else "",
            "-kv{}".format(cfg["kv_quant"]) if cfg.get("kv_quant") else "",
            batch,
        ),
        "value": round(tok_s, 2),
        "unit": "tok/s/chip",
        "vs_baseline": round(tok_s / TARGET_TOK_S, 4),
        "platform": ident["platform"],
        "backend": "{}:{}".format(ident["platform"], ident["device_kind"]),
        "device_count": ident["device_count"],
        "ttft_p{}_b1_ms".format(min(512, seq_len)): round(ttft_ms, 2),
        "ttft_target_ms": 200,  # BASELINE.md target is at prompt ~512
    }))


def _shared_prefix_smoke() -> None:
    """Shared-prefix TTFT scenario (``--shared-prefix``): N requests share a
    long system prompt; the radix prefix cache (llm/prefix_cache.py) should
    make every warm admission prefill ONLY its non-shared tail, so warm TTFT
    drops well below cold TTFT. Runs the real continuous-batching engine on
    the paged-KV backend (shared pages map by reference) on CPU — this is a
    mechanism check (cold vs warm ratio + hit rate), not a tok/s figure.

    Knobs: BENCH_PREFIX_LEN (system prompt tokens, default 1024),
    BENCH_PREFIX_REQS (requests, default 32), BENCH_PREFIX_TAIL (per-request
    unique tail tokens, default 16). Prints ONE JSON line."""
    import asyncio

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    import numpy as np  # noqa: F401

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

    sys_len = int(os.environ.get("BENCH_PREFIX_LEN", 1024))
    n_req = int(os.environ.get("BENCH_PREFIX_REQS", 32))
    tail_len = int(os.environ.get("BENCH_PREFIX_TAIL", 16))
    bundle = models.build_model("llama", {"preset": "llama-tiny", "dtype": "float32"})
    params = bundle.init(jax.random.PRNGKey(0))
    engine = LLMEngineCore(
        bundle, params,
        max_batch=4,
        max_seq_len=2048,
        prefill_buckets=[128, 256, 512, 1024, 1536, 2048],
        eos_token_id=None,
        decode_steps=2,
        cache_mode="paged",
        page_size=16,
        prefix_cache=4096,
        prefix_block=64,
    )

    def run_group(seed: int):
        """One cold + (n_req - 1) warm admissions of a fresh system prompt;
        returns per-request TTFT ms (sequential: TTFT must not include
        queueing behind another admission)."""
        system = [(i * 7 + seed) % 250 for i in range(sys_len)]

        async def one(idx: int) -> float:
            tail = [(idx * 13 + j * 3 + seed) % 250 for j in range(tail_len)]
            req = GenRequest(prompt_ids=system + tail, max_new_tokens=2)
            async for _ in engine.generate(req):
                pass
            return (req.first_token_at - req.submitted_at) * 1e3

        async def group():
            return [await one(i) for i in range(n_req)]

        return asyncio.run(group())

    # warmup group: compiles every trace both paths need (cold prefill
    # bucket, page gather, tail prefill_chunk) so the measured group times
    # execution, not XLA compilation
    run_group(seed=101)
    ttfts = run_group(seed=3)
    stats = engine._prefix.stats()
    engine.stop()
    cold = ttfts[0]
    warm = sorted(ttfts[1:])
    warm_p50 = warm[len(warm) // 2] if warm else 0.0
    hits = stats["hits"]
    misses = stats["misses"]
    line = {
        "metric": "llm_shared_prefix_ttft_cpusmoke",
        "value": round(warm_p50, 2),
        "unit": "ms",
        "platform": "cpu",
        "cold_ttft_ms": round(cold, 2),
        "warm_ttft_p50_ms": round(warm_p50, 2),
        "warm_ttft_max_ms": round(warm[-1], 2) if warm else 0.0,
        "cold_warm_speedup": round(cold / warm_p50, 2) if warm_p50 else 0.0,
        "cache_hit_rate": round(hits / (hits + misses), 4) if hits + misses else 0.0,
        "prefix_len": sys_len,
        "requests": n_req,
        # prefill compute actually performed (tokens through the model):
        # cold pays the whole prompt, warm only the non-shared tail window
        "prefill_tokens_cold": sys_len + tail_len,
        "prefill_tokens_warm": sys_len + tail_len - (
            stats["hit_tokens"] // max(1, hits)
        ),
        "note": "paged radix prefix cache; warm admissions prefill only the tail",
    }
    print(json.dumps(line))


def run_pipeline_ab(
    cfg: dict,
    *,
    batch: int = 4,
    decode_steps: int = 8,
    new_tokens: int = 96,
    prompt_len: int = 12,
    max_seq_len: int = 256,
    quantize=None,
    cache_mode: str = "dense",
) -> dict:
    """Pipelined-decode A/B on the REAL continuous-batching engine: the same
    workload at TPUSERVE_PIPELINE_DEPTH=1 (serial dispatch->sync->emit) vs 2
    (double-buffered chunk dispatch with device-resident token chaining,
    docs/pipelined_decode.md). Greedy, fixed prompts, eos disabled — the
    token streams must be byte-identical across depths; the step time is
    decode wall / dispatched chunks at steady state. Returns the result row
    (shared by the ``--pipeline-ab`` CPU scenario and the TPU battery)."""
    import asyncio

    import jax
    import numpy as np  # noqa: F401

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

    if quantize in ("int8", "int4"):
        from clearml_serving_tpu.ops.quant import random_quantized_llama

        bundle, params = random_quantized_llama(
            cfg, seed=0, bits=4 if quantize == "int4" else 8
        )
        quantize = None  # already applied to the tree
    else:
        bundle = models.build_model("llama", cfg)
        params = bundle.init(jax.random.PRNGKey(0))

    prompts = [
        [(7 * i + 3 + j) % 250 + 1 for j in range(prompt_len)]
        for i in range(batch)
    ]

    def measure(depth: int):
        engine = LLMEngineCore(
            bundle, params,
            max_batch=batch,
            max_seq_len=max_seq_len,
            prefill_buckets=[max(16, prompt_len)],
            eos_token_id=None,        # run to max_new_tokens: fixed work
            decode_steps=decode_steps,
            cache_mode=cache_mode,
            pipeline_depth=depth,
        )

        async def one(ids):
            req = GenRequest(
                prompt_ids=ids, max_new_tokens=new_tokens, temperature=0.0
            )
            return [t async for t in engine.generate(req)]

        async def group():
            outs = await asyncio.gather(*(one(p) for p in prompts))
            await engine.wait_drained()
            return outs

        # warmup: compile every trace (prefill bucket + decode chunk), then
        # measure a steady-state group. Step time divides by the DISPATCH
        # count actually issued (ragged admissions can add a partial chunk;
        # charging it to one depth only would skew the A/B).
        asyncio.run(group())
        seq0 = engine._dispatch_seq
        t0 = time.perf_counter()
        outs = asyncio.run(group())
        wall = time.perf_counter() - t0
        chunks = engine._dispatch_seq - seq0
        engine.stop()
        return outs, wall, max(1, chunks)

    outs1, wall1, chunks1 = measure(1)
    outs2, wall2, chunks2 = measure(2)
    toks = batch * new_tokens
    step1_ms = wall1 / chunks1 * 1e3
    step2_ms = wall2 / chunks2 * 1e3
    cpus = os.cpu_count() or 1
    return {
        "metric": "llm_pipelined_decode_ab",
        "value": round((1.0 - step2_ms / step1_ms) * 100.0, 2),
        "unit": "% step-time reduction (depth 2 vs 1)",
        "step_ms_depth1": round(step1_ms, 3),
        "step_ms_depth2": round(step2_ms, 3),
        "chunks_depth1": chunks1,
        "chunks_depth2": chunks2,
        "tok_s_depth1": round(toks / wall1, 2),
        "tok_s_depth2": round(toks / wall2, 2),
        "speedup": round(wall1 / wall2, 4),
        "identical_tokens": outs1 == outs2,
        # on mismatch: per-request (len1, len2, first-diff-index) triples —
        # enough to tell a lost/duplicated token from a value divergence
        "mismatch_detail": (
            None
            if outs1 == outs2
            else [
                (
                    len(a),
                    len(b),
                    next(
                        (i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                        min(len(a), len(b)),
                    ),
                )
                for a, b in zip(outs1, outs2)
                if a != b
            ]
        ),
        "batch": batch,
        "decode_steps": decode_steps,
        "new_tokens": new_tokens,
        "cache": cache_mode,
        "cpus": cpus,
        # pipelining hides chunk N's host-side retire (readback + emission)
        # behind chunk N+1's device compute. A single-core host has nothing
        # to hide behind — every cycle is already useful work — so the A/B
        # there measures pipeline overhead (~0), not the overlap win.
        "note": (
            "single-core host: overlap win not observable; expect >=10% "
            "only with >=2 cores or a real accelerator"
            if cpus == 1
            else "depths overlap retire host work with device compute"
        ),
    }


def run_spec_tree_ab(
    cfg: dict,
    *,
    spec_k: int = 4,
    spec_ngram: int = 1,
    spec_branch: int = 2,
    batch: int = 3,
    new_tokens: int = 64,
    step_token_budget: int = 20,
    max_seq_len: int = 256,
    cache_mode: str = "paged",
    page_size: int = 16,
) -> dict:
    """Draft-tree vs draft-chain verify rows at EQUAL verify budget
    (docs/spec_decode_trees.md, ISSUE 20): three arms of the same greedy
    workload on the ragged scheduler — no speculation, the n-gram CHAIN
    proposer at k, and the n-gram FOREST proposer at the same k with up
    to ``spec_branch`` root branches. Every verify row costs k+1 query
    positions in both spec arms; the forest only re-shapes WHICH drafts
    fill them. The headline is accepted decode tokens per ragged launch
    (ragged_decode_tokens / ragged_steps over the measured pass): the
    acceptance-rate gap closes exactly insofar as the tree arm commits
    more tokens from the same launch budget. Streams must be
    byte-identical across all three arms (greedy acceptance is
    exact-match; speculation may never change output).

    The workload is ambiguity-rich by construction: each prompt repeats
    an n-gram context with TWO distinct continuations, so the
    most-recent-match chain draft is sometimes wrong while an older
    match carries the answer — the regime the forest's depth-1 siblings
    exist for (``spec_ngram`` defaults to 1, where generated streams
    keep re-visiting ambiguous contexts). On unambiguous history the
    forest dedups to the chain drafts and the arms tie; do not expect a
    gap on a clean cycling tail."""
    import asyncio

    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

    bundle = models.build_model("llama", cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    # probed against llama-tiny's greedy continuations: each prompt's
    # generated stream re-visits single-token contexts with more than one
    # continuation in history (replaying both proposers offline over the
    # streams shows the forest strictly ahead), without collapsing into a
    # period-1 tail where both arms saturate and tie
    seeds = [
        [4, 288, 161, 312, 4, 288, 312, 161, 4, 288, 161, 312, 4, 288],
        [5, 9, 3, 11, 5, 9, 3, 11, 5, 9, 3, 11, 5, 9],
        [12, 4, 8, 21, 12, 4, 8, 21, 12, 4, 8, 21, 12, 4],
    ]
    prompts = [list(seeds[i % len(seeds)]) for i in range(batch)]

    arm_kwargs = {
        "none": {},
        "chain": dict(speculation="ngram", spec_k=spec_k,
                      spec_ngram=spec_ngram),
        "tree": dict(speculation="ngram", spec_k=spec_k,
                     spec_ngram=spec_ngram, spec_tree=True,
                     spec_branch=spec_branch),
    }

    def measure(mode: str):
        from clearml_serving_tpu.llm import compile_sentry

        if compile_sentry.enabled():
            # the sentry is process-wide: drop the previous arm's fence so
            # this arm's warmup compiles count as warmup, not serving
            compile_sentry.get().reset()
        engine = LLMEngineCore(
            bundle, params,
            max_batch=batch, max_seq_len=max_seq_len, prefill_buckets=[16],
            eos_token_id=None, decode_steps=4, scheduler="ragged",
            step_token_budget=step_token_budget,
            cache_mode=cache_mode, page_size=page_size, **arm_kwargs[mode],
        )

        async def group():
            async def one(ids):
                req = GenRequest(
                    prompt_ids=list(ids), max_new_tokens=new_tokens,
                    temperature=0.0,
                )
                return [t async for t in engine.generate(req)]

            outs = await asyncio.gather(*(one(p) for p in prompts))
            await engine.wait_drained()
            return outs

        async def warm():
            # registry sweep (pins the per-arm compile surface, including
            # the tree-arg kernel variant for the tree arm); the fence is
            # set manually AFTER the trace pass below, so the MEASURED
            # pass is what the strict sentry certifies compile-free
            from clearml_serving_tpu.llm.warmup import run_warmup

            return await run_warmup(engine, full=True, fence=False)

        asyncio.run(warm())
        asyncio.run(group())            # warmup pass: compiles every trace
        if engine._compile_sentry is not None:
            engine._compile_sentry.fence()
        base = dict(engine.counters)
        t0 = time.perf_counter()
        outs = asyncio.run(group())
        wall = time.perf_counter() - t0
        launches = engine.counters["ragged_steps"] - base["ragged_steps"]
        dec_tokens = (
            engine.counters["ragged_decode_tokens"]
            - base["ragged_decode_tokens"]
        )
        s = engine.lifecycle_stats()["ragged"]
        row = {
            "outs": outs,
            "tok_s": round(sum(len(o) for o in outs) / wall, 2),
            "ragged_launches": launches,
            "ragged_decode_tokens": dec_tokens,
        }
        if mode != "none":
            # pure-decode steps in the no-spec arm bypass the ragged
            # mixed-launch path, so per-launch accounting only compares
            # the two spec arms (whose verify rows always ride launches)
            row["accepted_tokens_per_launch"] = round(
                dec_tokens / max(1, launches), 3
            )
            row["dispatches_per_decode_token"] = round(
                launches / max(1, dec_tokens), 3
            )
            row["spec_verify_rows"] = s["step_rows"]["spec_verify"]
            snap = s["spec_acceptance"]
            row["acceptance_mean"] = round(
                snap["sum_ms"] / max(1, snap["count"]), 3
            )
            prop = s["spec_proposer"]
            row["proposer"] = {
                k: prop[k] for k in ("name", "proposed", "hit", "branched")
                if k in prop
            }
        if mode == "tree":
            snap = s["spec_tree_depth"]
            row["accept_depth_mean"] = round(
                snap["sum_ms"] / max(1, snap["count"]), 3
            )
            row["tree_fallbacks"] = s["spec_tree_fallbacks"]
        # per-arm certification (the slo_loadtest pattern): the sanitizer
        # is per-engine; the compile sentry is process-wide but reset at
        # the top of the arm, so "serve" counts exactly the compiles the
        # measured pass triggered past this arm's fence. In strict mode a
        # violation raises mid-run — completing at all is the certificate.
        sanitizer = engine._sanitizer
        san = (
            sanitizer.stats() if sanitizer is not None
            else {"checks": 0, "failures": -1}
        )
        sentry = engine._compile_sentry
        sen = (
            sentry.stats_brief() if sentry is not None
            else {"mode": "off", "serve": -1, "fenced": False}
        )
        row["certs"] = {
            "sanitizer_checks": san.get("checks", 0),
            "sanitizer_violations": san.get("failures", 0),
            "post_warmup_compiles": sen.get("serve", -1),
            "compile_sentry_mode": sen.get("mode", "off"),
        }
        engine.stop()
        return row

    none = measure("none")
    chain = measure("chain")
    tree = measure("tree")
    identical = (
        none.pop("outs") == chain.pop("outs") == tree.pop("outs")
    )
    # process-wide sentries (ownership ledger, sharding sentry) read ONCE
    # after all three arms — their counts span the whole run, and strict
    # mode already failed the run on the first violation
    from clearml_serving_tpu.llm import lifecycle_ledger, sharding_sentry

    ledger = lifecycle_ledger.arm() if lifecycle_ledger.enabled() else None
    led = (
        ledger.stats() if ledger is not None
        else {"strict": False, "leaks": -1, "double_releases": -1}
    )
    shard = sharding_sentry.arm() if sharding_sentry.enabled() else None
    shd = (
        shard.stats_brief() if shard is not None
        else {"strict": False, "implicit_transfers": -1,
              "unplanned_reshards": -1}
    )
    arm_certs = [none["certs"], chain["certs"], tree["certs"]]
    certs = {
        "sanitizer_checks": sum(c["sanitizer_checks"] for c in arm_certs),
        "sanitizer_violations": (
            -1 if any(c["sanitizer_violations"] < 0 for c in arm_certs)
            else sum(c["sanitizer_violations"] for c in arm_certs)
        ),
        "post_warmup_compiles": (
            -1 if any(c["post_warmup_compiles"] < 0 for c in arm_certs)
            else sum(c["post_warmup_compiles"] for c in arm_certs)
        ),
        "compile_sentry_mode": arm_certs[0]["compile_sentry_mode"],
        "leaks": (
            led.get("leaks", -1) + led.get("double_releases", 0)
            if led.get("leaks", -1) >= 0 else -1
        ),
        "ledger_mode": (
            "strict" if led.get("strict")
            else ("count" if ledger is not None else "off")
        ),
        "implicit_transfers": shd.get("implicit_transfers", -1),
        "unplanned_reshards": shd.get("unplanned_reshards", -1),
        "shard_sentry_mode": (
            "strict" if shd.get("strict")
            else ("count" if shard is not None else "off")
        ),
    }
    return {
        "metric": "llm_spec_tree_ab",
        # headline: the acceptance-gap close — extra committed tokens per
        # launch the tree buys from the SAME k+1 verify budget
        "value": round(
            tree["accepted_tokens_per_launch"]
            - chain["accepted_tokens_per_launch"], 3
        ),
        "unit": "accepted decode tokens per ragged launch, tree minus "
                "chain at equal k+1 verify budget",
        "no_spec": none,
        "chain": chain,
        "tree": tree,
        "identical_tokens": identical,
        "certs": certs,
        "spec_k": spec_k,
        "spec_branch": spec_branch,
        "batch": batch,
        "cache": cache_mode,
        "cpus": os.cpu_count() or 1,
    }


def run_kv_tier_ab(
    cfg: dict,
    *,
    n_prefixes: int = 3,
    prefix_len: int = 768,
    tail_len: int = 12,
    new_tokens: int = 8,
    decode_tokens: int = 48,
    page_size: int = 16,
    prefix_block: int = 16,
    device_cache_pages: int = 48,
    host_pages: int = 160,
    max_seq_len: int = 832,
    num_pages: int = 192,
) -> dict:
    """Host-RAM KV tiering A/B on the real engine (docs/kv_tiering.md):
    a constrained-HBM trace whose shared-prefix WORKING SET exceeds the
    device-side prefix-cache budget. Two engines differ only in the host
    tier: the tiered arm demotes evicted runs to pinned host RAM and
    re-onlines them on a hit via async DMA; the untiered arm drops them
    (the pre-tier behavior) and every revisit of an evicted prefix pays a
    cold prefill.

    Reports warm-TTFT by serving tier {hbm hit, host hit, cold}, the
    promotion DMA overlap ratio (share of the copy hidden behind other
    device work, observed at retire reaps), tok/s of a decode stream
    running CONCURRENTLY with the warm sweep, and stream byte-identity: a
    demoted-then-promoted run must produce the same tokens as the
    always-resident warm hit, under the armed KV sanitizer."""
    import asyncio

    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

    os.environ.setdefault("TPUSERVE_SANITIZE", "1")
    bundle = models.build_model("llama", cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    prompts = [
        [(13 * i + 5 * j) % 250 + 1 for j in range(prefix_len + tail_len)]
        for i in range(n_prefixes)
    ]
    decode_prompt = [(17 * j + 11) % 250 + 1 for j in range(tail_len)]
    blocks_per_prompt = (prefix_len + tail_len - 1) // prefix_block
    working_set_pages = n_prefixes * blocks_per_prompt * (
        prefix_block // page_size
    )

    def measure(tiered: bool):
        engine = LLMEngineCore(
            bundle, params,
            max_batch=2,
            max_seq_len=max_seq_len,
            # the cold bucket covers the whole prompt; the warm tail rides
            # the small chunk bucket (prefix + one chunk must fit too)
            prefill_buckets=[16, 32, 64, prefix_len + 2 * prefix_block],
            eos_token_id=None,
            decode_steps=2,
            cache_mode="paged",
            page_size=page_size,
            num_pages=num_pages,
            prefix_cache=256,
            prefix_block=prefix_block,
            prefix_cache_pages=device_cache_pages,
            prefix_cache_host_pages=host_pages if tiered else None,
        )

        async def one(ids, n, stamps=None):
            req = GenRequest(
                prompt_ids=list(ids), max_new_tokens=n, temperature=0.0
            )
            out, t0 = [], time.perf_counter()
            async for tok in engine.generate(req):
                if stamps is not None:
                    stamps.append(time.perf_counter())
                elif not out:
                    stamps_first[0] = time.perf_counter() - t0
                out.append(tok)
            return out

        stamps_first = [0.0]

        async def one_drained(ids, n):
            out = await one(ids, n)
            # each sequential request runs in its own asyncio.run: the
            # engine loop must drain before that event loop closes
            await engine.wait_drained()
            return out

        def timed(ids, n=new_tokens):
            """(stream, ttft_s, tier) — tier classified from the cache's
            hit counters around the request."""
            s0 = engine._prefix.stats()
            stream = asyncio.run(one_drained(ids, n))
            s1 = engine._prefix.stats()
            if s1["hits_by_tier"]["host"] > s0["hits_by_tier"]["host"]:
                tier = "host"
            elif s1["hits_by_tier"]["hbm"] > s0["hits_by_tier"]["hbm"]:
                tier = "hbm"
            else:
                tier = "cold"
            return stream, stamps_first[0], tier

        # warmup: compile every shape off the clock (prefill buckets, the
        # radix-hit gather + tail chunk, decode chunk, promotion scatter)
        warm_ids = [(3 * j + 7) % 250 + 1 for j in range(prefix_len + tail_len)]
        timed(warm_ids)
        timed(warm_ids)
        if tiered:
            engine._prefix.spill(0)
            timed(warm_ids)  # host-hit shapes (promotion scatter) compile
        # cold pass: working set exceeds the device budget, so the tiered
        # arm demotes older runs as it goes and the untiered arm drops them
        cold_ttfts, cold_streams = [], []
        for ids in prompts:
            stream, ttft, _tier = timed(ids)
            cold_ttfts.append(ttft)
            cold_streams.append(stream)
        # byte-identity pair on the LAST prefix (still resident): resident
        # warm hit vs demoted-then-promoted warm hit
        resident_stream, resident_ttft, resident_tier = timed(prompts[-1])
        identical = True
        if tiered:
            engine._prefix.spill(0)
            promoted_stream, _t, promoted_tier = timed(prompts[-1])
            identical = (
                promoted_stream == resident_stream
                and promoted_tier == "host"
                and resident_tier == "hbm"
            )
        # warm sweep over the whole working set with a CONCURRENT decode
        # stream (does the promotion DMA steal from live decodes?)
        sweep: dict = {"ttft": {"hbm": [], "host": [], "cold": []},
                       "hits": {"hbm": 0, "host": 0, "cold": 0}}
        decode_stamps: list = []

        async def sweep_group():
            decode_task = asyncio.create_task(
                one(decode_prompt, decode_tokens, stamps=decode_stamps)
            )
            while len(decode_stamps) < 2:
                await asyncio.sleep(0.002)
            for ids in prompts:
                s0 = engine._prefix.stats()
                t0 = time.perf_counter()
                req = GenRequest(
                    prompt_ids=list(ids), max_new_tokens=new_tokens,
                    temperature=0.0,
                )
                first = None
                async for _tok in engine.generate(req):
                    if first is None:
                        first = time.perf_counter() - t0
                s1 = engine._prefix.stats()
                if s1["hits_by_tier"]["host"] > s0["hits_by_tier"]["host"]:
                    tier = "host"
                elif s1["hits_by_tier"]["hbm"] > s0["hits_by_tier"]["hbm"]:
                    tier = "hbm"
                else:
                    tier = "cold"
                sweep["ttft"][tier].append(first)
                sweep["hits"][tier] += 1
            await decode_task
            await engine.wait_drained()

        asyncio.run(sweep_group())
        decode_tok_s = (
            (len(decode_stamps) - 1)
            / max(1e-9, decode_stamps[-1] - decode_stamps[0])
        )
        tier_stats = (engine.lifecycle_stats() or {}).get("kv_tier") or {}
        sanitizer = (
            engine._sanitizer.stats()
            if engine._sanitizer is not None
            else {"checks": 0, "failures": 0}
        )
        engine.stop()

        def med(xs):
            xs = sorted(xs)
            return round(xs[len(xs) // 2] * 1e3, 3) if xs else None

        return {
            "cold_streams": cold_streams,
            "identical": identical,
            "ttft_ms": {
                "cold": med(cold_ttfts),
                "hbm": med(sweep["ttft"]["hbm"]),
                "host": med(sweep["ttft"]["host"]),
                "warm_cold": med(sweep["ttft"]["cold"]),
            },
            "warm_hits": dict(sweep["hits"]),
            "decode_tok_s": round(decode_tok_s, 2),
            "promo_overlap_ratio": tier_stats.get("promo_overlap_ratio"),
            "demotions": tier_stats.get("demotions", 0),
            "promotions": tier_stats.get("promotions", 0),
            "sanitizer_checks": sanitizer["checks"],
            "sanitizer_violations": sanitizer["failures"],
        }

    tiered = measure(True)
    untiered = measure(False)
    identical = (
        tiered.pop("identical")
        and tiered["cold_streams"] == untiered["cold_streams"]
    )
    untiered.pop("identical", None)
    tiered.pop("cold_streams")
    untiered.pop("cold_streams")
    cold = tiered["ttft_ms"]["cold"]
    host = tiered["ttft_ms"]["host"]
    return {
        "metric": "llm_kv_tier_ab",
        # headline: how many cold-prefill TTFTs one host-tier warm hit saves
        "value": round(cold / host, 2) if (cold and host) else None,
        "unit": "x cold-prefill TTFT over host-tier warm TTFT",
        "tiered": tiered,
        "untiered": untiered,
        "identical_streams": identical,
        "n_prefixes": n_prefixes,
        "prefix_len": prefix_len,
        "working_set_pages": working_set_pages,
        "device_cache_pages": device_cache_pages,
        "host_pages": host_pages,
        "page_size": page_size,
        "cpus": os.cpu_count() or 1,
        "note": (
            "working set > device prefix-cache budget by construction: the "
            "untiered arm re-prefills evicted prefixes cold; the tiered arm "
            "serves them from host RAM with the promotion DMA overlapped "
            "with the tail prefill (overlap observed at retire reaps)"
        ),
    }


def run_paged_quant_ab(
    cfg: dict,
    *,
    batch: int = 4,
    decode_steps: int = 8,
    new_tokens: int = 64,
    prompt_len: int = 24,
    max_seq_len: int = 256,
    quantize=None,
    drift_steps: int = 6,
    page_size: int = 32,
) -> dict:
    """bf16-vs-int8 PAGED KV A/B on the real continuous-batching engine
    (docs/paged_kv_quant.md): the same greedy workload on two engines that
    differ ONLY in ``kv_quant`` — identical weights, page budget, and page
    size. Reports steady-state step ms, tok/s, pool bytes by kind (the
    capacity win: >= 1.8x total-pool reduction expected at D >= 64), and
    the max logit drift between the two KV representations measured on the
    raw paged decode path. On CPU the Pallas int8 kernel additionally runs
    in interpret=True mode against the XLA int8 reference (parity
    maxdiff)."""
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

    base_cfg = {k: v for k, v in cfg.items() if k != "kv_quant"}
    if quantize in ("int8", "int4"):
        from clearml_serving_tpu.ops.quant import random_quantized_llama

        # random_quantized_llama builds the tree with scan_layers=True
        # (stacked [L, ...] layers); the A/B bundles must match that layout
        base_cfg = dict(base_cfg, scan_layers=True)
        _, params = random_quantized_llama(
            base_cfg, seed=0, bits=4 if quantize == "int4" else 8
        )
    else:
        params = models.build_model("llama", base_cfg).init(
            jax.random.PRNGKey(0)
        )
    bundles = {
        "bf16": models.build_model("llama", base_cfg),
        "int8": models.build_model("llama", dict(base_cfg, kv_quant="int8")),
    }
    prompts = [
        [(7 * i + 3 + j) % 250 + 1 for j in range(prompt_len)]
        for i in range(batch)
    ]

    def measure(bundle):
        engine = LLMEngineCore(
            bundle, params,
            max_batch=batch,
            max_seq_len=max_seq_len,
            prefill_buckets=[max(16, prompt_len)],
            eos_token_id=None,
            decode_steps=decode_steps,
            cache_mode="paged",
            # default 32-token pages: the int8 Pallas path needs
            # page_size % 32 == 0 on TPU (docs/paged_kv_quant.md), and the
            # A/B must compare both representations on the SAME layout
            page_size=page_size,
        )

        async def one(ids):
            req = GenRequest(
                prompt_ids=ids, max_new_tokens=new_tokens, temperature=0.0
            )
            return [t async for t in engine.generate(req)]

        async def group():
            outs = await asyncio.gather(*(one(p) for p in prompts))
            await engine.wait_drained()
            return outs

        asyncio.run(group())  # warmup: compile prefill + decode chunk
        # best-of-N timed groups: single-group walls on a shared CPU jitter
        # by 20%+, which would dominate the A/B delta being measured
        wall, chunks, outs = None, 1, None
        for _ in range(3):
            seq0 = engine._dispatch_seq
            t0 = time.perf_counter()
            outs = asyncio.run(group())
            w = time.perf_counter() - t0
            c = max(1, engine._dispatch_seq - seq0)
            if wall is None or w / c < wall / chunks:
                wall, chunks = w, c
        pool_bytes = engine.paged_cache.pool_bytes()
        dtype = engine.paged_cache.pool_dtype
        pages = engine.paged_cache.pool.num_pages
        engine.stop()
        return outs, wall, chunks, pool_bytes, dtype, pages

    def max_logit_drift():
        """Raw paged decode path, greedy, both KV representations over the
        SAME token sequence: max |logits_bf16 - logits_int8| across steps
        (accuracy note for the docs; per-vector int8 is ~0.4% RMS)."""
        from clearml_serving_tpu.llm.kv_cache import PagedKVCache

        ids = prompts[0]
        tokens = jnp.asarray([ids], jnp.int32)
        lens = jnp.asarray([len(ids)], jnp.int32)
        caches, state = {}, {}
        for name, bundle in bundles.items():
            mini = bundle.init_cache(1, max(32, prompt_len + drift_steps))
            logits, mini = bundle.prefill(params, tokens, lens, mini)
            cache = PagedKVCache(
                bundle.n_layers, bundle.n_kv_heads, bundle.head_dim,
                num_pages=64, page_size=page_size, max_slots=1,
                dtype=base_cfg.get("dtype", "bfloat16"),
                kv_quant="int8" if name == "int8" else "",
            )
            # the prompt's K/V (and scales) into the slot's pages
            cache.pool.allocate(0, len(ids))
            pg, off = (
                jnp.asarray(c)
                for c in zip(*cache.pool.token_coords(0, 0, len(ids)))
            )
            for buf in ("k", "v") + (
                ("k_scale", "v_scale") if name == "int8" else ()
            ):
                rows = jnp.moveaxis(mini[buf][:, 0, : len(ids)], 1, 2)
                setattr(cache, buf,
                        getattr(cache, buf).at[:, :, pg, off].set(rows))
            caches[name] = cache
            state[name] = (jnp.argmax(logits, -1).astype(jnp.int32), logits)
        drift = float(
            jnp.max(jnp.abs(state["bf16"][1] - state["int8"][1]))
        )
        # chain the bf16 greedy tokens through BOTH paths so drift isolates
        # the KV representation, not diverging token histories
        nxt = state["bf16"][0]
        length = len(ids)
        for _ in range(drift_steps):
            step_logits = {}
            for name, bundle in bundles.items():
                cache = caches[name]
                cache.pool.extend(0, 1)
                ((wp, wo),) = cache.pool.token_coords(0, length, 1)
                table = jnp.asarray(cache.pool.page_table(64))
                args = (
                    params, nxt, cache.k, cache.v, table,
                    jnp.asarray([length], jnp.int32),
                    jnp.asarray([wp], jnp.int32), jnp.asarray([wo], jnp.int32),
                )
                if name == "int8":
                    out = bundle.decode_paged(
                        *args, k_scales=cache.k_scale, v_scales=cache.v_scale
                    )
                    cache.k, cache.v = out[1], out[2]
                    cache.k_scale, cache.v_scale = out[3], out[4]
                else:
                    out = bundle.decode_paged(*args)
                    cache.k, cache.v = out[1], out[2]
                step_logits[name] = out[0]
            drift = max(
                drift,
                float(jnp.max(jnp.abs(step_logits["bf16"] - step_logits["int8"]))),
            )
            length += 1
            nxt = jnp.argmax(step_logits["bf16"], -1).astype(jnp.int32)
        return drift

    outs_b, wall_b, chunks_b, bytes_b, dtype_b, pages_b = measure(bundles["bf16"])
    outs_q, wall_q, chunks_q, bytes_q, dtype_q, pages_q = measure(bundles["int8"])
    step_b = wall_b / chunks_b * 1e3
    step_q = wall_q / chunks_q * 1e3
    toks = batch * new_tokens
    total_b = bytes_b["kv"] + bytes_b["scale"]
    total_q = bytes_q["kv"] + bytes_q["scale"]
    row = {
        "metric": "llm_paged_kv_quant_ab",
        "value": round(total_b / total_q, 4),
        "unit": "x pool-bytes reduction (bf16 -> int8+scales)",
        "pool_bytes_bf16": total_b,
        "pool_bytes_int8": bytes_q["kv"],
        "pool_bytes_int8_scales": bytes_q["scale"],
        "pool_dtype": [dtype_b, dtype_q],
        "num_pages": pages_q,
        "equal_page_budget": pages_b == pages_q,
        "step_ms_bf16": round(step_b, 3),
        "step_ms_int8": round(step_q, 3),
        "step_time_ratio": round(step_q / step_b, 4),
        "tok_s_bf16": round(toks / wall_b, 2),
        "tok_s_int8": round(toks / wall_q, 2),
        "max_logit_drift": round(max_logit_drift(), 5),
        "identical_greedy_streams": outs_b == outs_q,
        "batch": batch,
        "decode_steps": decode_steps,
        "new_tokens": new_tokens,
        "note": (
            "int8 paged pools halve KV DMA bytes + pool HBM; streams may "
            "differ from bf16 by bounded quantization noise (drift above)"
        ),
    }
    import jax as _jax

    if _jax.devices()[0].platform != "tpu":
        # CPU smoke: exercise the Pallas int8 kernel in interpret mode
        # against the XLA int8 reference (the hardware path's parity gate)
        from clearml_serving_tpu.ops.paged_attention import (
            paged_attention, paged_attention_xla,
        )

        rng = np.random.default_rng(0)
        hkv, g, d, n, p, pp = 2, 2, 128, 9, 16, 4
        q = jnp.asarray(rng.normal(size=(2, hkv, g, d)).astype(np.float32))
        kf = rng.normal(size=(hkv, n, p, d)).astype(np.float32)
        absmax = np.abs(kf).max(-1)
        scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
        k8 = jnp.asarray(
            np.clip(np.round(kf / scale[..., None]), -127, 127).astype(np.int8)
        )
        ks = jnp.asarray(scale)
        table = jnp.asarray(
            rng.choice(np.arange(1, n), size=(2, pp), replace=False
                       ).astype(np.int32)
        )
        lengths = jnp.asarray([37, 64], jnp.int32)
        ref = paged_attention_xla(q, k8, k8, table, lengths, ks, ks)
        out = paged_attention(
            q, k8, k8, table, lengths, k_scale=ks, v_scale=ks, interpret=True
        )
        row["pallas_interpret_maxdiff"] = float(jnp.max(jnp.abs(ref - out)))
    return row


def _quantized_tree_bytes(params) -> dict:
    """Weight-tree byte accounting for the int4 A/B: total tree bytes, the
    bytes of the QUANTIZED projection leaves (values + scales — the subset
    the roofline's weight-read term streams every decode step; embeddings/
    norms stay full precision in every arm and would dilute the ratio), and
    the dense bf16-equivalent of that subset."""
    import jax

    def walk(tree, acc):
        if isinstance(tree, dict):
            if "_q4" in tree:
                acc["quant"] += tree["_q4"].nbytes + tree["_scale4"].nbytes
                # packed uint8 [K//2, N] -> bf16 [K, N] is 4x the bytes
                acc["dense_equiv"] += tree["_q4"].nbytes * 4
                return
            if "_q8" in tree:
                acc["quant"] += tree["_q8"].nbytes + tree["_scale"].nbytes
                acc["dense_equiv"] += tree["_q8"].nbytes * 2
                return
            for value in tree.values():
                walk(value, acc)
            return
        if isinstance(tree, (list, tuple)):
            for value in tree:
                walk(value, acc)

    acc = {"quant": 0, "dense_equiv": 0}
    walk(params, acc)
    total = int(sum(
        leaf.nbytes for leaf in jax.tree.leaves(params)
        if hasattr(leaf, "nbytes")
    ))
    return {"tree": total, "quant_leaves": int(acc["quant"]),
            "dense_equiv": int(acc["dense_equiv"])}


def run_int4_ab(
    cfg: dict,
    *,
    batch: int = 4,
    decode_steps: int = 8,
    new_tokens: int = 64,
    prompt_len: int = 24,
    max_seq_len: int = 256,
    from_bf16: bool = True,
    drift_steps: int = 6,
) -> dict:
    """w4a16 A/B on the real continuous-batching engine (docs/w4a16.md):
    the same greedy workload on three engines that differ ONLY in the
    weight tree / matmul route —

      int4_fused  packed int4, decode matmuls through the Pallas fused
                  dequant-matmul (ops/fused_matmul.py; the production path)
      int4_xla    the same packed int4 tree with cfg int4_fused=False
                  (XLA inline-dequant reference route)
      int8        per-channel int8 (the PR-5-era weight format)

    Reports best-of-3 steady-state step ms + tok/s per arm, weight-tree
    bytes (tree / quantized-leaf / dense-equivalent — the HBM weight-read
    term), fused-vs-XLA stream byte-identity, max logit drift of int4 vs
    int8 on the raw decode path (``from_bf16`` arms quantize ONE shared
    bf16 init so the drift isolates the weight format; random trees skip
    it), and — off-TPU — the fused kernel's interpret-mode parity maxdiff
    against the XLA reference."""
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
    from clearml_serving_tpu.ops.quant import (
        quantize_llama_params, random_quantized_llama,
    )

    base_cfg = {k: v for k, v in cfg.items() if k != "int4_fused"}
    base_cfg["scan_layers"] = True
    if from_bf16:
        p_bf16 = models.build_model("llama", base_cfg).init(
            jax.random.PRNGKey(0)
        )
        params4 = quantize_llama_params(p_bf16, bits=4)
        params8 = quantize_llama_params(p_bf16, bits=8)
    else:
        # 8B-scale: quantized trees built directly; full precision never
        # materializes (drift vs int8 is skipped — unrelated random trees)
        _, params4 = random_quantized_llama(base_cfg, seed=0, bits=4)
        _, params8 = random_quantized_llama(base_cfg, seed=0, bits=8)
    bundle_fused = models.build_model("llama", base_cfg)
    bundle_xla = models.build_model(
        "llama", dict(base_cfg, int4_fused=False)
    )
    arms = (
        ("int4_fused", bundle_fused, params4),
        ("int4_xla", bundle_xla, params4),
        ("int8", bundle_fused, params8),
    )
    prompts = [
        [(7 * i + 3 + j) % 250 + 1 for j in range(prompt_len)]
        for i in range(batch)
    ]

    def measure(bundle, params):
        engine = LLMEngineCore(
            bundle, params,
            max_batch=batch,
            max_seq_len=max_seq_len,
            prefill_buckets=[max(16, prompt_len)],
            eos_token_id=None,
            decode_steps=decode_steps,
        )

        async def one(ids):
            req = GenRequest(
                prompt_ids=ids, max_new_tokens=new_tokens, temperature=0.0
            )
            return [t async for t in engine.generate(req)]

        async def group():
            outs = await asyncio.gather(*(one(p) for p in prompts))
            await engine.wait_drained()
            return outs

        asyncio.run(group())  # warmup: compile prefill + decode chunk
        # best-of-N timed groups (shared-CPU wall jitter would drown the
        # delta; same protocol as run_paged_quant_ab)
        wall, chunks, outs = None, 1, None
        for _ in range(3):
            seq0 = engine._dispatch_seq
            t0 = time.perf_counter()
            outs = asyncio.run(group())
            w = time.perf_counter() - t0
            c = max(1, engine._dispatch_seq - seq0)
            if wall is None or w / c < wall / chunks:
                wall, chunks = w, c
        engine.stop()
        return outs, wall, chunks

    def max_logit_drift():
        """Raw dense decode, int4 vs int8 trees quantized from the SAME
        bf16 init, chained on the int8 arm's greedy tokens — the drift
        isolates the weight format, not diverging histories."""
        ids = prompts[0]
        tokens = jnp.asarray([ids], jnp.int32)
        lens = jnp.asarray([len(ids)], jnp.int32)
        caches, logits = {}, {}
        for name, p in (("int4", params4), ("int8", params8)):
            lg, caches[name] = bundle_fused.prefill(
                p, tokens, lens,
                bundle_fused.init_cache(1, prompt_len + drift_steps + 8),
            )
            logits[name] = lg
        drift = float(jnp.max(jnp.abs(logits["int4"] - logits["int8"])))
        nxt = jnp.argmax(logits["int8"], -1).astype(jnp.int32)
        for _ in range(drift_steps):
            step = {}
            for name, p in (("int4", params4), ("int8", params8)):
                step[name], caches[name] = bundle_fused.decode(
                    p, nxt, caches[name]
                )
            drift = max(
                drift,
                float(jnp.max(jnp.abs(step["int4"] - step["int8"]))),
            )
            nxt = jnp.argmax(step["int8"], -1).astype(jnp.int32)
        return drift

    results = {}
    for name, bundle, params in arms:
        outs, wall, chunks = measure(bundle, params)
        results[name] = {
            "outs": outs,
            "step_ms": wall / chunks * 1e3,
            "tok_s": batch * new_tokens / wall,
        }
    bytes4 = _quantized_tree_bytes(params4)
    bytes8 = _quantized_tree_bytes(params8)
    toks = batch * new_tokens
    row = {
        "metric": "llm_int4_weight_ab",
        "value": round(
            results["int4_xla"]["step_ms"] / results["int4_fused"]["step_ms"],
            4,
        ),
        "unit": "x step-time speedup (xla-dequant -> fused kernel)",
        "step_ms": {
            name: round(results[name]["step_ms"], 3) for name in results
        },
        "tok_s": {name: round(results[name]["tok_s"], 2) for name in results},
        "weight_bytes_int4": bytes4,
        "weight_bytes_int8": bytes8,
        "int4_vs_int8_quant_bytes": round(
            bytes4["quant_leaves"] / bytes8["quant_leaves"], 4
        ),
        "int4_vs_bf16_quant_bytes": round(
            bytes4["quant_leaves"] / bytes4["dense_equiv"], 4
        ),
        "identical_streams_fused_vs_xla": (
            results["int4_fused"]["outs"] == results["int4_xla"]["outs"]
        ),
        "batch": batch,
        "decode_steps": decode_steps,
        "new_tokens": new_tokens,
        "tokens_per_group": toks,
        "note": (
            "int4 group-quantized weights quarter the HBM weight-read "
            "term; the fused kernel makes the 4-bit read structural "
            "(docs/w4a16.md)"
        ),
    }
    if from_bf16:
        row["max_logit_drift_int4_vs_int8"] = round(max_logit_drift(), 5)
    if jax.devices()[0].platform != "tpu":
        # CPU smoke: the fused kernel itself in interpret mode against the
        # XLA dequant reference (the hardware path's parity gate), over a
        # few alignment-representative shapes
        from clearml_serving_tpu.ops.fused_matmul import (
            fused_int4_matmul, int4_matmul_xla,
        )
        from clearml_serving_tpu.ops.quant import quantize_int4

        rng = np.random.default_rng(0)
        maxdiff = 0.0
        for m, k, n, group in (
            (2, 128, 128, 128), (4, 256, 256, 128), (3, 256, 384, 64),
            (8, 512, 256, 128),
        ):
            w = jnp.asarray(
                (rng.normal(size=(k, n)) * k ** -0.5).astype(np.float32)
            )
            x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
            q, s = quantize_int4(w, group=group)
            ref = int4_matmul_xla(x, q, s, jnp.float32)
            out = fused_int4_matmul(x, q, s, interpret=True)
            maxdiff = max(maxdiff, float(jnp.max(jnp.abs(ref - out))))
        row["pallas_interpret_maxdiff"] = maxdiff
    return row


def _int4_ab_smoke() -> None:
    """CPU smoke for ``--int4-ab`` (acceptance: int4 quantized-leaf bytes
    ~0.5x int8 / ~0.25x bf16-equivalent, fused-vs-XLA streams byte-identical
    — on CPU the wrapper routes to the identical XLA expression by
    construction — and interpret-mode kernel parity <= 1e-5). Runs on a
    widened llama-tiny (dim 256 -> K spans one, two, and four 128-row scale
    groups across the projection shapes). Updates benchmarks/INT4_AB_cpu.json.
    Knobs: BENCH_I4_BATCH / BENCH_I4_STEPS / BENCH_I4_TOKENS."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    row = run_int4_ab(
        {"preset": "llama-tiny", "dtype": "bfloat16", "dim": 256,
         "n_heads": 4, "n_kv_heads": 2, "ffn_dim": 512},
        batch=int(os.environ.get("BENCH_I4_BATCH", 2)),
        decode_steps=int(os.environ.get("BENCH_I4_STEPS", 4)),
        new_tokens=int(os.environ.get("BENCH_I4_TOKENS", 24)),
        prompt_len=12,
        max_seq_len=128,
    )
    row["metric"] += "_cpusmoke"
    row["platform"] = "cpu"
    artifact = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks",
        "INT4_AB_cpu.json",
    )
    with open(artifact, "w") as f:
        json.dump(row, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(row))


def _spec_tree_ab_smoke() -> None:
    """CPU smoke for ``--spec-tree-ab`` (acceptance: byte-identical greedy
    streams across the no-spec / chain / tree arms, and the tree arm's
    accepted-tokens-per-launch STRICTLY above the chain arm at the same
    k+1 verify budget — the ISSUE-20 headline). Updates
    benchmarks/SPEC_TREE_AB_cpu.json (asserted by tier-1). Knobs:
    BENCH_SPEC_TREE_K / BENCH_SPEC_TREE_BRANCH / BENCH_SPEC_TREE_BATCH /
    BENCH_SPEC_TREE_TOKENS / BENCH_SPEC_TREE_BUDGET."""
    # strict-sentry certification (the slo_loadtest pattern, forced not
    # defaulted): the committed artifact's certs block claims 0 sanitizer
    # violations / ledger leaks / post-warmup compiles / implicit
    # transfers, and strict mode FAILS the run on any of them — so the
    # artifact existing at all is the proof
    os.environ["TPUSERVE_SANITIZE"] = "1"
    os.environ["TPUSERVE_COMPILE_SENTRY"] = "strict"
    os.environ["TPUSERVE_LEDGER"] = "strict"
    os.environ["TPUSERVE_SHARD_SENTRY"] = "strict"
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    row = run_spec_tree_ab(
        {"preset": "llama-tiny", "dtype": "float32"},
        spec_k=int(os.environ.get("BENCH_SPEC_TREE_K", 4)),
        spec_branch=int(os.environ.get("BENCH_SPEC_TREE_BRANCH", 2)),
        batch=int(os.environ.get("BENCH_SPEC_TREE_BATCH", 3)),
        new_tokens=int(os.environ.get("BENCH_SPEC_TREE_TOKENS", 64)),
        step_token_budget=int(os.environ.get("BENCH_SPEC_TREE_BUDGET", 20)),
    )
    row["metric"] += "_cpusmoke"
    row["platform"] = "cpu"
    artifact = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks",
        "SPEC_TREE_AB_cpu.json",
    )
    with open(artifact, "w") as f:
        json.dump(row, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(row))


def _kv_tier_ab_smoke() -> None:
    """CPU smoke for ``--kv-tier-ab`` (acceptance: byte-identical streams
    for a demoted-then-promoted run vs the always-resident warm hit under
    the armed sanitizer, and host-tier warm TTFT well under cold-prefill
    TTFT on a working set larger than the device prefix-cache budget).
    Updates benchmarks/KV_TIER_AB_cpu.json (asserted by tier-1). Knobs:
    BENCH_TIER_PREFIXES / BENCH_TIER_PREFIX_LEN / BENCH_TIER_HOST_PAGES /
    BENCH_TIER_DEVICE_PAGES."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    row = run_kv_tier_ab(
        # int8 KV: the tier holds int8 pages + scale rows (the 2x-cheaper
        # representation the design banks on)
        {"preset": "llama-tiny", "dtype": "float32", "kv_quant": "int8"},
        n_prefixes=int(os.environ.get("BENCH_TIER_PREFIXES", 3)),
        prefix_len=int(os.environ.get("BENCH_TIER_PREFIX_LEN", 768)),
        device_cache_pages=int(os.environ.get("BENCH_TIER_DEVICE_PAGES", 48)),
        host_pages=int(os.environ.get("BENCH_TIER_HOST_PAGES", 160)),
    )
    row["metric"] += "_cpusmoke"
    row["platform"] = "cpu"
    artifact = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks",
        "KV_TIER_AB_cpu.json",
    )
    with open(artifact, "w") as f:
        json.dump(row, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(row))


def _paged_quant_ab_smoke() -> None:
    """CPU smoke for ``--paged-quant-ab`` (acceptance: >= 1.8x pool-bytes
    reduction at equal page budget, no step-time regression, Pallas int8
    interpret parity). Runs at bf16 pools with head_dim 64 — the honest
    production layout; llama-tiny's D=16 would overstate the f32-scale
    overhead. Knobs: BENCH_PQ_BATCH / BENCH_PQ_STEPS / BENCH_PQ_TOKENS."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    row = run_paged_quant_ab(
        # llama-tiny widened to head_dim 64 (dim 256 / 4 heads), bf16 pools
        {"preset": "llama-tiny", "dtype": "bfloat16", "dim": 256,
         "n_heads": 4, "n_kv_heads": 2},
        batch=int(os.environ.get("BENCH_PQ_BATCH", 2)),
        decode_steps=int(os.environ.get("BENCH_PQ_STEPS", 4)),
        new_tokens=int(os.environ.get("BENCH_PQ_TOKENS", 24)),
        prompt_len=12,
        max_seq_len=128,
    )
    row["metric"] += "_cpusmoke"
    row["platform"] = "cpu"
    print(json.dumps(row))


def _pipeline_ab_smoke() -> None:
    """CPU smoke for ``--pipeline-ab`` (acceptance: >=10% steady-state step
    time reduction at depth 2 vs 1, byte-identical greedy streams). Knobs:
    BENCH_PIPE_BATCH / BENCH_PIPE_STEPS / BENCH_PIPE_TOKENS / BENCH_PIPE_CACHE."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    row = run_pipeline_ab(
        {"preset": "llama-tiny", "dtype": "float32"},
        batch=int(os.environ.get("BENCH_PIPE_BATCH", 4)),
        decode_steps=int(os.environ.get("BENCH_PIPE_STEPS", 8)),
        new_tokens=int(os.environ.get("BENCH_PIPE_TOKENS", 192)),
        cache_mode=os.environ.get("BENCH_PIPE_CACHE", "dense"),
    )
    row["metric"] += "_cpusmoke"
    row["platform"] = "cpu"
    print(json.dumps(row))


def _loadtest(smoke: bool, replicas: int = 0,
              disaggregated: bool = False) -> None:
    """``--loadtest [--smoke] [--replicas N] [--disaggregated]``:
    loadtest harnesses.

    Without ``--replicas``: the SLO-aware-scheduling loadtest — open-loop
    Poisson mixed-trace replay against the real engine with priority
    classes, the preemptible batch lane, the brownout controller, the
    armed KV sanitizer AND the strict compile sentry (the shared warmup
    registry llm/warmup.py runs first; any post-warmup XLA compile fails
    the run, and the committed headline asserts post_warmup_compiles == 0
    — benchmarks/slo_loadtest.py; docs/slo_scheduling.md;
    docs/static_analysis.md TPU6xx). Emits per-class p50/p99 TTFT +
    goodput vs offered-load curves and updates
    benchmarks/LOADTEST_cpu.json.

    With ``--replicas N`` (N >= 2): the replica-fleet router loadtest —
    1 vs N engine replicas behind the prefix-affine router on the
    repeated-conversation trace, plus the kill-one-replica chaos case
    (benchmarks/replica_loadtest.py; docs/replication.md). Headline:
    affine-hit rate, interactive p99 TTFT, aggregate goodput speedup,
    zero sanitizer/sentry violations, zero chaos 503s. Updates
    benchmarks/LOADTEST_replicas_cpu.json.

    With ``--replicas N --disaggregated``: the disaggregated
    prefill/decode loadtest — mono vs two-hybrid vs prefill/decode-split
    replicas with the KV transport shipping admissions' prefix KV
    (benchmarks/disagg_loadtest.py; docs/disaggregation.md). Headline:
    ship hit rate >= 0.9, byte-identical streams, zero sanitizer/sentry
    violations. Updates benchmarks/DISAGG_AB_cpu.json."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    if disaggregated:
        from benchmarks import disagg_loadtest

        row = disagg_loadtest.run(smoke=smoke, replicas=replicas or 2)
    elif replicas and replicas > 1:
        from benchmarks import replica_loadtest

        row = replica_loadtest.run(smoke=smoke, replicas=replicas)
    else:
        from benchmarks import slo_loadtest

        row = slo_loadtest.run(smoke=smoke)
    print(json.dumps(row))


if __name__ == "__main__":
    if "--shared-prefix" in sys.argv or (
        os.environ.get("BENCH_SCENARIO") == "shared_prefix"
    ):
        _shared_prefix_smoke()
    elif "--pipeline-ab" in sys.argv or (
        os.environ.get("BENCH_SCENARIO") == "pipeline_ab"
    ):
        _pipeline_ab_smoke()
    elif "--spec-tree-ab" in sys.argv or (
        os.environ.get("BENCH_SCENARIO") == "spec_tree_ab"
    ):
        _spec_tree_ab_smoke()
    elif "--kv-tier-ab" in sys.argv or (
        os.environ.get("BENCH_SCENARIO") == "kv_tier_ab"
    ):
        _kv_tier_ab_smoke()
    elif "--paged-quant-ab" in sys.argv or (
        os.environ.get("BENCH_SCENARIO") == "paged_quant_ab"
    ):
        _paged_quant_ab_smoke()
    elif "--int4-ab" in sys.argv or (
        os.environ.get("BENCH_SCENARIO") == "int4_ab"
    ):
        _int4_ab_smoke()
    elif "--loadtest" in sys.argv or (
        os.environ.get("BENCH_SCENARIO") == "loadtest"
    ):
        replicas = None
        if "--replicas" in sys.argv:
            try:
                replicas = int(sys.argv[sys.argv.index("--replicas") + 1])
            except (IndexError, ValueError):
                # fail loudly: silently running the default scale would
                # overwrite the committed artifact with numbers the
                # operator thinks are something else
                print("error: --replicas needs an integer argument",
                      file=sys.stderr)
                sys.exit(2)
        elif os.environ.get("BENCH_LOADTEST_REPLICAS"):
            try:
                replicas = int(os.environ["BENCH_LOADTEST_REPLICAS"])
            except ValueError:
                print("error: BENCH_LOADTEST_REPLICAS must be an integer",
                      file=sys.stderr)
                sys.exit(2)
        if replicas is not None and replicas < 2:
            # an EXPLICIT replica count below the harness minimum (0 and 1
            # included) must not silently fall through to the single-engine
            # SLO loadtest (and overwrite ITS artifact with numbers the
            # operator thinks are router output)
            print("error: --replicas needs >= 2 (the replica loadtest "
                  "always runs its own single-replica arm)", file=sys.stderr)
            sys.exit(2)
        disaggregated = "--disaggregated" in sys.argv or (
            os.environ.get("BENCH_LOADTEST_DISAGG", "") in ("1", "true")
        )
        if disaggregated and replicas is None:
            # the disaggregated harness needs a fleet; default to the
            # committed artifact's 2-replica shape rather than erroring
            replicas = 2
        _loadtest(
            "--smoke" in sys.argv
            or os.environ.get("BENCH_LOADTEST_SMOKE", "") in ("1", "true"),
            replicas=replicas or 0,
            disaggregated=disaggregated,
        )
    else:
        main()
