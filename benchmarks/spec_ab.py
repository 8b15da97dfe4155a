"""Engine-level speculative-decoding A/B (VERDICT r3 #5: "a TPU A/B tok/s
line").

Builds the SERVING engine (LLMEngineCore — slot batching, admission,
emission; not bench.py's raw fused scan) twice — speculation off / ngram —
and drives identical concurrent greedy workloads through generate().
Repetitive prompts keep the n-gram proposer in its favorable regime
(summaries/extraction/code-shaped traffic); outputs are greedy-exact either
way, so the delta is pure speculation win (or loss, on draft-miss traffic —
the miss workload is reported too).

Run standalone (on whatever backend jax selects; the committed
SPEC_AB_cpu.json is a CPU run):
    python benchmarks/spec_ab.py [--preset llama-tiny] [--batch 4]
Emits one JSON line per (workload, mode) to stdout.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _prompts(kind: str, batch: int, prompt_len: int, vocab: int):
    rng = np.random.RandomState(7)
    out = []
    for b in range(batch):
        if kind == "repeat":
            # period-8 loop: the spec_ngram=2 matcher locks on quickly
            period = list(rng.randint(2, min(vocab, 30000), size=8))
            ids = (period * (prompt_len // 8 + 1))[:prompt_len]
        else:  # "miss": i.i.d. tokens, drafts rarely hit
            ids = list(rng.randint(2, min(vocab, 30000), size=prompt_len))
        out.append([1] + [int(t) for t in ids])
    return out


def run_ab(
    preset: str = "llama-tiny",
    batch: int = 4,
    prompt_len: int = 96,
    new_tokens: int = 160,
    decode_steps: int = 8,
    spec_k: int = 4,
    quantize=None,
    dtype: str = "float32",
    scan_layers: bool = False,
    kv_quant=None,
):
    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

    cfg = {"preset": preset, "dtype": dtype}
    if scan_layers:
        cfg["scan_layers"] = True
    if kv_quant:
        cfg["kv_quant"] = kv_quant
    bundle = models.build_model("llama", cfg)
    import jax

    params = bundle.init(jax.random.PRNGKey(0))
    vocab = int(bundle.config["vocab_size"])
    max_seq = prompt_len + new_tokens + 8
    bucket = 1
    while bucket < prompt_len + 1:
        bucket *= 2
    results = []
    for mode in (None, "ngram"):
        engine = LLMEngineCore(
            bundle, params,
            max_batch=batch, max_seq_len=max_seq,
            prefill_buckets=[bucket],
            eos_token_id=None,  # run the full budget: equal-token A/B
            decode_steps=decode_steps,
            speculation=mode, spec_k=spec_k,
            quantize=quantize,
            prefill_segments_per_decode=None,
        )
        # greedy workloads exercise the exact argmax chain; "sampled"
        # (temperature 0.8 on repetitive prompts) exercises the rejection
        # chain (spec_sampling) — the A/B shows its win on real traffic
        for kind, temperature in (
            ("repeat", 0.0), ("miss", 0.0), ("sampled", 0.8)
        ):
            prompts = _prompts(
                "repeat" if kind == "sampled" else kind,
                batch, prompt_len, vocab,
            )

            async def drive():
                async def one(p):
                    n = 0
                    req = GenRequest(prompt_ids=p, max_new_tokens=new_tokens,
                                     temperature=temperature)
                    async for _ in engine.generate(req):
                        n += 1
                    return n

                # warmup: compile prefill + decode paths
                await one(prompts[0])
                t0 = time.time()
                counts = await asyncio.gather(*[one(p) for p in prompts])
                dt = time.time() - t0
                return sum(counts), dt

            total, dt = asyncio.run(drive())
            results.append({
                "metric": "llm_engine_spec_ab_{}_{}".format(
                    kind, mode or "off"
                ),
                "value": round(total / dt, 2),
                "unit": "tok/s/chip",
                "workload": kind,
                "speculation": mode or "off",
                "batch": batch,
                "preset": preset,
                "tokens": total,
                "wall_s": round(dt, 2),
            })
            print(json.dumps(results[-1]), flush=True)
        engine.stop()
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="llama-tiny")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--new-tokens", type=int, default=160)
    ap.add_argument("--decode-steps", type=int, default=8)
    ap.add_argument("--quantize", default=None)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--scan-layers", action="store_true")
    ap.add_argument("--kv-quant", default=None)
    a = ap.parse_args()
    run_ab(
        preset=a.preset, batch=a.batch, prompt_len=a.prompt_len,
        new_tokens=a.new_tokens, decode_steps=a.decode_steps,
        quantize=a.quantize, dtype=a.dtype, scan_layers=a.scan_layers,
        kv_quant=a.kv_quant,
    )


if __name__ == "__main__":
    main()
