"""Golden outputs of the ragged paged engine (ISSUE 25): greedy token ids and
top-2 log-probabilities of seeded prompts, recorded on the commit BEFORE the
stacked pools moved into the layer scan's carry. The model step may change
how the pools travel through a launch, never what is served: every case must
reproduce ``tests/data/paged_golden.json`` bit for bit (floats compare by
``float.hex``). Re-record only for a change that is meant to move numerics:
``JAX_PLATFORMS=cpu python tests/test_paged_golden.py --record``."""

import asyncio
import json
import pathlib
import random
import sys

import jax
import pytest

GOLDEN = pathlib.Path(__file__).parent / "data" / "paged_golden.json"

# name -> (model config, engine knobs): bf16 pools, int8 pools with scale
# pools, a speculative chain (verify rows + the in-launch accept), and the
# unrolled layer loop (scan_layers off is the preset's default; "scan" turns
# the lax.scan formulation on)
TINY = {"preset": "llama-tiny", "dtype": "bfloat16"}
RAGGED = dict(cache_mode="paged", scheduler="ragged", step_token_budget=24,
              page_size=8, num_pages=64)
CASES = {
    "bf16": (TINY, RAGGED),
    "bf16_scan": (dict(TINY, scan_layers=True), RAGGED),
    "int8": (dict(TINY, kv_quant="int8"), RAGGED),
    "int8_scan": (dict(TINY, kv_quant="int8", scan_layers=True), RAGGED),
    "spec_chain": (
        dict(TINY, dtype="float32"),
        dict(RAGGED, speculation="ngram", spec_k=2, spec_ngram=2),
    ),
}


def _prompts():
    rng = random.Random(25)
    out = [[rng.randrange(1, 250) for _ in range(n)] for n in (5, 23, 40)]
    # a repetitive prompt, so that the n-gram proposer has drafts to verify
    out.append([5, 9, 2, 17, 5, 9, 2, 17, 5, 9, 2])
    return out


def _serve(name):
    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

    cfg, knobs = CASES[name]
    bundle = models.build_model("llama", cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    engine = LLMEngineCore(
        bundle, params, max_batch=4, max_seq_len=96, prefill_buckets=[16, 64],
        eos_token_id=None, decode_steps=4, **knobs
    )

    async def go():
        # a row that tracks log-probabilities is never a verify row, so the
        # speculative case records its token ids alone
        lp = None if "speculation" in knobs else 2
        reqs = [GenRequest(prompt_ids=list(p), max_new_tokens=10, logprobs=lp)
                for p in _prompts()]

        async def one(req):
            return [int(t) async for t in engine.generate(req)]

        outs = await asyncio.gather(*(one(r) for r in reqs))
        await engine.wait_drained()
        if "speculation" in knobs:
            rows = engine.lifecycle_stats()["ragged"]["step_rows"]
            assert rows["spec_verify"] >= 1, rows
        return [
            {
                "tokens": toks,
                "logprobs": [
                    {
                        "chosen": float(e["logprob"]).hex(),
                        "top": [[int(i), float(lp).hex()] for i, lp in
                                zip(e["top_ids"][:2], e["top_logprobs"][:2])],
                    }
                    for e in req.logprob_entries
                ],
            }
            for toks, req in zip(outs, reqs)
        ]

    try:
        return asyncio.run(go())
    finally:
        engine.stop()


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_paged_step_serves_the_recorded_outputs(name):
    want = json.loads(GOLDEN.read_text())[name]
    got = _serve(name)
    assert [g["tokens"] for g in got] == [w["tokens"] for w in want]
    assert got == want


if __name__ == "__main__":
    if sys.argv[1:] != ["--record"]:
        sys.exit("usage: test_paged_golden.py --record")
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    GOLDEN.parent.mkdir(exist_ok=True)
    GOLDEN.write_text(
        json.dumps({n: _serve(n) for n in sorted(CASES)}, indent=1) + "\n"
    )
