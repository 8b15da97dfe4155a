#!/usr/bin/env python3
"""By hand, on the chip, once a configuration of the latent page layout is
sized (ISSUE 45; the readings go into the configuration's
``probes.tolerance.measured`` and PERF.md). One JSON line each.

    python3 scripts/latent_checks.py layer [--tokens 12288] [--seed n]
    python3 scripts/latent_checks.py precision [--seed n]

``layer``: one full and one window layer at the published widths (the
configuration cut to layers [full, sliding], both with routed experts,
unrolled), a probe of ``--tokens`` tokens prefilled through the paged planes in
the engine's chunks, against the plain float32 reference layer by layer: the
relative error of each layer's attention output, the overlap of the selected
sets with the reference's, and three controls that have to stand out
(reference with the indexer off, the window off, the selection bias off: the
last on the first layer's feed-forward output, which is what it moves).
With random weights the logits hardly feel WHICH keys were attended, so the
cell's ``correct`` alone would not show a wrong selection.

``precision``: the cell's own probes through the served endpoint against the
reference at float32 / highest (the cell's reading), with bfloat16 matmuls,
and with the cached rows (c, k^R, k^I) rounded to float8 (one step below the
configured bfloat16 rows), which the tolerance has to refuse.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
CONFIG = ROOT / "benchmark" / "configs" / "dots3-note-prev-ep8.json"


def rel(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def layer_check(tokens: int, seed: int, config: Path = CONFIG) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import sut
    from benchmark.correctness import ServedWeights, probe_set
    from benchmark.reference import dots3_note as ref
    from clearml_serving_tpu import models

    cfg = sut.load_config(config)
    model = dict(sut.model_block(cfg), n_layers=2, scan_layers=False,
                 layer_types=["full_attention", "sliding_attention"],
                 first_k_dense_replace=0)
    bundle = models.build_model("dots3_note", model)
    params = bundle.init(jax.random.PRNGKey(seed % (2 ** 31)),
                         weight_quant="int8")
    page, chunk = 16, 128
    pages = -(-tokens // page)
    k, v = bundle.paged_layout.init_pools(pages + 1, page)
    table = jnp.stack([jnp.arange(1, pages + 1),
                       jnp.zeros(pages, jnp.int32)]).astype(jnp.int32)
    from clearml_serving_tpu.ops.paged_attention import (
        ragged_layout, ragged_query_tile, ragged_view_tokens,
        ragged_work_items,
    )

    tile = ragged_query_tile(1, bundle.n_heads, bundle.head_dim,
                             model.get("dtype", "bfloat16"))
    view = ragged_view_tokens(chunk, 2)
    step = jax.jit(bundle.forward_ragged, static_argnames=("probe",),
                   donate_argnums=(7, 8))
    prompt = probe_set(seed, int(model["vocab_size"]), [tokens])[0]
    outs, ffns, sets, counts = [[], []], [], [], []
    for done in range(0, tokens, chunk):
        n = min(chunk, tokens - done)
        row_lens = np.array([n, 0], np.int32)
        starts, _ = ragged_layout(row_lens, 8, total=view)
        items = ragged_work_items(row_lens, tile, total=2 + view // tile)
        valid = np.arange(chunk) < n
        pos = done + np.arange(chunk)
        toks = np.zeros(chunk, np.int32)
        toks[:n] = prompt[done:done + n]
        wp = np.where(valid, 1 + np.minimum(pos // page, pages - 1), 0)
        _, k, v, probes = step(
            params, jnp.asarray(toks), jnp.asarray(pos, jnp.int32),
            jnp.zeros(chunk, jnp.int32), jnp.asarray(valid),
            jnp.asarray(np.where(valid, np.arange(chunk), view), jnp.int32),
            jnp.asarray([n - 1, 0], jnp.int32), k, v, table,
            jnp.asarray([done + n, 0], jnp.int32), jnp.asarray(starts),
            jnp.asarray(row_lens), jnp.asarray(wp, jnp.int32),
            jnp.asarray(np.where(valid, pos % page, 0), jnp.int32),
            jnp.asarray(items[0]), jnp.asarray(items[1]), probe=True)
        for layer in range(2):
            outs[layer].append(np.asarray(probes[layer][0][:n], np.float32))
        ffns.append(np.asarray(probes[0][3][:n], np.float32))
        sets.append(np.asarray(probes[0][1][:n]))
        counts.append(np.asarray(probes[0][2][:n]))
    served = [np.concatenate(o) for o in outs]
    ffn, sets, counts = (np.concatenate(a) for a in (ffns, sets, counts))
    weights = ServedWeights(params)
    ids = jnp.asarray(prompt, jnp.int32)
    last = jnp.asarray([tokens - 1])

    def reference(**controls):
        trace = []
        ref.forward(model, weights, ids, last, trace=trace, **controls)
        return trace

    trace = reference()
    visible = np.asarray(trace[0][1])
    topk = int(model["index_topk"])
    late = np.arange(tokens) >= topk          # rows whose selection is a choice
    overlap = [
        len(set(sets[t][:counts[t]].tolist())
            & set(np.nonzero(visible[t])[0].tolist())) / visible[t].sum()
        for t in np.nonzero(late)[0][:: max(1, late.sum() // 512)]
    ]
    out = {
        "check": "layer", "tokens": tokens, "seed": seed,
        "device": jax.devices()[0].device_kind,
        "kernels": "pallas" if jax.default_backend() == "tpu" else "xla",
        "full_attn_rel_err": rel(served[0], trace[0][0]),
        "window_attn_rel_err": rel(served[1], trace[1][0]),
        "full_ffn_rel_err": rel(ffn, trace[0][2]),
        "selected_overlap_mean": float(np.mean(overlap)) if overlap else None,
        "selected_overlap_min": float(np.min(overlap)) if overlap else None,
        "rows_compared": len(overlap),
    }
    del trace
    for name, layer in (("indexer", 0), ("windowed", 1)):
        off = reference(**{name: False})
        out["control_{}_off_rel_err".format(name)] = rel(
            served[layer], off[layer][0])
        del off
    # the bias moves the routing: the first layer's routed sum feels it
    # (its input is the same attention output on both sides)
    off = reference(bias=False)
    out["control_bias_off_ffn_rel_err"] = rel(ffn, off[0][2])
    return out


async def precision_check(seed: int, config: Path = CONFIG) -> dict:
    import aiohttp
    import jax

    from benchmark import correctness as cx, sut
    from benchmark.run import post_json, wait_warm

    cfg = sut.load_config(config)
    cfg.setdefault("name", config.stem)
    sut.place_caches(ROOT)
    out_dir = ROOT / "chiprun_out" / "latent_checks"
    out_dir.mkdir(parents=True, exist_ok=True)
    svc = sut.Service(cfg, seed, out_dir)
    await svc.start()
    await wait_warm(svc)
    model = sut.model_block(cfg)
    spec = cfg["probes"]
    prompts = cx.probe_set(seed, int(model["vocab_size"]), spec["prompt_tokens"])
    url = svc.base + "/serve/openai/v1/completions"
    async with aiohttp.ClientSession() as session:
        probes = [cx.parse_probe(await post_json(
            session, url, cx.probe_body(svc.name, ids, int(spec["new_tokens"]))))
            for ids in prompts]
    weights = cx.ServedWeights(svc.engine.params)
    module = __import__("benchmark.reference.dots3_note", fromlist=["forward"])
    import jax.numpy as jnp

    out = {"check": "precision", "seed": seed,
           "device": jax.devices()[0].device_kind}
    readings = {
        "highest": {},
        "bfloat16": {"precision": "bfloat16"},
        # the cached rows one precision below the configured bfloat16
        "float8_rows": {"row_dtype": jnp.float8_e4m3fn},
    }
    for precision, kw in readings.items():
        positions = []
        for ids, probe in zip(prompts, probes):
            tokens = jnp.asarray(list(ids) + probe["ids"][:-1], jnp.int32)
            at = jnp.arange(len(ids) - 1, len(ids) - 1 + len(probe["ids"]))
            logits = module.forward(model, weights, tokens, at, **kw)
            positions += cx.compare_probe(
                jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1), probe)
        verdict = cx.verdict(positions, spec["tolerance"])
        out[precision] = {k: verdict[k] for k in (
            "typical_position_rms", "p90_position_rms", "worst_position_rms",
            "outlier_share", "positions", "within")}
    await svc.stop()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("check", choices=("layer", "precision"))
    ap.add_argument("--tokens", type=int, default=12288)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 45)
    ap.add_argument("--config", type=Path, default=CONFIG,
                    help="a configuration file of arch dots3_note")
    args = ap.parse_args()
    if args.check == "layer":
        result = layer_check(args.tokens, args.seed, args.config)
    else:
        result = asyncio.run(precision_check(args.seed, args.config))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
