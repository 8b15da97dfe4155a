#!/usr/bin/env python3
"""By hand, on the chip, once a configuration of arch afmoe is sized (ISSUE
47; the readings go into the configuration's ``probes.tolerance.measured``
and PERF.md). One JSON line each.

    python3 scripts/window_checks.py layer [--tokens 6000] [--seed n]
    python3 scripts/window_checks.py precision [--seed n]

``layer``: one sliding and one full layer at the published widths (the
configuration cut to layers [sliding, full], both with routed experts,
unrolled), a probe of ``--tokens`` tokens prefilled through the standard
pools in the engine's 128-token chunks (the Pallas kernels on a TPU),
against the plain float32 reference layer by layer: the relative error of
each layer's attention output (after W^O) and feed-forward output, and four
controls that have to stand out: the reference with the window off and with
the gate off (the sliding layer's attention output), with rotation on the
full layer (its attention output), and with the selection bias off (the
first layer's feed-forward output, which is what it moves). With random
weights the logits hardly feel WHICH keys were attended, so the cell's
``correct`` alone would not show a sliding layer that attends everything.

``precision``: the cell's own probes through the served endpoint against the
reference at float32 / highest (the cell's reading) and with the K/V rows a
cache would hold rounded to float8_e4m3 (one precision below the configured
bfloat16 pages), which the tolerance has to refuse.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
CONFIG = ROOT / "benchmark" / "configs" / "trinity-mini-d8.json"


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
    from benchmark.reference import trinity_mini as ref
    from clearml_serving_tpu import models
    from clearml_serving_tpu.ops.paged_attention import (
        ragged_layout, ragged_query_tile, ragged_view_tokens,
        ragged_work_items,
    )

    cfg = sut.load_config(config)
    model = dict(sut.model_block(cfg), n_layers=2, scan_layers=False,
                 layer_types=["sliding_attention", "full_attention"],
                 num_dense_layers=0)
    bundle = models.build_model("afmoe", model)
    params = bundle.init(jax.random.PRNGKey(seed % (2 ** 31)),
                         weight_quant="int8")
    dtype = jnp.dtype(model.get("dtype", "bfloat16"))
    page, chunk = 16, 128
    pages = -(-tokens // page)
    shape = (2, bundle.n_kv_heads, pages + 1, page, bundle.head_dim)
    k, v = jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
    table = jnp.stack([jnp.arange(1, pages + 1),
                       jnp.zeros(pages, jnp.int32)]).astype(jnp.int32)
    tile = ragged_query_tile(
        bundle.n_kv_heads, bundle.n_heads // bundle.n_kv_heads,
        bundle.head_dim, dtype)
    view = ragged_view_tokens(chunk, 2)
    step = jax.jit(bundle.forward_ragged, static_argnames=("probe",),
                   donate_argnums=(7, 8))
    prompt = probe_set(seed, int(model["vocab_size"]), [tokens])[0]
    outs = [[[], []], [[], []]]               # [layer][attention, ffn]
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
            for part in range(2):
                outs[layer][part].append(
                    np.asarray(probes[layer][part][:n], np.float32))
    served = [[np.concatenate(p) for p in layer] for layer in outs]
    weights = ServedWeights(params)
    ids = jnp.asarray(prompt, jnp.int32)
    last = jnp.asarray([tokens - 1])

    def reference(**controls):
        trace = []
        ref.forward(model, weights, ids, last, trace=trace, **controls)
        return [[np.asarray(x, np.float32) for x in layer] for layer in trace]

    trace = reference()
    out = {
        "check": "layer", "tokens": tokens, "seed": seed,
        "device": jax.devices()[0].device_kind,
        "kernels": "pallas" if jax.default_backend() == "tpu" else "xla",
        "window_attn_rel_err": rel(served[0][0], trace[0][0]),
        "window_ffn_rel_err": rel(served[0][1], trace[0][1]),
        "full_attn_rel_err": rel(served[1][0], trace[1][0]),
        "full_ffn_rel_err": rel(served[1][1], trace[1][1]),
        # the scores' scale: QK-norm should hold it at one
        "window_attn_rms": float(np.sqrt(np.mean(trace[0][0] ** 2))),
    }
    for name, controls, layer, part in (
        ("window_off", {"windowed": False}, 0, 0),
        ("gate_off", {"gated": False}, 0, 0),
        ("rope_on_full", {"rope_full": True}, 1, 0),
        ("bias_off", {"bias": False}, 0, 1),
    ):
        off = reference(**controls)
        out["control_{}_rel_err".format(name)] = rel(
            served[layer][part], off[layer][part])
        del off
    return out


async def precision_check(seed: int, config: Path = CONFIG) -> dict:
    import aiohttp
    import jax
    import jax.numpy as jnp

    from benchmark import correctness as cx, sut
    from benchmark.reference import trinity_mini as ref
    from benchmark.run import post_json, wait_warm

    cfg = sut.load_config(config)
    cfg.setdefault("name", config.stem)
    sut.place_caches(ROOT)
    out_dir = ROOT / "chiprun_out" / "window_checks"
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
    out = {"check": "precision", "seed": seed,
           "device": jax.devices()[0].device_kind}
    readings = {
        "highest": {},
        # the K/V rows one precision below the configured bfloat16 pages
        "float8_rows": {"row_dtype": jnp.float8_e4m3fn},
    }
    for precision, kw in readings.items():
        positions = []
        for ids, probe in zip(prompts, probes):
            tokens = jnp.asarray(list(ids) + probe["ids"][:-1], jnp.int32)
            at = jnp.arange(len(ids) - 1, len(ids) - 1 + len(probe["ids"]))
            logits = ref.forward(model, weights, tokens, at, **kw)
            positions += cx.compare_probe(
                jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1), probe)
        verdict = cx.verdict(positions, spec["tolerance"])
        out[precision] = {k: verdict[k] for k in (
            "typical_position_rms", "p90_position_rms", "worst_position_rms",
            "outlier_share", "positions", "within")}
    out["hbm_peak_bytes"] = sut.device_block()["memory_peak_bytes"]
    await svc.stop()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("check", choices=("layer", "precision"))
    ap.add_argument("--tokens", type=int, default=6000)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 47)
    ap.add_argument("--config", type=Path, default=CONFIG,
                    help="a configuration file of arch afmoe")
    args = ap.parse_args()
    if args.check == "layer":
        result = layer_check(args.tokens, args.seed, args.config)
    else:
        result = asyncio.run(precision_check(args.seed, args.config))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
