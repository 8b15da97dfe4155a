#!/usr/bin/env python3
"""The routed feed-forward's kernel against its twin on the device, at a
configuration's published widths (docs/moe_experts.md):

    python scripts/moe_checks.py --config benchmark/configs/trinity-mini-d8.json \
        --tokens 128 --hit 128 72 32 0 --seed 7

One expert layer's int8 stacks are drawn on the device from ``--seed``; for
each ``--hit`` the router is held to that many of the held experts, and the
line gives the seconds a call of ``ops.moe_experts.moe_experts`` (by tile of
the expert width), of ``models.llama.moe_dropless`` (every expert) and of a
``lax.fori_loop`` over the hit experts with the slice inside each product
(the form the kernel would be deleted for, were it as fast), each against a
float32 sum over a token's own experts, and whether a row alone in the launch
answers bit for bit as beside the others. A time is a train of ``--reps``
calls waited for once, over ``--reps``, the median of five trains; on the CPU
the kernel is interpreted and the times say nothing.
"""

from __future__ import annotations

import argparse
import functools
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def loop_experts(x, gates, order, count, w_gate, w_up, w_down):
    """``moe_experts`` as a loop of XLA products over the hit experts."""
    import jax
    import jax.numpy as jnp

    def product(a, w, e):
        y = jnp.dot(a, w["_q8"][e].astype(a.dtype),
                    preferred_element_type=jnp.float32)
        return y * w["_scale"][e]

    def body(i, acc):
        e = order[i]
        h = jax.nn.silu(product(x, w_gate, e)) * product(x, w_up, e)
        gate = gates[:, e][:, None]
        h = jnp.where(gate != 0.0, h * gate, 0.0).astype(x.dtype)
        return acc + product(h, w_down, e)

    return jax.lax.fori_loop(
        0, count, body, jnp.zeros(x.shape, jnp.float32))


def check(config: Path, tokens: int, hits, seed: int, reps: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import sut
    from clearml_serving_tpu.models.llama import moe_dropless, moe_route
    from clearml_serving_tpu.ops import moe_experts as me
    from clearml_serving_tpu.ops.quant import dequantize, quantize_int8

    model = sut.model_block(sut.load_config(config))
    dim, width = int(model["dim"]), int(model["moe_intermediate_size"])
    n_router, top_k = int(model["router_experts"]), int(model["moe_top_k"])
    first, n_held = model.get("experts_held", (0, n_router))
    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 6)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def stack(key, a, b):
        w = jax.random.normal(key, (n_held, a, b), jnp.float32) * a ** -0.5
        return dict(zip(("_q8", "_scale"), quantize_int8(w, axis=-2)))

    stacks = [stack(keys[0], dim, width), stack(keys[1], dim, width),
              stack(keys[2], width, dim)]
    x = jax.random.normal(keys[3], (tokens, dim), jnp.float32).astype(dtype)
    logits = jax.random.normal(keys[4], (tokens, n_router), jnp.float32)
    valid = jnp.arange(tokens) < tokens - tokens // 8    # a padded tail

    def timed(fn, *args):
        """(result, seconds a call): ``reps`` calls queued behind each
        other and waited for once, so the host's dispatch hides behind the
        device; the median of five such trains."""
        out = jax.block_until_ready(fn(*args))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(reps):
                last = fn(*args)
            jax.block_until_ready(last)
            took.append((time.perf_counter() - t0) / reps)
        return out, statistics.median(took)

    def rel(a, b, rows):
        a = np.asarray(a, np.float64)[rows]
        b = np.asarray(b, np.float64)[rows]
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    tiles = sorted({t for t in (256, 512, 1024, width)
                    if width % t == 0 and t % 128 == 0}
                   | {me.width_tile(dim, width)})
    kernel = {
        tile: jax.jit(lambda x, g, o, c, *w, tile=tile: me.moe_experts(
            x, g, o, c, *w, tile=tile, interpret=not on_tpu))
        for tile in tiles}
    twin = jax.jit(lambda x, p, e, *w: moe_dropless(
        x, p, e, *(dequantize(a["_q8"], a["_scale"], dtype) for a in w)))
    loop = jax.jit(loop_experts)

    @jax.jit
    def exact(x, gates, *w):
        """float32, token by token over its own experts."""
        wg, wu, wd = (a["_q8"].astype(jnp.float32) * a["_scale"] for a in w)
        xf = x.astype(jnp.float32)

        def one(e, acc):
            h = jax.nn.silu(xf @ wg[e]) * (xf @ wu[e])
            return acc + jnp.where(
                gates[:, e][:, None] != 0, gates[:, e][:, None] * (h @ wd[e]),
                0.0)

        return jax.lax.fori_loop(0, n_held, one, jnp.zeros(x.shape))

    out = {
        "check": "moe_kernel", "config": config.name, "tokens": tokens,
        "seed": seed, "device": jax.devices()[0].device_kind,
        "dim": dim, "width": width, "experts_held": n_held,
        "default_tile": me.width_tile(dim, width), "cases": [],
    }
    live = np.asarray(valid)
    for n_hit in hits:
        n_hit = min(n_hit, n_held)
        # the router held to the first n_hit held experts (all of the
        # router's outputs at 0: a launch of padding)
        open_ = (jnp.arange(n_router) >= first) & (
            jnp.arange(n_router) < first + n_hit)
        top_p, top_e = moe_route(
            jnp.where(open_, logits, -30.0), top_k, scoring="sigmoid",
            bias=jnp.where(open_, 0.0, -1.0))
        local = top_e - first
        here = (local >= 0) & (local < n_held)
        took = here & valid[:, None] & (n_hit > 0)
        gates, hit = me.expert_gates(top_p, local, took, n_held)
        order, count = me.visit_order(hit)
        want = exact(x, gates, *stacks)
        case = {"hit": int(count), "kernel_s": {}, "kernel_rel_err": {}}
        for tile, fn in kernel.items():
            got, s = timed(fn, x, gates, order, count, *stacks)
            case["kernel_s"][str(tile)] = s
            if count > 0:
                case["kernel_rel_err"][str(tile)] = rel(got, want, live)
            else:
                case["kernel_zero"] = bool(np.all(np.asarray(got) == 0))
        got, case["twin_s"] = timed(
            twin, x, top_p, jnp.where(here, local, n_held), *stacks)
        got_loop, case["loop_s"] = timed(
            loop, x, gates, order, count, *stacks)
        if count > 0:
            case["twin_rel_err"] = rel(got, want, live)
            case["loop_rel_err"] = rel(got_loop, want, live)
            # row 0 alone in the launch (the others padding) against row 0
            # beside them
            fn = kernel[out["default_tile"]]
            alone = took & (jnp.arange(tokens) == 0)[:, None]
            g1, h1 = me.expert_gates(top_p, local, alone, n_held)
            y1 = fn(x, g1, *me.visit_order(h1), *stacks)
            y = fn(x, gates, order, count, *stacks)
            case["row_alone_bit_equal"] = bool(
                np.array_equal(np.asarray(y1)[0], np.asarray(y)[0]))
            case["padding_rows_zero"] = bool(
                np.all(np.asarray(y)[~live] == 0))
        out["cases"].append(case)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", type=Path, required=True)
    ap.add_argument("--tokens", type=int, default=128)
    ap.add_argument("--hit", type=int, nargs="+", default=[128, 72, 32, 0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    print(json.dumps(check(
        args.config, args.tokens, args.hit, args.seed, args.reps)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
