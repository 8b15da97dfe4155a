"""Microbench: Pallas paged-attention kernel vs XLA gather vs dense cache.

Two questions, two scenario families:

1. ``uniform`` (r1-r3 continuity): b=16 hkv=8 g=4 d=128, 16-token pages,
   sequences uniformly half-full (512 of 1024).  Answers "does the
   double-buffered-DMA kernel beat the plain-XLA page gather"
   (r3 on v5e, the kernel of that round: yes, 2.391 vs 2.744 ms).

2. ``ragged`` (VERDICT r3 #3): b=32/64 with a realistic serving length
   mix (128..4096 cycling) at 4096-token capacity.  This is where paging
   PAYS: a dense full-capacity cache must stream B*4096 positions of K/V
   through the MXU-adjacent bandwidth every decode step regardless of how
   short most sequences are, while paged contenders touch only live
   pages (~1/3 of capacity for this mix).  The summary also emits the
   HBM-capacity side of the argument: bytes a dense cache would pin vs
   the paged pool, and the max decode batch each fits in the same budget
   — the dense-fullcap configuration OOMs out of slots long before the
   paged pool does.

Contenders per scenario:
- pallas         ops.paged_attention (its block of pages follows from the
                 shapes: ops.paged_attention.decode_pages_per_block)
- xla_gather     ops.paged_attention_xla (the fallback the kernel must beat)
- dense          attention over a dense [B, Hkv, cap, D] cache, the
                 no-paging baseline

Timing: every timed section ends in a host readback that data-depends on
the result (np.asarray), so the clock stops after the device finished.
Prints one JSON line per contender plus a "winner" summary per scenario.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# standalone runs (`python benchmarks/paged_bench.py`) need the repo root on
# sys.path to reach the clearml_serving_tpu package
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

HKV, G, D = 8, 4, 128
PAGE = 16
ROUNDS = 50

# realistic serving mix for the ragged scenarios (vLLM-style ragged decode
# batch: many short chats, a few long-context stragglers)
RAGGED_MIX = (128, 256, 512, 512, 1024, 2048, 4096, 256)


def _time(fn, *args, rounds=ROUNDS):
    out = fn(*args)
    np.asarray(out)  # warmup + compile, readback-synced
    t0 = time.perf_counter()
    for _ in range(rounds):
        out = fn(*args)
    np.asarray(out)
    return (time.perf_counter() - t0) / rounds * 1e3  # ms


def _scenario(name, batch, seq_cap, lengths_list, platform, pa):
    """Time all contenders on one (batch, capacity, lengths) shape."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    pages_per_seq = seq_cap // PAGE
    lengths = np.asarray(lengths_list, np.int32)
    assert lengths.shape[0] == batch

    # paged pool sized by LIVE pages (+1 reserved null page 0 that padded
    # table entries point at) — that sizing IS paging's capacity win
    live_pages_per_seq = -(-lengths // PAGE)  # ceil
    n_pages = int(live_pages_per_seq.sum()) + 1
    q = jax.random.normal(ks[0], (batch, HKV, G, D), jnp.bfloat16)
    k_pool = jax.random.normal(ks[1], (HKV, n_pages, PAGE, D), jnp.bfloat16)
    v_pool = jax.random.normal(ks[2], (HKV, n_pages, PAGE, D), jnp.bfloat16)
    table = np.zeros((batch, pages_per_seq), np.int32)
    nxt = 1
    for b in range(batch):
        n = int(live_pages_per_seq[b])
        table[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
    page_table = jnp.asarray(table)
    lengths_dev = jnp.asarray(lengths)

    results = {}
    xla = jax.jit(pa.paged_attention_xla)
    results["xla_gather"] = _time(xla, q, k_pool, v_pool, page_table, lengths_dev)

    if platform == "tpu":
        # the kernel derives its block of pages from the shapes
        try:
            results["pallas"] = _time(
                jax.jit(pa.paged_attention), q, k_pool, v_pool, page_table,
                lengths_dev,
            )
        except Exception as ex:  # record, keep going
            print(json.dumps({"scenario": name, "contender": "pallas",
                              "error": str(ex)[:200]}))

    # dense baseline: full-capacity cache, masked softmax (what the dense
    # cache_mode engine does) — pays capacity-proportional bandwidth
    k_dense = jax.random.normal(ks[3], (batch, HKV, seq_cap, D), jnp.bfloat16)
    v_dense = jax.random.normal(ks[4], (batch, HKV, seq_cap, D), jnp.bfloat16)

    def dense_attn(q, k, v, lengths):
        s = jnp.einsum("bhgd,bhsd->bhgs", q, k, preferred_element_type=jnp.float32)
        s = s / np.sqrt(D)
        mask = jnp.arange(seq_cap)[None, None, None, :] < lengths[:, None, None, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum(
            "bhgs,bhsd->bhgd", p.astype(k.dtype), v, preferred_element_type=jnp.float32
        ).astype(q.dtype)

    try:
        results["dense_fullcap"] = _time(
            jax.jit(dense_attn), q, k_dense, v_dense, lengths_dev
        )
    except Exception as ex:  # an OOM here IS a result: paging fit, dense didn't
        print(json.dumps({"scenario": name, "contender": "dense_fullcap",
                          "error": str(ex)[:200]}))

    for cname, ms in results.items():
        print(json.dumps({"scenario": name, "contender": cname,
                          "ms": round(ms, 3), "platform": platform}))

    bytes_per_tok = HKV * D * 2 * 2  # K+V, bf16
    dense_bytes = batch * seq_cap * bytes_per_tok
    paged_bytes = n_pages * PAGE * bytes_per_tok
    best_pallas = min(
        (v for k, v in results.items() if k.startswith("pallas")), default=None
    )
    summary = {
        "metric": "paged_attention_decode_{}".format(name),
        "platform": platform,
        "batch": batch,
        "seq_cap": seq_cap,
        "live_frac": round(float(lengths.sum()) / (batch * seq_cap), 3),
        "xla_gather_ms": round(results["xla_gather"], 3),
        # capacity argument: same HBM budget fits this many more sequences
        "dense_cache_mb": round(dense_bytes / 2**20, 1),
        "paged_pool_mb": round(paged_bytes / 2**20, 1),
        "capacity_ratio": round(dense_bytes / paged_bytes, 2),
    }
    if "dense_fullcap" in results:
        summary["dense_ms"] = round(results["dense_fullcap"], 3)
    if best_pallas is not None:
        summary["best_pallas_ms"] = round(best_pallas, 3)
        summary["pallas_vs_gather"] = round(
            results["xla_gather"] / best_pallas, 3
        )
        if "dense_fullcap" in results:
            summary["pallas_vs_dense"] = round(
                results["dense_fullcap"] / best_pallas, 3
            )
    print(json.dumps(summary))


def main() -> None:
    from clearml_serving_tpu.ops import paged_attention as pa

    platform = jax.devices()[0].platform

    # r1-r3 continuity point: uniform half-full occupancy at b16
    _scenario(
        "b16_uniform", 16, 1024, [512] * 16, platform, pa
    )
    # where paging pays: big ragged batches at long capacity (VERDICT r3 #3)
    for batch in (32, 64):
        lengths = [RAGGED_MIX[i % len(RAGGED_MIX)] for i in range(batch)]
        _scenario(
            "b{}_ragged_4k".format(batch), batch, 4096, lengths, platform, pa
        )


if __name__ == "__main__":
    main()
