"""`attn_decode_roofline` on a recorded context: the bytes the chained decode
passes of the traced tail had to read (the program's
``ragged.decode_chain_kv_tokens`` at the trace's edges) over the HBM peak,
against the traced seconds of the kernel named paged_attention_decode."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import roofline, sut  # noqa: E402
from benchmark.layer_metrics import attn_decode_roofline  # noqa: E402
from tests.benchmark.test_bench_manifest import holds_entry  # noqa: E402

# the K/V cells in which the decode kernel runs: `mixtral8x7b.prefill_batch`
# launches only ragged steps at decode window 1 (PERF.md section 6, PR 41)
KV_CELLS = ["mistral7b.chat_steady", "mistral7b.decode_batch",
            "mixtral8x7b.chat_steady"]
KERNEL = ("paged_attention_decode.1_custom-call_bf16_32_8_4_128", 0.4, 2600)
OTHER = ("ragged_paged_attention.1_custom-call_bf16_352_8_4_128", 1.0, 300)


def recorded(config, gained, ops, counter=True, kv_pool=True):
    pool = {"num_pages": 1750, "page_size": 16} if kv_pool else None
    before = {"ragged": {"passes": 100}, "kv_pool": pool}
    after = {"ragged": {"passes": 190}, "kv_pool": pool}
    if counter:
        before["ragged"].update(decode_chain_kv_tokens=5_000_000, decode_chain_rows=4000)
        after["ragged"].update(decode_chain_kv_tokens=5_000_000 + gained,
                               decode_chain_rows=4000 + gained // 1300)
    return {
        "cfg": sut.load_config(ROOT / "benchmark" / "configs" / config),
        "before": before, "after": after, "trace_counters": (before, after),
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "trace": {"devices": 1, "busy_s": 4.0, "window_s": 5.0, "ops": ops},
    }


@pytest.mark.parametrize("config, kv_bytes", [
    ("mistral-7b-v0.3.json", 131072), ("mixtral-8x7b-d6.json", 24576)])
def test_share_is_the_counted_bytes_over_the_peak_and_the_kernels_seconds(config, kv_bytes):
    gained = 1_200_000                      # tokens attended in the tail
    ctx = recorded(config, gained, [OTHER, KERNEL, KERNEL])
    assert roofline.kv_bytes_per_token(sut.model_block(ctx["cfg"])) == kv_bytes
    want = gained * kv_bytes / 819e9 / 0.8  # both operations of that name
    assert attn_decode_roofline.read(ctx) == pytest.approx(100 * want)
    assert 0 < attn_decode_roofline.read(ctx) < 100


def test_no_chained_row_in_the_tail_reads_zero():
    assert attn_decode_roofline.read(recorded("mistral-7b-v0.3.json", 0, [OTHER])) == 0.0
    assert attn_decode_roofline.read(recorded("mistral-7b-v0.3.json", 0, [KERNEL])) == 0.0


@pytest.mark.parametrize("case", ["no_counter", "no_kernel", "no_pool", "no_trace",
                                  "no_edges"])
def test_reads_nothing_and_does_not_raise(case):
    """The parent's program has no such counter, a state cell no paged pool
    and no such kernel, an untraced run no trace."""
    ctx = recorded("mistral-7b-v0.3.json", 1_200_000, [OTHER, KERNEL],
                   counter=case != "no_counter", kv_pool=case != "no_pool")
    if case == "no_kernel":
        ctx["trace"]["ops"] = [OTHER]
    if case == "no_trace":
        ctx["trace"] = None
    if case == "no_edges":
        ctx.pop("trace_counters")
    assert attn_decode_roofline.read(ctx) is None


def manifest_lists_attn_decode_roofline_for_the_kv_cells(manifest):
    # never where the decode kernel does not run: a pass of prompts at decode
    # window 1 (PR 41), the state cache
    holds_entry(manifest, {
        "name": "attn_decode_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels (ops/paged_attention.py)",
        "moves": "tpot_p50_ms", "workloads": KV_CELLS},
        never=("mixtral8x7b.prefill_batch", "brumby14b.long_decode"))


def test_the_manifest_lists_it_for_the_kv_cells():
    manifest_lists_attn_decode_roofline_for_the_kv_cells(
        json.loads((ROOT / "BENCHMARK.json").read_text()))
