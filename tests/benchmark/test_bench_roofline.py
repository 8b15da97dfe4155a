"""Operations, bytes and peaks against hand counts."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import roofline  # noqa: E402
from benchmark.sut import load_config, model_block  # noqa: E402

MISTRAL = model_block(load_config(ROOT / "benchmark/configs/mistral-7b-v0.3.json"))
MIXTRAL = model_block(load_config(ROOT / "benchmark/configs/mixtral-8x7b-d6.json"))
ATTN = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096     # wq, wk + wv, wo
FFN = 3 * 4096 * 14336


def test_mistral_layer_and_weights():
    assert roofline.layer_matmul_params(MISTRAL, active=True) == ATTN + FFN
    assert roofline.layer_matmul_params(MISTRAL, active=False) == ATTN + FFN
    # 7.11e9 matmul weights of the 7.25e9 parameters (embedding left out)
    assert roofline.weight_bytes(MISTRAL) == 32 * (ATTN + FFN) + 4096 * 32768


def test_mixtral_needs_two_experts_a_token_and_holds_eight():
    assert roofline.layer_matmul_params(MIXTRAL, active=True) == ATTN + 4096 * 8 + 2 * FFN
    assert roofline.layer_matmul_params(MIXTRAL, active=False) == ATTN + 4096 * 8 + 8 * FFN
    assert roofline.weight_bytes(MIXTRAL) == pytest.approx(8.7e9, rel=0.02)


@pytest.mark.parametrize("model,expect", [(MISTRAL, 131072), (MIXTRAL, 24576)])
def test_kv_bytes_per_token(model, expect):
    assert roofline.kv_bytes_per_token(model) == expect


def test_pass_flops_by_hand():
    # one decode token attending to 1000 tokens, logits read once
    got = roofline.pass_flops(MISTRAL, 1, 1, 1000)
    want = 2 * 32 * (ATTN + FFN) + 2 * 4096 * 32768 + 4 * 32 * 32 * 128 * 1000
    assert got == want


def test_pass_bytes_by_hand():
    got = roofline.pass_bytes(MISTRAL, kv_tokens_read=1000, new_tokens=1)
    assert got == roofline.weight_bytes(MISTRAL) + 131072 * 1001


def test_a_decode_pass_is_memory_bound_on_the_v5e():
    peaks = roofline.peaks_for("TPU v5 lite")
    least = roofline.min_seconds(
        roofline.pass_flops(MISTRAL, 32, 32, 32 * 500),
        roofline.pass_bytes(MISTRAL, 32 * 500, 32), peaks)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(
        (roofline.weight_bytes(MISTRAL) + 131072 * 32 * 501) / 819e9)


def test_peaks_have_a_source_and_unknown_devices_are_an_error():
    table = json.loads((ROOT / "benchmark/peaks.json").read_text())
    assert "TPU v5e" in table["_source"]
    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            roofline.peaks_for(kind)
