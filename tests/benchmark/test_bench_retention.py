"""The state cache's cell: a CPU rehearsal of it at tiny widths (new files
under tests/benchmark/tiny_retention: the accepted tiny manifest may not be
edited), the counts of roofline_retention.py, and each new reader on a
recorded context."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import roofline, roofline_retention as rr, sut  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    retention_chunk_roofline, retention_kernel_share, retention_pass_roofline,
    retention_update_roofline, state_pool_share, state_slots_peak_share,
)

TINY = ROOT / "tests" / "benchmark" / "tiny_retention"
CELL = "brumby14b.long_decode"


def brumby():
    return sut.load_config(ROOT / "benchmark" / "configs" / "brumby-14b-d12.json")


# ---------------------------------------------------------------- rehearsal

def test_rehearsal_of_the_state_cell_walks_every_phase(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--manifest", str(TINY / "BENCHMARK.json"),
         "--traffic-dir", str(TINY / "traffic"), "--out", str(tmp_path / "out"),
         "--workload", "tiny.retention", "--seed", str(2 ** 31 + 91),
         "--seconds", "8", "--trace", "1", "--rehearse"],
        cwd=str(ROOT), env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["state_slots_peak_share"]["value"] == 100.0
    # no chip: neither a share of its memory nor a device metric is made up
    for name in ("state_pool_share", "retention_kernel_share",
                 "retention_update_roofline", "retention_pass_roofline"):
        assert name not in line["metrics"]
    detail = json.loads((tmp_path / "out" / "detail.json").read_text())
    assert detail["compiles_in_window"] == []
    assert detail["reference"]["repeat_identical"] and detail["reference"]["within"]
    assert detail["primed"]["launch_windows"] == [4, 1, 2]
    pool = detail["counters"]["after"]["state_pool"]
    assert pool["slots"] == 4 and pool["in_use_peak"] == 4 and pool["resets"] > 4
    assert detail["counters"]["after"]["kv_pool"] is None


def test_the_rehearsal_manifest_keeps_the_contracts_form():
    manifest = json.loads((TINY / "BENCHMARK.json").read_text())
    root = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(manifest) == set(root)
    by_name = {m["name"]: m for m in root["per_layer"]}
    for m in manifest["per_layer"]:
        assert {k: v for k, v in m.items() if k != "workloads"} == \
            {k: v for k, v in by_name[m["name"]].items() if k != "workloads"}
        assert m["workloads"] == ["tiny.retention"]
    cfg = sut.load_config(ROOT / manifest["configs"][0]["file"])
    assert cfg["engine"]["cache"] == "state" and cfg["reference"] == "brumby"


# -------------------------------------------------- the configuration file

def test_the_configuration_keeps_every_published_width():
    cfg = brumby()
    model = sut.model_block(cfg)
    assert (model["dim"], model["ffn_dim"], model["n_heads"], model["n_kv_heads"],
            model["head_dim"], model["vocab_size"]) == (5120, 17408, 40, 8, 128, 151936)
    assert model["n_layers"] == 12 and cfg["published"] == {"num_hidden_layers": 40}
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert (model["attention"], model["qk_norm"], model["retention_degree"]) == \
        ("power_retention", True, 2)
    assert model["norm_eps"] == 1e-6 and model["rope_theta"] == 1000000
    for key in ("retention_degree", "gate", "qk_norm", "normalisation",
                "state_dtype", "state_layout", "weights", "tokenizer"):
        assert cfg["assumed"][key]
    assert cfg["engine"] == {
        "cache": "state", "scheduler": "ragged", "weight_quant": "int8",
        "prefix_cache": 0, "max_batch": 16, "max_seq_len": 5120, "warmup": "off"}
    assert cfg["probes"]["prompt_tokens"] == [40, 200, 600, 4096]
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(x) for x in catalog.read_text().splitlines()
                   if '"Brumby-14B-Base"' in x)
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key


def manifest_reports_the_state_cell_as_the_issue_wrote(manifest):
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("brumby-14b-d12", "offline_long_decode", 1)
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"tpot_p50_ms", "out_tok_s", "setup_s", "req_ttft_p50_ms",
            "req_tpot_p90_ms", "state_pool_share", "state_slots_peak_share",
            "retention_kernel_share", "retention_update_roofline",
            "retention_chunk_roofline", "retention_pass_roofline"} <= reported
    assert not {"model_pass_roofline", "kv_pool_move_share", "prefix_hit_share",
                "kv_pool_used_peak_share", "kv_pool_peak_share"} & reported


def test_the_cell_and_its_traffic_are_what_the_issue_wrote():
    from benchmark import traffic

    manifest_reports_the_state_cell_as_the_issue_wrote(
        json.loads((ROOT / "BENCHMARK.json").read_text()))
    mix = traffic.load_mix("offline_long_decode")
    assert (mix["loop"], mix["clients"], mix["ramp_s"], mix["drain_s"],
            mix["stagger_first"]) == ("closed", 16, 12, 0, "answer")
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 0.4, "min": 1024, "max": 4096}
    assert mix["answer_tokens"] == {"dist": "lognormal", "median": 448,
                                    "sigma": 0.35, "min": 256, "max": 768}
    plan = traffic.make_plan("offline_long_decode", "brumby-14b-d12", 2 ** 31 + 5, 51)
    assert len(plan["clients"]) == 16 and all(len(c) >= 6 for c in plan["clients"])


# ----------------------------------------------------- roofline_retention

def test_roofline_retention_counts():
    model = sut.model_block(brumby())
    assert rr.feature_rows(model) == 8256
    # per layer and slot: 8 heads x 8256 x (128 + 1) float32
    assert rr.slot_bytes(model, layers=1) == 8 * 8256 * 129 * 4
    assert rr.slot_bytes(model) == 12 * rr.slot_bytes(model, layers=1)
    assert rr.update_bytes(model, 16) == 2 * 16 * rr.slot_bytes(model)
    # ISSUE 26: 2 x 8256 x 128 x (40 + 8) = 101 MFLOP per token and layer
    per_token_layer = 2 * 8256 * 128 * 48
    assert rr.update_flops(model, 1) == 12 * per_token_layer
    assert rr.chunk_flops(model, 0, 0) == 0.0
    one_chunk = rr.chunk_flops(model, 112, 1)
    assert one_chunk == 12 * (per_token_layer * 112 + 4 * 128 * 40 * 112 * 113 / 2)
    assert rr.chunk_bytes(model, 3) == rr.update_bytes(model, 3)
    passes = rr.pass_bytes(model, 4, 64, 2)
    assert passes == 4 * roofline.weight_bytes(model) + 66 * 2 * rr.slot_bytes(model)
    flops = rr.pass_flops(model, 64, 112, 1, 64)
    assert flops == pytest.approx(
        2 * 12 * roofline.layer_matmul_params(model, True) * 176
        + 2 * 5120 * 151936 * 64 + rr.update_flops(model, 64) + one_chunk)
    # a decode pass of 16 rows is bound by memory, its state 3/4 of the bytes
    peaks = roofline.peaks_for("TPU v5 lite")
    least = roofline.min_seconds(rr.pass_flops(model, 16, 0, 0, 16),
                                 rr.pass_bytes(model, 1, 16, 0), peaks)
    assert least["bound"] == "memory" and 0.020 < least["seconds"] < 0.024
    share = rr.update_bytes(model, 16) / rr.pass_bytes(model, 1, 16, 0)
    assert 0.70 < share < 0.78


# ------------------------------------------------ readers, recorded context

def recorded(ragged_after, ops, busy_s=4.0, state_pool=True):
    pool = {"slots": 16, "in_use": 16, "in_use_peak": 16, "bytes": 6599737344,
            "bytes_per_slot": 412483584, "resets": 40}
    before = {"ragged": {"decode_tokens": 1000, "prefill_tokens": 5000, "passes": 100,
                         "step_rows": {"prefill": 50, "decode": 900}},
              "state_pool": pool if state_pool else None}
    after = {"ragged": ragged_after, "state_pool": pool if state_pool else None}
    return {
        "cfg": brumby(), "before": before, "after": after,
        "trace_counters": (before, after),
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "trace": {"devices": 1, "busy_s": busy_s, "window_s": 5.0, "ops": ops},
    }


def tail():
    return {"decode_tokens": 2280, "prefill_tokens": 13064, "passes": 190,
            "step_rows": {"prefill": 122, "decode": 2100}}


def test_pool_readers_read_the_state_pool_block():
    ctx = recorded(tail(), [])
    assert state_pool_share.read(ctx) == pytest.approx(100 * 6599737344 / 17179869184)
    assert state_slots_peak_share.read(ctx) == 100.0
    ctx["device"] = {"platform": "cpu", "kind": "cpu"}
    assert state_pool_share.read(ctx) is None
    bare = recorded(tail(), [], state_pool=False)
    assert state_pool_share.read(bare) is None
    assert state_slots_peak_share.read(bare) is None


def test_kernel_readers_find_the_kernels_by_name():
    ops = [("power_retention_update.3_custom-call_f32_16_8_8_128", 2.0, 19000),
           ("power_retention_chunk.1_custom-call_f32_8_5_376_128", 0.5, 900),
           ("fusion.12_fusion_bf16_240_17408", 0.8, 1000)]
    ctx = recorded(tail(), ops)
    assert retention_kernel_share.read(ctx) == pytest.approx(62.5)
    model = sut.model_block(ctx["cfg"])
    peaks = roofline.peaks_for("TPU v5 lite")
    want = rr.update_bytes(model, 1280) / peaks["hbm_bytes_per_s"] / 2.0
    assert retention_update_roofline.read(ctx) == pytest.approx(100 * want)
    assert 0 < retention_update_roofline.read(ctx) < 100
    flops = rr.chunk_flops(model, 8064, 72)
    nbytes = rr.chunk_bytes(model, 72)
    want = max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]) / 0.5
    assert retention_chunk_roofline.read(ctx) == pytest.approx(100 * want)
    least = roofline.min_seconds(
        rr.pass_flops(model, 1280, 8064, 72, 1280),
        rr.pass_bytes(model, 90, 1280, 72), peaks)
    assert retention_pass_roofline.read(ctx) == pytest.approx(100 * least["seconds"] / 4.0)
    assert 0 < retention_pass_roofline.read(ctx) < 100


def test_readers_return_nothing_where_the_program_has_no_state_cache():
    """The parent's program (and every K/V cell): no ``state_pool`` block, no
    such counters, no such kernels. Nothing is read and nothing raises."""
    ops = [("ragged_paged_attention.1_custom-call_bf16_352_8_4_128", 1.0, 300)]
    ragged = {"decode_tokens": 2280, "step_rows": {"prefill": 122}}
    ctx = recorded(ragged, ops, state_pool=False)
    ctx["before"]["ragged"] = {"decode_tokens": 1000, "step_rows": {"prefill": 50}}
    for reader in (retention_kernel_share, retention_update_roofline,
                   retention_chunk_roofline, retention_pass_roofline,
                   state_pool_share, state_slots_peak_share):
        assert reader.read(ctx) is None, reader.__name__
    ctx["trace"] = None
    ctx.pop("trace_counters")
    for reader in (retention_kernel_share, retention_update_roofline,
                   retention_chunk_roofline, retention_pass_roofline):
        assert reader.read(ctx) is None, reader.__name__
