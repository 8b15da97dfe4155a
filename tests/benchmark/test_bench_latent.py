"""The latent page layout's cell: a CPU rehearsal of it at tiny widths (new
files under tests/benchmark/tiny_latent: the accepted tiny manifest may not
be edited), the configuration against the catalog, the counts of
roofline_latent.py, each new reader on a recorded context, and a hold by
name on every entry the cell brought."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import roofline, roofline_latent as rl, sut  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    index_kept_share, index_select_share, latent_decode_roofline,
    latent_pass_roofline, latent_ragged_roofline, moe_experts_hit_share,
)
from tests.benchmark.test_bench_manifest import holds_entry  # noqa: E402

TINY = ROOT / "tests" / "benchmark" / "tiny_latent"
CELL, CONFIG = "dots3note.doc_sessions", "dots3-note-prev-ep8"
MODEL_STEP = "model step (models/llama.py forward_ragged)"
KERNELS = "kernels (ops/paged_attention.py)"
CACHE = "cache (llm/kv_cache.py, llm/prefix_cache.py)"
NEW = {
    "latent_pass_roofline": ("higher", "device_trace", MODEL_STEP, "tpot_p50_ms"),
    "latent_decode_roofline": ("higher", "device_trace", KERNELS, "tpot_p50_ms"),
    "latent_ragged_roofline": ("higher", "device_trace", KERNELS, "ttft_p50_ms"),
    "index_select_share": ("lower", "device_trace", KERNELS, "tpot_p50_ms"),
    "index_kept_share": ("lower", "program_counter", CACHE, "tpot_p50_ms"),
    "moe_experts_hit_share": ("lower", "program_counter", MODEL_STEP,
                              "tpot_p50_ms"),
}


def dots3():
    return sut.load_config(ROOT / "benchmark" / "configs" / (CONFIG + ".json"))


def root_manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


# ---------------------------------------------------------------- rehearsal

def test_rehearsal_of_the_latent_cell_walks_every_phase(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--manifest", str(TINY / "BENCHMARK.json"),
         "--traffic-dir", str(TINY / "traffic"), "--out", str(tmp_path / "out"),
         "--workload", "tiny.latent", "--seed", str(2 ** 31 + 91),
         "--seconds", "8", "--trace", "1", "--rehearse"],
        cwd=str(ROOT), env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    metrics = line["metrics"]
    # contexts of 200-400 tokens against a top-16: the selection is sparse
    assert 0 < metrics["index_kept_share"]["value"] < 30
    assert 0 < metrics["moe_experts_hit_share"]["value"] <= 100
    assert metrics["prefix_hit_share"]["value"] > 50
    assert metrics["kv_pool_used_peak_share"]["value"] > 0
    # no chip: no device metric is made up
    for name in ("latent_pass_roofline", "latent_decode_roofline",
                 "latent_ragged_roofline", "index_select_share",
                 "hbm_peak_share"):
        assert name not in metrics
    detail = json.loads((tmp_path / "out" / "detail.json").read_text())
    assert detail["compiles_in_window"] == []
    assert detail["reference"]["repeat_identical"] and detail["reference"]["within"]
    after = detail["counters"]["after"]
    assert after["state_pool"] is None and after["kv_pool"]["num_pages"] == 1200
    assert after["latent"]["decode_latent_tokens"] > 0
    assert after["moe"]["experts_held"] == 4
    assert after["prefix"]["hit_tokens"] > 0


def test_the_rehearsal_manifest_keeps_the_contracts_form():
    manifest = json.loads((TINY / "BENCHMARK.json").read_text())
    root = root_manifest()
    assert set(manifest) == set(root)
    by_name = {m["name"]: m for m in root["per_layer"]}
    for m in manifest["per_layer"]:
        assert {k: v for k, v in m.items() if k != "workloads"} == \
            {k: v for k, v in by_name[m["name"]].items() if k != "workloads"}
        assert "tiny.latent" in m["workloads"]
    assert set(NEW) <= {m["name"] for m in manifest["per_layer"]}
    cfg = sut.load_config(ROOT / manifest["configs"][0]["file"])
    assert cfg["engine"]["cache"] == "paged" and cfg["reference"] == "dots3_note"
    assert cfg["arch"] == "dots3_note"


# -------------------------------------------------- the configuration file

def test_the_configuration_keeps_every_published_width():
    cfg = dots3()
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(x) for x in catalog.read_text().splitlines()
                   if '"dots3-note-prev"' in x)
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
        assert cfg["layer_types"] == row["config"]["layer_types"][:10]
        assert cfg["published"]["num_hidden_layers"] == 46
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "n_routed_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (10, 32, 19008)
    assert cfg["published"]["n_routed_experts"] == 256
    assert cfg["published"]["vocab_size"] == 152064 == 8 * cfg["vocab_size"]
    # no width among the cuts, and what the program is built from is the
    # published value, key for key
    for key, value in cfg["build"].items():
        if key in cfg and key != "layer_types":
            assert cfg[key] == value, key
    model = sut.model_block(cfg)
    assert (model["dim"], model["ffn_dim"], model["n_heads"], model["n_layers"],
            model["moe_top_k"], model["vocab_size"]) == \
        (5120, 13824, 128, 10, 8, 19008)
    assert (model["router_experts"], model["experts_held"]) == (256, [0, 32])
    assert model["layer_types"] == cfg["layer_types"] and model["scan_layers"]
    for key in ("apply_mla_qkv_lora_rescale", "index_rope",
                "sliding_window_size", "weights", "tokenizer", "topk_method"):
        assert cfg["assumed"][key]
    assert cfg["engine"] == {
        "cache": "paged", "scheduler": "ragged", "weight_quant": "int8",
        "prefix_cache": 4096, "max_batch": 32, "max_seq_len": 28672,
        "num_pages": 12289, "warmup": "off"}
    assert cfg["probes"]["prompt_tokens"] == [40, 600, 4096, 12288]
    assert (cfg["engine"]["num_pages"] - 1) * 16 == 196608


def test_the_parent_cannot_build_the_architecture(monkeypatch):
    """A program without this PR's model fails the cell at once, by name:
    ``arch`` is the model's own."""
    from clearml_serving_tpu import models

    monkeypatch.delitem(models._BUILDERS, "dots3_note")
    with pytest.raises(ValueError, match="unknown model arch 'dots3_note'"):
        models.build_model(dots3()["arch"], sut.model_block(dots3()))


def manifest_reports_the_latent_cell_as_the_issue_wrote(manifest):
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "doc_sessions", 1)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["file"] == "benchmark/configs/{}.json".format(CONFIG)
    assert entry["reduced"] == dots3()["reduced"]
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"ttft_p50_ms", "tpot_p50_ms", "setup_s", "prefix_hit_share",
            "kv_pool_peak_share", "kv_pool_used_peak_share", "hbm_peak_share",
            "req_tpot_p90_ms", "req_slo_share"} | set(NEW) <= reported
    assert not {"out_tok_s", "model_pass_roofline", "attn_decode_roofline",
                "state_pool_share", "retention_pass_roofline"} & reported


def manifest_holds_the_latent_entries(manifest):
    for name, (better, source, layer, moves) in NEW.items():
        holds_entry(manifest, {
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [CELL]})
    # entries that exist stand in the order they were handed in
    order = [m["name"] for m in manifest["per_layer"] if m["name"] in NEW]
    assert order == list(NEW)


def test_the_cell_and_its_traffic_are_what_the_issue_wrote():
    from benchmark import traffic

    manifest_reports_the_latent_cell_as_the_issue_wrote(root_manifest())
    manifest_holds_the_latent_entries(root_manifest())
    mix = traffic.load_mix("doc_sessions")
    assert (mix["loop"], mix["ramp_s"], mix["drain_s"]) == ("open", 12, 22)
    assert mix["system_prompt_tokens"] == [8192, 12288, 16384, 24576]
    assert mix["system_prompt"] == {"dist": "zipf", "values": [0, 1, 2, 3], "s": 1.0}
    assert mix["turns"] == {"dist": "choice", "values": [1, 2, 3, 4]}
    assert mix["user_tokens"] == {"dist": "lognormal", "median": 128,
                                  "sigma": 0.6, "min": 32, "max": 512}
    assert mix["answer_tokens"] == {"dist": "lognormal", "median": 96,
                                    "sigma": 0.45, "min": 32, "max": 256}
    assert mix["think_s"] == {"dist": "uniform", "min": 2.0, "max": 6.0}
    assert set(mix["session_rate_per_s"]) == {CONFIG} == set(mix["limits"])
    plan = traffic.make_plan("doc_sessions", CONFIG, 2 ** 31 + 5, 51)
    judged = [r for r in plan["requests"] if r["judged"]]
    assert len(judged) >= 10
    longest = max(traffic.prompt_tokens(r["messages"])
                  for r in plan["requests"] if not r["after"])
    assert 24576 < longest + 4 * (512 + 256) <= dots3()["engine"]["max_seq_len"]
    # the four documents fit the prefix cache's default budget as latent pages
    page_bytes = 16 * 2 * (4 * (640 + 128) + 6 * 1152)
    assert sum(mix["system_prompt_tokens"]) // 16 * page_bytes < 2 << 30


# --------------------------------------------------------- roofline_latent

def test_roofline_latent_counts_the_issues_arithmetic():
    model = sut.model_block(dots3())
    assert rl.layer_counts(model) == {"full": 4, "window": 6, "dense": 1, "moe": 9}
    full, window = (rl.attention_params(model, k) for k in (rl.FULL, rl.WINDOW))
    assert 144.0e6 < full < 144.1e6 and 90.8e6 < window < 90.9e6
    assert rl.expert_params(model) == 3 * 5120 * 1536
    fixed = rl.fixed_params(model)
    assert fixed == (4 * full + 6 * window + 3 * 5120 * 13824
                     + 9 * (5120 * 256 + rl.expert_params(model)) + 5120 * 19008)
    assert 1.6e9 < fixed < 1.7e9
    assert (rl.row_bytes(model, rl.FULL), rl.row_bytes(model, rl.WINDOW),
            rl.index_key_bytes(model)) == (1152, 2176, 256)
    # a decode pass of 32 rows at 16k tokens: 63% of the held experts hit
    hit = 0.63 * 32 * 9
    nbytes = rl.pass_bytes(model, 1, hit, 4 * 32 * 16384, 4 * 32 * 2048,
                           6 * 32 * 513, 32)
    assert 6.0e9 < nbytes < 7.2e9
    peaks = roofline.peaks_for("TPU v5 lite")
    least = roofline.min_seconds(
        rl.pass_flops(model, 32, 32, 32 * 8 / 8 * 9, 4 * 32 * 16384,
                      4 * 32 * 2048, 6 * 32 * 513), nbytes, peaks)
    assert least["bound"] == "memory" and 0.007 < least["seconds"] < 0.009
    assert rl.attention_flops(model, 10, 0) == 2.0 * 128 * 320 * 10
    assert rl.index_flops(model, 10) == 2.0 * 64 * 128 * 10


# ------------------------------------------------ readers on recorded edges

def recorded(ops, busy_s=4.0, latent=True):
    model = sut.model_block(dots3())
    zero = {k: 0 for k in (
        "rows_full", "rows_window", "index_keys_scored", "index_keys_kept",
        "window_keys", "decode_keys_full", "decode_keys_window",
        "mixed_keys_full", "mixed_keys_window", "decode_latent_tokens")}
    # 100 decode chunks of 4 passes, 32 rows at 16k; 10 mixed passes of 128
    chain = 100 * 4 * 32
    mixed = 10 * 128
    after = {
        "rows_full": 4 * (chain + mixed), "rows_window": 6 * (chain + mixed),
        "index_keys_scored": 4 * (chain + mixed) * 16384,
        "index_keys_kept": 4 * (chain + mixed) * 2048,
        "window_keys": 6 * (chain + mixed) * 513,
        "decode_keys_full": 4 * chain * 2048,
        "decode_keys_window": 6 * chain * 513,
        "mixed_keys_full": 4 * 10 * 2048, "mixed_keys_window": 6 * 10 * 640,
        "decode_latent_tokens": 4 * chain * 2048 + 6 * chain * 513,
    }
    before = {"latent": zero, "ragged": {"passes": 0, "decode_tokens": 0},
              "moe": {"experts_held": 32, "experts_hit": 0,
                      "local_assignments": 0, "layer_passes": 0}}
    edge = {"latent": after, "ragged": {"passes": 10, "decode_tokens": 0},
            "moe": {"experts_held": 32, "experts_hit": 410 * 9 * 20,
                    "local_assignments": (chain + mixed) * 9,
                    "layer_passes": 410 * 9}}
    if not latent:
        for e in (before, edge):
            del e["latent"], e["moe"]
    return {
        "cfg": dots3(), "device": {"kind": "TPU v5 lite"},
        "before": before, "after": edge, "trace_counters": (before, edge),
        "trace": {"devices": 1, "busy_s": busy_s, "ops": ops},
    }, model


def test_the_counter_readers_read_the_programs_blocks():
    ctx, _ = recorded([])
    assert index_kept_share.read(ctx) == pytest.approx(12.5)
    assert moe_experts_hit_share.read(ctx) == pytest.approx(100 * 20 / 32)


def test_the_roofline_readers_find_the_kernels_by_name():
    ops = [("latent_attention_decode.3_custom-call_bf16_32_128_512", 0.9, 1600),
           ("latent_ragged_attention.5_custom-call_bf16_352_64_1024", 0.2, 100),
           ("fusion.7_fusion_bf16_128_5120", 1.0, 50)]
    ctx, model = recorded(ops)
    peaks = roofline.peaks_for("TPU v5 lite")
    chain = 100 * 4 * 32
    want = (4 * chain * 2048 * 1152 + 6 * chain * 513 * 2176) \
        / peaks["hbm_bytes_per_s"] / 0.9
    assert latent_decode_roofline.read(ctx) == pytest.approx(100 * want)
    assert 0 < latent_decode_roofline.read(ctx) < 100
    ragged = latent_ragged_roofline.read(ctx)
    flops = rl.attention_flops(model, 4 * 1280 * 2048, 6 * 1280 * 513)
    assert ragged == pytest.approx(
        100 * flops / peaks["bf16_flops_per_s"] / 0.2)
    share = latent_pass_roofline.read(ctx)
    assert 0 < share < 100
    # the same work in half the busy time is twice the share
    assert latent_pass_roofline.read(recorded(ops, busy_s=2.0)[0]) == \
        pytest.approx(2 * share)


def test_a_trace_without_the_kernels_gives_nothing_to_read():
    ctx, _ = recorded([("fusion.7_fusion_bf16_128_5120", 1.0, 50)])
    assert latent_decode_roofline.read(ctx) is None
    assert latent_ragged_roofline.read(ctx) is None
    assert index_select_share.read(ctx) is None          # no trace file


@pytest.mark.parametrize("reader", [
    latent_pass_roofline, latent_decode_roofline, latent_ragged_roofline,
    index_select_share, index_kept_share, moe_experts_hit_share])
def test_readers_return_nothing_where_the_program_has_no_latent_layout(reader):
    """The parent's program, under this PR's benchmark files: no block, no
    metric, no error."""
    ctx, _ = recorded([("paged_attention_decode.1_custom-call", 1.0, 10)],
                      latent=False)
    assert reader.read(ctx) is None
    assert reader.read(dict(ctx, trace=None, trace_counters=None)) is None
