"""The hybrid cache's cell (falconh1.chat_short): a CPU rehearsal of it at tiny
widths (new files under tests/benchmark/tiny_hybrid: the accepted tiny
manifests may not be edited), the configuration against the catalog, the
traffic mix and its pinned plan, the counts of roofline_ssd.py against
arithmetic written out here at the published widths, each new reader on
hand-counted counters, and a hold by name on every entry the cell brought."""

import hashlib
import json
import os
import subprocess
import sys
from collections import Counter
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import roofline, roofline_ssd as rs, sut, traffic  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    hybrid_pass_roofline, ssd_chunk_roofline, ssd_kernel_share,
    ssd_update_roofline,
)
from tests.benchmark.test_bench_manifest import holds_entry  # noqa: E402

TINY = ROOT / "tests" / "benchmark" / "tiny_hybrid"
CELL, CONFIG = "falconh1.chat_short", "falcon-h1-34b-d8"
MODEL_STEP = "model step (models/llama.py forward_ragged)"
KERNELS = "kernels (ops/paged_attention.py)"
NEW = {
    "ssd_kernel_share": ("lower", KERNELS, "tpot_p50_ms"),
    "ssd_update_roofline": ("higher", KERNELS, "tpot_p50_ms"),
    "ssd_chunk_roofline": ("higher", KERNELS, "ttft_p50_ms"),
    "hybrid_pass_roofline": ("higher", MODEL_STEP, "tpot_p50_ms"),
}
LISTED = (
    "front_overhead_ms", "gen_late_p95_ms", "req_ttft_p90_ms", "req_slo_share",
    "req_tpot_p90_ms", "req_out_tok_s", "front_ttft_gap_ms",
    "kv_pool_peak_share", "kv_pool_used_peak_share", "kv_pool_move_share",
    "state_pool_share", "state_slots_peak_share", "attn_decode_roofline",
    "eng_queue_wait_ms", "eng_admit_ms", "eng_prefill_ms", "eng_ttft_ms",
    "eng_prefill_launches", "eng_first_launch_wait_ms", "eng_prefill_span_ms",
    "eng_first_emit_ms", "launch_hop_ms", "launch_upload_ms",
    "launch_enqueue_ms", "launch_tail_ms", "wait_readback_ms", "starve_ms",
    "starve_share", "idle_seen_share", "ragged_launch_share",
    "dense_rows_per_launch",
)
# sha256 of json.dumps(plan["requests"], sort_keys=True) of make_plan(
# "chat_short", CONFIG, 7, 51): what every window of the cell sends
PLAN_DIGEST = "102c27968837ef2af53dcf9067a0d72616d4d2a5ed3c7ac9ea6e8022ef9af2c8"


def falcon():
    return sut.load_config(ROOT / "benchmark" / "configs" / (CONFIG + ".json"))


def root_manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


# ---------------------------------------------------------------- rehearsal

def test_rehearsal_of_the_hybrid_cell_walks_every_phase(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--manifest", str(TINY / "BENCHMARK.json"),
         "--traffic-dir", str(TINY / "traffic"), "--out", str(tmp_path / "out"),
         "--workload", "tiny.hybrid", "--seed", str(2 ** 31 + 91),
         "--seconds", "8", "--trace", "1", "--rehearse"],
        cwd=str(ROOT), env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics["state_slots_peak_share"]["value"] > 0
    assert metrics["kv_pool_used_peak_share"]["value"] > 0
    assert metrics["dense_rows_per_launch"]["value"] > 0
    assert 0 < metrics["ragged_launch_share"]["value"] <= 100
    # no chip: no device metric is made up
    for name in list(NEW) + ["attn_decode_roofline", "state_pool_share",
                             "kv_pool_move_share", "idle_seen_share"]:
        assert name not in metrics
    detail = json.loads((tmp_path / "out" / "detail.json").read_text())
    assert detail["compiles_in_window"] == []
    assert detail["reference"]["repeat_identical"] and detail["reference"]["within"]
    after = detail["counters"]["after"]
    assert after["kv_pool"]["num_pages"] == 300 and after["prefix"] is None
    ssm, pool = after["ssm"], after["state_pool"]
    assert pool["slots"] == 4 and set(pool["planes"]) == {"h", "conv"}
    assert ssm["update_rows"] > 0 and ssm["chunk_tokens"] > ssm["chunk_rows"] > 0
    assert ssm["resets"] == pool["resets"] > 0 and ssm["layers"] == 3
    assert ssm["passes"] == after["sampler"]["passes"]
    kernels = after["kernels"]
    assert kernels["ssd_update"] == kernels["ssd_chunk"] == kernels["ragged"]


def test_the_rehearsal_manifest_keeps_the_contracts_form():
    manifest = json.loads((TINY / "BENCHMARK.json").read_text())
    root = root_manifest()
    assert set(manifest) == set(root)
    by_name = {m["name"]: m for m in root["per_layer"]}
    for m in manifest["per_layer"]:
        assert {k: v for k, v in m.items() if k != "workloads"} == \
            {k: v for k, v in by_name[m["name"]].items() if k != "workloads"}
        assert "tiny.hybrid" in m["workloads"]
    assert set(NEW) | set(LISTED) <= {m["name"] for m in manifest["per_layer"]}
    cfg = sut.load_config(ROOT / manifest["configs"][0]["file"])
    assert cfg["engine"]["cache"] == "paged" and cfg["reference"] == "falcon_h1"
    assert cfg["arch"] == "falcon_h1" and cfg["engine"]["prefix_cache"] == 0


# -------------------------------------------------- the configuration file

def test_the_configuration_keeps_every_published_width():
    cfg = falcon()
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(x) for x in catalog.read_text().splitlines()
                   if '"Falcon-H1-34B-Instruct"' in x)
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
        assert cfg["published"]["num_hidden_layers"] == 72 == \
            row["config"]["num_hidden_layers"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"]) == (5120, 20, 4, 128, 21504, 261120, 8)
    assert (cfg["mamba_d_ssm"], cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_d_conv"],
            cfg["mamba_chunk_size"]) == (4096, 32, 128, 256, 2, 4, 128)
    model = sut.model_block(cfg)
    assert (model["dim"], model["ffn_dim"], model["n_heads"],
            model["n_kv_heads"], model["head_dim"], model["n_layers"],
            model["vocab_size"], model["rope_theta"]) == \
        (5120, 21504, 20, 4, 128, 8, 261120, 100000000000)
    # what the program is built from is the published value, key for key
    for key, value in cfg["build"].items():
        if key not in ("scan_layers", "max_seq_len"):
            assert value == cfg[key], key
    assert model["scan_layers"] and not model["tie_embeddings"]
    for key in ("mixer", "state_dtype", "state_layout", "grouped_norm", "dt",
                "weights", "tokenizer", "rotary_layout"):
        assert cfg["assumed"][key]
    assert "FIRST of nine pipeline stages" in cfg["deployment"]
    assert {k: cfg["engine"][k] for k in (
        "cache", "scheduler", "weight_quant", "prefix_cache", "max_batch",
        "max_seq_len", "num_pages", "warmup")} == {
        "cache": "paged", "scheduler": "ragged", "weight_quant": "int8",
        "prefix_cache": 0, "max_batch": 64, "max_seq_len": 4096,
        "num_pages": 8193, "warmup": "off"}
    assert cfg["probes"]["prompt_tokens"] == [40, 200, 600, 2000]
    assert cfg["probes"]["new_tokens"] == 12
    tol = cfg["probes"]["tolerance"]
    assert 0 < tol["typical"] < tol["outlier"] and tol["outlier_share"] == 0.0
    assert "bfloat16" in tol["measured"]


def test_the_parent_cannot_build_the_architecture(monkeypatch):
    """A program without this PR's model fails the cell at once, by name."""
    from clearml_serving_tpu import models

    monkeypatch.delitem(models._BUILDERS, "falcon_h1")
    with pytest.raises(ValueError, match="unknown model arch 'falcon_h1'"):
        models.build_model(falcon()["arch"], sut.model_block(falcon()))


def test_the_cell_is_what_the_issue_wrote():
    manifest = root_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "chat_short", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["file"] == "benchmark/configs/{}.json".format(CONFIG)
    assert entry["reduced"] == falcon()["reduced"]
    assert entry["source"] == falcon()["source"]
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"ttft_p50_ms", "tpot_p50_ms", "setup_s", "hbm_peak_share"} \
        | set(NEW) | set(LISTED) <= reported
    # an open loop's tokens per second is its offered rate; the other
    # rooflines count another model; no page is shared
    assert not {"out_tok_s", "model_pass_roofline", "retention_pass_roofline",
                "latent_pass_roofline", "window_pass_roofline",
                "prefix_hit_share"} & reported
    for name, (better, layer, moves) in NEW.items():
        holds_entry(manifest, {
            "name": name, "unit": "%", "better": better,
            "source": "device_trace", "layer": layer, "moves": moves,
            "workloads": [CELL]})
    order = [m["name"] for m in manifest["per_layer"] if m["name"] in NEW]
    assert order == list(NEW)


# ------------------------------------------------------------- the traffic

def test_chat_short_is_what_the_issue_wrote():
    mix = traffic.load_mix("chat_short")
    assert (mix["loop"], mix["ramp_s"], mix["drain_s"]) == ("open", 12, 22)
    assert mix["turns"] == {"dist": "choice", "values": [1, 2, 3]}
    assert mix["system_prompt"] == {"dist": "zipf", "values": [0, 1, 2, 3],
                                    "s": 1.0}
    assert mix["system_prompt_tokens"] == [64, 96, 128, 192]
    assert mix["user_tokens"] == {"dist": "lognormal", "median": 64,
                                  "sigma": 0.6, "min": 16, "max": 256}
    assert mix["answer_tokens"] == {"dist": "lognormal", "median": 160,
                                    "sigma": 0.5, "min": 64, "max": 384}
    assert mix["think_s"] == {"dist": "uniform", "min": 2.0, "max": 6.0}
    assert set(mix["session_rate_per_s"]) == {CONFIG} == set(mix["knee"]) \
        == set(mix["limits"])
    assert "0.8" in mix["what"] and "sessions/s" in mix["knee"][CONFIG]
    limits = mix["limits"][CONFIG]
    assert limits["ttft_ms"] > 0 and limits["tpot_ms"] > 0 and limits["from"]
    cfg = falcon()
    # the longest history: 3 turns at the clips, under the engine's length
    assert 192 + 3 * 256 + 3 * 384 + 200 < cfg["engine"]["max_seq_len"]


@pytest.mark.parametrize("seeds", [(7, 2 ** 31 + 5), (2 ** 31 + 77, 123456)])
def test_the_plan_holds_the_same_requests_under_any_seed(seeds):
    def shape(seed):
        plan = traffic.make_plan("chat_short", CONFIG, seed, 51)
        judged = [r for r in plan["requests"] if r["judged"]]
        return len(judged), Counter(r["max_tokens"] for r in judged)

    a, b = shape(seeds[0]), shape(seeds[1])
    assert a == b and a[0] >= 100
    assert 64 <= min(a[1]) and max(a[1]) <= 384


def test_the_plan_of_chat_short_is_pinned():
    plan = traffic.make_plan("chat_short", CONFIG, 7, 51)
    digest = hashlib.sha256(json.dumps(
        plan["requests"], sort_keys=True).encode()).hexdigest()
    assert digest == PLAN_DIGEST
    first = [r for r in plan["requests"] if not r["after"]]
    systems = Counter(len(r["messages"][0]["content"]) for r in first)
    assert set(systems) <= {64, 96, 128, 192}
    assert systems.most_common(1)[0][0] == 64          # Zipf: rank one first


# ------------------------------------------------------------ roofline_ssd

def test_roofline_ssd_counts_the_issues_arithmetic():
    model = sut.model_block(falcon())
    # a row's state of one layer: 32 heads x 256 x 128 float32 = 4.19 MB
    assert rs.state_elements(model) == 32 * 256 * 128 == 1048576
    assert rs.state_bytes(model) == 4194304
    # read and written: 8.39 MB a row and layer, 67.1 MB over eight layers
    assert rs.update_bytes(model, 1) == 2 * 4194304 * 8 == 67108864
    assert rs.update_flops(model, 1) == 5 * 1048576 * 8
    # in_proj 5120 x 9248 = 47.35 M, out_proj 4096 x 5120 = 20.97 M,
    # q/k/v/o 13.11 + 2.62 + 2.62 + 13.11 = 31.46 M, MLP 330.30 M: 430.1 M
    assert 5120 * (2 * 4096 + 2 * 2 * 256 + 32) == 47349760
    assert 4096 * 5120 == 20971520
    attn = 5120 * 128 * (2 * 20 + 2 * 4)
    assert attn == 31457280 and 3 * 5120 * 21504 == 330301440
    assert rs.layer_matmul_params(model) == \
        47349760 + 20971520 + attn + 330301440 == 430080000
    # eight blocks 3.44 GB + the head 1.34 GB: 4.78 GB a pass
    assert rs.weight_bytes(model) == 8 * 430080000 + 5120 * 261120
    assert 4.77e9 < rs.weight_bytes(model) < 4.78e9
    assert rs.kv_bytes_per_token(model) == 16384 == \
        roofline.kv_bytes_per_token(model)
    # a chunk row: its state once, and a token's operands in float32
    assert rs.token_operand_bytes(model) == 4 * (2 * 4096 + 1024 + 32)
    assert rs.chunk_bytes(model, 2, 100) == 8 * (
        2 * 4194304 * 2 + 4 * 9248 * 100)
    assert rs.chunk_flops(model, 100) == 4.0 * 1048576 * 8 * 100
    # a decode pass of 64 rows at 500 tokens: the state is 45% of its bytes
    nbytes = rs.pass_bytes(model, 1, 64, 0, 0, 64 * 500)
    state = rs.update_bytes(model, 64)
    assert state == 64 * 67108864 and 0.44 < state / nbytes < 0.46
    assert nbytes == pytest.approx(
        rs.weight_bytes(model) + state + 16384 * (64 * 500 + 64))
    flops = rs.pass_flops(model, 64, 0, 64, 64 * 500)
    assert flops == pytest.approx(
        2.0 * 8 * 430080000 * 64 + 2.0 * 5120 * 261120 * 64
        + 4.0 * 1048576 * 8 * 64 + 4.0 * 8 * 20 * 128 * 64 * 500)
    least = roofline.min_seconds(flops, nbytes,
                                 roofline.peaks_for("TPU v5 lite"))
    assert least["bound"] == "memory" and 0.011 < least["seconds"] < 0.012
    # a mixed pass of 128 prompt tokens in 2 rows: still the weights' bytes
    mixed = roofline.min_seconds(
        rs.pass_flops(model, 0, 128, 2, 128 * 300),
        rs.pass_bytes(model, 1, 0, 2, 128, 2 * 364),
        roofline.peaks_for("TPU v5 lite"))
    assert mixed["bound"] == "memory" and 0.005 < mixed["seconds"] < 0.007


# ------------------------------------------------ readers on recorded edges

# hand-counted: 30 launches of one mixed pass + 3 chained passes; 40 rows
# decode at 500 tokens in every pass; 2 rows a launch bring 44 prompt tokens
# each onto 300 tokens of context
LAUNCHES, ROWS, CTX = 30, 40, 500
PASSES = LAUNCHES * 4
UPDATE_ROWS = PASSES * ROWS
CHUNK_ROWS, CHUNK_TOKENS = LAUNCHES * 2, LAUNCHES * 88


def recorded(ops, busy_s=4.0, ssm=True):
    mixed_kv = LAUNCHES * (ROWS * CTX + 2 * 344)
    pairs = LAUNCHES * (ROWS * CTX + 2 * (44 * 300 + 44 * 45 // 2))
    after = {
        "ssm": {"update_rows": UPDATE_ROWS, "chunk_rows": CHUNK_ROWS,
                "chunk_tokens": CHUNK_TOKENS, "passes": PASSES, "resets": 9,
                "rewinds": 0, "layers": 8},
        "ragged": {"decode_chain_kv_tokens": LAUNCHES * 3 * ROWS * (CTX + 2),
                   "mixed_kv_tokens": mixed_kv, "mixed_qk_pairs": pairs},
    }
    before = {"ssm": dict.fromkeys(after["ssm"], 0),
              "ragged": dict.fromkeys(after["ragged"], 0)}
    if not ssm:
        del before["ssm"], after["ssm"]
    return {
        "cfg": falcon(), "device": {"kind": "TPU v5 lite"},
        "before": before, "after": after, "trace_counters": (before, after),
        "trace": {"devices": 1, "busy_s": busy_s, "ops": ops},
    }, sut.model_block(falcon())


OPS = [("mamba2_ssd_update.3_custom-call_f32_65_2_16_128", 0.6, 960),
       ("mamba2_ssd_chunk.5_custom-call_f32_2_128_2048", 0.1, 240),
       ("paged_attention_decode.1_custom-call", 0.3, 720),
       ("fusion.7_fusion_bf16_128_5120", 2.0, 50)]


def test_the_readers_find_the_kernels_by_name():
    ctx, model = recorded(OPS)
    peaks = roofline.peaks_for("TPU v5 lite")
    assert ssd_kernel_share.read(ctx) == pytest.approx(100 * 0.7 / 4.0)
    want = UPDATE_ROWS * 67108864 / peaks["hbm_bytes_per_s"] / 0.6
    assert ssd_update_roofline.read(ctx) == pytest.approx(100 * want)
    assert 0 < ssd_update_roofline.read(ctx) < 100
    t_bytes = 8 * (2 * 4194304 * CHUNK_ROWS + 4 * 9248 * CHUNK_TOKENS) \
        / peaks["hbm_bytes_per_s"]
    t_ops = 4.0 * 1048576 * 8 * CHUNK_TOKENS / peaks["bf16_flops_per_s"]
    assert t_bytes > t_ops            # a chunk's state outweighs its tokens
    assert ssd_chunk_roofline.read(ctx) == pytest.approx(100 * t_bytes / 0.1)
    assert 0 < ssd_chunk_roofline.read(ctx) < 100
    share = hybrid_pass_roofline.read(ctx)
    assert 0 < share < 100
    assert hybrid_pass_roofline.read(recorded(OPS, busy_s=2.0)[0]) == \
        pytest.approx(2 * share)
    edge = ctx["after"]["ragged"]
    nbytes = (PASSES * rs.weight_bytes(model)
              + (UPDATE_ROWS + CHUNK_ROWS) * 67108864
              + 16384 * (edge["decode_chain_kv_tokens"]
                         + edge["mixed_kv_tokens"] + UPDATE_ROWS
                         + CHUNK_TOKENS))
    assert share == pytest.approx(100 * nbytes / peaks["hbm_bytes_per_s"] / 4.0)


def test_a_trace_without_the_kernels_gives_nothing_to_read():
    ctx, _ = recorded([("fusion.7_fusion_bf16_128_5120", 1.0, 50)])
    assert ssd_kernel_share.read(ctx) is None
    assert ssd_update_roofline.read(ctx) is None
    assert ssd_chunk_roofline.read(ctx) is None
    assert hybrid_pass_roofline.read(ctx) is not None


@pytest.mark.parametrize("reader", [
    ssd_update_roofline, ssd_chunk_roofline, hybrid_pass_roofline])
def test_readers_return_nothing_where_the_program_has_no_row_state(reader):
    """The parent's program, under this PR's benchmark files: no block, no
    metric, no error."""
    ctx, _ = recorded(OPS, ssm=False)
    assert reader.read(ctx) is None
    assert reader.read(dict(ctx, trace=None, trace_counters=None)) is None


def test_the_kernel_share_needs_a_trace_and_the_kernels():
    ctx, _ = recorded([("paged_attention_decode.1_custom-call", 1.0, 10)],
                      ssm=False)
    assert ssd_kernel_share.read(ctx) is None
    assert ssd_kernel_share.read(dict(ctx, trace=None)) is None
