"""The windowed paged path's cell (trinitymini.mixed_lengths): a CPU rehearsal
of it at tiny widths (new files under tests/benchmark/tiny_window: the
accepted tiny manifests may not be edited), the configuration against the
catalog, the traffic mix, the counts of roofline_window.py against
arithmetic written out here, each new reader on hand-counted counters, and a
hold by name on every entry the cell brought."""

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

from benchmark import roofline, roofline_window as rw, sut, traffic  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    moe_experts_hit_share, window_decode_roofline, window_keys_share,
    window_pass_roofline, window_ragged_roofline,
)
from tests.benchmark.test_bench_manifest import holds_entry  # noqa: E402

TINY = ROOT / "tests" / "benchmark" / "tiny_window"
CELL, CONFIG = "trinitymini.mixed_lengths", "trinity-mini-d8"
MODEL_STEP = "model step (models/llama.py forward_ragged)"
KERNELS = "kernels (ops/paged_attention.py)"
CACHE = "cache (llm/kv_cache.py, llm/prefix_cache.py)"
NEW = {
    "window_decode_roofline": ("higher", "device_trace", KERNELS, "tpot_p50_ms"),
    "window_ragged_roofline": ("higher", "device_trace", KERNELS, "ttft_p50_ms"),
    "window_pass_roofline": ("higher", "device_trace", MODEL_STEP, "tpot_p50_ms"),
    "window_keys_share": ("lower", "program_counter", CACHE, "tpot_p50_ms"),
}
LISTED = (
    "moe_experts_hit_share", "kv_pool_peak_share", "kv_pool_used_peak_share",
    "kv_pool_move_share", "req_ttft_p50_ms", "req_tpot_p90_ms",
    "eng_queue_wait_ms", "eng_admit_ms", "eng_prefill_ms", "eng_ttft_ms",
    "eng_prefill_launches", "eng_first_launch_wait_ms", "eng_prefill_span_ms",
    "eng_first_emit_ms", "launch_hop_ms", "launch_upload_ms",
    "launch_enqueue_ms", "launch_tail_ms", "starve_ms", "starve_share",
    "idle_seen_share", "ragged_launch_share", "dense_rows_per_launch",
    "front_overhead_ms",
)


def trinity():
    return sut.load_config(ROOT / "benchmark" / "configs" / (CONFIG + ".json"))


def root_manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


# ---------------------------------------------------------------- rehearsal

def test_rehearsal_of_the_window_cell_walks_every_phase(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--manifest", str(TINY / "BENCHMARK.json"),
         "--traffic-dir", str(TINY / "traffic"), "--out", str(tmp_path / "out"),
         "--workload", "tiny.window", "--seed", str(2 ** 31 + 91),
         "--seconds", "8", "--trace", "1", "--rehearse"],
        cwd=str(ROOT), env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    metrics = line["metrics"]
    # prompts of 20-400 tokens against a window of 24: the bound works
    assert 0 < metrics["window_keys_share"]["value"] < 60
    assert 0 < metrics["moe_experts_hit_share"]["value"] <= 100
    assert metrics["kv_pool_used_peak_share"]["value"] > 0
    assert metrics["dense_rows_per_launch"]["value"] > 0
    # no chip: no device metric is made up
    for name in ("window_pass_roofline", "window_decode_roofline",
                 "window_ragged_roofline", "hbm_peak_share"):
        assert name not in metrics
    detail = json.loads((tmp_path / "out" / "detail.json").read_text())
    assert detail["compiles_in_window"] == []
    assert detail["reference"]["repeat_identical"] and detail["reference"]["within"]
    after = detail["counters"]["after"]
    assert after["state_pool"] is None and after["kv_pool"]["num_pages"] == 1300
    assert "latent" not in after
    win = after["window"]
    assert win["rows_window"] == 3 * win["rows_full"] > 0
    assert win["decode_keys_window"] < 3 * win["decode_keys_full"]
    assert after["moe"]["experts_held"] == 16 and after["moe"]["experts_hit"] > 0


def test_the_rehearsal_manifest_keeps_the_contracts_form():
    manifest = json.loads((TINY / "BENCHMARK.json").read_text())
    root = root_manifest()
    assert set(manifest) == set(root)
    by_name = {m["name"]: m for m in root["per_layer"]}
    for m in manifest["per_layer"]:
        assert {k: v for k, v in m.items() if k != "workloads"} == \
            {k: v for k, v in by_name[m["name"]].items() if k != "workloads"}
        assert "tiny.window" in m["workloads"]
    assert set(NEW) <= {m["name"] for m in manifest["per_layer"]}
    cfg = sut.load_config(ROOT / manifest["configs"][0]["file"])
    assert cfg["engine"]["cache"] == "paged" and cfg["reference"] == "trinity_mini"
    assert cfg["arch"] == "afmoe" and cfg["sliding_window"] == 24


# -------------------------------------------------- the configuration file

def test_the_configuration_keeps_every_published_width():
    cfg = trinity()
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(json.loads(x) for x in catalog.read_text().splitlines()
                   if '"Trinity-Mini"' in x)
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
        assert cfg["layer_types"] == row["config"]["layer_types"][:8]
        assert cfg["published"]["num_hidden_layers"] == 32 == \
            row["config"]["num_hidden_layers"]
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"]) == (6144, 1024, 128, 8, 1)
    assert (cfg["sliding_window"], cfg["vocab_size"], cfg["route_scale"],
            cfg["num_hidden_layers"]) == (2048, 200192, 2.826, 8)
    model = sut.model_block(cfg)
    assert (model["dim"], model["ffn_dim"], model["n_heads"], model["n_kv_heads"],
            model["head_dim"], model["n_layers"], model["moe_top_k"],
            model["vocab_size"], model["sliding_window"]) == \
        (2048, 6144, 32, 4, 128, 8, 8, 200192, 2048)
    # what the program is built from is the published value, key for key
    assert (model["router_experts"], model["experts_held"]) == \
        (cfg["num_experts"], [0, 128])
    assert model["moe_intermediate_size"] == cfg["moe_intermediate_size"]
    assert model["n_shared_experts"] == cfg["num_shared_experts"]
    assert model["num_dense_layers"] == cfg["num_dense_layers"] == 2
    assert model["route_scale"] == cfg["route_scale"]
    assert model["route_norm"] is cfg["route_norm"] is True
    assert model["scoring_func"] == cfg["score_func"] == "sigmoid"
    assert model["embed_scale"] is cfg["mup_enabled"] is True
    assert model["max_seq_len"] == cfg["max_position_embeddings"] == 131072
    assert model["layer_types"] == cfg["layer_types"] and model["scan_layers"]
    for key in ("attention_gate", "rope", "sliding_window", "mup_enabled",
                "qk_norm", "norms", "router", "rope_layout", "training_keys",
                "weights", "tokenizer", "pages"):
        assert cfg["assumed"][key]
    assert "FIRST OF FOUR pipeline stages" in cfg["deployment"]
    assert {k: cfg["engine"][k] for k in (
        "cache", "scheduler", "weight_quant", "prefix_cache", "max_batch",
        "max_seq_len", "warmup")} == {
        "cache": "paged", "scheduler": "ragged", "weight_quant": "int8",
        "prefix_cache": 4096, "max_batch": 32, "max_seq_len": 17408,
        "warmup": "off"}
    assert cfg["probes"]["prompt_tokens"] == [40, 600, 2500, 6000]
    assert cfg["probes"]["new_tokens"] == 12
    # two probes lie past the window, two inside it
    assert sorted(n > cfg["sliding_window"]
                  for n in cfg["probes"]["prompt_tokens"]) == [False, False,
                                                               True, True]


def test_the_parent_cannot_build_the_architecture(monkeypatch):
    """A program without this PR's model fails the cell at once, by name:
    ``arch`` is the model's own."""
    from clearml_serving_tpu import models

    monkeypatch.delitem(models._BUILDERS, "afmoe")
    with pytest.raises(ValueError, match="unknown model arch 'afmoe'"):
        models.build_model(trinity()["arch"], sut.model_block(trinity()))


def manifest_reports_the_window_cell_as_the_issue_wrote(manifest):
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "mixed_lengths", 1)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["file"] == "benchmark/configs/{}.json".format(CONFIG)
    assert entry["reduced"] == trinity()["reduced"]
    assert entry["source"] == trinity()["source"]
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"ttft_p50_ms", "tpot_p50_ms", "out_tok_s", "setup_s",
            "hbm_peak_share"} | set(NEW) | set(LISTED) <= reported
    # their counts know no window and no 128-expert layer
    assert not {"model_pass_roofline", "attn_decode_roofline",
                "latent_pass_roofline", "state_pool_share",
                "prefix_hit_share"} & reported


def manifest_holds_the_window_entries(manifest):
    for name, (better, source, layer, moves) in NEW.items():
        holds_entry(manifest, {
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [CELL]})
    # entries that exist stand in the order they were handed in
    order = [m["name"] for m in manifest["per_layer"] if m["name"] in NEW]
    assert order == list(NEW)


def test_the_cell_is_what_the_issue_wrote():
    manifest_reports_the_window_cell_as_the_issue_wrote(root_manifest())
    manifest_holds_the_window_entries(root_manifest())
    assert len(root_manifest()["workloads"]) >= 7


# ------------------------------------------------------------- the traffic

def test_mixed_lengths_is_what_the_issue_wrote():
    mix = traffic.load_mix("mixed_lengths")
    assert (mix["loop"], mix["clients"], mix["ramp_s"], mix["drain_s"],
            mix["stagger_first"]) == ("closed", 24, 12, 0, "answer")
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1536,
                                    "sigma": 1.2, "min": 256, "max": 16384}
    assert mix["answer_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.5, "min": 64, "max": 512}
    # the floor is the mix file's arithmetic: every int8 matmul weight once
    # a pass over the HBM peak, times the mean answer
    model = sut.model_block(trinity())
    weights = rw.fixed_params(model) + 6 * 128 * rw.expert_params(model)
    assert 5.5e9 < weights < 5.65e9
    answers = traffic.strata(mix["answer_tokens"], 24 * 40)
    floor = weights / 819e9 * sum(answers) / len(answers)
    assert mix["floor_request_s"] <= floor < mix["floor_request_s"] * 1.1
    assert mix["floor_why"] and mix["nominal_request_s"] > mix["floor_request_s"]
    cfg = trinity()
    assert mix["clients"] <= cfg["engine"]["max_batch"]
    assert 16384 + 512 <= cfg["engine"]["max_seq_len"]
    prompts = traffic.strata(mix["prompt_tokens"], 24 * 40)
    past = sum(1 for n in prompts if n > cfg["sliding_window"]) / len(prompts)
    assert 0.38 < past < 0.43
    assert 0.14 < sum(1 for n in prompts if n > 5000) / len(prompts) < 0.18
    assert max(prompts) == 16384 and min(prompts) == 256


@pytest.mark.parametrize("seeds", [(7, 2 ** 31 + 5), (2 ** 31 + 77, 123456)])
def test_the_plan_holds_the_same_lengths_under_any_seed(seeds):
    """The multiset of prompt lengths, and of the answers' after each
    caller's first, is the mix file's alone: two seeds send the same work
    in another order."""
    def lengths(seed):
        plan = traffic.make_plan("mixed_lengths", CONFIG, seed, 51)
        assert plan["loop"] == "closed" and len(plan["clients"]) == 24
        every = [r for c in plan["clients"] for r in c]
        later = [r for c in plan["clients"] for r in c[1:]]
        return (Counter(len(r["messages"][0]["content"]) for r in every),
                Counter(r["max_tokens"] for r in later),
                sorted(c[0]["max_tokens"] for c in plan["clients"]),
                plan["per_client"])

    a, b = lengths(seeds[0]), lengths(seeds[1])
    # a caller's first answer is its drawn length cut by a stratified
    # fraction: which length meets which cut is the seed's
    assert (a[0], a[1], a[3]) == (b[0], b[1], b[3])
    assert len(a[2]) == len(b[2]) == 24 and max(a[2] + b[2]) <= 512
    assert sum(a[0].values()) == 24 * a[3] and len(a[0]) > 100
    plan = traffic.make_plan("mixed_lengths", CONFIG, seeds[0], 51)
    texts = {r["messages"][0]["content"][:64]
             for c in plan["clients"] for r in c}
    assert len(texts) == 24 * plan["per_client"]      # unshared prompts
    other = traffic.make_plan("mixed_lengths", CONFIG, seeds[1], 51)
    assert plan["clients"][0][0]["messages"] != other["clients"][0][0]["messages"]


# --------------------------------------------------------- roofline_window

def test_roofline_window_counts_the_issues_arithmetic():
    model = sut.model_block(trinity())
    assert rw.layer_counts(model) == {"full": 2, "window": 6, "dense": 2,
                                      "moe": 6}
    # four projections 2048 x 4096 (three of them: q, gate, o) and two
    # 2048 x 512: 27.3 M
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert rw.attention_params(model) == attn == 27262976
    assert rw.expert_params(model) == 3 * 2048 * 1024 == 6291456
    fixed = rw.fixed_params(model)
    assert fixed == (8 * attn + 2 * 3 * 2048 * 6144
                     + 6 * (2048 * 128 + 6291456) + 2048 * 200192)
    # a layer outside its routed experts 33.8 M; all experts 805.3 M a layer
    assert attn + 6291456 + 2048 * 128 == 33816576
    assert 128 * rw.expert_params(model) == 805306368
    # every int8 matmul weight once: 5.57 GB
    assert 5.56e9 < fixed + 6 * 128 * rw.expert_params(model) < 5.58e9
    # a key of a layer: K and V of 4 heads x 128 in bfloat16; a token over
    # the eight layers 16,384 B
    assert rw.key_bytes(model) == 2 * 4 * 128 * 2 == 2048
    assert 8 * rw.key_bytes(model) == 16384
    assert rw.attention_flops(model, 10) == 4.0 * 32 * 128 * 10
    # a decode pass of 24 rows at 6,000 tokens, 78% of the experts hit:
    # memory bound, about 6 ms
    keys = 24 * (2 * 6000 + 6 * 2048)
    nbytes = rw.pass_bytes(model, 1, 0.78 * 128 * 6, keys, 24)
    assert nbytes == pytest.approx(
        fixed + 0.78 * 128 * 6 * 6291456 + keys * 2048 + 24 * 8 * 2048)
    flops = rw.pass_flops(model, 24, 24, 24 * 8 * 6, keys)
    assert flops == pytest.approx(
        2.0 * (fixed - 2048 * 200192) * 24 + 2.0 * 2048 * 200192 * 24
        + 2.0 * 6291456 * 24 * 8 * 6 + 4.0 * 32 * 128 * keys)
    least = roofline.min_seconds(flops, nbytes,
                                 roofline.peaks_for("TPU v5 lite"))
    assert least["bound"] == "memory" and 0.005 < least["seconds"] < 0.008


# ------------------------------------------------ readers on recorded edges

WIN = 2048
# hand-counted: 100 decode chunks of 4 passes over 24 rows at 6,000 tokens,
# and 40 mixed passes: one 128-query chunk ending at 5,000 keys each
CHAIN, CTX = 100 * 4 * 24, 6000
MIXED, CHUNK, KV = 40, 128, 5000


def recorded(ops, busy_s=4.0, window=True):
    model = sut.model_block(trinity())
    pairs_full = MIXED * sum(KV - CHUNK + i + 1 for i in range(CHUNK))
    after = {
        "rows_full": 2 * (CHAIN + MIXED * CHUNK),
        "rows_window": 6 * (CHAIN + MIXED * CHUNK),
        "decode_keys_full": 2 * CHAIN * CTX,
        "decode_keys_window": 6 * CHAIN * WIN,
        "mixed_keys_full": 2 * MIXED * KV,
        "mixed_keys_window": 6 * MIXED * (WIN + CHUNK - 1),
        "mixed_pairs_full": 2 * pairs_full,
        "mixed_pairs_window": 6 * MIXED * CHUNK * WIN,
        "window_keys_unbounded": 6 * (CHAIN * CTX + MIXED * KV),
    }
    passes = 100 * 4 + MIXED
    before = {"window": dict.fromkeys(after, 0),
              "ragged": {"passes": 0, "decode_tokens": 0},
              "moe": {"experts_held": 128, "experts_hit": 0,
                      "local_assignments": 0, "layer_passes": 0}}
    edge = {"window": after, "ragged": {"passes": MIXED, "decode_tokens": 0},
            "moe": {"experts_held": 128, "experts_hit": passes * 6 * 96,
                    "local_assignments": (CHAIN + MIXED * CHUNK) * 8 * 6,
                    "layer_passes": passes * 6}}
    if not window:
        for e in (before, edge):
            del e["window"], e["moe"]
    return {
        "cfg": trinity(), "device": {"kind": "TPU v5 lite"},
        "before": before, "after": edge, "trace_counters": (before, edge),
        "trace": {"devices": 1, "busy_s": busy_s, "ops": ops},
    }, model


def test_the_counter_readers_read_the_programs_blocks():
    ctx, _ = recorded([])
    kept = 6 * (CHAIN * WIN + MIXED * (WIN + CHUNK - 1))
    assert window_keys_share.read(ctx) == pytest.approx(
        100 * kept / (6 * (CHAIN * CTX + MIXED * KV)))
    assert 30 < window_keys_share.read(ctx) < 40
    assert moe_experts_hit_share.read(ctx) == pytest.approx(100 * 96 / 128)


def test_the_roofline_readers_find_the_kernels_by_name():
    ops = [("paged_attention_decode.3_custom-call_bf16_32_4_8_128", 0.9, 3200),
           ("ragged_paged_attention.5_custom-call_bf16_4_2816_128", 0.2, 320),
           ("fusion.7_fusion_bf16_128_2048", 1.0, 50)]
    ctx, model = recorded(ops)
    peaks = roofline.peaks_for("TPU v5 lite")
    want = (2 * CHAIN * CTX + 6 * CHAIN * WIN) * 2048 \
        / peaks["hbm_bytes_per_s"] / 0.9
    assert window_decode_roofline.read(ctx) == pytest.approx(100 * want)
    assert 0 < window_decode_roofline.read(ctx) < 100
    pairs = 2 * MIXED * sum(KV - CHUNK + i + 1 for i in range(CHUNK)) \
        + 6 * MIXED * CHUNK * WIN
    t_ops = 4.0 * 32 * 128 * pairs / peaks["bf16_flops_per_s"]
    t_bytes = (2 * MIXED * KV + 6 * MIXED * (WIN + CHUNK - 1)) * 2048 \
        / peaks["hbm_bytes_per_s"]
    assert t_ops > t_bytes            # a 128-query tile is compute bound
    assert window_ragged_roofline.read(ctx) == pytest.approx(
        100 * t_ops / 0.2)
    share = window_pass_roofline.read(ctx)
    assert 0 < share < 100
    # the same work in half the busy time is twice the share
    assert window_pass_roofline.read(recorded(ops, busy_s=2.0)[0]) == \
        pytest.approx(2 * share)
    # by hand: the passes' bytes bind (440 passes x 0.54 GB of fixed weights
    # + 96 experts hit a layer)
    passes = 100 * 4 + MIXED
    tokens = CHAIN + MIXED * CHUNK
    keys = (2 * CHAIN * CTX + 6 * CHAIN * WIN + 2 * MIXED * KV
            + 6 * MIXED * (WIN + CHUNK - 1))
    nbytes = (passes * rw.fixed_params(model)
              + passes * 6 * 96 * rw.expert_params(model)
              + keys * 2048 + tokens * 8 * 2048)
    assert share == pytest.approx(
        100 * nbytes / peaks["hbm_bytes_per_s"] / 4.0)


def test_a_trace_without_the_kernels_gives_nothing_to_read():
    ctx, _ = recorded([("fusion.7_fusion_bf16_128_2048", 1.0, 50)])
    assert window_decode_roofline.read(ctx) is None
    assert window_ragged_roofline.read(ctx) is None
    assert window_pass_roofline.read(ctx) is not None


def test_no_chained_row_in_the_tail_reads_zero():
    ctx, _ = recorded([("ragged_paged_attention.5_custom-call", 0.2, 320)])
    for edge in ctx["trace_counters"]:
        edge["window"] = dict(edge["window"], decode_keys_full=0,
                              decode_keys_window=0)
    assert window_decode_roofline.read(ctx) == 0.0


@pytest.mark.parametrize("reader", [
    window_pass_roofline, window_decode_roofline, window_ragged_roofline,
    window_keys_share])
def test_readers_return_nothing_where_the_program_has_no_window(reader):
    """The parent's program, under this PR's benchmark files: no block, no
    metric, no error."""
    ctx, _ = recorded([("paged_attention_decode.1_custom-call", 1.0, 10)],
                      window=False)
    assert reader.read(ctx) is None
    assert reader.read(dict(ctx, trace=None, trace_counters=None)) is None
