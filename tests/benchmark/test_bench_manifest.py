"""BENCHMARK.json against the contract it is checked by, and against the
files it names."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import sut  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head).*(size|dim)|_dim$|_rank$|"
                   r"^head_dim$|expansion|experts_per_tok")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}     # and "workloads"
PER_LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
MANIFESTS = [ROOT / "BENCHMARK.json",
             ROOT / "tests" / "benchmark" / "tiny" / "BENCHMARK.json"]


def load(path):
    return json.loads(path.read_text())


def cells_of(metric, manifest):
    return metric.get("workloads") or [w["name"] for w in manifest["workloads"]]


def without_cells(entry):
    return {k: v for k, v in entry.items() if k != "workloads"}


def holds_entry(manifest, want, never=()):
    """A pin on one ``per_layer`` entry, as benchmark/README.md ("Pinning an
    entry in a test") has it: found by its name, once, wherever it stands;
    every key but ``workloads`` equal to ``want``'s; ``workloads`` holds at
    least the cells of ``want`` (those its author measured), only cells of
    the manifest, each once, and none of ``never``."""
    held = [m for m in manifest["per_layer"] if m["name"] == want["name"]]
    assert len(held) == 1, (want["name"], "is held", len(held), "times")
    entry = held[0]
    assert without_cells(entry) == without_cells(want)
    assert ("workloads" in entry) == ("workloads" in want)
    listed = entry.get("workloads", [])
    assert set(want.get("workloads", [])) <= set(listed), (want["name"], listed)
    assert set(listed) <= {w["name"] for w in manifest["workloads"]}, listed
    assert len(listed) == len(set(listed)) and not set(never) & set(listed)


@pytest.fixture(params=MANIFESTS, ids=["benchmark", "tiny"])
def manifest(request):
    return load(request.param)


def test_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"][:2] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["workloads"]) <= 24 and 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16 and 1 <= len(manifest["per_layer"]) <= 128
    assert len(json.dumps(manifest)) <= 64 * 1024


def test_names_units_and_lines(manifest):
    names = []
    for group, keys in (("end_to_end", E2E_KEYS), ("per_layer", PER_LAYER_KEYS)):
        for m in manifest[group]:
            assert set(m) - {"workloads"} == keys, m
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
            names.append(m["name"])
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    names += [w["name"] for w in manifest["workloads"]]
    names += [c["name"] for c in manifest["configs"]]
    assert len(names) == len(set(names))
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_every_cell_reports_enough(manifest):
    e2e = {m["name"]: set(cells_of(m, manifest)) for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for w in manifest["workloads"]:
        assert w["name"] in e2e["setup_s"]
        assert any(w["name"] in cells for n, cells in e2e.items() if n != "setup_s")
        assert any(w["name"] in cells_of(m, manifest) for m in manifest["per_layer"])


def test_every_moves_is_reported_where_the_metric_is(manifest):
    e2e = {m["name"]: set(cells_of(m, manifest)) for m in manifest["end_to_end"]}
    layers = {}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        missing = set(cells_of(m, manifest)) - e2e[m["moves"]]
        assert not missing, (m["name"], "moves", m["moves"], "not reported in", missing)
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        layers.setdefault(m["layer"].split(" ")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_tails_only_where_the_population_reaches_100(manifest):
    for m in manifest["end_to_end"]:
        if m["name"].endswith("_p90_ms"):
            assert all("chat" in c for c in cells_of(m, manifest))


def manifest_reports_every_tpot_tail_once(manifest):
    """The 90th percentile of TPOT is judged (an end-to-end `*_p90_ms`) only
    where runs hold it steady, which since PR 41 is nowhere (PERF.md section
    2); everywhere else it stands beside the layers as `req_tpot_p90_ms`: no
    cell twice, none left out."""
    judged = [c for m in manifest["end_to_end"] if m["name"] == "tpot_p90_ms"
              for c in cells_of(m, manifest)]
    beside = cells_of(next(m for m in manifest["per_layer"]
                           if m["name"] == "req_tpot_p90_ms"), manifest)
    assert not set(judged) & set(beside)
    assert sorted(judged + beside) == sorted(w["name"] for w in manifest["workloads"])


def test_every_cell_reports_its_tpot_tail_once():
    manifest_reports_every_tpot_tail_once(load(MANIFESTS[0]))


def files_behind_the_names(manifest, root):
    used = {w["config"] for w in manifest["workloads"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        cfg = sut.load_config(root / c["file"])      # refuses unpinned knobs
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert not [k for k in c["reduced"] if WIDTH.search(k)]
        assert (root / "benchmark" / "reference" / (cfg["reference"] + ".py")).is_file()
    for m in manifest["per_layer"]:
        reader = root / "benchmark" / "layer_metrics" / (m["name"] + ".py")
        assert reader.is_file(), "no reader for " + m["name"]
        assert m["source"] in reader.read_text(), (m["name"], "names another source")


def test_files_behind_the_names(manifest):
    files_behind_the_names(manifest, ROOT)


def manifest_cells_have_their_mix_and_rate(manifest):
    from benchmark import traffic

    for w in manifest["workloads"]:
        mix = traffic.load_mix(w["traffic"])
        assert mix["loop"] in ("open", "closed")
        if mix["loop"] == "open":
            assert w["config"] in mix["session_rate_per_s"]
        plan = traffic.make_plan(w["traffic"], w["config"], 1, manifest["run_seconds"])
        assert plan["window_s"] == manifest["run_seconds"]


def test_cells_have_their_mix_and_rate():
    manifest_cells_have_their_mix_and_rate(load(MANIFESTS[0]))


def test_a_knob_beyond_sizing_is_refused(tmp_path):
    cfg = load(ROOT / "benchmark/configs/mistral-7b-v0.3.json")
    cfg["engine"]["decode_steps"] = 8
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="decode_steps"):
        sut.load_config(path)


def test_published_widths_are_kept():
    mistral = sut.model_block(sut.load_config(ROOT / "benchmark/configs/mistral-7b-v0.3.json"))
    mixtral = sut.model_block(sut.load_config(ROOT / "benchmark/configs/mixtral-8x7b-d6.json"))
    for model in (mistral, mixtral):
        assert (model["dim"], model["ffn_dim"], model["n_heads"],
                model["n_kv_heads"], model["head_dim"]) == (4096, 14336, 32, 8, 128)
    assert (mistral["n_layers"], mistral["vocab_size"]) == (32, 32768)
    assert (mixtral["n_layers"], mixtral["vocab_size"],
            mixtral["n_experts"], mixtral["moe_top_k"]) == (6, 32000, 8, 2)
