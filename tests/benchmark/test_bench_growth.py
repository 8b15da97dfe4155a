"""The manifest can grow: what a later PR hands in by benchmark/README.md's
three sections (one configuration, one cell of it on the paged cache listed
wherever a K/V cell is, one per-layer entry at the END) passes everything
`tests/benchmark` holds about the root manifest's entries. A later PR may
edit no file here, so a test that held an entry by its place or a metric's
cells by an exact list would refuse it (PR 28 wrote one, PR 41 the next, PR 42
lost its metric to it). Every function of a test module here whose one
parameter is ``manifest`` is such a hold: its own test calls it on the root
manifest, this file on the grown one. A guard reads the sources for the two
forms README.md's "Pinning an entry in a test" forbids."""

import copy
import importlib
import inspect
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

THIS = Path(__file__).resolve()
HERE = THIS.parent
README = 'benchmark/README.md, "Pinning an entry in a test"'
LIKE = "mistral7b.decode_batch"          # a K/V cell: the new one reports what it does
CONFIG, CELL, METRIC = "grown-20b-d8", "grown20b.decode_batch", "grown_router_share"
# holds that need the new names' files: run below against a scratch root
NEEDS_FILES = {"test_files_behind_the_names"}


def grown(root: dict) -> dict:
    """``root`` as a `model_config` PR would hand it in."""
    out = copy.deepcopy(root)
    out["configs"].append({
        "name": CONFIG, "source": "https://huggingface.co/example/grown-20b/blob/main/config.json",
        "file": "benchmark/configs/{}.json".format(CONFIG),
        "reduced": ["num_hidden_layers"],
        "why": "stands for the next architecture: every width kept, layers cut to one chip"})
    out["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "offline_decode", "chips": 1,
        "why": "closed loop, 32 callers on the paged cache: reports what " + LIKE + " does"})
    # `setup_s` and `tpot_p50_ms` have no list; `out_tok_s` and every per-layer
    # metric with a list that a K/V cell reports gain the cell
    for m in out["end_to_end"] + out["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    out["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "model step (models/llama.py forward_ragged)",
        "moves": "tpot_p50_ms", "workloads": [CELL]})
    return out


def holds():
    """(id, function) of every hold in `tests/benchmark`, this file's apart."""
    found = []
    for path in sorted(HERE.glob("test_*.py")):
        if path == THIS:
            continue
        module = importlib.import_module("tests.benchmark." + path.stem)
        for name, fn in inspect.getmembers(module, inspect.isfunction):
            if fn.__module__ == module.__name__ and name not in NEEDS_FILES \
                    and list(inspect.signature(fn).parameters) == ["manifest"]:
                found.append(("{}::{}".format(path.stem, name), fn))
    return found


HOLDS = holds()


@pytest.fixture(scope="module")
def root_manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_holds_are_found():
    names = {i.split("::")[1] for i, _ in HOLDS}
    assert {"manifest_holds_ragged_launch_share", "manifest_holds_the_eleven_in_their_order",
            "manifest_lists_attn_decode_roofline_for_the_kv_cells",
            "manifest_holds_dense_rows_per_launch", "manifest_reports_every_tpot_tail_once",
            "test_every_cell_reports_enough", "test_names_units_and_lines"} <= names


def test_the_grown_manifest_is_grown(root_manifest):
    big = grown(root_manifest)
    assert [m["name"] for m in big["per_layer"]][:-1] == \
        [m["name"] for m in root_manifest["per_layer"]]
    assert big["per_layer"][-1]["name"] == METRIC
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in big[g]
                if "workloads" not in m or CELL in m["workloads"]}
    like = {m["name"] for g in ("end_to_end", "per_layer") for m in root_manifest[g]
            if "workloads" not in m or LIKE in m["workloads"]}
    assert reported == like | {METRIC}
    assert {"setup_s", "tpot_p50_ms", "out_tok_s", "starve_ms", "launch_upload_ms",
            "idle_seen_share", "ragged_launch_share", "attn_decode_roofline",
            "dense_rows_per_launch"} <= reported


@pytest.mark.parametrize("hold", [fn for _, fn in HOLDS], ids=[i for i, _ in HOLDS])
def test_every_hold_takes_the_grown_manifest(hold, root_manifest):
    hold(grown(root_manifest))


def test_the_grown_manifest_has_its_files_behind_the_names(tmp_path, root_manifest):
    """`test_files_behind_the_names` on a scratch root that holds the
    benchmark's files and the two a `model_config` PR would add."""
    from tests.benchmark.test_bench_manifest import files_behind_the_names

    for part in ("configs", "reference", "layer_metrics"):
        shutil.copytree(ROOT / "benchmark" / part, tmp_path / "benchmark" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.3.json").read_text())
    cfg.update(name=CONFIG, reduced=["num_hidden_layers"], num_hidden_layers=8)
    (tmp_path / "benchmark/configs" / (CONFIG + ".json")).write_text(json.dumps(cfg))
    (tmp_path / "benchmark/layer_metrics" / (METRIC + ".py")).write_text(
        '"""model step: a share of the traced tail. Source: device_trace. '
        'Moves tpot_p50_ms."""\n\n\ndef read(ctx):\n    return None\n')
    files_behind_the_names(grown(root_manifest), tmp_path)


# ------------------------------------------------------------- the guard

# an entry taken by its place in `per_layer`
BY_PLACE = re.compile(r"""per_layer["']?\s*\]?\s*\[\s*-?\d+\s*\]""")
# a `workloads` list held by equality: itself, or as a key of a dict literal
EXACT_LIST = re.compile(
    r"""\[\s*["']workloads["']\s*\]\s*==|==\s*\{[^{}]*["']workloads["']\s*:[^{}]*\}""",
    re.S)
# the tiny manifests are the benchmark's own files, which no later PR grows
ALLOWED = {("test_bench_retention.py", 'm["workloads"] == ["tiny.retention"]')}


def pins_in(name: str, source: str) -> list:
    found = [m.group(0) for rx in (BY_PLACE, EXACT_LIST) for m in rx.finditer(source)]
    return [f for f in found if not any(
        name == n and f in allowed for n, allowed in ALLOWED)]


@pytest.mark.parametrize("source,caught", [
    ('entry = json.loads(text)["per_layer"][-1]', True),
    ("assert per_layer[52]['name'] == 'x'", True),
    ('assert entry["workloads"] == ALL_CELLS', True),
    ('assert entry == {\n    "name": "x", "moves": "tpot_p50_ms",\n'
     '    "workloads": KV_CELLS}', True),
    ('holds_entry(manifest, {\n    "name": "x", "workloads": KV_CELLS})', False),
    ('at = names.index("attn_decode_roofline")\nassert names[at + 1:at + 12] == NAMES',
     False),
    ('manifest["per_layer"].append(dict(ENTRY, workloads=cells))', False),
])
def test_the_guard_knows_a_pin_when_it_reads_one(source, caught):
    assert bool(pins_in("x.py", source)) == caught


def test_no_test_holds_an_entry_by_its_place_or_its_cells_by_an_exact_list():
    found = {path.name: pins_in(path.name, path.read_text())
             for path in sorted(HERE.glob("*.py")) if path != THIS}
    found = {k: v for k, v in found.items() if v}
    assert not found, (
        "{}: find an entry by its name and hold its `workloads` as 'at least "
        "these' (`test_bench_manifest.holds_entry`): a later PR appends entries "
        "and lists its cells, and may edit no test: {}".format(README, found))
