"""`dense_rows_per_launch` (rows the dense layers of a ragged pass multiplied,
per ragged step of the window): on hand-made counters, nothing on counters as
the parent commit's program gives them, the entry a `benchmark` PR is to
register (``ENTRY``: the root manifest cannot take it from a program PR,
PERF.md section 7), and one CPU rehearsal that prints the engine's own compact
axis."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.layer_metrics import dense_rows_per_launch  # noqa: E402
from tests.benchmark.test_bench_manifest import (  # noqa: E402
    PER_LAYER_KEYS, holds_entry)
from tests.benchmark.test_bench_phase_metrics import counters  # noqa: E402
from tests.benchmark.test_bench_rehearsal import TINY, run  # noqa: E402

# the cells whose pass packs its tokens: in ``brumby14b.long_decode`` the state
# pass keeps one axis and the reader would print that axis, not a measurement
ENTRY = {
    "name": "dense_rows_per_launch", "unit": "rows", "better": "lower",
    "source": "program_counter",
    "layer": "model step (models/llama.py forward_ragged)",
    "moves": "tpot_p50_ms",
    "workloads": ["mistral7b.chat_steady", "mistral7b.decode_batch",
                  "mixtral8x7b.prefill_batch", "mixtral8x7b.chat_steady"]}


def with_rows(scale, steps, rows):
    out = counters(scale)
    out["ragged"] = {"steps": steps, "passes": 3 * steps}
    if rows is not None:
        out["ragged"].update(dense_rows=rows, dense_axis=128, layout_axis=352)
    return out


@pytest.mark.parametrize("before,after,want", [
    ((0, 0), (20, 2560), 128.0),       # the compact axis, every launch
    ((7, 1792), (12, 3072), 256.0),    # a budget of 256
    ((3, 1056), (13, 4576), 352.0),    # dense layers on the aligned layout
])
def test_rows_over_ragged_steps(before, after, want):
    ctx = {"before": with_rows(1, *before), "after": with_rows(3, *after)}
    assert dense_rows_per_launch.read(ctx) == pytest.approx(want)


def test_reads_nothing_on_the_parents_counters_or_without_a_ragged_step():
    assert dense_rows_per_launch.read(
        {"before": with_rows(1, 4, None), "after": with_rows(3, 9, None)}) is None
    assert dense_rows_per_launch.read(
        {"before": with_rows(1, 5, 640), "after": with_rows(3, 5, 640)}) is None
    assert dense_rows_per_launch.read(
        {"before": counters(1), "after": counters(3)}) is None


def manifest_holds_dense_rows_per_launch(manifest):
    # never on the state cache: its pass keeps one axis (the comment on ENTRY)
    holds_entry(manifest, ENTRY, never=("brumby14b.long_decode",))
    assert set(ENTRY) == PER_LAYER_KEYS | {"workloads"}
    assert ENTRY["layer"] in {m["layer"] for m in manifest["per_layer"]
                              if m["name"] != ENTRY["name"]}
    assert ENTRY["moves"] in {m["name"] for m in manifest["end_to_end"]}


def test_the_entry_to_register_fits_the_manifest_and_the_reader():
    manifest_holds_dense_rows_per_launch(
        json.loads((ROOT / "BENCHMARK.json").read_text()))
    doc = " ".join(dense_rows_per_launch.__doc__.split())
    assert doc.startswith("model step:")
    assert re.search(r"Source: program_counter\. Moves tpot_p50_ms\.$", doc)


def test_rehearsal_prints_the_engines_compact_axis(tmp_path):
    """``tiny.chat --trace 1`` on a copy of the tiny manifest that gains the
    entry (the tiny manifest is the benchmark's own file): the line reads the
    engine's static compact axis, which follows from its token budget."""
    manifest = json.loads((TINY / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in manifest["workloads"]]
    manifest["per_layer"].append(dict(ENTRY, workloads=cells))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest, indent=1))
    proc = run(["--manifest", str(path),
                "--workload", "tiny.chat", "--seed", str(2 ** 31 + 83),
                "--seconds", "8", "--trace", "1", "--rehearse"], tmp_path, 420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0, proc.stderr[-3000:]
    rows = line["metrics"]["dense_rows_per_launch"]["value"]
    # tiny.chat sets no budget: the program's default of 128, in whole
    # alignment blocks (1 with the XLA twin on the CPU)
    assert rows == 128
