"""The per-layer readers of the engine's launch timeline (``starve_ms``,
``starve_share``, the four ``launch_*_ms``, ``wait_readback_ms``,
``idle_seen_share``, the three ``eng_*`` stretches of a prefill): each on a
hand-made ``ctx``, each giving nothing on counters as the parent commit's
program gives them, ``idle_seen_share`` held under 100 by construction, the
entries where the manifest has them, and one CPU rehearsal printing them."""

import importlib
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tests.benchmark.test_bench_manifest import holds_entry  # noqa: E402
from tests.benchmark.test_bench_phase_metrics import (  # noqa: E402
    STEP, counters as parent_counters, hist)
from tests.benchmark.test_bench_rehearsal import TINY, run  # noqa: E402

PARTS = dict(hop_out=0.5, upload=1.5, enqueue=1.25, tail=0.25, hop_back=0.5)
STRETCH = dict(first_launch_wait=30.0, prefill_span=80.0, first_emit=10.0)
ALL_CELLS = ["mistral7b.chat_steady", "mistral7b.decode_batch",
             "mixtral8x7b.prefill_batch", "mixtral8x7b.chat_steady",
             "brumby14b.long_decode"]
TTFT_CELLS = ["mistral7b.chat_steady", "mixtral8x7b.chat_steady",
              "mixtral8x7b.prefill_batch"]
SCHEDULER = "scheduler (llm/engine.py loop)"


def counters(scale, parked=1):
    """The parent's counters after ``scale`` x 10 cycles of 100 ms (launch 4,
    wait 80: test_bench_phase_metrics) with the timeline's blocks beside
    them: the five parts of ``launch``, 3 ms of ``wait`` that is readback, a
    starve of 12 ms for every launch but the ``parked`` that followed a park,
    and a prefill of 120 ms cut in three."""
    out = parent_counters(scale)
    n, m = 10 * scale, 4 * scale
    out["pipeline"].update(
        launch_parts={p + "_ms": hist(v * n, n) for p, v in PARTS.items()},
        readback_ms=hist(3.0 * n, n),
        starve_ms=hist(12.0 * (n - parked), n - parked))
    out["requests"].update({p + "_ms": hist(v * m, m) for p, v in STRETCH.items()})
    return out


def trace(gaps, busy):
    """A device that ran ``busy`` s between the idle ``gaps`` (start, length)."""
    idle = sum(length for _, length in gaps)
    return {"devices": 1, "window_s": busy + idle, "busy_s": busy, "gaps": gaps}


@pytest.fixture
def ctx():
    return {
        "before": counters(1), "after": counters(3),
        # the traced tail: 10 launches, none after a park, so 0.12 s starved;
        # the device idled 0.15 s of 1.0 s
        "trace_counters": (counters(2, parked=0), counters(3, parked=0)),
        "trace": trace([(0.1 * k, 0.015) for k in range(10)], 0.85),
        "records": [],
    }


EXPECT = [
    ("starve_ms", 12.0), ("starve_share", 12.0), ("launch_hop_ms", 1.0),
    ("launch_upload_ms", 1.5), ("launch_enqueue_ms", 1.25),
    ("launch_tail_ms", 0.25), ("wait_readback_ms", 3.0),
    ("idle_seen_share", 80.0), ("eng_first_launch_wait_ms", 30.0),
    ("eng_prefill_span_ms", 80.0), ("eng_first_emit_ms", 10.0),
]
NAMES = [n for n, _ in EXPECT]


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


@pytest.mark.parametrize("name,value", EXPECT)
def test_reader_on_a_hand_made_window(ctx, name, value):
    assert reader(name).read(ctx) == pytest.approx(value)


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_nothing_on_the_parents_counters(ctx, name):
    """The parent commit's program has phases, cycles and request stamps but
    no timeline: no value, no exception, the metric is left out of the line
    (the driver lays these files over the parent's checkout too)."""
    for edge, scale in (("before", 1), ("after", 3)):
        ctx[edge] = parent_counters(scale)
    ctx["trace_counters"] = (parent_counters(2), parent_counters(3))
    assert reader(name).read(ctx) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_window_without_launches_or_first_tokens_reads_nothing(ctx, name):
    ctx["after"] = ctx["before"]
    ctx["trace_counters"] = (ctx["before"], ctx["before"])
    assert reader(name).read(ctx) is None


def test_the_parts_add_up_to_the_launch_phase(ctx):
    parts = sum(reader(n).read(ctx) for n in (
        "launch_hop_ms", "launch_upload_ms", "launch_enqueue_ms", "launch_tail_ms"))
    assert parts == pytest.approx(reader("step_launch_ms").read(ctx))
    stretches = sum(reader(n).read(ctx) for n in NAMES[-3:])
    assert stretches == pytest.approx(reader("eng_prefill_ms").read(ctx))
    assert reader("starve_ms").read(ctx) <= (
        reader("step_gap_ms").read(ctx) + reader("wait_readback_ms").read(ctx))
    assert reader("wait_readback_ms").read(ctx) <= reader("step_wait_ms").read(ctx)


def test_a_launch_after_a_park_counts_in_the_launches_and_not_in_the_sum(ctx):
    # 20 launches gained, 20 starves of 12 ms; with two more parks 18
    assert reader("starve_ms").read(ctx) == pytest.approx(12.0)
    ctx["after"] = counters(3, parked=3)
    assert reader("starve_ms").read(ctx) == pytest.approx(12.0 * 18 / 20)
    assert reader("starve_share").read(ctx) == pytest.approx(12.0 * 18 / 20)


def test_idle_seen_share_needs_a_device_trace(ctx):
    share = reader("idle_seen_share")
    assert share.read(dict(ctx, trace=None)) is None
    assert share.read(dict(ctx, trace={"devices": 0})) is None
    assert share.read(dict(ctx, trace_counters=None)) is None
    assert share.read(dict(ctx, trace=trace([], 1.0))) is None      # never idle


@pytest.mark.parametrize("latency_ms", [0.0, 0.4, 3.0, 25.0])
def test_idle_seen_share_cannot_pass_100_where_the_gaps_hold_the_windows(latency_ms):
    """Every starve window (the previous result on the host -> the next call)
    lies inside the device's gap between the two launches, which is longer by
    the copy's latency before it and the launch's latency behind it: where
    ``idle_explained_share`` runs over, this stays under 100, and reads 100
    only when the program sees all of the idle chip."""
    starves_ms = [4.0 + (7 * k) % 11 for k in range(40)]
    gaps = [(0.1 * k, (s + latency_ms) / 1e3) for k, s in enumerate(starves_ms)]
    before, after = counters(1, parked=0), counters(5, parked=0)   # 40 launches
    after["pipeline"]["starve_ms"] = hist(
        before["pipeline"]["starve_ms"]["sum_ms"] + sum(starves_ms),
        before["pipeline"]["starve_ms"]["count"] + len(starves_ms))
    ctx = {"before": before, "after": after, "trace_counters": (before, after),
           "trace": trace(gaps, 3.0)}
    got = reader("idle_seen_share").read(ctx)
    assert got == pytest.approx(
        100 * sum(starves_ms) / (sum(starves_ms) + 40 * latency_ms))
    assert got <= 100.0 + 1e-9 and (latency_ms == 0 or got < 100.0)


def added_entries():
    per_layer = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
    return [m for m in per_layer if m["name"] in NAMES]


def manifest_holds_the_eleven_in_their_order(manifest):
    names = [m["name"] for m in manifest["per_layer"]]
    # appended: the driver's check reads an entry put before one that was
    # there as a change to that one; later entries follow them
    at = names.index("attn_decode_roofline")
    assert names[at + 1:at + 12] == NAMES
    for name in NAMES:
        ttft = name.startswith("eng_")
        holds_entry(manifest, {
            "name": name,
            "unit": "%" if name.endswith("_share") else "ms",
            "better": "higher" if name == "idle_seen_share" else "lower",
            "source": ("device_trace" if name == "idle_seen_share"
                       else "program_span"),
            "layer": "device" if name == "idle_seen_share" else SCHEDULER,
            "moves": "ttft_p50_ms" if ttft else "tpot_p50_ms",
            "workloads": TTFT_CELLS if ttft else ALL_CELLS,
        })


def test_the_manifest_holds_the_eleven_behind_what_was_there():
    manifest_holds_the_eleven_in_their_order(
        json.loads((ROOT / "BENCHMARK.json").read_text()))


@pytest.mark.parametrize("name", NAMES)
def test_each_reader_names_its_source_and_what_it_moves(name):
    entry = next(m for m in added_entries() if m["name"] == name)
    doc = " ".join(reader(name).__doc__.split())
    assert re.search(r"Source: {}\. Moves {}\.$".format(
        entry["source"], entry["moves"]), doc)
    assert doc.startswith(entry["layer"].split(" ")[0] + ":")


def test_rehearsal_prints_the_launch_timeline(tmp_path):
    """``tiny.chat --trace 1`` on a copy of the tiny manifest that gains the
    entries (the tiny manifest is the benchmark's own file): every metric read
    from the program alone is on the line, and they add up as on the chip."""
    manifest = json.loads((TINY / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in manifest["workloads"]]
    wanted = NAMES + ["step_{}_ms".format(p) for p in STEP] + [
        "step_gap_ms", "eng_prefill_ms"]
    root = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
    have = {m["name"] for m in manifest["per_layer"]}
    manifest["per_layer"] += [dict(m, workloads=cells) for m in root
                              if m["name"] in wanted and m["name"] not in have]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest, indent=1))
    proc = run(["--manifest", str(path),              # the later one wins
                "--workload", "tiny.chat", "--seed", str(2 ** 31 + 79),
                "--seconds", "8", "--trace", "1", "--rehearse"], tmp_path, 420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0, proc.stderr[-3000:]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # no device plane in a CPU trace: the share of the idle chip is left out
    assert "idle_seen_share" not in got
    assert set(got) >= set(NAMES) - {"idle_seen_share"}
    # tiny.chat runs pipelined decode chunks between its ragged steps: there
    # the loop's ``launch`` phase is only what the retire left of the hop, so
    # the parts are not held against step_launch_ms here (tests/
    # test_launch_timeline.py holds them equal in the serial step)
    assert all(got[n] >= 0 for n in NAMES if n in got)
    assert got["launch_upload_ms"] > 0 and got["launch_enqueue_ms"] > 0
    # a starve lies outside the device wait but for the readback before it
    assert got["starve_ms"] <= got["step_gap_ms"] + got["wait_readback_ms"]
    assert 0 <= got["starve_share"] < 100
    assert 0 <= got["wait_readback_ms"] <= got["step_wait_ms"]
    stretches = (got["eng_first_launch_wait_ms"] + got["eng_prefill_span_ms"]
                 + got["eng_first_emit_ms"])
    assert stretches == pytest.approx(got["eng_prefill_ms"], rel=0.01)
