"""`ragged_launch_share` (ragged steps over all launches of the window) and
`breakdown.idle_gaps` (the device's idle seconds under the innermost
``engine.*`` annotation of the host plane): each on hand-made counters, spans
and gaps, the join against ``host_spans.idle_by_phase`` of the same spans, and
the manifest's entry, found by its name."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import host_spans  # noqa: E402
from benchmark.layer_metrics import _common, ragged_launch_share  # noqa: E402
from tests.benchmark.test_bench_manifest import holds_entry  # noqa: E402
from tests.benchmark.test_bench_phase_metrics import counters  # noqa: E402

ALL_CELLS = ["mistral7b.chat_steady", "mistral7b.decode_batch",
             "mixtral8x7b.prefill_batch", "mixtral8x7b.chat_steady",
             "brumby14b.long_decode"]


def with_steps(scale, steps):
    out = counters(scale)                       # 10 x scale launches
    if steps is not None:
        out["ragged"] = {"steps": steps, "passes": 3 * steps}
    return out


@pytest.mark.parametrize("before,after,want", [
    (0, 20, 100.0),          # 20 launches gained, all ragged steps
    (7, 12, 25.0),           # 5 of 20: the rest are pipelined decode chunks
    (4, 4, 0.0),             # none: a share of launches may read 0
])
def test_ragged_steps_over_launches(before, after, want):
    ctx = {"before": with_steps(1, before), "after": with_steps(3, after)}
    assert ragged_launch_share.read(ctx) == pytest.approx(want)


def test_reads_nothing_without_the_counter_or_without_launches():
    assert ragged_launch_share.read(
        {"before": with_steps(1, None), "after": with_steps(3, None)}) is None
    assert ragged_launch_share.read(
        {"before": with_steps(2, 5), "after": with_steps(2, 5)}) is None


def manifest_holds_ragged_launch_share(manifest):
    holds_entry(manifest, {
        "name": "ragged_launch_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler (llm/engine.py loop)",
        "moves": "tpot_p50_ms", "workloads": ALL_CELLS})


def test_the_manifest_holds_it_by_its_name():
    manifest_holds_ragged_launch_share(
        json.loads((ROOT / "BENCHMARK.json").read_text()))
    doc = " ".join(ragged_launch_share.__doc__.split())
    assert doc.startswith("scheduler:") and "``better`` has no meaning" in doc
    assert re.search(r"Source: program_counter\. Moves tpot_p50_ms\.$", doc)


def span(name, start, end, seq=1, line="loop"):
    return ("engine." + name, seq, start, end, line)


# one serial cycle of 100 ms on the loop thread, the dispatch worker's upload
# and enqueue inside ``launch``, the readback at the end of ``wait``; then a
# second cycle's ``admin`` and nothing
CYCLE = [
    span("admin", 0.000, 0.002), span("plan", 0.002, 0.008),
    span("launch", 0.008, 0.016),
    span("dispatch", 0.009, 0.015, line="worker"),
    span("upload", 0.009, 0.013, line="worker"),
    span("enqueue", 0.013, 0.014, line="worker"),
    span("wait", 0.016, 0.090), span("readback", 0.088, 0.090),
    span("emit", 0.090, 0.096), span("yield", 0.096, 0.100),
    span("admin", 0.100, 0.102, seq=2),
]
# the device runs 0.0145 -> 0.087 and again from 0.110: idle before the first
# launch lands, from the result to the end of the cycle, and beyond it
GAPS = [(0.0, 0.0145), (0.087, 0.023)]


def test_innermost_takes_the_span_that_started_last():
    cover = host_spans.innermost(CYCLE)
    at = lambda t: next(n for s, e, n in cover if s <= t < e)  # noqa: E731
    assert at(0.0085) == "engine.launch"       # the hop out
    assert at(0.010) == "engine.upload"
    assert at(0.0135) == "engine.enqueue"
    assert at(0.0145) == "engine.dispatch"     # the worker's tail
    assert at(0.0155) == "engine.launch"       # the hop back
    assert at(0.05) == "engine.wait" and at(0.089) == "engine.readback"
    assert all(a[1] <= b[0] for a, b in zip(cover, cover[1:]))


def test_idle_gaps_name_what_the_host_was_doing():
    ctx = {"trace": {"devices": 1, "busy_s": 0.0725, "window_s": 0.11,
                     "ops": [("fusion.1", 0.07, 3)], "gaps": GAPS,
                     "host_spans": CYCLE}}
    got = _common.breakdown(ctx)
    assert got["device_ops"] == [["fusion.1", 0.07]]
    gaps = got["idle_gaps"]
    assert len(gaps) <= 10 and all(
        re.match(r"^(all_gaps|one_gap):_(engine\.[a-z]+|no_cycle)$", n) for n, _ in gaps)
    totals = {n.split(":_")[1]: v for n, v in gaps if n.startswith("all_gaps")}
    # the seven largest of eleven labels
    assert totals == pytest.approx({
        "no_cycle": 0.008, "engine.plan": 0.006, "engine.emit": 0.006,
        "engine.admin": 0.004, "engine.upload": 0.004, "engine.yield": 0.004,
        "engine.readback": 0.002})
    # the totals come largest first; then the longest single gaps, each under
    # the annotation that held most of it
    values = [v for n, v in gaps if n.startswith("all_gaps")]
    assert values == sorted(values, reverse=True)
    singles = [(n, v) for n, v in gaps if n.startswith("one_gap")]
    assert singles[0] == ("one_gap:_no_cycle", pytest.approx(0.023))
    assert singles[1] == ("one_gap:_engine.plan", pytest.approx(0.0145))


def test_the_join_agrees_with_the_loop_phases_of_the_same_spans():
    """``idle_by_phase`` (by hand, PERF.md section 6) sees only the loop
    thread's six phases: the innermost join splits ``launch`` into the worker's
    spans and ``wait`` into the readback, and leaves every phase's total."""
    fine = host_spans.summed(host_spans.idle_by_span(GAPS, CYCLE))
    busy = [(-1.0, 0.0), (0.0145, 0.087), (0.110, 0.2)]
    coarse = host_spans.idle_by_phase(busy, CYCLE)
    fold = {"engine.upload": "engine.launch", "engine.enqueue": "engine.launch",
            "engine.dispatch": "engine.launch", "engine.readback": "engine.wait",
            host_spans.NO_SPAN: None}
    folded = {}
    for name, secs in fine.items():
        key = fold.get(name, name)
        folded[key] = folded.get(key, 0.0) + secs
    assert folded == pytest.approx({k: v for k, v in coarse.items() if v})
    assert sum(fine.values()) == pytest.approx(sum(length for _, length in GAPS))
    assert fine["engine.readback"] == pytest.approx(0.002)
    assert fine[host_spans.NO_SPAN] == pytest.approx(0.008)


def test_a_trace_without_annotations_or_without_a_device_still_breaks_down():
    ctx = {"trace": {"devices": 1, "ops": [], "gaps": GAPS, "host_spans": []}}
    assert _common.breakdown(ctx)["idle_gaps"][0] == [
        "all_gaps:_no_cycle", pytest.approx(0.0375)]
    assert _common.breakdown({"trace": None}) == {"device_ops": [], "idle_gaps": []}
