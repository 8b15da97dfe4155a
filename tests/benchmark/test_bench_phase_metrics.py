"""The per-layer readers of the engine's own spans (``step_*``, ``eng_*``,
``idle_explained_share``, ``front_ttft_gap_ms``, ``kv_pool_used_peak_share``):
each on a hand-made ``ctx``, each giving nothing for a program that lacks the
block, and one CPU rehearsal of ``tiny.chat`` with ``--trace 1`` printing them."""

import importlib
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tests.benchmark.test_bench_rehearsal import TINY, run  # noqa: E402

STEP = ("admin", "plan", "launch", "wait", "emit", "yield")
REQ = ("queue_wait", "admit", "prefill", "ttft")


def hist(total, count):
    return {"buckets": [1.0], "counts": [0, count], "sum_ms": total, "count": count}


def counters(scale):
    """Cumulative program counters after ``scale`` x 10 cycles of 100 ms
    (admin 2, plan 6, launch 4, wait 80, emit 5, yield 3) and ``scale`` x 4
    first tokens (queue 50, admit 30, prefill 120, ttft 200; 3 launches)."""
    step = dict(zip(STEP, (2.0, 6.0, 4.0, 80.0, 5.0, 3.0)))
    req = dict(zip(REQ, (50.0, 30.0, 120.0, 200.0)))
    n, m = 10 * scale, 4 * scale
    return {
        "pipeline": {
            "dispatch_ms": hist(1.0 * n, n), "retire_ms": hist(85.0 * n, n),
            "phases": {p + "_ms": hist(v * n, n) for p, v in step.items()},
            "cycle_ms": hist(100.0 * n, n),
        },
        "requests": dict({p + "_ms": hist(v * m, m) for p, v in req.items()},
                         prefill_launches=hist(3.0 * m, m)),
        "kv_pool": {"num_pages": 200, "used_pages_peak": 30 * scale},
    }


def record(ttft_s, ok=True):
    return {"judged": True, "status": 200 if ok else 500, "done": True,
            "error": None, "n_out": 4, "want": 4, "completion_tokens": 4,
            "lp_finite": True, "due": 10.0, "first": 10.0 + ttft_s}


@pytest.fixture
def ctx():
    return {
        "before": counters(1), "after": counters(3),
        # the traced tail: 10 cycles = 1 s of loop time, 0.2 s outside the
        # device wait; the device idled 0.25 s of a 1.1 s window
        "trace_counters": (counters(2), counters(3)),
        "trace": {"devices": 1, "window_s": 1.1, "busy_s": 0.85},
        "records": [record(0.22), record(0.26), record(9.0, ok=False)],
    }


EXPECT = [
    ("step_admin_ms", 2.0), ("step_plan_ms", 6.0), ("step_launch_ms", 4.0),
    ("step_wait_ms", 80.0), ("step_emit_ms", 5.0), ("step_yield_ms", 3.0),
    ("step_gap_ms", 20.0), ("idle_explained_share", 80.0),
    ("eng_queue_wait_ms", 50.0), ("eng_admit_ms", 30.0),
    ("eng_prefill_ms", 120.0), ("eng_ttft_ms", 200.0),
    ("eng_prefill_launches", 3.0), ("front_ttft_gap_ms", 40.0),
    ("kv_pool_used_peak_share", 45.0),
]


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


@pytest.mark.parametrize("name,value", EXPECT)
def test_reader_on_a_hand_made_window(ctx, name, value):
    assert reader(name).read(ctx) == pytest.approx(value)


@pytest.mark.parametrize("name,value", EXPECT)
def test_reader_gives_nothing_without_its_block(ctx, name, value):
    """The parent commit's program has none of these blocks: no value, no
    exception, and the harness leaves the metric out of the line."""
    for edge in ("before", "after"):
        ctx[edge] = {"pipeline": {"dispatch_ms": hist(1.0, 1)},
                     "kv_pool": {"num_pages": 200}}
    ctx["trace_counters"] = (ctx["before"], ctx["after"])
    assert reader(name).read(ctx) is None


def test_idle_share_needs_a_device_trace(ctx):
    share = reader("idle_explained_share")
    assert share.read(dict(ctx, trace=None)) is None
    assert share.read(dict(ctx, trace={"devices": 0})) is None
    assert share.read(dict(ctx, trace_counters=None)) is None
    busy = {"devices": 1, "window_s": 1.0, "busy_s": 1.0}
    assert share.read(dict(ctx, trace=busy)) is None      # never idle


def test_a_window_without_cycles_or_first_tokens_reads_nothing(ctx):
    ctx["after"] = ctx["before"]
    for name in ("step_plan_ms", "step_gap_ms", "eng_ttft_ms",
                 "eng_prefill_launches", "front_ttft_gap_ms"):
        assert reader(name).read(ctx) is None


def test_idle_time_goes_to_the_phase_that_enclosed_it():
    from benchmark import host_spans

    busy = [(0.0, 1.0), (0.5, 1.2), (1.5, 2.0), (2.6, 3.0)]
    assert host_spans.merged(busy) == [(0.0, 1.2), (1.5, 2.0), (2.6, 3.0)]
    spans = [("engine.wait", 1, 0.0, 1.25, "loop"),
             ("engine.emit", 1, 1.25, 1.45, "loop"),
             ("engine.dispatch", 2, 1.3, 1.4, "worker"),    # not a loop phase
             ("engine.yield", 1, 1.45, 1.6, "loop"),
             ("engine.plan", 2, 2.1, 2.5, "loop")]
    idle = host_spans.idle_by_phase(busy, spans)
    assert idle == pytest.approx({
        "engine.wait": 0.05, "engine.emit": 0.2, "engine.yield": 0.05,
        "engine.plan": 0.4, None: 0.2})
    assert sum(idle.values()) == pytest.approx(0.3 + 0.6)


XSPACE = """
planes {
  name: "/device:TPU:0"
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "hlo_category" } }
  event_metadata { key: 1 value { id: 1 name: "%while.3"
    stats { metadata_id: 2 str_value: "while" } } }
  event_metadata { key: 2 value { id: 2 name: "%ragged_paged_attention.5"
    stats { metadata_id: 1 str_value: "jit(_ragged_paged_step)/while/body/closed_call/attn/ragged_paged_attention/pallas_call:" }
    stats { metadata_id: 2 str_value: "custom-call" } } }
  event_metadata { key: -3 value { id: -3 name: "%fusion.7"
    stats { metadata_id: 1 str_value: "jit(_ragged_paged_step)/while/body/closed_call/ffn/dot_general:" } } }
  event_metadata { key: 4 value { id: 4 name: "%copy.1"
    stats { metadata_id: 1 str_value: "jit(_ragged_paged_step)/while/body/squeeze:" } } }
  lines { name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000000 }
    events { metadata_id: -3 offset_ps: 4000000000 duration_ps: 3000000000 }
    events { metadata_id: 2 offset_ps: 7000000000 duration_ps: 1000000000 }
    events { metadata_id: 4 offset_ps: 8000000000 duration_ps: 500000000 } }
  lines { name: "XLA Modules"
    events { metadata_id: 2 duration_ps: 9000000000 } }
}
planes {
  name: "/host:CPU"
  lines { name: "XLA Ops" events { metadata_id: 2 duration_ps: 5000000000 } }
}
"""


def test_device_time_goes_to_the_named_scope_in_its_op_name(tmp_path):
    """A TPU trace keeps an operation's ``op_name`` as stat ``tf_op`` of the
    event's metadata: the scope is one of its components, the ``while`` that
    holds the layer scan is not counted beside its body, and only the device
    planes' ``XLA Ops`` lines count."""
    from jax.profiler import ProfileData

    from benchmark import host_spans

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert host_spans.device_ms_by_scope(str(path)) == pytest.approx(
        {"attn": 5.0, "ffn": 3.0, None: 0.5})


def test_the_recorded_chip_trace_groups_by_op_name():
    from benchmark import host_spans, xplane

    recorded = ROOT / "tests" / "benchmark" / "data" / "tpu_small.xplane.pb"
    plane = xplane.device_planes(xplane.load(str(recorded)))[0]
    ops_ms = 1e3 * sum(d for _, _, d in xplane.line_events(plane, xplane.OPS_LINE))
    # its one named operation is `jit(f)/dot_general:`
    got = host_spans.device_ms_by_scope(str(recorded), scopes=("dot_general",))
    assert set(got) == {"dot_general", None} and got["dot_general"] > 0
    assert sum(got.values()) == pytest.approx(ops_ms, rel=2e-3)


def added_entries():
    names = [n for n, _ in EXPECT]
    per_layer = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
    return [m for m in per_layer if m["name"] in names]


def manifest_holds_every_phase_metric(manifest):
    names = sorted(n for n, _ in EXPECT)
    assert sorted(m["name"] for m in manifest["per_layer"] if m["name"] in names) == names


def test_every_new_metric_is_in_the_manifest():
    manifest_holds_every_phase_metric(
        json.loads((ROOT / "BENCHMARK.json").read_text()))


def tiny_manifest_with_the_added_entries(tmp_path):
    """The tiny manifest is the benchmark's own file and stays as it is: the
    rehearsal runs on a copy that gains the entries BENCHMARK.json gained,
    those of the cells that judge a TTFT cut to the tiny cells."""
    manifest = json.loads((TINY / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in manifest["workloads"]]
    for m in added_entries():
        manifest["per_layer"].append(dict(m, workloads=cells) if "workloads" in m else m)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest, indent=1))
    return path


def test_rehearsal_prints_the_engine_spans(tmp_path):
    manifest = tiny_manifest_with_the_added_entries(tmp_path)
    proc = run(["--manifest", str(manifest),     # the later one wins
                "--workload", "tiny.chat", "--seed", str(2 ** 31 + 78),
                "--seconds", "8", "--trace", "1", "--rehearse"], tmp_path, 420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    # `correct` is test_bench_rehearsal's to hold: on a loaded test machine
    # an open loop under the Python tracer may answer some requests late, and
    # the spans are printed all the same
    assert line["attempted"] > 0, proc.stderr[-3000:]
    got = line["metrics"]
    # no device plane in a CPU trace: the share of the device's idle time is
    # left out; everything read from the program alone is there
    assert "idle_explained_share" not in got
    assert set(got) >= {n for n, _ in EXPECT} - {
        "idle_explained_share", "front_ttft_gap_ms"}
    phases = sum(got["step_{}_ms".format(p)]["value"] for p in STEP)
    gap = got["step_gap_ms"]["value"]
    assert gap == pytest.approx(phases - got["step_wait_ms"]["value"], rel=0.01)
    assert 0 < gap < phases
    # means over the window: a request popped before it opened and answered
    # inside it counts in three of the four, so no exact sum here
    assert got["eng_ttft_ms"]["value"] >= got["eng_prefill_ms"]["value"] > 0
    assert got["eng_prefill_launches"]["value"] >= 1
    assert 0 < got["kv_pool_used_peak_share"]["value"] <= 100
