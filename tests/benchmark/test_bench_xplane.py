"""The reduction from a profiler trace to busy time, operations and gaps: on
hand-made events, and on a small trace recorded on a TPU v5 lite (four
launches of one jitted matmul + tanh + sum; my chip run, PR 23)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import xplane  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "tpu_small.xplane.pb"
EVENTS = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("a", 3.0, 0.5), ("custom-call.1", 3.5, 0.25)]


@pytest.mark.parametrize("lo,hi,expect", [
    (None, None, 2.25), (1.0, None, 1.25), (None, 3.25, 1.75), (0.25, 0.75, 0.5),
])
def test_union_counts_overlap_once(lo, hi, expect):
    assert xplane.union_seconds(EVENTS, lo, hi) == pytest.approx(expect)


def test_gaps_between_operations():
    assert xplane.gaps(EVENTS) == [(1.5, 1.5)]
    assert xplane.gaps(EVENTS, min_s=2.0) == []


def test_by_name_sums_and_sorts():
    assert xplane.by_name(EVENTS)[0] == ("a", 1.5, 2)


@pytest.mark.parametrize("name,kernel", [
    ("closed_call.8_custom-call", True), ("fusion.184", False),
    ("copy.138", False), ("paged_attention_pallas", True),
])
def test_kernels_are_custom_calls(name, kernel):
    assert xplane.is_kernel(name) is kernel


def test_recorded_trace_reduces():
    red = xplane.reduce_trace(str(RECORDED))
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    # the 10 ms sleep between the third and the fourth launch is idle time
    assert red["window_s"] - red["busy_s"] > 0.005
    assert red["n_launches"] == 4
    assert max(length for _, length in red["gaps"]) > 0.005
    assert sum(t for _, t, _ in red["ops"]) >= red["busy_s"] * 0.999
    assert red["kernel_s"] == 0.0


def test_a_trace_without_a_device_plane_gives_no_device_numbers(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    red = xplane.reduce_trace(xplane.find_xplane(str(tmp_path)))
    assert red["devices"] == 0 and "busy_s" not in red
