"""One CPU rehearsal of run.py on a tiny test-only configuration: it walks
set-up, reference, priming, ramp, window and drain, prints the contract's last
line naming the CPU, and exits non-zero because that is no chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
TINY = ROOT / "tests" / "benchmark" / "tiny"


def run(args, tmp_path, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)       # one CPU device, as one chip
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--manifest", str(TINY / "BENCHMARK.json"),
         "--traffic-dir", str(TINY / "traffic"),
         "--out", str(tmp_path / "out")] + args,
        cwd=str(ROOT), env=env, capture_output=True, text=True, timeout=timeout)


def test_without_a_chip_nothing_runs_and_nothing_is_printed(tmp_path):
    proc = run(["--workload", "tiny.chat", "--seed", "1", "--seconds", "5",
                "--trace", "0"], tmp_path, 120)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "nothing was run" in proc.stderr


@pytest.mark.parametrize("cell,trace,metric", [
    ("tiny.chat", "0", "ttft_p50_ms"),
    ("tiny.closed", "1", "sched_tokens_per_launch"),
])
def test_rehearsal_walks_every_phase(tmp_path, cell, trace, metric):
    proc = run(["--workload", cell, "--seed", str(2 ** 31 + 77), "--seconds", "8",
                "--trace", trace, "--rehearse"], tmp_path, 420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert metric in line["metrics"]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    if trace == "0":
        assert line["metrics"]["setup_s"]["value"] > 0
    else:
        # no device plane in a CPU trace: device metrics are left out, never
        # filled from the host
        assert "device_idle_share" not in line["metrics"]
        assert "busy_s" not in line["device"]
    # a closed loop says how far from its plan's end it was; every number
    # compared stands beside its limit, last on the line
    if cell == "tiny.closed":
        far = line["closed_loop"]
        assert 0 <= far["deepest_request"] < far["block0_per_client"] == far["per_client"]
    else:
        assert "closed_loop" not in line
    assert list(line)[-1] == "checks"
    assert all(len(pair) == 2 for pair in line["checks"].values())
    assert line["checks"]["failed_requests"] == [0, 0]
    assert line["checks"]["compiles_in_window"] == [0, 0]
    detail = json.loads((tmp_path / "out" / "detail.json").read_text())
    assert detail["compiles_in_window"] == []
    assert detail["reference"]["repeat_identical"] and detail["reference"]["within"]
    assert detail["primed"]["launch_windows"] == [4, 1, 2]
    records = [json.loads(x) for x in
               (tmp_path / "out" / "records.jsonl").read_text().splitlines()]
    assert any(r["judged"] for r in records)
    assert all(r["late"] is not None for r in records if r["sent"])
