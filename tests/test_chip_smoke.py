"""chip_smoke.py off the chip (the on-chip run is the builder's/driver's):
the sandbox contract of the script, and the compile-cache placement rule
every chip entry point goes through.

- the default invocation finds no accelerator, exits non-zero BEFORE any
  phase and prints no result line;
- alone in a directory (nothing else of the repo) it fails the same way;
- ``--size tiny`` walks all three phases for real — CLI, server children,
  HTTP round trips, SIGTERM drain, the kernel child in interpret mode —
  prints ``"device_ok": false`` and still exits non-zero: it proves the
  control flow, never "passed on CPU".
"""

import atexit
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
from pathlib import Path

import jax

from clearml_serving_tpu.engines import jax_engine

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_default_invocation_without_a_chip_fails_and_prints_no_result(tmp_path):
    out = subprocess.run(
        [sys.executable, str(SMOKE), "--out", str(tmp_path / "out")],
        capture_output=True, text=True, timeout=300, env=_env(),
    )
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout
    assert "no TPU" in out.stderr


def test_script_alone_fails_and_prints_no_result(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SMOKE, alone / "chip_smoke.py")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=str(alone),
        capture_output=True, text=True, timeout=300, env=_env(PYTHONPATH=""),
    )
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout


_TINY_WALK = None


def start_tiny_walk():
    """Start ``chip_smoke.py --size tiny`` in the background (once). It is
    a minute of child processes that wait on each other, so conftest starts
    it when collection ends and the test below joins it: the walk overlaps
    the first test files instead of adding its length to the tier-1 run."""
    global _TINY_WALK
    if _TINY_WALK is None:
        tmp = Path(tempfile.mkdtemp(prefix="chip_smoke_tiny_"))
        proc = subprocess.Popen(
            [sys.executable, str(SMOKE), "--size", "tiny",
             "--out", str(tmp / "out")],
            stdout=open(tmp / "stdout", "w"), stderr=open(tmp / "stderr", "w"),
            # no program of this run is worth caching: keep the (cold) cache
            # out of the test's time
            env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp / "xla_cache"),
                     JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="1000"),
        )
        _TINY_WALK = (proc, tmp)
        atexit.register(_stop_tiny_walk)
    return _TINY_WALK


def _stop_tiny_walk():
    proc, tmp = _TINY_WALK
    if proc.poll() is None:
        # SIGINT, not SIGKILL: the script's ``finally`` stops its server
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
    shutil.rmtree(tmp, ignore_errors=True)


def test_tiny_size_walks_every_phase_and_still_fails_off_chip():
    proc, tmp = start_tiny_walk()
    returncode = proc.wait(timeout=600)
    stderr = (tmp / "stderr").read_text()
    assert returncode == 1, stderr[-2000:]
    detail, verdict = (tmp / "stdout").read_text().strip().splitlines()[-2:]
    # the last line is the verdict, with exactly the contract's keys
    assert json.loads(verdict) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": 1},
    }
    summary = json.loads(detail)
    assert summary["ok"] is False and summary["device_ok"] is False
    assert summary["device"]["platform"] == "cpu"
    phases = summary["phases"]
    assert set(phases) == {"A", "B", "C"}
    for name, phase in phases.items():
        assert phase["passed"], (name, phase.get("error"))
    a, b, c = phases["A"], phases["B"], phases["C"]
    # A: requests round-tripped through the ragged scheduler; the repaired
    # sentry counted compiles and none for the repeated request
    assert a["ragged"]["steps"] > 0
    assert a["ragged"]["step_rows"]["prefill"] > 0
    assert a["ragged"]["step_rows"]["decode"] > 0
    assert a["weights"]["quant"] == "int8"
    assert a["compiles"]["after_a"] > 0
    assert a["compiles"]["after_c"] == a["compiles"]["after_b"]
    # the health block names the implementation and why, on every path
    assert a["kernels"]["decode"] == "xla"
    assert "platform cpu" in a["kernels"]["reason"]["ragged"]
    assert "dense" in b["kernels"]["reason"]["decode"]
    assert summary["stats_queue"] in ("python", "native")
    # C: every variant ran through the Pallas interpreter, and says so
    assert c["mode"] == "interpret"
    names = [v["variant"] for v in c["variants"]]
    assert any("tree_anc" in n for n in names)
    # stacked pools (ISSUE 25): one case of each attention kernel, the
    # write; and the ragged kernel's chunk row of two tiles beside decode
    # rows, with its stacked twin (ISSUE 30)
    assert sum(n.endswith(" stacked") for n in names) == 5
    assert sum(" chunk of " in n for n in names) == 2
    assert len(set(names)) == len(names)
    assert sum(n.startswith("paged_kv_write") for n in names) == 2
    assert any(n.startswith("fused_int4_matmul") for n in names)
    assert all(v["ok"] for v in c["variants"])
    # every child is gone: the state roots and logs are all that is left
    assert (tmp / "out" / "result.json").exists()


# -- compile-cache placement (engines/jax_engine.py) --------------------------

def test_cache_dir_from_the_environment_is_left_alone(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> code sets nothing (jax reads the
    variable itself at import; the config is untouched by the call)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    before = jax.config.jax_compilation_cache_dir
    jax_engine.enable_persistent_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "placed").exists()


def test_cache_dir_defaults_to_the_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        jax_engine.enable_persistent_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
