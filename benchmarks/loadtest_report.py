"""Per-endpoint latency-percentile report (BASELINE.md: "req/s + p50/p99
TTFT per endpoint").

Boots a REAL router process with two endpoints — the sklearn iris example
(CPU hot loop, router-overhead bound) and a tiny continuous-batching LLM
endpoint (streaming chat, TTFT) — drives each through the loadtest harness
(examples/loadtest/loadtest.py, the reference's `ab -n .. -c ..` recipe),
and writes ``benchmarks/LOADTEST_<platform>.json`` with req/s + p50/p99
latency + p50/p99 TTFT per endpoint.

    python benchmarks/loadtest_report.py            # cpu (JAX_PLATFORMS=cpu)
    python benchmarks/loadtest_report.py --platform default   # real backend

CPU numbers measure the router/orchestration overhead path; the LLM tok/s
story lives in bench.py. Platform is recorded in the artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
PORT = int(os.environ.get("LOADTEST_PORT", 18090))

BOOT = '''
import sys, os
sys.path.insert(0, {repo!r})
os.environ["TPUSERVE_STATE_ROOT"] = {state_root!r}
import jax as _jax  # record the REAL backend for the report artifact
_d = _jax.devices()[0]
with open(os.path.join({state_root!r}, "backend.txt"), "w") as _f:
    _f.write("{{}}:{{}}".format(_d.platform, _d.device_kind))
import joblib
from sklearn.datasets import load_iris
from sklearn.linear_model import LogisticRegression
x, y = load_iris(return_X_y=True)
joblib.dump(LogisticRegression(max_iter=200).fit(x, y),
            os.path.join({state_root!r}, "sk.pkl"))
from clearml_serving_tpu.serving.endpoints import ModelEndpoint
from clearml_serving_tpu.serving.model_request_processor import ModelRequestProcessor
p = ModelRequestProcessor(force_create=True)
rec = p.registry.register("iris", path=os.path.join({state_root!r}, "sk.pkl"),
                          framework="sklearn")
p.add_endpoint(
    ModelEndpoint(engine_type="sklearn", serving_url="test_model_sklearn",
                  model_id=rec.id),
    preprocess_code=os.path.join({repo!r}, "examples/sklearn/preprocess.py"),
)
p.add_endpoint(dict(engine_type="llm", serving_url="test_llm",
                    auxiliary_cfg={{"engine": {{"preset": {preset!r},
                                                "max_batch": 8,
                                                "max_seq_len": 256,
                                                "decode_steps": 8}}}}))
p.serialize()
os.environ["TPUSERVE_SERVICE_ID"] = p._service.id
from clearml_serving_tpu.serving.main import build_app, setup_processor
from aiohttp import web
web.run_app(build_app(setup_processor()), host="127.0.0.1", port={port})
'''


def _wait_healthy(timeout=180):
    import urllib.request

    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            with urllib.request.urlopen(
                "http://127.0.0.1:{}/health".format(PORT), timeout=2
            ) as r:
                if r.status == 200:
                    return True
        except Exception:
            time.sleep(1)
    return False


def _loadtest(url, payload, n, c):
    out = subprocess.run(
        [
            sys.executable,
            str(REPO / "examples/loadtest/loadtest.py"),
            url,
            "--payload",
            json.dumps(payload),
            "-n",
            str(n),
            "-c",
            str(c),
        ],
        capture_output=True,
        text=True,
        timeout=1200,
    )
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    if not lines:
        return {"error": (out.stderr or "no output").strip()[-300:]}
    return json.loads(lines[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="cpu", choices=["cpu", "default"])
    ap.add_argument("--preset", default=None, help="llm preset override")
    ap.add_argument("-n", type=int, default=2000, help="requests per endpoint")
    ap.add_argument("-c", type=int, default=64, help="concurrency")
    args = ap.parse_args()
    force_cpu = args.platform == "cpu"
    preset = args.preset or ("llama-tiny" if force_cpu else "llama3-1b")

    import tempfile

    state_root = tempfile.mkdtemp(prefix="loadtest_state_")
    boot = BOOT.format(
        repo=str(REPO), state_root=state_root, port=PORT, preset=preset,
    )
    # this parent never imports jax: the router child is the one process
    # that owns the backend (a chip belongs to one process). --platform cpu
    # forces the child onto the CPU; default inherits the environment.
    env = dict(os.environ)
    if force_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-c", boot],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE,
        env=env,
    )
    try:
        if not _wait_healthy():
            proc.terminate()
            err = proc.stderr.read().decode()[-500:] if proc.stderr else ""
            print(json.dumps({"error": "router failed to boot", "stderr": err}))
            sys.exit(1)

        base = "http://127.0.0.1:{}".format(PORT)
        try:
            with open(os.path.join(state_root, "backend.txt")) as f:
                backend = f.read().strip()
        except OSError:
            backend = "unknown"
        report = {
            "platform": args.platform,
            "backend": backend,
            "llm_preset": preset,
            "n": args.n,
            "concurrency": args.c,
            "endpoints": {},
        }
        report["endpoints"]["sklearn_process"] = _loadtest(
            base + "/serve/test_model_sklearn",
            {"x0": 5.1, "x1": 3.5, "x2": 1.4, "x3": 0.2},
            args.n,
            args.c,
        )
        # streaming chat: TTFT percentiles; fewer requests (each generates
        # tokens), lower concurrency than max_batch*queue to keep it honest
        report["endpoints"]["llm_chat_stream"] = _loadtest(
            base + "/serve/openai/v1/chat/completions",
            {
                "model": "test_llm",
                "messages": [{"role": "user", "content": "hello there"}],
                "max_tokens": 16,
                "stream": True,
            },
            max(64, args.n // 10),
            min(16, args.c),
        )
        # LOADTEST_<platform>.json now belongs to the SLO loadtest harness
        # (benchmarks/slo_loadtest.py, `bench.py --loadtest`); this router-
        # overhead report keeps its own artifact under a _router_ name
        out_path = REPO / "benchmarks" / "LOADTEST_router_{}.json".format(
            args.platform
        )
        out_path.write_text(json.dumps(report, indent=2) + "\n")
        print(json.dumps(report))
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


if __name__ == "__main__":
    main()
