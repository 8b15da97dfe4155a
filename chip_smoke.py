#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py                  # one TPU chip, Llama-3-8B width
    python3 chip_smoke.py --size tiny      # sandbox control-flow proof (CPU)

Drives the request path a user drives — CLI -> control-plane state ->
``python -m clearml_serving_tpu.serving.main`` -> real HTTP on
``/serve/openai/v1/chat/completions`` -> ``LLMEngineCore`` -> paged cache ->
Pallas kernels — and checks what comes out by the repo's own means:

- Phase A: ``llama3-8b`` at all 32 layers and every published width, int8
  weights generated packed (random, seeded), paged bf16 KV, ragged scheduler,
  the full warmup sweep at startup. One non-streaming completion, eight concurrent streams
  with prompts spread over 64-1500 bytes, the first completion again
  (identical token ids, finite logprobs), then the engine's own health block:
  platform, scheduler counters, weight format, ``pallas`` for the decode and
  ragged launches, a compile count that is > 0 and flat across the repeat,
  peak HBM. SIGTERM -> drain -> exit 0.
- Phase B: the README's LLM quick start verbatim (``llama3-1b``: dense cache,
  two-dispatch scheduler, bf16, head_dim 64 -> the XLA attention, and the
  health block must say why).
- Phase C: every Pallas kernel variant the repo ships, compiled by Mosaic
  (``interpret=False``) at Llama-3-8B shapes against its XLA reference.

The parent process is stdlib only and never imports jax: a chip belongs to
one process, so each phase is one child that owns the chip while it lives.
Any non-zero child, any non-200 answer, any failed assertion exits non-zero.
No tokens/s and no latency are printed: this script states no speed.

Without an accelerator the default invocation exits 2 before any phase and
prints no result line. ``--size tiny`` is the sandbox mode: it walks every
phase at ``llama-tiny`` shapes (Phase C in interpret mode, said so in its
output), prints ``"device_ok": false`` and still exits non-zero — it proves
the control flow, never "passed on CPU".

A run that reached the phases prints two JSON lines on stdout. The first
is the detail: per-phase pass/fail, versions, seconds to ``/ready``, peak
HBM, which stats queue served (also written to ``result.json``). The LAST
is the verdict and holds exactly these keys, the device as jax reports it:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Phase A: ISSUE 21's configuration, with two changes. scan_layers — the
# unrolled 32-layer graph "takes many minutes to compile" (models/llama.py)
# and the whole script has 1200 s; the scanned build runs the same layer
# body 32 times. warmup=full instead of startup — the startup pass leaves
# shapes that depend on request timing (the finish-row gather's pad size,
# the decode windows concurrent streams happen to form) to first use, so a
# second run compiled programs the first never met and its compile cache
# grew; the full sweep compiles them all before /ready. KV: 8 slots x 2048
# tokens x 128 KB = 2.1 GB bf16 next to 8.6 GB of int8 weights on the
# 16 GB chip.
SIZES = {
    "full": {
        "a_aux": [
            "engine.preset=llama3-8b", "engine.config.scan_layers=true",
            "engine.weight_quant=int8", "engine.cache=paged",
            "engine.scheduler=ragged", "engine.page_size=16",
            "engine.max_batch=8", "engine.max_seq_len=2048",
            "engine.warmup=full",
        ],
        # README.md "LLM endpoint" quick start, verbatim
        "b_aux": [
            "engine.preset=llama3-1b", "engine.max_batch=16",
            "engine.decode_steps=8",
        ],
        "a_tokens": 32, "stream_tokens": 64,
        "stream_prompt_bytes": [64, 269, 474, 679, 884, 1089, 1294, 1500],
        "b_prompt_bytes": [40, 90, 150, 230],
        "ready_timeout": 900.0, "request_timeout": 600.0,
    },
    "tiny": {
        "a_aux": [
            "engine.preset=llama-tiny", "engine.config.scan_layers=true",
            "engine.weight_quant=int8", "engine.cache=paged",
            "engine.scheduler=ragged", "engine.page_size=16",
            "engine.max_batch=8", "engine.max_seq_len=256",
            "engine.warmup=full",
        ],
        "b_aux": [
            "engine.preset=llama-tiny", "engine.max_batch=16",
            "engine.decode_steps=8",
        ],
        "a_tokens": 4, "stream_tokens": 8,
        "stream_prompt_bytes": [8, 40, 90, 150],
        "b_prompt_bytes": [8, 40],
        "ready_timeout": 300.0, "request_timeout": 300.0,
    },
}


class SmokeFailure(Exception):
    """A check of the smoke failed; the message says which."""


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


def log(message):
    print("[chip_smoke] " + message, file=sys.stderr, flush=True)


def child_env(out_dir: Path, extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env["TPUSERVE_CACHE_DIR"] = str(out_dir / "artifact_cache")
    # every program goes to the persistent compile cache, not only those
    # over jax's compile-time threshold: a compile that straddles the
    # threshold would otherwise be cached by one run and not the next
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    env.update(extra or {})
    return env


def run_child(argv, env, timeout, log_path: Path):
    """Run one child to its end (it owns the chip meanwhile). Non-zero exit
    is a failure; returns its stdout."""
    with open(log_path, "w") as err:
        try:
            proc = subprocess.run(
                argv, cwd=str(ROOT), env=env, stdout=subprocess.PIPE,
                stderr=err, timeout=timeout, text=True,
            )
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                "{} did not finish in {:.0f} s (see {})".format(
                    " ".join(argv[1:4]), timeout, log_path
                )
            )
    if proc.returncode != 0:
        tail = log_path.read_text()[-2000:]
        raise SmokeFailure(
            "{} exited {}:\n{}".format(" ".join(argv[1:5]), proc.returncode, tail)
        )
    return proc.stdout


# ------------------------------------------------------------- HTTP (stdlib)

def _request(method, url, body=None):
    data = None if body is None else json.dumps(body).encode("utf-8")
    return urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )


def http_json(method, url, body=None, timeout=60.0):
    try:
        with urllib.request.urlopen(_request(method, url, body),
                                    timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode("utf-8"))
    except urllib.error.HTTPError as ex:
        payload = ex.read().decode("utf-8", "replace")
        try:
            return ex.code, json.loads(payload)
        except ValueError:
            return ex.code, {"raw": payload}


def http_stream(url, body, timeout):
    """POST a streaming chat completion; returns (status, usage of the final
    chunk, finish_reason, whether [DONE] arrived)."""
    usage, finish, done = None, None, False
    try:
        with urllib.request.urlopen(_request("POST", url, body),
                                    timeout=timeout) as resp:
            status = resp.status
            for raw in resp:
                line = raw.decode("utf-8").strip()
                if not line.startswith("data:"):
                    continue
                payload = line[5:].strip()
                if payload == "[DONE]":
                    done = True
                    break
                chunk = json.loads(payload)
                if chunk.get("usage"):
                    usage = chunk["usage"]
                for choice in chunk.get("choices") or []:
                    if choice.get("finish_reason"):
                        finish = choice["finish_reason"]
    except urllib.error.HTTPError as ex:
        return ex.code, None, ex.read().decode("utf-8", "replace")[:500], False
    return status, usage, finish, done


def prompt_of(n_bytes: int, salt: int) -> str:
    """Deterministic ASCII content of exactly ``n_bytes`` bytes (the byte
    tokenizer gives one token per byte)."""
    words = ["pallas", "paged", "ragged", "mosaic", "tensor", "chip",
             "page", "token", "query", "head"]
    out = []
    i = salt
    while sum(len(w) + 1 for w in out) < n_bytes:
        out.append(words[i % len(words)])
        i += 3
    return " ".join(out)[:n_bytes].ljust(n_bytes, ".")


# ------------------------------------------------------------ server phases

class Server:
    """One ``python -m clearml_serving_tpu.serving.main`` child on a fresh
    state root, set up through the CLI exactly as the README does."""

    def __init__(self, name, out_dir: Path, endpoint, aux):
        self.name = name
        self.endpoint = endpoint
        self.dir = out_dir / name
        self.dir.mkdir(parents=True, exist_ok=True)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = "http://127.0.0.1:{}".format(self.port)
        self.env = child_env(out_dir, dict(
            TPUSERVE_STATE_ROOT=str(self.dir / "state"),
            TPUSERVE_HOST="127.0.0.1",
            TPUSERVE_PORT=str(self.port),
            TPUSERVE_COMPILE_SENTRY="1",
        ))
        cli = [sys.executable, "-m", "clearml_serving_tpu"]
        created = run_child(
            cli + ["create", "--name", "chip-smoke-" + name],
            self.env, 120, self.dir / "cli_create.log",
        )
        check("id=" in created, "create printed no service id: " + created)
        self.env["TPUSERVE_SERVICE_ID"] = created.strip().rsplit("id=", 1)[1]
        run_child(
            cli + ["model", "add", "--engine", "llm", "--endpoint", endpoint,
                   "--aux-config"] + list(aux),
            self.env, 120, self.dir / "cli_model_add.log",
        )
        self.proc = None
        self.log_file = None
        self.ready_seconds = None

    def start(self, ready_timeout):
        self.log_file = open(self.dir / "server.log", "w")
        t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "clearml_serving_tpu.serving.main"],
            cwd=str(ROOT), env=self.env, stdout=self.log_file,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        last = None
        while time.monotonic() - t0 < ready_timeout:
            check(self.proc.poll() is None,
                  "{} server exited {} before /ready:\n{}".format(
                      self.name, self.proc.returncode, self.log_tail()))
            try:
                status, last = http_json("GET", self.base + "/ready", timeout=10)
            except (urllib.error.URLError, OSError, ValueError):
                time.sleep(0.5)
                continue
            engine = (last.get("engines") or {}).get(self.endpoint)
            if status == 200 and engine is None:
                # launch prefetches every endpoint, so an engine missing
                # from /ready failed to load; a request surfaces the error
                code, body = http_json("POST", chat_url(self), {
                    "model": self.endpoint, "max_tokens": 1,
                    "messages": [{"role": "user", "content": "x"}],
                }, timeout=600)
                raise SmokeFailure("{} endpoint did not load: HTTP {} {}".format(
                    self.name, code, json.dumps(body)[:1500]))
            engine = engine or {}
            warm = engine.get("warmup")
            check(not str(warm).startswith("failed"),
                  "{} warmup {}".format(self.name, warm))
            if status == 200 and engine.get("ready"):
                self.ready_seconds = round(time.monotonic() - t0, 1)
                return last
            time.sleep(0.5)
        raise SmokeFailure("{} not ready after {:.0f} s; last /ready: {}\n{}".format(
            self.name, ready_timeout, json.dumps(last)[:1500], self.log_tail()))

    def ready(self):
        status, payload = http_json("GET", self.base + "/ready", timeout=30)
        check(status == 200, "{} /ready answered {}: {}".format(
            self.name, status, json.dumps(payload)[:800]))
        return payload["engines"][self.endpoint], payload

    def log_tail(self, n=3000):
        self.log_file.flush()
        return (self.dir / "server.log").read_text(errors="replace")[-n:]

    def stop(self):
        """SIGTERM -> graceful drain -> exit code 0."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            code = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(self.name + " did not exit 120 s after SIGTERM")
        check(code == 0, "{} exited {} after SIGTERM:\n{}".format(
            self.name, code, self.log_tail()))

    def kill(self):
        if self.proc is not None and self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=30)
        if self.log_file is not None:
            self.log_file.close()


def chat_url(server):
    return server.base + "/serve/openai/v1/chat/completions"


def completion_with_ids(server, cfg, tag):
    """One non-streaming chat completion, greedy, fixed length; returns its
    token ids (``return_tokens_as_token_ids``) after checking the count and
    that every logprob is a finite number."""
    n = cfg["a_tokens"]
    status, body = http_json("POST", chat_url(server), {
        "model": server.endpoint,
        "messages": [{"role": "user", "content": prompt_of(48, 1)}],
        "max_tokens": n, "min_tokens": n, "temperature": 0,
        "logprobs": True, "top_logprobs": 1,
        "return_tokens_as_token_ids": True,
    }, timeout=cfg["request_timeout"])
    check(status == 200, "{}: HTTP {}: {}".format(tag, status, json.dumps(body)[:800]))
    check(body["usage"]["completion_tokens"] == n,
          "{}: usage {}".format(tag, body["usage"]))
    entries = body["choices"][0]["logprobs"]["content"]
    check(len(entries) == n, "{}: {} logprob entries".format(tag, len(entries)))
    for e in entries:
        lp = e["logprob"]
        check(isinstance(lp, (int, float)) and lp == lp and abs(lp) != float("inf")
              and lp <= 1e-3, "{}: logprob {!r} is not a finite log-probability".format(tag, lp))
    return [e["token"] for e in entries], body["choices"][0]["message"]["content"]


def streams(server, cfg, prompt_bytes, tag):
    """Concurrent streaming completions; every stream must deliver its
    tokens, finish by length and end in [DONE]."""
    n = cfg["stream_tokens"]

    def one(i_bytes):
        i, nb = i_bytes
        return http_stream(chat_url(server), {
            "model": server.endpoint,
            "messages": [{"role": "user", "content": prompt_of(nb, i)}],
            "max_tokens": n, "min_tokens": n, "temperature": 0,
            "stream": True, "stream_options": {"include_usage": True},
        }, timeout=cfg["request_timeout"])

    with ThreadPoolExecutor(max_workers=len(prompt_bytes)) as pool:
        results = list(pool.map(one, enumerate(prompt_bytes)))
    for nb, (status, usage, finish, done) in zip(prompt_bytes, results):
        where = "{} stream ({} prompt bytes)".format(tag, nb)
        check(status == 200, "{}: HTTP {} {}".format(where, status, finish))
        check(usage and usage["completion_tokens"] == n,
              "{}: usage {}".format(where, usage))
        check(usage["prompt_tokens"] >= nb, "{}: usage {}".format(where, usage))
        check(finish == "length", "{}: finish_reason {}".format(where, finish))
        check(done, where + ": no [DONE]")


def check_loop_health(health, tag):
    """A cold compile that outlasted the watchdog's grace, or a failed
    step, is a failure of the smoke even when every request was answered."""
    check(health["watchdog_trips"] == 0 and health["step_failures"] == 0,
          "{}: watchdog_trips {} step_failures {}".format(
              tag, health["watchdog_trips"], health["step_failures"]))


def compile_count(health):
    """Programs the (armed) compile sentry saw built, before and after the
    warmup fence."""
    return health["compile"]["warmup"] + health["compile"]["serve"]


def device_of(health):
    dev = health["device"]
    return {"platform": dev["platform"], "kind": dev["device_kind"],
            "count": dev["device_count"]}


def phase_a(cfg, out_dir, want_tpu):
    server = Server("phase_a", out_dir, "chat8b", cfg["a_aux"])
    try:
        server.start(cfg["ready_timeout"])
        ids_a, text_a = completion_with_ids(server, cfg, "A(a)")
        compiles_a = compile_count(server.ready()[0])
        check(compiles_a > 0, "A: the compile sentry counted no compile after (a)")
        streams(server, cfg, cfg["stream_prompt_bytes"], "A(b)")
        compiles_b = compile_count(server.ready()[0])
        ids_c, text_c = completion_with_ids(server, cfg, "A(c)")
        check(ids_c == ids_a and text_c == text_a,
              "A(c): the repeated request answered differently: {} vs {}".format(
                  ids_a, ids_c))
        health, payload = server.ready()
        compiles_c = compile_count(health)
        check(compiles_c == compiles_b,
              "A(c): {} programs compiled for a request already served".format(
                  compiles_c - compiles_b))
        check(health["compile"]["fenced"], "A: the full warmup sweep set no fence")
        check_loop_health(health, "A")
        check(health["scheduler"] == "ragged", "A: scheduler " + str(health["scheduler"]))
        rows = health["ragged"]["step_rows"]
        check(health["ragged"]["steps"] > 0 and rows["prefill"] > 0 and rows["decode"] > 0,
              "A: ragged counters {}".format(health["ragged"]))
        check(health["weights"]["quant"] == "int8", "A: weights {}".format(health["weights"]))
        kernels = health["kernels"]
        if want_tpu:
            check(kernels["decode"] == "pallas" and kernels["ragged"] == "pallas",
                  "A: attention kernels {}".format(kernels))
            check(health["device"]["peak_bytes_in_use"],
                  "A: no peak_bytes_in_use in {}".format(health["device"]))
            check(health["device"]["device_count"] == 1,
                  "A: one engine owns one chip: {}".format(health["device"]))
        else:
            check(kernels["decode"] == "xla" and "platform" in kernels["reason"]["decode"],
                  "A: attention kernels {}".format(kernels))
        server.stop()
        return {
            "ready_seconds": server.ready_seconds,
            "device": device_of(health),
            "kernels": kernels,
            "compiles": {"after_a": compiles_a, "after_b": compiles_b,
                         "after_c": compiles_c,
                         "after_fence": health["compile"]["serve"]},
            "ragged": {"steps": health["ragged"]["steps"], "step_rows": rows},
            "weights": health["weights"],
            "kv_pool": health["kv_pool"],
            "peak_bytes_in_use": health["device"]["peak_bytes_in_use"],
            "memory": health["device"]["memory"],
            "stats_queue": payload["stats_queue"],
            "aux_config": list(cfg["a_aux"]),
        }
    finally:
        server.kill()


def phase_b(cfg, out_dir, want_tpu):
    server = Server("phase_b", out_dir, "chat", cfg["b_aux"])
    try:
        server.start(cfg["ready_timeout"])
        streams(server, cfg, cfg["b_prompt_bytes"], "B")
        health, payload = server.ready()
        check_loop_health(health, "B")
        check(health["scheduler"] == "two_dispatch", "B: scheduler " + str(health["scheduler"]))
        check(health["weights"]["quant"] == "none", "B: weights {}".format(health["weights"]))
        kernels = health["kernels"]
        why = (kernels.get("reason") or {}).get("decode", "")
        check(kernels["decode"] == "xla" and "dense" in why,
              "B: attention kernels {}".format(kernels))
        if want_tpu:
            # the documented quick start never reaches a Pallas kernel, and
            # the health block must say why: head_dim 64
            check("head_dim 64" in why, "B: reason does not name head_dim 64: " + why)
        compiles = compile_count(health)
        check(compiles > 0, "B: the compile sentry counted no compile")
        server.stop()
        return {
            "ready_seconds": server.ready_seconds,
            "device": device_of(health),
            "kernels": kernels,
            "compiles": compiles,
            "peak_bytes_in_use": health["device"]["peak_bytes_in_use"],
            "stats_queue": payload["stats_queue"],
            "aux_config": list(cfg["b_aux"]),
        }
    finally:
        server.kill()


# ------------------------------------------- children that import jax (only)

def probe_child():
    """Prints the device identity and library versions as one JSON line."""
    import jax
    import jaxlib

    sys.path.insert(0, str(ROOT))
    from clearml_serving_tpu.utils.tpu import device_identity

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # the CPU-only wheel set has no libtpu
        libtpu = None
    print(json.dumps({
        "device": device_identity(),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu,
                     "python": sys.version.split()[0]},
    }))


def kernel_child(size):
    """Phase C: every Pallas variant against its XLA reference. On the chip
    each kernel is compiled by Mosaic (interpret=False); off-chip (tiny) the
    Pallas interpreter runs them and the output line says so."""
    import functools

    import numpy as np

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, str(ROOT))
    from clearml_serving_tpu.ops import fused_matmul as fm
    from clearml_serving_tpu.ops import paged_attention as pa
    from clearml_serving_tpu.ops.quant import quantize_int4

    platform = jax.devices()[0].platform
    interpret = platform != "tpu"
    if size == "full":
        check(platform == "tpu", "Phase C needs a TPU, jax reports " + platform)
        hkv, g, d, pages_per_seq = 8, 4, 128, 128   # Llama-3-8B heads, 2048/16
        int4_shapes = [("qkv_o", 4096, 4096), ("kv", 4096, 1024),
                       ("gate_up", 4096, 14336), ("down", 14336, 4096)]
        int4_rows = (8, 64, 256)
    else:
        hkv, g, d, pages_per_seq = 2, 2, 64, 4
        int4_shapes = [("proj", 256, 256)]
        int4_rows = (8,)
    dtype = jnp.bfloat16
    results = []

    # Tolerance: the reference is the repo's XLA implementation evaluated in
    # float32 at the highest matmul precision on the SAME inputs (bf16 / int8
    # / int4 values are exactly representable in f32), so the whole
    # difference is the kernel's own rounding: probabilities (or unpacked
    # weights) feed the MXU in bf16 and the output is stored in bf16, each a
    # relative error of at most 2**-9, at different points on the two sides.
    # Four such roundings of the largest output value bound it: 2**-7.
    def compare(name, out, ref):
        out = np.asarray(out, np.float32)
        ref = np.asarray(ref, np.float32)
        check(out.shape == ref.shape, "{}: shape {} vs {}".format(name, out.shape, ref.shape))
        check(np.isfinite(out).all(), name + ": non-finite kernel output")
        diff = float(np.max(np.abs(out - ref)))
        tol = float(2.0 ** -7 * max(1.0, np.max(np.abs(ref))))
        results.append({"variant": name, "max_abs_diff": round(diff, 6),
                        "tolerance": round(tol, 6), "ok": diff <= tol})
        log("C {:<44s} max_abs_diff {:.5f} (tol {:.5f}) {}".format(
            name, diff, tol, "ok" if diff <= tol else "FAIL"))

    def f32(x):
        return None if x is None else x.astype(jnp.float32)

    def ref_pools(k, v, quant):
        # bf16 pools go to the reference as f32 (int8 pools dequantize into
        # the f32 query dtype by themselves)
        return (k, v) if quant else (f32(k), f32(v))

    def reference(fn, *args):
        # only the reference runs at the highest precision: the context
        # would otherwise reach the kernels' own bf16 dots ("Bad lhs type")
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    def quantize_pool(pool):
        """Per-(token, head) symmetric int8 like models/llama._kv_store."""
        x = pool.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(x), axis=-1)
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        q = jnp.clip(jnp.round(x / scale[..., None]), -127, 127).astype(jnp.int8)
        return q, scale.astype(jnp.float32)

    def pools(key, page, rows, quant):
        n = rows * pages_per_seq + 1
        kk, kv = jax.random.split(key)
        k = jax.random.normal(kk, (hkv, n, page, d), jnp.float32).astype(dtype)
        v = jax.random.normal(kv, (hkv, n, page, d), jnp.float32).astype(dtype)
        ids = np.arange(1, n, dtype=np.int32)
        np.random.default_rng(0).shuffle(ids)   # scattered pages, page 0 null
        table = jnp.asarray(ids.reshape(rows, pages_per_seq))
        if quant:
            k, ks = quantize_pool(k)
            v, vs = quantize_pool(v)
            return k, v, table, {"k_scale": ks, "v_scale": vs}
        return k, v, table, {}

    def stacked(pool):
        """[3, ...]: the pool as layer 2 under two layers of other data."""
        other = jax.random.normal(jax.random.PRNGKey(9), (2,) + pool.shape,
                                  jnp.float32).astype(pool.dtype)
        return jnp.concatenate([other, pool[None]])

    # -- decode: ragged lengths incl. an empty row and a one-token row
    for quant, page in ((False, 16), (True, 32)):
        rows = 8
        cap = pages_per_seq * page
        k, v, table, scales = pools(jax.random.PRNGKey(1), page, rows, quant)
        q = jax.random.normal(jax.random.PRNGKey(2), (rows, hkv, g, d),
                              jnp.float32).astype(dtype)
        lengths = jnp.asarray(
            [cap, 1, 17, 0, cap // 4 + 3, cap // 2, 33, cap - 49], jnp.int32)
        out = jax.jit(functools.partial(pa.paged_attention, interpret=interpret))(
            q, k, v, table, lengths, **scales)
        ref = reference(
            pa.paged_attention_xla, f32(q), *ref_pools(k, v, quant), table,
            lengths, scales.get("k_scale"), scales.get("v_scale"))
        compare("paged_attention {}/P{}".format(
            "int8" if quant else "bf16", page), out, ref)
        if not quant:
            # the form the model step calls: the stack of all layers and a
            # traced layer index (the page DMAs start at [layer, h, page])
            out = jax.jit(functools.partial(pa.paged_attention, interpret=interpret))(
                q, stacked(k), stacked(v), table, lengths, layer=jnp.int32(2))
            compare("paged_attention bf16/P16 stacked", out, ref)

    # -- ragged: decode rows + a multi-block prefill row + a verify row (on
    # a 3-token history, so masking its siblings moves its output), an idle
    # row, and trailing work items no row owns (item_rows == -1); then a
    # chunk row of two query tiles beside decode rows
    def ragged_case(name, row_lens, history, page, quant, tree_row=None,
                    stack_too=False):
        rows = len(row_lens)
        cap = pages_per_seq * page
        row_lens = np.asarray(row_lens, np.int32)
        history = np.clip(np.asarray(history, np.int32), 0, cap - row_lens)
        kv_lens = history + row_lens
        starts, t_pad = pa.ragged_layout(row_lens)
        t_pad += 16
        tile = pa.ragged_query_tile(hkv, g, d, dtype)
        # (two more than any batch on these shapes fills: empty items)
        item_rows, item_q0 = pa.ragged_work_items(
            row_lens, tile, total=pa.ragged_item_count(rows, t_pad, tile) + 2)
        k, v, table, scales = pools(jax.random.PRNGKey(3), page, rows, quant)
        q = jax.random.normal(jax.random.PRNGKey(4), (t_pad, hkv, g, d),
                              jnp.float32).astype(dtype)
        anc = None
        if tree_row is not None:
            # a 5-node draft tree (two branches); every other token keeps
            # the plain-causal sentinel
            anc_np = np.full((t_pad, 5), -1, np.int32)
            anc_np[:, 0] = -2
            s4 = int(starts[tree_row])
            anc_np[s4:s4 + 5] = pa.tree_ancestors(
                np.asarray([-1, 0, 0, 1, 2], np.int32), width=5)
            anc = jnp.asarray(anc_np)
        args = (k, v, table, jnp.asarray(kv_lens), jnp.asarray(starts),
                jnp.asarray(row_lens))
        plan = {"item_rows": jnp.asarray(item_rows),
                "item_q0": jnp.asarray(item_q0)}
        kernel = jax.jit(functools.partial(
            pa.ragged_paged_attention, interpret=interpret))
        out = kernel(q, *args, tree_anc=anc, **plan, **scales)
        ref = reference(
            pa.ragged_paged_attention_xla, f32(q),
            *ref_pools(k, v, quant), *args[2:],
            scales.get("k_scale"), scales.get("v_scale"), anc)
        if anc is not None:
            # the operand must matter: the tree row's reference differs
            # from its plain-causal reference (siblings masked out)
            plain = reference(
                pa.ragged_paged_attention_xla, f32(q),
                *ref_pools(k, v, quant), *args[2:],
                scales.get("k_scale"), scales.get("v_scale"), None)
            moved = float(jnp.max(jnp.abs((ref - plain)[s4:s4 + 5])))
            check(moved > 0.05, "tree_anc changed the tree row by only "
                  "{}".format(moved))
        compare(name, out, ref)
        if stack_too:
            out = kernel(q, stacked(k), stacked(v), *args[2:],
                         layer=jnp.int32(2), **plan)
            compare(name + " stacked", out, ref)

    for quant, page in ((False, 16), (True, 32)):
        cap = pages_per_seq * page
        for tree in (False, True):
            ragged_case(
                "ragged_paged_attention {}/P{}{}".format(
                    "int8" if quant else "bf16", page,
                    " tree_anc" if tree else ""),
                [1, 43, 1, 0, 5, 1, 1, 9],
                [cap - 2, 100, 16, 0, 3, 0, 777, cap - 60], page, quant,
                tree_row=4 if tree else None,
                stack_too=not quant and not tree)
    # a chunk row of two tiles beside decode rows (ISSUE 30): one tile and
    # 40 queries, so the row is two work items on the same context
    tile = pa.ragged_query_tile(hkv, g, d, dtype)
    cap = pages_per_seq * 16
    # (the tiny walk's rows hold 64 tokens: its chunk is half a row)
    chunk = tile + 40 if cap > 2 * tile else cap // 2
    ragged_case(
        "ragged_paged_attention bf16/P16 chunk of {}".format(chunk),
        [1, chunk, 1, 1], [900, 200, 30, 1200], 16, False, stack_too=True)

    # -- the write of a launch's new K/V into the stacked pools: a prefill
    # run that crosses pages, decode rows between pads on the null page, a
    # page left and come back to
    for quant, page in ((False, 16), (True, 32)):
        k, v, _table, _scales = pools(jax.random.PRNGKey(7), page, 8, quant)
        coords = ([(3, o) for o in range(page - 6, page)]
                  + [(4, o) for o in range(10)] + [(7, 2)] + [(0, 0)] * 7
                  + [(8, page - 1)] + [(0, 0)] * 7
                  + [(5, 1), (6, 3), (5, 2), (5, 9)] + [(0, 0)] * 4)
        wp, wo = (jnp.asarray(c, jnp.int32) for c in zip(*coords))
        new = [jax.random.normal(jax.random.PRNGKey(8 + i),
                                 (len(coords), hkv, d), jnp.float32)
               for i in range(2)]
        if quant:
            new = [jnp.round(x * 40).astype(jnp.int8) for x in new]
        else:
            new = [x.astype(dtype) for x in new]
        out = jax.jit(functools.partial(pa.paged_kv_write, interpret=interpret))(
            stacked(k), stacked(v), *new, wp, wo, layer=jnp.int32(2))
        ref = pa.paged_kv_write_xla(stacked(k), stacked(v), *new, wp, wo,
                                    layer=2)
        # page 0 takes every pad: its winner is open
        compare("paged_kv_write {}/P{} stacked".format(
            "int8" if quant else "bf16", page),
            jnp.stack(out)[:, :, :, 1:], jnp.stack(ref)[:, :, :, 1:])
        check(results[-1]["max_abs_diff"] == 0.0,
              "paged_kv_write is a copy: it must equal the scatter exactly")

    # -- w4a16: the 8B projections at decode / verify / cap row counts
    for name, kdim, ndim in int4_shapes:
        w = jax.random.normal(jax.random.PRNGKey(5), (kdim, ndim),
                              jnp.float32) * kdim ** -0.5
        packed, scale = quantize_int4(w)
        for m in int4_rows:
            x = jax.random.normal(jax.random.PRNGKey(6 + m), (m, kdim),
                                  jnp.float32).astype(dtype)
            check(fm.int4_kernel_unsupported_reason(
                x, packed, scale, interpret=interpret, platform=platform
            ) is None, "int4 {} M={} is not routed to the kernel".format(name, m))
            out = jax.jit(functools.partial(
                fm.fused_int4_matmul, interpret=interpret))(x, packed, scale)
            ref = reference(
                fm.int4_matmul_xla, f32(x), packed, scale, jnp.float32)
            compare("fused_int4_matmul {} M{} K{} N{}".format(
                name, m, kdim, ndim), out, ref)

    print(json.dumps({
        "mode": "interpret" if interpret else "mosaic",
        "platform": platform, "variants": results,
        "ok": all(r["ok"] for r in results),
    }))


# -------------------------------------------------------------------- parent

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", choices=sorted(SIZES), default="full",
                    help="full = the published widths (default); tiny = "
                         "llama-tiny shapes for the CPU sandbox")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="output directory (state roots, logs, result.json)")
    ap.add_argument("--child", choices=("probe", "kernels"), help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child == "probe":
        return probe_child()
    if args.child == "kernels":
        return kernel_child(args.size)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    cfg = SIZES[args.size]
    me = [sys.executable, str(Path(__file__).resolve()), "--size", args.size]
    env = child_env(out_dir)

    try:
        probe = json.loads(run_child(
            me + ["--child", "probe"], env, 300, out_dir / "probe.log"
        ).strip().splitlines()[-1])
    except SmokeFailure as ex:
        log("FAILED before any phase: {}".format(ex))
        return 2
    device_ok = probe["device"]["platform"] == "tpu"
    log("device {} | {}".format(probe["device"], probe["versions"]))
    if not device_ok and args.size == "full":
        # no accelerator: fail before any phase, print no result line
        log("no TPU (platform {!r}): nothing was run. --size tiny walks the "
            "phases on CPU to prove the control flow".format(
                probe["device"]["platform"]))
        return 2

    summary = {
        "ok": False, "device_ok": device_ok,
        "device": {"platform": probe["device"]["platform"],
                   "kind": probe["device"]["device_kind"],
                   "count": probe["device"]["device_count"]},
        "versions": probe["versions"], "size": args.size, "phases": {},
    }
    phases = [
        ("A", lambda: phase_a(cfg, out_dir, device_ok)),
        ("B", lambda: phase_b(cfg, out_dir, device_ok)),
        ("C", lambda: json.loads(run_child(
            me + ["--child", "kernels"], env, 900, out_dir / "phase_c.log"
        ).strip().splitlines()[-1])),
    ]
    failed = []
    for name, run in phases:
        t0 = time.monotonic()
        try:
            detail = run()
            check(detail.get("ok", True), "phase {} reported a failed variant".format(name))
            summary["phases"][name] = dict(detail, passed=True)
            log("phase {} passed in {:.0f} s".format(name, time.monotonic() - t0))
        except SmokeFailure as ex:
            summary["phases"][name] = {"passed": False, "error": str(ex)[:4000]}
            failed.append(name)
            log("phase {} FAILED: {}".format(name, ex))
    if "A" in summary["phases"] and summary["phases"]["A"].get("passed"):
        a = summary["phases"]["A"]
        summary["stats_queue"] = a["stats_queue"]
        summary["peak_bytes_in_use"] = a["peak_bytes_in_use"]
        # the engine's own report of its devices supersedes the probe's
        summary["device"] = a["device"]
    summary["ok"] = device_ok and not failed
    (out_dir / "result.json").write_text(json.dumps(summary, indent=1))
    print(json.dumps(summary))
    # the verdict line: exactly these keys, last on stdout
    device = summary["device"]
    print(json.dumps({"ok": summary["ok"], "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}}), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
