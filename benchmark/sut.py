"""The system under test, as the benchmark sees it: the one file that touches
the program. Everything here goes through what a deployment uses — the
control-plane state (``ModelRequestProcessor`` + ``ModelEndpoint``, as the CLI
writes them), ``serving.main.build_app`` on a real socket, the OpenAI route,
and the engine's ``health()`` / ``lifecycle_stats()`` — copied in spirit from
``chip_smoke.py``'s ``Server``. The service runs in this process because only
the process that holds the chip can trace it.
"""

from __future__ import annotations

import json
import os
import socket
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

# HF config.json key -> the program's model key (models/llama.py)
_HF_TO_MODEL = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "ffn_dim", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "num_local_experts": "n_experts",
    "num_experts_per_tok": "moe_top_k", "hidden_act": "hidden_act",
    "tie_word_embeddings": "tie_embeddings",
}
# Only what sizing forces may be pinned (README.md, "Pinned knobs").
ENGINE_KEYS = ("cache", "scheduler", "weight_quant", "prefix_cache",
               "max_batch", "max_seq_len", "num_pages", "warmup")


def load_config(path) -> dict:
    with open(path) as f:
        cfg = json.load(f)
    extra = set(cfg.get("engine", {})) - set(ENGINE_KEYS)
    if extra:
        raise ValueError(
            "{}: engine knobs {} are not among those sizing forces ({}); "
            "leave them to the program's defaults".format(
                path, sorted(extra), ", ".join(ENGINE_KEYS))
        )
    return cfg


def model_block(cfg: dict) -> dict:
    """The program's model config from the published keys of the file."""
    model = {dst: cfg[src] for src, dst in _HF_TO_MODEL.items() if src in cfg}
    if cfg.get("sliding_window"):
        model["sliding_window"] = cfg["sliding_window"]
    model.update(cfg.get("build", {}))
    return model


def aux_config(cfg: dict, seed: int) -> dict:
    engine = {k: cfg["engine"][k] for k in ENGINE_KEYS if k in cfg["engine"]}
    engine.update({
        # an unknown preset name resolves to no preset: every size comes
        # from the configuration file
        "preset": cfg["name"], "arch": cfg.get("arch", "llama"),
        "config": model_block(cfg), "seed": int(seed) % (2 ** 31),
    })
    return {"engine": engine}


def place_caches(checkout: Path) -> None:
    """Compile cache at a fixed path inside the checkout (the path is part of
    the cache's key), every program cached whatever it cost to compile; the
    program's artifact cache and state under the run's own directory."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(checkout / ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ["TPUSERVE_COMPILE_SENTRY"] = "1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


class Service:
    """One LLM endpoint behind the real router, on 127.0.0.1:<free port>."""

    def __init__(self, cfg: dict, seed: int, out_dir: Path):
        self.cfg = cfg
        self.name = cfg["name"]
        self.seed = seed
        self.out_dir = out_dir
        self.processor = None
        self.runner = None
        self.base = None

    async def start(self) -> None:
        from aiohttp import web

        from clearml_serving_tpu.engines import load_engine_modules
        from clearml_serving_tpu.serving.endpoints import ModelEndpoint
        from clearml_serving_tpu.serving.main import build_app
        from clearml_serving_tpu.serving.model_request_processor import (
            ModelRequestProcessor,
        )

        os.environ["TPUSERVE_CACHE_DIR"] = str(self.out_dir / "artifact_cache")
        load_engine_modules()
        state_root = self.out_dir / "state"
        state_root.mkdir(parents=True, exist_ok=True)
        mrp = ModelRequestProcessor(
            state_root=str(state_root), force_create=True,
            name="benchmark-" + self.name,
        )
        mrp.add_endpoint(ModelEndpoint(
            engine_type="llm", serving_url=self.name,
            auxiliary_cfg=aux_config(self.cfg, self.seed),
        ))
        mrp.serialize()
        # as serving.main's setup_processor: the initial sync builds the
        # engine (weights from the seed, on the device) before the port opens
        mrp.launch(poll_frequency_sec=3600.0)
        mrp._get_processor(self.name)      # a failed load raises here
        self.processor = mrp
        app = build_app(mrp)
        self.runner = web.AppRunner(app, access_log=None)
        await self.runner.setup()         # on_startup: starts engine.warmup
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        await web.TCPSite(self.runner, "127.0.0.1", port).start()
        self.base = "http://127.0.0.1:{}".format(port)

    @property
    def endpoint(self):
        lookup = getattr(self.processor, "_engine_processor_lookup", {})
        if self.name not in lookup:
            raise RuntimeError("endpoint {!r} did not load".format(self.name))
        return lookup[self.name]

    @property
    def engine(self):
        return self.endpoint.engine

    def warmup_state(self):
        return self.endpoint.warmup_state

    async def stop(self) -> None:
        if self.runner is not None:
            await self.runner.cleanup()
        try:
            self.engine.stop()
        finally:
            self.processor.stop()

    # ------------------------------------------------------------- counters

    def snapshot(self) -> dict:
        """Every program counter the per-layer readers use, at one instant.
        Histograms are cumulative, so a window is a difference of two."""
        engine = self.engine
        stats = engine.lifecycle_stats()
        prefix = getattr(engine, "_prefix", None)
        stats["prefix"] = prefix.stats() if prefix is not None else None
        return stats

    def light_sample(self) -> dict:
        """Cheap enough for twice a second inside the window."""
        h = self.engine.health()
        return {
            "active_slots": h.get("active_slots"),
            "queue_depth": h.get("queue_depth"),
            "pool": ((h.get("brownout") or {}).get("signals") or {}).get("pool"),
        }


def device_block() -> dict:
    """The device as jax reports it, and the peak on the fullest chip."""
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs:
        try:
            peak = max(peak, int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)))
        except Exception:  # the CPU backend reports no memory stats
            pass
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}

