"""OpenAI-compatible LLM engine endpoint ("llm" engine type).

Route-surface parity with the reference's vLLM engine handlers
(clearml_serving/serving/preprocess_service.py:836-1095): chat completions
(+SSE streaming), completions, models, tokenize/detokenize — dispatched through
the router's ``/serve/openai/{type}`` path exactly like the reference
(serve_type "v1/chat/completions" → ``v1_chat_completions``). Capability-gated
routes (embeddings / pooling / classify / score / audio) return a clean
backend error when the loaded model does not support them, mirroring the
reference's task/runner gating (preprocess_service.py:711-808).

The compute path is the continuous-batching engine in engine.py on TPU via
JAX — no CUDA, no vLLM.
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
import uuid
from typing import Any, AsyncIterator, Dict, List, Optional, Tuple

from ..engines.base import BaseEngineRequest, EndpointModelError, register_engine
from ..serving.responses import StreamingOutput
from .tokenizer import load_tokenizer

# engine.py / sampling.py import jax at module level; defer so registering the
# "llm" engine (CLI import path) stays jax-free.
if False:  # typing only
    from .engine import GenRequest, LLMEngineCore  # noqa: F401


def _now() -> int:
    return int(time.time())


def _gen_id(prefix: str) -> str:
    return "{}-{}".format(prefix, uuid.uuid4().hex[:24])


@register_engine("llm", modules=["jax", "flax"])
class LLMEngineRequest(BaseEngineRequest):
    """One continuous-batching engine per endpoint per process."""

    is_preprocess_async = True
    is_process_async = True
    is_postprocess_async = True

    def __init__(self, *args, **kwargs):
        self.engine = None
        self.encoder = None
        self.audio = None
        self.tokenizer = None
        self._model_name = "model"
        # aux engine.chat block (reference vLLM chat_settings:
        # examples/vllm/preprocess.py:14-33): response_role etc.
        self._chat_cfg: Dict[str, Any] = {}
        # endpoint-level SLO class default (docs/slo_scheduling.md): aux
        # engine.default_priority; a request body `priority` overrides it
        self._default_priority = "interactive"
        # startup shape warmup (aux engine.warmup; llm/warmup.py)
        self._warmup_needed = False
        self._warmup_full = False
        self._warmup_task = None
        super().__init__(*args, **kwargs)

    def start_warmup(self) -> None:
        """Start the shared warmup task (llm/warmup.py) on the running
        event loop. The server calls this at startup for every endpoint
        loaded with aux engine.warmup, so the sweep compiles before the
        first request and /ready holds 503 until it finished; a lazily
        loaded endpoint starts it from its first request instead."""
        if (
            self._warmup_needed
            and self.engine is not None
            and self._warmup_task is None
        ):
            self._warmup_task = asyncio.create_task(
                self.engine.warmup(full=self._warmup_full)
            )

    @property
    def warmup_state(self) -> Optional[str]:
        """None without aux engine.warmup; else pending / running / done /
        "failed: <error>" — /ready reports it and is 503 until "done"."""
        task = self._warmup_task
        if task is None:
            return "pending" if self._warmup_needed else None
        if not task.done():
            return "running"
        if task.cancelled():
            return "failed: cancelled"
        if task.exception() is not None:
            return "failed: {}".format(task.exception())
        return "done"

    async def _ensure_warm(self) -> None:
        """Arrivals share one warmup task and wait for it; afterwards this
        is one attribute read. A failed warmup FAILS THE ENDPOINT: a
        program that did not compile in the sweep will not compile for a
        user either, so every request gets the load error (and /ready
        stays 503) instead of the first user meeting it as a 5xx."""
        if not self._warmup_needed or self.engine is None:
            return
        self.start_warmup()
        try:
            await asyncio.shield(self._warmup_task)
        except asyncio.CancelledError:
            raise
        except Exception as ex:
            raise EndpointModelError(
                "llm endpoint {!r} failed its engine.warmup sweep: {}: {}"
                .format(self.endpoint.serving_url, type(ex).__name__, ex)
            ) from ex
        self._warmup_needed = False

    # -- loading --------------------------------------------------------------

    def _native_load(self) -> Any:
        import jax

        from ..engines.jax_engine import enable_persistent_compilation_cache, load_bundle
        from .. import models
        from .engine import LLMEngineCore, PRIORITY_CLASSES

        enable_persistent_compilation_cache()
        aux = self.endpoint.auxiliary_cfg if isinstance(self.endpoint.auxiliary_cfg, dict) else {}
        engine_cfg = dict(aux.get("engine") or {})
        self._chat_cfg = dict(engine_cfg.get("chat") or {})

        # weight quantization (docs/w4a16.md): aux engine.weight_quant
        # ("quantize" stays as the legacy alias) selects int8 per-channel or
        # int4 group-quantized weights; int4 decode matmuls route through
        # the Pallas fused dequant-matmul (ops/fused_matmul.py). Validated
        # at ENDPOINT LOAD like default_priority: a typo'd value must fail
        # fast naming the knob, not surface as a per-request error after
        # the endpoint looked healthy.
        weight_quant = engine_cfg.get(
            "weight_quant", engine_cfg.get("quantize")
        )
        legacy = engine_cfg.get("quantize")
        if (
            engine_cfg.get("weight_quant") and legacy
            and engine_cfg["weight_quant"] != legacy
        ):
            # same fail-fast contract as the engine kwargs: a config that
            # spells the knob both ways with different values must not
            # silently pick one
            raise ValueError(
                "aux engine.weight_quant={!r} conflicts with the legacy "
                "engine.quantize={!r} alias; set only one".format(
                    engine_cfg["weight_quant"], legacy
                )
            )
        if weight_quant in ("", None):
            weight_quant = None
        elif str(weight_quant) not in ("int8", "int4"):
            raise ValueError(
                "aux engine.weight_quant must be 'int8' or 'int4': got "
                "{!r}".format(weight_quant)
            )

        # multi-LoRA (reference vLLM knob `lora_modules`,
        # preprocess_service.py:740-767): aux engine.lora = {"modules":
        # {name: adapter_dir}, "rank": r?, "targets": [...]?, "max_loras": n?}
        # — adapters load host-side, install into stacked factors, and route
        # by the OpenAI request's `model` field (models/lora.py).
        lora_overrides, lora_adapters = self._load_lora_cfg(engine_cfg)
        cfg_overrides = dict(lora_overrides)
        if engine_cfg.get("kv_quant"):
            # int8 KV cache: a serving-time build knob like lora, so it can
            # be set per endpoint without touching the stored bundle config.
            # Honored by BOTH cache backends: the dense cache stores
            # int8+scales in its buffers, and the paged backend allocates
            # int8 page pools with per-page scale rows and dequantizes
            # inside the Pallas decode kernel (docs/paged_kv_quant.md) —
            # so `engine.cache: paged` endpoints get the halved KV HBM the
            # b>=32 roofline configs need.
            cfg_overrides["kv_quant"] = str(engine_cfg["kv_quant"])

        if self._model_local_path:
            bundle, params = load_bundle(
                self._model_local_path, config_overrides=cfg_overrides or None
            )
        elif engine_cfg.get("preset"):
            # weightless demo/bench mode: architecture preset, random params
            bundle = models.build_model(
                engine_cfg.get("arch", "llama"),
                {
                    "preset": engine_cfg["preset"],
                    **(engine_cfg.get("config") or {}),
                    **cfg_overrides,
                },
            )
            key = jax.random.PRNGKey(int(engine_cfg.get("seed", 0)))
            # with engine.weight_quant the packed tree is generated
            # directly (models/llama init): llama3-8b is 16 GB in bf16 —
            # a full-precision tree for the engine to quantize afterwards
            # does not fit the 16 GB chip the int8 one serves from
            params = (
                bundle.init(key, weight_quant=str(weight_quant))
                if weight_quant
                else bundle.init(key)
            )
        else:
            raise EndpointModelError(
                "llm endpoint {!r} needs a model bundle or aux_config engine.preset".format(
                    self.endpoint.serving_url
                )
            )

        mesh = None
        if aux.get("mesh"):
            from ..parallel import mesh_from_aux_cfg

            try:
                mesh = mesh_from_aux_cfg(aux)
            except ValueError as ex:
                # an endpoint that asks for a mesh this host cannot build
                # must not silently serve from one device
                raise ValueError(
                    "aux mesh={!r} cannot be built on this host's {} "
                    "device(s): {}".format(
                        aux.get("mesh"), len(jax.devices()), ex
                    )
                ) from ex

        self.tokenizer = load_tokenizer(
            self._model_local_path, int(bundle.config.get("vocab_size", 0))
        )

        # task gating like the reference's model-task handler instantiation
        # (preprocess_service.py:711-808): encoder bundles (no .decode) serve
        # the embeddings/pooling/classify/score/rerank routes; decoder bundles
        # serve chat/completions.
        task = engine_cfg.get("task")
        if task is None:
            if hasattr(bundle, "encode") and hasattr(bundle, "init_cache") and not hasattr(bundle, "prefill"):
                task = "transcribe"  # speech encoder-decoder (whisper family)
            elif hasattr(bundle, "decode"):
                task = "generate"
            else:
                task = "embed"
        encoder_tasks = {
            "embed", "embedding", "pooling", "classify", "classification",
            "score", "rerank",
        }
        audio_tasks = {"transcribe", "translate", "audio"}
        if task not in encoder_tasks and task not in audio_tasks and task != "generate":
            raise EndpointModelError(
                "unknown engine task {!r} for endpoint {!r} (expected "
                "'generate' or one of {})".format(
                    task,
                    self.endpoint.serving_url,
                    sorted(encoder_tasks | audio_tasks),
                )
            )
        if task in audio_tasks:
            from .audio import AudioCore

            self.audio = AudioCore(
                bundle,
                params,
                decode_steps=int(engine_cfg.get("decode_steps", 16)),
                max_new_tokens=engine_cfg.get("max_tokens"),
            )
            self._model_name = self.endpoint.serving_url
            return self.audio
        if task in encoder_tasks:
            from .encoder import EncoderCore

            hf = getattr(self.tokenizer, "_tok", None)
            self.encoder = EncoderCore(
                bundle,
                params,
                pooling=engine_cfg.get("pooling", "mean"),
                normalize=bool(engine_cfg.get("normalize", True)),
                seq_buckets=engine_cfg.get("seq_buckets"),
                batch_buckets=engine_cfg.get("batch_buckets"),
                sep_token_id=getattr(hf, "sep_token_id", None),
                cls_token_id=getattr(hf, "cls_token_id", None),
            )
            self._model_name = self.endpoint.serving_url
            return self.encoder
        engine_kwargs = dict(
            max_batch=int(engine_cfg.get("max_batch", 8)),
            max_seq_len=int(engine_cfg.get("max_seq_len", bundle.config.get("max_seq_len", 2048))),
            prefill_buckets=engine_cfg.get("prefill_buckets"),
            mesh=mesh,
            eos_token_id=self.tokenizer.eos_token_id,
            decode_steps=int(engine_cfg.get("decode_steps", 4)),
            weight_quant=weight_quant,
            cache_mode=engine_cfg.get("cache", "dense"),
            # int8 paged pools default to 32-token pages: the int8 Pallas
            # tile is (32, 128), so 16-token pages would silently route
            # every TPU decode to the XLA-gather fallback and forfeit the
            # halved-DMA win (docs/paged_kv_quant.md); an explicit
            # engine.page_size still wins
            page_size=int(
                engine_cfg.get("page_size")
                or (32 if (
                    engine_cfg.get("kv_quant")
                    and engine_cfg.get("cache", "dense") == "paged"
                ) else 16)
            ),
            num_pages=int(engine_cfg["num_pages"]) if engine_cfg.get("num_pages") else None,
            long_prefill_threshold=engine_cfg.get("long_prefill_threshold"),
            long_bucket_step=engine_cfg.get("long_bucket_step"),
            chunked_prefill_size=engine_cfg.get("chunked_prefill"),
            prefill_segments_per_decode=engine_cfg.get(
                "prefill_segments_per_decode", 2
            ),
            prefill_stall_timeout=engine_cfg.get("prefill_stall_timeout"),
            speculation=engine_cfg.get("speculation"),
            spec_k=int(engine_cfg.get("spec_k", 4)),
            spec_ngram=int(engine_cfg.get("spec_ngram", 2)),
            spec_sampling=bool(engine_cfg.get("spec_sampling", True)),
            # draft-tree verify rows (docs/spec_decode_trees.md): aux
            # engine.spec_tree branches each verify row's k-draft budget
            # across up to engine.spec_branch root continuations (needs
            # speculation + a paged cache — the constructor validates at
            # ENDPOINT LOAD; tree rows engage under the ragged scheduler)
            spec_tree=bool(engine_cfg.get("spec_tree", False)),
            spec_branch=int(engine_cfg.get("spec_branch", 2)),
            pipeline_chunk=int(engine_cfg.get("pipeline_chunk", 512)),
            # decode-pipeline depth (docs/pipelined_decode.md): None defers
            # to TPUSERVE_PIPELINE_DEPTH (default 2); 1 = serial decode
            pipeline_depth=(
                int(engine_cfg["pipeline_depth"])
                if engine_cfg.get("pipeline_depth")
                else None
            ),
            # ragged token-budget scheduler (docs/ragged_attention.md):
            # engine.cache decides it (paged and state run it, paced by
            # engine.step_token_budget; dense runs the two-dispatch loop).
            # aux engine.scheduler is no choice: a value that contradicts
            # the cache is refused by the constructor at ENDPOINT LOAD
            scheduler=engine_cfg.get("scheduler"),
            step_token_budget=(
                int(engine_cfg["step_token_budget"])
                if engine_cfg.get("step_token_budget")
                else None
            ),
            # multi-step ragged decode rows (docs/ragged_attention.md):
            # max chained positions per decode row per mixed launch;
            # unset inherits decode_steps, 1 restores q=1 rows
            ragged_decode_steps=(
                int(engine_cfg["ragged_decode_steps"])
                if engine_cfg.get("ragged_decode_steps")
                else None
            ),
            lora_adapters=lora_adapters,
            prefix_cache=engine_cfg.get("prefix_cache"),
            prefix_block=int(engine_cfg.get("prefix_block", 64)),
            logprobs_k=int(engine_cfg.get("logprobs_k", 20)),
            prefix_cache_bytes=(
                int(float(engine_cfg["prefix_cache_mb"]) * (1 << 20))
                if engine_cfg.get("prefix_cache_mb")
                else None
            ),
            prefix_cache_pages=(
                int(engine_cfg["prefix_cache_pages"])
                if engine_cfg.get("prefix_cache_pages")
                else None
            ),
            # host-RAM KV tier (docs/kv_tiering.md): aux
            # engine.prefix_cache_host_pages preallocates that many host
            # pages behind the prefix cache (paged backend); eviction then
            # demotes instead of dropping. 0/unset disables.
            prefix_cache_host_pages=(
                int(engine_cfg["prefix_cache_host_pages"])
                if engine_cfg.get("prefix_cache_host_pages")
                else None
            ),
            # "auto" sizes the tier from /proc/meminfo at endpoint load
            # (clamped; HostTierAutoSizeError names unsupported platforms)
            prefix_cache_host_bytes=(
                "auto"
                if str(engine_cfg.get("prefix_cache_host_mb", "")
                       ).strip().lower() == "auto"
                else int(float(engine_cfg["prefix_cache_host_mb"]) * (1 << 20))
                if engine_cfg.get("prefix_cache_host_mb")
                else None
            ),
            tokenizer=self.tokenizer,  # guided decoding needs token bytes
            # request-lifecycle hardening (docs/robustness.md): production
            # defaults ON at the serving front — bounded admission and a
            # stall watchdog; aux engine.* knobs override, 0/false disables
            max_pending=self._lifecycle_knob(
                engine_cfg, "max_pending",
                max(16, 4 * int(engine_cfg.get("max_batch", 8))),
            ),
            queue_timeout=self._lifecycle_knob(engine_cfg, "queue_timeout", None),
            ttft_timeout=self._lifecycle_knob(engine_cfg, "ttft_timeout", None),
            total_timeout=self._lifecycle_knob(engine_cfg, "timeout", None),
            watchdog_interval=self._lifecycle_knob(
                engine_cfg, "watchdog_interval", 30.0
            ),
            # SLO-aware scheduling (docs/slo_scheduling.md): preemptible
            # batch lane + brownout controller; aux engine.* knobs override
            preempt_batch=bool(engine_cfg.get("preemption", True)),
            preempt_budget=int(engine_cfg.get("preempt_budget", 2)),
            starvation_floor=int(engine_cfg.get("starvation_floor", 8)),
            brownout=(
                bool(engine_cfg["brownout"])
                if "brownout" in engine_cfg
                else None
            ),
            brownout_batch_cap=int(engine_cfg.get("brownout_batch_cap", 32)),
            brownout_dwell=float(engine_cfg.get("brownout_dwell", 2.0)),
        )
        # startup shape warmup (llm/warmup.py, docs/static_analysis.md
        # TPU6xx): parsed BEFORE engine construction because the replica
        # group's ring-entry gate needs it. "startup" runs the cheap
        # per-bucket pass before the first request is admitted, "full"
        # runs the whole zero-recompile-certified sweep. Runs as ONE
        # shared task the first arrivals await.
        warmup_mode = str(engine_cfg.get("warmup", "off")).lower()
        if warmup_mode in ("1", "true", "on"):
            warmup_mode = "startup"
        if warmup_mode in ("0", "false"):
            warmup_mode = "off"
        if warmup_mode not in ("off", "startup", "full"):
            # fail at ENDPOINT LOAD, same contract as default_priority
            raise ValueError(
                "aux engine.warmup must be off/startup/full: got {!r}"
                .format(engine_cfg.get("warmup"))
            )
        # replica fleet (docs/replication.md): aux engine.replicas > 1
        # builds N identically configured engine replicas — ONE shared
        # params tree (read-only for compute), private KV pools — behind
        # the prefix-affine router (serving/replica_router.py). Validated
        # at ENDPOINT LOAD like default_priority: a bad value must fail
        # fast naming the knob, not 422 per request.
        raw_replicas = engine_cfg.get("replicas")
        if raw_replicas is None:
            n_replicas = 1
        else:
            try:
                n_replicas = int(raw_replicas)
                # a non-integral float (2.5) must not silently truncate
                if float(raw_replicas) != n_replicas:
                    raise ValueError(raw_replicas)
            except (TypeError, ValueError):
                raise ValueError(
                    "aux engine.replicas must be an integer >= 1: got {!r}"
                    .format(raw_replicas)
                )
        if not 1 <= n_replicas <= 16:
            raise ValueError(
                "aux engine.replicas must be in 1..16: got {}".format(
                    n_replicas
                )
            )
        # replica roles (docs/disaggregation.md): aux engine.replica_roles
        # dedicates replicas to prefill or decode and wires the KV
        # transport between them. Accepts a list or a comma string;
        # validated at ENDPOINT LOAD naming the knob.
        raw_roles = engine_cfg.get("replica_roles")
        replica_roles = None
        if raw_roles is not None:
            if isinstance(raw_roles, str):
                replica_roles = [
                    r.strip().lower() for r in raw_roles.split(",") if r.strip()
                ]
            elif isinstance(raw_roles, (list, tuple)):
                replica_roles = [str(r).strip().lower() for r in raw_roles]
            else:
                raise ValueError(
                    "aux engine.replica_roles must be a list (or comma "
                    "string) of prefill/decode/hybrid: got {!r}"
                    .format(raw_roles)
                )
            if n_replicas <= 1:
                raise ValueError(
                    "aux engine.replica_roles needs engine.replicas >= 2 "
                    "(got {} replica)".format(n_replicas)
                )
        # replica backend (docs/replication.md): "inprocess" = N engines
        # on this heap (the default), "process" = supervised worker
        # subprocesses (serving/process_replica.py). Validated at ENDPOINT
        # LOAD like every other fleet knob.
        replica_backend = str(
            engine_cfg.get("replica_backend", "inprocess")
        ).strip().lower()
        if replica_backend not in ("inprocess", "process"):
            raise ValueError(
                "aux engine.replica_backend must be inprocess/process: got "
                "{!r}".format(engine_cfg.get("replica_backend"))
            )
        # KV transport backend for disaggregated fleets
        # (docs/disaggregation.md): in-heap shared slabs or the socket
        # wire (llm/kv_wire.py). The process backend always uses sockets
        # (its workers have no shared heap).
        kv_transport_backend = str(
            engine_cfg.get("kv_transport_backend", "shared")
        ).strip().lower()
        if kv_transport_backend not in ("shared", "socket"):
            raise ValueError(
                "aux engine.kv_transport_backend must be shared/socket: "
                "got {!r}".format(engine_cfg.get("kv_transport_backend"))
            )
        if replica_backend == "process":
            if n_replicas <= 1:
                raise ValueError(
                    "aux engine.replica_backend=process needs "
                    "engine.replicas >= 2 (got {})".format(n_replicas)
                )
            if self._model_local_path:
                raise EndpointModelError(
                    "engine.replica_backend=process needs an engine.preset "
                    "model: worker processes rebuild the model from the "
                    "preset spec, and a local-path bundle cannot be "
                    "re-materialized in them yet (docs/replication.md)"
                )
            if lora_adapters:
                raise ValueError(
                    "engine.replica_backend=process does not support LoRA "
                    "adapters yet: the adapter registry is not shipped to "
                    "worker processes (docs/replication.md)"
                )
            from ..serving.process_replica import build_process_fleet

            # JSON-safe engine kwargs only: the worker rebuilds tokenizer-
            # dependent pieces (eos id rides along as plain data) and owns
            # its own mesh; anything unserializable stays parent-side
            worker_engine_cfg = {}
            for key, value in engine_kwargs.items():
                if key in ("tokenizer", "mesh", "lora_adapters"):
                    continue
                try:
                    json.dumps(value)
                except (TypeError, ValueError):
                    continue
                worker_engine_cfg[key] = value
            self.engine = build_process_fleet(
                {
                    "arch": engine_cfg.get("arch", "llama"),
                    "config": {
                        "preset": engine_cfg["preset"],
                        **(engine_cfg.get("config") or {}),
                        **cfg_overrides,
                    },
                    "seed": int(engine_cfg.get("seed", 0)),
                },
                worker_engine_cfg,
                n_replicas,
                roles=replica_roles,
                warmup_mode=warmup_mode,
                affinity_blocks=int(
                    engine_cfg.get("router_affinity_blocks", 4)
                ),
                spill_queue_depth=(
                    int(engine_cfg["router_spill_queue_depth"])
                    if engine_cfg.get("router_spill_queue_depth") is not None
                    else None
                ),
                spill_brownout_stage=int(
                    engine_cfg.get("router_spill_stage", 2)
                ),
                fleet_shed_stage=int(
                    engine_cfg.get("router_fleet_shed_stage", 3)
                ),
                kv_transport_pages=(
                    int(engine_cfg["kv_transport_pages"])
                    if engine_cfg.get("kv_transport_pages")
                    else None
                ),
            )
        elif n_replicas > 1:
            from .replica import ReplicaGroup

            engines = [
                # "rN" everywhere: the engine's replica id must match the
                # ring member names, registry keys, and /ready blocks so
                # one identity joins every surface (PromQL on(replica))
                LLMEngineCore(
                    bundle, params, replica="r{}".format(i), **engine_kwargs
                )
                for i in range(n_replicas)
            ]
            self.engine = ReplicaGroup(
                engines,
                warmup_mode=warmup_mode,
                affinity_blocks=int(
                    engine_cfg.get("router_affinity_blocks", 4)
                ),
                # `is not None`, not truthiness: an explicit 0 is the
                # documented "never spill on queue depth" spelling and
                # must not silently fall back to the max_pending default
                spill_queue_depth=(
                    int(engine_cfg["router_spill_queue_depth"])
                    if engine_cfg.get("router_spill_queue_depth") is not None
                    else None
                ),
                spill_brownout_stage=int(
                    engine_cfg.get("router_spill_stage", 2)
                ),
                fleet_shed_stage=int(
                    engine_cfg.get("router_fleet_shed_stage", 3)
                ),
                roles=replica_roles,
                kv_transport_pages=(
                    int(engine_cfg["kv_transport_pages"])
                    if engine_cfg.get("kv_transport_pages")
                    else None
                ),
                kv_transport_backend=kv_transport_backend,
            )
        else:
            self.engine = LLMEngineCore(bundle, params, **engine_kwargs)
        self._default_priority = str(
            engine_cfg.get("default_priority", "interactive")
        )
        if self._default_priority not in PRIORITY_CLASSES:
            # fail at ENDPOINT LOAD: a typo'd default would otherwise 422
            # every request that omits an explicit body priority
            raise ValueError(
                "aux engine.default_priority must be one of {}: got {!r}"
                .format("/".join(PRIORITY_CLASSES), self._default_priority)
            )
        self._warmup_full = warmup_mode == "full"
        self._warmup_needed = warmup_mode != "off"
        self._warmup_task = None
        self._model_name = self.endpoint.serving_url
        self._register_metrics(n_replicas > 1)
        return self.engine

    def _register_metrics(self, fleet: bool) -> None:
        """Prometheus wiring for the engine (or engine group). Every
        provider holds its engine WEAKLY: the process-lifetime registry
        must not pin an evicted endpoint's engine (params + KV = GBs of
        device memory) after the processor cache drops it.

        Fleet mode (docs/replication.md): each replica registers its OWN
        lifecycle/prefix-cache entry — the engine's payloads carry the
        replica id, so the lifecycle families grow a ``replica`` label —
        and the router registers the ring/route counters."""
        import weakref

        model = self._model_name

        def _lifecycle_provider(engine_ref, inject_model=None):
            def provider():
                engine = engine_ref()
                if engine is None:
                    return None
                s = engine.lifecycle_stats()
                if inject_model is not None:
                    s["model"] = inject_model
                return s
            return provider

        def _register_prefix(engine, key, replica=None):
            prefix = getattr(engine, "_prefix", None)
            if prefix is None or not hasattr(prefix, "stats"):
                # process-backend proxies expose a routing-only prefix
                # probe (block size + match lengths over the RPC) with no
                # stats surface — the real cache lives in the worker and
                # reports through the health RPC, not this collector
                return None
            # hit rate / shared pages / CoW visible from day one on the
            # same Prometheus registry the serving process already exports.
            # Fleet entries keep the real model label and carry `replica`
            # (same {model, replica} split as the lifecycle families)
            try:
                from ..statistics.metrics import register_prefix_cache

                pool = (
                    engine.paged_cache.pool
                    if engine.paged_cache is not None
                    else None
                )
                return register_prefix_cache(
                    engine._prefix, pool, key=key,
                    model=model if replica is not None else None,
                    replica=replica,
                )
            except Exception:
                return None  # registry unavailable etc.

        try:
            from ..statistics.metrics import (
                prune_engine_lifecycle,
                prune_prefix_caches,
                prune_replica_router,
                register_engine_lifecycle,
            )

            if not fleet:
                self._prefix_collector = _register_prefix(self.engine, model)
                self._lifecycle_collector = register_engine_lifecycle(
                    _lifecycle_provider(weakref.ref(self.engine)), key=model
                )
                # hot-reload hygiene: a previous FLEET incarnation of this
                # endpoint left per-replica entries (model@rN) that would
                # otherwise pin dead engines' caches and export frozen
                # series forever
                prune_prefix_caches(model, {model})
                prune_engine_lifecycle(model, {model})
                prune_replica_router(model, set())
                return
            keep = {
                "{}@{}".format(model, r.name) for r in self.engine.replicas
            }
            for replica in self.engine.replicas:
                key = "{}@{}".format(model, replica.name)
                self._prefix_collector = _register_prefix(
                    replica.engine, key, replica=replica.name
                )
                self._lifecycle_collector = register_engine_lifecycle(
                    _lifecycle_provider(
                        weakref.ref(replica.engine), inject_model=model
                    ),
                    key=key,
                )
            # prune a previous incarnation's bare-model entry and any
            # replicas beyond the current count (scale-down reload)
            prune_prefix_caches(model, keep)
            prune_engine_lifecycle(model, keep)
            from ..statistics.metrics import register_replica_router

            group_ref = weakref.ref(self.engine)

            def _router_provider():
                group = group_ref()
                if group is None:
                    return None
                s = group.router.stats()
                s["model"] = model
                return s

            self._router_collector = register_replica_router(
                _router_provider, key=model
            )
        except Exception:
            self._lifecycle_collector = None

    @staticmethod
    def _lifecycle_knob(engine_cfg: Dict[str, Any], key: str, default):
        """Aux-config override for a lifecycle knob: absent -> default,
        0/false/None -> disabled (the engine treats falsy as off)."""
        if key not in engine_cfg:
            return default
        value = engine_cfg[key]
        return float(value) if value else None

    def _load_lora_cfg(self, engine_cfg: Dict[str, Any]):
        """(config_overrides, adapters) from the aux engine.lora block."""
        from pathlib import Path

        lora_cfg = dict(engine_cfg.get("lora") or {})
        modules = dict(lora_cfg.get("modules") or {})
        if not modules:
            return {}, None
        from ..models import lora as lora_lib
        from ..models import llama as llama_mod

        # layer count comes from the model config (stored bundle meta or the
        # preset); adapters only apply to the llama-family decoder arch
        if self._model_local_path:
            from ..utils.files import read_json

            meta = read_json(Path(self._model_local_path) / "model_config.json")
            if not meta or meta.get("arch") != "llama":
                raise EndpointModelError(
                    "lora modules need a native llama-family bundle "
                    "(got {!r})".format((meta or {}).get("arch"))
                )
            model_cfg = llama_mod.resolve_config(dict(meta.get("config") or {}))
        else:
            model_cfg = llama_mod.resolve_config(
                {
                    "preset": engine_cfg.get("preset", ""),
                    **(engine_cfg.get("config") or {}),
                }
            )
        n_layers = int(model_cfg["n_layers"])
        adapters: Dict[str, Any] = {}
        for name, p in modules.items():
            path = Path(str(p))
            if not path.is_absolute() and self._model_local_path:
                cand = Path(self._model_local_path) / str(p)
                if cand.exists():
                    path = cand
            adapters[name] = lora_lib.load_adapter(path, n_layers)
        rank = int(lora_cfg.get("rank") or 0) or max(
            ab["a"].shape[-1] for tree in adapters.values() for ab in tree.values()
        )
        targets = list(
            lora_cfg.get("targets")
            or sorted({t for tree in adapters.values() for t in tree})
        )
        overrides = {
            "lora_rank": rank,
            "lora_targets": targets,
            "max_loras": max(len(adapters), int(lora_cfg.get("max_loras") or 0)),
        }
        return overrides, adapters

    # -- helpers ----------------------------------------------------------------

    def _adapter_for(self, body: Dict[str, Any]) -> Optional[str]:
        """OpenAI multi-LoRA routing: a `model` field naming a loaded adapter
        selects it; anything else (endpoint name, absent) is the base model."""
        name = body.get("model")
        if (
            self.engine is not None
            and name
            and name in getattr(self.engine, "_adapter_index", {})
        ):
            return name
        return None

    def _gen_request_from_body(self, body: Dict[str, Any], prompt_ids: List[int],
                               chat: bool = True, guided_override=None):
        """``guided_override``: a GuidedSpec that supersedes the body's own
        response_format/guided_* (tool_choice required/forced compiles the
        tool-call JSON into the grammar)."""
        from .engine import GenRequest

        logit_bias = body.get("logit_bias") or None
        if logit_bias is not None:
            logit_bias = {int(k): float(v) for k, v in logit_bias.items()}
        # logprobs: chat uses `logprobs: bool` + `top_logprobs: int`;
        # completions uses `logprobs: int` directly (0 = chosen token only)
        if chat:
            logprobs = (
                int(body.get("top_logprobs", 0) or 0)
                if body.get("logprobs")
                else None
            )
        else:
            raw_lp = body.get("logprobs")
            logprobs = int(raw_lp) if raw_lp is not None and raw_lp is not False else None
        request = GenRequest(
            prompt_ids=prompt_ids,
            max_new_tokens=int(body.get("max_tokens") or body.get("max_completion_tokens") or 128),
            temperature=float(body.get("temperature", 0.0) or 0.0),
            top_k=int(body.get("top_k", 0) or 0),
            top_p=float(body.get("top_p", 1.0) or 1.0),
            presence_penalty=float(body.get("presence_penalty", 0.0) or 0.0),
            frequency_penalty=float(body.get("frequency_penalty", 0.0) or 0.0),
            repetition_penalty=float(body.get("repetition_penalty", 1.0) or 1.0),
            seed=(int(body["seed"]) if body.get("seed") is not None else None),
            logit_bias=logit_bias,
            logprobs=logprobs,
            adapter=self._adapter_for(body),
            min_tokens=int(body.get("min_tokens", 0) or 0),
            guided=guided_override or self._guided_spec(body),
            # per-request lifecycle budgets (seconds); engine defaults apply
            # when absent. `timeout` bounds the WHOLE request (vLLM-style).
            total_timeout=(
                float(body["timeout"]) if body.get("timeout") is not None else None
            ),
            queue_timeout=(
                float(body["queue_timeout"])
                if body.get("queue_timeout") is not None
                else None
            ),
            ttft_timeout=(
                float(body["ttft_timeout"])
                if body.get("ttft_timeout") is not None
                else None
            ),
            # SLO class: body `priority` wins, else the endpoint's aux
            # engine.default_priority (docs/slo_scheduling.md); the engine's
            # validate() rejects unknown values with a 422
            priority=str(
                body.get("priority") or self._default_priority
            ),
        )
        # vLLM `return_tokens_as_token_ids`: logprob token strings become
        # "token_id:<id>" (API-layer formatting, so not a GenRequest field)
        request.tokens_as_ids = bool(body.get("return_tokens_as_token_ids"))
        return request

    @staticmethod
    def _guided_spec(body: Dict[str, Any]):
        """OpenAI ``response_format`` (json_object / json_schema) and
        vLLM-style ``guided_regex`` / ``guided_json`` extras -> GuidedSpec.
        Enforced on device by the engine's grammar tables (llm/guided.py);
        the reference's vLLM engine applies the same surface host-side."""
        import json as _json

        from .guided import GuidedSpec

        if body.get("guided_choice"):
            from .guided import _regex_escape_literal

            choices = body["guided_choice"]
            if not isinstance(choices, (list, tuple)) or not choices:
                raise ValueError("guided_choice must be a non-empty list")
            return GuidedSpec(
                "regex",
                "({})".format(
                    "|".join(_regex_escape_literal(str(c)) for c in choices)
                ),
            )
        if body.get("guided_regex"):
            return GuidedSpec("regex", str(body["guided_regex"]))
        if body.get("guided_json") is not None:
            schema = body["guided_json"]
            if isinstance(schema, str):
                schema = _json.loads(schema)
            # NO sort_keys: property DECLARATION order is part of the
            # grammar (json_schema_to_regex emits members in order);
            # sorting would reorder the forced output's keys
            return GuidedSpec("json_schema", _json.dumps(schema))
        rf = body.get("response_format")
        if not rf:
            return None
        if isinstance(rf, str):  # audio routes use a plain string; tolerate
            return None
        kind = rf.get("type")
        if kind == "json_object":
            return GuidedSpec("json_object")
        if kind == "json_schema":
            schema = (rf.get("json_schema") or {}).get("schema")
            if schema is None:
                raise ValueError("response_format.json_schema.schema missing")
            return GuidedSpec("json_schema", _json.dumps(schema))
        if kind in (None, "text"):
            return None
        raise ValueError("unsupported response_format type {!r}".format(kind))

    def _n_requests(self, body: Dict[str, Any], prompt_ids: List[int],
                    chat: bool = True, guided_override=None):
        """OpenAI `n` choices: n independent requests through the continuous
        batch; seeded requests offset the seed per choice so choices differ."""
        n = int(body.get("n", 1) or 1)
        if n < 1:
            raise ValueError("n must be >= 1")
        requests = []
        for i in range(n):
            r = self._gen_request_from_body(
                body, list(prompt_ids), chat=chat,
                guided_override=guided_override,
            )
            if r.seed is not None and i:
                r.seed = r.seed + i
            requests.append(r)
        return requests

    @staticmethod
    def _report_gen_stats(request, collect_fn) -> None:
        """TTFT + token counts into the sampled-stats pipeline (BASELINE.md
        per-endpoint metrics). Streaming handlers call this when the SSE body
        finishes — the router defers the stats packet to stream completion
        (StreamingOutput.on_complete), so streaming TTFT is recorded too."""
        if collect_fn is None:
            return
        stats = {"gen_tokens": request.produced, "prompt_tokens": request.prompt_len}
        if request.first_token_at is not None:
            stats["ttft"] = round(request.first_token_at - request.submitted_at, 6)
        collect_fn(stats)

    @staticmethod
    def _stops_from_body(body: Dict[str, Any]) -> List[str]:
        """OpenAI `stop`: str | [str] (stop TOKEN ids go through the engine;
        strings are matched on the decoded text here)."""
        stop = body.get("stop")
        if stop is None:
            return []
        if isinstance(stop, str):
            return [stop] if stop else []
        return [str(s) for s in stop if s]

    @staticmethod
    def _first_stop_hit(text: str, stops: List[str]) -> int:
        """Earliest index where any stop string occurs, or -1."""
        hits = [text.find(s) for s in stops]
        hits = [h for h in hits if h >= 0]
        return min(hits) if hits else -1

    def _tokens_covering(self, ids: List[int], n_chars: int) -> int:
        """Smallest token count whose decoded prefix covers n_chars — the
        single criterion both the streaming and non-streaming paths use to
        trim tokens/logprobs/usage to emitted text."""
        j = len(ids)
        while j > 0 and len(self.tokenizer.decode(ids[: j - 1])) >= n_chars:
            j -= 1
        return j

    async def _collect_text(self, request, stops: Optional[List[str]] = None) -> Dict[str, Any]:
        ids: List[int] = []
        stops = stops or []
        # stop scanning decodes only a TAIL window per token (a full decode
        # per token would be O(T^2) of blocking tokenizer work on the event
        # loop): every token decodes to >= 1 character, so a window of
        # max-stop-length + margin tokens always covers a newly completed
        # stop match; the full decode happens once, on hit or at the end
        window = (max(len(s) for s in stops) + 8) if stops else 0
        async for token in self.engine.generate(request):
            ids.append(token)
            if stops:
                tail = self.tokenizer.decode(ids[-window:])
                if self._first_stop_hit(tail, stops) >= 0:
                    # OpenAI semantics: output excludes the stop sequence
                    request.stopped_on_string = True
                    request.cancel()
                    text = self.tokenizer.decode(ids)
                    cut = self._first_stop_hit(text, stops)
                    if cut >= 0:
                        # trim ids to the tokens that produce text[:cut] so
                        # logprobs/usage stay consistent with the returned
                        # text (no phantom stop-sequence tokens)
                        ids = ids[: self._tokens_covering(ids, cut)]
                        request.produced = len(ids)
                        text = text[:cut]
                    return {
                        "text": text,
                        "ids": ids,
                        "finish_reason": "stop",
                    }
        eos = self.tokenizer.eos_token_id
        if ids and eos is not None and ids[-1] == eos:
            ids = ids[:-1]
            finish = "stop"
        else:
            finish = self._finish_reason(request)
        return {"text": self.tokenizer.decode(ids), "ids": ids, "finish_reason": finish}

    async def _stream_deltas(self, request, stops: Optional[List[str]] = None) -> AsyncIterator[Dict[str, Any]]:
        """Yields text deltas (incremental decode keeps multi-byte tokens
        correct for HF tokenizers). Stop strings hold back a potential
        stop-prefix tail so matched stops are never partially emitted."""
        ids: List[int] = []
        sent = ""
        stops = stops or []
        holdback = max((len(s) for s in stops), default=1) - 1
        eos = self.tokenizer.eos_token_id
        lp_cursor = 0

        def take_entries(upto_tokens: int):
            """Logprob entries for tokens [lp_cursor, upto_tokens) — only
            tokens whose text has actually been emitted, so streamed entries
            never lead the deltas or include held-back/stop tokens."""
            nonlocal lp_cursor
            new = request.logprob_entries[lp_cursor:upto_tokens]
            lp_cursor = max(lp_cursor, upto_tokens)
            return new

        def entries_for(n_chars: int):
            """None when logprobs are off — and then the token-boundary
            decode (O(ids)) is skipped entirely, so plain streams pay no
            extra detokenization."""
            if request.logprobs is None:
                return None
            return take_entries(self._tokens_covering(ids, n_chars))

        async for token in self.engine.generate(request):
            if eos is not None and token == eos:
                break
            ids.append(token)
            text = self.tokenizer.decode(ids)
            if text.endswith("�"):  # partial multi-byte sequence
                continue
            if stops:
                cut = self._first_stop_hit(text, stops)
                if cut >= 0:
                    request.stopped_on_string = True
                    request.cancel()
                    # trim to the tokens producing text[:cut] so streamed
                    # entries/usage match the non-streaming path exactly
                    j = self._tokens_covering(ids, cut)
                    del ids[j:]
                    request.produced = j
                    entries = take_entries(j) if request.logprobs is not None else None
                    if cut > len(sent) or entries:
                        yield {"delta": text[len(sent):cut],
                               "entries": entries}
                    return
                text = text[: len(text) - holdback] if holdback else text
            if len(text) > len(sent):
                prev = len(sent)
                sent = text
                yield {
                    "delta": text[prev:],
                    "entries": entries_for(len(text)),
                }
        # flush any held-back tail: if the final decode legitimately ends with
        # the replacement character (truncated multi-byte at stop, or a real
        # '�' from the tokenizer), it must not be silently dropped — and
        # logprob entries for tokens that decoded to EMPTY text (so no delta
        # ever carried them) still need a final (possibly empty-delta) piece
        text = self.tokenizer.decode(ids)
        if stops:
            cut = self._first_stop_hit(text, stops)
            if cut >= 0:
                request.stopped_on_string = True
                text = text[:cut]
                j = self._tokens_covering(ids, cut)
                del ids[j:]
                request.produced = j
        tail_entries = (
            take_entries(len(ids)) if request.logprobs is not None else None
        )
        if len(text) > len(sent) or tail_entries:
            yield {"delta": text[len(sent):], "entries": tail_entries}

    def _finish_reason(self, request) -> str:
        """OpenAI semantics: "length" covers BOTH max_tokens truncation and
        hitting the model's context limit."""
        if request.stopped_on_string:
            return "stop"
        if request.produced >= request.max_new_tokens:
            return "length"
        if request.prompt_len + request.produced >= self.engine.max_seq_len:
            return "length"
        return "stop"

    # -- logprob formatting (OpenAI chat vs completions shapes) ---------------

    def _token_str(self, tid: int) -> str:
        return self.tokenizer.decode([int(tid)])

    def _token_repr(self, tid: int, as_ids: bool) -> str:
        """vLLM return_tokens_as_token_ids: "token_id:<id>" instead of the
        decoded piece (lets callers distinguish tokens that decode alike)."""
        return "token_id:{}".format(int(tid)) if as_ids else self._token_str(tid)

    def _chat_lp_entries(self, entries: List[dict], k: int,
                         as_ids: bool = False) -> List[dict]:
        """Chat-shape logprob items from engine entries ({"id", "logprob",
        "top_ids", "top_logprobs"}); shared by the streaming chunks and the
        final response."""
        content = []
        for entry in entries:
            tok = self._token_repr(entry["id"], as_ids)
            tops = []
            for t, lp in zip(entry["top_ids"][:k], entry["top_logprobs"][:k]):
                ts = self._token_repr(t, as_ids)
                tops.append(
                    {"token": ts, "logprob": lp, "bytes": list(ts.encode("utf-8"))}
                )
            content.append(
                {
                    "token": tok,
                    "logprob": entry["logprob"],
                    "bytes": list(tok.encode("utf-8")),
                    "top_logprobs": tops,
                }
            )
        return content

    def _chat_logprobs(self, request, ids: List[int]) -> Dict[str, Any]:
        return {
            "content": self._chat_lp_entries(
                request.logprob_entries[: len(ids)], int(request.logprobs or 0),
                as_ids=getattr(request, "tokens_as_ids", False),
            )
        }

    def _completion_lp_entries(
        self, entries: List[dict], k: int, offset: int = 0,
        as_ids: bool = False,
    ) -> Tuple[Dict[str, Any], int]:
        """-> (logprobs dict, next text offset). text_offset tracks the
        EMITTED text even in token_id mode, so each token decodes once."""
        tokens, token_logprobs, top_logprobs, offsets = [], [], [], []
        for entry in entries:
            decoded = self._token_str(entry["id"])
            tokens.append(
                "token_id:{}".format(int(entry["id"])) if as_ids else decoded
            )
            token_logprobs.append(entry["logprob"])
            tops = {}
            for t, lp in zip(entry["top_ids"][:k], entry["top_logprobs"][:k]):
                tops[self._token_repr(t, as_ids)] = lp
            top_logprobs.append(tops)
            offsets.append(offset)
            offset += len(decoded)
        return {
            "tokens": tokens,
            "token_logprobs": token_logprobs,
            "top_logprobs": top_logprobs,
            "text_offset": offsets,
        }, offset

    def _completion_logprobs(self, request, ids: List[int]) -> Dict[str, Any]:
        lp, _ = self._completion_lp_entries(
            request.logprob_entries[: len(ids)], int(request.logprobs or 0),
            as_ids=getattr(request, "tokens_as_ids", False),
        )
        return lp

    async def _fanout_stream(self, requests, stops, collect_fn, *,
                             head, delta, finish, usage):
        """Shared multi-choice SSE core (chat and completions n>1
        streaming): one _stream_deltas pump per choice feeds a queue and
        chunks interleave by arrival, tagged with the OpenAI per-chunk
        index by the format callbacks. ``head``: pre-built leading chunks;
        ``delta(i, req, piece)`` / ``finish(i, req)`` format per-choice
        chunks; ``usage()`` returns the trailing usage chunk or None. The
        finally block frees every decode slot and reports stats on normal
        completion AND client disconnect."""
        queue: "asyncio.Queue" = asyncio.Queue()

        async def pump(i, req):
            try:
                async for piece in self._stream_deltas(req, stops):
                    await queue.put((i, "delta", piece))
                await queue.put((i, "finish", None))
            except Exception as ex:  # surfaced as an SSE error event
                await queue.put((i, "error", ex))

        tasks: List[asyncio.Task] = []
        try:
            for chunk in head:
                yield chunk
            tasks = [
                asyncio.get_running_loop().create_task(pump(i, r))
                for i, r in enumerate(requests)
            ]
            live = len(requests)
            while live:
                i, kind, payload = await queue.get()
                if kind == "error":
                    yield "data: {}\n\n".format(json.dumps(
                        {"error": {"message": str(payload),
                                   "type": type(payload).__name__}}
                    ))
                    yield "data: [DONE]\n\n"
                    return
                if kind == "finish":
                    yield finish(i, requests[i])
                    live -= 1
                    continue
                yield delta(i, requests[i], payload)
            tail = usage()
            if tail is not None:
                yield tail
            yield "data: [DONE]\n\n"
        finally:
            for t in tasks:
                t.cancel()
            for r in requests:
                r.cancel()
                self._report_gen_stats(r, collect_fn)

    def _prompt_logprobs_payload(self, prompt_ids: List[int], n_top: int,
                                 adapter: Optional[str], entries=None):
        """vLLM `prompt_logprobs` extension: per-prompt-position dicts of
        token_id -> {logprob, rank, decoded_token} (first position None —
        no conditional), the top-n_top tokens plus the actual token with
        its EXACT vocab rank. Blocking device work unless precomputed
        ``entries`` are passed (echo+prompt_logprobs shares ONE scoring
        pass) — call off-loop."""
        if entries is None:
            entries = self.engine.score_prompt(prompt_ids, adapter=adapter)
        out: List[Optional[dict]] = [None]
        for e, tok in zip(entries, prompt_ids[1:]):
            d: Dict[str, Any] = {}
            for r_i, (t, lp) in enumerate(
                zip(e["top_ids"][:n_top], e["top_logprobs"][:n_top])
            ):
                d[str(int(t))] = {
                    "logprob": lp,
                    "rank": r_i + 1,
                    "decoded_token": self._token_str(int(t)),
                }
            d.setdefault(str(int(tok)), {
                "logprob": e["logprob"],
                "rank": int(e["rank"]),
                "decoded_token": self._token_str(int(tok)),
            })
            out.append(d)
        return out

    def _prompt_logprobs_n(self, body: Dict[str, Any]) -> Optional[int]:
        """Parse + validate the vLLM `prompt_logprobs` knob (None = off)."""
        raw = body.get("prompt_logprobs")
        if raw is None or raw is False:
            return None
        n_top = int(raw)
        if n_top < 0:
            raise ValueError("prompt_logprobs must be >= 0")
        ceiling = int(self.engine.logprobs_k)
        if n_top > ceiling:
            raise ValueError(
                "prompt_logprobs {} exceeds the engine ceiling {}".format(
                    n_top, ceiling
                )
            )
        return n_top

    def _echo_prompt_logprobs(self, prompt_ids: List[int], request,
                              entries=None):
        """OpenAI `echo` + `logprobs`: the logprobs block starts with the
        PROMPT tokens — the first has null logprob/top (no conditional), the
        rest come from one teacher-forced scoring pass
        (engine.score_prompt, same LoRA adapter as the generation). Returns
        (lp dict, next text offset) for the generated entries to append to.
        Blocking device work unless precomputed ``entries`` are passed —
        callers run it via asyncio.to_thread."""
        k = int(request.logprobs or 0)
        as_ids = getattr(request, "tokens_as_ids", False)
        if entries is None:
            entries = self.engine.score_prompt(
                prompt_ids, adapter=getattr(request, "adapter", None)
            )
        first = self._token_repr(prompt_ids[0], as_ids)
        lp, offset = self._completion_lp_entries(
            entries, k, offset=len(self._token_str(prompt_ids[0])),
            as_ids=as_ids,
        )
        lp["tokens"].insert(0, first)
        lp["token_logprobs"].insert(0, None)
        lp["top_logprobs"].insert(0, None)
        lp["text_offset"].insert(0, 0)
        return lp, offset

    # -- OpenAI route handlers (dispatched by serve_type) -----------------------

    def _require_engine(self, route: str) -> None:
        if self.engine is None:
            raise EndpointModelError(
                "model {!r} does not support {} (encoder endpoint — task-gated "
                "like the reference's vLLM handler instantiation)".format(
                    self._model_name, route
                )
            )

    def _require_encoder(self, route: str) -> None:
        if self.encoder is None:
            raise EndpointModelError(
                "model {!r} does not support {} (decoder-only LLM endpoint; "
                "serve an encoder bundle or set aux_config engine.task)".format(
                    self._model_name, route
                )
            )

    async def v1_chat_completions(self, body: Dict[str, Any], state: dict, collect_fn=None):
        from .tools import (
            TOOL_TAG,
            parse_tool_calls,
            render_chat_with_tools,
            resolve_tool_choice,
            split_tag_holdback,
            strip_tool_blocks,
            tool_call_objects,
            tool_call_schema,
            validate_tools,
        )

        self._require_engine("v1/chat/completions")
        await self._ensure_warm()
        messages = body.get("messages") or []
        tool_mode, forced_tool = resolve_tool_choice(body)
        # OpenAI semantics: tool_choice "none" only prevents CALLING — the
        # definitions stay visible in the prompt (multi-turn histories
        # reference them); only parsing/constraint is disabled
        tools_render = validate_tools(body["tools"]) if body.get("tools") else []
        tools = tools_render if tool_mode != "none" else []
        tool_names = [t["name"] for t in tools]
        guided_override = None
        if tool_mode in ("required", "forced"):
            # arguments enforced BY CONSTRUCTION: the tool-call JSON
            # compiles into the on-device decode grammar (llm/guided.py)
            from .guided import GuidedSpec

            # no sort_keys: the grammar must force name BEFORE arguments
            # (sorting would make the model commit arguments first — in
            # multi-tool required mode, before the tool is even pinned)
            guided_override = GuidedSpec(
                "json_schema",
                json.dumps(tool_call_schema(tools, forced_tool)),
            )
        # OpenAI `parallel_tool_calls` (default true): false caps auto-mode
        # parses at ONE call (required/forced already emit exactly one by
        # grammar construction)
        single_call = body.get("parallel_tool_calls") is False
        prompt = render_chat_with_tools(self.tokenizer, messages, tools_render)
        # encode_chat: no special-token re-add — HF chat templates already
        # emit BOS in the template text (double-BOS degrades fidelity)
        prompt_ids = self.tokenizer.encode_chat(prompt)
        stops = self._stops_from_body(body)
        model = body.get("model", self._model_name)
        completion_id = _gen_id("chatcmpl")
        created = _now()
        # vLLM `response_role`: request body overrides the endpoint's
        # aux-config chat block; default matches OpenAI ("assistant")
        role = str(
            body.get("response_role")
            or self._chat_cfg.get("response_role")
            or "assistant"
        )
        include_usage = bool(
            (body.get("stream_options") or {}).get("include_usage")
        )

        def chat_chunk(choice, usage="omit"):
            chunk = {
                "id": completion_id, "object": "chat.completion.chunk",
                "created": created, "model": model,
                "choices": [choice] if choice is not None else [],
            }
            if include_usage:
                # OpenAI stream_options semantics: every chunk carries
                # usage: null; one final choices-less chunk carries totals
                chunk["usage"] = None if usage == "omit" else usage
            return "data: {}\n\n".format(json.dumps(chunk))

        plp_n = self._prompt_logprobs_n(body)  # validate BEFORE any device work
        if body.get("stream"):
            if plp_n is not None:
                # vLLM semantics: prompt_logprobs cannot stream
                raise EndpointModelError(
                    "prompt_logprobs is not supported with streaming"
                )
            n_stream = int(body.get("n", 1) or 1)
            if n_stream != 1:
                if tools:
                    # the tool-call sniff/buffer machinery is per-choice
                    # state; multi-choice streaming is supported for plain
                    # chat only
                    raise EndpointModelError(
                        "streaming chat with tools supports a single "
                        "choice (n=1)"
                    )
                requests = self._n_requests(
                    body, prompt_ids, guided_override=guided_override
                )
                for i, r in enumerate(requests):
                    self.engine.validate(r)
                    # shed/deadline BEFORE the 200 headers: a saturated
                    # engine answers 429/408, not a broken SSE body; the
                    # reserve accounts for this batch's own earlier choices
                    self.engine.check_admission(r, reserve=i)

                def chat_delta(i, req, piece):
                    choice = {"index": i,
                              "delta": {"content": piece["delta"]},
                              "finish_reason": None}
                    if piece.get("entries") is not None:
                        choice["logprobs"] = {
                            "content": self._chat_lp_entries(
                                piece["entries"], int(req.logprobs or 0),
                                as_ids=getattr(req, "tokens_as_ids", False),
                            )
                        }
                    return chat_chunk(choice)

                def chat_finish(i, req):
                    return chat_chunk({
                        "index": i, "delta": {},
                        "finish_reason": self._finish_reason(req),
                    })

                def chat_usage():
                    if not include_usage:
                        return None
                    total = sum(r.produced for r in requests)
                    return chat_chunk(None, usage={
                        "prompt_tokens": requests[0].prompt_len,
                        "completion_tokens": total,
                        "total_tokens": requests[0].prompt_len + total,
                    })

                return StreamingOutput(self._fanout_stream(
                    requests, stops, collect_fn,
                    head=[
                        chat_chunk({"index": i, "delta": {"role": role},
                                    "finish_reason": None})
                        for i in range(n_stream)
                    ],
                    delta=chat_delta, finish=chat_finish, usage=chat_usage,
                ))
            request = self._gen_request_from_body(
                body, prompt_ids, guided_override=guided_override
            )
            # validate BEFORE returning the stream — a late ValueError would
            # abort mid-SSE after the 200 headers are already sent; same for
            # load-shed/expired-deadline (429/408 precede the headers)
            self.engine.validate(request)
            self.engine.check_admission(request)
            # required/forced always buffers (output IS a tool call); auto
            # sniffs the first text for a call-shaped prefix and buffers
            # only then, so plain answers still stream token by token. A
            # guided response_format (json_object/json_schema) forces the
            # output to start with '{'/'[' without it being a tool call, so
            # sniffing would buffer the whole response — stream normally.
            buffer_all = tool_mode in ("required", "forced")
            sniffing = (
                tool_mode == "auto" and bool(tools)
                and request.guided is None
            )

            def call_prefix(text):
                """Could `text` still grow into a tool call? -> 'yes'
                (buffer to end), 'maybe' (keep sniffing), 'no' (flush)."""
                s = text.lstrip()
                if not s:
                    return "maybe"
                if s.startswith(("{", "[", "<tool_call>")):
                    return "yes"
                if "<tool_call>".startswith(s):
                    return "maybe"
                return "no"

            async def sse():
                # mode machine: "buffer" = withholding a (suspected or
                # certain) tool call to stream end; "sniff" = deciding from
                # the first text; "watch" = streaming live but holding back
                # a potential <tool_call> tag (hermes models narrate BEFORE
                # calling, so tags can appear mid-answer); "stream" = plain.
                mode = "buffer" if buffer_all else (
                    "sniff" if sniffing else "stream"
                )
                held: List[str] = []      # text awaiting the decision
                stashed: List[dict] = []  # logprob entries withheld with it
                watch_pending = ""        # tag holdback in watch mode

                def lp(entries):
                    return {"content": self._chat_lp_entries(
                        entries, int(request.logprobs or 0),
                        as_ids=getattr(request, "tokens_as_ids", False),
                    )}

                def content_chunk(text, entries):
                    choice = {"index": 0, "delta": {"content": text},
                              "finish_reason": None}
                    if entries:
                        # withheld entries attach to the chunk that finally
                        # emits their text — every entry is delivered once
                        choice["logprobs"] = lp(entries)
                    return chat_chunk(choice)

                def watch_emit(text):
                    """Emittable prefix of `text`; switches to buffer mode
                    when a full tool tag appears, holds back partial tags."""
                    nonlocal mode, watch_pending, held
                    watch_pending += text
                    idx = watch_pending.find(TOOL_TAG)
                    if idx >= 0:
                        emit = watch_pending[:idx]
                        held = [watch_pending[idx:]]
                        watch_pending = ""
                        mode = "buffer"
                        return emit
                    emit, watch_pending = split_tag_holdback(watch_pending)
                    return emit

                try:
                    yield chat_chunk({"index": 0,
                                      "delta": {"role": role},
                                      "finish_reason": None})
                    try:
                        async for piece in self._stream_deltas(request, stops):
                            entries = piece.get("entries") or []
                            if mode in ("buffer", "sniff"):
                                held.append(piece["delta"])
                                stashed.extend(entries)
                                if mode == "sniff":
                                    # verdict settles within the first few
                                    # non-space chars; 'yes' locks buffer
                                    # mode so long buffered outputs don't
                                    # re-join `held` on every delta
                                    verdict = call_prefix("".join(held))
                                    if verdict == "yes":
                                        mode = "buffer"
                                    elif verdict == "no":
                                        mode = "watch"
                                        text, held = "".join(held), []
                                        emit = watch_emit(text)
                                        if emit:
                                            yield content_chunk(emit, stashed)
                                            stashed = []
                                continue
                            if mode == "watch":
                                emit = watch_emit(piece["delta"])
                                stashed.extend(entries)
                                if emit:
                                    yield content_chunk(emit, stashed)
                                    stashed = []
                                continue
                            choice = {"index": 0,
                                      "delta": {"content": piece["delta"]},
                                      "finish_reason": None}
                            if piece.get("entries") is not None:
                                choice["logprobs"] = lp(piece["entries"])
                            yield chat_chunk(choice)
                    except Exception as ex:
                        yield "data: {}\n\n".format(json.dumps(
                            {"error": {"message": str(ex), "type": type(ex).__name__}}
                        ))
                        yield "data: [DONE]\n\n"
                        return
                    finish = self._finish_reason(request)
                    text = "".join(held) + watch_pending
                    calls = (
                        parse_tool_calls(text, tool_names)
                        if text and tools and finish != "length"
                        else None
                    )
                    if calls and single_call:
                        calls = calls[:1]
                    if calls:
                        # prose around <tool_call> blocks still streams as
                        # content (OpenAI allows content + tool_calls)
                        prose = (
                            strip_tool_blocks(text)
                            if TOOL_TAG in text else ""
                        )
                        if prose:
                            yield content_chunk(prose, stashed)
                            stashed = []
                        for ci, tc in enumerate(tool_call_objects(calls)):
                            first = {
                                "index": 0,
                                "delta": {"tool_calls": [{
                                    "index": ci, "id": tc["id"],
                                    "type": "function",
                                    "function": {
                                        "name": tc["function"]["name"],
                                        "arguments": "",
                                    },
                                }]},
                                "finish_reason": None,
                            }
                            if ci == 0 and stashed:
                                first["logprobs"] = lp(stashed)
                                stashed = []
                            yield chat_chunk(first)
                            yield chat_chunk({
                                "index": 0,
                                "delta": {"tool_calls": [{
                                    "index": ci,
                                    "function": {"arguments":
                                                 tc["function"]["arguments"]},
                                }]},
                                "finish_reason": None,
                            })
                        finish = "tool_calls"
                    elif text:
                        yield content_chunk(text, stashed)
                        stashed = []
                    yield chat_chunk({"index": 0, "delta": {},
                                      "finish_reason": finish})
                    if include_usage:
                        yield chat_chunk(None, usage={
                            "prompt_tokens": request.prompt_len,
                            "completion_tokens": request.produced,
                            "total_tokens": request.prompt_len
                            + request.produced,
                        })
                    yield "data: [DONE]\n\n"
                finally:
                    # runs on normal completion AND on client disconnect
                    # (GeneratorExit): free the decode slot early and record
                    # streaming TTFT/token stats at stream end
                    request.cancel()
                    self._report_gen_stats(request, collect_fn)

            return StreamingOutput(sse())

        requests = self._n_requests(body, prompt_ids,
                                    guided_override=guided_override)
        results = await asyncio.gather(
            *[self._collect_text(r, stops) for r in requests]
        )
        for r in requests:
            self._report_gen_stats(r, collect_fn)
        # vLLM prompt_logprobs extension: one scoring pass, shared by choices
        plp_payload = None
        if plp_n is not None:
            plp_payload = await asyncio.to_thread(
                self._prompt_logprobs_payload, prompt_ids, plp_n,
                requests[0].adapter,
            )
        choices = []
        for i, (r, res) in enumerate(zip(requests, results)):
            choice = {
                "index": i,
                "message": {"role": role, "content": res["text"]},
                "finish_reason": res["finish_reason"],
                "logprobs": (
                    self._chat_logprobs(r, res["ids"])
                    if r.logprobs is not None
                    else None
                ),
            }

            # a body-supplied guided response_format pins the OUTPUT shape —
            # the JSON answer is the deliverable, not a tool call; skipping
            # the parse keeps stream and non-stream responses identical
            # (streaming disables its call sniff under the same condition)
            parse_ok = tool_mode in ("required", "forced") or r.guided is None
            if tools and parse_ok and res["finish_reason"] != "length":
                calls = parse_tool_calls(res["text"], tool_names)
                if calls and single_call:
                    calls = calls[:1]
                if calls:
                    # hermes-style prose around the <tool_call> blocks is
                    # kept as content (OpenAI allows content + tool_calls)
                    prose = (
                        strip_tool_blocks(res["text"])
                        if TOOL_TAG in res["text"] else ""
                    )
                    choice["message"] = {
                        "role": role,
                        "content": prose or None,
                        "tool_calls": tool_call_objects(calls),
                    }
                    choice["finish_reason"] = "tool_calls"
            choices.append(choice)
        out = {
            "id": completion_id,
            "object": "chat.completion",
            "created": created,
            "model": model,
            "choices": choices,
            # OpenAI semantics: the prompt counts once regardless of n
            "usage": {
                "prompt_tokens": requests[0].prompt_len,
                "completion_tokens": sum(r.produced for r in requests),
                "total_tokens": requests[0].prompt_len
                + sum(r.produced for r in requests),
            },
        }
        if plp_payload is not None:
            # vLLM ChatCompletionResponse shape: prompt_logprobs is a
            # TOP-LEVEL response field (per-choice is the completions shape)
            out["prompt_logprobs"] = plp_payload
        return out

    def _check_token_ids(self, ids: List[int]) -> List[int]:
        core = self.engine if self.engine is not None else self.encoder
        vocab = int(core.bundle.config.get("vocab_size", 0))
        for t in ids:
            if not (0 <= int(t) < vocab):
                raise ValueError(
                    "token id {} out of range for vocab size {}".format(t, vocab)
                )
        return [int(t) for t in ids]

    def _encode_prompts(self, prompt) -> List[List[int]]:
        """OpenAI completions `prompt` polymorphism: str | [str] | [int] |
        [[int]] — token-id forms pass through (range-checked, not re-encoded)."""
        if isinstance(prompt, str):
            return [self.tokenizer.encode(prompt)]
        if isinstance(prompt, list):
            if not prompt:
                return [self.tokenizer.encode("")]
            if all(isinstance(p, int) for p in prompt):
                return [self._check_token_ids(prompt)]
            if all(isinstance(p, list) for p in prompt):
                return [self._check_token_ids(p) for p in prompt]
            return [self.tokenizer.encode(str(p)) for p in prompt]
        return [self.tokenizer.encode(str(prompt))]

    async def v1_completions(self, body: Dict[str, Any], state: dict, collect_fn=None):
        self._require_engine("v1/completions")
        await self._ensure_warm()
        if body.get("suffix") is not None:
            # vLLM rejects suffix explicitly — even "" — (fill-in-middle
            # needs a FIM-trained model + template); silent ignoring would
            # return a continuation the client believes is an infill.
            # Checked before prompt tokenization: doomed requests pay no
            # host work and report THIS error, not a downstream one.
            raise EndpointModelError(
                "suffix is not supported (no fill-in-middle template)"
            )
        prompt_id_lists = self._encode_prompts(body.get("prompt") or "")
        stops = self._stops_from_body(body)
        model = body.get("model", self._model_name)
        completion_id = _gen_id("cmpl")
        created = _now()

        plp_n = self._prompt_logprobs_n(body)  # validate BEFORE any device work
        if body.get("stream") and plp_n is not None:
            # vLLM semantics: prompt_logprobs cannot stream (checked before
            # the max_tokens=0 short-circuit so that path can't bypass it)
            raise EndpointModelError(
                "prompt_logprobs is not supported with streaming"
            )
        raw_max = body.get("max_tokens", body.get("max_completion_tokens"))
        if raw_max is not None and int(raw_max) == 0:
            # OpenAI's canonical prompt-scoring call: echo + logprobs +
            # max_tokens 0 returns the scored prompt and generates nothing
            # (the falsy-zero would otherwise fall through to the default
            # budget and bill 128 unasked-for tokens)
            return await self._zero_completion(body, prompt_id_lists, model,
                                               completion_id, created,
                                               collect_fn, plp_n)

        if body.get("stream"):
            if len(prompt_id_lists) != 1:
                raise EndpointModelError(
                    "streaming completions support a single prompt per request"
                )
            stream_n = int(body.get("n", 1) or 1)
            if (
                body.get("best_of") is not None
                and int(body["best_of"]) != stream_n
            ):
                # OpenAI: a server-side candidate pool cannot stream (which
                # choice to emit is unknown until the end); best_of == n
                # degenerates to plain n and may stream
                raise EndpointModelError(
                    "best_of must equal n when streaming"
                )
            stream_requests = self._n_requests(body, prompt_id_lists[0],
                                               chat=False)
            for i, r in enumerate(stream_requests):
                self.engine.validate(r)
                self.engine.check_admission(r, reserve=i)

            include_usage = bool(
                (body.get("stream_options") or {}).get("include_usage")
            )

            def cmpl_chunk(choices, usage="omit"):
                chunk = {
                    "id": completion_id, "object": "text_completion",
                    "created": created, "model": model, "choices": choices,
                }
                if include_usage:
                    chunk["usage"] = None if usage == "omit" else usage
                return "data: {}\n\n".format(json.dumps(chunk))

            echo = bool(body.get("echo"))

            lp_offsets = [0] * stream_n

            def cmpl_delta(i, req, piece):
                choice = {"index": i, "text": piece["delta"],
                          "finish_reason": None}
                if piece.get("entries") is not None:
                    lp, lp_offsets[i] = self._completion_lp_entries(
                        piece["entries"], int(req.logprobs or 0),
                        offset=lp_offsets[i],
                        as_ids=getattr(req, "tokens_as_ids", False),
                    )
                    choice["logprobs"] = lp
                return cmpl_chunk([choice])

            def cmpl_finish(i, req):
                return cmpl_chunk(
                    [{"index": i, "text": "",
                      "finish_reason": self._finish_reason(req)}]
                )

            def cmpl_usage():
                if not include_usage:
                    return None
                total = sum(r.produced for r in stream_requests)
                return cmpl_chunk([], usage={
                    "prompt_tokens": stream_requests[0].prompt_len,
                    "completion_tokens": total,
                    "total_tokens": stream_requests[0].prompt_len + total,
                })

            async def sse():
                head = []
                if echo:
                    # OpenAI echo semantics: the prompt text arrives as
                    # each choice's first chunk (logprob entries scored
                    # ONCE off-loop; choices share the prompt)
                    prompt_text = self.tokenizer.decode(prompt_id_lists[0])
                    echo_lp = None
                    if stream_requests[0].logprobs is not None:
                        echo_lp, off = await asyncio.to_thread(
                            self._echo_prompt_logprobs,
                            prompt_id_lists[0], stream_requests[0],
                        )
                        lp_offsets[:] = [off] * stream_n
                    for i in range(stream_n):
                        first = {"index": i, "text": prompt_text,
                                 "finish_reason": None}
                        if echo_lp is not None:
                            first["logprobs"] = {
                                k: list(v) for k, v in echo_lp.items()
                            }
                        head.append(cmpl_chunk([first]))
                async for chunk in self._fanout_stream(
                    stream_requests, stops, collect_fn,
                    head=head, delta=cmpl_delta, finish=cmpl_finish,
                    usage=cmpl_usage,
                ):
                    yield chunk

            return StreamingOutput(sse())

        # n choices per prompt, all generated concurrently through the
        # continuous batch (OpenAI batched-prompt semantics: choice index is
        # prompt-major, prompt_idx * n + choice_idx). vLLM `best_of`:
        # generate best_of candidates per prompt server-side, return the
        # top n ranked by cumulative logprob; every candidate's tokens
        # count toward usage (OpenAI billing semantics).
        n = int(body.get("n", 1) or 1)
        best_of = int(body.get("best_of") or n)
        if best_of < n:
            raise ValueError("best_of must be >= n")
        cand_body = dict(body, n=best_of) if best_of != n else body
        requests: List[Any] = []
        for ids in prompt_id_lists:
            requests.extend(self._n_requests(cand_body, ids, chat=False))
        # ranking needs per-token chosen logprobs; when the user did not ask
        # for them (None OR false — the request parser treats both as off),
        # collect them internally and omit them from the reply
        lp_internal = best_of != n and requests[0].logprobs is None
        if lp_internal:
            for r in requests:
                r.logprobs = 0
        results = await asyncio.gather(
            *[self._collect_text(r, stops) for r in requests]
        )
        for r in requests:
            self._report_gen_stats(r, collect_fn)
        if best_of != n:
            def cumulative_lp(i: int) -> float:
                # +1 keeps the finishing token's entry (EOS is stripped
                # from ids): vLLM's cumulative_logprob includes it, and
                # without it an immediate-EOS candidate would sum an empty
                # slice to 0.0 and outrank every real completion
                ents = requests[i].logprob_entries[: len(results[i]["ids"]) + 1]
                return sum(e["logprob"] for e in ents)

            sel: List[int] = []
            for p in range(len(prompt_id_lists)):
                grp = list(range(p * best_of, (p + 1) * best_of))
                grp.sort(key=cumulative_lp, reverse=True)
                sel.extend(grp[:n])
        else:
            sel = list(range(len(requests)))
        echo = bool(body.get("echo"))
        # echo+logprobs: ONE teacher-forced scoring pass per distinct
        # prompt (choices share it), off the event loop — the jitted
        # forward (plus a first-hit compile) would stall every concurrent
        # stream if run inline
        # echo+logprobs and prompt_logprobs share ONE teacher-forced scoring
        # pass per distinct prompt; the payload build (O(prompt x top_k)
        # tokenizer decodes) stays off the event loop with it
        echo_lp: Dict[int, Any] = {}
        plp: Dict[int, Any] = {}
        want_echo_lp = (
            echo and requests[0].logprobs is not None and not lp_internal
        )
        if want_echo_lp or plp_n is not None:
            def build_payloads(ids, req0):
                entries = self.engine.score_prompt(ids, req0.adapter)
                e = (
                    self._echo_prompt_logprobs(ids, req0, entries=entries)
                    if want_echo_lp
                    else None
                )
                q = (
                    self._prompt_logprobs_payload(
                        ids, plp_n, req0.adapter, entries=entries
                    )
                    if plp_n is not None
                    else None
                )
                return e, q

            for p, ids in enumerate(prompt_id_lists):
                e, q = await asyncio.to_thread(
                    build_payloads, ids, requests[p * best_of]
                )
                if e is not None:
                    echo_lp[p] = e
                if q is not None:
                    plp[p] = q
        choices = []
        for i, idx in enumerate(sel):
            r, res = requests[idx], results[idx]
            choice = {
                "index": i,
                "text": res["text"],
                "finish_reason": res["finish_reason"],
                "logprobs": (
                    self._completion_logprobs(r, res["ids"])
                    if r.logprobs is not None and not lp_internal
                    else None
                ),
            }
            if idx // best_of in plp:
                choice["prompt_logprobs"] = plp[idx // best_of]
            if echo:
                # OpenAI `echo`: the prompt text leads the output; with
                # logprobs, prompt-token entries lead the block (first one
                # null — no conditional)
                p_ids = requests[idx].prompt_ids
                choice["text"] = self.tokenizer.decode(p_ids) + res["text"]
                if idx // best_of in echo_lp:
                    lp0, off = echo_lp[idx // best_of]
                    lp = {k2: list(v2) for k2, v2 in lp0.items()}
                    gen_lp, _ = self._completion_lp_entries(
                        r.logprob_entries[: len(res["ids"])],
                        int(r.logprobs or 0), offset=off,
                        as_ids=getattr(r, "tokens_as_ids", False),
                    )
                    for key in ("tokens", "token_logprobs", "top_logprobs",
                                "text_offset"):
                        lp[key].extend(gen_lp[key])
                    choice["logprobs"] = lp
            choices.append(choice)
        prompt_tokens = sum(len(ids) for ids in prompt_id_lists)
        return {
            "id": completion_id,
            "object": "text_completion",
            "created": created,
            "model": model,
            "choices": choices,
            "usage": {
                "prompt_tokens": prompt_tokens,
                "completion_tokens": sum(r.produced for r in requests),
                "total_tokens": prompt_tokens + sum(r.produced for r in requests),
            },
        }

    async def _zero_completion(self, body, prompt_id_lists, model,
                               completion_id, created, collect_fn,
                               plp_n=None):
        """max_tokens=0 completions: no generation; echo/logprobs and
        prompt_logprobs still apply (per-prompt scoring passes off the
        event loop) — this IS the canonical prompt-scoring call."""
        echo = bool(body.get("echo"))
        n = int(body.get("n", 1) or 1)
        if n < 1:
            raise ValueError("n must be >= 1")
        choices = []
        for p, ids in enumerate(prompt_id_lists):
            if not ids:
                raise ValueError("prompt must not be empty")
            # a probe request runs the SAME validation (prompt length,
            # logprobs ceiling, guided config) every generating path runs —
            # this path must not 500 where those would 4xx
            probe = self._gen_request_from_body(body, list(ids), chat=False)
            probe.max_new_tokens = 1
            probe.prompt_len = len(ids)
            self.engine.validate(probe)
            text = self.tokenizer.decode(ids) if echo else ""
            lp = None
            plp_payload = None
            if (probe.logprobs is not None and echo) or plp_n is not None:
                def build_payloads(ids=ids, probe=probe):
                    entries = self.engine.score_prompt(ids, probe.adapter)
                    e = (
                        self._echo_prompt_logprobs(ids, probe,
                                                   entries=entries)
                        if probe.logprobs is not None and echo
                        else None
                    )
                    q = (
                        self._prompt_logprobs_payload(
                            ids, plp_n, probe.adapter, entries=entries
                        )
                        if plp_n is not None
                        else None
                    )
                    return e, q

                e, plp_payload = await asyncio.to_thread(build_payloads)
                if e is not None:
                    lp = e[0]
            if probe.logprobs is not None and lp is None:
                # logprobs without echo: nothing generated -> empty block
                lp = {"tokens": [], "token_logprobs": [],
                      "top_logprobs": [], "text_offset": []}
            for _ in range(n):
                choice = {
                    "index": len(choices),
                    "text": text,
                    "finish_reason": "length",
                    "logprobs": dict(lp) if lp is not None else None,
                }
                if plp_payload is not None:
                    choice["prompt_logprobs"] = plp_payload
                choices.append(choice)
        if collect_fn is not None:
            collect_fn({
                "gen_tokens": 0,
                "prompt_tokens": sum(len(i) for i in prompt_id_lists),
            })
        prompt_tokens = sum(len(i) for i in prompt_id_lists)
        out = {
            "id": completion_id,
            "object": "text_completion",
            "created": created,
            "model": model,
            "choices": choices,
            "usage": {
                "prompt_tokens": prompt_tokens,
                "completion_tokens": 0,
                "total_tokens": prompt_tokens,
            },
        }
        if body.get("stream"):
            include_usage = bool(
                (body.get("stream_options") or {}).get("include_usage")
            )

            async def sse():
                for ch in choices:
                    chunk = {
                        "id": completion_id, "object": "text_completion",
                        "created": created, "model": model, "choices": [ch],
                    }
                    if include_usage:
                        chunk["usage"] = None
                    yield "data: {}\n\n".format(json.dumps(chunk))
                if include_usage:
                    yield "data: {}\n\n".format(json.dumps({
                        "id": completion_id, "object": "text_completion",
                        "created": created, "model": model, "choices": [],
                        "usage": out["usage"],
                    }))
                yield "data: [DONE]\n\n"

            return StreamingOutput(sse())
        return out

    async def v1_models(self, body: Dict[str, Any], state: dict, collect_fn=None):
        data = [
            {
                "id": self._model_name,
                "object": "model",
                "created": _now(),
                "owned_by": "tpu-serving",
            }
        ]
        # loaded LoRA adapters list as models with a parent (vLLM-compatible
        # multi-LoRA discovery; select one via the request's `model` field)
        for name in getattr(self.engine, "adapter_names", []) or []:
            data.append(
                {
                    "id": name,
                    "object": "model",
                    "created": _now(),
                    "owned_by": "tpu-serving",
                    "parent": self._model_name,
                }
            )
        return {"object": "list", "data": data}

    async def version(self, body: Dict[str, Any], state: dict, collect_fn=None):
        """The 13th OpenAI route type (reference preprocess_service.py:890
        ``show_version`` → GET /serve/openai/version)."""
        from ..version import __version__

        return {"version": __version__}

    @property
    def _max_model_len(self) -> int:
        core = self.engine if self.engine is not None else self.encoder
        return core.max_seq_len if core is not None else 0

    async def v1_tokenize(self, body: Dict[str, Any], state: dict, collect_fn=None):
        ids = self.tokenizer.encode(str(body.get("prompt") or body.get("text") or ""))
        return {"tokens": ids, "count": len(ids), "max_model_len": self._max_model_len}

    async def v1_detokenize(self, body: Dict[str, Any], state: dict, collect_fn=None):
        ids = body.get("tokens") or []
        return {"prompt": self.tokenizer.decode([int(i) for i in ids])}

    # -- encoder routes (OpenAI embeddings API + vLLM-compatible extensions) --

    def _encode_texts(self, value) -> List[List[int]]:
        """OpenAI embeddings `input` polymorphism, same as completions
        `prompt`: str | [str] | [int] | [[int]]."""
        return self._encode_prompts(value)

    @staticmethod
    def _format_vec(vec, fmt: str):
        if fmt == "base64":
            import base64

            import numpy as _np

            return base64.b64encode(
                _np.asarray(vec, _np.float32).tobytes()
            ).decode("ascii")
        return [float(x) for x in vec]

    async def v1_embeddings(self, body: Dict[str, Any], state: dict, collect_fn=None):
        self._require_encoder("v1/embeddings")
        id_lists = self._encode_texts(body.get("input") or "")
        fmt = body.get("encoding_format", "float")
        if fmt not in ("float", "base64"):
            raise ValueError("encoding_format must be 'float' or 'base64'")
        dims = body.get("dimensions")
        if dims is not None:
            dims = int(dims)  # type/lower-bound BEFORE the device forward
            if dims < 1:
                raise ValueError("dimensions must be >= 1")
        vecs = await asyncio.to_thread(self.encoder.embed, id_lists)
        if dims is not None:
            # OpenAI `dimensions` (matryoshka truncation): keep the leading
            # dims and re-normalize so cosine similarity stays meaningful
            import numpy as _np

            full = len(vecs[0]) if len(vecs) else 0
            if full and dims > full:
                raise ValueError(
                    "dimensions must be in [1, {}]".format(full)
                )
            out_vecs = []
            for v in vecs:
                t = _np.asarray(v, _np.float32)[:dims]
                norm = float(_np.linalg.norm(t))
                out_vecs.append(t / norm if norm > 0 else t)
            vecs = out_vecs
        n_tokens = sum(len(ids) for ids in id_lists)
        if collect_fn is not None:
            collect_fn({"prompt_tokens": n_tokens, "n_inputs": len(id_lists)})
        return {
            "object": "list",
            "model": body.get("model", self._model_name),
            "data": [
                {
                    "object": "embedding",
                    "index": i,
                    "embedding": self._format_vec(vec, fmt),
                }
                for i, vec in enumerate(vecs)
            ],
            "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
        }

    async def v1_pooling(self, body: Dict[str, Any], state: dict, collect_fn=None):
        """vLLM pooling API: raw per-token hidden states (or pooled vector)."""
        self._require_encoder("v1/pooling")
        id_lists = self._encode_texts(body.get("input") or "")
        per_token = body.get("return_token_states", False)
        if per_token:
            states = await asyncio.to_thread(self.encoder.token_states, id_lists)
            data = [
                {"object": "pooling", "index": i, "data": s.tolist()}
                for i, s in enumerate(states)
            ]
        else:
            vecs = await asyncio.to_thread(self.encoder.embed, id_lists)
            data = [
                {"object": "pooling", "index": i, "data": [float(x) for x in v]}
                for i, v in enumerate(vecs)
            ]
        n_tokens = sum(len(ids) for ids in id_lists)
        if collect_fn is not None:
            collect_fn({"prompt_tokens": n_tokens, "n_inputs": len(id_lists)})
        return {
            "object": "list",
            "model": body.get("model", self._model_name),
            "data": data,
            "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
        }

    async def v1_classify(self, body: Dict[str, Any], state: dict, collect_fn=None):
        self._require_encoder("v1/classify")
        id_lists = self._encode_texts(body.get("input") or "")
        logits = await asyncio.to_thread(self.encoder.classify, id_lists)
        import numpy as _np

        probs = _np.exp(logits - logits.max(axis=-1, keepdims=True))
        probs = probs / probs.sum(axis=-1, keepdims=True)
        labels = self.endpoint_labels()
        data = []
        for i in range(len(id_lists)):
            idx = int(_np.argmax(probs[i]))
            data.append(
                {
                    "index": i,
                    "label": labels[idx] if idx < len(labels) else str(idx),
                    "probs": [float(p) for p in probs[i]],
                    "num_classes": int(probs.shape[-1]),
                }
            )
        n_tokens = sum(len(ids) for ids in id_lists)
        if collect_fn is not None:
            collect_fn({"prompt_tokens": n_tokens, "n_inputs": len(id_lists)})
        return {
            "object": "list",
            "model": body.get("model", self._model_name),
            "data": data,
            "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
        }

    def endpoint_labels(self) -> List[str]:
        aux = self.endpoint.auxiliary_cfg if isinstance(self.endpoint.auxiliary_cfg, dict) else {}
        return list((aux.get("engine") or {}).get("labels") or [])

    def _score_pairs_body(self, body: Dict[str, Any]):
        t1, t2 = body.get("text_1"), body.get("text_2")
        if t1 is None or t2 is None:
            raise ValueError("score requests need text_1 and text_2")
        list1 = t1 if isinstance(t1, list) else [t1]
        list2 = t2 if isinstance(t2, list) else [t2]
        if len(list1) == 1 and len(list2) > 1:
            list1 = list1 * len(list2)
        if len(list2) == 1 and len(list1) > 1:
            list2 = list2 * len(list1)
        if len(list1) != len(list2):
            raise ValueError("text_1/text_2 lengths do not broadcast")
        # cross-encoder: segments encoded bare; EncoderCore assembles the
        # [CLS] a [SEP] b [SEP] pair itself. bi-encoder: full encodes.
        bare = self.encoder.is_cross_encoder
        pairs = [
            (
                self.tokenizer.encode(str(a), add_bos=not bare),
                self.tokenizer.encode(str(b), add_bos=not bare),
            )
            for a, b in zip(list1, list2)
        ]
        return pairs

    async def v1_score(self, body: Dict[str, Any], state: dict, collect_fn=None):
        """vLLM score API: pairwise relevance of text_1 x text_2."""
        self._require_encoder("v1/score")
        pairs = self._score_pairs_body(body)
        scores = await asyncio.to_thread(self.encoder.score_pairs, pairs)
        n_tokens = sum(len(a) + len(b) for a, b in pairs)
        if collect_fn is not None:
            collect_fn({"prompt_tokens": n_tokens, "n_inputs": len(pairs)})
        return {
            "object": "list",
            "model": body.get("model", self._model_name),
            "data": [
                {"object": "score", "index": i, "score": s}
                for i, s in enumerate(scores)
            ],
            "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
        }

    async def v1_rerank(self, body: Dict[str, Any], state: dict, collect_fn=None):
        """Jina/Cohere-compatible rerank (vLLM do_rerank semantics): score
        each document against the query, return top_n descending."""
        self._require_encoder("v1/rerank")
        query = body.get("query")
        documents = body.get("documents") or []
        if query is None or not documents:
            raise ValueError("rerank requests need query and documents")
        doc_texts = []
        for i, d in enumerate(documents):
            if isinstance(d, dict):
                text = d.get("text", d.get("content"))
                if not isinstance(text, str):
                    raise ValueError(
                        "documents[{}] needs a string 'text' field".format(i)
                    )
                doc_texts.append(text)
            else:
                doc_texts.append(str(d))
        bare = self.encoder.is_cross_encoder
        q_ids = self.tokenizer.encode(str(query), add_bos=not bare)
        doc_ids = [self.tokenizer.encode(t, add_bos=not bare) for t in doc_texts]
        scores = await asyncio.to_thread(self.encoder.rerank, q_ids, doc_ids)
        order = sorted(range(len(scores)), key=lambda i: scores[i], reverse=True)
        top_n = int(body.get("top_n") or len(order))
        results = [
            {
                "index": i,
                "document": {"text": doc_texts[i]},
                "relevance_score": scores[i],
            }
            for i in order[:top_n]
        ]
        n_tokens = len(q_ids) + sum(len(d) for d in doc_ids)
        if collect_fn is not None:
            collect_fn({"prompt_tokens": n_tokens, "n_inputs": len(doc_ids)})
        return {
            "id": _gen_id("rerank"),
            "model": body.get("model", self._model_name),
            "results": results,
            "usage": {"total_tokens": n_tokens},
        }

    # -- audio routes (OpenAI transcription API; whisper-family bundles) ------

    def _require_audio(self, route: str) -> None:
        if self.audio is None:
            raise EndpointModelError(
                "model {!r} does not support {} (serve a speech bundle — "
                "arch 'whisper' — on this endpoint)".format(self._model_name, route)
            )

    def _audio_pcm(self, body: Dict[str, Any]):
        from ..ops.audio import decode_wav

        data = body.get("file")
        if isinstance(data, str):
            import base64

            try:
                data = base64.b64decode(data)
            except Exception:
                raise ValueError("'file' must be WAV bytes or base64-encoded WAV")
        if not isinstance(data, (bytes, bytearray)):
            raise ValueError(
                "audio requests need a 'file' field (multipart upload or "
                "base64 WAV in JSON)"
            )
        return decode_wav(bytes(data), target_rate=self.audio.sampling_rate)

    async def _audio_route(self, body, collect_fn, task: str, route: str):
        self._require_audio(route)
        pcm = self._audio_pcm(body)
        duration = round(len(pcm) / self.audio.sampling_rate, 3)
        verbose = body.get("response_format") == "verbose_json"
        # verbose_json decodes WITH timestamp conditioning (segments need
        # the marker tokens); the plain paths keep the faster
        # <|notimestamps|> prompt. Bundles converted before the timestamp
        # vocabulary was recorded fall back to text-only verbose output.
        with_ts = verbose and self.audio.timestamp_begin is not None
        # batching front door: concurrent same-(task, timestamps) requests
        # share one encode/decode pass (AudioCore micro-batcher)
        windows = await self.audio.transcribe_windows_async(
            pcm, task, timestamps=with_ts
        )
        ids = [t for w in windows for t in w]
        ts_begin = self.audio.timestamp_begin
        text_ids = (
            [t for t in ids if t < ts_begin] if ts_begin is not None else ids
        )
        text = self.tokenizer.decode(text_ids)
        if collect_fn is not None:
            collect_fn(
                {
                    "gen_tokens": len(ids),
                    "audio_seconds": duration,
                }
            )
        if body.get("response_format") == "text":
            from ..serving.responses import TextOutput

            return TextOutput(text)
        out = {"text": text}
        if verbose:
            out.update(
                task=task,
                duration=duration,
                language=body.get("language"),
            )
            if with_ts:
                segments = self.audio.parse_segments(windows, duration)
                for seg in segments:
                    seg["text"] = self.tokenizer.decode(seg["tokens"])
                granularities = body.get("timestamp_granularities") or ["segment"]
                if isinstance(granularities, str):
                    granularities = [granularities]
                if "segment" in granularities:
                    out["segments"] = segments
                if "word" in granularities:
                    # whisper-faithful word timing: DTW over cross-attention
                    # alignment heads; proportional interpolation only when
                    # the bundle lacks the alignment surface or the DTW
                    # pass fails (docs/parity.md Whisper row)
                    words = None
                    try:
                        words = await asyncio.to_thread(
                            self.audio.words_dtw, pcm, windows,
                            self.tokenizer, task,
                        )
                    except Exception:
                        # degraded word timing must leave a signal — a
                        # silent fall-back would hide a persistently
                        # failing DTW pass that still pays encode+align
                        logging.getLogger(__name__).warning(
                            "word-timestamp DTW failed; falling back to "
                            "proportional interpolation",
                            exc_info=True,
                        )
                    out["words"] = (
                        words
                        if words is not None
                        else self.audio.words_from_segments(segments)
                    )
        return out

    async def v1_audio_transcriptions(self, body, state, collect_fn=None):
        return await self._audio_route(
            body or {}, collect_fn, "transcribe", "v1/audio/transcriptions"
        )

    async def v1_audio_translations(self, body, state, collect_fn=None):
        return await self._audio_route(
            body or {}, collect_fn, "translate", "v1/audio/translations"
        )

    # -- phases -----------------------------------------------------------------

    async def preprocess(self, body: Any, state: dict, collect_fn=None) -> Any:
        if self._preprocess is not None and hasattr(self._preprocess, "preprocess"):
            out = self._preprocess.preprocess(body, state, collect_fn)
            if asyncio.iscoroutine(out):
                out = await out
            return out
        return body

    async def process(self, data: Any, state: dict, collect_fn=None) -> Any:
        """Plain /serve/{endpoint} POST: non-streaming chat completion for
        decoder endpoints, embeddings for encoder endpoints."""
        if self.engine is None and self.encoder is not None:
            return await self.v1_embeddings(data or {}, state, collect_fn)
        return await self.v1_chat_completions(data or {}, state, collect_fn)

    async def postprocess(self, data: Any, state: dict, collect_fn=None) -> Any:
        if self._preprocess is not None and hasattr(self._preprocess, "postprocess"):
            out = self._preprocess.postprocess(data, state, collect_fn)
            if asyncio.iscoroutine(out):
                out = await out
            return out
        return data
