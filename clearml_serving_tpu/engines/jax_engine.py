"""In-process JAX/XLA engine — the TPU-native replacement for the reference's
Triton path (SURVEY.md §2.9 row 1), embedded directly in the serving process.

Model payloads are **jax bundles**: a directory with

    model_config.json   {"arch": "mlp"|"cnn"|"bert"|"llama", "config": {...}}
    params.msgpack      flax-serialized parameter pytree

(see save_bundle/load_bundle). The engine:

- builds the architecture from the models registry and restores params;
- jit-compiles ``apply`` once per **batch bucket** — incoming batches are padded
  up to the next bucket size so arbitrary client batch sizes cannot trigger an
  XLA recompilation storm (the TPU analog of Triton's dynamic batcher, and the
  #1 "hard part" in SURVEY.md §7);
- enables JAX's persistent compilation cache so container restart ≠ recompile
  (SURVEY.md §5.4);
- converts JSON bodies to typed arrays per the endpoint I/O spec and back.

A user ``Preprocess.load()`` returning a callable replaces the native loader:
the callable is treated as ``fn(*inputs) -> outputs`` and jitted the same way.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .base import BaseEngineRequest, EndpointModelError, register_engine
from ..utils.files import atomic_write_json, read_json

# NOTE: jax is imported lazily inside functions — engines/__init__ imports this
# module unconditionally, and CLI/statistics processes must not pay JAX/libtpu
# initialization (or contend for the TPU device lock) just to mutate config.

_DEFAULT_BUCKETS = [1, 2, 4, 8, 16, 32, 64, 128]
# the one in-code location of the persistent compilation cache: a fixed
# directory inside the checkout (the path is part of the cache key, so a
# directory that moves never hits)
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_persistent_compilation_cache() -> None:
    """Place JAX's persistent compilation cache. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set the cache was placed from outside
    — JAX reads the variable itself and this function sets nothing;
    otherwise the cache lives in ``<checkout>/.jax_cache``. Every entry
    point that compiles goes through here (LLM and jax endpoints, bench,
    loadtests), so no other code names a cache directory."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    _CHECKOUT_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


# -- bundle IO ----------------------------------------------------------------

def save_bundle(path, arch: str, config: dict, params) -> None:
    """Write a jax model bundle directory."""
    import jax
    from flax import serialization

    path = Path(path)
    path.mkdir(parents=True, exist_ok=True)
    atomic_write_json(path / "model_config.json", {"arch": arch, "config": config})
    (path / "params.msgpack").write_bytes(serialization.msgpack_serialize(
        jax.tree.map(np.asarray, params)
    ))


def _endpoint_input_spec(endpoint) -> Tuple[List[List[int]], List[str]]:
    """Endpoint I/O spec -> per-input example shapes (batch dim 1) + dtypes."""
    sizes = endpoint.input_size or []
    types = endpoint.input_type or []
    if sizes and not isinstance(sizes[0], (list, tuple)):
        sizes = [sizes]  # single flat shape
    if isinstance(types, str):
        types = [types]
    shapes = [[1] + [int(d) for d in s] for s in sizes]
    torch_types = []
    for t in types:
        torch_types.append(
            {"float32": "float32", "float64": "float64", "int64": "int64",
             "int32": "int32", "uint8": "uint8", "bool": "bool"}.get(str(t), "float32")
        )
    return shapes, torch_types


def load_bundle(path, endpoint=None, config_overrides=None) -> Tuple[Any, Any]:
    """Returns (model_bundle namespace, params).

    ``config_overrides`` merges into the stored model config before the
    architecture builds (native jax bundles only) — used by the llm engine
    to enable serving-time features the checkpoint doesn't know about, e.g.
    LoRA stacks (lora_rank/max_loras) or scan_layers.

    Dispatches on payload format — the breadth Triton's multi-backend repo
    gives the reference (triton_helper.py:159-183):
    - ``*.onnx`` file (or dir containing one) -> ONNX->JAX importer
    - ``*.graphdef`` / ``*.pb`` frozen TF graph (or TF1 SavedModel wrapper)
      -> native GraphDef->JAX importer
    - ``*.pt`` / ``*.torchscript`` TorchScript -> ONNX (in-memory) -> JAX
      (needs the endpoint's input_size/input_type spec for example shapes)
    - otherwise: native jax bundle dir (model_config.json + params.msgpack)
    """
    import jax
    import jax.numpy as jnp
    from flax import serialization
    from .. import models
    from .importers.onnx_import import find_onnx_file, load_onnx_bundle

    path = Path(path)
    # a native bundle dir wins even if a stray .onnx sits next to it (e.g. a
    # converter that kept its source beside the output)
    is_native = path.is_dir() and (path / "model_config.json").exists()
    onnx_file = None if is_native else find_onnx_file(path)
    if onnx_file is not None:
        return load_onnx_bundle(onnx_file)
    if not is_native:
        from .importers.graphdef_import import (
            find_graphdef_file,
            load_graphdef_bundle,
        )

        gd_file = find_graphdef_file(path)
        if gd_file is not None:
            return load_graphdef_bundle(gd_file)
    ts_file = None
    if path.is_file() and path.suffix in (".pt", ".torchscript"):
        ts_file = path
    elif path.is_dir():
        cands = sorted(path.glob("*.pt")) + sorted(path.glob("*.torchscript"))
        if cands and not (path / "model_config.json").exists():
            ts_file = cands[0]
    if ts_file is not None:
        from .importers.torchscript_import import load_torchscript_bundle

        if endpoint is None or not endpoint.input_size:
            raise EndpointModelError(
                "TorchScript model {} needs the endpoint's input_size/"
                "input_type spec to derive export shapes".format(ts_file)
            )
        shapes, dtypes = _endpoint_input_spec(endpoint)
        return load_torchscript_bundle(ts_file, shapes, dtypes)

    if path.is_file():  # single-file bundles not supported; need the dir
        path = path.parent
    meta = read_json(path / "model_config.json")
    if not meta:
        raise EndpointModelError(
            "not a jax model bundle (missing model_config.json): {}".format(path)
        )
    model_cfg = dict(meta.get("config") or {})
    if config_overrides:
        model_cfg.update(config_overrides)
    bundle = models.build_model(meta["arch"], model_cfg)
    params_bytes = (path / "params.msgpack").read_bytes()
    params = serialization.msgpack_restore(bytearray(params_bytes))
    params = jax.tree.map(jnp.asarray, params)
    # architectures may adapt the stored layout to the build (e.g. stacking
    # per-layer dicts for scan_layers)
    prepare = getattr(bundle, "prepare_params", None)
    if prepare is not None:
        params = prepare(params)
    return bundle, params


# -- batching -----------------------------------------------------------------

def bucket_for(batch: int, buckets: List[int]) -> int:
    for b in buckets:
        if batch <= b:
            return b
    return batch  # beyond the largest bucket: compile exactly (rare)


@register_engine("jax", modules=["jax", "flax"])
class JaxEngineRequest(BaseEngineRequest):
    """Serve a jax bundle (or user-loaded callable) on the local TPU devices."""

    def __init__(self, *args, **kwargs):
        enable_persistent_compilation_cache()
        self._apply_fn: Optional[Callable] = None
        self._params = None
        self._jitted: Dict[int, Callable] = {}
        super().__init__(*args, **kwargs)
        aux = self.endpoint.auxiliary_cfg or {}
        if isinstance(aux, str):
            aux = {}
        batching = (aux.get("batching") or {}) if isinstance(aux, dict) else {}
        self._buckets = sorted(int(b) for b in batching.get("buckets", _DEFAULT_BUCKETS))
        self._warmup_done = False

    # -- loading ------------------------------------------------------------

    def _load_model(self) -> None:
        super()._load_model()
        if self._model is not None and callable(self._model):
            # user load() returned fn(*inputs)
            self._apply_fn = self._model
            self._params = None
        elif self._model_local_path:
            bundle, params = load_bundle(self._model_local_path, endpoint=self.endpoint)
            self._apply_fn = bundle.apply
            self._params = params
            self._model = bundle
        else:
            raise EndpointModelError(
                "jax endpoint {!r} has neither a model bundle nor a user load()".format(
                    self.endpoint.serving_url
                )
            )

    def _compiled(self, bucket: int) -> Callable:
        import jax

        fn = self._jitted.get(bucket)
        if fn is None:
            # bind the apply fn as a local: a lambda closing over self would
            # bake the attribute lookup's trace-time value in (TPU201)
            apply_fn = self._apply_fn
            if self._params is not None:
                fn = jax.jit(lambda params, *xs: apply_fn(params, *xs))
            else:
                fn = jax.jit(lambda *xs: apply_fn(*xs))
            self._jitted[bucket] = fn
        return fn

    # -- request IO ---------------------------------------------------------

    def _body_to_arrays(self, data: Any) -> List[np.ndarray]:
        """JSON body -> list of typed input arrays per the endpoint I/O spec.
        Accepts {"name": values, ...} or a bare array for single-input models."""
        names = self.endpoint.input_name or []
        types = self.endpoint.input_type or []
        if isinstance(data, dict) and names:
            raw = []
            for i, name in enumerate(names):
                if name not in data:
                    raise ValueError("missing input {!r}".format(name))
                raw.append(data[name])
        elif isinstance(data, dict) and len(data) == 1:
            raw = [next(iter(data.values()))]
        else:
            raw = [data]
        arrays = []
        for i, r in enumerate(raw):
            dt = np.dtype(types[i]) if i < len(types) else np.float32
            arrays.append(np.asarray(r, dtype=dt))
        return arrays

    def process(self, data: Any, state: dict, collect_fn=None) -> Any:
        if self._preprocess is not None and hasattr(self._preprocess, "process"):
            # User process() is a full override of the compiled path (same
            # delegation contract as the CPU engines / reference triton engine).
            return self._preprocess.process(data, state, collect_fn)
        if isinstance(data, (list, dict)):
            arrays = self._body_to_arrays(data)
        elif isinstance(data, np.ndarray):
            arrays = [data]
        elif isinstance(data, (tuple,)):
            arrays = [np.asarray(a) for a in data]
        else:
            arrays = [np.asarray(data)]

        batch = arrays[0].shape[0] if arrays[0].ndim > 0 else 1
        bucket = bucket_for(batch, self._buckets)
        padded = []
        for a in arrays:
            if a.ndim == 0:
                a = a[None]
            if a.shape[0] != bucket:
                pad = [(0, bucket - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
                a = np.pad(a, pad)
            padded.append(a)
        import jax

        fn = self._compiled(bucket)
        if self._params is not None:
            out = fn(self._params, *padded)
        else:
            out = fn(*padded)
        out = jax.tree.map(lambda t: np.asarray(t)[:batch], out)
        return out

    def postprocess(self, data: Any, state: dict, collect_fn=None) -> Any:
        if self._preprocess is not None and hasattr(self._preprocess, "postprocess"):
            return self._preprocess.postprocess(data, state, collect_fn)
        # numpy -> JSON-friendly (recursive; no jax needed here)
        def _to_list(x):
            if isinstance(x, np.ndarray):
                return x.tolist()
            if isinstance(x, dict):
                return {k: _to_list(v) for k, v in x.items()}
            if isinstance(x, (list, tuple)):
                return [_to_list(v) for v in x]
            return x
        if isinstance(data, (list, tuple)) and len(data) == 1:
            return _to_list(data[0])
        return _to_list(data)
