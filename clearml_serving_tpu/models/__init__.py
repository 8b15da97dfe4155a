"""Model architecture registry for the JAX engine tier.

Each architecture module exposes ``build(config: dict) -> ModelBundle`` with
pure functional ``init`` / ``apply``. Model payloads on disk are "jax bundles":
a directory with ``model_config.json`` ({"arch": ..., "config": {...}}) and a
``params.msgpack`` flax-serialized parameter pytree — the TPU-native analog of
the reference's Triton model-repository folders (triton_helper.py:159-183).
"""

from types import SimpleNamespace
from typing import Any, Callable, Dict

_BUILDERS: Dict[str, Callable[[dict], Any]] = {}


def register_model(name: str):
    def _decorator(fn):
        _BUILDERS[name] = fn
        return fn

    return _decorator


def build_model(arch: str, config: dict) -> SimpleNamespace:
    try:
        builder = _BUILDERS[arch]
    except KeyError:
        raise ValueError(
            "unknown model arch {!r}; registered: {}".format(arch, sorted(_BUILDERS))
        ) from None
    return builder(config or {})


def registered_archs():
    return sorted(_BUILDERS)


from . import mlp  # noqa: E402,F401
from . import cnn  # noqa: E402,F401
from . import bert  # noqa: E402,F401
from . import llama  # noqa: E402,F401
from . import whisper  # noqa: E402,F401
from . import dots3_note  # noqa: E402,F401
from . import afmoe  # noqa: E402,F401
from . import falcon_h1  # noqa: E402,F401
