"""The comparison that decides ``correct``: the served path against the plain
float32 reference, and the checks on the program's own health block.

Probes go through the OpenAI route like any request (``/v1/completions`` with
token-id prompts, greedy, ``logprobs: 20``), alone in the engine, twice. The
answers must agree id for id between the two sends, and each reported
log-probability must agree with the reference's log-softmax at the same
token id: the first generated position checks the (chunked) prefill, the
later ones decoding through the paged cache.

Tolerance. Both sides hold the same int8 weights. The reference multiplies
scale * q in float32 at the highest precision; the system rounds the
dequantised weights and every activation to bfloat16 (8 bits of mantissa,
relative error 2**-9 per rounding) and accumulates in float32. Through
2 * n_layers residual additions the roundings add like a random walk, so the
difference grows with the square root of the depth.

What is judged is the root mean square difference per compared position
(its top-20 log-probabilities), so that one number stands for one token of
the model's output. The median over the positions is the arithmetic's
precision: steady from run to run, and a lower precision than the
configuration states raises it in proportion, so it is held to about twice
what bfloat16 measured (``probes.tolerance.typical`` in the configuration
file, with the measurement beside it). A position far off that (over
``outlier``) is either a fault or, in an expert model only, a token whose
second and third expert are a rounding apart and swap between the two
precisions: both answers are then right and differ much. ``outlier_share``
is the share of positions that may be so: 0 for a dense model.
"""

from __future__ import annotations

import importlib
import math
import random

TOP_K = 20


def probe_set(seed: int, vocab: int, sizes) -> list:
    """Seeded token-id prompts (ids over the whole vocabulary but the
    byte tokenizer's three specials)."""
    rng = random.Random(seed * 7919 + 13)
    out = []
    for n in sizes:
        ids = []
        while len(ids) < n:
            t = rng.randrange(vocab)
            if t not in (256, 257, 258):
                ids.append(t)
        out.append(ids)
    return out


def probe_body(model: str, ids: list, n_new: int) -> dict:
    # ``seed`` changes nothing under greedy decoding; it puts the probe on
    # the launch program the population uses (the one with sampling extras),
    # so that the reference checks that program and set-up compiles no other
    return {"model": model, "prompt": ids, "max_tokens": n_new,
            "temperature": 0, "seed": 0, "logprobs": TOP_K,
            "return_tokens_as_token_ids": True}


def parse_probe(payload: dict) -> dict:
    """Generated ids and, per position, {token id: logprob} of the top-k."""
    lp = payload["choices"][0]["logprobs"]
    ids = [int(t.split(":", 1)[1]) for t in lp["tokens"]]
    tops = [
        {int(k.split(":", 1)[1]): float(v) for k, v in top.items()}
        for top in lp["top_logprobs"]
    ]
    return {"ids": ids, "tops": tops,
            "usage": payload.get("usage") or {}}


class ServedWeights:
    """The weights the engine serves, as a reference takes them. ``embed(ids)``
    and ``final_norm`` are float32; ``lm_head`` and what ``view`` gives are
    served leaves, which the pure function ``f32`` turns into float32
    (scale * q for an int8 leaf) where they are used, inside the reference's
    jitted step, so that no float32 copy of a whole layer ever exists: the
    reference must not set the run's memory peak."""

    def __init__(self, params):
        import jax.numpy as jnp

        self._p = params
        self._stacked = isinstance(params["layers"], dict)
        self.final_norm = params["final_norm"].astype(jnp.float32)
        self.lm_head = params.get("lm_head")
        if self.lm_head is None:
            self.lm_head = params["embed"].T

    def embed(self, tokens):
        import jax.numpy as jnp

        return self._p["embed"][tokens].astype(jnp.float32)

    @staticmethod
    def f32(leaf):
        import jax.numpy as jnp

        if isinstance(leaf, dict):
            if "_q8" not in leaf:
                raise ValueError("only int8 and plain leaves are served here: "
                                 "{}".format(sorted(leaf)))
            return leaf["_q8"].astype(jnp.float32) * leaf["_scale"].astype(jnp.float32)
        return leaf.astype(jnp.float32)

    def layer_args(self, i: int):
        """What to hand the jitted step for layer ``i``: the stacked tree and
        the index (one compiled step serves every layer), or that layer."""
        import jax.numpy as jnp

        layers = self._p["layers"]
        return (layers, jnp.int32(i)) if self._stacked else (layers[i], None)

    @staticmethod
    def view(layers, i):
        return LayerView(layers, i)


class LayerView:
    """One layer's served leaves, sliced where they are asked for: ``w[name]``
    a whole leaf of the layer, ``w.expert(name, e)`` one expert of a stack."""

    def __init__(self, layers, i):
        self._layers, self._i = layers, i

    def _cut(self, leaf, *index):
        import jax

        index = tuple(x for x in index if x is not None)
        return jax.tree_util.tree_map(lambda a: a[index], leaf) if index else leaf

    def __getitem__(self, name):
        return self._cut(self._layers[name], self._i)

    def expert(self, name, e):
        return self._cut(self._layers[name], self._i, e)


def reference_logprobs(reference: str, model: dict, weights, prompt: list,
                       generated: list):
    """log-softmax of the reference's logits at the positions that produced
    ``generated``, from one full causal pass over prompt + generated."""
    import jax
    import jax.numpy as jnp

    module = importlib.import_module("benchmark.reference." + reference)
    tokens = jnp.asarray(list(prompt) + list(generated[:-1]), jnp.int32)
    positions = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(generated))
    logits = module.forward(model, weights, tokens, positions)
    return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)


def compare_probe(ref_lp, probe: dict) -> list:
    """Per compared position, the root mean square of |served - reference|
    over its reported top-k logprobs."""
    import numpy as np

    ref = np.asarray(ref_lp)
    out = []
    for pos, top in enumerate(probe["tops"]):
        sq = [(lp - float(ref[pos, tok])) ** 2 if math.isfinite(lp) else float("inf")
              for tok, lp in top.items()]
        if sq:
            out.append(math.sqrt(sum(sq) / len(sq)))
    return out


def verdict(positions: list, tolerance: dict) -> dict:
    """The median position against ``typical``, and the share of positions
    over ``outlier`` against ``outlier_share``."""
    from benchmark.reduce import percentile

    if not positions:
        return {"within": False, "positions": 0}
    typical = percentile(positions, 0.5)
    share = sum(1 for x in positions if x > tolerance["outlier"]) / len(positions)
    return {
        "typical_position_rms": typical, "p90_position_rms": percentile(positions, 0.9),
        "worst_position_rms": max(positions), "outlier_share": share,
        "positions": len(positions), "tolerance": {
            k: tolerance[k] for k in ("typical", "outlier", "outlier_share")},
        "within": typical <= tolerance["typical"]
        and share <= tolerance["outlier_share"] + 1e-9,
    }


def compiles(health: dict) -> int:
    """Programs the engine has compiled so far, by its own health block."""
    return health["compile"]["warmup"] + health["compile"]["serve"]


def health_checks(before: dict, after: dict, want_tpu: bool) -> list:
    """Faults the program's own health block shows across the window."""
    faults = []
    if compiles(after) != compiles(before):
        faults.append("{} programs compiled inside the window".format(
            compiles(after) - compiles(before)))
    for key in ("watchdog_trips", "step_failures"):
        if after.get(key):
            faults.append("{} = {}".format(key, after[key]))
    kernels = after.get("kernels") or {}
    if want_tpu and not (kernels.get("decode") == "pallas"
                         and kernels.get("ragged") == "pallas"):
        faults.append("attention kernels {}".format(
            {k: kernels.get(k) for k in ("decode", "ragged", "reason")}))
    return faults
