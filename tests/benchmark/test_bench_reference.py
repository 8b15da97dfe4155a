"""The plain references against the program's own full forward pass, at tiny
widths in float32 on the CPU, and the logprob comparison that decides
``correct``."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import correctness as cx  # noqa: E402
from benchmark.sut import load_config, model_block  # noqa: E402

TINY = Path(__file__).resolve().parent / "tiny"


@pytest.mark.parametrize("name,quant", [
    ("tiny-dense", None), ("tiny-dense", "int8"),
    ("tiny-moe", None), ("tiny-moe", "int8"),
])
def test_reference_agrees_with_the_programs_full_forward(name, quant):
    import jax
    import jax.numpy as jnp

    from clearml_serving_tpu import models

    cfg = load_config(TINY / (name + ".json"))
    # the program's full forward drops tokens over an expert's capacity; the
    # served (ragged) path and the reference do not: give it room for all
    model = dict(model_block(cfg), dtype="float32", moe_capacity_factor=8.0)
    bundle = models.build_model("llama", model)
    params = bundle.init(jax.random.PRNGKey(3), weight_quant=quant) if quant \
        else bundle.init(jax.random.PRNGKey(3))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 512, 24), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = bundle.apply(params, tokens[None])[0]
    weights = cx.ServedWeights(params)
    ref = cx.reference_logprobs(cfg["reference"], model, weights,
                                list(map(int, tokens[:20])), list(map(int, tokens[20:])) + [0])
    want_lp = jax.nn.log_softmax(want[19:24].astype(jnp.float32), axis=-1)
    # float32 on both sides: only the order of summation differs
    assert np.max(np.abs(np.asarray(ref) - np.asarray(want_lp))) < 2e-4


def test_compare_probe_reads_the_reported_ids():
    ref = np.log(np.full((2, 8), 1 / 8))
    probe = {"tops": [{1: float(ref[0, 1]), 5: float(ref[0, 5]) + 0.03},
                      {2: float(ref[1, 2]) - 0.01}]}
    positions = cx.compare_probe(ref, probe)
    assert positions == pytest.approx([(0.03 ** 2 / 2) ** 0.5, 0.01])
    probe["tops"][1][3] = float("nan")
    assert cx.compare_probe(ref, probe)[1] == float("inf")


@pytest.mark.parametrize("positions,tol,within", [
    ([0.01, 0.02, 0.03], {"typical": 0.04, "outlier": 0.15, "outlier_share": 0.0}, True),
    ([0.05, 0.06, 0.07], {"typical": 0.04, "outlier": 0.15, "outlier_share": 0.0}, False),
    ([0.01, 0.02, 0.4], {"typical": 0.04, "outlier": 0.15, "outlier_share": 0.0}, False),
    ([0.01, 0.02, 0.4], {"typical": 0.04, "outlier": 0.15, "outlier_share": 0.34}, True),
    ([0.01, float("inf")], {"typical": 0.04, "outlier": 0.15, "outlier_share": 0.0}, False),
    ([], {"typical": 0.04, "outlier": 0.15, "outlier_share": 0.0}, False),
])
def test_verdict(positions, tol, within):
    assert cx.verdict(positions, tol)["within"] is within


def test_parse_probe_reads_token_ids():
    payload = {"choices": [{"logprobs": {
        "tokens": ["token_id:7", "token_id:300"],
        "top_logprobs": [{"token_id:7": -0.1, "token_id:9": -2.5},
                         {"token_id:300": -0.2}]}}], "usage": {"completion_tokens": 2}}
    got = cx.parse_probe(payload)
    assert got["ids"] == [7, 300] and got["tops"][0] == {7: -0.1, 9: -2.5}


def test_probe_set_is_seeded_and_avoids_the_specials():
    a, b = cx.probe_set(2 ** 31 + 5, 512, [30, 60]), cx.probe_set(2 ** 31 + 5, 512, [30, 60])
    assert a == b and [len(x) for x in a] == [30, 60]
    assert not {256, 257, 258} & set(a[0] + a[1])
    assert a != cx.probe_set(6, 512, [30, 60])


@pytest.mark.parametrize("before,after,tpu,n_faults", [
    ({"warmup": 5, "serve": 0}, {"warmup": 5, "serve": 0}, False, 0),
    ({"warmup": 5, "serve": 0}, {"warmup": 5, "serve": 2}, False, 1),
    ({"warmup": 5, "serve": 0}, {"warmup": 5, "serve": 0}, True, 1),
])
def test_health_checks(before, after, tpu, n_faults):
    def health(compile_block):
        return {"compile": compile_block, "watchdog_trips": 0, "step_failures": 0,
                "kernels": {"decode": "xla", "ragged": "xla", "reason": {}}}

    assert len(cx.health_checks(health(before), health(after), tpu)) == n_faults
