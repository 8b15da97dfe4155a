"""The Falcon-H1 reference with its recurrent state rounded to bfloat16 after
every token: one precision under the configuration's float32 state. NOT a
yardstick: the second reading that sets ``probes.tolerance`` (the comparison
has to refuse it; PERF.md, PR 51). No configuration under
``benchmark/configs/`` names it; a copy of one, made by hand for that
reading, does (``"reference": "falcon_h1_bf16_state"``)."""

import jax.numpy as jnp

from . import falcon_h1


def forward(model, weights, tokens, positions):
    return falcon_h1.forward(model, weights, tokens, positions,
                             state_dtype=jnp.bfloat16)
