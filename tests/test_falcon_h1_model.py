"""arch falcon_h1 end to end at tiny widths on the CPU: the served path (the
standard paged pools AND the row state in one carry, the XLA twins of the
paged and the SSD kernels) against the plain reference's LOGITS, prompts
taken in several launches and then decoded through both caches, rows of
unequal phase in one launch, the five controls told apart, and the model's
refusals by name. The engine's side is tests/test_hybrid_engine.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.correctness import ServedWeights
from benchmark.reference import falcon_h1 as ref
from clearml_serving_tpu import models

TINY = dict(
    vocab_size=304, dim=64, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=16,
    ffn_dim=96, rope_theta=1e11, norm_eps=1e-5, dtype="float32",
    mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
    mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=128, scan_layers=True,
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    ssm_in_multiplier=0.25, ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804,
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
)
PAGE, PAGES_PER_SEQ, ROWS = 8, 16, 3


def tiny(quant=None, **over):
    cfg = dict(TINY, **over)
    bundle = models.build_model("falcon_h1", cfg)
    return cfg, bundle, bundle.init(jax.random.PRNGKey(1), weight_quant=quant)


class Driver:
    """Launches as the engine builds them, for ROWS batch rows: a ragged
    mixed pass over whatever each row brings (a prompt chunk, one token, or
    nothing), or a decode pass."""

    def __init__(self, bundle, params, controls=None, junk=True):
        self.bundle, self.params = bundle, params
        shape = (bundle.n_layers, bundle.n_kv_heads,
                 ROWS * PAGES_PER_SEQ + 1, PAGE, bundle.head_dim)
        self.k = jnp.zeros(shape, jnp.float32)
        state = bundle.init_state(ROWS)
        if junk:    # what a last owner left: a starting row must not see it
            state = jax.tree.map(lambda a: a + 3.0, state)
        self.v = (jnp.zeros(shape, jnp.float32), state)
        self.table = (1 + jnp.arange(ROWS * PAGES_PER_SEQ, dtype=jnp.int32)
                      ).reshape(ROWS, PAGES_PER_SEQ)
        self.lens = [0] * ROWS
        self.ragged = jax.jit(lambda *a: bundle.forward_ragged(
            *a, controls=controls))
        self.decode = jax.jit(lambda *a, **kw: bundle.decode_paged(
            *a, controls=controls, **kw))

    def _coords(self, row, pos):
        return int(self.table[row][pos // PAGE]), pos % PAGE

    def launch(self, feed, width=56):
        """feed = {row: [tokens]} -> {row: logits at the row's last token}."""
        toks, pos, trow, wp, wo = ([0] * width for _ in range(5))
        valid = [False] * width
        last, kv, starts, lens = ([0] * ROWS for _ in range(4))
        at = 0
        for row in sorted(feed):
            n = len(feed[row])
            starts[row], lens[row] = at, n
            for i, t in enumerate(feed[row]):
                p = self.lens[row] + i
                toks[at + i], pos[at + i], trow[at + i] = int(t), p, row
                valid[at + i] = True
                wp[at + i], wo[at + i] = self._coords(row, p)
            last[row] = at + n - 1
            self.lens[row] += n
            kv[row] = self.lens[row]
            at += n
        assert at <= width
        i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
        valid = jnp.asarray(valid)
        logits, self.k, self.v = self.ragged(
            self.params, i32(toks), i32(pos), i32(trow), valid,
            jnp.where(valid, jnp.arange(width), width), i32(last), self.k,
            self.v, self.table, i32(kv), i32(starts), i32(lens), i32(wp),
            i32(wo))
        return {row: np.asarray(logits[row]) for row in feed}

    def step(self, feed):
        """feed = {row: token}: one decode pass; the other rows idle."""
        toks, lens, wp, wo = ([0] * ROWS for _ in range(4))
        live = [False] * ROWS
        for row, t in feed.items():
            toks[row], lens[row], live[row] = int(t), self.lens[row], True
            wp[row], wo[row] = self._coords(row, self.lens[row])
            self.lens[row] += 1
        i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
        logits, self.k, self.v = self.decode(
            self.params, i32(toks), self.k, self.v, self.table, i32(lens),
            i32(wp), i32(wo), active=jnp.asarray(live))
        return {row: np.asarray(logits[row]) for row in feed}


def serve(bundle, params, prompt, n_new, chunk=24, controls=None, row=1):
    """One sequence: the prompt in launches of ``chunk`` tokens, then greedy
    decode passes. Returns (logits at the positions that produce the new
    tokens, the whole sequence, the driver)."""
    d = Driver(bundle, params, controls)
    for at in range(0, len(prompt), chunk):
        out = d.launch({row: prompt[at:at + chunk]})
    got, seq = [out[row]], list(prompt)
    for _ in range(n_new - 1):
        seq.append(int(np.argmax(got[-1])))
        got.append(d.step({row: seq[-1]})[row])
    return np.stack(got), seq, d


def prompt_of(n, seed=0):
    return list(np.random.RandomState(seed).randint(0, 300, size=n))


def reference(cfg, params, seq, n_prompt, **kw):
    return np.asarray(ref.forward(
        cfg, ServedWeights(params), jnp.asarray(seq, jnp.int32),
        jnp.arange(n_prompt - 1, len(seq)), **kw))


# ------------------------------------------- served path against reference

@pytest.mark.parametrize("n_prompt, chunk", [
    (3, 24),     # shorter than the convolution's window
    (24, 24),    # one launch
    (90, 24),    # four launches, the last a part
    (49, 16),    # the last launch brings ONE token: the update takes it
    (30, 1),     # token by token: only the update kernel's twin
], ids=["three", "one_launch", "four_launches", "one_token_tail", "by_token"])
def test_prefill_then_decode_gives_the_references_logits(n_prompt, chunk):
    cfg, bundle, params = tiny()
    prompt = prompt_of(n_prompt)
    got, seq, _ = serve(bundle, params, prompt, 5, chunk=chunk)
    np.testing.assert_allclose(
        got, reference(cfg, params, seq, n_prompt), atol=3e-4)


def test_the_unrolled_layers_give_the_same_logits():
    cfg, bundle, params = tiny(scan_layers=False)
    prompt = prompt_of(40, seed=2)
    got, seq, _ = serve(bundle, params, prompt, 3)
    np.testing.assert_allclose(
        got, reference(cfg, params, seq, len(prompt)), atol=3e-4)


def test_rows_of_unequal_phase_share_a_launch():
    """Row 0 decodes while row 2 starts its prompt and row 1 goes on with
    its own; a slot that changes hands is counted as zero: every stream is
    the reference's, and an idle row's state comes back bit for bit."""
    cfg, bundle, params = tiny()
    a, b, c = prompt_of(20, 1), prompt_of(50, 2), prompt_of(33, 3)
    d = Driver(bundle, params)
    out = d.launch({0: a, 1: b[:10]})
    seq_a = a + [int(np.argmax(out[0]))]
    logits_a = [out[0]]
    out = d.launch({0: [seq_a[-1]], 1: b[10:30], 2: c[:5]})
    logits_a.append(out[0])
    seq_a.append(int(np.argmax(out[0])))
    idle = jax.tree.map(lambda x: np.asarray(x[:, 0]), d.v[1])
    out = d.launch({1: b[30:], 2: c[5:]})
    for plane, was in idle.items():
        assert np.array_equal(np.asarray(d.v[1][plane][:, 0]), was)
    seq_b, seq_c = b + [int(np.argmax(out[1]))], c + [int(np.argmax(out[2]))]
    logits_b, logits_c = [out[1]], [out[2]]
    out = d.step({0: seq_a[-1], 1: seq_b[-1], 2: seq_c[-1]})
    logits_a.append(out[0]), logits_b.append(out[1]), logits_c.append(out[2])
    for seq, n, got in ((seq_a, 20, logits_a), (seq_b, 50, logits_b),
                        (seq_c, 33, logits_c)):
        want = reference(cfg, params, seq, n)
        np.testing.assert_allclose(np.stack(got), want[:len(got)], atol=3e-4)
    # row 0's request ends; another takes the row from position 0
    d.lens[0] = 0
    e = prompt_of(12, 4)
    out = d.launch({0: e})
    np.testing.assert_allclose(
        out[0], reference(cfg, params, e, 12)[0], atol=3e-4)


CONTROLS = {
    "multipliers_off": dict(multipliers=False),
    "gate_after_norm": dict(gate_after_norm=True),
    "state_bfloat16": dict(round_state=True),
    "attention_dropped": dict(drop_attention=True),
    "mixer_dropped": dict(drop_mixer=True),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_fails_the_comparison(control):
    """A mechanism switched in the served path alone is far from the
    reference: the comparison would refuse it."""
    cfg, bundle, params = tiny()
    prompt = prompt_of(60, seed=4)
    got, seq, _ = serve(bundle, params, prompt, 3)
    exact = reference(cfg, params, seq, len(prompt))
    assert np.abs(got - exact).max() < 3e-4
    changed, _, _ = serve(bundle, params, prompt, 1,
                          controls=CONTROLS[control])
    # a rounded state is a matter of precision, not of kind: told apart by
    # how far the float32 path lies from the reference
    floor = (5 * np.abs(got[0] - exact[0]).max()
             if control == "state_bfloat16" else 0.02)
    assert np.abs(changed[0] - exact[0]).max() > floor


def test_the_references_bfloat16_state_is_told_apart():
    """The tolerance's second reading: the REFERENCE with its state rounded
    to bfloat16 after every token is further from the served path than the
    float32 one, by an order of magnitude."""
    cfg, bundle, params = tiny()
    prompt = prompt_of(80, seed=5)
    got, seq, _ = serve(bundle, params, prompt, 3)
    exact = reference(cfg, params, seq, len(prompt))
    rounded = reference(cfg, params, seq, len(prompt),
                        state_dtype=jnp.bfloat16)
    assert np.abs(got - exact).max() * 10 < np.abs(got - rounded).max()


def test_int8_weights_stay_close_to_the_float_ones():
    cfg, bundle, params = tiny()
    _, _, packed = tiny(quant="int8")
    assert set(packed["layers"]["w_in"]) == {"_q8", "_scale"}
    prompt = prompt_of(48, seed=6)
    got, seq, _ = serve(bundle, packed, prompt, 3)
    np.testing.assert_allclose(
        got, reference(cfg, packed, seq, len(prompt)), atol=5e-4)


def test_the_branches_come_out_at_unit_scale():
    """Random weights undo the multipliers: each branch of a block adds
    about as much as the others, so the comparison sees all three."""
    cfg, bundle, params = tiny()
    prompt = prompt_of(64, seed=7)
    exact = serve(bundle, params, prompt, 1)[0][0]
    for control in ("attention_dropped", "mixer_dropped"):
        changed = serve(bundle, params, prompt, 1,
                        controls=CONTROLS[control])[0][0]
        assert 0.05 < np.abs(changed - exact).std() / exact.std() < 3.0


# --------------------------------------------------------------- refusals

REFUSALS = {
    "kv_quant": (dict(kv_quant="int8"), "kv_quant cannot serve arch falcon_h1"),
    "lora": (dict(lora_rank=8), "lora adapters are not served"),
    "tied": (dict(tie_embeddings=True), "untied"),
    "norm_before_gate": (dict(mamba_norm_before_gate=True), "gated FIRST"),
    "heads": (dict(mamba_n_heads=3), "must give mamba_d_ssm"),
    "bias": (dict(attention_bias=True), "attention_bias must be false"),
    "act": (dict(hidden_act="gelu"), "must be 'silu'"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_what_the_model_cannot_do_is_refused_by_name(case):
    over, message = REFUSALS[case]
    with pytest.raises(ValueError, match=message):
        tiny(**over)


def test_other_rows_and_the_dense_surface_are_refused_by_name():
    cfg, bundle, params = tiny()
    d = Driver(bundle, params)
    with pytest.raises(ValueError, match="plain rows only"):
        bundle.forward_ragged(params, *[None] * 16, lora_idx=jnp.zeros(3))
    with pytest.raises(ValueError, match="row state beside the V pool"):
        bundle.decode_paged(params, jnp.zeros(3, jnp.int32), d.k, d.v[0],
                            d.table, *[jnp.zeros(3, jnp.int32)] * 3)
    with pytest.raises(ValueError, match="engine.cache=paged only"):
        bundle.init_cache(2, 64)
    with pytest.raises(ValueError, match="weight_quant 'int8' or none"):
        bundle.init(jax.random.PRNGKey(0), weight_quant="int4")
