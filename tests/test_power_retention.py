"""ops/power_retention.py: the recurrent form against the attention form, the
chunk form against both, what a launch must leave untouched, and each Pallas
kernel (interpret mode) against its XLA twin. Tiny sizes, float32, CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from clearml_serving_tpu.ops import power_retention as pr

B, HKV, G, L = 3, 2, 3, 2


def _draw(d, s, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, s, HKV, G, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, s, HKV, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, s, HKV, d)), jnp.float32)
    log_g = jax.nn.log_sigmoid(
        jnp.asarray(rng.normal(size=(B, s, HKV)) + 1.0, jnp.float32))
    return q, k, v, log_g


def _dirty_pools(d, fill=7.0):
    s_shape, z_shape = pr.state_shapes(L, B, HKV, d)
    return jnp.full(s_shape, fill, jnp.float32), jnp.full(z_shape, fill, jnp.float32)


def _update_fns():
    return {
        "xla": pr.power_retention_update_xla,
        "kernel": lambda *a, **kw: pr.power_retention_update(*a, interpret=True, **kw),
    }


def _chunk_fns():
    return {
        "xla": pr.power_retention_chunk_xla,
        "kernel": lambda *a, **kw: pr.power_retention_chunk(
            *a, interpret=True, chunk=8, **kw),
    }


def _run_chunks(fn, data, cuts, layer=0):
    """Feed rows their sequences in the launches ``cuts`` (per launch, tokens
    per row) through the flat ragged layout; returns y per row and the pools."""
    q, k, v, log_g = data
    d, s = q.shape[-1], q.shape[1]
    s_pool, z_pool = _dirty_pools(d)
    out = np.zeros(q.shape, np.float32)
    pos = [0] * B
    for lens in cuts:
        lens = np.asarray(lens, np.int32)
        starts, off = np.zeros(B, np.int32), 0
        for b in range(B):
            starts[b] = off
            off += -(-int(lens[b]) // 8) * 8
        t = max(off, 8) + 8
        flat = [np.zeros((t,) + a.shape[2:], np.float32) for a in (q, k, v, log_g)]
        for b in range(B):
            for dst, src in zip(flat, (q, k, v, log_g)):
                dst[starts[b]:starts[b] + lens[b]] = src[b, pos[b]:pos[b] + lens[b]]
        y, s_pool, z_pool = fn(
            *map(jnp.asarray, flat), jnp.asarray(starts), jnp.asarray(lens),
            jnp.asarray(lens > 0), jnp.asarray([p == 0 for p in pos]),
            s_pool, z_pool, layer=layer)
        for b in range(B):
            out[b, pos[b]:pos[b] + lens[b]] = np.asarray(y)[starts[b]:starts[b] + lens[b]]
            pos[b] += int(lens[b])
    assert pos == [s] * B, "the cuts must cover every sequence"
    return out, s_pool, z_pool


@pytest.mark.parametrize("d", [2, 8, 16])
def test_the_diagonal_features_give_the_squared_dot_product(d):
    rng = np.random.default_rng(d)
    x, y = (jnp.asarray(rng.normal(size=(5, d)), jnp.float32) for _ in range(2))
    w = jnp.asarray(pr.shift_weights(d), jnp.float32)[:, None]
    got = jnp.sum(pr.features(x) * w * pr.features(y), axis=(-1, -2))
    assert np.allclose(got, jnp.sum(x * y, -1) ** 2, rtol=1e-5, atol=1e-5)
    assert pr.state_rows(128) == 8320 and pr.z_rows(128) == 72
    assert pr.state_shapes(12, 16, 8, 128) == ((12, 16, 8, 128, 8320), (12, 16, 8, 72, 128))


@pytest.mark.parametrize("impl", ["xla", "kernel"])
@pytest.mark.parametrize("d", [8, 16])
def test_recurrent_form_equals_attention_form(impl, d):
    q, k, v, log_g = _draw(d, 19)
    want = pr.power_retention_attention(q, k, v, log_g)
    update = _update_fns()[impl]
    s_pool, z_pool = _dirty_pools(d)
    active = jnp.asarray([True, False, True])
    ys = []
    for t in range(q.shape[1]):
        y, s_pool, z_pool = update(
            q[:, t], k[:, t], v[:, t], log_g[:, t], active,
            jnp.asarray([t == 0] * B), s_pool, z_pool, layer=1)
        ys.append(y)
    got = jnp.stack(ys, 1)
    assert np.allclose(got[0], want[0], atol=2e-4) and np.allclose(got[2], want[2], atol=2e-4)
    # the idle row read nothing and its slot is bit for bit what it was; so
    # is the layer that was not named
    assert not np.asarray(got[1]).any()
    assert (np.asarray(s_pool[1, 1]) == 7.0).all() and (np.asarray(z_pool[1, 1]) == 7.0).all()
    assert (np.asarray(s_pool[0]) == 7.0).all() and (np.asarray(z_pool[0]) == 7.0).all()


CUTS = {
    "one_chunk": [[21, 21, 21]],
    "uneven": [[21, 5, 0], [0, 16, 7], [0, 0, 14]],
    "token_by_token_rows_mixed": [[2, 9, 3]] + [[1, 1, 1]] * 12 + [[7, 0, 6]],
}


@pytest.mark.parametrize("impl", ["xla", "kernel"])
@pytest.mark.parametrize("cuts", sorted(CUTS))
def test_chunk_form_equals_attention_form_however_it_is_cut(impl, cuts):
    data = _draw(16, 21, seed=3)
    want = pr.power_retention_attention(*data)
    got, s_pool, _ = _run_chunks(_chunk_fns()[impl], data, CUTS[cuts])
    assert np.abs(got - np.asarray(want)).max() < 2e-5
    assert (np.asarray(s_pool[1]) == 7.0).all()     # the other layer


def test_chunk_and_update_leave_the_same_state():
    data = _draw(16, 13, seed=5)
    _, s_chunk, z_chunk = _run_chunks(pr.power_retention_chunk_xla, data, [[13] * B])
    q, k, v, log_g = data
    s_pool, z_pool = _dirty_pools(16)
    for t in range(13):
        _, s_pool, z_pool = pr.power_retention_update_xla(
            q[:, t], k[:, t], v[:, t], log_g[:, t], jnp.ones(B, bool),
            jnp.asarray([t == 0] * B), s_pool, z_pool, layer=0)
    nd = pr.n_shifts(16)
    assert np.allclose(s_chunk[0], s_pool[0], rtol=1e-4, atol=1e-5)
    assert np.allclose(z_chunk[0][:, :, :nd], z_pool[0][:, :, :nd], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_a_launch_with_nothing_to_do_changes_no_bit(impl):
    d = 16
    rng = np.random.default_rng(9)
    s_shape, z_shape = pr.state_shapes(L, B, HKV, d)
    s_pool = jnp.asarray(rng.normal(size=s_shape), jnp.float32)
    z_pool = jnp.asarray(rng.normal(size=z_shape), jnp.float32)
    q, k, v, log_g = _draw(d, 1)
    none = jnp.zeros(B, bool)
    _, s1, z1 = _update_fns()[impl](q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
                                    none, none, s_pool, z_pool, layer=0)
    flat = [jnp.zeros((16,) + a.shape[2:], jnp.float32) for a in (q, k, v, log_g)]
    _, s2, z2 = _chunk_fns()[impl](*flat, jnp.zeros(B, jnp.int32),
                                   jnp.asarray([5, 0, 3]), none, none,
                                   s_pool, z_pool, layer=1)
    for got, want in ((s1, s_pool), (z1, z_pool), (s2, s_pool), (z2, z_pool)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_a_reused_slot_carries_nothing_over(impl):
    data = _draw(16, 9, seed=11)
    clean, _, _ = _run_chunks(_chunk_fns()[impl], data, [[9] * B])
    # the same sequences after other ones lived in the slots
    other = _draw(16, 9, seed=12)
    fn = _chunk_fns()[impl]
    q, k, v, log_g = other
    s_pool, z_pool = _dirty_pools(16, fill=3.0)
    lens, starts = jnp.asarray([9] * B), jnp.asarray([0, 16, 32])

    def flat(a):
        out = np.zeros((48,) + a.shape[2:], np.float32)
        for b in range(B):
            out[16 * b:16 * b + 9] = a[b]
        return jnp.asarray(out)

    _, s_pool, z_pool = fn(*map(flat, other), starts, lens, jnp.ones(B, bool),
                           jnp.ones(B, bool), s_pool, z_pool, layer=0)
    y, _, _ = fn(*map(flat, data), starts, lens, jnp.ones(B, bool),
                 jnp.ones(B, bool), s_pool, z_pool, layer=0)
    for b in range(B):
        # 3.0 in every entry of the state would move y by whole units
        assert np.allclose(np.asarray(y)[16 * b:16 * b + 9], clean[b], atol=1e-5)


@pytest.mark.parametrize("active,amap,mode", [
    ([1, 0, 1, 0], [0, 0, 2, 2], [1, 0, 2, 0]),
    ([0, 0, 1, 1], [2, 2, 2, 3], [0, 0, 2, 1]),
    ([0, 0, 0, 0], [0, 0, 0, 0], [3, 0, 0, 0]),
])
def test_row_plan_names_a_resident_block_for_every_skipped_row(active, amap, mode):
    reset = jnp.asarray([False, False, True, False])
    got_map, got_mode = pr._row_plan(jnp.asarray(active, bool), reset)
    assert got_map.tolist() == amap and got_mode.tolist() == mode


def test_kernel_routing_is_one_pure_decision():
    assert pr.retention_kernel_unsupported_reason(128, platform="tpu") is None
    assert "128-lane" in pr.retention_kernel_unsupported_reason(64, platform="tpu")
    assert "TPU only" in pr.retention_kernel_unsupported_reason(128, platform="cpu")
    q = jnp.zeros((1, 1, 1, 128))
    with pytest.raises(ValueError, match="power_retention_update"):
        pr.power_retention_update(q, q[:, :, 0], q[:, :, 0], jnp.zeros((1, 1)),
                                  jnp.ones(1, bool), jnp.zeros(1, bool),
                                  *(jnp.zeros(s) for s in pr.state_shapes(1, 1, 1, 128)))
