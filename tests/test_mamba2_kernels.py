"""ops/mamba2.py: the chunked SSD form against the token-by-token recurrence
under different cuts of one sequence and mixes of reset / continuing rows,
and the Pallas kernels (interpret mode) against their XLA twins."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from clearml_serving_tpu.ops import mamba2

H, G, P, N = 16, 2, 128, 128      # the kernels' tiles: P = 128, N % 128 == 0
B = 4                             # batch rows (+ the null slot)


def _draw(seed, t):
    r = np.random.default_rng(seed)
    x = r.standard_normal((t, H, P)).astype(np.float32)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(2e-1), (t, H))).astype(np.float32)
    a = -r.uniform(1.0, 16.0, (H,)).astype(np.float32)
    bm = r.standard_normal((t, G, N)).astype(np.float32) * 0.5
    cm = r.standard_normal((t, G, N)).astype(np.float32) * 0.5
    return x, dt, a, bm, cm


def _recurrence(x, dt, a, bm, cm, h0=None):
    """The plain scan over tokens of ONE sequence: (y [T, H, P], h)."""
    t = x.shape[0]
    h = np.zeros((H, N, P), np.float64) if h0 is None else h0.astype(np.float64)
    per = H // G
    ys = []
    for i in range(t):
        bh = np.repeat(bm[i], per, axis=0).astype(np.float64)   # [H, N]
        ch = np.repeat(cm[i], per, axis=0).astype(np.float64)
        decay = np.exp(dt[i].astype(np.float64) * a)[:, None, None]
        h = decay * h + bh[:, :, None] * (dt[i][:, None] * x[i])[:, None, :]
        ys.append(np.einsum("hnp,hn->hp", h, ch))
    return np.stack(ys), h


def _launch(chunks, pool, reset, kernel):
    """One launch: ``chunks`` = {row: (x, dt, a, bm, cm) slices}. Rows with
    one token go through the update, the others through the chunk."""
    t_pad = 32
    dtx = np.zeros((t_pad, H, P), np.float32)
    ld = np.zeros((t_pad, H), np.float32)
    bm = np.zeros((t_pad, G, N), np.float32)
    cm = np.zeros((t_pad, G, N), np.float32)
    tok_row = np.zeros(t_pad, np.int32)
    in_chunk = np.zeros(t_pad, bool)
    is_chunk = np.zeros(B, bool)
    is_one = np.zeros(B, bool)
    first = {}
    at = 0
    for row, (x, dt, _a, b_, c_) in chunks.items():
        n = x.shape[0]
        first[row] = (at, n)
        dtx[at:at + n] = dt[:, :, None] * x
        ld[at:at + n] = dt * _a      # (A is the model's; a test row brings its own)
        bm[at:at + n], cm[at:at + n] = b_, c_
        tok_row[at:at + n] = row
        if n > 1:
            in_chunk[at:at + n] = True
            is_chunk[row] = True
        else:
            is_one[row] = True
        at += n
    assert at <= t_pad
    kw = dict(layer=0, interpret=True) if kernel else dict(layer=0)
    chunk = mamba2.mamba2_ssd_chunk if kernel else mamba2.mamba2_ssd_chunk_xla
    update = mamba2.mamba2_ssd_update if kernel else mamba2.mamba2_ssd_update_xla
    rows, count = mamba2.compact_rows(jnp.asarray(is_chunk))
    y, pool = chunk(jnp.asarray(dtx), jnp.asarray(ld), jnp.asarray(bm),
                    jnp.asarray(cm), jnp.asarray(tok_row),
                    jnp.asarray(in_chunk), rows, count, jnp.asarray(reset),
                    pool, **kw)
    last = np.array([first[r][0] if r in first else 0 for r in range(B)])
    rows, count = mamba2.compact_rows(jnp.asarray(is_one))
    y1, pool = update(jnp.asarray(dtx[last]), jnp.asarray(np.exp(ld[last])),
                      jnp.asarray(bm[last]), jnp.asarray(cm[last]), rows,
                      count, jnp.asarray(reset), pool, **kw)
    y, y1 = np.asarray(y), np.asarray(y1)
    out = {}
    for row, (s, n) in first.items():
        out[row] = y[s:s + n] if n > 1 else y1[row][None]
    return out, pool


def _fresh_pool(seed=0):
    # junk in every slot: a reset must count it as zero
    r = np.random.default_rng(100 + seed)
    return jnp.asarray(r.standard_normal(
        mamba2.state_shape(1, B, H, N, P)).astype(np.float32))


CUTS = {
    "whole": [24],
    "halves": [12, 12],
    "uneven": [1, 7, 16],
    "ones_between": [5, 1, 1, 17],
    "token_by_token": [1] * 6,
}


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("cut", sorted(CUTS))
def test_cuts_of_one_sequence_give_the_recurrence(cut, kernel):
    sizes = CUTS[cut]
    x, dt, a, bm, cm = _draw(3, sum(sizes))
    want_y, want_h = _recurrence(x, dt, a, bm, cm)
    pool = _fresh_pool()
    got, at = [], 0
    for k, n in enumerate(sizes):
        sl = slice(at, at + n)
        reset = np.zeros(B, bool)
        reset[2] = k == 0
        out, pool = _launch({2: (x[sl], dt[sl], a, bm[sl], cm[sl])}, pool,
                            reset, kernel)
        got.append(out[2])
        at += n
    np.testing.assert_allclose(np.concatenate(got), want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pool[0, 2]), want_h, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_rows_of_a_launch_do_not_see_each_other(kernel):
    """Three rows in one launch: one starts (reset), one continues with a
    chunk, one continues with a single token; the fourth is idle and its
    slot, like the null slot's neighbours, comes back bit for bit."""
    seqs = {r: _draw(10 + r, 20) for r in (0, 1, 3)}
    pool = _fresh_pool(1)
    before = np.asarray(pool)
    # first launch: rows 1 and 3 take 8 tokens each, both from position 0
    reset = np.array([False, True, False, True])
    cut = lambda r, s: tuple(  # noqa: E731
        v[s] if v.ndim > 1 else v for v in seqs[r])
    out_a, pool = _launch(
        {1: cut(1, slice(0, 8)), 3: cut(3, slice(0, 8))}, pool, reset, kernel)
    # second: row 0 starts with 12, row 1 goes on with 11, row 3 with 1
    reset = np.array([True, False, False, False])
    out_b, pool = _launch(
        {0: cut(0, slice(0, 12)), 1: cut(1, slice(8, 19)),
         3: cut(3, slice(8, 9))}, pool, reset, kernel)
    for row, pieces, n in ((0, [out_b[0]], 12), (1, [out_a[1], out_b[1]], 19),
                           (3, [out_a[3], out_b[3]], 9)):
        x, dt, a, bm, cm = seqs[row]
        want_y, want_h = _recurrence(x[:n], dt[:n], a, bm[:n], cm[:n])
        np.testing.assert_allclose(
            np.concatenate(pieces), want_y, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(pool[0, row]), want_h, rtol=2e-4, atol=2e-4)
    assert np.array_equal(np.asarray(pool[0, 2]), before[0, 2])


def test_kernels_agree_with_their_twins_on_an_empty_launch():
    """No row advances: both kernels run their idle steps on the null slot
    and every real slot comes back bit for bit."""
    pool = _fresh_pool(2)
    before = np.asarray(pool)
    none = jnp.zeros((B,), bool)
    rows, count = mamba2.compact_rows(none)
    z = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
    y, pool = mamba2.mamba2_ssd_chunk(
        z(16, H, P), z(16, H), z(16, G, N), z(16, G, N),
        jnp.zeros((16,), jnp.int32), jnp.zeros((16,), bool), rows, count,
        none, pool, layer=0, interpret=True)
    assert not np.asarray(y).any()
    y, pool = mamba2.mamba2_ssd_update(
        z(B, H, P), jnp.ones((B, H)), z(B, G, N), z(B, G, N), rows, count,
        none, pool, layer=0, interpret=True)
    assert not np.asarray(y).any()
    assert np.array_equal(np.asarray(pool[:, :B]), before[:, :B])


def test_a_bfloat16_state_is_another_answer():
    """The control of the tolerance: the state rounded to bfloat16 after
    every update must not pass for the float32 one."""
    x, dt, a, bm, cm = _draw(5, 24)
    want_y, _ = _recurrence(x, dt, a, bm, cm)
    pool = _fresh_pool()
    reset = np.array([False, False, True, False])
    rows, count = mamba2.compact_rows(jnp.asarray(reset))
    ys = []
    for i in range(24):
        y, pool = mamba2.mamba2_ssd_update_xla(
            jnp.asarray(dt[i:i + 1, :, None] * x[i:i + 1]).repeat(B, 0),
            jnp.asarray(np.exp(dt[i:i + 1] * a)).repeat(B, 0),
            jnp.asarray(bm[i:i + 1]).repeat(B, 0),
            jnp.asarray(cm[i:i + 1]).repeat(B, 0), rows, count,
            jnp.asarray(reset if i == 0 else np.zeros(B, bool)), pool,
            layer=0, round_state=True)
        ys.append(np.asarray(y[2]))
    err = np.abs(np.stack(ys) - want_y).max()
    assert err > 1e-3, err


@pytest.mark.parametrize("sizes,reason", [
    ((16, 2, 64, 128, 128), "mamba_d_head 64"),
    ((16, 2, 128, 64, 128), "mamba_d_state 64"),
    ((12, 3, 128, 128, 128), "12 heads in 3 groups"),
    ((16, 2, 128, 128, 1024), "1024 tokens a launch"),
])
def test_unsupported_sizes_are_named(sizes, reason):
    got = mamba2.ssd_kernel_unsupported_reason(*sizes, platform="tpu")
    assert got and reason in got
    assert mamba2.ssd_kernel_unsupported_reason(
        32, 2, 128, 256, 128, platform="tpu") is None
    assert "platform cpu" in mamba2.ssd_kernel_unsupported_reason(
        32, 2, 128, 256, 128, platform="cpu")
