"""The window bound of the two paged kernels (docs/window_attention.md):
both kernels (interpret mode) against their XLA references over ragged row
lengths, the references against a mask built from positions, the page walk
(pages wholly behind the window are never visited) and what is refused by
name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from clearml_serving_tpu.ops import paged_attention as pa
from clearml_serving_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_xla,
    ragged_paged_attention,
    ragged_paged_attention_xla,
    window_first_page,
    window_kernel_unsupported_reason,
)
from test_ragged_ops import _setup, _small_plan

PAGE = 16


def _decode_setup(key, lengths, hkv=2, g=2, d=64, pages_per_seq=8):
    rows = len(lengths)
    ks = jax.random.split(key, 3)
    n_pages = rows * pages_per_seq + 2
    k_pool = jax.random.normal(ks[0], (hkv, n_pages, PAGE, d), jnp.float32)
    v_pool = jax.random.normal(ks[1], (hkv, n_pages, PAGE, d), jnp.float32)
    order = np.random.RandomState(1).permutation(n_pages - 2) + 1
    table = order[: rows * pages_per_seq].reshape(rows, pages_per_seq)
    q = jax.random.normal(ks[2], (rows, hkv, g, d), jnp.float32)
    return (q, k_pool, v_pool, jnp.asarray(table.astype(np.int32)),
            jnp.asarray(np.asarray(lengths, np.int32)))


def _poisoned(pools, table, first_pages):
    """The pools with one more page of NaN, and the table with every page
    before ``first_pages[r]`` pointing at it: a walk that visits one of them
    reads NaN into its flash update (0 x NaN), whatever the mask says."""
    k_pool, v_pool = pools
    bad = k_pool.shape[1] - 1
    k_pool = k_pool.at[:, bad].set(jnp.nan)
    v_pool = v_pool.at[:, bad].set(jnp.nan)
    table = np.array(table)
    for r, first in enumerate(first_pages):
        table[r, :int(first)] = bad
    return k_pool, v_pool, jnp.asarray(table)


@pytest.mark.parametrize("window", [1, 5, 16, 40, 64, 1000])
def test_decode_kernel_window_against_reference(monkeypatch, window):
    """Rows shorter and longer than the window, lengths at page and block
    edges, an empty row: the kernel reads what the reference reads."""
    monkeypatch.setattr(pa, "_DECODE_BLOCK_TOKENS", 32)
    args = _decode_setup(jax.random.PRNGKey(3),
                         [1, 0, 16, 33, 64, 65, 100, 128])
    ref = paged_attention_xla(*args, window=window)
    out = paged_attention(*args, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    if window < 100:
        assert not np.allclose(np.asarray(ref),
                               np.asarray(paged_attention_xla(*args)))


def test_decode_reference_window_is_the_mask_from_positions():
    q, k_pool, v_pool, table, lengths = _decode_setup(
        jax.random.PRNGKey(4), [50, 7, 90])
    window = 24
    out = np.asarray(paged_attention_xla(
        q, k_pool, v_pool, table, lengths, window=window))
    for r, n in enumerate(np.asarray(lengths)):
        keys = np.asarray(k_pool[:, table[r]]).reshape(2, -1, 64)[:, :n]
        vals = np.asarray(v_pool[:, table[r]]).reshape(2, -1, 64)[:, :n]
        pos = n - 1
        seen = np.arange(n) > pos - window
        s = np.einsum("kgd,ktd->kgt", np.asarray(q[r]), keys) / 8.0
        s = np.where(seen, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("kgt,ktd->kgd", p / p.sum(-1, keepdims=True), vals)
        np.testing.assert_allclose(out[r], want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("window", [5, 16, 40])
def test_decode_kernel_never_visits_pages_behind_the_window(
        monkeypatch, window):
    """Every page wholly behind a row's window points at a page of NaN: the
    kernel's output stays the reference's (of the clean table), so no such
    page was copied into a flash update; with the walk's start one page
    early, the NaN shows."""
    monkeypatch.setattr(pa, "_DECODE_BLOCK_TOKENS", 32)
    q, k_pool, v_pool, table, lengths = _decode_setup(
        jax.random.PRNGKey(5), [1, 0, 16, 33, 64, 65, 100, 128])
    first = window_first_page(np.asarray(lengths) - 1, window, PAGE)
    assert first.max() >= 5 and first.min() == 0
    ref = paged_attention_xla(q, k_pool, v_pool, table, lengths,
                              window=window)
    k_bad, v_bad, t_bad = _poisoned((k_pool, v_pool), table, first)
    out = paged_attention(q, k_bad, v_bad, t_bad, lengths, window=window,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # the count: pages a row's walk covers = its last page - its first + 1
    visited = -(-np.asarray(lengths) // PAGE) - first
    assert visited.max() <= -(-(window + PAGE - 1) // PAGE) + 1
    late = np.array(table)
    late[-1, : int(first[-1]) + 1] = k_bad.shape[1] - 1   # one page too many
    out = paged_attention(q, k_bad, v_bad, jnp.asarray(late), lengths,
                          window=window, interpret=True)
    assert np.isnan(np.asarray(out[-1])).any()


ROWS = dict(row_lens=(1, 21, 5, 1, 40), kv_extra=(70, 13, 61, 3, 100))


@pytest.mark.parametrize("window", [1, 8, 20, 33, 64, 500])
def test_ragged_kernel_window_against_reference(monkeypatch, window):
    """Decode rows, a verify-sized row and chunks of several tiles whose
    windows begin before, inside and after their history."""
    _small_plan(monkeypatch)
    *operands, item_rows, item_q0 = _setup(
        jax.random.PRNGKey(11), rows=5, pages_per_seq=10, **ROWS)
    ref = ragged_paged_attention_xla(*operands, window=window)
    out = ragged_paged_attention(
        *operands, item_rows=item_rows, item_q0=item_q0, window=window,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    if window < 100:
        assert not np.allclose(
            np.asarray(ref), np.asarray(ragged_paged_attention_xla(*operands)))


def test_ragged_reference_window_is_the_mask_from_positions():
    q, k_pool, v_pool, table, kv_lens, starts, lens, _, _ = _setup(
        jax.random.PRNGKey(12), rows=2, pages_per_seq=6, row_lens=(30, 1),
        kv_extra=(40, 50))
    window = 17
    out = np.asarray(ragged_paged_attention_xla(
        q, k_pool, v_pool, table, kv_lens, starts, lens, window=window))
    for r in range(2):
        n, kv = int(lens[r]), int(kv_lens[r])
        keys = np.asarray(k_pool[:, table[r]]).reshape(2, -1, 64)[:, :kv]
        vals = np.asarray(v_pool[:, table[r]]).reshape(2, -1, 64)[:, :kv]
        for i in range(n):
            pos = kv - n + i
            seen = (np.arange(kv) <= pos) & (np.arange(kv) > pos - window)
            s = np.einsum("kgd,ktd->kgt",
                          np.asarray(q[int(starts[r]) + i]), keys) / 8.0
            s = np.where(seen, s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            want = np.einsum("kgt,ktd->kgd", p / p.sum(-1, keepdims=True),
                             vals)
            np.testing.assert_allclose(out[int(starts[r]) + i], want,
                                       rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("window", [8, 20, 33])
def test_ragged_kernel_never_visits_pages_behind_the_window(
        monkeypatch, window):
    """As for the decode kernel; a row's first work item starts the
    earliest, so its first page bounds what any item of the row visits."""
    _small_plan(monkeypatch)
    q, k_pool, v_pool, table, kv_lens, starts, lens, item_rows, item_q0 = \
        _setup(jax.random.PRNGKey(13), rows=5, pages_per_seq=10, **ROWS)
    n_pages = k_pool.shape[1]
    grow = ((0, 0), (0, 1), (0, 0), (0, 0))
    k_pool, v_pool = jnp.pad(k_pool, grow), jnp.pad(v_pool, grow)
    first_pos = np.asarray(kv_lens) - np.asarray(lens)   # query 0 of a row
    first = window_first_page(first_pos, window, PAGE)
    assert first.max() >= 3
    ref = ragged_paged_attention_xla(
        q, k_pool, v_pool, table, kv_lens, starts, lens, window=window)
    k_bad, v_bad, t_bad = _poisoned((k_pool, v_pool), table, first)
    assert k_bad.shape[1] == n_pages + 1
    out = ragged_paged_attention(
        q, k_bad, v_bad, t_bad, kv_lens, starts, lens, item_rows=item_rows,
        item_q0=item_q0, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_window_first_page_is_the_page_of_the_first_visible_key():
    assert window_first_page(100, 0, 16) == 0
    # position 100 under a window of 40 sees 61..100: page 3
    assert window_first_page(100, 40, 16) == 3
    assert window_first_page(5, 40, 16) == 0
    got = window_first_page(np.array([0, 15, 16, 47, 48]), 16, 16)
    assert list(got) == [0, 0, 0, 2, 2]


def test_window_refusals_by_name():
    assert window_kernel_unsupported_reason(0, True, True) is None
    assert window_kernel_unsupported_reason(64) is None
    assert "int8" in window_kernel_unsupported_reason(64, quantized=True)
    assert "tree" in window_kernel_unsupported_reason(64, tree=True)
    q, k_pool, v_pool, table, lengths = _decode_setup(
        jax.random.PRNGKey(6), [20, 40])
    k8 = (k_pool * 10).astype(jnp.int8)
    scale = jnp.ones(k_pool.shape[:-1], jnp.float32)
    with pytest.raises(ValueError, match="window on int8"):
        paged_attention(q, k8, k8, table, lengths, k_scale=scale,
                        v_scale=scale, window=16, interpret=True)
    *operands, item_rows, item_q0 = _setup(jax.random.PRNGKey(14))
    anc = jnp.full((operands[0].shape[0], 2), -2, jnp.int32)
    with pytest.raises(ValueError, match="draft-tree"):
        ragged_paged_attention(
            *operands, item_rows=item_rows, item_q0=item_q0, tree_anc=anc,
            window=16, interpret=True)
    with pytest.raises(ValueError, match="draft-tree"):
        ragged_paged_attention_xla(*operands, tree_anc=anc, window=16)
    # without a window both still run
    ragged_paged_attention(
        *operands, item_rows=item_rows, item_q0=item_q0, tree_anc=anc,
        interpret=True)


@pytest.mark.parametrize("window", [20, 33, 64])
def test_a_querys_output_does_not_depend_on_how_its_prompt_was_chunked(
        monkeypatch, window):
    """The walk skips pages but keeps the blocks on the row's own grid, so a
    query's keys meet the same blocks in the same order whatever tile it
    rides in and wherever that tile's window begins: the last 8 queries of a
    40-query chunk, a chunk of those 8 alone (what a prefix-cache hit leaves
    of the prompt) and 8 decode-sized items read BIT FOR BIT the same. A
    probe sent twice must answer with the same ids (benchmark `correct`)."""
    _small_plan(monkeypatch)
    outs = []
    for queries in (40, 8):
        q, k_pool, v_pool, table, kv_lens, starts, lens, items, q0 = _setup(
            jax.random.PRNGKey(21), rows=1, pages_per_seq=10,
            row_lens=(queries,), kv_extra=(140 - queries,), slack=8)
        full = jax.random.normal(jax.random.PRNGKey(22), (40,) + q.shape[1:])
        q = q.at[:queries].set(full[40 - queries:])
        out = ragged_paged_attention(
            q, k_pool, v_pool, table, kv_lens, starts, lens, item_rows=items,
            item_q0=q0, window=window, interpret=True)
        outs.append(np.asarray(out[queries - 8:queries]))
    np.testing.assert_array_equal(outs[0], outs[1])
    # and the decode kernel on the same row reads the last query's output
    last = paged_attention(
        full[-1:], k_pool, v_pool, table, kv_lens, window=window,
        interpret=True)
    np.testing.assert_allclose(np.asarray(last[0]), outs[0][-1],
                               rtol=2e-5, atol=2e-5)
