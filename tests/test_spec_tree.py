"""Draft-tree speculative decoding unit tests (docs/spec_decode_trees.md):
the proposer interface's forest topology contract, the tree-topology
causal mask against the XLA reference and an explicit dense softmax
(chain / binary / forest, int8 KV, partial pages), tree acceptance
walks, and chain-as-degenerate-tree byte-identity for both the greedy
rule and the seeded rejection sampler."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.llm import faults
from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
from clearml_serving_tpu.llm.sampling import (
    SamplingParams,
    greedy_tree_walk,
    make_sampling_params,
    speculative_sample_chain,
    speculative_sample_tree,
)
from clearml_serving_tpu.llm.spec_proposer import (
    DraftForest,
    NgramChainProposer,
    NgramForestProposer,
    chain_parents,
    make_proposer,
    validate_forest,
)
from clearml_serving_tpu.ops.paged_attention import (
    ragged_layout,
    ragged_query_tile,
    ragged_work_items,
    ragged_paged_attention,
    ragged_paged_attention_xla,
    tree_ancestors,
)


# -- proposer interface -------------------------------------------------------


def _tokbuf(rows, pattern, buf_len=64):
    buf = np.zeros((rows, buf_len), np.int32)
    for r in range(rows):
        seq = pattern(r)
        buf[r, : len(seq)] = seq
    return buf


def test_chain_proposer_matches_legacy_drafts():
    """Single-match history: the chain proposer continues from the LAST
    match, exactly like engine._ngram_draft_rows."""
    k = 4
    seq = [5, 6, 7, 8, 9, 1, 2, 3, 5, 6]           # tail (5,6) matched at 0
    buf = _tokbuf(1, lambda r: seq)
    forest = NgramChainProposer(ngram=2).propose([0], [len(seq)], buf, k)
    validate_forest(forest)
    assert list(forest.parents[0]) == [-1, 0, 1, 2, 3]
    assert list(forest.tokens[0][1:]) == [7, 8, 9, 1]
    assert bool(forest.hits[0])


def test_chain_proposer_fallback_repeats_last():
    buf = _tokbuf(1, lambda r: [1, 2, 3, 4])
    forest = NgramChainProposer(ngram=2).propose([0], [4], buf, 3)
    assert list(forest.tokens[0][1:]) == [4, 4, 4]
    assert not bool(forest.hits[0])


def test_forest_proposer_branches_across_matches():
    """Two matches with distinct continuations: primary chain from the
    most recent match + one depth-1 sibling from the older one."""
    k = 4
    # tail (1, 2): occurs at 0 (-> 7) and at 4 (-> 9); most recent is 4
    seq = [1, 2, 7, 8, 1, 2, 9, 3, 1, 2]
    buf = _tokbuf(1, lambda r: seq)
    prop = NgramForestProposer(ngram=2, branch=2)
    forest = prop.propose([0], [len(seq)], buf, k)
    validate_forest(forest)
    assert int(forest.n_nodes[0]) == k + 1
    # primary chain: 3 deep from the recent match (9, 3, 1), sibling: 7
    assert list(forest.tokens[0][1:4]) == [9, 3, 1]
    assert list(forest.parents[0][1:4]) == [0, 1, 2]
    assert forest.tokens[0][4] == 7 and forest.parents[0][4] == 0
    assert prop.stats()["branched"] == 1


def test_forest_proposer_single_match_degenerates_to_chain():
    seq = [5, 6, 7, 8, 9, 1, 2, 3, 5, 6]
    buf = _tokbuf(1, lambda r: seq)
    chain = NgramChainProposer(ngram=2).propose([0], [len(seq)], buf, 4)
    forest = NgramForestProposer(ngram=2, branch=2).propose(
        [0], [len(seq)], buf, 4)
    np.testing.assert_array_equal(forest.tokens, chain.tokens)
    np.testing.assert_array_equal(forest.parents, chain.parents)


REPLAYS = {
    # three tokens drawn at random: nearly every context has been seen
    # with more than one continuation, and the most recent is often wrong
    "ambiguous": (np.random.default_rng(0).integers(1, 4, 64), 1),
    # one continuation a context: the forest dedups to the chain's drafts
    "periodic": (np.asarray([1, 2, 1, 3] * 16), 2),
}


@pytest.mark.parametrize("stream", sorted(REPLAYS))
def test_forest_against_chain_a_verify_row_at_equal_budget(stream):
    """The draft tree's reason to exist, without a model: replay a known
    stream through both proposers at the SAME k+1 node budget under
    greedy acceptance and count the tokens a verify row commits (each row
    is k+1 positions of a launch's budget). On ambiguous history a
    depth-1 sibling from an older match carries rows whose chain draft is
    rejected at once, and the forest commits strictly more a row; where
    every context has one continuation the two tie."""
    k = 4
    truth, ngram = REPLAYS[stream]
    truth = truth.astype(np.int32)

    def tokens_a_row(proposer):
        start = hist = 9
        rows = 0
        while hist + k + 1 < len(truth):
            buf = np.zeros((1, len(truth)), np.int32)
            buf[0, :hist] = truth[:hist]
            forest = proposer.propose([0], [hist], buf, k)
            validate_forest(forest)
            # a node at depth d drafts truth[hist + d - 1]; the argmax
            # behind an accepted node at depth d is truth[hist + d]
            greedy = truth[hist + forest.depths]
            _, acc, _ = greedy_tree_walk(
                jnp.asarray(greedy), jnp.asarray(forest.tokens),
                jnp.asarray(forest.parents), jnp.asarray(forest.n_nodes))
            hist += int(acc[0]) + 1     # accepted drafts + the bonus token
            rows += 1
        return (hist - start) / rows

    chain = tokens_a_row(NgramChainProposer(ngram=ngram))
    forest = NgramForestProposer(ngram=ngram, branch=2)
    tree = tokens_a_row(forest)
    if stream == "ambiguous":
        assert forest.stats()["branched"] >= 1
        assert tree > 1.2 * chain, (tree, chain)
    else:
        assert forest.stats()["branched"] == 0
        assert tree == chain == 5.0


def test_make_proposer_registry():
    assert make_proposer("ngram-forest", branch=3).branch == 3
    with pytest.raises(ValueError, match="unknown spec proposer"):
        make_proposer("medusa")


def test_validate_forest_rejects_bad_topology():
    k = 2
    good = DraftForest(
        tokens=np.zeros((1, k + 1), np.int32),
        parents=chain_parents(k)[None],
        depths=np.arange(k + 1, np.int32)[None]
        if False else np.arange(k + 1, dtype=np.int32)[None],
        n_nodes=np.array([k + 1], np.int32),
        hits=np.zeros(1, bool),
    )
    validate_forest(good)
    bad = DraftForest(
        tokens=np.zeros((1, k + 1), np.int32),
        parents=np.array([[-1, 2, 0]], np.int32),   # parent after child
        depths=np.array([[0, 1, 1]], np.int32),
        n_nodes=np.array([k + 1], np.int32),
        hits=np.zeros(1, bool),
    )
    with pytest.raises(ValueError, match="not before"):
        validate_forest(bad)


# -- tree ancestor builder ----------------------------------------------------


def test_tree_ancestors_chain_and_forest():
    anc = tree_ancestors(chain_parents(3))
    assert list(anc[0]) == [0, -1, -1, -1]
    assert list(anc[3]) == [0, 1, 2, 3]
    # binary-ish forest: 1,2 children of root; 3 child of 1; 4 child of 2
    anc = tree_ancestors([-1, 0, 0, 1, 2])
    assert list(anc[3][:3]) == [0, 1, 3]
    assert list(anc[4][:3]) == [0, 2, 4]
    assert list(anc[2][:2]) == [0, 2] and anc[2][2] == -1
    # dead nodes mask to nothing in-row
    anc = tree_ancestors([-1, 0, 0], n_nodes=2)
    assert list(anc[2]) == [-1, -1, -1]
    with pytest.raises(ValueError, match="depth"):
        tree_ancestors(chain_parents(3), width=2)


# -- tree mask parity ---------------------------------------------------------


def _tree_setup(key, parents_rows, *, hkv=2, g=2, d=64, page=16,
                pages_per_seq=4, hist=(12, 5)):
    """Rows: one tree row per parents list (row_len = node count), with
    per-row history. Returns operands + flat tree_anc."""
    rows = len(parents_rows)
    row_lens = np.array([len(p) for p in parents_rows], np.int32)
    kv_lens = row_lens + np.asarray(hist[:rows], np.int32)
    ks = jax.random.split(key, 3)
    n_pages = rows * pages_per_seq + 1
    k_pool = jax.random.normal(ks[0], (hkv, n_pages, page, d), jnp.float32)
    v_pool = jax.random.normal(ks[1], (hkv, n_pages, page, d), jnp.float32)
    page_table = np.zeros((rows, pages_per_seq), np.int32)
    for r in range(rows):
        page_table[r] = 1 + r * pages_per_seq + np.arange(pages_per_seq)
    starts, t_pad = ragged_layout(row_lens)
    item_rows, item_q0 = ragged_work_items(
        row_lens, ragged_query_tile(hkv, g, d, jnp.float32))
    q = jax.random.normal(ks[2], (t_pad, hkv, g, d), jnp.float32)
    dmax = max(len(p) for p in parents_rows)
    tree_anc = np.full((t_pad, dmax), -1, np.int32)
    tree_anc[:, 0] = -2                                  # default: plain
    for r, parents in enumerate(parents_rows):
        anc = tree_ancestors(parents, width=dmax)
        s = int(starts[r])
        tree_anc[s: s + len(parents)] = anc
    return (q, k_pool, v_pool, jnp.asarray(page_table), jnp.asarray(kv_lens),
            jnp.asarray(starts), jnp.asarray(row_lens),
            jnp.asarray(item_rows), jnp.asarray(item_q0),
            jnp.asarray(tree_anc))


def _dense_tree_reference(q, k_pool, v_pool, page_table, kv_lens, starts,
                          row_lens, tree_anc):
    """Explicit per-query softmax over the allowed set: history plus the
    query's own ancestor path."""
    out = np.zeros_like(np.asarray(q))
    d = q.shape[-1]
    for r in range(page_table.shape[0]):
        kv_len, row_len = int(kv_lens[r]), int(row_lens[r])
        base, s = kv_len - row_len, int(starts[r])
        pages = np.asarray(page_table[r])
        k = np.asarray(k_pool[:, pages]).reshape(k_pool.shape[0], -1, d)
        v = np.asarray(v_pool[:, pages]).reshape(v_pool.shape[0], -1, d)
        for i in range(row_len):
            anc = set(int(a) for a in np.asarray(tree_anc[s + i]) if a >= 0)
            plain = int(tree_anc[s + i, 0]) == -2
            allowed = [
                p for p in range(min(base + i + 1, kv_len))
                if p < base or plain or (p - base) in anc
            ]
            qi = np.asarray(q[s + i])
            for h in range(q.shape[1]):
                sc = qi[h] @ k[h, allowed].T * (d ** -0.5)
                p = np.exp(sc - sc.max(axis=-1, keepdims=True))
                p /= p.sum(axis=-1, keepdims=True)
                out[s + i, h] = p @ v[h, allowed]
    return out


TOPOLOGIES = {
    "chain": [list(chain_parents(4))],
    "binary": [[-1, 0, 0, 1, 1, 2, 2]],
    "forest": [[-1, 0, 0, 1, 2], list(chain_parents(4)), [-1, 0, 0, 0]],
    # more than the 8 nodes of a small work item: the ragged kernel takes
    # this row as a query tile (its mask behind a check of the sentinels)
    "wide": [[-1, 0, 0, 1, 1, 2, 2, 3, 3, 4, 4, 5], list(chain_parents(3))],
}


@pytest.mark.parametrize("topo", sorted(TOPOLOGIES))
def test_tree_mask_xla_matches_dense_reference(topo):
    args = _tree_setup(jax.random.PRNGKey(0), TOPOLOGIES[topo],
                       hist=(12, 5, 17))
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     _br, _bq, tree_anc) = args
    out = ragged_paged_attention_xla(
        q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
        tree_anc=tree_anc,
    )
    want = _dense_tree_reference(
        q, k_pool, v_pool, page_table, kv_lens, starts, row_lens, tree_anc)
    for r in range(page_table.shape[0]):
        s, n = int(starts[r]), int(row_lens[r])
        np.testing.assert_allclose(
            np.asarray(out[s: s + n]), want[s: s + n], rtol=1e-5, atol=1e-5)


def test_tree_mask_chain_topology_equals_plain_causal():
    """A chain tree's ancestor mask admits exactly the causal triangle:
    outputs must be BIT-identical to the untreed reference."""
    args = _tree_setup(jax.random.PRNGKey(1), TOPOLOGIES["chain"])
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     _br, _bq, tree_anc) = args
    a = ragged_paged_attention_xla(
        q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
        tree_anc=tree_anc)
    b = ragged_paged_attention_xla(
        q, k_pool, v_pool, page_table, kv_lens, starts, row_lens)
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("topo", sorted(TOPOLOGIES))
@pytest.mark.parametrize("page", [16, 32])
def test_tree_mask_kernel_interpret_matches_xla(monkeypatch, topo, page):
    """Pallas kernel (interpret) vs XLA reference across topologies,
    including a partial final page (history not page-aligned), over context
    blocks of two pages."""
    from clearml_serving_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "_DECODE_BLOCK_TOKENS", 2 * page)
    args = _tree_setup(jax.random.PRNGKey(2), TOPOLOGIES[topo],
                       page=page, hist=(page + 3, 5, 2 * page))
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     item_rows, item_q0, tree_anc) = args
    ref = ragged_paged_attention_xla(
        q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
        tree_anc=tree_anc)
    out = ragged_paged_attention(
        q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
        item_rows=item_rows, item_q0=item_q0, tree_anc=tree_anc,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_tree_mask_kernel_int8_interpret_matches_xla(monkeypatch):
    from clearml_serving_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "_DECODE_BLOCK_TOKENS", 32)
    def _quantize(pool):
        x = np.asarray(pool, np.float32)
        absmax = np.abs(x).max(axis=-1)
        scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
        qv = np.clip(np.round(x / scale[..., None]), -127, 127)
        return jnp.asarray(qv.astype(np.int8)), jnp.asarray(scale)

    args = _tree_setup(jax.random.PRNGKey(3), TOPOLOGIES["forest"],
                       hist=(9, 5, 17))
    (q, k_pool, v_pool, page_table, kv_lens, starts, row_lens,
     item_rows, item_q0, tree_anc) = args
    k8, ks = _quantize(k_pool)
    v8, vs = _quantize(v_pool)
    ref = ragged_paged_attention_xla(
        q, k8, v8, page_table, kv_lens, starts, row_lens, ks, vs,
        tree_anc=tree_anc)
    out = ragged_paged_attention(
        q, k8, v8, page_table, kv_lens, starts, row_lens,
        item_rows=item_rows, item_q0=item_q0,
        k_scale=ks, v_scale=vs, tree_anc=tree_anc,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


# -- acceptance walks ---------------------------------------------------------


def test_greedy_tree_walk_takes_longest_path():
    # topology: 0 -> {1, 4}; 1 -> 2 -> 3 (primary chain), 4 sibling
    parents = jnp.asarray([[-1, 0, 1, 2, 0]], jnp.int32)
    tokens = jnp.asarray([[100, 7, 8, 9, 5]], jnp.int32)
    n_nodes = jnp.asarray([5], jnp.int32)
    # argmax per node: root prefers 7, node1 prefers 8, node2 prefers 0
    greedy = jnp.asarray([[7, 8, 0, 1, 2]], jnp.int32)
    path, acc, nodes = greedy_tree_walk(greedy, tokens, parents, n_nodes)
    assert int(acc[0]) == 2
    assert list(np.asarray(path[0][:3])) == [7, 8, 0]   # drafts + bonus
    # compaction map: accepted nodes 1, 2 land at positions 1, 2
    assert list(np.asarray(nodes[0])) == [0, 1, 2, 3, 4]
    # root prefers the SIBLING: path goes 0 -> 4
    greedy = jnp.asarray([[5, 8, 0, 1, 2]], jnp.int32)
    path, acc, nodes = greedy_tree_walk(greedy, tokens, parents, n_nodes)
    assert int(acc[0]) == 1
    assert list(np.asarray(path[0][:2])) == [5, 2]
    # compaction map: sibling node 4's K/V moves to row position 1;
    # everything past acc stays identity
    assert list(np.asarray(nodes[0])) == [0, 4, 2, 3, 4]
    # nothing matches: bonus only
    greedy = jnp.asarray([[3, 8, 0, 1, 2]], jnp.int32)
    path, acc, nodes = greedy_tree_walk(greedy, tokens, parents, n_nodes)
    assert int(acc[0]) == 0 and int(path[0, 0]) == 3
    assert list(np.asarray(nodes[0])) == [0, 1, 2, 3, 4]


def test_greedy_tree_walk_chain_matches_cumprod_rule():
    b, k, v = 3, 4, 11
    rng = np.random.default_rng(0)
    drafts = rng.integers(0, v, (b, k)).astype(np.int32)
    argmax = rng.integers(0, v, (b, k + 1)).astype(np.int32)
    argmax[0, :2] = drafts[0, :2]                       # partial accept
    argmax[1] = np.concatenate([drafts[1], [3]])        # full accept
    tokens = np.concatenate(
        [np.full((b, 1), 9, np.int32), drafts], axis=1)
    parents = np.broadcast_to(chain_parents(k), (b, k + 1))
    path, acc, nodes = greedy_tree_walk(
        jnp.asarray(argmax), jnp.asarray(tokens),
        jnp.asarray(parents), jnp.full((b,), k + 1, jnp.int32))
    # a chain accepts in node order: the compaction map is identity
    np.testing.assert_array_equal(
        np.asarray(nodes), np.broadcast_to(np.arange(k + 1), (b, k + 1)))
    want_acc = np.sum(np.cumprod(drafts == argmax[:, :k], axis=1), axis=1)
    np.testing.assert_array_equal(np.asarray(acc), want_acc)
    for r in range(b):
        a = int(want_acc[r])
        np.testing.assert_array_equal(
            np.asarray(path[r][:a]), drafts[r][:a])
        assert int(path[r][a]) == int(argmax[r, a])


def test_sample_tree_chain_byte_identical_to_chain_sampler():
    """The tentpole identity: on the degenerate chain topology, the tree
    sampler's emitted tokens and acceptance counts are byte-identical to
    speculative_sample_chain under the same rng (greedy rows are covered
    by the cumprod test above; this is the seeded sampled path)."""
    b, k, v = 4, 4, 37
    key = jax.random.PRNGKey(42)
    logits = jax.random.normal(key, (b, k + 1, v)) * 3.0
    kd, kr = jax.random.split(jax.random.PRNGKey(7))
    drafts = jax.random.randint(kd, (b, k), 0, v, jnp.int32)
    # make some drafts likely-accepted so both branches exercise
    drafts = drafts.at[0].set(jnp.argmax(logits[0, :k], axis=-1))
    params = make_sampling_params(b, temperature=0.9, top_k=0, top_p=1.0)
    ct, ca = speculative_sample_chain(logits, drafts, params, kr)
    tokens = jnp.concatenate(
        [jnp.full((b, 1), 5, jnp.int32), drafts], axis=1)
    parents = jnp.broadcast_to(
        jnp.asarray(chain_parents(k)), (b, k + 1))
    tt, ta, tn = speculative_sample_tree(
        logits, tokens, parents, jnp.full((b,), k + 1, jnp.int32),
        params, kr)
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(ta))
    for r in range(b):
        a = int(ca[r])
        assert (np.asarray(ct[r][: a + 1]).tobytes()
                == np.asarray(tt[r][: a + 1]).tobytes())


def test_sample_tree_law_on_binary_tree():
    """Distributional sanity: the emitted FIRST token's law must equal
    the root's warped softmax regardless of topology (the rejection
    scheme is unbiased)."""
    v = 8
    key = jax.random.PRNGKey(0)
    logits_row = jax.random.normal(key, (v,)) * 2.0
    n = 5
    logits = jnp.broadcast_to(logits_row, (1, n, v))
    # binary tree with drafts on the two most likely tokens
    top2 = np.argsort(np.asarray(logits_row))[::-1][:2]
    tokens = jnp.asarray(
        [[0, int(top2[0]), int(top2[1]), 3, 4]], jnp.int32)
    parents = jnp.asarray([[-1, 0, 0, 1, 2]], jnp.int32)
    n_nodes = jnp.asarray([n], jnp.int32)
    params = make_sampling_params(1, temperature=1.0)

    @jax.jit
    def draw(key):
        path, acc, _ = speculative_sample_tree(
            logits, tokens, parents, n_nodes, params, key)
        return path[0, 0]

    trials = 4000
    keys = jax.random.split(jax.random.PRNGKey(123), trials)
    first = np.asarray(jax.vmap(draw)(keys))
    counts = np.bincount(first, minlength=v) / trials
    want = np.asarray(jax.nn.softmax(logits_row))
    np.testing.assert_allclose(counts, want, atol=0.03)


# -- engine integration -------------------------------------------------------


@pytest.fixture(scope="module")
def eparts():
    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"})
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


def _engine(bundle, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("prefill_buckets", [16, 64])
    kw.setdefault("eos_token_id", None)
    kw.setdefault("decode_steps", 2)
    kw.setdefault("cache_mode", "paged")
    kw.setdefault("scheduler", "ragged")
    kw.setdefault("step_token_budget", 12)
    return LLMEngineCore(bundle, params, **kw)


def _staggered(engine, prompts, n=8, seeds=None):
    async def one(i, ids):
        if i:
            await asyncio.sleep(0.05 * i)
        seed = seeds[i] if seeds else None
        req = GenRequest(
            prompt_ids=list(ids), max_new_tokens=n,
            temperature=0.7 if seed is not None else 0.0, seed=seed,
        )
        return [t async for t in engine.generate(req)]

    async def run():
        outs = await asyncio.gather(*(one(i, p) for i, p in enumerate(prompts)))
        await engine.wait_drained()
        return outs

    return asyncio.run(run())


SPEC_A = [5, 9, 2, 17, 5, 9, 2]
SPEC_B = [3, 3, 7, 3, 3, 7, 3]


def test_spec_tree_engine_requires_ngram_and_paged(eparts):
    """spec_tree is a mode OF n-gram speculation on the PAGED ragged path
    (dense chunk layers cannot express a tree mask) — anything else is a
    construction-time error, not a silent downgrade."""
    bundle, params = eparts
    with pytest.raises(ValueError, match="spec_tree"):
        _engine(bundle, params, spec_tree=True)
    with pytest.raises(ValueError, match="spec_tree"):
        _engine(bundle, params, cache_mode="dense", scheduler=None,
                speculation="ngram", spec_k=2, spec_ngram=2, spec_tree=True)


def test_spec_tree_engine_greedy_three_arm_identity(eparts, monkeypatch):
    """The headline verify guarantee across all three arms: plain ragged
    decode, chain spec (k drafts, PR 13), and draft-TREE spec (same k+1
    verify budget, forest proposer) emit byte-identical GREEDY streams.
    The tree arm must actually verify tree rows (depth histogram
    populated, forest proposer live) — not silently fall back."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle, params = eparts
    spec_kw = dict(speculation="ngram", spec_k=4, spec_ngram=2)
    arms = {}
    stats = {}
    for name, kw in (
        ("plain", {}),
        ("chain", spec_kw),
        ("tree", dict(spec_kw, spec_tree=True, spec_branch=2)),
    ):
        engine = _engine(bundle, params, **kw)
        # row 0 greedy, row 1 seeded: the sampled tree walk rides the
        # same launches (seeded streams are distribution-exact, not
        # byte-stable across arms, so only the greedy row is compared)
        arms[name] = _staggered(engine, [SPEC_A, SPEC_B], n=10,
                                seeds=[None, 22])
        stats[name] = engine.lifecycle_stats()["ragged"]
        assert engine._sanitizer.failures == 0
        engine.stop()
    assert stats["tree"]["spec_tree_fallbacks"] == 0
    assert arms["chain"][0] == arms["plain"][0]
    assert arms["tree"][0] == arms["plain"][0]
    for arm in ("plain", "chain", "tree"):
        assert len(arms[arm][1]) == 10          # seeded row completed
    assert stats["tree"]["step_rows"]["spec_verify"] >= 1
    assert stats["tree"]["spec_tree_depth"]["count"] >= 1
    assert stats["tree"]["spec_proposer"]["name"] == "ngram-forest"
    assert stats["tree"]["spec_proposer"]["proposed"] >= 1
    assert stats["chain"]["spec_tree_depth"] is None
    assert stats["chain"]["spec_proposer"]["name"] == "ngram-chain"
    assert stats["plain"]["spec_proposer"] is None


@pytest.mark.chaos
def test_spec_tree_chaos_fault_demotes_row_to_plain_decode(eparts,
                                                          monkeypatch):
    """An ``engine.spec.tree`` fault mid-planning demotes ONLY the matched
    request's verify row to plain decode in the same launch: both greedy
    streams stay byte-identical to an undisturbed run (the demoted row
    simply decodes draft-free that step), the fallback is counted, and
    nothing leaks — the seam sits before any allocation."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    bundle, params = eparts
    marker = 211
    marked = [marker] + SPEC_A
    kw = dict(speculation="ngram", spec_k=2, spec_ngram=2,
              spec_tree=True, spec_branch=2)

    clean = _engine(bundle, params, **kw)
    want = _staggered(clean, [marked, SPEC_B], n=10)
    clean.stop()

    engine = _engine(bundle, params, **kw)
    faults.configure([
        {"point": "engine.spec.tree", "action": "raise",
         "match_token": marker, "times": 2},
    ])
    try:
        got = _staggered(engine, [marked, SPEC_B], n=10)
        assert got == want
        assert engine.counters["spec_tree_fallbacks"] >= 1
        stats = engine.lifecycle_stats()["ragged"]
        assert stats["spec_tree_fallbacks"] >= 1
        # the sibling kept speculating: verify rows still ran somewhere
        assert stats["step_rows"]["spec_verify"] >= 1
        pool = engine.paged_cache.pool
        assert pool.free_pages == pool.num_pages - 1  # nothing leaked
    finally:
        faults.clear()
        engine.stop()
