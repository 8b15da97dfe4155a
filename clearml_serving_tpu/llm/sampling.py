"""Batched token sampling — one jitted function for the whole decode batch.

Per-slot temperature / top-k / top-p / penalties / seeds as data (arrays over
the batch), never as Python branches, so a single XLA executable covers every
mix of sampling settings in the continuous batch (recompilation-free,
SURVEY.md §7 hard part 1).

What a launch's sampling tail COSTS follows from what its live rows asked
for, decided on the device by two ``lax.cond``s over those same arrays
(:func:`row_needs`); the settings stay data and the executable stays one:
- every live row greedy (``temperature == 0``): the argmax of the (penalized)
  logits, and nothing else of the vocabulary's size — no temperature scaling,
  sort, softmax, cumulative sum or random draw;
- some live row samples, none filters: the scaling and the Gumbel draw;
- some live row samples with ``top_k > 0`` or ``top_p < 1``: one sort of the
  whole ``[B, V]`` block more (for every row of the launch — the known
  remainder), with the softmax, cumulative sum and cutoff behind it.
A row's result never depends on which of these its launch took: a row that
asked for no filter gets none in the sorted branch either. (Until PR 34 a
temperature-only row went through the nucleus mask at ``top_p == 1`` and
could lose a few tail tokens of ~1e-8 mass to the float32 rounding of a
cumulative sum that reaches 1 early; it now samples from
``softmax(logits / T)`` exactly.)

OpenAI/vLLM sampling-parameter parity (reference §2.8 route surface):
- ``presence_penalty`` / ``frequency_penalty``: subtracted from the logits of
  tokens already generated (vLLM semantics: output tokens only), presence as
  a flat hit, frequency scaled by the count.
- ``repetition_penalty``: multiplicative push-down on every token seen in the
  prompt OR the output (vLLM semantics), divide positive logits, multiply
  negative ones.
- ``logit_bias``: dense additive bias row per slot (built host-side from the
  OpenAI sparse {token_id: bias} map).
- ``seed``: per-request deterministic sampling stream — the row's key is
  fold_in(PRNGKey(seed), tokens_generated_so_far), so identical requests
  replay identical samples regardless of batch composition; unseeded rows
  draw from the engine's shared stream (split per row).

All extras are optional (None skips their compute at trace time, keeping the
no-extras graph identical to the minimal sampler).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class SamplingParams(NamedTuple):
    temperature: jnp.ndarray  # [B] float32; 0 => greedy
    top_k: jnp.ndarray        # [B] int32; 0 => disabled
    top_p: jnp.ndarray        # [B] float32; 1.0 => disabled


class SamplingExtras(NamedTuple):
    """Per-slot penalty/bias/seed state (all optional as a bundle)."""

    presence: jnp.ndarray    # [B] f32; 0 disables
    frequency: jnp.ndarray   # [B] f32; 0 disables
    repetition: jnp.ndarray  # [B] f32; 1.0 disables
    bias: jnp.ndarray        # [B, V] f32 dense additive bias
    seeds: jnp.ndarray       # [B] int32; < 0 => unseeded (shared stream)
    counters: jnp.ndarray    # [B] int32 tokens generated so far (seed stream)
    # vLLM min_tokens: the request's stop tokens (EOS and stop_token_ids)
    # are suppressed until `min_new` tokens were generated (None fields
    # disable — old constructions stay valid)
    min_new: Optional[jnp.ndarray] = None  # [B] int32; 0 disables
    stop: Optional[jnp.ndarray] = None     # [B, K] int32, -1-padded


def make_sampling_params(batch, temperature=0.0, top_k=0, top_p=1.0):
    import numpy as np

    return SamplingParams(
        temperature=jnp.asarray(np.full(batch, temperature, np.float32)),
        top_k=jnp.asarray(np.full(batch, top_k, np.int32)),
        top_p=jnp.asarray(np.full(batch, top_p, np.float32)),
    )


def penalize_logits(
    logits: jnp.ndarray,
    extras: SamplingExtras,
    counts: Optional[jnp.ndarray],
    prompt_mask: Optional[jnp.ndarray],
) -> jnp.ndarray:
    """Apply bias + penalties to raw logits [B, V] (before temperature).

    ``counts`` [B, V] int32: per-slot generated-token histogram.
    ``prompt_mask`` [B, V] bool: tokens present in the prompt."""
    logits = logits + extras.bias
    if counts is not None:
        counts_f = counts.astype(jnp.float32)
        logits = logits - extras.frequency[:, None] * counts_f
        logits = logits - extras.presence[:, None] * (counts_f > 0)
    seen = None
    if counts is not None:
        seen = counts > 0
    if prompt_mask is not None:
        seen = prompt_mask if seen is None else (seen | prompt_mask)
    if seen is not None:
        rp = extras.repetition[:, None]
        logits = jnp.where(
            seen,
            jnp.where(logits > 0, logits / rp, logits * rp),
            logits,
        )
    if extras.min_new is not None and extras.stop is not None:
        v_idx = jnp.arange(logits.shape[-1], dtype=jnp.int32)
        is_stop = jnp.any(
            v_idx[None, None, :] == extras.stop[:, :, None], axis=1
        )                                                       # [B, V]
        # never blank the whole row: when an upstream constraint (a guided
        # grammar in an accepting-only state) leaves stop tokens as the only
        # admissible choices, the grammar wins over the min_tokens floor —
        # suppressing them too would force a grammar-violating sample
        others_alive = jnp.any(
            jnp.where(is_stop, -jnp.inf, logits) > jnp.float32(-1e29),
            axis=-1, keepdims=True,
        )
        blocked = (
            (extras.counters < extras.min_new)[:, None] & is_stop & others_alive
        )
        logits = jnp.where(blocked, jnp.float32(-1e30), logits)
    return logits


def _row_keys(rng: jax.Array, extras: SamplingExtras, batch: int):
    """Per-row PRNG keys: seeded rows get fold_in(PRNGKey(seed), counter);
    unseeded rows split the shared stream."""
    shared = jax.random.split(rng, batch)                     # [B, 2] u32
    seeded = jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c)
    )(jnp.maximum(extras.seeds, 0), extras.counters)
    use_seed = (extras.seeds >= 0)[:, None]
    return jnp.where(use_seed, seeded, shared)


def row_needs(temperature, top_k, top_p, live=None):
    """Per row, what the sampler has to do for it: ``(filters, draws)``.

    ``draws``: the row samples (``temperature > 0``) and is ``live`` (a slot
    that is not advancing in this launch keeps its last request's settings,
    and nobody reads its token). ``filters``: it also asked for top-k or
    top-p. Plain comparisons, so the engine counts its launches from its
    host rows (numpy) with the predicate the device branches on."""
    draws = temperature > 0.0
    if live is not None:
        draws = draws & live
    return draws & ((top_k > 0) | (top_p < 1.0)), draws


def warp_logits(
    logits: jnp.ndarray,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
) -> jnp.ndarray:
    """Temperature-scale + top-k + top-p mask: [N, V] logits with per-row
    params [N] -> masked scaled logits (softmax of the result IS the
    sampling distribution). Shared by sample_tokens and the speculative
    rejection sampler so both sample from the identical law.

    The sort, softmax, cumulative sum and cutoff run only when some row
    samples WITH a filter (``row_needs``); a row without one gets its
    scaled logits back in either branch."""
    n, v = logits.shape
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    filters, _ = row_needs(temperature, top_k, top_p)

    def masked(scaled):
        # top-k (k == 0 disables): masking below the k-th value is monotone,
        # so the masked row's descending sort IS the sort with the same mask
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]          # [N, V]
        k = jnp.where(top_k > 0, top_k, v)
        kth = jnp.take_along_axis(
            sorted_desc, jnp.minimum(k - 1, v - 1)[:, None], axis=-1
        )                                                          # [N, 1]
        kept = jnp.where(scaled < kth, -jnp.inf, scaled)
        sorted_kept = jnp.where(sorted_desc < kth, -jnp.inf, sorted_desc)

        # top-p (nucleus) mask over the sorted distribution
        probs_sorted = jax.nn.softmax(sorted_kept, axis=-1)
        cumulative = jnp.cumsum(probs_sorted, axis=-1)
        # keep tokens while cumulative(prev) < top_p  (always keep the first)
        keep_sorted = (cumulative - probs_sorted) < top_p[:, None]
        cutoff = jnp.where(
            keep_sorted, sorted_kept, jnp.inf
        ).min(axis=-1, keepdims=True)                              # lowest kept logit
        kept = jnp.where(kept < cutoff, -jnp.inf, kept)
        return jnp.where(filters[:, None], kept, scaled)

    return jax.lax.cond(jnp.any(filters), masked, lambda scaled: scaled, scaled)


@partial(jax.jit, donate_argnums=())
@jax.named_scope("sample")
def sample_tokens(
    logits: jnp.ndarray,
    params: SamplingParams,
    rng: jax.Array,
    extras: Optional[SamplingExtras] = None,
    counts: Optional[jnp.ndarray] = None,
    prompt_mask: Optional[jnp.ndarray] = None,
    live: Optional[jnp.ndarray] = None,
):
    """logits: [B, V] float32 -> token ids [B] int32.

    Rows with temperature == 0 take the argmax; others sample from the
    temperature-scaled, top-k/top-p-filtered distribution. Penalties/bias
    (extras) apply to BOTH paths — greedy decoding respects them too.

    ``live`` [B] bool: the rows whose token the caller reads. A row outside
    it is treated as greedy, so a freed slot's stale settings switch on
    neither the draw nor (in ``warp_logits``) the sort.
    """
    b, v = logits.shape
    if extras is not None:
        logits = penalize_logits(logits, extras, counts, prompt_mask)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temperature = params.temperature
    if live is not None:
        temperature = jnp.where(live, temperature, 0.0)

    def draw():
        scaled = warp_logits(logits, temperature, params.top_k, params.top_p)
        if extras is None:
            sampled = jax.random.categorical(rng, scaled, axis=-1)
        else:
            sampled = jax.vmap(
                lambda key, row: jax.random.categorical(key, row)
            )(_row_keys(rng, extras, b), scaled)
        return jnp.where(temperature <= 0.0, greedy, sampled.astype(jnp.int32))

    return jax.lax.cond(jnp.any(temperature > 0.0), draw, lambda: greedy)


def greedy_tree_walk(
    greedy: jnp.ndarray,    # [B, N] int32 argmax token per tree node
    tokens: jnp.ndarray,    # [B, N] int32 node tokens (node 0 = root)
    parents: jnp.ndarray,   # [B, N] int32, parents[:, 0] == -1
    n_nodes: jnp.ndarray,   # [B] int32 live node count (>= 1)
):
    """Longest accepted root-to-leaf path under GREEDY acceptance
    (docs/spec_decode_trees.md): walking from the root, a child node is
    accepted iff its draft token equals the argmax of its parent's
    verify logits — at most one child can match, so the walk is
    deterministic. Returns (path [B, N], acc [B]): path[b, :acc] are the
    accepted draft tokens in path order and path[b, acc] is the bonus
    token (the argmax at the last accepted node).

    Nodes are processed in index order; the parent-before-child layout
    (spec_proposer.DraftForest) makes that a topological order, and the
    frontier test ``parents[:, j] == cur`` skips every node off the
    accepted path. On the degenerate chain topology this reproduces the
    chain rule acc = sum(cumprod(drafts == argmax[:, :k])) exactly.

    The third output ``nodes`` [B, N] maps row POSITION to the tree NODE
    whose K/V belongs there after acceptance: nodes[b, i] is the node
    index of the i-th accepted path token (identity for i == 0 and for
    every position past acc) — the engine's in-launch KV path compaction
    gathers pool entries at nodes[b, i] and rewrites them at position i,
    so the kept prefix is contiguous exactly like a chain's.
    """
    b, n = tokens.shape
    rows = jnp.arange(b)
    col = jnp.arange(n, dtype=jnp.int32)[None, :]
    cur = jnp.zeros(b, jnp.int32)
    acc = jnp.zeros(b, jnp.int32)
    path = jnp.zeros((b, n), jnp.int32)
    nodes = jnp.broadcast_to(col.astype(jnp.int32), (b, n))
    for j in range(1, n):
        tok = tokens[:, j]
        ok = (
            (j < n_nodes)
            & (parents[:, j] == cur)
            & (tok == greedy[rows, cur])
        )
        path = jnp.where((col == acc[:, None]) & ok[:, None],
                         tok[:, None], path)
        nodes = jnp.where((col == acc[:, None] + 1) & ok[:, None],
                          jnp.int32(j), nodes)
        cur = jnp.where(ok, j, cur)
        acc = acc + ok.astype(jnp.int32)
    bonus = greedy[rows, cur]
    path = jnp.where(col == acc[:, None], bonus[:, None], path)
    return path, acc, nodes


def speculative_sample_tree(
    logits: jnp.ndarray,    # [B, N, V] verify logits per tree node
    tokens: jnp.ndarray,    # [B, N] int32 node tokens (node 0 = root)
    parents: jnp.ndarray,   # [B, N] int32, parents[:, 0] == -1
    n_nodes: jnp.ndarray,   # [B] int32 live node count
    params: SamplingParams,
    rng: jax.Array,
):
    """Multi-draft rejection sampling over a draft TREE (the SpecInfer /
    recursive-rejection scheme specialized to point-mass proposers,
    docs/spec_decode_trees.md).

    Walking from the root in node-index order, each frontier child with
    draft token d is accepted with probability P_cur(d) / (1 - R) where
    P_cur = softmax(warp(logits_cur)) and R is the mass of this node's
    already-REJECTED sibling drafts (the sequential point-mass residual
    correction); an accepted child advances the walk and resets R. After
    all nodes are processed, one token is sampled from the last accepted
    node's residual (its rejected children masked out, renormalized by
    the categorical) — or its plain warped distribution when every child
    was accepted. The emitted path's marginal law is exactly
    autoregressive sampling from the warped per-position distributions.

    On the degenerate chain topology (parents j-1, one child per node)
    the sibling correction divides by exactly 1.0 and the residual masks
    exactly the rejected draft, so the emitted tokens are BYTE-IDENTICAL
    to :func:`speculative_sample_chain` under the same rng — the shapes
    of both internal draws (u [B, N-1], categorical over [B, N, V])
    match the chain's, which tests/test_spec_tree.py pins.

    Returns (path [B, N], acc [B], nodes [B, N]) with the chain
    function's token contract: path[b, :acc] accepted draft tokens in
    path order, path[b, acc] the residual/bonus sample, entries past acc
    meaningless. ``nodes`` maps row position to accepted tree node like
    :func:`greedy_tree_walk` (identity past acc) for KV path compaction.
    """
    b, n, v = logits.shape
    rep = lambda x: jnp.repeat(x, n)
    warped = warp_logits(
        logits.reshape(b * n, v),
        rep(params.temperature), rep(params.top_k), rep(params.top_p),
    ).reshape(b, n, v)
    probs = jax.nn.softmax(warped, axis=-1)
    r_acc, r_gum = jax.random.split(rng)
    u = jax.random.uniform(r_acc, (b, n - 1))
    rows = jnp.arange(b)
    col = jnp.arange(n, dtype=jnp.int32)[None, :]
    cur = jnp.zeros(b, jnp.int32)
    acc = jnp.zeros(b, jnp.int32)
    path = jnp.zeros((b, n), jnp.int32)
    nodes = jnp.broadcast_to(col.astype(jnp.int32), (b, n))
    rej_mass = jnp.zeros(b, jnp.float32)
    rejected = jnp.zeros((b, n), bool)
    for j in range(1, n):
        tok = tokens[:, j]
        test = (j < n_nodes) & (parents[:, j] == cur)
        p_tok = probs[rows, cur, tok]
        p_adj = p_tok / jnp.maximum(1.0 - rej_mass, 1e-9)
        ok = test & (u[:, j - 1] < p_adj)
        rej = test & ~ok
        path = jnp.where((col == acc[:, None]) & ok[:, None],
                         tok[:, None], path)
        nodes = jnp.where((col == acc[:, None] + 1) & ok[:, None],
                          jnp.int32(j), nodes)
        rejected = rejected.at[:, j].set(rej)
        rej_mass = jnp.where(
            ok, 0.0, jnp.where(rej, rej_mass + p_tok, rej_mass)
        )
        cur = jnp.where(ok, j, cur)
        acc = acc + ok.astype(jnp.int32)
    # residual per NODE: its rejected children's draft tokens masked out.
    # Only the final node's row is selected, but drawing the categorical
    # over the full [B, N, V] keeps the gumbel stream aligned with the
    # chain sampler's w_all draw (byte-identity on chain topologies).
    par_oh = jax.nn.one_hot(parents[:, 1:], n, dtype=jnp.float32)
    tok_oh = jax.nn.one_hot(tokens[:, 1:], v, dtype=jnp.float32)
    rej_w = rejected[:, 1:].astype(jnp.float32)[..., None] * par_oh
    rej_tokens = jnp.einsum("bjn,bjv->bnv", rej_w, tok_oh) > 0.0
    w_all = jnp.where(rej_tokens, -jnp.inf, warped)
    fallback = jax.random.categorical(
        r_gum, w_all, axis=-1
    ).astype(jnp.int32)                                        # [B, N]
    f_at = jnp.take_along_axis(fallback, cur[:, None], axis=1)[:, 0]
    path = jnp.where(col == acc[:, None], f_at[:, None], path)
    return path, acc, nodes


def speculative_sample_chain(
    logits: jnp.ndarray,   # [B, K+1, V] verify-pass logits (float32)
    drafts: jnp.ndarray,   # [B, K] int32 proposed draft tokens
    params: SamplingParams,
    rng: jax.Array,
):
    """Rejection-based speculative SAMPLING over a deterministic draft
    chain (vLLM spec-decode semantics for temperature > 0).

    The n-gram proposer is a point mass q = delta(d_i), so the standard
    accept rule collapses to: accept draft d_i with probability P_i(d_i);
    at the first rejection emit one sample from the residual (P_i with the
    draft removed, renormalized); if all K drafts are accepted emit a
    bonus sample from P_K. The marginal law of the emitted prefix is
    EXACTLY autoregressive sampling from the warped per-position
    distributions P_i = softmax(warp(logits_i)) — same warp (temperature /
    top-k / top-p) sample_tokens uses, so speculated and plain slots draw
    from an identical law.

    Returns (tokens [B, K+1], acc [B]): tokens[b, :acc[b]] are the accepted
    drafts and tokens[b, acc[b]] is the residual/bonus sample; entries past
    acc[b] are meaningless (the engine emits acc+1 per round).
    """
    b, k1, v = logits.shape
    k = k1 - 1
    rep = lambda x: jnp.repeat(x, k1)
    warped = warp_logits(
        logits.reshape(b * k1, v),
        rep(params.temperature), rep(params.top_k), rep(params.top_p),
    ).reshape(b, k1, v)
    probs = jax.nn.softmax(warped, axis=-1)
    r_acc, r_gum = jax.random.split(rng)
    u = jax.random.uniform(r_acc, (b, k))
    p_draft = jnp.take_along_axis(
        probs[:, :k], drafts[..., None].astype(jnp.int32), axis=-1
    )[..., 0]                                                      # [B, K]
    accept = u < p_draft
    acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)
    # fallback samples per position: residual (draft masked out) for the
    # K draft positions, plain bonus for position K. A row whose residual
    # is empty (P(d) == 1) is unreachable: u < 1 always accepts it.
    draft_hot = jax.nn.one_hot(drafts, v, dtype=bool)              # [B, K, V]
    w_resid = jnp.where(draft_hot, -jnp.inf, warped[:, :k])
    w_all = jnp.concatenate([w_resid, warped[:, k:]], axis=1)      # [B, K+1, V]
    fallback = jax.random.categorical(
        r_gum, w_all, axis=-1
    ).astype(jnp.int32)                                            # [B, K+1]
    f_at = jnp.take_along_axis(fallback, acc[:, None], axis=1)[:, 0]
    tokens = jnp.concatenate(
        [drafts.astype(jnp.int32), fallback[:, k:]], axis=1
    )
    tokens = tokens.at[jnp.arange(b), acc].set(f_at)
    return tokens, acc
