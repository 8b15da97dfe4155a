"""Warmup shape registry: compile the serve loop's XLA key space BEFORE the
serve fence (docs/static_analysis.md TPU6xx, docs/slo_scheduling.md).

Every serve-time XLA compile is a 100-1000 ms stall of the loop thread that
masquerades as scheduling tail — PR 6's loadtest measured each unwarmed
shape costing 100-1000 ms mid-run, and PR 10's tiering work re-discovered
the same class on resume-commit shapes. The fix was an inline warmup block
private to the loadtest; this module is that block extracted, generalized
over the ENGINE'S OWN configuration (prefill buckets, prefix block, page
size, scheduler), and made a registry three consumers share:

- engine startup (``LLMEngineCore.warmup()``, e.g. at endpoint load),
- a replica's re-admission to the ring (llm/replica.py),
- tests (the warmup-coverage suite proves a warmed engine serves in-class
  traffic with ZERO further compiles under the strict compile sentry).

``WARMUP_COVERED`` is the machine-readable half: the engine jit entries
whose key space the sweep drives. The static analyzer (TPU603,
analyze/rules_compile.py) parses it FROM SOURCE — keep it a literal — and
requires every ``"serve"``-role entry of the engine's ``__compile_keys__``
to appear here, so a new dispatch-path jit entry cannot land without
either a warmup extension or an explicit role reclassification.

What the sweep enumerates (derived from engine attributes, never
hard-coded): cold prefill per bucket; radix-hit assemble + tail chunk per
bucket; every resume-commit final-segment length 1..block per hit bucket
(preempted histories resume with arbitrary tails); multi-segment tails
(partially evicted prefixes replay tails longer than one block) - the
bucketed sweep is the dense engine's compile surface, a ragged engine
(paged, state) runs the same requests through the one static shape of its
step; power-of-two CoW
copy buckets (and, on int8 pools, their scale-row copies); a spec-decode
round when speculation is on. Coverage assumption, stated plainly: the
sweep warms the PLAIN-SAMPLING serve surface (the first-token program,
``_first_token_jit``, has ONE shape, and every swept request runs it) —
sampling-extras / guided / logprob variants trace on first use (each is
one bounded compile per variant, not a per-request key), and the compile
sentry attributes them when armed.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

# The engine jit entries whose compile keys the sweep drives (conditional
# on the engine's configuration: a dense engine has no paged or ragged
# entries to warm, a paged or state engine no prefill programs). Parsed
# from source by analyze/rules_compile.py (TPU603) — MUST stay a literal;
# the analyzer's build-time mirror is consistency-tested in
# tests/test_analyze_compile.py.
WARMUP_COVERED = frozenset({
    "_prefill_jit",
    "_prefill_ring_jit",
    "_prefill_pipeline_jit",
    "_prefill_chunk_first_jit",
    "_prefill_chunk_jit",
    "_assemble_prefix_jit",
    "_insert_jit",
    "_merge_rows_jit",
    "_decode_chunk_jit",
    "_decode_paged_chunk_jit",
    "_first_token_jit",
    "_set_sampling_row_jit",
    "_spec_chunk_jit",
    "_ragged_paged_jit",
    "_ragged_state_jit",
    "_ragged_unpack_jit",
    "_ragged_chain_jit",
})


def _ids(seed: int, n: int, vocab: int) -> List[int]:
    """Deterministic token content: the same (seed, n) always yields the
    same ids, so a stored radix prefix is hit by the later sweep steps
    that rely on it."""
    lim = max(2, min(250, vocab - 2))
    return [(seed * 13 + i * 11) % lim + 1 for i in range(n)]


def _tail(seed: int, n: int, vocab: int) -> List[int]:
    lim = max(2, min(250, vocab - 2))
    return [(seed * 53 + j * 3) % lim + 1 for j in range(n)]


def warmup_plan(engine, full: bool = True) -> List[Dict[str, Any]]:
    """Enumerate the warmup REQUEST sweep for this engine's configuration:
    a list of ``{"prompt_ids": [...], "max_new_tokens": n}`` specs in the
    order they must run (earlier steps seed the radix runs later steps
    hit). ``full=False`` keeps only the per-bucket cold+hit pass — the
    cheap startup subset; the full sweep is what the zero-recompile
    certification runs."""
    vocab = max(engine._vocab, 8)
    buckets = list(engine._buckets)
    if buckets[-1] < engine.max_seq_len:
        # _bucket_for falls back to max_seq_len for prompts past the last
        # configured bucket — that implicit bucket is part of the compile
        # surface too (the sentry caught exactly this hole in testing)
        buckets.append(engine.max_seq_len)
    prefix = engine._prefix
    block = prefix.block if prefix is not None else 0
    plan: List[Dict[str, Any]] = []

    def req(ids: List[int], max_new: int = 2) -> None:
        if 0 < len(ids) < engine.max_seq_len:
            plan.append({"prompt_ids": ids, "max_new_tokens": max_new})

    def bucket_prefix_len(b: int) -> int:
        # largest block multiple that leaves room for a sub-block tail in
        # the same bucket (0 = no stored prefix at this bucket)
        return ((b - block) // block) * block if block and b > block else 0

    # 1) cold prefill per bucket + radix store/hit per bucket: the repeat
    # runs the hit path (gather/assemble + tail chunk) at that bucket
    for b in buckets:
        p = bucket_prefix_len(b)
        head = _ids(b, p, vocab)
        reps = 2 if (p and prefix is not None) else 1
        for rep in range(reps):
            tail = [
                (rep * 37 + j * 5 + b) % max(2, min(250, vocab - 2)) + 1
                for j in range(max(1, min(b - p, block or b) - 1))
            ]
            req(head + tail)
    if not full or prefix is None:
        return plan

    # 2) resume-commit tails, single-page: a preempted request's history
    # (and a partially evicted prefix) can resume with ANY final-segment
    # length 1..block, and the commit's tail slice/scatter compiles once
    # per (bucket, length-class) — the exact class PR 6 measured at
    # 100-200 ms per unwarmed length on the loop thread
    for b in buckets:
        p = bucket_prefix_len(b)
        if p < block:
            continue
        head = _ids(b, p, vocab)
        for t in range(1, block + 1):
            req(head + _tail(t, t, vocab))

    # 4) multi-segment tails: when eviction shortened a stored run, a hit
    # replays a tail LONGER than one block — non-final chunk segments
    # (with_logits=False) are a distinct trace per bucket
    if block:
        seed_run = _ids(7, 2 * block - 1, vocab)
        req(seed_run)
        heads = [seed_run[:block]]
        heads += [
            _ids(b, bucket_prefix_len(b), vocab)
            for b in buckets
            if bucket_prefix_len(b) >= block
        ]
        for i, head in enumerate(heads):
            req(head + _tail(100 + i, block + 1, vocab))

    # 5) speculation: one longer greedy request so the spec draft/verify
    # chunk (and its commit bookkeeping) traces before the fence
    if engine._speculation is not None:
        k = engine._spec_k
        req(
            _ids(5, max(1, 2 * block or 8), vocab),
            max_new=max(4, 2 * engine.decode_steps * (k + 1)),
        )
    return plan


def warm_ragged_variants(engine) -> int:
    """Compile every (decode window, spec-row) ragged launch variant for
    this engine's configuration with null-row operands — see the call site
    in :func:`run_warmup`. Returns the number of launches run. Operand
    construction mirrors ``engine._dispatch_ragged_device_inner`` one for
    one (dtype-strong numpy uploads, same None-ness per variant); every
    scatter lands in the dead null page (no state slot is touched), so
    the pools round-trip through the donated call value-unchanged."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b = engine.max_batch
    k_ = engine._spec_k
    windows = []
    p = 1
    while p <= engine._ragged_steps_cap:
        windows.append(p)
        p *= 2
    spec_opts = [False] + ([True] if engine._speculation else [])
    sampling = engine._batch_sampling()
    lora = (
        jnp.asarray(np.zeros(b, np.int32)) if engine._lora_enabled else None
    )
    ran = 0

    def key():
        return engine._next_rng()

    def unpack(steps, spec_on=False):
        # the variant's unpack program (the launch's ONE upload sliced back
        # into operands, engine._upload_ragged_operands), over a null buffer
        layout, total = engine._ragged_layouts[(steps, False, spec_on)]
        engine._ragged_unpack_jit(
            np.zeros(total, np.int32), layout, engine._chain_null
        )

    def chain_of(sampled):
        # the rows' pending tokens for a launch behind this one
        # (engine._dispatch_ragged_device_inner): the window's last row,
        # a finishing prompt's first token put in
        if engine.pipeline_depth > 1:
            engine._ragged_chain_jit(
                sampled, engine._first_null, np.int32(b)
            )

    def spec_args(on):
        if not on:
            return None
        return (
            jnp.asarray(np.zeros(b, bool)),
            jnp.asarray(np.zeros(b, bool)),
            jnp.asarray(np.zeros((b, k_), np.int32)),
            jnp.asarray(np.zeros((b, k_ + 1), np.int32)),
            key(),
        )

    if engine.paged_cache is not None:
        cache = engine.paged_cache
        # the launch's two token axes: per-token operands on the compact
        # one, the ancestor lists in the kernel's aligned view
        dense, tpad = engine._ragged_dense, engine._ragged_tpad
        page_table = jnp.asarray(
            np.zeros((b, engine._pages_per_seq), np.int32)
        )

        def tree_args(on):
            # draft-tree verify variant (docs/spec_decode_trees.md): the
            # tree arrays are FIXED-SHAPE ([B, k+1] topology + [tpad, k+1]
            # ancestor lists) so the whole topology space is ONE compile
            # key — warmed with the plain-causal sentinel (-2), which
            # drives the tree kernel variant over a null launch
            if not (on and getattr(engine, "_spec_tree", False)):
                return None
            anc = np.full((tpad, k_ + 1), -1, np.int32)
            anc[:, 0] = -2
            parents = np.zeros((b, k_ + 1), np.int32)
            parents[:, 0] = -1
            return (
                jnp.asarray(np.zeros((b, k_ + 1), np.int32)),
                jnp.asarray(parents),
                jnp.asarray(np.full(b, k_ + 1, np.int32)),
                jnp.asarray(anc),
            )
        # the kernel's work plan at its one static length, owned by no row
        items = (
            jnp.asarray(np.full(engine._ragged_items, -1, np.int32)),
            jnp.asarray(np.zeros(engine._ragged_items, np.int32)),
        ) if engine._ragged_kernel else (None, None)
        for steps in windows:
            for spec_on in spec_opts:
                unpack(steps, bool(spec_on))
                chain = None
                if steps > 1:
                    chain = (
                        jnp.stack([key() for _ in range(steps - 1)]),
                        jnp.asarray(np.zeros((steps - 1, b), bool)),
                        jnp.asarray(np.zeros((steps - 1, b), np.int32)),
                        jnp.asarray(np.zeros((steps - 1, b), np.int32)),
                    )
                with cache.dispatch_lock:
                    (
                        sampled, _logits, cache.k, cache.v_carry,
                        new_ks, new_vs, _counts, _lp, _gs, _sg, _sa,
                    ) = engine._ragged_paged_jit(
                        engine.params,
                        jnp.asarray(np.zeros(dense, np.int32)),
                        jnp.asarray(np.zeros(dense, np.int32)),
                        jnp.asarray(np.zeros(dense, np.int32)),
                        jnp.asarray(np.zeros(dense, bool)),
                        jnp.asarray(np.full(dense, tpad, np.int32)),
                        jnp.asarray(np.zeros(b, np.int32)),
                        cache.k, cache.v_carry, cache.k_scale, cache.v_scale,
                        page_table,
                        jnp.asarray(np.zeros(b, np.int32)),
                        jnp.asarray(np.zeros(b, np.int32)),
                        jnp.asarray(np.zeros(b, np.int32)),
                        jnp.asarray(np.zeros(dense, np.int32)),
                        jnp.asarray(np.zeros(dense, np.int32)),
                        items[0], items[1],
                        jnp.asarray(np.zeros(b, bool)),
                        sampling, key(), lora,
                        None, None, None, None, None,
                        want_lp=False,
                        spec=spec_args(spec_on),
                        chain=chain,
                        tree=tree_args(spec_on),
                    )
                    if engine._paged_quant:
                        cache.k_scale = new_ks
                        cache.v_scale = new_vs
                chain_of(sampled)
                jax.block_until_ready(sampled)
                ran += 1
    else:
        # state cache (docs/state_cache.md): the one token axis (the
        # kernels' view) is a static size and there are no spec rows, so
        # the decode window is the only compile key. Null rows (row_lens 0,
        # chain masks False) touch no slot: the donated pools come back
        # value-unchanged
        cache = engine.state_cache
        tpad = engine._ragged_tpad
        for steps in windows:
            unpack(steps)
            chain = None
            if steps > 1:
                chain = (
                    jnp.stack([key() for _ in range(steps - 1)]),
                    jnp.asarray(np.zeros((steps - 1, b), bool)),
                )
            with cache.dispatch_lock:
                (
                    sampled, _logits, cache.s, cache.z, _counts, _lp, _gs,
                ) = engine._ragged_state_jit(
                    engine.params,
                    jnp.asarray(np.zeros(tpad, np.int32)),
                    jnp.asarray(np.zeros(tpad, np.int32)),
                    jnp.asarray(np.zeros(tpad, np.int32)),
                    jnp.asarray(np.zeros(tpad, bool)),
                    jnp.asarray(np.zeros(b, np.int32)),
                    cache.s, cache.z,
                    jnp.asarray(np.zeros(b, np.int32)),
                    jnp.asarray(np.zeros(b, np.int32)),
                    jnp.asarray(np.zeros(b, np.int32)),
                    jnp.asarray(np.zeros(b, bool)),
                    jnp.asarray(np.zeros(b, bool)),
                    sampling, key(),
                    None, None, None, None, None,
                    want_lp=False,
                    chain=chain,
                )
            chain_of(sampled)
            jax.block_until_ready(sampled)
            ran += 1
    return ran


async def run_warmup(
    engine,
    full: bool = True,
    extra_prompts: Optional[List[List[int]]] = None,
    fence: bool = True,
) -> Dict[str, Any]:
    """Drive the warmup sweep against a live engine, then (optionally) set
    the compile sentry's warmup fence: every XLA compile after the fence
    is attributed to serving and — in strict mode — raises. Returns
    ``{"requests", "cow_buckets", "fenced"}``.

    ``extra_prompts`` lets a caller append workload-specific prompts (the
    loadtest replays its trace mix twice so production-shaped shared
    prefixes run warm); each is swept twice, cold then radix-hit.
    """
    import jax.numpy as jnp

    from . import compile_sentry
    from .engine import GenRequest

    plan = warmup_plan(engine, full=full)
    for spec in plan:
        request = GenRequest(
            prompt_ids=spec["prompt_ids"],
            max_new_tokens=spec["max_new_tokens"],
        )
        async for _ in engine.generate(request):
            pass
    if extra_prompts:
        for rep in range(2):  # second pass runs the warm radix path
            for ids in extra_prompts:
                request = GenRequest(
                    prompt_ids=list(ids), max_new_tokens=2
                )
                async for _ in engine.generate(request):
                    pass

    # copy-on-write program warmup: apply_pending_cow pads pair lists to
    # power-of-two buckets (llm/shapes.py) and each bucket is a distinct
    # DONATED program that would otherwise compile on the dispatch path
    # mid-run. Null-page self-copies are no-ops by construction. On int8
    # pools the scale pools CoW in the same batch — warm those programs too.
    cow = 0
    cache = engine.paged_cache
    if full and cache is not None:
        # bound by max_seq_len, not the last configured bucket: prompts in
        # the implicit fallback bucket hold pages_needed(max_seq_len)
        # pages, and their resumes can CoW-burst past a smaller bound
        max_pairs = 2 * cache.pool.pages_needed(engine.max_seq_len)
        p = 1
        while p <= max_pairs:
            zeros = jnp.zeros((p,), jnp.int32)
            with cache.dispatch_lock:
                cache.k = cache._copy_pages(cache.k, zeros, zeros)
                cache.v = cache._copy_pages(cache.v, zeros, zeros)
                if cache.k_scale is not None:
                    cache.k_scale = cache._copy_pages(
                        cache.k_scale, zeros, zeros
                    )
                    cache.v_scale = cache._copy_pages(
                        cache.v_scale, zeros, zeros
                    )
            cow += 1
            p *= 2

    # KV-transport movement programs (docs/disaggregation.md): a
    # disaggregated engine's serve path adds the ship export (the
    # host-tier demote gather verbatim, pow2-padded page lists) and the
    # receive import (the promotion staging scatter, pow2-padded slabs) —
    # both would otherwise compile at the first ship/receive mid-serve.
    # Null-page round trips are dead by construction: the gather reads
    # page 0 and the scatter writes it back, and the fence records reap
    # below so the drained audit stays clean.
    ship_buckets = 0
    if full and cache is not None and (
        getattr(engine, "_kv_transport", None) is not None
    ):
        max_pages = cache.pool.pages_needed(engine.max_seq_len)
        p = 1
        while True:
            pages = [0] * p
            slabs = cache.export_pages(pages)
            cache.import_pages(
                slabs["hk"], slabs["hv"], pages,
                slabs.get("hk_scale"), slabs.get("hv_scale"),
            )
            ship_buckets += 1
            if p >= max_pages:
                break
            p *= 2
        cache.reap_promotions(force=True)

    # multi-step / spec-as-row ragged launch variants
    # (docs/ragged_attention.md): the per-launch decode window buckets to a
    # power of two (llm/shapes.decode_steps_bucket) and spec-verify rows
    # toggle the k+1 logit-gather + acceptance trace — each (window, spec)
    # pair is a distinct executable on the serve path. The traffic sweep
    # above only reliably drives the q=1 no-spec launch (sequential
    # requests rarely overlap), so the remaining variants warm DIRECTLY
    # with null-row operands: every write coordinate targets the dead null
    # page (page 0) / a dead position, every mask is False, and the pools
    # round-trip through the donated call like any dispatch.
    if full and engine._ragged:
        warm_ragged_variants(engine)

    await engine.wait_drained()
    fenced = False
    if fence and full and compile_sentry.enabled():
        # only the FULL sweep certifies: fencing after the reduced
        # startup pass would declare a knowingly-incomplete surface
        # warmed — resume tails and CoW programs would then count (and in
        # strict mode raise) as serve-time violations on a healthy engine.
        # Callers that deliberately fence a partial sweep (tests proving
        # the fence machinery) call compile_sentry.get().fence() directly.
        compile_sentry.get().fence()
        fenced = True
    return {
        "requests": len(plan) + 2 * len(extra_prompts or []),
        "cow_buckets": cow,
        "ship_buckets": ship_buckets,
        "fenced": fenced,
    }
