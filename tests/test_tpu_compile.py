"""What only the chip's compiler shows, without a chip: the paged step
compiled for a described TPU v5e (the `on-chip-measurement` guide, section
2). ISSUE 25's fault was of this kind: with the stacked KV pools in the layer
scan's carry, a scatter whose update window is ``[Hkv, D]`` makes XLA keep
the stack in the scatter's layout and convert ALL of it to the kernels'
layout in front of every attention call; CPU tests and the Pallas interpreter
cannot see a layout. The topology is described inside a fixture, never at
import: every xdist worker collects the same tests and one loads libtpu."""

import re

import jax
import jax.numpy as jnp
import pytest

from clearml_serving_tpu import models
from clearml_serving_tpu.ops import paged_attention as pa

# kernel-aligned widths at a small depth: head_dim 128, 16-token bf16 pages (32
# for int8). The pools are of a deployment's size (147 MB each; only shapes
# are compiled): a stack that fits the chip's fast memory is prefetched into
# it whole, which no real pool is.
LAYERS, HKV, PAGES, D = 3, 2, 6000, 128
ROWS, TOKENS, PAGES_PER_SEQ = 4, 16, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip("no v5e:2x2 topology can be described here: {}".format(e))
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(lowered):
    """The HLO of a lowered program compiled for the described chip. Such a
    compile could be written to the persistent cache and never read back
    without a chip (the next run would warn): keep it out."""
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def _compiled_step(one_chip, monkeypatch, quant):
    """One ragged pass and two chained decode passes over donated stacked
    pools, as the engine's ``_ragged_paged_step`` chains them, compiled for
    the described chip with the Pallas kernels routed in."""
    monkeypatch.setattr(pa, "paged_kernel_unsupported_reason",
                        lambda *a, **k: None)
    cfg = dict(vocab_size=512, dim=512, n_layers=LAYERS, n_heads=4,
               n_kv_heads=HKV, head_dim=D, ffn_dim=512, scan_layers=True,
               dtype="bfloat16")
    page = 16
    if quant:
        cfg["kv_quant"], page = "int8", 32
    bundle = models.build_model("llama", cfg)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def i32(*shape):
        return on_chip(shape, jnp.int32)

    params = jax.tree.map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0))))
    stack = (LAYERS, HKV, PAGES, page, D)
    pools = [on_chip(stack, jnp.int8 if quant else jnp.bfloat16)] * 2
    if quant:
        pools += [on_chip(stack[:-1], jnp.float32)] * 2

    def step(params, pools, tok, valid, row_last, table, per_row, per_tok,
             blocks, chain_coords):
        names = ("k_scales", "v_scales")
        out = bundle.forward_ragged(
            params, tok, tok, tok, valid, tok, row_last, pools[0], pools[1],
            table, per_row, per_row, per_row, per_tok, per_tok, blocks,
            blocks, **dict(zip(names, pools[2:])))
        nxt = jnp.argmax(out[0], -1).astype(jnp.int32)

        def body(carry, coords):
            nxt, pools, i = carry
            # rows masked out of a chained pass, as the engine masks them
            out = bundle.decode_paged(
                params, nxt, pools[0], pools[1], table, per_row + i,
                coords, coords, active=coords > 0,
                **dict(zip(names, pools[2:])))
            nxt = jnp.argmax(out[0], -1).astype(jnp.int32)
            return (nxt, tuple(out[1:]), i + 1), nxt

        (_, pools, _), toks = jax.lax.scan(
            body, (nxt, tuple(out[1:]), jnp.int32(0)), chain_coords)
        return toks, pools

    lowered = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, i32(TOKENS), on_chip((TOKENS,), jnp.bool_), i32(ROWS),
        i32(ROWS, PAGES_PER_SEQ), i32(ROWS), i32(TOKENS),
        i32(TOKENS // 8), i32(2, ROWS))
    return _compiled_text(lowered), page


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_the_v5e_step_updates_the_stacked_pools_in_place(
        one_chip, monkeypatch, quant):
    hlo, page = _compiled_step(one_chip, monkeypatch, quant)
    assert hlo.count("tpu_custom_call") >= 2      # both attention kernels
    layer = "{},{},{},{}".format(HKV, PAGES, page, D)
    shape = re.compile(
        r"^\s*(?:ROOT )?%?(\S+) = \w+\[(?:\d+,)?" + layer + r"\]\S* ([\w\-]+)\(")
    movers = []
    for line in hlo.splitlines():
        m = shape.match(line)
        if not m:
            continue
        name, op = m.groups()
        # what may have a pool's shape: the buffer itself on its way through
        # the loops, and the write into it
        writes = op in ("scatter", "fusion") and "kv_write" in line
        if op not in ("parameter", "get-tuple-element", "bitcast") and not writes:
            movers.append((name, op))
    assert not movers, movers
    # and the stack keeps the kernels' row-major layout from end to end
    stack_shapes = set(re.findall(
        r"\w+\[{},{}\](\{{[\d,]+)".format(LAYERS, layer), hlo))
    assert stack_shapes == {"{4,3,2,1,0"}, stack_shapes


# ------------------------------------- the ragged pass's two token axes

@pytest.mark.parametrize("experts", [0, 8], ids=["mistral7b", "mixtral8x7b"])
def test_the_dense_layers_multiply_the_compact_axis(one_chip, monkeypatch, experts):
    """ISSUE 42: a ragged pass at the K/V cells' widths (d 4096, 32/8 heads x
    128, FFN 14336, int8 weights; 128 packed tokens, 32 rows, so a view of 352)
    compiled for the described v5e. Outside the attention scope no instruction
    of the layer loop has 352 rows, and none re-lays a layer's expert weights
    out of the scan (at 128 tokens the compiler copied both [8, 4096, 14336]
    stacks twice a layer while the expert axis was free on the weights alone:
    ``_ffn_moe_dropless``)."""
    monkeypatch.setattr(pa, "paged_kernel_unsupported_reason",
                        lambda *a, **k: None)
    layers, rows, dense, page, hkv = 2, 32, 128, 16, 8
    cfg = dict(vocab_size=32000, dim=4096, n_layers=layers, n_heads=32,
               n_kv_heads=hkv, head_dim=D, ffn_dim=14336, scan_layers=True,
               dtype="bfloat16")
    if experts:
        cfg.update(n_experts=experts, moe_top_k=2)
    bundle = models.build_model("llama", cfg)

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0),
                                           weight_quant="int8")))
    pool = on_chip((layers, hkv, 2000, page, D), jnp.bfloat16)
    view = pa.ragged_view_tokens(dense, rows)
    assert view == 352
    items = pa.ragged_item_count(
        rows, view, pa.ragged_query_tile(hkv, 4, D, jnp.bfloat16))

    def step(params, k, v, tok, valid, row_last, table, per_row, item):
        return bundle.forward_ragged(
            params, tok, tok, tok, valid, tok, row_last, k, v, table, per_row,
            per_row, per_row, tok, tok, item, item)

    hlo = _compiled_text(jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pool, pool, on_chip((dense,)), on_chip((dense,), jnp.bool_),
        on_chip((rows,)), on_chip((rows, 256)), on_chip((rows,)),
        on_chip((items,))))
    assert "ragged_paged_attention" in hlo
    shaped = re.compile(
        r"^\s*(?:ROOT )?%?(\S+) = \(?(\w+)\[([\d,]+)\]\S* ([\w\-]+)\(")
    wide, moved, computation = [], [], None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?(\S+) \(.*\) -> .* \{", line)
        if head:
            computation = head.group(1)
        m = shaped.match(line)
        # what runs is what the loops and the entry hold; a fused
        # computation's instructions are its fusion's own business
        if not m or computation is None or "fused_computation" in computation:
            continue
        name, _, dims, op = m.groups()
        dims = [int(n) for n in dims.split(",")]
        if op in ("parameter", "get-tuple-element", "bitcast", "tuple"):
            continue
        # (the view's side of the token map is a vector of 352, once a pass)
        if view in dims and len(dims) > 1 and "/attn/" not in line:
            wide.append((name, op, dims))
        if experts and dims[-3:] in ([experts, 4096, 14336],
                                     [experts, 14336, 4096]):
            moved.append((name, op, dims))
    assert not wide, wide
    assert not moved, moved


# ------------------------------------------- the decode kernel's work plan

@pytest.mark.parametrize("kind, page", [("bf16", 16), ("int8", 32)])
def test_the_decode_kernel_compiles_at_the_cells_shapes(one_chip, kind, page):
    """ISSUE 28: the decode kernel at the benchmark's shapes (32 rows, Hkv 8,
    G 4, D 128, 4096 tokens a row, a Mistral-7B deployment's pool of 28000
    tokens over 32 layers) lowers for the described v5e, as one custom call
    under its trace name; its scratch is what the shapes say: two slots of a
    32-page block of all 8 heads a side (4 MB), one DMA semaphore per slot and
    side, one scalar for the walk."""
    rows, hkv, g, layers, tokens = 32, 8, 4, 32, 28000
    pages_per_seq, pages = 4096 // page, tokens // page
    dtype = jnp.int8 if kind == "int8" else jnp.bfloat16

    def on_chip(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = on_chip((layers, hkv, pages, page, D), dtype)
    scales = {}
    if kind == "int8":
        scale = on_chip((layers, hkv, pages, page), jnp.float32)
        scales = {"k_scale": scale, "v_scale": scale}
    operands = (on_chip((rows, hkv, g, D), jnp.bfloat16), pool, pool,
                on_chip((rows, pages_per_seq), jnp.int32),
                on_chip((rows,), jnp.int32), on_chip((), jnp.int32))

    def attend(q, k, v, table, lengths, layer, **scales):
        return pa.paged_attention(q, k, v, table, lengths, layer=layer, **scales)

    block = pa.decode_pages_per_block(hkv, D, page, pages_per_seq, dtype)
    assert block * page == 512
    call, = [e for e in jax.make_jaxpr(attend)(*operands, **scales).eqns
             if e.primitive.name == "pallas_call"]
    n_scratch = call.params["grid_mapping"].num_scratch_operands
    scratch = [(v.aval.shape, str(v.aval.dtype))
               for v in call.params["jaxpr"].invars[-n_scratch:]]
    buf = ((2, hkv, 512, D), jnp.dtype(dtype).name)
    assert scratch[:2] == [buf, buf]
    assert [shape for shape, _ in scratch[2:]] == [(2, 2), (1,)]
    assert call.params["grid_mapping"].grid == (rows,)

    hlo = _compiled_text(jax.jit(attend).lower(*operands, **scales))
    assert hlo.count("tpu_custom_call") == 1
    assert "paged_attention_decode" in hlo


# ------------------------------------------- the ragged kernel's work plan

def _ragged_operands(one_chip, kind, page, pages_per_seq=None, items=None):
    """The ragged kernel's operands at the benchmark's shapes: 352 flat
    tokens, 32 rows, Hkv 8, G 4, D 128, a Mistral-7B deployment's pool of
    28000 tokens over 32 layers, rows of 4096 tokens."""
    rows, tokens, hkv, g, layers = 32, 352, 8, 4, 32
    pages_per_seq = pages_per_seq or 4096 // page
    dtype = jnp.int8 if kind == "int8" else jnp.bfloat16

    def on_chip(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = on_chip((layers, hkv, 28000 // page, page, D), dtype)
    scales = {}
    if kind == "int8":
        scale = on_chip((layers, hkv, 28000 // page, page), jnp.float32)
        scales = {"k_scale": scale, "v_scale": scale}
    tile = pa.ragged_query_tile(hkv, g, D, jnp.bfloat16)
    items = items or pa.ragged_item_count(rows, tokens, tile)
    operands = (on_chip((tokens, hkv, g, D), jnp.bfloat16), pool, pool,
                on_chip((rows, pages_per_seq)), on_chip((rows,)),
                on_chip((rows,)), on_chip((rows,)), on_chip((items,)),
                on_chip((items,)), on_chip(()))

    def attend(q, k, v, table, kv_lens, starts, row_lens, item_rows, item_q0,
               layer, **scales):
        return pa.ragged_paged_attention(
            q, k, v, table, kv_lens, starts, row_lens, item_rows=item_rows,
            item_q0=item_q0, layer=layer, **scales)

    return attend, operands, scales


@pytest.mark.parametrize("kind, page", [("bf16", 16), ("int8", 32)])
def test_the_ragged_kernel_compiles_at_the_cells_shapes(one_chip, kind, page):
    """ISSUE 30: the ragged kernel at the benchmark's shapes lowers for the
    described v5e as ONE custom call under its trace name. The plan is what
    the shapes say: a grid step per work item (32 rows + 352 // 128 further
    tiles = 34), a tile of 128 queries in four sub tiles of 32 (128 MXU rows
    a head), two slots of a 512-token block of all 8 heads a side, two q
    slots and the out buffer head-major, the tile's flash state, one DMA
    semaphore per (slot, side), per q slot and for the out copies, four
    scalars for the walk."""
    attend, operands, scales = _ragged_operands(one_chip, kind, page)
    call, = [e for e in jax.make_jaxpr(attend)(*operands, **scales).eqns
             if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (34,)
    n_scratch = call.params["grid_mapping"].num_scratch_operands
    scratch = [(v.aval.shape, str(v.aval.dtype))
               for v in call.params["jaxpr"].invars[-n_scratch:]]
    pool = jnp.dtype(jnp.int8 if kind == "int8" else jnp.bfloat16).name
    assert scratch[:7] == [
        ((2, 8, 512, D), "bfloat16"), ((8, 512, D), "bfloat16"),
        ((2, 8, 512, D), pool), ((2, 8, 512, D), pool),
        ((4, 8, 128, 1), "float32"), ((4, 8, 128, 1), "float32"),
        ((4, 8, 128, D), "float32")]
    assert [shape for shape, _ in scratch[7:]] == [(2, 2), (2,), (1,), (4,)]

    hlo = _compiled_text(jax.jit(attend).lower(*operands, **scales))
    assert hlo.count("tpu_custom_call") == 1
    assert "ragged_paged_attention" in hlo


@pytest.mark.parametrize("pages_per_seq", [256, 272])
def test_the_smem_estimate_agrees_with_the_compiler_on_the_work_plan(
        one_chip, pages_per_seq):
    """paged_kernel_smem_bytes against the v5e compiler for the ragged
    kernel's scalar operands (page table, three row vectors, layer, the
    plan's two vectors, the walk's counters), at the cells' page tables
    ([32, 256] Mistral, [32, 272] Mixtral): the longest plan the estimate
    lets through compiles, and one 1,024 items longer (8 KB) does not, for
    want of scalar memory."""
    def estimate(items):
        return pa.paged_kernel_smem_bytes(32, pages_per_seq, 352, 0, items)

    fit = max(n for n in range(120000, 132000, 8)
              if estimate(n) <= pa.SMEM_BYTES)
    attend, operands, _ = _ragged_operands(
        one_chip, "bf16", 16, pages_per_seq, items=fit)
    assert "ragged_paged_attention" in _compiled_text(
        jax.jit(attend).lower(*operands))
    attend, operands, _ = _ragged_operands(
        one_chip, "bf16", 16, pages_per_seq, items=fit + 1024)
    assert estimate(fit + 1024) > pa.SMEM_BYTES
    with pytest.raises(Exception, match="smem"):
        _compiled_text(jax.jit(attend).lower(*operands))
    # the engine's own plan of 34 items is well inside (its page table is
    # nearly all of the 53.5 KB)
    assert estimate(34) < pa.SMEM_BYTES // 16


# ------------------------------------------------- the state cache's step

def test_the_v5e_state_step_updates_the_state_pools_in_place(
        one_chip, monkeypatch):
    """ISSUE 26: the state pools ride the layer scan's carry like the K/V
    stacks. One ragged pass and two chained decode passes over donated
    pools of a deployment's slot size, compiled for the described chip with
    both retention kernels routed in: no operation but the buffers' own way
    through the loops may have a pool's shape, and both pools keep the
    kernels' row-major layout (z's rows are padded to whole tiles for that:
    with 65 the compiler picks another layout and copies the stack)."""
    from clearml_serving_tpu.ops import power_retention as pr

    monkeypatch.setattr(pr, "retention_kernel_unsupported_reason",
                        lambda *a, **k: None)
    slots, tokens = 16, 48
    cfg = dict(vocab_size=512, dim=512, n_layers=LAYERS, n_heads=4,
               n_kv_heads=HKV, head_dim=D, ffn_dim=512, scan_layers=True,
               dtype="bfloat16", attention="power_retention", qk_norm=True)
    bundle = models.build_model("llama", cfg)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def i32(*shape):
        return on_chip(shape, jnp.int32)

    params = jax.tree.map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0))))
    s_shape, z_shape = pr.state_shapes(LAYERS, slots, HKV, D)

    def step(params, s_pool, z_pool, tok, valid, row_last, per_row, reset,
             chain_mask):
        logits, s_pool, z_pool = bundle.forward_ragged_state(
            params, tok, tok, tok, valid, row_last, s_pool, z_pool, per_row,
            per_row, reset)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)

        def body(carry, mask):
            nxt, s_pool, z_pool, i = carry
            logits, s_pool, z_pool = bundle.decode_state(
                params, nxt, s_pool, z_pool, per_row + i, mask)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (nxt, s_pool, z_pool, i + 1), nxt

        (_, s_pool, z_pool, _), toks = jax.lax.scan(
            body, (nxt, s_pool, z_pool, jnp.int32(0)), chain_mask)
        return toks, s_pool, z_pool

    lowered = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, on_chip(s_shape, jnp.float32), on_chip(z_shape, jnp.float32),
        i32(tokens), on_chip((tokens,), jnp.bool_), i32(slots), i32(slots),
        on_chip((slots,), jnp.bool_), on_chip((2, slots), jnp.bool_))
    hlo = _compiled_text(lowered)
    for name in ("power_retention_update", "power_retention_chunk"):
        assert name in hlo, name
    pools = {"f32[{}]".format(",".join(map(str, s))) for s in (s_shape, z_shape)}
    shape = re.compile(r"^\s*(?:ROOT )?%?(\S+) = \(?(\w+\[[\d,]+\])\S* ([\w\-]+)\(")
    movers, layouts = [], set()
    for line in hlo.splitlines():
        m = shape.match(line)
        if not m or m.group(2) not in pools:
            continue
        # z, under a hundredth of the bytes, is small enough here to be
        # prefetched into the chip's fast memory whole (copy-start / -done
        # to memory space 1), which says nothing of a deployment's: only S
        # is held to "no operation of its shape"
        big = m.group(2) == "f32[{}]".format(",".join(map(str, s_shape)))
        if big and m.group(3) not in ("parameter", "get-tuple-element", "bitcast"):
            movers.append((m.group(1), m.group(3)))
        layouts.update(re.findall(re.escape(m.group(2)) + r"(\{[\d,]+)", line))
    assert not movers, movers
    assert layouts == {"{4,3,2,1,0"}, layouts


# ------------------------------------------- the sampler's two conditionals

def _outside_conditionals(hlo):
    """The instructions of a compiled module that run whatever a
    conditional's predicate says: every computation reached from ENTRY
    through calls, fusions, loops and reducers, but not through
    ``branch_computations``."""
    bodies, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            bodies[name].append(line)
    seen, todo, lines = set(), ["ENTRY"], []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in bodies[name]:
            lines.append(line)
            always = re.sub(r"branch_computations=\{[^}]*\}", "", line)
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", always)
    return lines


@pytest.mark.parametrize(
    "rows, vocab", [(32, 32768), (32, 32000), (16, 151936)],
    ids=["mistral", "mixtral", "brumby"])
def test_the_sampler_sorts_and_draws_only_inside_a_conditional(
        one_chip, rows, vocab):
    """ISSUE 34: ``sample_tokens`` as the cells call it (bias extras, a live
    mask) at their ``[max_batch, vocab]``. The v5e compiler keeps both
    ``lax.cond``s as conditionals: ONE whole-vocabulary sort, and it, the
    per-row keys and the Gumbel bits are all inside a branch, so a launch
    of greedy rows runs none of them."""
    from clearml_serving_tpu.llm.sampling import (
        SamplingExtras, SamplingParams, sample_tokens)

    def on_chip(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = jnp.int32
    params = SamplingParams(
        on_chip(rows), on_chip(rows, dtype=i32), on_chip(rows))
    extras = SamplingExtras(
        presence=on_chip(rows), frequency=on_chip(rows),
        repetition=on_chip(rows), bias=on_chip(rows, vocab),
        seeds=on_chip(rows, dtype=i32), counters=on_chip(rows, dtype=i32),
        min_new=on_chip(rows, dtype=i32), stop=on_chip(rows, 8, dtype=i32))
    hlo = _compiled_text(sample_tokens.lower(
        on_chip(rows, vocab), params, on_chip(2, dtype=jnp.uint32), extras,
        on_chip(rows, vocab, dtype=i32), on_chip(rows, vocab, dtype=jnp.bool_),
        live=on_chip(rows, dtype=jnp.bool_)))
    sort = re.compile(r" sort\(")
    draw = re.compile(r'op_name="[^"]*(_threefry|_gumbel|_uniform|random_bits)')
    assert len(sort.findall(hlo)) == 1
    assert draw.search(hlo) and " conditional(" in hlo
    always = _outside_conditionals(hlo)
    assert len(always) > 20       # the walk found the entry's fusions
    assert not [l for l in always if sort.search(l) or draw.search(l)]


# ------------------------------------ the latent page layout's kernels

LATENT_ROWS, LATENT_TOKENS, LATENT_PAGES, LATENT_TABLE = 32, 128, 12289, 1793


def _latent_case(one_chip, case):
    """The latent kernels at the `dots3note.doc_sessions` cell's shapes: 32
    rows of up to 28,672 tokens in 16-token pages, a pool of 196,608 tokens;
    full layers 128 heads on 640-lane rows (512 read back), window layers 64
    heads on 1152-lane rows (1024 read back), a top-2048 selection a token."""
    from clearml_serving_tpu.ops import latent_attention as la

    def on_chip(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    bf16 = jnp.bfloat16
    full = on_chip((4, 1, LATENT_PAGES, 16, 640), bf16)
    window = on_chip((6, 1, LATENT_PAGES, 16, 1152), bf16)
    table = on_chip((LATENT_ROWS, LATENT_TABLE))
    rows = on_chip((LATENT_ROWS,))
    view = pa.ragged_view_tokens(LATENT_TOKENS, LATENT_ROWS)
    tile = pa.ragged_query_tile(1, 128, 1152, bf16)
    items = on_chip((pa.ragged_item_count(LATENT_ROWS, view, tile),))

    def selection(n):
        return (on_chip((n, 2048)), on_chip((n, 2048)), on_chip((n,)))

    if case == "decode_window":
        return (lambda q, p, t, n: la.latent_attention_decode(
            q, p, t, n, layer=jnp.int32(2), v_width=1024, window=513),
            (on_chip((LATENT_ROWS, 64, 1152), bf16), window, table, rows),
            "latent_attention_decode")
    if case == "decode_selected":
        return (lambda q, p, t, n, a, b, c: la.latent_attention_decode(
            q, p, t, n, layer=jnp.int32(1), v_width=512, selected=(a, b, c)),
            (on_chip((LATENT_ROWS, 128, 640), bf16), full, table, rows)
            + selection(LATENT_ROWS), "latent_attention_decode")
    if case == "ragged_window":
        return (lambda q, p, t, kv, st, ln, ir, iq: la.latent_ragged_attention(
            q, p, t, kv, st, ln, ir, iq, layer=jnp.int32(3), v_width=1024,
            tile=tile, window=513),
            (on_chip((view, 64, 1152), bf16), window, table, rows, rows, rows,
             items, items), "latent_ragged_attention")
    if case == "ragged_selected":
        return (lambda q, p, a, b, c, v: la.latent_ragged_attention(
            q, p, None, None, None, None, None, None, layer=jnp.int32(1),
            v_width=512, tile=1, selected=(a, b, c), tok_valid=v),
            (on_chip((LATENT_TOKENS, 128, 640), bf16), full)
            + selection(LATENT_TOKENS) + (on_chip((LATENT_TOKENS,), jnp.bool_),),
            "latent_ragged_attention")
    width = {"write_full": 640, "write_window": 1152, "write_index": 128}[case]
    pool = on_chip((4, 1, LATENT_PAGES, 16, width), bf16)
    tokens = on_chip((LATENT_TOKENS,))
    return (lambda p, r, a, b: la.latent_kv_write(p, r, a, b, layer=jnp.int32(1)),
            (pool, on_chip((LATENT_TOKENS, width), bf16), tokens, tokens),
            "latent_kv_write")


@pytest.mark.parametrize("case", [
    "decode_window", "decode_selected", "ragged_window", "ragged_selected",
    "write_full", "write_window", "write_index"])
def test_the_latent_kernels_compile_at_the_cells_shapes(one_chip, case):
    """ISSUE 45: every latent kernel lowers for the described v5e as ONE
    custom call under its trace name (a write kernel that read its rows from a
    [T, W] operand was refused here: a bf16 row is half a packed sublane)."""
    fn, operands, name = _latent_case(one_chip, case)
    hlo = _compiled_text(jax.jit(fn).lower(*operands))
    assert hlo.count("tpu_custom_call") == 1
    assert name in hlo


# ------------------------- the window bound of the two paged kernels (PR 47)

WIN_ROWS, WIN_HKV, WIN_G, WIN_LAYERS = 32, 4, 8, 8
WIN_PAGES, WIN_PAGES_PER_SEQ, WIN_TOKENS = 12289, 17408 // 16 + 1, 352


def _window_case(one_chip, case, window):
    """The two paged kernels at trinity-mini-d8's shapes (32 rows, Hkv 4, G 8,
    D 128, pages of 16, the cell's pool of 12,289 pages over 8 layers, rows of
    17,408 tokens) with a static ``window``."""
    def on_chip(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    bf16 = jnp.bfloat16
    pool = on_chip((WIN_LAYERS, WIN_HKV, WIN_PAGES, 16, D), bf16)
    table, rows = on_chip((WIN_ROWS, WIN_PAGES_PER_SEQ)), on_chip((WIN_ROWS,))
    if case == "decode":
        return (
            lambda q, k, v, t, n, layer: pa.paged_attention(
                q, k, v, t, n, layer=layer, window=window),
            (on_chip((WIN_ROWS, WIN_HKV, WIN_G, D), bf16), pool, pool, table,
             rows, on_chip(())),
            "paged_attention_decode")
    tile = pa.ragged_query_tile(WIN_HKV, WIN_G, D, bf16)
    items = on_chip((pa.ragged_item_count(WIN_ROWS, WIN_TOKENS, tile),))
    return (
        lambda q, k, v, t, kv, st, n, ir, iq, layer: pa.ragged_paged_attention(
            q, k, v, t, kv, st, n, item_rows=ir, item_q0=iq, layer=layer,
            window=window),
        (on_chip((WIN_TOKENS, WIN_HKV, WIN_G, D), bf16), pool, pool, table,
         rows, rows, rows, items, items, on_chip(())),
        "ragged_paged_attention")


@pytest.mark.parametrize("case", ["decode", "ragged"])
@pytest.mark.parametrize("window", [0, 2048], ids=["causal", "window2048"])
def test_the_windowed_kernels_compile_at_the_published_shapes(
        one_chip, case, window):
    """ISSUE 47: both kernels lower for the described v5e at the published
    shapes as ONE custom call under their trace names, with the window and
    without it (the full layers of the same model)."""
    fn, operands, name = _window_case(one_chip, case, window)
    hlo = _compiled_text(jax.jit(fn).lower(*operands))
    assert hlo.count("tpu_custom_call") == 1
    assert name in hlo


@pytest.mark.parametrize("case", ["decode", "ragged"])
def test_window_zero_lowers_to_the_kernel_of_before(one_chip, case):
    """``window=0`` adds no operation to either kernel: its kernel body is
    the one a call without the argument traces (what Mistral's and Mixtral's
    cells run), equation for equation, and the windowed body is longer."""
    def body(window):
        fn, operands, _ = _window_case(one_chip, case, window)
        if window is None:       # the call of before: no such argument
            if case == "decode":
                fn = lambda q, k, v, t, n, layer: pa.paged_attention(  # noqa: E731
                    q, k, v, t, n, layer=layer)
            else:
                fn = lambda q, k, v, t, kv, st, n, ir, iq, layer: (  # noqa: E731
                    pa.ragged_paged_attention(
                        q, k, v, t, kv, st, n, item_rows=ir, item_q0=iq,
                        layer=layer))
        call, = [e for e in jax.make_jaxpr(fn)(*operands).eqns
                 if e.primitive.name == "pallas_call"]
        return str(call.params["jaxpr"])

    assert body(0) == body(None)
    assert len(body(2048)) > len(body(0))


# ------------------------------------------------- the SSD kernels (PR 51)

@pytest.mark.parametrize("case", ["update", "chunk"])
def test_the_ssd_kernels_compile_at_the_published_shapes(
        one_chip, monkeypatch, case):
    """ops/mamba2.py at Falcon-H1-34B's widths (32 heads x 128, state 256, 2
    groups), 64 rows and a launch of 256 tokens: Mosaic takes both kernels,
    and the 2.2 GB pool is updated in place (no temporary of its size)."""
    from clearml_serving_tpu.ops import mamba2

    reason = mamba2.ssd_kernel_unsupported_reason
    monkeypatch.setattr(
        mamba2, "ssd_kernel_unsupported_reason",
        lambda *a, **k: reason(*a, **dict(k, platform="tpu")))
    layers, rows, heads, groups, p, n, t = 8, 64, 32, 2, 128, 256, 256

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = on_chip(mamba2.state_shape(layers, rows, heads, n, p))
    plan = (on_chip((rows,), jnp.int32), on_chip((), jnp.int32),
            on_chip((rows,), jnp.bool_))
    if case == "update":
        fn = lambda dtx, decay, bm, cm, r, c, z, h: mamba2.mamba2_ssd_update(  # noqa: E731
            dtx, decay, bm, cm, r, c, z, h, layer=3)
        args = (on_chip((rows, heads, p)), on_chip((rows, heads)),
                on_chip((rows, groups, n)), on_chip((rows, groups, n)),
                *plan, pool)
    else:
        fn = lambda dtx, ld, bm, cm, row, m, r, c, z, h: mamba2.mamba2_ssd_chunk(  # noqa: E731
            dtx, ld, bm, cm, row, m, r, c, z, h, layer=3)
        args = (on_chip((t, heads, p)), on_chip((t, heads)),
                on_chip((t, groups, n)), on_chip((t, groups, n)),
                on_chip((t,), jnp.int32), on_chip((t,), jnp.bool_),
                *plan, pool)
    lowered = jax.jit(fn, donate_argnums=(len(args) - 1,)).trace(
        *args).lower(lowering_platforms=("tpu",))
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    memory = compiled.memory_analysis()
    pool_bytes = 4 * layers * (rows + 1) * heads * n * p
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 16
    assert "mamba2_ssd_" + case in compiled.as_text()


# ------------------------------- the held experts' kernel (PR 53, ISSUE 53)

@pytest.mark.parametrize("config, calls", [
    ("trinity-mini-d8", 6), ("dots3-note-prev-ep8", 5)])
def test_the_expert_pass_visits_the_stacks_in_place(
        one_chip, monkeypatch, config, calls):
    """The mixed pass of both expert configurations at their published
    sizes (128 packed tokens, 32 rows, int8 weights, the cell's pool),
    compiled for the described v5e: every expert layer is a ``moe_experts``
    Mosaic call (six unrolled layers; one unrolled and four in dots3's
    scanned period, on the WHOLE stacked operand), and no instruction but a
    parameter on its way through the loop has an expert stack's shape: no
    copy, no slice out of the scan's operand, no dequantised temporary."""
    from benchmark import sut
    from clearml_serving_tpu.ops import moe_experts as me

    monkeypatch.setattr(pa, "paged_kernel_unsupported_reason",
                        lambda *a, **k: None)
    reason = me.moe_kernel_unsupported_reason
    monkeypatch.setattr(
        me, "moe_kernel_unsupported_reason",
        lambda *a, **k: reason(*a, **dict(k, platform="tpu")))
    cfg = sut.load_config("benchmark/configs/{}.json".format(config))
    model = sut.model_block(cfg)
    bundle = models.build_model(cfg["arch"], model)

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def shapes(make):
        return jax.tree.map(lambda a: on_chip(a.shape, a.dtype),
                            jax.eval_shape(make))

    params = shapes(lambda: bundle.init(jax.random.PRNGKey(0),
                                        weight_quant="int8"))
    rows, dense, page = 32, 128, 16
    pages = cfg["engine"]["num_pages"]
    if cfg["arch"] == "afmoe":
        pool = on_chip((bundle.n_layers, bundle.n_kv_heads, pages, page, D),
                       jnp.bfloat16)
        k, v = pool, (pool, on_chip((bundle.paged_window.counters,)))
        tile = pa.ragged_query_tile(
            bundle.n_kv_heads, bundle.n_heads // bundle.n_kv_heads, D,
            jnp.bfloat16)
    else:
        k, v = shapes(lambda: bundle.paged_layout.init_pools(pages, page))
        tile = pa.ragged_query_tile(
            1, bundle.paged_layout.n_heads, bundle.paged_layout.head_dim,
            jnp.bfloat16)
    items = pa.ragged_item_count(
        rows, pa.ragged_view_tokens(dense, rows), tile)

    def step(params, k, v, tok, valid, row_last, table, per_row, item):
        return bundle.forward_ragged(
            params, tok, tok, tok, valid, tok, row_last, k, v, table, per_row,
            per_row, per_row, tok, tok, item, item)

    hlo = _compiled_text(jax.jit(step, donate_argnums=(1, 2)).lower(
        params, k, v, on_chip((dense,)), on_chip((dense,), jnp.bool_),
        on_chip((rows,)), on_chip((rows, cfg["engine"]["max_seq_len"] // page + 1)),
        on_chip((rows,)), on_chip((items,))))
    assert len(re.findall(r"= \S+ custom-call\(.*moe_experts", hlo)) == calls
    n_held = model.get("experts_held", (0, model["router_experts"]))[1]
    dim, width = model["dim"], model["moe_intermediate_size"]
    stack = ([n_held, dim, width], [n_held, width, dim])
    shaped = re.compile(
        r"^\s*(?:ROOT )?%?(\S+) = \(?(\w+)\[([\d,]+)\]\S* ([\w\-]+)\(")
    moved = []
    for line in hlo.splitlines():
        m = shaped.match(line)
        if not m:
            continue
        name, dtype, dims, op = m.groups()
        dims = [int(n) for n in dims.split(",")]
        if dims[-3:] in stack and op not in (
                "parameter", "get-tuple-element", "bitcast"):
            moved.append((name, dtype, dims, op))
    assert not moved, moved


_LLAMA_PROGRAMS = "tests/data/llama_step_programs.json"


def _llama_step_digests():
    """sha256 of the lowered text of a ragged pass and a decode pass of
    ``models/llama.py`` at tiny widths, int8 weights: Mistral's shape of
    model (no experts) and Mixtral's (8 experts, top-2)."""
    import hashlib

    out = {}
    for name, experts in (("mistral", 0), ("mixtral", 8)):
        cfg = dict(vocab_size=256, dim=128, n_layers=2, n_heads=4,
                   n_kv_heads=2, head_dim=32, ffn_dim=256, scan_layers=True,
                   dtype="bfloat16")
        if experts:
            cfg.update(n_experts=experts, moe_top_k=2)
        bundle = models.build_model("llama", cfg)
        params = jax.eval_shape(lambda: bundle.init(
            jax.random.PRNGKey(0), weight_quant="int8"))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        pool = jax.ShapeDtypeStruct((2, 2, 9, 16, 32), jnp.bfloat16)
        tok, rows = i32(16), i32(4)

        def ragged(params, k, v, tok, valid, row_last, table, per_row):
            return bundle.forward_ragged(
                params, tok, tok, tok, valid, tok, row_last, k, v, table,
                per_row, per_row, per_row, tok, tok)

        def decode(params, k, v, nxt, table, per_row, live):
            return bundle.decode_paged(
                params, nxt, k, v, table, per_row, per_row, per_row,
                active=live)

        texts = (
            jax.jit(ragged).lower(
                params, pool, pool, tok,
                jax.ShapeDtypeStruct((16,), jnp.bool_), rows, i32(4, 4),
                rows).as_text(),
            jax.jit(decode).lower(
                params, pool, pool, rows, i32(4, 4), rows,
                jax.ShapeDtypeStruct((4,), jnp.bool_)).as_text(),
        )
        for step, text in zip(("ragged", "decode"), texts):
            out["{}.{}".format(name, step)] = hashlib.sha256(
                text.encode()).hexdigest()
    return out


def test_the_llama_step_programs_are_the_recorded_ones():
    """ISSUE 53 leaves ``models/llama.py`` alone: Mistral's and Mixtral's
    step programs lower to the text recorded from the parent commit
    (``python tests/test_tpu_compile.py --record`` re-records, for a PR
    that means to change them; ``recorded_with`` names the jax it holds
    for)."""
    import json

    with open(_LLAMA_PROGRAMS) as f:
        recorded = json.load(f)
    if recorded["recorded_with"] != jax.__version__:
        pytest.skip("recorded with jax {}".format(recorded["recorded_with"]))
    assert _llama_step_digests() == recorded["sha256"]


if __name__ == "__main__":
    import json
    import sys

    if sys.argv[1:] == ["--record"]:
        with open(_LLAMA_PROGRAMS, "w") as f:
            json.dump({"recorded_with": jax.__version__,
                       "sha256": _llama_step_digests()}, f, indent=1)
            f.write("\n")
