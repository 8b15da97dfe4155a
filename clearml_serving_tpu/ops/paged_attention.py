"""Paged decode attention: Pallas TPU kernel + XLA reference.

The KV cache lives in fixed-size **pages** in HBM; each sequence owns a list of
pages (its page table row). Decode attention for one new token per sequence
gathers exactly the sequence's pages — HBM traffic scales with the tokens that
exist, not with a max-length dense cache. This is the kernel behind the
≥1500 tok/s/chip target (SURVEY.md §7 hard part 2; PAPERS.md "Ragged Paged
Attention").

Canonical layout (head-major pools — the TPU tiling wants the page's
[page_size, head_dim] plane to be the trailing block):
    q            [B, Hkv, G, D]    one new token per sequence, query heads
                                   grouped under their shared KV head (GQA)
    k/v pools    [Hkv, N_pages, P, D], or the model's whole stack
                 [L, Hkv, N_pages, P, D] with ``layer`` (an int32 scalar,
                 traced or static) naming the layer to attend
    page_table   [B, pages_per_seq] int32 page ids into the pool
    lengths      [B] int32         tokens currently in each sequence

The stacked form is what the model step passes (models/llama.py): the pools
of all layers stay ONE donated buffer for the whole launch, carried through
the layer scan, and every entry point here reads layer ``layer`` of it in
place — the kernels' page DMAs start at ``k_hbm.at[layer, h, page]`` (the
scalar rides in the scalar prefetch), the XLA references gather
``pool[layer, :, page_table]``, and the step's new K/V go in through
:func:`paged_kv_write` (below, "KV write"), which patches the touched pages
of the donated stack in place. Nothing slices a layer's pool out of the
stack, which on the chip is a copy of the whole layer (PERF.md, PR 25). A
4-D pool is the one-layer stack: same kernel, ``layer`` 0.

Pallas design (decode; the work plan of ISSUE 28): the grid walks the ROWS,
in order, and a grid step owns one row with ALL its kv heads (``q`` / ``out``
blocks ``[1, Hkv, G, D]``; scores and the PV product are batched over the
heads). K/V pools stay in HBM (memory_space=ANY); the kernel issues manual
double-buffered async copies of one block of pages at a time into VMEM
scratch ``[2 slots, Hkv, PB*P, D]`` a side — block i+1's DMAs fly while block
i's flash update runs on the MXU. What the plan is made of:

- **No dead traffic**: pages past a sequence's length are never copied, and
  a row of length 0 (an empty slot, a prefill row, a row whose decode window
  has closed: models/llama.py ``decode_paged(..., active=)``) costs no DMA
  and no flash block; its output is zeros.
- **A page is one descriptor a side for all kv heads**
  (``k_hbm.at[layer, :, page]`` -> ``k_buf.at[slot, :, j*P:(j+1)*P]``, a
  strided ``[Hkv, P, D]`` copy): Hkv times fewer descriptors, starts and
  waits than a copy per head. All of a block's copies of one side signal
  one semaphore and are waited for page by page (ONE wait for a whole full
  block, which a byte-counting DMA semaphore allows, measured the same: 98.3
  against 98.5 us, so the second path is not kept). The page loops are
  ``fori_loop``s over the pages that exist, not unrolled ``pl.when``s.
- **Only the first live row of a call starts cold**: the page buffers, their
  semaphores and a block counter (SMEM) persist across grid steps, and while
  a row's last block is computed the NEXT LIVE row's first block is already
  in flight into the other slot.
- **The block follows from the shapes** (:func:`decode_pages_per_block`): as
  many pages as fit 4 MB of scratch, at most 512 tokens; no caller and no
  environment variable sets it.
- **bf16 operand feed**: K/V stream into the dot products in pool dtype
  (bf16) with f32 accumulation (preferred_element_type); per (row, head) the
  arithmetic is the block-by-block flash update it always was (same blocks,
  same order, f32 running max / sum / accumulator).

Kernel speed on the chip (TPU v5 lite, PERF.md PR 28; 32 rows, Hkv 8, G 4,
D 128, pages of 16, bf16, the 32-layer stack, shuffled page tables; a call
is one layer): 11 live rows of 0.9-1.7k tokens and 21 dead ones 98 us = 77%
of the HBM peak (the plan before: 656 us with the dead rows handed in at
length 1, 463 us at length 0); 32 live rows of 450-900 tokens 147 us = 74%
(before: 762 us); 8 rows of ~3k 147 us = 84%. With the flash update taken
out the same calls take 92 / 134 / 143 us, with the DMAs taken out 35 / 58 /
47: the kernel is bound by its page DMAs, not by the MXU's M = G rows.

The ragged kernel (mixed prefill + decode rows; its section below) walks a
host-built list of work items with the same plan since ISSUE 30: a decode or
verify row, or a 128-query tile of a prompt chunk, a grid step, all kv heads,
a tile's context read once. On the chip (PERF.md PR 30; 352 flat tokens, 32
rows, the shapes above; a call is one layer): 31 decode rows of ~420 tokens
and a 97-query chunk on 512 tokens 107 us (the plan before, a grid step per 8
queries and head: 549 us); 8 decode rows of ~1,250 and a 100-query chunk at
1,500 tokens 111 us (866 us); 7 decode rows of 1-4k and a 128-query row at
4,096 tokens 221 us (2,318 us); outputs bit for bit the old plan's. The two
transposes that make q and out head-major around it are XLA operations of 5
us each.

Mosaic portability notes baked into the kernels (each one is a refusal of
the v5e compiler, libtpu 0.0.34):

- never insert a minor dim on an i1 vector (``bool[:, None]`` fails to
  compile) — masks build as 2-D i32 iota compares;
- DMA slices must be lane-aligned: ``D % 128 == 0`` gates the Pallas path
  ("slice shape along dimension 3 must be aligned to tiling" at D=64);
- no ``select`` between i1 vectors and no scalar-bool broadcast into one
  ("Unsupported target bitwidth for truncation": vector<i8> -> vector<i1>)
  — the draft-tree mask ORs per-query terms built from i32 compares only;
- a 2-D scalar-prefetch table pads its minor dim to 128 SMEM lanes, so the
  ``[T, DMAX]`` ancestor table rides flat (1-D).

int8 paged KV (r4, docs/paged_kv_quant.md): pools may store int8 with a
per-(token, head) f32 scale pool ``[Hkv, N, P]`` beside each side —
``k_scale``/``v_scale`` operands. The kernel streams the int8 pages through
the SAME manual double-buffered DMA plan (half the bytes of bf16: the
dominant decode DMA term), and dequantization fuses into the flash update
next to the MXU:

- K side: the dot runs on the raw int8 block cast to the compute dtype
  (int8 -> bf16 is LOSSLESS: 8-bit mantissa covers [-127, 127]) and the
  f32 scores multiply by ``k_scale`` per key column — algebraically the
  dequantized matmul, without materializing a dequantized [PB*P, D] tile.
- V side: the f32 probs multiply by ``v_scale`` per value row before the
  PV dot — same fusion.

Scales do NOT ride the per-page DMA plan: an f32 scale row is [P] (16-64
lanes), and Mosaic requires DMA slices tile-aligned — the same constraint
that gates D % 128 would reject every scale-row copy. Instead the tiny
scale vectors (4 bytes per token-head vs 128+ data bytes) are pre-gathered
by XLA into a lane-aligned [B, Hkv, 1, PP*P] operand that the grid
pipeline DMAs into VMEM like any blocked input (a row's scales of all heads
a grid step, in the decode kernel and, by the work item's row, in the ragged
kernel).
The gather reads scale rows at table capacity rather than live length; that
dead traffic is bounded by scale_bytes/kv_bytes = 4/D of the int8 stream
(~3% at D=128).

Alignment gates for the int8 path: D % 128 == 0 (unchanged) and
page_size % 32 == 0 on hardware — the int8 tile is (32, 128), so a 16-row
page plane cannot be sliced out of an int8 pool (bf16's 16-sublane tile
could). Misaligned int8 shapes (including the default 16-token pages)
route to the XLA gather, exactly like D=64 does today; interpret=True
exercises the kernel on any shape.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def paged_kernel_unsupported_reason(
    row_widths, page_size: int, kv_dtype, *, platform: Optional[str] = None
) -> Optional[str]:
    """Why paged pools of this shape cannot take the Mosaic kernels (decode
    and ragged share the gates) — None if they can. ``row_widths``: the
    widths of a cached row in every plane of the layout — one ``head_dim``
    for K/V pages, or a tuple (llm/kv_cache.py, the latent layout).

    The one routing decision for paged attention: pure in its arguments
    (``platform`` = the backend the program compiles for, default
    ``jax.default_backend()``). models/llama.py calls it at trace time to
    pick kernel or XLA gather, and the engine calls it at construction
    with the same arguments for its health block."""
    platform = platform or jax.default_backend()
    if platform != "tpu":
        return "platform {}: the Mosaic kernels compile for TPU only".format(
            platform
        )
    # Mosaic requires DMA slices tile-aligned: a [P, D] page plane with
    # D % 128 != 0 cannot be sliced out of the pool, and a page_size off
    # the sublane tile would misalign the k_buf/v_buf destination offsets
    # (j*P). The sublane tile is dtype-dependent: 16 rows for bf16 pools,
    # 32 for int8.
    if isinstance(row_widths, int):
        if row_widths % 128:
            return "head_dim {} is not a multiple of the 128-lane tile".format(
                row_widths
            )
    else:
        for width in row_widths:
            if width % 128:
                return (
                    "row width {} of the layout's planes {} is not a "
                    "multiple of the 128-lane tile".format(
                        width, tuple(row_widths))
                )
    min_sublane = 32 // jnp.dtype(kv_dtype).itemsize   # 8 f32, 16 bf16, 32 int8
    if page_size % min_sublane:
        return "page_size {} is not a multiple of the {}-row {} sublane " \
               "tile".format(page_size, min_sublane, jnp.dtype(kv_dtype).name)
    return None


def window_kernel_unsupported_reason(window: int, quantized: bool = False,
                                     tree: bool = False) -> Optional[str]:
    """Why the Mosaic kernels cannot take this ``window`` with these
    operands — None if they can (always at ``window`` 0). What the window
    bound is not taught is refused by name, never computed as something
    else (docs/window_attention.md)."""
    if window < 0:
        return "window {} is negative (0 = no window)".format(window)
    if window and quantized:
        return (
            "a window on int8 K/V pools is not implemented: the int8 flash "
            "update (scale rows folded into scores and probabilities) has "
            "not been taken through the windowed walk"
        )
    if window and tree:
        return (
            "a window on draft-tree verify rows is not implemented: a "
            "tree's ancestor mask is written against the causal bound alone"
        )
    return None


def window_first_page(first_pos, window: int, page_size: int):
    """The first page of a row that holds a key the query at ``first_pos``
    sees under ``window`` (it sees ``first_pos - window < s <= first_pos``):
    where the kernels' page walks start. 0 without a window. Works on
    ints, numpy arrays and traced scalars alike."""
    if not window:
        return 0
    import numpy as np

    first = first_pos - (window - 1)
    maximum = jnp.maximum if isinstance(first, jax.Array) else np.maximum
    return maximum(first, 0) // page_size


def _plus(x, offset):
    """``x + offset``, and ``x`` itself where ``offset`` is the Python 0 of
    a kernel without a window: that kernel's program gains no operation."""
    return x if isinstance(offset, int) and offset == 0 else x + offset


def _check_kernel_operands(name, q, k_pool, quantized, interpret,
                           window=0, tree=False):
    """The kernel entry points run the kernel or raise — never a reference."""
    if jnp.issubdtype(k_pool.dtype, jnp.signedinteger) and not quantized:
        raise ValueError(
            "int8 KV pools need k_scale/v_scale operands (per-token dequant)"
        )
    reason = window_kernel_unsupported_reason(window, quantized, tree)
    if reason is not None:
        raise ValueError("{}: {}".format(name, reason))
    if not interpret:
        reason = paged_kernel_unsupported_reason(
            q.shape[-1], k_pool.shape[-2], k_pool.dtype, platform="tpu"
        )
        if reason is not None:
            raise ValueError("{}: {}".format(name, reason))


# Scalar memory of one v5e core, as its compiler reports it ("Allocation
# would exceed memory (size=1048576) ... space=smem").
SMEM_BYTES = 1 << 20


def paged_kernel_smem_bytes(
    rows: int, pages_per_seq: int, tokens: int = 0, tree_width: int = 0,
    items: int = 0,
) -> int:
    """SMEM the kernels' scalar-prefetch operands and scalar scratch take:
    the decode kernel for ``rows`` sequences (``tokens == 0``), or the
    ragged kernel for a launch of ``tokens`` flat tokens on a work plan of
    ``items`` (+ a ``[tokens, tree_width]`` ancestor table).
    The 2-D page table pads to (8, 128) int32 tiles — measured against the
    compiler: s32[250, 1023] allocates 256 * 1024 * 4 bytes — and each 1-D
    vector to a 512-byte line, or to 4 KB once it is longer than that
    (s32[140000] allocates 548 KB); 2 KB covers the compiler's own scalars
    (at the boundary it reported 1.1 KB more than the operands sum to; with
    a work plan of 140,000 items 0.6 KB more). The
    engine compares this with
    :data:`SMEM_BYTES` at construction, so an oversized max_seq_len /
    max_batch / token budget is a load-time error instead of a Mosaic
    RESOURCE_EXHAUSTED on the first request."""
    def vec(n):
        line = 4096 if 4 * n > 4096 else 512
        return -(-4 * n // line) * line

    table = (-(-rows // 8) * 8) * (-(-pages_per_seq // 128) * 128) * 4
    if not tokens:
        # + lengths, layer, and the walk's block counter (SMEM scratch)
        return 2048 + table + vec(rows) + 2 * vec(1)
    return (
        2048 + table + 3 * vec(rows)           # kv_lens, row_starts, row_lens
        + 2 * vec(1)                           # layer, the walk's counters
        + 2 * vec(items)                       # item_rows, item_q0
        + vec(tokens * tree_width)             # flat ancestor table
    )


def _stacked(layer, *pools):
    """(layer [1] int32, pools as [L, Hkv, N, ...] stacks): a pool of one
    layer becomes the one-layer stack (a bitcast under jit), so that the
    kernels have one formulation. A stack needs its ``layer``."""
    if pools[0].ndim == 4:
        if layer is not None:
            raise ValueError("layer indexes a stacked [L, Hkv, N, P, D] pool")
        layer, pools = 0, tuple(p[None] for p in pools)
    elif layer is None:
        raise ValueError("a stacked [L, Hkv, N, P, D] pool needs its layer")
    return (jnp.asarray(layer, jnp.int32).reshape(1),) + tuple(pools)


def gather_pages(pool, page_table, layer=None):
    """``pool[:, page_table]`` of one layer -> [Hkv, R, PP, P(, D)], from a
    layer's pool or (``layer`` given) in ONE gather from the stack: slicing
    the layer out first would materialise it."""
    if layer is None:
        return pool[:, page_table]
    heads = jnp.arange(pool.shape[1], dtype=jnp.int32)[:, None, None]
    return pool[layer, heads, page_table[None]]


# ----------------------------------------------------------------- reference

def paged_attention_xla(q, k_pool, v_pool, page_table, lengths,
                        k_scale=None, v_scale=None, layer=None,
                        window: int = 0):
    """Reference implementation in plain XLA ops (also the CPU fallback).

    q: [B, Hkv, G, D]; pools: [Hkv, N, P, D], or stacked [L, Hkv, N, P, D]
    with ``layer``; page_table: [B, PP]; lengths: [B] -> out [B, Hkv, G, D].

    ``k_scale``/``v_scale`` ([Hkv, N, P] f32) dequantize int8 pools: the
    per-(token, head) symmetric scales of models/llama._kv_store. Dequant
    happens in f32 and casts to the query dtype before the attention math,
    mirroring the dense path's _kv_load, so XLA fuses it into the gather.

    ``window`` (static; 0 = none): the row's query, at position
    ``lengths - 1``, sees the keys ``lengths - 1 - window < s``.
    """
    b, hkv, g, d = q.shape
    p = k_pool.shape[-2]
    pp = page_table.shape[1]
    # gather pages -> [Hkv, B, PP, P, D] -> [B, T, Hkv, D]-equivalent einsum order
    k = gather_pages(k_pool, page_table, layer).reshape(hkv, b, pp * p, d)
    v = gather_pages(v_pool, page_table, layer).reshape(hkv, b, pp * p, d)
    if k_scale is not None:
        ks = gather_pages(k_scale, page_table, layer).reshape(hkv, b, pp * p, 1)
        vs = gather_pages(v_scale, page_table, layer).reshape(hkv, b, pp * p, 1)
        k = (k.astype(jnp.float32) * ks).astype(q.dtype)
        v = (v.astype(jnp.float32) * vs).astype(q.dtype)
    t_idx = jnp.arange(pp * p, dtype=jnp.int32)[None]
    valid = t_idx < lengths[:, None]                          # [B, T]
    if window:
        valid = valid & (t_idx >= lengths[:, None] - window)
    scores = jnp.einsum(
        "bkgd,kbtd->bkgt", q, k, preferred_element_type=jnp.float32
    ) * (d ** -0.5)
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    # manual stable softmax: zero-length rows (inactive batch slots) must
    # produce zeros, not NaN, matching the Pallas kernel
    row_max = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), -1e30)
    probs = jnp.exp(scores - row_max)
    probs = jnp.where(valid[:, None, None, :], probs, 0.0)
    denom = jnp.sum(probs, axis=-1, keepdims=True)
    probs = (probs / jnp.where(denom == 0.0, 1.0, denom)).astype(v.dtype)
    out = jnp.einsum("bkgt,kbtd->bkgd", probs, v)
    return out.astype(q.dtype)


# ----------------------------------------------------------------- pallas

# VMEM the decode kernel's page buffers may take (two slots of K and of V,
# all kv heads), and the longest block of context one flash update sees. The
# block of a call follows from its shapes (:func:`decode_pages_per_block`).
_DECODE_SCRATCH_BYTES = 4 << 20
_DECODE_BLOCK_TOKENS = 512


def decode_pages_per_block(hkv, head_dim, page_size, pages_per_seq, kv_dtype):
    """Pages the decode kernel fetches and flash-processes as one block, all
    kv heads together: as many as fit :data:`_DECODE_SCRATCH_BYTES` double
    buffered on both sides, at most :data:`_DECODE_BLOCK_TOKENS` tokens and
    a row's table (32 pages at Hkv 8, D 128, bf16 pages of 16)."""
    per_token = 4 * hkv * head_dim * jnp.dtype(kv_dtype).itemsize
    tokens = min(_DECODE_SCRATCH_BYTES // per_token, _DECODE_BLOCK_TOKENS)
    return max(1, min(tokens // page_size, pages_per_seq))


def _paged_attention_kernel(
    # scalar prefetch
    page_table_ref,    # [B, PP] int32 (SMEM)
    lengths_ref,       # [B] int32 (SMEM); 0 = the row asks for nothing
    layer_ref,         # [1] int32 (SMEM): the layer of the stack to attend
    # then, positionally (in_specs order):
    #   q_ref            [1, Hkv, G, D] VMEM: one row, all its kv heads
    #   k_hbm            [L, Hkv, N, P, D] ANY (stays in HBM)
    #   v_hbm            [L, Hkv, N, P, D] ANY
    #   k_scale_ref      [1, Hkv, 1, cap_pad] f32 VMEM  (quantized=True only:
    #   v_scale_ref      [1, Hkv, 1, cap_pad] f32 VMEM   the row's pre-gathered
    #                    per-token scales in sequence order — module docstring)
    #   out_ref          [1, Hkv, G, D] VMEM
    # scratch, all of it kept from one grid step to the next:
    #   k_buf            [2, Hkv, PB*P, D] VMEM (double-buffered page blocks)
    #   v_buf            [2, Hkv, PB*P, D] VMEM
    #   sems             [2, 2] DMA semaphores (slot, k/v): one a block side,
    #                    signalled by each of its page copies
    #   walk             [1] int32 SMEM: blocks consumed so far in this call
    *refs,
    page_size: int,
    pages_per_block: int,
    quantized: bool = False,
    window: int = 0,
):
    if quantized:
        (q_ref, k_hbm, v_hbm, k_scale_ref, v_scale_ref,
         out_ref, k_buf, v_buf, sems, walk) = refs
    else:
        q_ref, k_hbm, v_hbm, out_ref, k_buf, v_buf, sems, walk = refs
        k_scale_ref = v_scale_ref = None
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    hkv, g, d = q_ref.shape[1:]
    p = page_size
    pb = pages_per_block
    block_tokens = pb * p
    layer = layer_ref[0]
    length = lengths_ref[b]

    def first_page(row):
        """The first page of ``row`` that holds a visible key: the pages
        before it are never copied and the blocks wholly before it never
        walked (0, a Python int, without a window: the program of before).
        The blocks keep their places on the row (block i = pages i * PB
        ...), so a key meets the same block whatever the window skipped."""
        if not window:
            return 0
        return window_first_page(lengths_ref[row] - 1, window, p)

    def first_block(row):
        """Of a row the walk may name past the last (``rows`` = none)."""
        if not window:
            return 0
        return first_page(jnp.minimum(row, rows - 1)) // pb

    b0 = first_block(b)
    # blocks that contain live tokens; DMA never touches pages past length
    n_blocks = _plus((length + block_tokens - 1) // block_tokens, -b0)

    def next_live(row):
        """First row after ``row`` that attends anything (``rows`` if none)."""
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < rows, lengths_ref[jnp.minimum(r, rows - 1)] == 0
            ),
            lambda r: r + 1, row + 1,
        )

    def block_pages(row, block):
        left = lengths_ref[row] - block * block_tokens
        return jnp.minimum((left + p - 1) // p, pb)

    def block_first(row, block):
        """The first page of ``block`` the window leaves to copy."""
        if not window:
            return 0
        return jnp.clip(first_page(row) - block * pb, 0, pb)

    def page_copies(row, block, slot, j):
        """A page's K and V planes of ALL kv heads: one strided descriptor a
        side, onto the slot's semaphore of that side."""
        page = page_table_ref[row, block * pb + j]
        dst = pl.ds(pl.multiple_of(j * p, p), p)
        return tuple(
            pltpu.make_async_copy(
                hbm.at[layer, pl.ds(0, hkv), page],
                buf.at[slot, pl.ds(0, hkv), dst],
                sems.at[slot, side],
            )
            for side, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))
        )

    def block_copies(act, row, block, slot):
        """Start, or wait for, the copies of the pages a block has."""
        def page(j, carry):
            for copy in page_copies(row, block, slot, j):
                getattr(copy, act)()
            return carry

        jax.lax.fori_loop(
            block_first(row, block), block_pages(row, block), page, 0)

    start_block = functools.partial(block_copies, "start")
    wait_block = functools.partial(block_copies, "wait")

    @pl.when(b == 0)
    def _first():
        # the only cold start of a call: the first live row's first block
        walk[0] = 0
        first = next_live(-1)

        @pl.when(first < rows)
        def _():
            start_block(first, first_block(first), 0)

    @pl.when(n_blocks > 0)
    def _run():
        # this row's block 0 is in flight already, in the slot after the
        # last block any row consumed; while its last block is computed the
        # next live row's block 0 flies into the other slot
        done = walk[0]
        after = next_live(b)

        def body(i, carry):
            m_prev, l_prev, acc_prev = carry
            slot = jax.lax.rem(done + i, 2)
            blk = _plus(i, b0)      # the block's place on the row

            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                start_block(b, blk + 1, 1 - slot)

            @pl.when(jnp.logical_and(i + 1 == n_blocks, after < rows))
            def _prefetch_next_row():
                start_block(after, first_block(after), 1 - slot)

            wait_block(b, blk, slot)
            # K/V feed the MXU in pool dtype (bf16) with f32 accumulation,
            # batched over the kv heads. int8 pools (quantized): the block
            # feeds the dot as raw int8 cast to the output compute dtype —
            # int8 -> bf16 is lossless — and the per-token scales fold into
            # the f32 scores/probs, so dequant fuses into the flash update
            # without materializing a dequantized tile (module docstring).
            q = q_ref[0]                                        # [Hkv, G, D]
            k = k_buf[slot]                                     # [Hkv, PB*P, D]
            v = v_buf[slot]
            if quantized:
                op_dtype = out_ref.dtype
                k = k.astype(op_dtype)
                v = v.astype(op_dtype)
            scores = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * (d ** -0.5)                                     # [Hkv, G, PB*P]
            if quantized:
                # scale rows of pages past length come from the gathered
                # null-page padding: finite garbage, masked right below
                k_s = k_scale_ref[0, :, :, pl.ds(i * block_tokens,
                                                 block_tokens)]  # [Hkv, 1, PB*P]
                scores = scores * k_s
            token_ids = (
                blk * block_tokens
                + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
            )
            valid = token_ids < length
            if window:
                # the first block's pages behind the window were not copied,
                # and its first copied page may begin behind it
                valid = valid & (token_ids >= length - window)
            scores = jnp.where(valid, scores, -jnp.inf)
            # rows past length were never DMA'd: their buffer bytes are
            # arbitrary (NaN/inf poisons 0*v), so zero them before the matmul.
            # (int8 garbage is always finite, but the zeroing also keeps the
            # masked rows from polluting the scaled-probs matmul below.)
            # Mask built as an i32 iota compare: Mosaic cannot insert a
            # minor dim on an i1 vector (bool[:, None] fails to compile).
            row_ids = blk * block_tokens + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_tokens, 1), 1
            )
            live = row_ids < length
            if window:
                live = live & (row_ids >= first_page(b) * p)
            v = jnp.where(live, v, jnp.zeros_like(v))

            block_max = jnp.maximum(
                jnp.max(scores, axis=2, keepdims=True), -1e30
            )
            m_new = jnp.maximum(m_prev, block_max)              # [Hkv, G, 1]
            probs = jnp.exp(scores - m_new)                     # [Hkv, G, PB*P]
            probs = jnp.where(valid, probs, 0.0)
            correction = jnp.exp(m_prev - m_new)                # [Hkv, G, 1]
            # the softmax denominator sums the UNSCALED probs; v_scale
            # belongs only to the PV product
            l_new = l_prev * correction + jnp.sum(
                probs, axis=2, keepdims=True
            )
            pv = probs
            if quantized:
                # V dequant folded into the probs (per value row); probs are
                # zero past length, so garbage scales multiply into zeros
                v_s = v_scale_ref[0, :, :, pl.ds(i * block_tokens,
                                                 block_tokens)]  # [Hkv, 1, PB*P]
                pv = probs * v_s
            acc_new = acc_prev * correction + jax.lax.dot_general(
                pv.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )                                                   # [Hkv, G, D]
            return m_new, l_new, acc_new

        m0 = jnp.full((hkv, g, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((hkv, g, 1), jnp.float32)
        acc0 = jnp.zeros((hkv, g, d), jnp.float32)
        _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
        walk[0] = done + n_blocks
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc / safe_l).astype(out_ref.dtype)

    @pl.when(n_blocks == 0)
    def _empty():
        out_ref[0] = jnp.zeros((hkv, g, d), out_ref.dtype)


def paged_attention(
    q, k_pool, v_pool, page_table, lengths, *,
    k_scale=None, v_scale=None, layer=None, window: int = 0,
    interpret: bool = False,
):
    """Pallas paged decode attention — compiled by Mosaic, or interpreted
    under ``interpret=True``. Never the XLA reference: operands the
    compiler cannot take raise ``ValueError`` with
    :func:`paged_kernel_unsupported_reason`'s reason; choosing
    :func:`paged_attention_xla` instead is the caller's decision.

    Shapes as in :func:`paged_attention_xla` (head-major pools; the stack
    of all layers with ``layer``, which the page DMAs index in place). A
    row of length 0 costs no DMA and no flash block, and reads zeros.
    ``k_scale``/``v_scale`` ([Hkv, N, P] f32, stacked like the pools):
    per-(token, head) dequant scales for int8 pools (required when the
    pools are int8); dequant fuses into the in-kernel flash update (module
    docstring).

    ``window`` (static; 0 = none, the program of before): the row's query
    sees its last ``window`` keys. The block walk starts at the first page
    that holds one (:func:`window_first_page`): pages wholly behind the
    window cost no DMA and no flash block, the partial first page is masked.
    Not with int8 pools (:func:`window_kernel_unsupported_reason`).
    """
    quantized = k_scale is not None
    window = int(window)
    _check_kernel_operands("paged_attention", q, k_pool, quantized, interpret,
                           window)

    b, hkv, g, d = q.shape
    page_size = k_pool.shape[-2]
    pages_per_seq = page_table.shape[1]
    pb = decode_pages_per_block(hkv, d, page_size, pages_per_seq, k_pool.dtype)
    cap = pages_per_seq * page_size

    kernel = functools.partial(
        _paged_attention_kernel,
        page_size=page_size,
        pages_per_block=pb,
        quantized=quantized,
        window=window,
    )
    row_spec = pl.BlockSpec((1, hkv, g, d), lambda b, *_: (b, 0, 0, 0))
    in_specs = [
        row_spec,
        pl.BlockSpec(memory_space=pl.ANY),   # K pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),   # V pool stays in HBM
    ]
    scales = []
    if quantized:
        # pre-gather the tiny scale vectors into sequence order (XLA-side:
        # scale rows are not tile-aligned for the per-page DMA plan — see
        # module docstring); the grid pipeline DMAs each row into VMEM.
        # [Hkv, N, P] -> [Hkv, B, PP, P] -> [B, Hkv, 1, PP*P], padded up to
        # a block-token multiple: the kernel slices fixed block_tokens-wide
        # windows, and when pages_per_seq % pb != 0 the last window would
        # run past cap — dynamic-slice CLAMPING would then silently feed
        # valid tokens the wrong rows' scales.
        block_tokens = pb * page_size
        cap_pad = -(-cap // block_tokens) * block_tokens
        pad = ((0, 0), (0, 0), (0, 0), (0, cap_pad - cap))

        def gather(scale):
            seq = jnp.moveaxis(
                gather_pages(scale, page_table, layer).reshape(hkv, b, cap),
                0, 1,
            ).reshape(b, hkv, 1, cap)
            return jnp.pad(seq, pad)

        scale_spec = pl.BlockSpec(
            (1, hkv, 1, cap_pad), lambda b, *_: (b, 0, 0, 0)
        )
        in_specs += [scale_spec, scale_spec]
        scales = [gather(k_scale), gather(v_scale)]
    layer, k_pool, v_pool = _stacked(layer, k_pool, v_pool)
    inputs = [q, k_pool, v_pool] + scales
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # page_table, lengths, layer
        grid=(b,),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, hkv, pb * page_size, d), k_pool.dtype),
            pltpu.VMEM((2, hkv, pb * page_size, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        # the rows are walked in order: the page buffers, their semaphores
        # and the walk's counter carry from one row to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="paged_attention_decode",  # the kernel's name in a trace
    )(page_table, lengths, layer, *inputs)


# ------------------------------------------------------------------ KV write
#
# The write of a launch's new K/V into the stacked pools. As XLA ops it is a
# ROW scatter (every index given; see paged_kv_write_xla), which the v5e
# executes at ~70 ns a row: 0.4 ms a layer for a 352-token ragged pass, 10% of
# a Mistral-7B launch (PERF.md, PR 25). The kernel works on PAGES instead: a
# launch's tokens arrive in runs that share a page (a prefill chunk fills
# page after page; a decode row is a run of one), a [P, D] page plane is the
# smallest block of a bf16 pool a DMA can move (a row is half a packed
# sublane), and so each run is one fetch of its page for all heads, a patch
# of its rows in VMEM, and one store.

def paged_kv_write_xla(k_pool, v_pool, k_new, v_new, write_page,
                       write_offset, layer=None):
    """Reference (and the CPU / gated-shape path): ``k_new`` / ``v_new``
    ``[T, Hkv, D]`` stored at ``pool[(layer,) h, write_page[t],
    write_offset[t]]``; returns the pools. EVERY index is given, heads too,
    so that the scatter's update window is one [D] row: with the heads left
    as a slice the window is [Hkv, D], XLA then keeps the whole stack in a
    layout with the heads next to D, and converts ALL of it to the kernels'
    row-major layout in front of every attention call (the v5e compiler's
    output; tests/test_tpu_compile.py). Also writes the scale pools
    (``[T, Hkv]`` into ``[(L,) Hkv, N, P]``)."""
    heads = jnp.arange(k_new.shape[1], dtype=jnp.int32)
    at = (heads, write_page[:, None], write_offset[:, None])
    if layer is not None:
        at = (layer,) + at
    return (k_pool.at[at].set(k_new.astype(k_pool.dtype)),
            v_pool.at[at].set(v_new.astype(v_pool.dtype)))


def _kv_write_kernel(
    # scalar prefetch (SMEM)
    page_ref,      # [T] int32 page id per token
    off_ref,       # [T] int32 offset within the page
    layer_ref,     # [1] int32
    # inputs: k_new_ref / v_new_ref [TB, Hkv, D] VMEM (this step's tokens);
    # k_in / v_in [L, Hkv, N, P, D] ANY, aliased to the outputs and unused
    k_new_ref, v_new_ref, k_in, v_in,
    # outputs (the same buffers as k_in / v_in)
    k_hbm, v_hbm,
    # scratch: k_buf / v_buf [2, Hkv, P, D]; sems [2 slots, k/v, fetch/store]
    k_buf, v_buf, sems,
    *, block: int,
):
    del k_in, v_in
    base = pl.program_id(0) * block
    layer = layer_ref[0]
    hkv, p = k_buf.shape[1], k_buf.shape[2]

    def copies(page, slot, store):
        """The K and the V copy of one page's planes, all heads: HBM -> slot
        (``store`` 0) or slot -> HBM (``store`` 1)."""
        out = []
        for side, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
            ends = (hbm.at[layer, pl.ds(0, hkv), page], buf.at[slot])
            out.append(pltpu.make_async_copy(
                *(ends[::-1] if store else ends), sems.at[slot, side, store]
            ))
        return out

    def start(page, slot, store):
        for c in copies(page, slot, store):
            c.start()

    def wait(page, slot, store):
        for c in copies(page, slot, store):
            c.wait()

    def run_end(t0, page):
        """First token of the block past ``t0`` that writes another page."""
        return jax.lax.while_loop(
            lambda t: jnp.logical_and(
                t < block, page_ref[base + jnp.minimum(t, block - 1)] == page
            ),
            lambda t: t + 1, t0 + 1,
        )

    rows = jax.lax.broadcasted_iota(jnp.int32, (p, k_buf.shape[3]), 0)

    def patch(t, slot):
        here = rows == off_ref[base + t]
        for buf, new in ((k_buf, k_new_ref), (v_buf, v_new_ref)):
            for h in range(hkv):   # one [P, D] tile per head
                row = new[pl.ds(t, 1), h, :]                     # [1, D]
                buf[slot, h] = jnp.where(here, row, buf[slot, h])

    # Runs of tokens that share a page, two page buffers. Run j patches slot
    # j % 2 while the fetch of run j+1 flies into the other slot, which is
    # issued only once the store of run j-1 (that slot's last use) has
    # landed; consecutive runs differ in their page by construction. So a
    # page is never read while a store to it is in flight, whatever the
    # coordinates, and tokens apply in order (the last of a duplicate wins).
    page0 = page_ref[base]
    start(page0, 0, store=0)

    def run(carry):
        t0, j, page, prev = carry
        slot = jax.lax.rem(j, 2)
        t1 = run_end(t0, page)
        nxt = page_ref[base + jnp.minimum(t1, block - 1)]
        wait(page, slot, store=0)

        @pl.when(t1 < block)
        def _prefetch():
            @pl.when(j > 0)
            def _landed():
                wait(prev, 1 - slot, store=1)

            start(nxt, 1 - slot, store=0)

        jax.lax.fori_loop(t0, t1, lambda t, c: (patch(t, slot), c)[1], 0)
        start(page, slot, store=1)
        return t1, j + 1, nxt, page

    _, n_runs, _, last = jax.lax.while_loop(
        lambda c: c[0] < block, run, (0, 0, page0, page0)
    )
    # in flight still: the last run's store, and the one before it (a run
    # waits for its predecessor's store only when it prefetches a successor).
    # A wait needs the semaphore and the size alone, so any page stands in.

    @pl.when(n_runs > 1)
    def _before_last():
        wait(last, jax.lax.rem(n_runs, 2), store=1)

    wait(last, jax.lax.rem(n_runs - 1, 2), store=1)


def paged_kv_write(k_pool, v_pool, k_new, v_new, write_page, write_offset, *,
                   layer=None, interpret: bool = False):
    """Pallas write of a launch's new K/V into the pools, IN PLACE
    (``input_output_aliases``; the pools stay in HBM): :func:
    `paged_kv_write_xla`'s result wherever coordinates are not duplicated
    (of duplicates the last token wins; XLA leaves the winner open — the
    engine's only duplicates are its pads on the null page). Same gates as
    the attention kernels (:func:`paged_kernel_unsupported_reason`); never
    the reference."""
    _check_kernel_operands("paged_kv_write", k_new, k_pool, True, interpret)
    t, hkv, d = k_new.shape
    one_layer = k_pool.ndim == 4
    layer, k_pool, v_pool = _stacked(layer, k_pool, v_pool)
    page_size = k_pool.shape[3]
    # tokens per grid step: a step's new K/V sit in VMEM whole, 2 MB a side
    # at most (the pipeline holds two steps of both)
    fit = (2 << 20) // (max(hkv, 16) * d * k_pool.dtype.itemsize)
    block = max(b for b in range(1, min(t, max(fit, 1)) + 1) if t % b == 0)
    new_spec = pl.BlockSpec((block, hkv, d), lambda i, *_: (i, 0, 0))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    pools = pl.pallas_call(
        functools.partial(_kv_write_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # write_page, write_offset, layer
            grid=(t // block,),
            in_specs=[new_spec, new_spec, anywhere, anywhere],
            out_specs=[anywhere, anywhere],
            scratch_shapes=[
                pltpu.VMEM((2, hkv, page_size, d), k_pool.dtype),
                pltpu.VMEM((2, hkv, page_size, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2, 2)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # operands count the scalar prefetch: 3 + (k_new, v_new, k, v)
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
        name="paged_kv_write",
    )(write_page, write_offset, layer,
      k_new.astype(k_pool.dtype), v_new.astype(v_pool.dtype), k_pool, v_pool)
    return tuple(pool[0] for pool in pools) if one_layer else tuple(pools)


# ----------------------------------------------------------- ragged (mixed)

# Ragged paged attention (PAPERS.md "Ragged Paged Attention",
# docs/ragged_attention.md): ONE kernel over a batch whose rows sit at
# arbitrary phases — a decode row contributes one query token, a prefill row
# contributes a whole prompt chunk. The queries of all rows flatten into one
# token-major operand; per-row offsets/lengths ride in SMEM. This is what
# lets the engine's token-budget scheduler put chunked prefill and decode in
# a single launch instead of two dispatches (llm/engine.py ragged mode).
#
# Layout:
#     q           [T, Hkv, G, D]   flattened ragged queries: row r occupies
#                                  q[row_starts[r] : row_starts[r]+row_lens[r]]
#     page_table  [R, PP]          one row per batch row (same pools/ids as
#                                  the decode kernel above)
#     kv_lens     [R]              tokens present per row INCLUDING this
#                                  step's chunk (K/V are written before the
#                                  attention call, like decode_paged)
#     row_starts  [R], row_lens [R]  the ragged row map (row_lens 0 = idle)
#
# Causality: query i of row r sits at absolute position
# kv_lens[r] - row_lens[r] + i and attends KV positions <= its own — decode
# rows (row_lens 1) degenerate to exactly the decode kernel's masking,
# prefill rows get the standard causal triangle against their own history.
#
# Pallas design (the work plan of ISSUE 30, the decode kernel's of ISSUE 28
# carried over): the grid walks a host-built list of WORK ITEMS, in order. An
# item is (row, first query) and owns up to ``query_tile`` queries of its row
# with ALL the kv heads: a decode row and a verify row are one item, a prompt
# chunk is one item per query tile (:func:`ragged_work_items`; the count
# follows in the kernel from ``row_lens``, so a row the engine dropped after
# planning, an idle row and the padding of the list are items of zero queries:
# no DMA, and the zeros the output was born with). What the plan is made of:
#
# - **A page is one strided descriptor a side for all kv heads**, into
#   ``[2 slots, Hkv, block, D]`` buffers, semaphores per (slot, side),
#   ``fori_loop``s over the pages that exist: the decode kernel's, with its
#   block (:func:`decode_pages_per_block`, at most 512 tokens). Pages past an
#   item's causal bound are never copied.
# - **A tile's context is read once**: an item of up to 8 queries (decode,
#   verify) runs the flash update on ``[Hkv, 8*G, block]`` scores; a larger
#   one keeps the flash state of its whole tile in VMEM and updates it, sub
#   tile by sub tile (``_RAGGED_SUB_ROWS`` MXU rows a head), against each
#   block while the block is there. Sub tiles whose causal bound lies under a
#   block skip it. The tile (:func:`ragged_query_tile`) follows from the
#   shapes and ``_RAGGED_SCRATCH_BYTES`` of VMEM: 128 queries at Hkv 8, G 4,
#   D 128.
# - **Only the first live item of a call starts cold**: buffers, semaphores
#   and the walk's counters (SMEM) persist across grid steps; while an item's
#   last block is computed, the next live item's first block and its queries
#   are in flight. A further tile of the same row is a next item like any
#   other: its first block is fetched again, into the other slot.
# - **q and out are head-major** ``[Hkv, T*G, D]`` in HBM (the wrapper's two
#   transposes) and move by manual copies of ``_RAGGED_QB`` = 8 tokens, all
#   heads a descriptor: that a row's segment starts at a multiple of 8
#   (:func:`ragged_layout`) is what these copies need, and all they need.
#   ``out`` is born zero (an aliased operand), so tokens no item owns read 0.
#   This aligned view ([:func:`ragged_view_tokens`] rows) exists around the
#   token-mixing kernel only: the model step keeps its tokens packed on a
#   compact axis for everything that is per token, places q into the view
#   and gathers out back (models/llama.py ``forward_ragged``).
# - the int8 path's pre-gathered per-row scale operands pipeline per ITEM via
#   an index map that reads the item's row, all heads a block.

_RAGGED_QB = 8  # tokens a q / out copy moves; ragged_layout's row alignment
# The most queries an item holds, the MXU rows a head of one flash update of
# a large item, and the VMEM the tile's flash state and q / out buffers may
# take: the tile of a call follows from its shapes (ragged_query_tile).
_RAGGED_TILE_QUERIES = 128
_RAGGED_SUB_ROWS = 128
_RAGGED_SCRATCH_BYTES = 12 << 20
_RAGGED_VMEM_LIMIT = 64 << 20   # of the v5e's 128 MiB: scratch, scales, temporaries


def ragged_layout(row_lens, q_block: int = _RAGGED_QB, total: int | None = None):
    """Host-side layout of a ragged batch: returns (row_starts [R], t_pad)
    with every row's flat segment aligned to ``q_block`` (8 for the kernel's
    q / out copies, 1 packs rows densely for the XLA reference). ``total``
    pads the flat token axis to a fixed static size so engine traces stay
    bucketed."""
    import numpy as np

    lens = np.asarray(row_lens, np.int32)
    starts = np.zeros(lens.shape[0], np.int32)
    off = 0
    for r, n in enumerate(lens):
        starts[r] = off
        if n > 0:
            off += -(-int(n) // q_block) * q_block
    t_pad = -(-max(off, 1) // q_block) * q_block
    if total is not None:
        if total < t_pad:
            raise ValueError(
                "ragged layout needs {} tokens but total={}".format(t_pad, total)
            )
        t_pad = -(-int(total) // q_block) * q_block
    return starts, int(t_pad)


def ragged_view_tokens(tokens: int, rows: int, q_block: int = _RAGGED_QB) -> int:
    """Rows of the aligned view that holds any launch of at most ``tokens``
    tokens in ``rows`` rows under :func:`ragged_layout`: every row may waste
    one copy less a token. With ``q_block`` 1 the view is the compact axis
    itself. The engine sizes its layout (and ``tree_anc``) with this and the
    model step its view, from the same shapes."""
    q_block = int(q_block)
    return -(-(int(tokens) + int(rows) * (q_block - 1)) // q_block) * q_block


def _ragged_sub_queries(g):
    """Queries of one flash update of a large item: ``_RAGGED_SUB_ROWS``
    MXU rows a head, whole 8-query copies."""
    return max(_RAGGED_QB, _RAGGED_SUB_ROWS // g // _RAGGED_QB * _RAGGED_QB)


def ragged_query_tile(hkv, g, head_dim, q_dtype):
    """Queries one work item of the ragged kernel owns at most: whole sub
    tiles, as many as keep the tile's flash state (f32 accumulator, running
    max and sum, a lane tile each), two q buffers and the out buffer inside
    :data:`_RAGGED_SCRATCH_BYTES`, at most :data:`_RAGGED_TILE_QUERIES`
    (128 at Hkv 8, G 4, D 128, bf16). The engine builds its item list with
    the same function over the same shapes."""
    sq = _ragged_sub_queries(g)
    per_query = hkv * g * (
        4 * (head_dim + 2 * 128) + 3 * head_dim * jnp.dtype(q_dtype).itemsize
    )
    fit = min(_RAGGED_SCRATCH_BYTES // per_query, _RAGGED_TILE_QUERIES)
    return max(sq, fit // sq * sq)


def ragged_item_count(rows: int, tokens: int, query_tile: int) -> int:
    """Length of the item list that holds any batch of ``rows`` rows on
    ``tokens`` flat tokens: a live row is one item, and one more for every
    whole tile of queries before its last."""
    return rows + tokens // query_tile


def ragged_work_items(row_lens, query_tile: int, total: int | None = None):
    """Host-side work plan of the ragged kernel: (item_rows [NI], item_q0
    [NI]) numpy int32 — per item its row and the first query it owns; it
    owns ``min(query_tile, row_lens[row] - q0)`` of them. Rows in order,
    a row's tiles in order. ``total`` pads the list with items of no row
    (-1) to a static length."""
    import numpy as np

    rows, q0s = [], []
    for r, n in enumerate(np.asarray(row_lens, np.int32)):
        for q0 in range(0, int(n), query_tile):
            rows.append(r)
            q0s.append(q0)
    n_items = max(len(rows), 1) if total is None else int(total)
    if len(rows) > n_items:
        raise ValueError(
            "ragged batch needs {} work items but total={}".format(
                len(rows), n_items))
    pad = n_items - len(rows)
    return (np.asarray(rows + [-1] * pad, np.int32),
            np.asarray(q0s + [0] * pad, np.int32))


def tree_ancestors(parents, n_nodes=None, *, width=None):
    """Host-side tree-topology mask metadata for a verify row
    (docs/spec_decode_trees.md): per-node ancestor lists.

    ``parents`` [N] int32 with ``parents[0] == -1`` and
    ``parents[j] < j`` (spec_proposer.DraftForest layout). Returns
    ``[N, width]`` int32 where row j lists the in-row indices of node
    j's root-to-node path INCLUDING itself, -1 padded. ``width``
    defaults to N (the deepest possible chain). Dead nodes (>=
    ``n_nodes``) get all -1 rows — they still mask causally but match
    no ancestor, so their (garbage) outputs attend history only.

    The kernels treat ``anc[t, 0] == -2`` as the PLAIN-CAUSAL sentinel
    (non-tree rows); this builder never emits it — the engine stamps it
    on every token outside a tree row."""
    import numpy as np

    parents = np.asarray(parents, np.int32)
    n = parents.shape[0]
    live = n if n_nodes is None else int(n_nodes)
    w = n if width is None else int(width)
    out = np.full((n, w), -1, np.int32)
    for j in range(live):
        chain = []
        node = j
        while node >= 0:
            chain.append(node)
            node = int(parents[node])
        if len(chain) > w:
            raise ValueError(
                "tree depth {} exceeds ancestor width {}".format(
                    len(chain), w))
        out[j, : len(chain)] = chain[::-1]
    return out


def ragged_paged_attention_xla(q, k_pool, v_pool, page_table, kv_lens,
                               row_starts, row_lens,
                               k_scale=None, v_scale=None,
                               tree_anc=None, layer=None, window: int = 0):
    """Reference ragged paged attention in plain XLA ops (CPU fallback).

    Shapes per the module's ragged section (pools of one layer, or the
    stack with ``layer``); returns [T, Hkv, G, D] with zeros at tokens no
    row owns. Per-token math mirrors
    :func:`paged_attention_xla` exactly (same contraction order, f32
    softmax, probs cast to the value dtype before the PV product) so a
    decode row's output is the decode reference's output — the engine's
    byte-identity A/B rests on that.

    The pool gather runs per ROW ([Hkv, R, cap, D]) and fans out to
    tokens by row index — the per-token [T, cap] operand still
    materializes for the score/PV einsums (acceptable at the fallback's
    test/smoke scale; the Pallas kernel is the capacity-scale path), but
    HBM gather traffic stays R*cap, not T*cap.

    ``window`` (static; 0 = none): a query at position p sees the keys
    ``p - window < s <= p``."""
    if window and tree_anc is not None:
        raise ValueError("ragged_paged_attention_xla: {}".format(
            window_kernel_unsupported_reason(window, tree=True)))
    t, hkv, g, d = q.shape
    p = k_pool.shape[-2]
    pp = page_table.shape[1]
    cap = pp * p
    t_idx = jnp.arange(t, dtype=jnp.int32)
    ends = row_starts + row_lens
    in_row = (t_idx[None, :] >= row_starts[:, None]) & (
        t_idx[None, :] < ends[:, None]
    )                                                       # [R, T]
    tok_valid = jnp.any(in_row, axis=0)                     # [T]
    tok_row = jnp.argmax(in_row, axis=0).astype(jnp.int32)  # [T]
    qi = t_idx - row_starts[tok_row]
    base = (kv_lens - row_lens)[tok_row]
    bound = jnp.where(
        tok_valid, jnp.minimum(base + qi + 1, kv_lens[tok_row]), 0
    )                                                       # [T]
    k_rows = gather_pages(k_pool, page_table, layer).reshape(
        hkv, -1, cap, d
    )                                                       # [Hkv, R, cap, D]
    v_rows = gather_pages(v_pool, page_table, layer).reshape(hkv, -1, cap, d)
    if k_scale is not None:
        ks = gather_pages(k_scale, page_table, layer).reshape(hkv, -1, cap, 1)
        vs = gather_pages(v_scale, page_table, layer).reshape(hkv, -1, cap, 1)
        k_rows = (k_rows.astype(jnp.float32) * ks).astype(q.dtype)
        v_rows = (v_rows.astype(jnp.float32) * vs).astype(q.dtype)
    k = k_rows[:, tok_row]                                  # [Hkv, T, cap, D]
    v = v_rows[:, tok_row]
    scores = jnp.einsum(
        "thgd,htcd->thgc", q, k, preferred_element_type=jnp.float32
    ) * (d ** -0.5)
    valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < bound[:, None]
    if window:
        valid = valid & (
            jnp.arange(cap, dtype=jnp.int32)[None, :] >= bound[:, None] - window
        )
    if tree_anc is not None:
        # tree-topology pruning INSIDE the causal bound
        # (docs/spec_decode_trees.md): a tree row's query attends its
        # history plus its own root-to-node ancestor path only. In-row
        # offsets compare against the per-token ancestor list;
        # anc[t, 0] == -2 marks plain-causal tokens (mask unchanged).
        off = jnp.arange(cap, dtype=jnp.int32)[None, :] - base[:, None]
        anc = jnp.any(
            off[:, :, None] == tree_anc[:, None, :], axis=-1
        )                                                   # [T, cap]
        plain = (tree_anc[:, 0] == -2)[:, None]
        valid = valid & (plain | (off < 0) | anc)
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    row_max = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), -1e30)
    probs = jnp.exp(scores - row_max)
    probs = jnp.where(valid[:, None, None, :], probs, 0.0)
    denom = jnp.sum(probs, axis=-1, keepdims=True)
    probs = (probs / jnp.where(denom == 0.0, 1.0, denom)).astype(v.dtype)
    out = jnp.einsum("thgc,htcd->thgd", probs, v)
    return out.astype(q.dtype)




def _ragged_attention_kernel(
    # scalar prefetch (SMEM): item_rows [NI], item_q0 [NI],
    # page_table [R, PP], kv_lens [R], row_starts [R], row_lens [R],
    # layer [1], tree only: tree_anc [T * tree_width] (flat; per token: in-row
    # ancestor indices incl. self, -1 padded; first entry -2 => plain causal)
    *refs,
    page_size: int,
    pages_per_block: int,
    query_tile: int,
    sub_queries: int,
    group: int,
    quantized: bool = False,
    tree_width: int = 0,
    window: int = 0,
):
    # then positionally: q_hbm [Hkv, T*G, D] (ANY); k_hbm/v_hbm
    # [L,Hkv,N,P,D] (ANY); quantized only: k_scale_ref/v_scale_ref
    # [1,Hkv,1,cap_pad] (the item's ROW's pre-gathered scales, pipelined by
    # the item_rows index map); the zeros out is born as (ANY, aliased,
    # unused); out_hbm [Hkv, T*G, D] (ANY).
    # Scratch, all of it kept from one grid step to the next:
    #   q_buf  [2, Hkv, QT*G, D]   the item's queries / the next item's
    #   out_buf   [Hkv, QT*G, D]
    #   k_buf, v_buf [2, Hkv, PB*P, D]   double-buffered page blocks
    #   m_ref, l_ref [NSUB, Hkv, SQ*G, 1], acc_ref [NSUB, Hkv, SQ*G, D] f32:
    #          the flash state of a large item's tile
    #   kv_sems [2, 2] (slot, k/v), q_sems [2] (slot), out_sem [1]
    #   walk [4] int32 SMEM: context blocks consumed so far, the pages of
    #          the coming item's first block that are in flight, live items
    #          so far, out copies in flight
    (item_rows_ref, item_q0_ref, page_table_ref, kv_lens_ref,
     row_starts_ref, row_lens_ref, layer_ref) = refs[:7]
    refs = refs[7:]
    tree = tree_width > 0
    if tree:
        tree_anc_ref, refs = refs[0], refs[1:]
    if quantized:
        q_hbm, k_hbm, v_hbm, k_scale_ref, v_scale_ref = refs[:5]
        refs = refs[5:]
    else:
        (q_hbm, k_hbm, v_hbm), refs = refs[:3], refs[3:]
        k_scale_ref = v_scale_ref = None
    (_zeros, out_hbm, q_buf, out_buf, k_buf, v_buf, m_ref, l_ref, acc_ref,
     kv_sems, q_sems, out_sem, walk) = refs
    i = pl.program_id(0)
    ni = pl.num_programs(0)
    hkv, d = q_buf.shape[1], q_buf.shape[3]
    g = group
    p = page_size
    pb = pages_per_block
    bt = pb * p                      # tokens of a context block
    qt = query_tile
    sq = sub_queries
    sm = sq * g                      # MXU rows a head of a large flash update
    cq = _RAGGED_QB                  # queries a q / out copy moves
    cr = cq * g
    n_tokens = q_hbm.shape[1] // g
    layer = layer_ref[0]

    def item(j):
        """(row, first query, queries, position of the row's query 0, causal
        bound of the last query, first page to copy) of item ``j``; 0
        queries = nothing to do. The first page that holds a key the item's
        FIRST query sees: the pages before it are never copied and the
        blocks wholly before it never walked (0, a Python int, without a
        window: the program of before). The blocks keep their places on the
        row, so a query's keys meet the same blocks in the same order
        whatever tile it rides in: its output does not depend on how the
        prompt was cut into chunks."""
        row = jnp.maximum(item_rows_ref[j], 0)
        q0 = item_q0_ref[j]
        row_len = row_lens_ref[row]
        qn = jnp.where(
            item_rows_ref[j] >= 0, jnp.clip(row_len - q0, 0, qt), 0
        )
        base = kv_lens_ref[row] - row_len
        page0 = window_first_page(base + q0, window, p) if window else 0
        return (row, q0, qn, base, jnp.where(qn > 0, base + q0 + qn, 0),
                page0)

    def next_live(j):
        """First item after ``j`` with queries (``ni`` if none)."""
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < ni, item(jnp.minimum(r, ni - 1))[2] == 0
            ),
            lambda r: r + 1, j + 1,
        )

    def first_block(page0):
        return page0 // pb if window else 0

    def block_pages(bound, block):
        return jnp.clip((bound - block * bt + p - 1) // p, 0, pb)

    def block_copies(act, row, block, slot, pages, page0):
        """Start, or wait for, the pages of a block up to its ``pages``-th,
        from the first the window leaves to copy (``page0``): a page's K and
        V planes of ALL kv heads are one strided descriptor a side, onto the
        slot's semaphore of that side."""
        def page(j, carry):
            page_id = page_table_ref[row, block * pb + j]
            dst = pl.ds(pl.multiple_of(j * p, p), p)
            for side, (hbm, buf) in enumerate(
                ((k_hbm, k_buf), (v_hbm, v_buf))
            ):
                getattr(pltpu.make_async_copy(
                    hbm.at[layer, pl.ds(0, hkv), page_id],
                    buf.at[slot, pl.ds(0, hkv), dst],
                    kv_sems.at[slot, side],
                ), act)()
            return carry

        first = jnp.clip(page0 - block * pb, 0, pb) if window else 0
        jax.lax.fori_loop(first, pages, page, 0)

    def q_copies(act, j, slot):
        """Item ``j``'s queries, 8 tokens of all heads a descriptor."""
        row, q0, qn = item(j)[:3]
        t0 = row_starts_ref[row] + q0

        def chunk(c, carry):
            getattr(pltpu.make_async_copy(
                q_hbm.at[pl.ds(0, hkv),
                         pl.ds(pl.multiple_of((t0 + c * cq) * g, cr), cr)],
                q_buf.at[slot, pl.ds(0, hkv),
                         pl.ds(pl.multiple_of(c * cr, cr), cr)],
                q_sems.at[slot],
            ), act)()
            return carry

        jax.lax.fori_loop(0, (qn + cq - 1) // cq, chunk, 0)

    def out_copies(act, t0, chunks):
        def chunk(c, carry):
            getattr(pltpu.make_async_copy(
                out_buf.at[pl.ds(0, hkv),
                           pl.ds(pl.multiple_of(c * cr, cr), cr)],
                out_hbm.at[pl.ds(0, hkv),
                           pl.ds(pl.multiple_of((t0 + c * cq) * g, cr), cr)],
                out_sem.at[0],
            ), act)()
            return carry

        jax.lax.fori_loop(0, chunks, chunk, 0)

    @pl.when(i == 0)
    def _first():
        # the only cold start of a call: the first live item's first block
        # and its queries
        first = next_live(-1)
        row, _, _, _, bound, page0 = item(jnp.minimum(first, ni - 1))
        block0 = first_block(page0)
        pages = jnp.where(first < ni, block_pages(bound, block0), 0)
        walk[0] = 0
        walk[1] = pages
        walk[2] = 0
        walk[3] = 0
        block_copies("start", row, block0, 0, pages, page0)

        @pl.when(first < ni)
        def _():
            q_copies("start", first, 0)

    row, q0, qn, base, bound, page0 = item(i)
    row_len = row_lens_ref[row]
    b0 = first_block(page0)          # blocks wholly behind the window
    n_blocks = _plus((bound + bt - 1) // bt, -b0)
    t0 = row_starts_ref[row] + q0

    def flash(q, k, v, block, carry, q_first):
        """One flash update of queries ``q_first ...`` of the row (``q``
        [Hkv, M, D], row index = query * G + head of the group) against a
        context block, batched over the kv heads: the decode kernel's
        arithmetic, with the ragged terms in the mask."""
        m_prev, l_prev, acc_prev = carry
        rows_m = q.shape[1]
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * (d ** -0.5)                                     # [Hkv, M, PB*P]
        if quantized:
            scores = scores * k_scale_ref[0, :, :, pl.ds(block * bt, bt)]
        # per-query causal masking: query q_first+qi attends KV positions
        # <= base+q_first+qi; i32 iota compares (Mosaic: no i1 minor dim)
        shape = (1, rows_m, bt)
        token_ids = block * bt + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        qi = jax.lax.broadcasted_iota(jnp.int32, shape, 1) // g
        q_live = (q_first + qi) < row_len                   # query exists
        valid = (token_ids < base + q_first + qi + 1) & q_live
        if window:
            # the first block's keys behind the first query's window (its
            # pages before ``page0`` were not copied), and the keys the
            # tile's later queries no longer see
            valid = valid & (token_ids > base + q_first + qi - window)
        if tree:
            # tree-topology pruning inside the unchanged causal bound
            # (docs/spec_decode_trees.md): parent-before-child node order
            # keeps base+q+1 a valid upper bound, so trees only MASK within
            # the pages already copied. Ancestor lists live flat in SMEM;
            # the per-query unroll is static (queries x DMAX scalar reads).
            # Every term is an i32 vector compare ORed/ANDed together:
            # Mosaic refuses a select between i1 vectors and a scalar bool
            # broadcast into one (module docstring), so the plain-causal
            # sentinel and the av >= 0 guard are vector compares too.
            tok_off = token_ids - base          # in-row kv offset
            history = tok_off < 0               # history always
            in_row = tok_off >= 0
            tok0 = row_starts_ref[row] + q_first

            def allow_mask():
                allow = None
                for qs in range(rows_m // g):
                    a0 = jnp.minimum(tok0 + qs, n_tokens - 1) * tree_width
                    # anc[t, 0] == -2: plain-causal token, mask unchanged
                    match = history | (
                        jnp.full_like(tok_off, tree_anc_ref[a0]) == -2
                    )
                    for a in range(tree_width):
                        # av < 0 is padding; tok_off >= 0 stands in for
                        # av >= 0 once tok_off == av
                        match = match | (
                            (tok_off == tree_anc_ref[a0 + a]) & in_row
                        )
                    term = (qi == qs) & match
                    allow = term if allow is None else allow | term
                return allow.astype(jnp.int32)

            if rows_m == cr:
                allow = allow_mask()
            else:
                # a prompt chunk's sub tile: plain causal throughout, unless
                # a verify row of more than 8 nodes came this way
                n_tree = jax.lax.fori_loop(
                    0, rows_m // g,
                    lambda qs, n: n + (tree_anc_ref[
                        jnp.minimum(tok0 + qs, n_tokens - 1) * tree_width
                    ] != -2).astype(jnp.int32),
                    0,
                )
                allow = jax.lax.cond(
                    n_tree > 0, allow_mask,
                    lambda: jnp.ones(shape, jnp.int32),
                )
            valid = valid & (allow != 0)
        scores = jnp.where(valid, scores, -jnp.inf)
        block_max = jnp.maximum(jnp.max(scores, axis=2, keepdims=True), -1e30)
        m_new = jnp.maximum(m_prev, block_max)              # [Hkv, M, 1]
        probs = jnp.exp(scores - m_new)
        probs = jnp.where(valid, probs, 0.0)
        correction = jnp.exp(m_prev - m_new)
        # the softmax denominator sums the UNSCALED probs; v_scale belongs
        # only to the PV product
        l_new = l_prev * correction + jnp.sum(probs, axis=2, keepdims=True)
        pv = probs
        if quantized:
            pv = probs * v_scale_ref[0, :, :, pl.ds(block * bt, bt)]
        acc_new = acc_prev * correction + jax.lax.dot_general(
            pv.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                   # [Hkv, M, D]
        return m_new, l_new, acc_new

    def run(small):
        """The item: its context blocks in order through the two slots, the
        next live item's first block and queries in flight before its last
        block is computed. ``small`` (static): up to 8 queries, the flash
        state a loop carry; else the tile's state in VMEM, sub tile by sub
        tile against each block."""
        done, pending = walk[0], walk[1]
        q_slot = jax.lax.rem(walk[2], 2)
        nxt = next_live(i)
        more = nxt < ni
        n_row, _, _, _, n_bound, n_page0 = item(jnp.minimum(nxt, ni - 1))
        n_block0 = first_block(n_page0)
        n_pages = jnp.where(more, block_pages(n_bound, n_block0), 0)

        @pl.when(more)
        def _next_queries():
            q_copies("start", nxt, 1 - q_slot)

        q_copies("wait", i, q_slot)
        n_sub = (qn + sq - 1) // sq
        if not small:
            def init(s, carry):
                m_ref[s] = jnp.full(m_ref.shape[1:], -jnp.inf, jnp.float32)
                l_ref[s] = jnp.zeros(l_ref.shape[1:], jnp.float32)
                acc_ref[s] = jnp.zeros(acc_ref.shape[1:], jnp.float32)
                return carry

            jax.lax.fori_loop(0, n_sub, init, 0)

        def body(step, carry):
            slot = jax.lax.rem(done + step, 2)
            block = _plus(step, b0)     # the block's place on the row

            @pl.when(step + 1 < n_blocks)
            def _prefetch():
                block_copies("start", row, block + 1, 1 - slot,
                             block_pages(bound, block + 1), page0)

            @pl.when(step + 1 == n_blocks)
            def _prefetch_next_item():
                block_copies("start", n_row, n_block0, 1 - slot, n_pages,
                             n_page0)

            block_copies("wait", row, block, slot, jnp.where(
                step == 0, pending, block_pages(bound, block)), page0)

            # K/V feed the MXU in pool dtype (bf16) with f32 accumulation;
            # int8 pools as raw int8 cast to the compute dtype (lossless),
            # their per-token scales folded into the f32 scores / probs
            k = k_buf[slot]                                 # [Hkv, PB*P, D]
            v = v_buf[slot]
            if quantized:
                k = k.astype(out_buf.dtype)
                v = v.astype(out_buf.dtype)
            # rows past the bound were never DMA'd: zero before the matmul
            row_ids = block * bt + jax.lax.broadcasted_iota(
                jnp.int32, (1, bt, 1), 1
            )
            live = row_ids < bound
            if window:
                live = live & (row_ids >= page0 * p)
            v = jnp.where(live, v, jnp.zeros_like(v))
            if small:
                return flash(q_buf[q_slot, :, :cr], k, v, block, carry, q0)

            def sub(s, c):
                at = pl.ds(pl.multiple_of(s * sm, sm), sm)
                state = flash(
                    q_buf[q_slot, :, at], k, v, block,
                    (m_ref[s], l_ref[s], acc_ref[s]), q0 + s * sq,
                )
                m_ref[s], l_ref[s], acc_ref[s] = state
                return c

            # sub tiles whose last query's bound lies under this block
            # have nothing in it
            under = jnp.maximum(block * bt - base - q0, 0) // sq
            jax.lax.fori_loop(jnp.minimum(under, n_sub - 1), n_sub, sub, 0)
            return carry

        def result(l, acc):
            return (acc / jnp.where(l == 0.0, 1.0, l)).astype(out_buf.dtype)

        if small:
            _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (
                jnp.full((hkv, cr, 1), -jnp.inf, jnp.float32),
                jnp.zeros((hkv, cr, 1), jnp.float32),
                jnp.zeros((hkv, cr, d), jnp.float32),
            ))
        else:
            jax.lax.fori_loop(0, n_blocks, body, 0)
        # the out buffer is the previous item's until its copies landed
        out_copies("wait", 0, walk[3])
        if small:
            out_buf[:, :cr] = result(l, acc)
        else:
            def write(s, carry):
                at = pl.ds(pl.multiple_of(s * sm, sm), sm)
                out_buf[:, at] = result(l_ref[s], acc_ref[s])
                return carry

            jax.lax.fori_loop(0, n_sub, write, 0)
        chunks = (qn + cq - 1) // cq
        out_copies("start", t0, chunks)
        walk[0] = done + n_blocks
        walk[1] = n_pages
        walk[2] = walk[2] + 1
        walk[3] = chunks

    pl.when(jnp.logical_and(qn > 0, qn <= cq))(lambda: run(True))
    pl.when(qn > cq)(lambda: run(False))

    @pl.when(i == ni - 1)
    def _drain():
        out_copies("wait", 0, walk[3])
        walk[3] = 0


def ragged_paged_attention(
    q, k_pool, v_pool, page_table, kv_lens, row_starts, row_lens, *,
    item_rows=None, item_q0=None,
    k_scale=None, v_scale=None, tree_anc=None, layer=None, window: int = 0,
    interpret: bool = False,
):
    """Ragged paged attention over mixed prefill+decode rows — compiled by
    Mosaic, or interpreted under ``interpret=True``. Never the XLA
    reference: like :func:`paged_attention`, operands the compiler cannot
    take (the SAME gates as the decode kernel: D % 128, dtype-dependent
    page sublane tile) raise ``ValueError``;
    :func:`ragged_paged_attention_xla` is the caller's choice. Pools and
    scale pools are one layer's, or the stack with ``layer``, as in
    :func:`paged_attention`.

    ``item_rows``/``item_q0`` ([NI] int32, any NI that holds the batch) are
    the host-built work plan (:func:`ragged_work_items`); the kernel
    REQUIRES them (a grid cannot be derived from traced row metadata on
    device), and ``row_starts`` at multiples of 8 (:func:`ragged_layout`).
    The plan MUST be built at :func:`ragged_query_tile` of THIS call's
    shapes and ``q.dtype``: the kernel cuts every item at that tile and
    cannot check the list against it. A plan of a larger tile leaves
    queries at zero, one of a smaller tile writes outputs twice.

    ``tree_anc`` ([T, DMAX] int32, optional) turns spec-verify rows into
    draft-TREE rows (docs/spec_decode_trees.md): per flat token, the
    in-row indices of its root-to-node ancestor path (self included, -1
    padded); ``tree_anc[t, 0] == -2`` keeps token t plain-causal. Only
    the mask changes — the page DMA plan is topology-blind.

    ``window`` (static; 0 = none, the program of before): a query at
    position p sees the keys ``p - window < s <= p``. A work item's block
    walk starts at the first page that holds a key its FIRST query sees
    (:func:`window_first_page`); pages wholly behind it cost no DMA and no
    flash block, and the mask takes the partial first page and the keys the
    tile's later queries no longer see. Not with int8 pools nor with
    ``tree_anc`` (:func:`window_kernel_unsupported_reason`)."""
    quantized = k_scale is not None
    window = int(window)
    if item_rows is None or item_q0 is None:
        raise ValueError(
            "ragged_paged_attention needs the host-built item_rows/item_q0 "
            "work plan (ragged_work_items)"
        )
    _check_kernel_operands(
        "ragged_paged_attention", q, k_pool, quantized, interpret, window,
        tree_anc is not None,
    )

    t, hkv, g0, d = q.shape
    page_size = k_pool.shape[-2]
    pages_per_seq = page_table.shape[1]
    if t % _RAGGED_QB:
        raise ValueError(
            "ragged q length {} must be a multiple of {}".format(t, _RAGGED_QB)
        )
    # 8 tokens of a head are 8*G rows of q's minor tile: whole sublane tiles
    # of its dtype, or the group pads with query heads of zeros (G 1 in bf16)
    g = g0
    while (_RAGGED_QB * g) % (32 // q.dtype.itemsize):
        g += 1
    qt = ragged_query_tile(hkv, g0, d, q.dtype)
    sq = _ragged_sub_queries(g0)
    pb = decode_pages_per_block(hkv, d, page_size, pages_per_seq, k_pool.dtype)
    block_tokens = pb * page_size
    cap = pages_per_seq * page_size

    kernel = functools.partial(
        _ragged_attention_kernel,
        page_size=page_size,
        pages_per_block=pb,
        query_tile=qt,
        sub_queries=sq,
        group=g,
        quantized=quantized,
        tree_width=0 if tree_anc is None else tree_anc.shape[1],
        window=window,
    )
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [anywhere, anywhere, anywhere]   # q, K and V pools stay in HBM
    scales = []
    if quantized:
        # per-ROW pre-gathered scales (same rationale/padding as the decode
        # kernel's: f32 scale rows are not tile-alignable for the page DMA
        # plan); the grid pipeline picks each item's row via item_rows
        cap_pad = -(-cap // block_tokens) * block_tokens
        pad = ((0, 0), (0, 0), (0, 0), (0, cap_pad - cap))
        r = page_table.shape[0]

        def gather(scale):
            seq = jnp.moveaxis(
                gather_pages(scale, page_table, layer).reshape(hkv, r, cap),
                0, 1,
            ).reshape(r, hkv, 1, cap)
            return jnp.pad(seq, pad)

        # index maps take *_ for the scalar-prefetch refs: their count is 7
        # or 8 (tree_anc) and the map never reads beyond item_rows
        scale_spec = pl.BlockSpec(
            (1, hkv, 1, cap_pad),
            lambda i, rows, *_: (jnp.maximum(rows[i], 0), 0, 0, 0),
        )
        in_specs += [scale_spec, scale_spec]
        scales = [gather(k_scale), gather(v_scale)]
    layer, k_pool, v_pool = _stacked(layer, k_pool, v_pool)
    if g != g0:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, g - g0), (0, 0)))
    q_heads = jnp.moveaxis(q, 0, 1).reshape(hkv, t * g, d)
    inputs = [q_heads, k_pool, v_pool] + scales + [jnp.zeros_like(q_heads)]
    prefetch = [item_rows, item_q0, page_table, kv_lens, row_starts,
                row_lens, layer]
    if tree_anc is not None:
        if tree_anc.shape[0] != t:
            raise ValueError(
                "tree_anc rows {} != flat token count {}".format(
                    tree_anc.shape[0], t))
        # flat: a 2-D SMEM operand pads its minor dim to 128 lanes
        prefetch.append(tree_anc.astype(jnp.int32).reshape(-1))
    n_sub = qt // sq
    state = (n_sub, hkv, sq * g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # plan, tables, layer (+ tree)
        grid=(item_rows.shape[0],),
        in_specs=in_specs + [anywhere],
        out_specs=anywhere,
        scratch_shapes=[
            pltpu.VMEM((2, hkv, qt * g, d), q.dtype),
            pltpu.VMEM((hkv, qt * g, d), q.dtype),
            pltpu.VMEM((2, hkv, block_tokens, d), k_pool.dtype),
            pltpu.VMEM((2, hkv, block_tokens, d), v_pool.dtype),
            pltpu.VMEM(state + (1,), jnp.float32),
            pltpu.VMEM(state + (1,), jnp.float32),
            pltpu.VMEM(state + (d,), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SMEM((4,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_heads.shape, q.dtype),
        # operands count the scalar prefetch; the last input is the output
        input_output_aliases={len(prefetch) + len(inputs) - 1: 0},
        # the items are walked in order: buffers, semaphores and the walk's
        # counters carry from one item to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_RAGGED_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="ragged_paged_attention",  # the kernel's name in a trace
    )(*prefetch, *inputs)
    return jnp.moveaxis(out.reshape(hkv, t, g, d), 0, 1)[:, :, :g0]
