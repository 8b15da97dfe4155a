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
pipeline DMAs into VMEM like any blocked input (the decode kernel: a row's
scales of all heads a grid step; the ragged kernel: per q block and head).
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
    head_dim: int, page_size: int, kv_dtype, *, platform: Optional[str] = None
) -> Optional[str]:
    """Why paged pools of this shape cannot take the Mosaic kernels (decode
    and ragged share the gates) — None if they can.

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
    if head_dim % 128:
        return "head_dim {} is not a multiple of the 128-lane tile".format(
            head_dim
        )
    min_sublane = 32 // jnp.dtype(kv_dtype).itemsize   # 8 f32, 16 bf16, 32 int8
    if page_size % min_sublane:
        return "page_size {} is not a multiple of the {}-row {} sublane " \
               "tile".format(page_size, min_sublane, jnp.dtype(kv_dtype).name)
    return None


def _check_kernel_operands(name, q, k_pool, quantized, interpret):
    """The kernel entry points run the kernel or raise — never a reference."""
    if jnp.issubdtype(k_pool.dtype, jnp.signedinteger) and not quantized:
        raise ValueError(
            "int8 KV pools need k_scale/v_scale operands (per-token dequant)"
        )
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
    rows: int, pages_per_seq: int, tokens: int = 0, tree_width: int = 0
) -> int:
    """SMEM the kernels' scalar-prefetch operands (and the decode kernel's
    one scalar of scratch) take: the decode kernel
    for ``rows`` sequences (``tokens == 0``), or the ragged kernel for a
    ``tokens``-wide launch (+ a ``[tokens, tree_width]`` ancestor table).
    The 2-D page table pads to (8, 128) int32 tiles — measured against the
    compiler: s32[250, 1023] allocates 256 * 1024 * 4 bytes — and each 1-D
    vector to a 512-byte line; 2 KB covers the compiler's own scalars (at
    the boundary it reported 1.1 KB more than the operands sum to). The
    engine compares this with
    :data:`SMEM_BYTES` at construction, so an oversized max_seq_len /
    max_batch / token budget is a load-time error instead of a Mosaic
    RESOURCE_EXHAUSTED on the first request."""
    def vec(n):
        return -(-4 * n // 512) * 512

    table = (-(-rows // 8) * 8) * (-(-pages_per_seq // 128) * 128) * 4
    if not tokens:
        # + lengths, layer, and the walk's block counter (SMEM scratch)
        return 2048 + table + vec(rows) + 2 * vec(1)
    nb = -(-tokens // _RAGGED_QB)
    return (
        2048 + table + 2 * vec(rows) + vec(1)          # kv_lens, row_lens, layer
        + 2 * vec(nb)                                  # block_rows, block_q0
        + vec(tokens * tree_width)                     # flat ancestor table
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
                        k_scale=None, v_scale=None, layer=None):
    """Reference implementation in plain XLA ops (also the CPU fallback).

    q: [B, Hkv, G, D]; pools: [Hkv, N, P, D], or stacked [L, Hkv, N, P, D]
    with ``layer``; page_table: [B, PP]; lengths: [B] -> out [B, Hkv, G, D].

    ``k_scale``/``v_scale`` ([Hkv, N, P] f32) dequantize int8 pools: the
    per-(token, head) symmetric scales of models/llama._kv_store. Dequant
    happens in f32 and casts to the query dtype before the attention math,
    mirroring the dense path's _kv_load, so XLA fuses it into the gather.
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
    # blocks that contain live tokens; DMA never touches pages past length
    n_blocks = (length + block_tokens - 1) // block_tokens

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

        jax.lax.fori_loop(0, block_pages(row, block), page, 0)

    start_block = functools.partial(block_copies, "start")
    wait_block = functools.partial(block_copies, "wait")

    @pl.when(b == 0)
    def _first():
        # the only cold start of a call: the first live row's first block
        walk[0] = 0
        first = next_live(-1)

        @pl.when(first < rows)
        def _():
            start_block(first, 0, 0)

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

            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                start_block(b, i + 1, 1 - slot)

            @pl.when(jnp.logical_and(i + 1 == n_blocks, after < rows))
            def _prefetch_next_row():
                start_block(after, 0, 1 - slot)

            wait_block(b, i, slot)
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
                i * block_tokens
                + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
            )
            valid = token_ids < length
            scores = jnp.where(valid, scores, -jnp.inf)
            # rows past length were never DMA'd: their buffer bytes are
            # arbitrary (NaN/inf poisons 0*v), so zero them before the matmul.
            # (int8 garbage is always finite, but the zeroing also keeps the
            # masked rows from polluting the scaled-probs matmul below.)
            # Mask built as an i32 iota compare: Mosaic cannot insert a
            # minor dim on an i1 vector (bool[:, None] fails to compile).
            row_ids = i * block_tokens + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_tokens, 1), 1
            )
            v = jnp.where(row_ids < length, v, jnp.zeros_like(v))

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
    k_scale=None, v_scale=None, layer=None, interpret: bool = False,
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
    """
    quantized = k_scale is not None
    _check_kernel_operands("paged_attention", q, k_pool, quantized, interpret)

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
# Pallas design: the grid runs (T/QB, Hkv) where QB (`q_block`) is a small
# static query block. The flattened layout is Q-BLOCK ALIGNED — every row's
# segment starts at a QB boundary (ragged_layout below builds it), so each
# q block belongs to exactly ONE row and the host passes that mapping as two
# scalar-prefetch vectors (block_rows / block_q0). Each grid step runs a
# manual double-buffered page-DMA plan per (q block, kv head) against its
# row's pages (a [P, D] plane a descriptor: the decode kernel's plan until
# ISSUE 28, whose row walk is not carried over here) — including the int8
# path's pre-gathered per-row scale operands,
# which pipeline per BLOCK via an index map that reads block_rows — and runs
# the flash update on a [QB*G, pages_per_block*P] score tile. Pages past the
# block's causal bound are never copied: a prefill chunk's early q blocks
# stop their DMA train at their own triangle's edge.

_RAGGED_QB = 8  # default query block (sublane-friendly; decode rows pad to it)


def ragged_layout(row_lens, q_block: int = _RAGGED_QB, total: int | None = None):
    """Host-side layout of a ragged batch: returns (row_starts [R],
    block_rows [NB], block_q0 [NB], t_pad) as numpy int32, with every row's
    flat segment aligned to ``q_block`` (the kernel's one-row-per-q-block
    contract). ``total`` pads the flat token axis to a fixed static size so
    engine traces stay bucketed; blocks not owned by any row carry -1."""
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
    nb = t_pad // q_block
    block_rows = np.full(nb, -1, np.int32)
    block_q0 = np.zeros(nb, np.int32)
    for r, n in enumerate(lens):
        if n <= 0:
            continue
        b0 = int(starts[r]) // q_block
        for j in range(-(-int(n) // q_block)):
            block_rows[b0 + j] = r
            block_q0[b0 + j] = j * q_block
    return starts, block_rows, block_q0, int(t_pad)


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
                               tree_anc=None, layer=None):
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
    HBM gather traffic stays R*cap, not T*cap."""
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
    # scalar prefetch (SMEM): block_rows [NB], block_q0 [NB],
    # page_table [R, PP], kv_lens [R], row_lens [R], layer [1],
    # tree only: tree_anc [T * tree_width] (flat; per token: in-row
    # ancestor indices incl. self, -1 padded; first entry -2 => plain causal)
    *refs,
    page_size: int,
    pages_per_block: int,
    q_block: int,
    quantized: bool = False,
    tree_width: int = 0,
):
    # then positionally: q_ref [QB,1,G,D]; k_hbm/v_hbm [L,Hkv,N,P,D] (ANY);
    # quantized only: k_scale_ref/v_scale_ref [1,1,1,cap_pad] (per-ROW
    # pre-gathered scales, pipelined by the block_rows index map);
    # out_ref [QB,1,G,D]; scratch k_buf/v_buf [2, PB*P, D], sems [2, PB, 2]
    (block_rows_ref, block_q0_ref, page_table_ref, kv_lens_ref,
     row_lens_ref, layer_ref) = refs[:6]
    refs = refs[6:]
    tree = tree_width > 0
    if tree:
        tree_anc_ref, refs = refs[0], refs[1:]
    if quantized:
        (q_ref, k_hbm, v_hbm, k_scale_ref, v_scale_ref,
         out_ref, k_buf, v_buf, sems) = refs
    else:
        q_ref, k_hbm, v_hbm, out_ref, k_buf, v_buf, sems = refs
        k_scale_ref = v_scale_ref = None
    bi = pl.program_id(0)
    h = pl.program_id(1)
    g, d = q_ref.shape[2], q_ref.shape[3]
    p = page_size
    pb = pages_per_block
    qb = q_block
    row_raw = block_rows_ref[bi]
    live = row_raw >= 0
    row = jnp.maximum(row_raw, 0)
    q0 = block_q0_ref[bi]
    kv_len = kv_lens_ref[row]
    row_len = row_lens_ref[row]
    layer = layer_ref[0]
    base = kv_len - row_len          # absolute position of the row's query 0
    # causal bound of this block's LAST query — pages past it never DMA
    bound = jnp.where(live, jnp.minimum(kv_len, base + q0 + qb), 0)
    block_tokens = pb * p
    n_blocks = (bound + block_tokens - 1) // block_tokens

    def _copies(block_idx, slot, j):
        page_idx = block_idx * pb + j
        page = page_table_ref[row, page_idx]
        dst = pl.ds(j * p, p)
        return (
            pltpu.make_async_copy(
                k_hbm.at[layer, h, page], k_buf.at[slot, dst],
                sems.at[slot, j, 0]
            ),
            pltpu.make_async_copy(
                v_hbm.at[layer, h, page], v_buf.at[slot, dst],
                sems.at[slot, j, 1]
            ),
        )

    def start_block(block_idx, slot):
        for j in range(pb):  # static unroll; ragged tail gated per page
            @pl.when((block_idx * pb + j) * p < bound)
            def _start(j=j):
                ck, cv = _copies(block_idx, slot, j)
                ck.start()
                cv.start()

    def wait_block(block_idx, slot):
        for j in range(pb):
            @pl.when((block_idx * pb + j) * p < bound)
            def _wait(j=j):
                ck, cv = _copies(block_idx, slot, j)
                ck.wait()
                cv.wait()

    @pl.when(n_blocks > 0)
    def _run():
        start_block(0, 0)

        def body(i, carry):
            m_prev, l_prev, acc_prev = carry
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                start_block(i + 1, jax.lax.rem(i + 1, 2))

            wait_block(i, slot)
            # queries flatten to [QB*G, D]: query-in-block index = ri // G
            q = q_ref[:, 0].reshape(qb * g, d)                  # [QB*G, D]
            k = k_buf[slot]                                     # [PB*P, D]
            v = v_buf[slot]
            if quantized:
                op_dtype = out_ref.dtype
                k = k.astype(op_dtype)
                v = v.astype(op_dtype)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * (d ** -0.5)                                     # [QB*G, PB*P]
            if quantized:
                k_s = k_scale_ref[0, 0, :, pl.ds(i * block_tokens,
                                                 block_tokens)]  # [1, PB*P]
                scores = scores * k_s
            # per-query causal masking: query q0+qi attends KV positions
            # <= base+q0+qi; 2-D i32 iota compares (Mosaic: no i1 minor dim)
            token_ids = (
                i * block_tokens
                + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            )
            qi = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) // g
            q_live = (q0 + qi) < row_len                        # query exists
            valid = (token_ids < base + q0 + qi + 1) & q_live
            if tree:
                # tree-topology pruning inside the unchanged causal
                # bound (docs/spec_decode_trees.md): the DMA plan above
                # is untouched — parent-before-child node order keeps
                # base+q0+qi+1 a valid upper bound, so trees only MASK
                # within the pages already copied. Ancestor lists live
                # flat in SMEM (scalar prefetch); the per-query unroll is
                # static (q_block x DMAX scalar reads). Every term is an
                # i32 vector compare ORed/ANDed together: Mosaic refuses
                # a select between i1 vectors and a scalar bool broadcast
                # into one (module docstring), so the plain-causal
                # sentinel and the av >= 0 guard are vector compares too.
                tok_off = token_ids - base          # in-row kv offset
                history = tok_off < 0               # history always
                in_row = tok_off >= 0
                allow = None
                for qs in range(qb):
                    a0 = (bi * qb + qs) * tree_width
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
                valid = valid & allow
            scores = jnp.where(valid, scores, -jnp.inf)
            # rows past the bound were never DMA'd: zero before the matmul
            row_ids = i * block_tokens + jax.lax.broadcasted_iota(
                jnp.int32, (block_tokens, 1), 0
            )
            v = jnp.where(row_ids < bound, v, jnp.zeros_like(v))

            block_max = jnp.maximum(jnp.max(scores, axis=1), -1e30)
            m_new = jnp.maximum(m_prev, block_max)              # [QB*G]
            probs = jnp.exp(scores - m_new[:, None])
            probs = jnp.where(valid, probs, 0.0)
            correction = jnp.exp(m_prev - m_new)
            l_new = l_prev * correction + jnp.sum(probs, axis=1)
            pv = probs
            if quantized:
                v_s = v_scale_ref[0, 0, :, pl.ds(i * block_tokens,
                                                 block_tokens)]  # [1, PB*P]
                pv = probs * v_s
            acc_new = acc_prev * correction[:, None] + jax.lax.dot_general(
                pv.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc_new

        m0 = jnp.full((qb * g,), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((qb * g,), jnp.float32)
        acc0 = jnp.zeros((qb * g, d), jnp.float32)
        _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out_ref[:, 0] = (acc / safe_l[:, None]).reshape(qb, g, d).astype(
            out_ref.dtype
        )

    @pl.when(n_blocks == 0)
    def _empty():
        out_ref[:, 0] = jnp.zeros((qb, g, d), out_ref.dtype)


def ragged_paged_attention(
    q, k_pool, v_pool, page_table, kv_lens, row_starts, row_lens, *,
    block_rows=None, block_q0=None,
    k_scale=None, v_scale=None, tree_anc=None, layer=None,
    pages_per_block: int = 32, q_block: int = _RAGGED_QB,
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

    ``block_rows``/``block_q0`` ([T/q_block] int32) are the host-computed
    q-block -> row map (:func:`ragged_layout`); the kernel REQUIRES them
    (they cannot be derived from traced row metadata on device) and the
    flat layout must be q_block-aligned per row.

    ``tree_anc`` ([T, DMAX] int32, optional) turns spec-verify rows into
    draft-TREE rows (docs/spec_decode_trees.md): per flat token, the
    in-row indices of its root-to-node ancestor path (self included, -1
    padded); ``tree_anc[t, 0] == -2`` keeps token t plain-causal. Only
    the mask changes — the page DMA plan is topology-blind."""
    quantized = k_scale is not None
    if block_rows is None or block_q0 is None:
        raise ValueError(
            "ragged_paged_attention needs the host-built block_rows/block_q0 "
            "q-block map (ragged_layout)"
        )
    _check_kernel_operands(
        "ragged_paged_attention", q, k_pool, quantized, interpret
    )

    t, hkv, g, d = q.shape
    page_size = k_pool.shape[-2]
    pages_per_seq = page_table.shape[1]
    if t % q_block:
        raise ValueError(
            "ragged q length {} must be a multiple of q_block {}".format(
                t, q_block
            )
        )
    pb = max(1, min(pages_per_block, pages_per_seq))
    cap = pages_per_seq * page_size

    kernel = functools.partial(
        _ragged_attention_kernel,
        page_size=page_size,
        pages_per_block=pb,
        q_block=q_block,
        quantized=quantized,
        tree_width=0 if tree_anc is None else tree_anc.shape[1],
    )
    nb = t // q_block
    # index maps take *_ for the scalar-prefetch refs: their count is 6
    # or 7 (tree_anc) and the maps never read beyond block_rows
    in_specs = [
        pl.BlockSpec(
            (q_block, 1, g, d), lambda b, h, *_: (b, h, 0, 0)
        ),
        pl.BlockSpec(memory_space=pl.ANY),   # K pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),   # V pool stays in HBM
    ]
    scales = []
    if quantized:
        # per-ROW pre-gathered scales (same rationale/padding as the decode
        # kernel's: f32 scale rows are not tile-alignable for the page DMA
        # plan); the grid pipeline picks each q block's row via block_rows
        block_tokens = pb * page_size
        cap_pad = -(-cap // block_tokens) * block_tokens
        pad = ((0, 0), (0, 0), (0, 0), (0, cap_pad - cap))
        r = page_table.shape[0]

        def gather(scale):
            seq = jnp.moveaxis(
                gather_pages(scale, page_table, layer).reshape(hkv, r, cap),
                0, 1,
            ).reshape(r, hkv, 1, cap)
            return jnp.pad(seq, pad)

        def scale_idx(b, h, br, *_):
            return (jnp.maximum(br[b], 0), h, 0, 0)

        in_specs += [
            pl.BlockSpec((1, 1, 1, cap_pad), scale_idx),
            pl.BlockSpec((1, 1, 1, cap_pad), scale_idx),
        ]
        scales = [gather(k_scale), gather(v_scale)]
    layer, k_pool, v_pool = _stacked(layer, k_pool, v_pool)
    inputs = [q, k_pool, v_pool] + scales
    prefetch = [block_rows, block_q0, page_table, kv_lens, row_lens, layer]
    if tree_anc is not None:
        if tree_anc.shape[0] != t:
            raise ValueError(
                "tree_anc rows {} != flat token count {}".format(
                    tree_anc.shape[0], t))
        # flat: a 2-D SMEM operand pads its minor dim to 128 lanes
        prefetch.append(tree_anc.astype(jnp.int32).reshape(-1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # maps, tables, layer (+ tree)
        grid=(nb, hkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (q_block, 1, g, d), lambda b, h, *_: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, pb * page_size, d), k_pool.dtype),
            pltpu.VMEM((2, pb * page_size, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, pb, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, hkv, g, d), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(*prefetch, *inputs)
