"""w4a16 fused dequant-matmul: Pallas TPU kernel + XLA reference.

Decode is weight-read bound (PERF.md section 4): every step streams the whole
projection stack out of HBM for a handful of activation rows. int4 group
quantization (ops/quant.py: packed ``_q4`` uint8 [K//2, N] + per-(group,
out-channel) ``_scale4`` f32 [K//group, N]) stores those bytes at a quarter
of bf16 — but the saving is only real if the *HBM read* is 4-bit. The
existing XLA path (``dequantize_int4`` inlined in the consumer matmul) keeps
weights at rest int4, yet XLA materializes a bf16 operand tile between the
unpack and the dot; whether the read stays 4-bit is fusion-dependent. This
kernel makes it structural, the same way the int8 KV path did for page reads
(ops/paged_attention.py, docs/paged_kv_quant.md):

- **Packed tiles stream HBM -> VMEM raw.** The grid runs (N blocks, K
  steps); the uint8 ``_q4`` operand is blocked ``[groups_per_step * GP,
  BN]`` and the grid pipeline double-buffers it — step k+1's packed tile
  flies while step k's dots run on the MXU. The bf16 weight never exists in
  HBM, so the weight-bytes term is exactly K/2 * N.
- **Group scales stay VMEM-resident.** The ``_scale4`` block for one N block
  ([G, BN] f32, ~1/64 of the packed bytes at group 128) is fetched once per
  N block (its index map ignores the K axis) and read per group from there.
- **Unpack + scale fuse into the MXU contraction.** Byte row j of a packed
  group tile holds unpacked rows 2j (low nibble) and 2j+1 (high), and
  Mosaic cannot cheaply re-interleave sublanes. So the activations are
  de-interleaved instead, XLA-side, per quantization group: columns
  ``[evens of group g | odds of group g]``. The kernel stacks the two
  nibble planes along sublanes (``[lo; hi]``, a tile-aligned concat) and one
  dot per group contracts ``x_g [M, group] @ [lo; hi] [group, BN]`` — every
  activation slice is a 128-lane-aligned window at the default group 128.
  Within a group the scale depends only on the output channel, so it folds
  into the f32 accumulation *after* the dot: one multiply per output
  element per group, never a dequantized [rows, N] tile write.

What the v5e compiler (libtpu 0.0.34) refused in the first design, which
kept two activation planes ``x_even``/``x_odd`` whole in VMEM and sliced
``[M, group/2]`` windows out of them: "cannot statically prove that index in
dimension 1 is a multiple of 128" — a 64-lane window of a lane dim is not
loadable. The per-group de-interleave above is the repair.

Alignment gates (hardware; ``interpret=True`` runs any shape for parity
tests). :func:`int4_kernel_unsupported_reason` is the ONE routing decision:
callers (models/llama ``_mm``, the engine's health block) evaluate it and
take :func:`int4_matmul_xla` when it names a reason;
:func:`fused_int4_matmul` itself only ever runs the kernel.

- N % 128 == 0 (block width in {512, 256, 128} dividing N — lane tiling);
- group % 128 == 0: the activation window is ``group`` lanes and the packed
  group tile ``group/2`` rows (uint8 sublane tile is 32);
- groups must divide K evenly with an even group size (nibble pairs must not
  straddle a group boundary);
- flattened activation rows M <= 256 (decode / speculative-verify shapes;
  prefill's M = B*S takes the XLA path, where the matmul is compute-bound
  and operand materialization is amortized anyway).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant import dequantize_int4

# flattened activation rows the kernel accepts: the [M, BN] f32 accumulator
# and the [M, K-step] activation block sit in VMEM next to the packed weight
# tiles (256 x 512 x 4 B = 512 KB + 256 x 1024 x 2 B x 2 buffers = 1 MB).
MAX_FUSED_ROWS = 256

_BLOCK_N_CANDIDATES = (512, 256, 128)
# quantization groups contracted per grid step (largest that divides the
# group count): 8 groups of 128 = a [512, BN] uint8 packed tile per step
_GROUPS_PER_STEP_CANDIDATES = (8, 4, 2, 1)


def int4_matmul_xla(x, packed, scale, dtype=None):
    """Reference: the exact pre-kernel path (``models/llama._w`` inline
    dequant) — unpack+scale in XLA, fused into the consumer matmul by the
    compiler. Shapes :func:`int4_kernel_unsupported_reason` rejects take
    this path, byte-identical to the historical streams."""
    return x @ dequantize_int4(packed, scale, dtype or x.dtype)


def int4_kernel_unsupported_reason(
    x, packed, scale, *, interpret: bool = False,
    platform: Optional[str] = None,
) -> Optional[str]:
    """Why (x, packed, scale) cannot take the Pallas kernel — None if it can.

    The one routing decision for int4 matmuls: pure in its arguments
    (shapes/dtypes of the operands, ``platform`` = the backend the program
    is compiled for, default ``jax.default_backend()``), so the model's
    ``_mm`` and the engine's health block agree by construction.
    ``interpret=True`` lifts the hardware gates (platform, tiling) — the
    Pallas interpreter runs any shape on any backend."""
    if packed.ndim != 2 or scale.ndim != 2:
        return "kernel takes 2-D packed/scale (got {}D/{}D); stacked trees " \
               "route per layer inside the scan".format(packed.ndim, scale.ndim)
    if packed.dtype != jnp.uint8:
        return "packed weights must be uint8 nibbles"
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return "activations must be floating point"
    if x.shape[-1] != packed.shape[0] * 2:
        return "K mismatch: x has {} columns, packed holds {} rows".format(
            x.shape[-1], packed.shape[0] * 2
        )
    k2, n = packed.shape
    ng = scale.shape[0]
    if scale.shape[1] != n:
        return "scale output dim {} != weight output dim {}".format(
            scale.shape[1], n
        )
    k = 2 * k2
    if ng < 1 or k % ng:
        return "{} scale groups do not divide K={}".format(ng, k)
    group_k = k // ng
    if group_k % 2:
        return "odd group size {} (nibble pairs straddle groups)".format(group_k)
    m = 1
    for d in x.shape[:-1]:
        m *= int(d)
    if m == 0:
        return "empty activation batch"
    if m > MAX_FUSED_ROWS:
        return "M={} activation rows exceed the fused kernel's cap {} " \
               "(prefill-shaped; XLA path)".format(m, MAX_FUSED_ROWS)
    if interpret:
        return None
    platform = platform or jax.default_backend()
    if platform != "tpu":
        return "platform {}: the Mosaic kernel compiles for TPU only".format(
            platform
        )
    # hardware tiling gates (mirrors paged_attention's D%128/sublane gates)
    if group_k % 128:
        return "group size {} is not lane-aligned (the activation window " \
               "is one group wide; need group % 128 == 0)".format(group_k)
    if n % 128:
        return "N={} is not lane-tileable (need N % 128 == 0)".format(n)
    return None


def _pick_block_n(n: int) -> int:
    for bn in _BLOCK_N_CANDIDATES:
        if n % bn == 0:
            return bn
    # only interpret mode reaches here (the hardware gate requires
    # N % 128 == 0): a single full-width block
    return n


def _w4a16_kernel(
    # positionally (in_specs order):
    #   x_ref      [M, GPS*group] VMEM  this K step's activation columns,
    #                                   de-interleaved per group: [evens|odds]
    #   scale_ref  [G, BN] f32 VMEM     resident group scales for this N block
    #   w_ref      [GPS*GP, BN] uint8   this K step's packed group tiles
    #   out_ref    [M, BN] VMEM
    # scratch:
    #   acc_ref    [M, BN] f32 VMEM     accumulator across the K steps
    x_ref,
    scale_ref,
    w_ref,
    out_ref,
    acc_ref,
    *,
    gp: int,
    gps: int,
):
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    op_dtype = x_ref.dtype
    acc = acc_ref[...]
    for j in range(gps):  # static unroll over this step's groups
        # Unpack next to the MXU: nibble -> signed level in [-8, 7], cast to
        # the compute dtype (exact: 4-bit ints are representable in bf16).
        # No scale multiply here — within a group the scale is per output
        # channel only, so it rides the f32 accumulation below instead of
        # touching every weight element.
        w = w_ref[j * gp:(j + 1) * gp, :].astype(jnp.int32)      # [GP, BN]
        lo = ((w & 0xF) - 8).astype(op_dtype)                    # rows 2j
        hi = ((w >> 4) - 8).astype(op_dtype)                     # rows 2j+1
        x_g = x_ref[:, j * 2 * gp:(j + 1) * 2 * gp]              # [M, group]
        part = jax.lax.dot_general(
            x_g, jnp.concatenate([lo, hi], axis=0),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                        # [M, BN] f32
        srow = scale_ref[pl.ds(kk * gps + j, 1), :]              # [1, BN] f32
        acc = acc + part * srow
    acc_ref[...] = acc

    @pl.when(kk == pl.num_programs(1) - 1)
    def _store():
        out_ref[...] = acc.astype(out_ref.dtype)


def fused_int4_matmul(x, packed, scale, *, interpret: bool = False):
    """``x [..., K] @ dequant(packed [K//2, N], scale [G, N]) -> [..., N]``
    through the Pallas kernel — compiled by Mosaic, or interpreted under
    ``interpret=True``. Raises ``ValueError`` naming
    :func:`int4_kernel_unsupported_reason`'s reason on operands the kernel
    cannot take: routing to :func:`int4_matmul_xla` is the caller's
    decision (models/llama ``_mm``), never a silent one in here. The output
    dtype is ``x.dtype``."""
    reason = int4_kernel_unsupported_reason(
        x, packed, scale, interpret=interpret, platform="tpu"
    )
    if reason is not None:
        raise ValueError("fused_int4_matmul: " + reason)

    k2, n = packed.shape
    k = 2 * k2
    ng = scale.shape[0]
    group_k = k // ng
    gp = group_k // 2
    bn = _pick_block_n(n)
    gps = next(c for c in _GROUPS_PER_STEP_CANDIDATES if ng % c == 0)

    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    # de-interleave the activation columns per quantization group —
    # [evens of group g | odds of group g] — so one dot per group contracts
    # against the sublane-stacked [lo; hi] nibble planes (module docstring)
    xd = x2.reshape(m, ng, gp, 2).swapaxes(2, 3).reshape(m, k)
    # pad rows up to the activation dtype's sublane tile (16 for bf16, 8 for
    # f32) so every block shape is a whole number of tiles
    tile_m = 8 * max(1, 4 // x2.dtype.itemsize)
    m_pad = -(-m // tile_m) * tile_m
    if m_pad != m:
        xd = jnp.pad(xd, ((0, m_pad - m), (0, 0)))

    kernel = functools.partial(_w4a16_kernel, gp=gp, gps=gps)
    out = pl.pallas_call(
        kernel,
        grid=(n // bn, ng // gps),
        in_specs=[
            pl.BlockSpec((m_pad, gps * group_k), lambda i, kk: (0, kk)),
            pl.BlockSpec((ng, bn), lambda i, kk: (0, i)),
            pl.BlockSpec((gps * gp, bn), lambda i, kk: (kk, i)),
        ],
        out_specs=pl.BlockSpec((m_pad, bn), lambda i, kk: (0, i)),
        scratch_shapes=[pltpu.VMEM((m_pad, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xd, scale.astype(jnp.float32), packed)
    return out[:m].reshape(x.shape[:-1] + (n,))
