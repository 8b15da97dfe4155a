"""The routed feed-forward of a layer that HOLDS many small experts, as one
Pallas kernel that visits only the experts a token of the launch chose, with
``models/llama.moe_dropless`` as its XLA twin (docs/moe_experts.md).

For T tokens x [T, D], stacks W^G, W^U [E, D, F] and W^D [E, F, D] (float,
or int8 with one float32 scale an output channel: ``ops/quant.quantize_int8``
at ``axis=-2``) and a token's gates g[t, e] (0 where t did not choose e, or
e is not held here, or t is padding):

    y[t] = sum over e with g[t, e] != 0 of
           g[t, e] * (SiLU(x[t] W^G_e) * (x[t] W^U_e)) W^D_e

The twin sends every token through ALL E experts and reads all three stacks
whole. At 128 tokens a launch the read of the weights binds (an expert's
three matrices are 6 MB at Trinity-Mini's widths and a launch's 128 x 8
choices leave 44% of 128 experts without a token), so the kernel SKIPS: the
list of hit experts, ascending and compacted to the front (``visit_order``),
is prefetched into scalar memory, the grid is (slot, tile of the expert
width F), a weight block's index map names expert ``order[slot]``, and a slot
past the count repeats the last block fetched (no copy) and skips its
products. A visited expert multiplies all T rows; sorting the tokens by
expert would save operations the weight read hides anyway and add a gather
and a scatter to every pass.

A row's result does not depend on what else rides in the launch: the experts
come in ascending order whatever the hit set, a row's term for an expert it
did not choose is an exact zero (selected, not ``0 * value``: that expert's
hidden may overflow) added to a float32 accumulator, and the tile walk of F
is the same for every expert. int8 blocks are widened in VMEM and the scale
is applied to the PRODUCT (exact in the int8 values), so the stacks stay int8
in HBM and no dequantised copy of one is ever written.

The stacks may arrive with a leading layer axis and a ``layer`` index (a
layer scan's stacked operand: a slice of it handed to a custom call would be
copied out first, ops/paged_attention.py's lesson).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT = 64 << 20
_BLOCK_BYTES = 3 << 20     # a weight block [D, tile] as it lies in HBM


def _parts(w):
    """(values, scale or None) of a stack leaf: an array, or int8 values
    with their scales (ops/quant.py's ``_q8`` leaf)."""
    if isinstance(w, dict):
        return w["_q8"], w["_scale"]
    return w, None


def width_tile(dim: int, width: int, itemsize: int = 1) -> Optional[int]:
    """The tile of the expert width F a grid step takes: the largest
    multiple of 128 that divides F whose [dim, tile] block stays under
    ``_BLOCK_BYTES`` (three blocks, double buffered, and their widened
    copies share VMEM). None where F has no such tile."""
    best = None
    for tile in range(128, width + 1, 128):
        if width % tile == 0 and dim * tile * itemsize <= _BLOCK_BYTES:
            best = tile
    return best


def moe_kernel_unsupported_reason(
    tokens: int, act_dtype, w_gate, *, platform: Optional[str] = None,
) -> Optional[str]:
    """Why the routed feed-forward of ``tokens`` rows of ``act_dtype`` over
    the gate stack ``w_gate`` [.., E, dim, width] (an array or a shape, or
    ops/quant.py's packed leaf of them) cannot take the Mosaic kernel, or
    None. Pure in its arguments
    (``paged_attention.paged_kernel_unsupported_reason``): the models call
    it at trace time and the engine at construction for
    ``health()["kernels"]``."""
    platform = platform or jax.default_backend()
    if platform != "tpu":
        return "platform {}: the Mosaic kernels compile for TPU only".format(
            platform)
    if isinstance(w_gate, dict) and "_q8" not in w_gate:
        return ("expert stacks packed as {}: the kernel widens int8 only"
                .format(sorted(w_gate)))
    values, scale = _parts(w_gate)
    act_dtype, w_dtype = jnp.dtype(act_dtype), jnp.dtype(values.dtype)
    dim, width = values.shape[-2:]
    if scale is None and w_dtype != act_dtype:
        return "expert stacks {} beside {} activations".format(
            w_dtype, act_dtype)
    rows = 32 // act_dtype.itemsize
    if tokens % rows:
        return ("{} tokens a launch: the kernel multiplies whole {}-row "
                "tiles of {}".format(tokens, rows, act_dtype))
    if dim % 128 or width_tile(dim, width, w_dtype.itemsize) is None:
        return ("dim {} / expert width {}: the kernel takes [dim, tile] "
                "blocks of whole 128-lane tiles under {} bytes".format(
                    dim, width, _BLOCK_BYTES))
    return None


def kernel_route(bundle, params, token_axes):
    """(route, reason) for ``health()["kernels"]["moe"]``: "pallas" where the
    routed layers of ``bundle`` (its ``expert_stack(params)``: the first gate
    stack) take the kernel at every one of the launches' ``token_axes``,
    else "xla" with the first reason; (None, None) without routed layers."""
    w_gate = bundle.expert_stack(params)
    if w_gate is None:
        return None, None
    dtype = bundle.config.get("dtype", "bfloat16")
    why = next(filter(None, (
        moe_kernel_unsupported_reason(tokens, dtype, w_gate)
        for tokens in token_axes)), None)
    return ("pallas" if why is None else "xla"), why


# ------------------------------------------------------- what a launch visits

def expert_gates(top_p, local, took, n_held: int):
    """(gates [T, n_held] float32, hit [n_held] int32). ``local`` [T, k] a
    choice's index into the held stacks, ``took`` [T, k] whether it counts
    (the expert is held here and the token is not padding). A token's
    choices are distinct, so a gate is one ``top_p`` or 0, exactly; ``hit``
    marks the experts some counted choice named."""
    chose = jnp.logical_and(
        took[..., None],
        local[..., None] == jnp.arange(n_held, dtype=local.dtype))
    gates = jnp.sum(
        jnp.where(chose, top_p.astype(jnp.float32)[..., None], 0.0), axis=1)
    return gates, jnp.any(chose, axis=(0, 1)).astype(jnp.int32)


def visit_order(hit):
    """hit [E] -> (order [E] int32, count): the hit experts ascending in the
    first ``count`` places, the last of them repeated behind (a grid step
    there names the block already resident); expert 0 throughout where none
    is hit."""
    n = hit.shape[0]
    seen = jnp.cumsum(hit.astype(jnp.int32))
    count = seen[-1]
    place = jnp.minimum(jnp.arange(n, dtype=jnp.int32),
                        jnp.maximum(count - 1, 0))
    # the s-th hit expert is preceded by exactly the experts with seen <= s
    order = jnp.sum((seen[None, :] <= place[:, None]).astype(jnp.int32),
                    axis=1)
    return jnp.where(count > 0, order, 0).astype(jnp.int32), count


# --------------------------------------------------------------------- kernel

def _experts_kernel(
    # scalar prefetch (SMEM)
    layer_ref, order_ref, count_ref,
    # x [T, D]; gates [T, E]; blocks of expert order[slot]: W^G, W^U
    # [1, 1, D, tile], W^D [1, 1, tile, D] and, with int8 stacks, their
    # scales [1, 1, 1, tile] / [1, 1, 1, D]
    x_ref, gates_ref, wg_ref, wu_ref, wd_ref, *rest,
):
    del layer_ref
    scales, out_ref = rest[:-1], rest[-1]
    slot, j = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(slot == 0, j == 0))
    def _start():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(slot < count_ref[0])
    def _visit():
        x = x_ref[...]

        def product(a, w_ref, scale_ref):
            y = jnp.dot(a, w_ref[0, 0].astype(a.dtype),
                        preferred_element_type=jnp.float32)
            return y if scale_ref is None else y * scale_ref[0, 0]

        sg, su, sd = scales if scales else (None,) * 3
        h = jax.nn.silu(product(x, wg_ref, sg)) * product(x, wu_ref, su)
        # this expert's column of the gates: one lane of [T, E]
        lane = jax.lax.broadcasted_iota(jnp.int32, gates_ref.shape, 1)
        gate = jnp.sum(
            jnp.where(lane == order_ref[slot], gates_ref[...], 0.0),
            axis=1, keepdims=True)                              # [T, 1]
        h = jnp.where(gate != 0.0, h * gate, 0.0).astype(x.dtype)
        out_ref[...] += product(h, wd_ref, sd)


def moe_experts(x, gates, order, count, w_gate, w_up, w_down, *,
                layer=None, tile: Optional[int] = None,
                interpret: bool = False):
    """The routed sum of the module docstring, float32 [T, D]. ``gates``
    [T, E] float32 and (``order``, ``count``) = ``visit_order(hit)`` of the
    experts with a non-zero gate; the stacks [E, D, F] / [E, F, D] (arrays,
    or int8 ``{"_q8", "_scale"}`` leaves), with a leading layer axis where
    ``layer`` is given. Never the twin: it runs the kernel or raises."""
    (qg, sg), (qu, su), (qd, sd) = _parts(w_gate), _parts(w_up), _parts(w_down)
    operands = [qg, qu, qd] + ([sg, su, sd] if sg is not None else [])
    if layer is None:
        operands, layer = [a[None] for a in operands], 0
    n_exp, dim, width = operands[0].shape[1:]
    t = x.shape[0]
    if not interpret:
        reason = moe_kernel_unsupported_reason(t, x.dtype, w_gate)
        if reason is not None:
            raise ValueError("moe_experts: " + reason)
    tile = tile or width_tile(dim, width, qg.dtype.itemsize) or width
    n_tiles = width // tile

    def expert_block(shape, place):
        """A block of expert ``order[slot]``; an idle slot names the last
        block a visit fetched: the last expert's last tile."""
        def index(s, j, l, order, count):
            j = jnp.where(s < count[0], j, n_tiles - 1)
            return (l[0], order[s]) + place(j)

        return pl.BlockSpec((1, 1) + shape, index)

    def whole(shape):
        return pl.BlockSpec(shape, lambda s, j, *_: (0,) * len(shape))

    up = expert_block((dim, tile), lambda j: (0, j))
    down = expert_block((tile, dim), lambda j: (j, 0))
    in_specs = [whole((t, dim)), whole((t, n_exp)), up, up, down]
    if sg is not None:
        up_scale = expert_block((1, tile), lambda j: (0, j))
        in_specs += [up_scale, up_scale,
                     expert_block((1, dim), lambda j: (0, 0))]
    return pl.pallas_call(
        _experts_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,            # layer, order, count
            grid=(n_exp, n_tiles),
            in_specs=in_specs,
            out_specs=whole((t, dim)),
        ),
        out_shape=jax.ShapeDtypeStruct((t, dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_experts",
    )(jnp.asarray(layer, jnp.int32).reshape(1), order.astype(jnp.int32),
      count.reshape(1).astype(jnp.int32), x, gates.astype(jnp.float32),
      *operands)
