"""What the state cache's readers share: the traced tail's counts from the
program's ``ragged`` block, and the two retention kernels' traced seconds by
name. Each returns None where the program has no such counter or the trace no
such kernel (a program without a state cache)."""

from benchmark.layer_metrics import _common

UPDATE = "power_retention_update"
CHUNK = "power_retention_chunk"


def kernel_seconds(ctx, name):
    """Seconds of the traced tail's operations named ``name`` (a Pallas
    kernel's name is its operation's name in the trace), or None."""
    t = _common.traced(ctx)
    if t is None:
        return None
    hit = [secs for op, secs, _ in t.get("ops") or [] if name in op]
    return sum(hit) if hit else None


def tail_counts(ctx):
    """Over the traced tail: decode tokens (each is one row through the
    update kernel in every layer), prompt tokens that rode as chunk rows, the
    chunk rows themselves and the model passes."""
    edges = _common.trace_edges(ctx)
    if edges is None or _common.dig(edges[1], "state_pool") is None:
        return None
    out = {
        "decode_tokens": _common.delta(ctx, "ragged", "decode_tokens", edges=edges),
        "prefill_tokens": _common.delta(ctx, "ragged", "prefill_tokens", edges=edges),
        "chunk_rows": _common.delta(ctx, "ragged", "step_rows", "prefill", edges=edges),
        "passes": _common.delta(ctx, "ragged", "passes", edges=edges),
    }
    return None if any(v is None for v in out.values()) else out
