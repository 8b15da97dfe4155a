"""What the windowed paged path's readers share: the gains of the program's
``window`` / ``moe`` blocks of ``lifecycle_stats()``
(docs/window_attention.md). None where the program has no such block (a
program whose paged path knows no window, as every one before it)."""

from benchmark.layer_metrics import _common

WINDOW = ("rows_full", "rows_window", "decode_keys_full", "decode_keys_window",
          "mixed_keys_full", "mixed_keys_window", "mixed_pairs_full",
          "mixed_pairs_window", "window_keys_unbounded")
MOE = ("experts_hit", "local_assignments", "layer_passes")


def gains(ctx, edges):
    """{counter: gain between ``edges``} of both blocks, with
    ``decode_tokens`` of the ``ragged`` block."""
    if edges is None or _common.dig(edges[1], "window") is None:
        return None
    out = {k: _common.delta(ctx, "window", k, edges=edges) for k in WINDOW}
    out.update({k: _common.delta(ctx, "moe", k, edges=edges) for k in MOE})
    out["decode_tokens"] = _common.delta(ctx, "ragged", "decode_tokens",
                                         edges=edges)
    return None if any(v is None for v in out.values()) else out
