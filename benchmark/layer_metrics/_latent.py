"""What the latent page layout's readers share: the traced tail's gains of
the program's ``latent`` / ``moe`` blocks of ``lifecycle_stats()``
(docs/latent_cache.md). None where the program has no such block (a program
without the layout, as every one before it existed)."""

from benchmark.layer_metrics import _common

LATENT = ("rows_full", "rows_window", "index_keys_scored", "index_keys_kept",
          "window_keys", "decode_keys_full", "decode_keys_window",
          "mixed_keys_full", "mixed_keys_window")
MOE = ("experts_hit", "local_assignments", "layer_passes")


def gains(ctx, edges):
    """{counter: gain between ``edges``} of both blocks, with ``passes``,
    ``decode_tokens`` of the ``ragged`` block and the layer counts."""
    if edges is None or _common.dig(edges[1], "latent") is None:
        return None
    out = {k: _common.delta(ctx, "latent", k, edges=edges) for k in LATENT}
    out.update({k: _common.delta(ctx, "moe", k, edges=edges) for k in MOE})
    out["passes"] = _common.delta(ctx, "ragged", "passes", edges=edges)
    out["decode_tokens"] = _common.delta(ctx, "ragged", "decode_tokens",
                                         edges=edges)
    out["experts_held"] = _common.dig(edges[1], "moe", "experts_held")
    return None if any(v is None for v in out.values()) else out
