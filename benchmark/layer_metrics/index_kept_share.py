"""cache: keys the learned selection kept over keys it scored, per row of a
full layer, over the window (``latent.index_keys_kept`` /
``index_keys_scored``): how sparse the full layers' attention ran; 100 while
every context is under the top-k. None where the program has no such
counter. Source: program_counter. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    kept = _common.delta(ctx, "latent", "index_keys_kept")
    scored = _common.delta(ctx, "latent", "index_keys_scored")
    if kept is None or not scored:
        return None
    return 100.0 * kept / scored
