"""cache: the high-water mark of the page pool's raw occupancy since the
engine started (``kv_pool.used_pages_peak`` over ``num_pages``): pages held by
live requests and by the prefix cache, which ``kv_pool_peak_share`` leaves
out. Source: program_counter. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    peak = _common.dig(ctx["after"], "kv_pool", "used_pages_peak")
    pages = _common.dig(ctx["after"], "kv_pool", "num_pages")
    return 100.0 * peak / pages if peak is not None and pages else None
