"""device: 1 - union of the device's operation intervals over the traced
tail. Source: device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    t = _common.traced(ctx)
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t and t["window_s"] else None
