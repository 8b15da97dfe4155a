"""model step: device-busy milliseconds per launch in the traced tail (union
of the device's operation intervals over the pipeline's dispatches there).
Source: device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    t, edges = _common.traced(ctx), _common.trace_edges(ctx)
    if t is None or edges is None:
        return None
    steps = _common.launches(ctx, edges)
    return 1000.0 * t["busy_s"] / steps if steps else None
