"""kernels: device time of the Pallas attention kernels (the trace's custom
calls) over device-busy time, in the traced tail. Source: device_trace. Moves
tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    t = _common.traced(ctx)
    return 100.0 * t["kernel_s"] / t["busy_s"] if t and t["busy_s"] else None
