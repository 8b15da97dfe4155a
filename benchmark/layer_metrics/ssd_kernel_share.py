"""kernels: device time of the two SSD kernels (the trace's operations named
mamba2_ssd_update and mamba2_ssd_chunk) over device-busy time, in the traced
tail. Source: device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common, _ssd


def read(ctx):
    t = _common.traced(ctx)
    if t is None or not t["busy_s"]:
        return None
    parts = [_ssd.kernel_seconds(ctx, name)
             for name in (_ssd.UPDATE, _ssd.CHUNK)]
    if all(p is None for p in parts):
        return None
    return 100.0 * sum(p or 0.0 for p in parts) / t["busy_s"]
