"""front: median TTFT of the judged requests, in a cell that does not judge
ttft_p50_ms end to end. Source: host_clock."""

from benchmark.layer_metrics import _common


def read(ctx):
    from benchmark.reduce import ttft_ms

    return _common.raw_percentile([ttft_ms(r) for r in _common.ok_judged(ctx)], 0.5)
