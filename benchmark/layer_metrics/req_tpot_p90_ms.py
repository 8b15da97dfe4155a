"""scheduler: 90th percentile of per-request TPOT, in a cell whose population
is under 100 requests, where the tail is not judged. Source: host_clock."""

from benchmark.layer_metrics import _common


def read(ctx):
    from benchmark.reduce import tpot_ms

    return _common.raw_percentile([tpot_ms(r) for r in _common.ok_judged(ctx)], 0.9)
