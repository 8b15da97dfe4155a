"""scheduler: 90th percentile of per-request TPOT, beside the layers because no
cell can judge it: a closed cell's population is under 100 requests, and an
open cell's 121-135 leave 13 beyond it, whose runs spread by 0.4% to 9% from
one machine to the next (PERF.md section 2). Source: host_clock."""

from benchmark.layer_metrics import _common


def read(ctx):
    from benchmark.reduce import tpot_ms

    return _common.raw_percentile([tpot_ms(r) for r in _common.ok_judged(ctx)], 0.9)
