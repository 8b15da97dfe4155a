"""front: 90th percentile of TTFT over the judged requests; a fifth judged
tail once the ledger shows its spread. Source: host_clock."""

from benchmark.layer_metrics import _common


def read(ctx):
    from benchmark.reduce import ttft_ms

    return _common.raw_percentile([ttft_ms(r) for r in _common.ok_judged(ctx)], 0.9)
