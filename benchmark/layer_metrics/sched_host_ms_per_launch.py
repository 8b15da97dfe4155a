"""scheduler: host milliseconds per launch in the pipeline's dispatch and
retire stages (``pipeline.dispatch_ms`` + ``pipeline.retire_ms`` sums over
the dispatch count). The stages overlap device work under pipeline depth 2, so
this can pass the launch's device time without the chip idling. Source:
program_span. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    d, _ = _common.hist_delta(ctx, "pipeline", "dispatch_ms")
    r, _ = _common.hist_delta(ctx, "pipeline", "retire_ms")
    steps = _common.launches(ctx)
    if d is None or r is None or not steps:
        return None
    return (d + r) / steps
