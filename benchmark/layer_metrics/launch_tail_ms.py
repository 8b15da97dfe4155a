"""scheduler: the dispatch worker's milliseconds per launch after the jitted
call returned (``pipeline.launch_parts.tail_ms``): the state cache's
``advance``, the finishing rows' logit gather (a second small launch). The
device already runs. Source: program_span. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _timeline


def read(ctx):
    return _timeline.per_launch_ms(ctx, _timeline.part("tail"))
