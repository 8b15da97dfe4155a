"""scheduler: milliseconds per launch inside the jitted step's call
(``pipeline.launch_parts.enqueue_ms``): argument handling and the enqueue;
the device starts somewhere inside it. Source: program_span. Moves
tpot_p50_ms."""

from benchmark.layer_metrics import _timeline


def read(ctx):
    return _timeline.per_launch_ms(ctx, _timeline.part("enqueue"))
