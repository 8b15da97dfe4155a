"""scheduler: milliseconds per launch of the ``wait`` phase that are host time
with an idle chip (``pipeline.readback_ms``): from the instant the retire's
first device-to-host copy returned until every copy is in hand.
``step_wait_ms`` less this is the device wait proper. Source: program_span.
Moves tpot_p50_ms."""

from benchmark.layer_metrics import _timeline


def read(ctx):
    return _timeline.per_launch_ms(ctx, _timeline.READBACK)
