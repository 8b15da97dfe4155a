"""scheduler: per launch of the window, the stretch in which the program KNOWS
the chip had nothing queued: from the instant the previous launch's first
device-to-host copy returned (the device had finished) until this launch's
jitted call (``pipeline.starve_ms``, its sum over the launches gained). A
launch that follows a park observes none: an engine without work is not
starved. A lower bound of the idle chip per launch (it leaves out the copy's
own latency, the enqueue and the launch latency), so it cannot run over the
way ``step_gap_ms`` does; with a launch in flight it reads 0. Source:
program_span. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _timeline


def read(ctx):
    return _timeline.per_launch_ms(ctx, _timeline.STARVE)
