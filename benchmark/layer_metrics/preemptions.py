"""scheduler: requests preempted inside the window. Source: program_counter."""

from benchmark.layer_metrics import _common


def read(ctx):
    return _common.delta(ctx, "preemptions")
