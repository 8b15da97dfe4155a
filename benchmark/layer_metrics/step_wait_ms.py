"""scheduler: milliseconds per scheduling cycle in the engine's ``wait`` phase
(``pipeline.phases.wait_ms``, sum over count gained in the window): retire
entered until every host copy of the launch's results is in hand, the
blocking device wait; read it beside ``launch_device_ms``. The six phases
add up to ``cycle_ms``. Source: program_span. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.step_phase_ms(ctx, "wait")
