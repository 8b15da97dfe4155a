"""scheduler: milliseconds per scheduling cycle in the engine's ``admin`` phase
(``pipeline.phases.admin_ms``, sum over count gained in the window): loop
top until the step is entered: deadline sweep, reaping, brownout,
preemption, admission pops, commits, job opening. The six phases add up to
``cycle_ms``. Source: program_span. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.step_phase_ms(ctx, "admin")
