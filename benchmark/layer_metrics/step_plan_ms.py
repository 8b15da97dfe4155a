"""scheduler: milliseconds per scheduling cycle in the engine's ``plan`` phase
(``pipeline.phases.plan_ms``, sum over count gained in the window):
``_prepare_ragged`` / ``_prepare_dispatch``, the numpy planning of one
launch on the loop thread. The six phases add up to ``cycle_ms``. Source:
program_span. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.step_phase_ms(ctx, "plan")
