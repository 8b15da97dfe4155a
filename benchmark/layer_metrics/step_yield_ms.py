"""scheduler: milliseconds per scheduling cycle in the engine's ``yield`` phase
(``pipeline.phases.yield_ms``, sum over count gained in the window): retire
returned until the next loop top: the sanitizer and the ``sleep(0)`` that
hands the event loop to the HTTP handlers. The six phases add up to
``cycle_ms``. Source: program_span. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.step_phase_ms(ctx, "yield")
