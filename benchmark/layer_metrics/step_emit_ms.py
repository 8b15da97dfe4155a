"""scheduler: milliseconds per scheduling cycle in the engine's ``emit`` phase
(``pipeline.phases.emit_ms``, sum over count gained in the window): copies
in hand until the retire returns: token emission, slot frees, first tokens
of finishing prefill jobs. The six phases add up to ``cycle_ms``. Source:
program_span. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.step_phase_ms(ctx, "emit")
