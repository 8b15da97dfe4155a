"""scheduler: milliseconds per scheduling cycle in the engine's ``launch``
phase (``pipeline.phases.launch_ms``, sum over count gained in the window):
the loop thread waiting for the dispatch worker: both thread hops, page
allocation, uploads and the jitted call returning (pipelined step: what is
left of that after the retire). The six phases add up to ``cycle_ms``.
Source: program_span. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.step_phase_ms(ctx, "launch")
