"""scheduler: ``cycle_ms`` less ``wait_ms`` per scheduling cycle, over the
window: the loop thread's time per launch outside the device wait. Under the
serial ragged step it bounds the idle chip per launch from above: the device
already runs during the last part of ``launch`` (see
``idle_explained_share``). Under the pipelined step a chunk is in flight and
the same time overlaps the device. Source: program_span. Moves
tpot_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    gap, cycles = _phases.gap_ms(ctx)
    return gap / cycles if cycles else None
