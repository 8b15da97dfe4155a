"""model step: held experts that received a token, over the held experts of
the expert layers a pass ran, over the window (``moe.experts_hit`` over
``moe.layer_passes`` x ``experts_held``): the share of the routed weights a
pass has to read. None where the program has no such counter. Source:
program_counter. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    hit = _common.delta(ctx, "moe", "experts_hit")
    layers = _common.delta(ctx, "moe", "layer_passes")
    held = _common.dig(ctx["after"], "moe", "experts_held")
    if hit is None or not layers or not held:
        return None
    return 100.0 * hit / (layers * held)
