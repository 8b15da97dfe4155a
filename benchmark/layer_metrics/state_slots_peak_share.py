"""cache: the most state slots owned at once since the engine started
(``state_pool.in_use_peak``) over the slots there are, in percent. Admission
to a state cache is bounded by free slots, so 100 means every row was taken
at some time. Source: program_counter. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    peak = _common.dig(ctx["after"], "state_pool", "in_use_peak")
    slots = _common.dig(ctx["after"], "state_pool", "slots")
    return 100.0 * peak / slots if peak is not None and slots else None
