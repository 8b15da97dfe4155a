"""scheduler: decode tokens emitted per ragged launch that carried decode rows
(``ragged.tokens_per_launch`` histogram, sum over count, across the window):
the rows in the launch times the decode window it could afford.
Source: program_counter. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    s, n = _common.hist_delta(ctx, "ragged", "tokens_per_launch")
    return s / n if n else None
