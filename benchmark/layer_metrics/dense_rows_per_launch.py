"""model step: rows the dense layers of a ragged pass multiplied
(``ragged.dense_rows``: the compact token axis, whole, a launch) over the
ragged steps (``ragged.steps``) that the window gained. The axis is static,
so this reads ``ragged.dense_axis`` wherever a ragged step ran: 128 in the K/V
cells, where a program whose dense layers ran on the kernel's aligned layout
would read that layout's 352 rows. On the state cache the pass keeps the one
aligned axis, so 368 in ``brumby14b.long_decode`` says "not compacted" and
measures nothing. Lower is better. None where the program counts no such rows
(the parent of PR 42) or launched no ragged step. Source: program_counter.
Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    rows = _common.delta(ctx, "ragged", "dense_rows")
    steps = _common.delta(ctx, "ragged", "steps")
    if rows is None or not steps:
        return None
    return rows / steps
