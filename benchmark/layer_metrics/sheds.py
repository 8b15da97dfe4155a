"""scheduler: requests shed (queue + pool) inside the window. Source:
program_counter."""

from benchmark.layer_metrics import _common


def read(ctx):
    q, p = _common.delta(ctx, "sheds", "queue"), _common.delta(ctx, "sheds", "pool")
    return None if q is None or p is None else q + p
