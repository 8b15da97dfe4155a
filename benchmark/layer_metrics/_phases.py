"""What the readers of the engine's own spans share (``lifecycle_stats()``
blocks ``pipeline.phases`` / ``pipeline.cycle_ms`` and ``requests``, both
cumulative histograms). A program without these blocks, as every one before
they existed, gives None and the metric is left out of the line."""

from benchmark.layer_metrics import _common


def mean(ctx, *path, edges=None):
    """Sum over count that a cumulative histogram gained."""
    s, n = _common.hist_delta(ctx, *path, edges=edges)
    return s / n if n else None


def step_phase_ms(ctx, phase):
    """Loop-thread milliseconds per scheduling cycle in one phase."""
    return mean(ctx, "pipeline", "phases", phase + "_ms")


def gap_ms(ctx, edges=None):
    """(sum, cycles) of ``cycle_ms`` less ``wait_ms``: loop-thread time in
    which the engine was not blocked on the device's result."""
    c, n = _common.hist_delta(ctx, "pipeline", "cycle_ms", edges=edges)
    w, _ = _common.hist_delta(ctx, "pipeline", "phases", "wait_ms", edges=edges)
    if c is None or w is None or not n:
        return None, None
    return c - w, n


def request_phase_ms(ctx, phase):
    """Mean over the requests whose first token fell in the window."""
    return mean(ctx, "requests", phase + "_ms")
