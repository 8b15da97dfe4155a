"""What the readers of the engine's launch timeline share (``lifecycle_stats()``
blocks ``pipeline.launch_parts`` / ``readback_ms`` / ``starve_ms``, cumulative
histograms with one observation per launch; ``starve_ms`` has none for a
launch that follows a park). A program without these blocks, as every one
before they existed, gives None and the metric is left out of the line."""

from benchmark.layer_metrics import _common

STARVE = ("pipeline", "starve_ms")
READBACK = ("pipeline", "readback_ms")


def part(name):
    return ("pipeline", "launch_parts", name + "_ms")


def gained_ms(ctx, *paths, edges=None):
    """Milliseconds the histograms at ``paths`` gained together."""
    sums = [_common.hist_delta(ctx, *path, edges=edges)[0] for path in paths]
    return None if None in sums else sum(sums)


def per_launch_ms(ctx, *paths):
    """The same over the launches of the window."""
    total, launches = gained_ms(ctx, *paths), _common.launches(ctx)
    return total / launches if total is not None and launches else None
