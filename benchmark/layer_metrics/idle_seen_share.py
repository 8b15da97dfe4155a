"""device: over the traced tail, the seconds ``pipeline.starve_ms`` gained
between the trace's edges over the device's idle time (``window_s`` -
``busy_s``), x 100: how much of the idle chip the program sees by itself. A
diagnostic whose target is 100 FROM BELOW: every starve window lies inside a
gap of the device (the previous launch had finished, the next was not yet
called), so it cannot run over the way ``idle_explained_share`` does, save for
a launch at the trace's edge whose window lies before the first traced
operation. What is missing to 100 is idle the program cannot see: the first
copy's own latency, the enqueue, the launch latency, small launches between
steps. Numerator from the program's spans, denominator from the device trace.
Source: device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common, _timeline


def read(ctx):
    t, edges = _common.traced(ctx), _common.trace_edges(ctx)
    if t is None or edges is None:
        return None
    starved = _timeline.gained_ms(ctx, _timeline.STARVE, edges=edges)
    idle_s = t["window_s"] - t["busy_s"]
    if starved is None or not _common.launches(ctx, edges) or idle_s <= 0:
        return None
    return 100.0 * (starved / 1000.0) / idle_s
