"""device: over the traced tail, the loop thread's time outside the device
wait (``cycle_ms`` - ``wait_ms`` sums between the trace's edges) over the
device's idle time (``window_s`` - ``busy_s``), x 100. A diagnostic with 100
as its target, not a quantity to raise: near 100 the engine's phases account
for the idle chip; far under, idle the host spans do not see (launch latency,
uploads the device waits for). Over 100 by as much as host time lies over
device work: the device starts before ``launch`` ends (the dispatch worker
has enqueued the step while the loop thread still waits for the hop back;
about half of ``launch`` in the chip traces of PERF.md section 5), less the
idle time inside ``wait`` (the copy returns after the device finished).
Numerator from the program's spans, denominator from the device trace.
Source: device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common, _phases


def read(ctx):
    t, edges = _common.traced(ctx), _common.trace_edges(ctx)
    if t is None or edges is None:
        return None
    gap_ms, cycles = _phases.gap_ms(ctx, edges)
    idle_s = t["window_s"] - t["busy_s"]
    if not cycles or idle_s <= 0:
        return None
    return 100.0 * (gap_ms / 1000.0) / idle_s
