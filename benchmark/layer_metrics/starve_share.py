"""scheduler: 100 x the milliseconds ``pipeline.starve_ms`` gained over those
``pipeline.cycle_ms`` gained in the window: the share of the loop's time in
which the program knows the chip had nothing queued. The program's own lower
bound of ``device_idle_share``, over the whole window and with no profiler
(what an operator reads as ``engine_device_starve_ms`` over
``engine_step_phase_ms{phase="cycle"}``). Source: program_span. Moves
tpot_p50_ms."""

from benchmark.layer_metrics import _common, _timeline


def read(ctx):
    starved = _timeline.gained_ms(ctx, _timeline.STARVE)
    cycle, cycles = _common.hist_delta(ctx, "pipeline", "cycle_ms")
    if starved is None or not cycles or not cycle:
        return None
    return 100.0 * starved / cycle
