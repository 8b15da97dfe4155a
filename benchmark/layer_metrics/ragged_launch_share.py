"""scheduler: 100 x the ragged steps (``ragged.steps``) over all launches
(``pipeline.dispatch_ms.count``: ragged steps and pipelined decode chunks)
that the window gained. Not a quantity to raise or lower (``better`` has no
meaning): it says how to read the per-launch means beside it. At 100 every
launch is a serial ragged step, the four ``launch_*_ms`` add up to
``step_launch_ms`` and every launch can starve; below it the rest are decode
chunks, which read 0 in ``starve_ms`` behind a chunk in flight and whose
``launch`` phase holds the event loop's handlers. None where the program
counts no ragged steps. Source: program_counter. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    steps = _common.delta(ctx, "ragged", "steps")
    launches = _common.launches(ctx)
    if steps is None or not launches:
        return None
    return 100.0 * steps / launches
