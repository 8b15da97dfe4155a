"""scheduler: the dispatch worker's milliseconds per launch before the jitted
step is called (``pipeline.launch_parts.upload_ms``): page allocation, the
page table, every ``jnp.asarray`` upload, the wait for the pool's dispatch
lock. The chip has nothing of this launch queued until it ends. Source:
program_span. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _timeline


def read(ctx):
    return _timeline.per_launch_ms(ctx, _timeline.part("upload"))
