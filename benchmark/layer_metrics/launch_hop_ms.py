"""scheduler: the two thread hops of a launch, milliseconds per launch of the
window: ``hop_out`` (the loop thread's ``launch`` mark until the dispatch
worker's first line) plus ``hop_back`` (the worker's last line until the loop
thread has the result) of ``pipeline.launch_parts``. With
``launch_upload_ms``, ``launch_enqueue_ms`` and ``launch_tail_ms`` it adds up
to ``step_launch_ms`` under the serial ragged step. Source: program_span.
Moves tpot_p50_ms."""

from benchmark.layer_metrics import _timeline


def read(ctx):
    return _timeline.per_launch_ms(
        ctx, _timeline.part("hop_out"), _timeline.part("hop_back"))
