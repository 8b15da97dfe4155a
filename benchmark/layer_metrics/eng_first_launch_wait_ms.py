"""scheduler: mean of the engine's ``requests.first_launch_wait_ms`` over the
requests whose first token fell in the window: job opened until the jitted
call of the first launch that carried a chunk of its prompt (the launch in
flight when the job opened, then plan and upload of its own). With
``eng_prefill_span_ms`` and ``eng_first_emit_ms`` it adds up to
``eng_prefill_ms``. Source: program_span. Moves ttft_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.request_phase_ms(ctx, "first_launch_wait")
