"""scheduler: mean of the engine's ``requests.prefill_span_ms`` over the
requests whose first token fell in the window: the jitted call of the first
launch that carried a chunk of its prompt until the result of the launch that
carried the last is on the host: ``eng_prefill_launches`` cycles less the
first one's host part. Source: program_span. Moves ttft_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.request_phase_ms(ctx, "prefill_span")
