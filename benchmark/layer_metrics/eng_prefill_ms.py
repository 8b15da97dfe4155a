"""scheduler: mean of the engine's ``requests.prefill_ms`` over the requests
whose first token fell in the window: job opened until the first token is
emitted: the launches that carry its prompt chunks. Source: program_span.
Moves ttft_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.request_phase_ms(ctx, "prefill")
