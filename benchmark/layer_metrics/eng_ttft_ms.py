"""scheduler: mean of the engine's ``requests.ttft_ms`` over the requests whose
first token fell in the window: submission to the engine until the first
token is emitted, on the engine's clock. Source: program_span. Moves
ttft_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.request_phase_ms(ctx, "ttft")
