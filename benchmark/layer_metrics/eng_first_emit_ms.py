"""scheduler: mean of the engine's ``requests.first_emit_ms`` over the requests
whose first token fell in the window: the last prompt launch's result on the
host until the request's first ``_emit``: the other copies, the emission of
every decode row ahead of it, its own first-token sample. Source:
program_span. Moves ttft_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.request_phase_ms(ctx, "first_emit")
