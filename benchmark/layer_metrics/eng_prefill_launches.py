"""scheduler: launches that carried a chunk of one request's prompt before its
first token, mean over the requests whose first token fell in the window
(``requests.prefill_launches``). Source: program_counter. Moves ttft_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.mean(ctx, "requests", "prefill_launches")
