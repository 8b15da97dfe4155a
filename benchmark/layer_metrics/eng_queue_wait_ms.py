"""scheduler: mean of the engine's ``requests.queue_wait_ms`` over the requests
whose first token fell in the window: submission (or a preempted request's
re-queue) until it is popped from the queue with a slot reserved. Source:
program_span. Moves ttft_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.request_phase_ms(ctx, "queue_wait")
