"""scheduler: mean of the engine's ``requests.admit_ms`` over the requests
whose first token fell in the window: slot reserved until its prefill job is
opened: the admission worker's two thread hops and the wait for the next
loop top. Source: program_span. Moves ttft_p50_ms."""

from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.request_phase_ms(ctx, "admit")
