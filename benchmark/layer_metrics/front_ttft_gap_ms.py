"""front: mean client-side TTFT of the judged requests that were right, less
the engine's own mean TTFT over the window (``requests.ttft_ms``): what the
socket, router, tokeniser and SSE writer add to a first token. A difference
of two means over populations that are not the same (the engine's also holds
the unjudged requests whose first token fell in the window), so only for
cells in which a TTFT is short against the window: not ``prefill_batch``,
where it is scatter until the records are joined per request id (PERF.md
section 7). Source: host_clock. Moves ttft_p50_ms."""

from benchmark.layer_metrics import _common, _phases


def read(ctx):
    from benchmark.reduce import ttft_ms

    client = [v for v in map(ttft_ms, _common.ok_judged(ctx)) if v is not None]
    engine = _phases.request_phase_ms(ctx, "ttft")
    if not client or engine is None:
        return None
    return sum(client) / len(client) - engine
