"""front: the round trip, inside the window, of a request that does all of
the front's work (socket, router, request processor, endpoint, tokenizer) and
none of the engine's (``POST v1/tokenize``). The engine reports no per-request
timestamps through the route yet (PERF.md, tracing issue), so client-side
TTFT less engine-side TTFT cannot be taken. Source: host_clock. Moves
ttft_p50_ms."""


def read(ctx):
    from benchmark.reduce import percentile

    return percentile(ctx["front_probe_ms"], 0.5)
