"""front: how late the load generator sent a request (sent - the later of its
due time and its predecessor's end), 95th percentile over every request of
the run. A starved generator must not read as a fast server. Source:
host_clock. Moves ttft_p50_ms."""


def read(ctx):
    return ctx["summary"].get("gen_late_p95_ms")
