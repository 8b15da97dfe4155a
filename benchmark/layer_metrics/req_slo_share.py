"""front: share of the judged requests that met both latency limits of the
mix file (3x unloaded TTFT p50, 1.5x unloaded TPOT p50, measured once when
the rate was found); a failed request misses. Source: host_clock."""


def read(ctx):
    return ctx["summary"].get("req_slo_share")
