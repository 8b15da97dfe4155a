"""cache: the largest share of the page pool held by live requests at any
health sample inside the window (pages reclaimable from the prefix cache do
not count). Source: program_counter. Moves tpot_p50_ms."""


def read(ctx):
    vals = [s["pool"] for s in ctx["samples"] if s.get("pool") is not None]
    return 100.0 * max(vals) if vals else None
