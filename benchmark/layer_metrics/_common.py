"""Helpers the per-layer readers share. A reader is one file named after its
metric, with ``read(ctx) -> float | None``; one that finds nothing to read
returns None and the harness leaves the metric out of the line. ``ctx`` holds:
``summary`` and ``records`` (client side), ``before`` / ``after`` (program
counters at the window's edges; histograms are cumulative), ``samples``
(health twice a second), ``trace`` (xplane.reduce_trace of the traced tail)
with ``trace_counters`` (counters at its edges) and ``trace_path`` (the
``.xplane.pb`` itself, there until every reader ran), ``cfg``, ``mix``,
``device``, ``front_probe_ms``, ``window_s``.
"""

from __future__ import annotations


def dig(d, *path):
    for key in path:
        if not isinstance(d, dict) or key not in d:
            return None
        d = d[key]
    return d


def delta(ctx, *path, edges=None):
    """after - before of a counter, over the window or over ``edges``."""
    before, after = edges or (ctx["before"], ctx["after"])
    a, b = dig(after, *path), dig(before, *path)
    if a is None or b is None:
        return None
    return a - b


def hist_delta(ctx, *path, edges=None):
    """(sum, count) a cumulative histogram gained."""
    s = delta(ctx, *path, "sum_ms", edges=edges)
    n = delta(ctx, *path, "count", edges=edges)
    if s is None or n is None:
        return None, None
    return s, n


def launches(ctx, edges=None):
    """Programs the engine launched: every dispatch of the pipeline, ragged
    step or decode chunk (``ragged.steps`` counts only the former)."""
    return delta(ctx, "pipeline", "dispatch_ms", "count", edges=edges)


def sent_prompt_tokens(ctx):
    """Prompt tokens (the server's count) of the requests sent in the window."""
    win = ctx["window"]
    return sum(r["prompt_tokens"] for r in ctx["records"]
               if r["sent"] is not None and r["prompt_tokens"]
               and win["t_open"] <= r["sent"] < win["t_close"])


def trace_edges(ctx):
    return ctx.get("trace_counters")


def traced(ctx):
    t = ctx.get("trace")
    return t if t and t.get("devices") else None


def ok_judged(ctx):
    from benchmark.reduce import request_ok

    return [r for r in ctx["records"] if r["judged"] and request_ok(r)]


def raw_percentile(values, q, at_least=10):
    from benchmark.reduce import percentile

    values = [v for v in values if v is not None]
    return percentile(values, q) if len(values) >= at_least else None


def breakdown(ctx) -> dict:
    """The ten device operations with most time, and the idle gaps by what the
    host was doing: the seconds of all gaps under each innermost ``engine.*``
    annotation of the host plane (``host_spans.idle_by_span``; ``no_cycle``
    where none encloses), the seven largest first, then the longest single
    gaps up to ten entries, each under the annotation that held most of it."""
    from benchmark import host_spans

    t = traced(ctx)
    if t is None:
        return {"device_ops": [], "idle_gaps": []}
    ops = [[name[:64], secs] for name, secs, _ in t["ops"][:10]]
    gaps = sorted(t["gaps"])
    parts = host_spans.idle_by_span(gaps, t.get("host_spans") or [])
    out = [["all_gaps:_" + k, v] for k, v in
           sorted(host_spans.summed(parts).items(), key=lambda kv: -kv[1])[:7]]
    longest = sorted(zip(gaps, parts), key=lambda gp: -gp[0][1])
    out += [["one_gap:_" + max(split, key=split.get), length]
            for (_, length), split in longest[:10 - len(out)]]
    return {"device_ops": ops, "idle_gaps": out}
