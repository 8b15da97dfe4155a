"""From per-request records to numbers: the arithmetic of the yardstick.

Percentiles are by linear interpolation between order statistics (numpy's
default), copied in spirit from ``benchmarks/slo_loadtest.py::_percentile``
(nearest rank there; see PERF.md Open questions). A percentile is reported
only where at least ``MIN_BEYOND`` samples lie beyond it: a 90th percentile
needs 100 samples, a median 20.
"""

from __future__ import annotations

import json
import math

MIN_BEYOND = 10


def percentile(values, q: float):
    """The ``q``-quantile (0..1) by linear interpolation; None if empty."""
    xs = sorted(values)
    if not xs:
        return None
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def enough_for(n: int, q: float) -> bool:
    """Do ``n`` samples leave MIN_BEYOND beyond the ``q``-quantile, on the
    side that has fewer?"""
    return n * min(q, 1.0 - q) >= MIN_BEYOND - 1e-9


def tpot_ms(rec: dict):
    """Per-request time per output token: (t_last - t_first) / (n - 1). The
    engine emits up to ``decode_steps`` tokens per launch, so single gaps
    are 0 or a whole launch and their percentiles mean nothing."""
    if rec["first"] is None or rec["n_out"] < 2:
        return None
    return (rec["last"] - rec["first"]) * 1000.0 / (rec["n_out"] - 1)


def ttft_ms(rec: dict):
    """Due time (open loop) or send time (closed loop, where due == sent) to
    the first streamed token."""
    if rec["first"] is None or rec["due"] is None:
        return None
    return (rec["first"] - rec["due"]) * 1000.0


def load_records(path) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def request_ok(rec: dict) -> bool:
    """A response is right when it ended, carried exactly its drawn number
    of tokens (by logprob entries and by the server's own count) and every
    logprob was a finite number."""
    return bool(
        rec["status"] == 200 and rec["done"] and rec["error"] is None
        and rec["n_out"] == rec["want"]
        and rec["completion_tokens"] == rec["want"]
        and rec["lp_finite"]
    )


def summarise(records: list, window_s: float, limits: dict = None) -> dict:
    """Every client-side number of a run. ``limits`` ({"ttft_ms", "tpot_ms"})
    are the cell's latency limits for ``req_slo_share``."""
    judged = [r for r in records if r["judged"]]
    ok = [r for r in judged if request_ok(r)]
    ttft = [x for x in (ttft_ms(r) for r in ok) if x is not None]
    tpot = [x for x in (tpot_ms(r) for r in ok) if x is not None]
    out = {
        "attempted": len(judged),
        "failed": len(judged) - len(ok),
        "sent_total": sum(1 for r in records if r["sent"] is not None),
        "tokens_in_window": sum(r["tok_in_window"] for r in records),
        "out_tok_s": sum(r["tok_in_window"] for r in records) / window_s,
        "ttft_p50_ms": percentile(ttft, 0.5) if enough_for(len(ttft), 0.5) else None,
        "ttft_p90_ms": percentile(ttft, 0.9) if enough_for(len(ttft), 0.9) else None,
        "tpot_p50_ms": percentile(tpot, 0.5) if enough_for(len(tpot), 0.5) else None,
        "tpot_p90_ms": percentile(tpot, 0.9) if enough_for(len(tpot), 0.9) else None,
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "tpot_mean_ms": sum(tpot) / len(tpot) if tpot else None,
        "prompt_tokens_mean": (
            sum(r["prompt_tokens"] for r in ok) / len(ok) if ok else None
        ),
        "answer_tokens_mean": sum(r["want"] for r in ok) / len(ok) if ok else None,
    }
    late = [r["late"] * 1000.0 for r in records if r.get("late") is not None]
    out["gen_late_p95_ms"] = percentile(late, 0.95)
    if limits and ok:
        met = sum(
            1 for r in ok
            if ttft_ms(r) is not None and tpot_ms(r) is not None
            and ttft_ms(r) <= limits["ttft_ms"] and tpot_ms(r) <= limits["tpot_ms"]
        )
        # a failed request misses its limits
        out["req_slo_share"] = 100.0 * met / len(judged)
    return out


def spread(values) -> float:
    """Distance between the quartiles over the median, as the contract has
    it (``statistics.quantiles(values, n=4)``)."""
    from statistics import median, quantiles

    q = quantiles(values, n=4)
    return (q[2] - q[0]) / median(values)
