"""Percentile, TTFT / TPOT and population arithmetic of the yardstick."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import reduce as rd  # noqa: E402


def rec(**kw):
    base = {"id": "r", "want": 4, "judged": True, "due": 10.0, "sent": 10.0,
            "first": 10.5, "last": 10.8, "end": 10.9, "n_out": 4,
            "tok_in_window": 4, "lp_finite": True, "done": True, "status": 200,
            "finish": "length", "error": None, "late": 0.001,
            "prompt_tokens": 100, "completion_tokens": 4}
    base.update(kw)
    return base


@pytest.mark.parametrize("values,q,expect", [
    ([1, 2, 3, 4, 5], 0.5, 3.0),
    ([1, 2, 3, 4], 0.5, 2.5),
    ([10, 20, 30, 40, 50], 0.9, 46.0),
    ([5], 0.9, 5.0),
    ([3, 1, 2], 0.0, 1.0),
    ([3, 1, 2], 1.0, 3.0),
])
def test_percentile_matches_numpy(values, q, expect):
    import numpy as np

    assert rd.percentile(values, q) == pytest.approx(expect)
    assert rd.percentile(values, q) == pytest.approx(float(np.quantile(values, q)))


def test_percentile_of_nothing():
    assert rd.percentile([], 0.5) is None


@pytest.mark.parametrize("n,q,ok", [
    (100, 0.9, True), (99, 0.9, False), (20, 0.5, True), (19, 0.5, False),
    (200, 0.95, True), (199, 0.95, False),
])
def test_ten_samples_beyond_a_percentile(n, q, ok):
    assert rd.enough_for(n, q) is ok


def test_tpot_is_per_request():
    assert rd.tpot_ms(rec()) == pytest.approx(100.0)       # 0.3 s over 3 gaps
    assert rd.tpot_ms(rec(n_out=1)) is None
    assert rd.tpot_ms(rec(first=None)) is None


def test_ttft_is_timed_from_due():
    assert rd.ttft_ms(rec(due=10.0, sent=10.2)) == pytest.approx(500.0)


@pytest.mark.parametrize("change", [
    {"n_out": 3}, {"completion_tokens": 5}, {"lp_finite": False},
    {"done": False}, {"status": 429}, {"error": "x"},
])
def test_a_wrong_response_fails(change):
    assert rd.request_ok(rec()) is True
    assert rd.request_ok(rec(**change)) is False


def test_summarise_counts_the_population():
    records = [rec(id=str(i), first=10.5 + 0.001 * i) for i in range(30)]
    records += [rec(id="bad", n_out=2), rec(id="ramp", judged=False, tok_in_window=0)]
    s = rd.summarise(records, 2.0, {"ttft_ms": 510.0, "tpot_ms": 1000.0})
    assert s["attempted"] == 31 and s["failed"] == 1
    assert s["out_tok_s"] == pytest.approx(31 * 4 / 2.0)
    assert s["ttft_p50_ms"] == pytest.approx(514.5)
    assert s["tpot_p90_ms"] is None            # 30 samples: no tail
    assert s["req_slo_share"] == pytest.approx(100.0 * 11 / 31)


def test_spread_is_iqr_over_median():
    from statistics import median, quantiles

    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q = quantiles(xs, n=4)
    assert rd.spread(xs) == pytest.approx((q[2] - q[0]) / median(xs))
