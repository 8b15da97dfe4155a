"""The noise study: where the run-to-run spread of a metric comes from.

    python3 benchmark/noise.py <run dir> <run dir> ...   # dirs with records.jsonl

For each latency metric it splits the variance of the runs' values into the
part that scatters request by request (what a run's statistic would vary by
if its requests were drawn afresh from one distribution: a bootstrap of each
run's own records) and the rest, which moved all requests of a run together
(host, machine, the schedule the seed drew). More samples cure the first;
only a steadier set-up or machine cures the second. Prints one JSON object.
"""

from __future__ import annotations

import json
import random
import sys
from pathlib import Path
from statistics import mean, median, pvariance

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from benchmark import reduce as rd  # noqa: E402

STATS = {
    "ttft_p50_ms": (rd.ttft_ms, 0.5), "ttft_p90_ms": (rd.ttft_ms, 0.9),
    "tpot_p50_ms": (rd.tpot_ms, 0.5), "tpot_p90_ms": (rd.tpot_ms, 0.9),
}


def run_samples(path: Path, fn) -> list:
    recs = rd.load_records(path / "records.jsonl")
    return [x for x in (fn(r) for r in recs if r["judged"] and rd.request_ok(r))
            if x is not None]


def bootstrap_var(samples: list, q: float, rng, rounds: int = 200) -> float:
    n = len(samples)
    vals = [rd.percentile([samples[rng.randrange(n)] for _ in range(n)], q)
            for _ in range(rounds)]
    return pvariance(vals)


def study(dirs: list) -> dict:
    rng = random.Random(0)
    out = {}
    for name, (fn, q) in STATS.items():
        runs = [run_samples(Path(d), fn) for d in dirs]
        runs = [r for r in runs if len(r) >= 20]
        if len(runs) < 3:
            continue
        values = [rd.percentile(r, q) for r in runs]
        total = pvariance(values)
        sampling = mean(bootstrap_var(r, q, rng) for r in runs)
        med = median(values)
        out[name] = {
            "runs": len(runs), "samples_per_run": round(mean(len(r) for r in runs), 1),
            "values": values, "median": med,
            "spread_iqr_over_median": rd.spread(values) if len(values) >= 4 else None,
            "cv_total": total ** 0.5 / med,
            "cv_request_scatter": sampling ** 0.5 / med,
            "cv_moved_together": max(0.0, total - sampling) ** 0.5 / med,
        }
    toks = []
    for d in dirs:
        detail = Path(d) / "detail.json"
        if detail.is_file():
            toks.append(json.loads(detail.read_text())["summary"]["out_tok_s"])
    if len(toks) >= 4:
        out["out_tok_s"] = {"runs": len(toks), "values": toks, "median": median(toks),
                            "spread_iqr_over_median": rd.spread(toks)}
    return out


if __name__ == "__main__":
    print(json.dumps(study(sys.argv[1:]), indent=1))
