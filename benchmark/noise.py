"""The noise study: where the run-to-run spread of a metric comes from.

    python3 benchmark/noise.py <run dir> <run dir> ...   # dirs with records.jsonl

For each latency metric it splits the variance of the runs' values into the
part that scatters request by request (what a run's statistic would vary by
if its requests were drawn afresh from one distribution: a bootstrap of each
run's own records) and the rest, which moved all requests of a run together
(host, machine, the schedule the seed drew). More samples cure the first;
only a steadier set-up or machine cures the second. Prints one JSON object.

    python3 benchmark/noise.py --check --cell <cell> [--manifest BENCHMARK.json] \
        --set <run dir> <run dir> ... --set <run dir> <run dir> ...

The rule a new or changed cell is held to, run before it is handed in: two
sets of untraced runs of the same code (dirs with ``detail.json``, four or
more a set; six on the same six seeds is what a full check makes). For every
end-to-end metric the cell reports it prints each set's spread and the bound
(the manifest's ``bound`` x the median of all runs), both in the metric's
unit, and ``too_noisy`` where twice the mean of the two spreads passes the
bound. A spread is the distance between the quartiles
(``statistics.quantiles(values, n=4)``), leaving out the run farthest from
the set's median where that narrows it; ``ranges`` beside it is max - min
taken the same way, the stricter reading. ``setup_s`` is printed and not
judged by spread. Exit 1 if any metric is too noisy or a run is not
``correct``. This is a READING of the driver's check from its contract and
from the words of two refusals (ledger, PR 37; PERF.md section 2 on PR 41),
not the check: a pass here is no promise.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
from pathlib import Path
from statistics import mean, median, pvariance, quantiles

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


MIN_RUNS = 4       # fewer leave no quartiles once the farthest run is out


def less_farthest(values: list) -> list:
    """``values`` without the one farthest from their median."""
    med = median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return values[:far] + values[far + 1:]


def set_spread(values: list) -> float:
    """Quartile distance of one set, in the metric's unit, leaving out the
    run farthest from the median where that narrows it."""
    def iqr(xs):
        q = quantiles(xs, n=4)
        return q[2] - q[0]

    return min(iqr(values), iqr(less_farthest(values)))


def set_range(values: list) -> float:
    """Max - min of one set, leaving out the run farthest from the median
    (an end of the range, so this never widens it)."""
    rest = less_farthest(values)
    return max(rest) - min(rest)


def judge(name: str, bound, sets: list) -> dict:
    """One metric of one cell: ``sets`` are the two lists of run values."""
    for values in sets:
        if len(values) < MIN_RUNS:
            raise ValueError("{}: a set of {} runs; the rule needs {} or more".format(
                name, len(values), MIN_RUNS))
    med = median([v for values in sets for v in values])
    out = {"median": med, "set_medians": [median(v) for v in sets],
           "spreads": [set_spread(v) for v in sets],
           "ranges": [set_range(v) for v in sets]}
    if name == "setup_s":       # judged by its median alone (PERF.md section 2)
        return dict(out, verdict="not_judged_by_spread")
    out["bound"] = bound * med
    out["twice_mean_spread"] = 2.0 * mean(out["spreads"])
    out["verdict"] = "too_noisy" if out["twice_mean_spread"] > out["bound"] else "ok"
    return out


def check(manifest: dict, cell: str, sets: list) -> dict:
    """The noise rule over two sets of run directories of ``cell``."""
    if len(sets) != 2:
        raise ValueError("the rule compares two sets, not {}".format(len(sets)))
    if cell not in [w["name"] for w in manifest["workloads"]]:
        raise ValueError("no workload {!r} in the manifest".format(cell))
    details = []
    for dirs in sets:
        details.append([json.loads((Path(d) / "detail.json").read_text()) for d in dirs])
        for d, det in zip(dirs, details[-1]):
            if det["workload"] != cell or det["trace"]:
                raise ValueError("{} is not an untraced run of {}".format(d, cell))
    out = {"cell": cell, "runs": [len(s) for s in sets], "metrics": {},
           "not_correct": [str(d) for dirs, dets in zip(sets, details)
                           for d, det in zip(dirs, dets) if not det["result"]["correct"]]}
    for m in manifest["end_to_end"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        values = [[det["result"]["metrics"][m["name"]]["value"] for det in dets]
                  for dets in details]
        out["metrics"][m["name"]] = dict(judge(m["name"], m["bound"], values),
                                         unit=m["unit"])
    out["too_noisy"] = [n for n, v in out["metrics"].items()
                        if v["verdict"] == "too_noisy"]
    return out


def main(argv: list) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dirs", nargs="*", help="run dirs for the noise study")
    ap.add_argument("--check", action="store_true",
                    help="judge two --set of runs of --cell by the noise rule")
    ap.add_argument("--manifest", default=str(HERE.parent / "BENCHMARK.json"))
    ap.add_argument("--cell")
    ap.add_argument("--set", dest="sets", action="append", nargs="+", default=[],
                    help="the run dirs of one set (give it twice)")
    args = ap.parse_args(argv)
    if not args.check:
        print(json.dumps(study(args.dirs), indent=1))
        return 0
    try:
        with open(args.manifest) as f:
            out = check(json.load(f), args.cell, args.sets)
    except (ValueError, OSError, KeyError) as ex:
        print("noise.py --check: {!r}".format(ex), file=sys.stderr)
        return 2
    print(json.dumps(out, indent=1))
    return 1 if out["too_noisy"] or out["not_correct"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
