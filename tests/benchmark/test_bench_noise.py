"""`noise.py --check`: the rule a cell's runs are held to before it is handed
in (twice the mean of two sets' spreads may not pass the bound), on the two
refusals' own numbers (ledger, PR 37; PERF.md section 2 on PR 41's first
check), on quiet sets, on sets too small to judge, and through the command
line on run directories as `run.py` leaves them."""

import json
import sys
from pathlib import Path
from statistics import median

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import noise  # noqa: E402

CELL = "mistral7b.decode_batch"


def runs(mid: float, spread: float, far: float = None) -> list:
    """Six runs around ``mid`` whose quartiles lie ``spread`` apart once the
    farthest (``far``, by default 3 spreads off) is left out."""
    far = mid + 3 * spread if far is None else far
    return [mid - spread / 2, mid + spread / 2, far, mid, mid - spread / 2,
            mid + spread / 2]


def test_a_spread_is_the_quartiles_distance_less_the_farthest_run():
    values = runs(50.0, 2.0)
    assert noise.less_farthest(values) == [49.0, 51.0, 50.0, 49.0, 51.0]
    assert noise.set_spread(values) == pytest.approx(2.0)
    assert noise.set_range(values) == pytest.approx(2.0)
    # leaving a run out never widens what is judged
    even = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    assert noise.set_spread(even) <= noise.set_spread(even + [6.5])
    assert noise.set_range(even) == pytest.approx(4.0)


@pytest.mark.parametrize("case,mid,spreads,bound,verdict", [
    # ledger, PR 37: "the spread is 1.77702 and 2.63071 ms, and the bound is
    # 2.52817 ms, 5% of 50.5633 ms"
    ("pr37_tpot_p50", 50.5633, (1.77702, 2.63071), 0.05, "too_noisy"),
    # PR 41's first check, `tpot_p90_ms` in `mixtral8x7b.chat_steady`: 1.12 and
    # 0.56 ms against 1.39 ms, 6% of 23.2 ms
    ("pr41_tpot_p90", 23.2, (1.12, 0.56), 0.06, "too_noisy"),
    ("quiet", 28.0, (0.25, 0.35), 0.05, "ok"),
    # one set over half the bound, the mean of the two under it
    ("one_loud_set", 28.0, (1.0, 0.2), 0.05, "ok"),
    ("at_the_limit", 100.0, (2.5, 2.5), 0.05, "ok"),
    ("just_over", 100.0, (2.6, 2.5), 0.05, "too_noisy"),
])
def test_twice_the_mean_spread_against_the_bound(case, mid, spreads, bound, verdict):
    got = noise.judge("tpot_p50_ms", bound, [runs(mid, s) for s in spreads])
    assert got["spreads"] == pytest.approx(list(spreads))
    assert got["bound"] == pytest.approx(bound * got["median"])
    assert got["median"] == pytest.approx(mid, rel=0.01)
    assert got["twice_mean_spread"] == pytest.approx(sum(spreads))
    assert got["verdict"] == verdict, case


def test_one_far_run_a_set_does_no_harm_and_two_do():
    quiet = runs(28.0, 0.3, far=40.0)
    assert noise.judge("tpot_p50_ms", 0.05, [quiet, quiet])["verdict"] == "ok"
    two_far = [27.85, 28.15, 40.0, 28.0, 27.85, 41.0]
    assert noise.judge("tpot_p50_ms", 0.05, [two_far, quiet])["verdict"] == "too_noisy"


@pytest.mark.parametrize("sizes", [(3, 6), (6, 3), (0, 6)])
def test_fewer_than_four_runs_a_set_is_an_error_not_a_pass(sizes):
    sets = [runs(28.0, 0.3)[:n] for n in sizes]
    with pytest.raises(ValueError, match="needs 4 or more"):
        noise.judge("tpot_p50_ms", 0.05, sets)


def test_setup_is_printed_and_not_judged_by_spread():
    got = noise.judge("setup_s", 0.1, [runs(60.0, 30.0), runs(62.0, 25.0)])
    assert got["verdict"] == "not_judged_by_spread" and "bound" not in got
    assert got["spreads"] == pytest.approx([30.0, 25.0])
    assert got["set_medians"] == pytest.approx(
        [median(runs(60.0, 30.0)), median(runs(62.0, 25.0))])


# ------------------------------------------------------- the command line

def write_sets(tmp_path, tpot, toks=None, setup=None, cell=CELL, trace=0, correct=True):
    """Run directories as `run.py` leaves them: two sets, ``tpot`` = the two
    lists of `tpot_p50_ms`."""
    sets = []
    for k, values in enumerate(tpot):
        sets.append([])
        for i, v in enumerate(values):
            d = tmp_path / "set{}_s{}".format(k + 1, i)
            d.mkdir()
            metrics = {"tpot_p50_ms": v, "out_tok_s": (toks or tpot)[k][i] * 38.0,
                       "setup_s": (setup or tpot)[k][i] * 2.0}
            (d / "detail.json").write_text(json.dumps({
                "workload": cell, "trace": trace, "result": {
                    "correct": correct,
                    "metrics": {n: {"value": x} for n, x in metrics.items()}}}))
            sets[-1].append(str(d))
    return sets


def check_argv(sets, cell=CELL):
    argv = ["--check", "--manifest", str(ROOT / "BENCHMARK.json"), "--cell", cell]
    for s in sets:
        argv += ["--set"] + s
    return argv


def test_a_quiet_cell_passes_through_the_command_line(tmp_path, capsys):
    sets = write_sets(tmp_path, [runs(28.0, 0.25), runs(28.1, 0.35)])
    assert noise.main(check_argv(sets)) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["cell"] == CELL and out["runs"] == [6, 6]
    assert out["too_noisy"] == [] and out["not_correct"] == []
    # the end-to-end metrics the manifest gives this cell, and no other
    assert set(out["metrics"]) == {"tpot_p50_ms", "out_tok_s", "setup_s"}
    tpot = out["metrics"]["tpot_p50_ms"]
    assert tpot["unit"] == "ms" and tpot["verdict"] == "ok"
    assert tpot["spreads"] == pytest.approx([0.25, 0.35])
    assert tpot["bound"] == pytest.approx(0.05 * tpot["median"])
    assert out["metrics"]["out_tok_s"]["bound"] == pytest.approx(
        0.1 * out["metrics"]["out_tok_s"]["median"])
    assert out["metrics"]["setup_s"]["verdict"] == "not_judged_by_spread"


def test_a_noisy_metric_is_named_and_the_exit_code_is_1(tmp_path, capsys):
    quiet = [runs(28.0, 0.25), runs(28.1, 0.35)]
    sets = write_sets(tmp_path, [runs(28.0, 0.9), runs(28.0, 0.8)], toks=quiet,
                      setup=[runs(30.0, 9.0), runs(30.0, 9.0)])
    assert noise.main(check_argv(sets)) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["too_noisy"] == ["tpot_p50_ms"]        # not a set-up that swings
    assert out["metrics"]["out_tok_s"]["verdict"] == "ok"


@pytest.mark.parametrize("case", ["three_runs", "one_set", "another_cell",
                                  "a_traced_run", "no_such_cell", "no_detail"])
def test_what_cannot_be_judged_is_an_error(case, tmp_path, capsys):
    n = 3 if case == "three_runs" else 6
    sets = write_sets(tmp_path, [runs(28.0, 0.3)[:n], runs(28.0, 0.3)],
                      cell="mistral7b.chat_steady" if case == "another_cell" else CELL,
                      trace=int(case == "a_traced_run"))
    if case == "one_set":
        sets = sets[:1]
    if case == "no_detail":
        (Path(sets[0][0]) / "detail.json").unlink()
    argv = check_argv(sets, "nowhere.cell" if case == "no_such_cell" else CELL)
    assert noise.main(argv) == 2
    got = capsys.readouterr()
    assert got.out == "" and "noise.py --check" in got.err


def test_a_run_that_is_not_correct_fails_the_check(tmp_path, capsys):
    sets = write_sets(tmp_path, [runs(28.0, 0.25), runs(28.1, 0.35)], correct=False)
    assert noise.main(check_argv(sets)) == 1
    assert len(json.loads(capsys.readouterr().out)["not_correct"]) == 12


def test_without_check_it_is_the_noise_study_as_before(capsys):
    assert noise.main([]) == 0
    assert json.loads(capsys.readouterr().out) == {}
