"""window.py, metrics.py and check.py on plain data."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import check  # noqa: E402
import metrics  # noqa: E402
import window  # noqa: E402

STEADY = {"opens_after": {"reports": 2}, "closes_on": "report"}
SWEEP = {"opens_after": {"trials": 2}, "closes_on": "trial"}


def test_steady_window_opens_at_the_kth_report_and_closes_on_a_report():
    reports = [(10.0 + i, 5) for i in range(20)]          # a report a second, 5 steps each
    w = window.measure(STEADY, 7.5, reports, [])
    assert (w.t_open, w.t_close) == (11.0, 18.0)           # 18.5 is the deadline
    assert w.steps == 35 and w.reports == 7 and w.seconds == 7.0
    assert window.open_time(STEADY, reports[:1], []) is None
    assert window.measure(STEADY, 7.5, reports[:1], []) is None


def test_sweep_window_opens_at_the_kth_terminal_and_drops_the_trial_in_flight():
    terminals = [(20.0, "a", "Succeeded"), (40.0, "b", "Succeeded"), (61.0, "c", "Succeeded"),
                 (83.0, "d", "Failed"), (104.0, "e", "Succeeded")]
    reports = [(t - 1.0, 10) for t, _, _ in terminals] + [(110.0, 5)]
    w = window.measure(SWEEP, 50.0, sorted(reports), terminals)
    assert (w.t_open, w.t_close) == (40.0, 83.0)
    assert w.trials == ("c", "d") and w.failed == ("d",)
    assert w.steps == 20                                   # e's and the later report are outside
    empty = window.measure(SWEEP, 10.0, sorted(reports), terminals)
    assert empty.trials == () and empty.seconds == 0.0


def _run(rule, w, **cell):
    cell = dict({"window": rule, "batch_size": 4, "seq_len": 2048, "chips": 1}, **cell)
    return types.SimpleNamespace(cell=cell, window=w, t_start=3.0)


def test_end_to_end_metrics_take_all_work_over_all_seconds():
    w = window.measure(STEADY, 7.5, [(10.0 + i, 5) for i in range(20)], [])
    run = _run(STEADY, w)
    assert metrics.train_tokens_per_s(run) == pytest.approx(35 * 8192 / 7.0)
    assert metrics.chip_s_per_trial(run) is None
    assert metrics.setup_s(run) == pytest.approx(8.0)
    terminals = [(20.0, "a", "Succeeded"), (40.0, "b", "Succeeded"), (61.0, "c", "Succeeded"),
                 (83.0, "d", "Succeeded")]
    sweep = _run(SWEEP, window.measure(SWEEP, 50.0, [], terminals))
    assert metrics.chip_s_per_trial(sweep) == pytest.approx(21.5)
    assert metrics.train_tokens_per_s(sweep) is None
    nothing = _run(SWEEP, window.measure(SWEEP, 5.0, [], terminals))
    assert metrics.chip_s_per_trial(nothing) is None       # a failed run, never a zero


def test_report_rows_must_begin_with_what_the_tee_saw():
    ok = check.compare_reports({"t": [1.0, 2.0]}, {"t": [1.0, 2.0, 3.0]})
    assert ok == {"report_rows_lost_or_changed": [0.0, 0.0]} and check.verdict(ok)
    assert check.compare_reports({"t": [1.0, 2.0]}, {"t": [1.0]})["report_rows_lost_or_changed"][0] == 1
    assert check.compare_reports({"t": [1.0, 2.0]}, {"t": [1.0, 2.5]})["report_rows_lost_or_changed"][0] == 1
    assert not check.verdict(check.compare_reports({"t": [1.0]}, {}))


def test_assignments_are_held_to_the_feasible_space():
    space = {"learning_rate": {"min": "3e-5", "max": "3e-3"}}
    fixed = {"embed_dim": "64"}
    good = [{"learning_rate": "0.001", "embed_dim": "64"}]
    assert check.verdict(check.compare_assignments(good, space, fixed))
    for bad in ({"learning_rate": "0.01", "embed_dim": "64"}, {"learning_rate": "0.001", "embed_dim": "128"},
                {"embed_dim": "64"}):
        assert not check.verdict(check.compare_assignments([bad], space, fixed))


def test_training_gaps_are_gaps_of_norms_by_the_worst_leaf():
    ref = {"loss": [10.0, 9.0, 8.0], "grad_norm": {"a": 1.0, "b": 2.0, "c": 1e-6},
           "delta_norm": {"a": 0.1, "b": 0.2, "c": 0.3}}
    prog = {"loss": [10.0, 9.09, 8.0], "grad_norm": {"a": 1.1, "b": 2.0, "c": 3e-6},
            "delta_norm": {"a": 0.1, "b": 0.21, "c": 0.9}}
    gaps = check.training_gaps(prog, ref)
    assert gaps["loss_gap"] == pytest.approx(0.01)
    assert gaps["grad_norm_gap"] == pytest.approx(0.1)      # c is held against the median leaf
    # c's reference gradient is nought to rounding: its change is not compared
    assert gaps["delta_norm_gap"] == pytest.approx(0.01 / 0.2)
    with pytest.raises(ValueError):
        check.training_gaps(dict(prog, loss=[1.0]), ref)
