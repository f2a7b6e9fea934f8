"""When the measured window opens and closes, from a cell's rule and the events
the tees recorded. Pure functions of plain lists, so the tests can drive them.

``reports``: [(host time, steps that trial ran since its previous report)], in
order of time. ``terminals``: [(host time, trial, condition)], in order of time.

- ``{"opens_after": {"reports": K}, "closes_on": "report"}``: opens at the K-th
  report, closes at the last report not later than ``seconds`` after that.
- ``{"opens_after": {"trials": K}, "closes_on": "trial"}``: opens when the K-th
  trial reaches a terminal condition, closes at the last one not later than
  ``seconds`` after that.

Every rate is all the work between those two instants over all the seconds
between them.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Window:
    t_open: float
    t_close: float
    steps: int            # train steps finished inside (closes_on report)
    reports: int          # reports inside, the opening one not counted
    trials: Tuple[str, ...]      # trials that reached a terminal condition inside
    failed: Tuple[str, ...]      # those of them that did not succeed

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def _events(rule, reports, terminals) -> List[float]:
    return [r[0] for r in reports] if "reports" in rule["opens_after"] else [t[0] for t in terminals]


def open_time(rule, reports: Sequence, terminals: Sequence) -> Optional[float]:
    k = next(iter(rule["opens_after"].values()))
    times = _events(rule, reports, terminals)
    return times[k - 1] if len(times) >= k else None


def measure(rule, seconds: float, reports: Sequence, terminals: Sequence) -> Optional[Window]:
    t_open = open_time(rule, reports, terminals)
    if t_open is None:
        return None
    deadline = t_open + seconds
    inside_reports = [r for r in reports if t_open < r[0] <= deadline]
    inside_trials = [t for t in terminals if t_open < t[0] <= deadline]
    closers = inside_reports if rule["closes_on"] == "report" else inside_trials
    t_close = closers[-1][0] if closers else t_open
    inside_reports = [r for r in inside_reports if r[0] <= t_close]
    return Window(
        t_open=t_open, t_close=t_close,
        steps=sum(r[1] for r in inside_reports), reports=len(inside_reports),
        trials=tuple(t[1] for t in inside_trials),
        failed=tuple(t[1] for t in inside_trials if t[2] != "Succeeded"),
    )
