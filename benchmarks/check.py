"""The comparison that decides ``correct``: every number beside its limit.

Three parts, each a dict ``{name: [value, limit]}``; a run is correct when no
value is above its limit.

- the model step (``compare_training``): what tees.py read off the first three
  steps of the trial's own compiled step, against reference_lm.py at the same
  learning rate;
- the report path (``compare_reports``): what each trial handed to
  ``ctx.report`` up to the close of the window, against the rows read back
  from the observation store on disk — an exact comparison, limit 0;
- the suggester (``compare_assignments``): assignments outside the cell's
  feasible space — limit 0.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Sequence

# A leaf whose reference gradient is under this share of the median leaf's is
# nought to rounding; under Adam it moves by round-off alone, so it is left out
# of the parameters' change (a rule on the reference's gradient, not on names).
NOUGHT_GRADIENT_SHARE = 1e-3


def training_gaps(program: Dict, reference: Dict) -> Dict[str, float]:
    """``program`` and ``reference``: ``{"loss": [l1, l2, l3], "grad_norm":
    {leaf: norm}, "delta_norm": {leaf: norm}}``.

    - ``loss_gap``: widest |program − reference| / |reference| over the steps.
    - ``grad_norm_gap``: by the worst leaf, the gap between the program's norm
      of the first gradient and the reference's — not the norm of a difference
      — against the reference's norm of that leaf or of the median leaf,
      whichever is larger.
    - ``delta_norm_gap``: the same for the parameters' change after the steps,
      over the leaves whose reference gradient is not nought.
    """
    if len(program["loss"]) != len(reference["loss"]) or not program["loss"]:
        raise ValueError("program and reference followed a different number of steps")
    if set(program["grad_norm"]) != set(reference["grad_norm"]):
        raise ValueError("program and reference have different leaves")
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(program["loss"], reference["loss"]))
    ref_g = reference["grad_norm"]
    median_g = statistics.median(ref_g.values())
    grad_gap = max(
        abs(program["grad_norm"][k] - ref_g[k]) / max(ref_g[k], median_g) for k in ref_g
    )
    counted = [k for k in ref_g if ref_g[k] >= NOUGHT_GRADIENT_SHARE * median_g]
    ref_d = reference["delta_norm"]
    median_d = statistics.median(ref_d[k] for k in counted)
    delta_gap = max(
        abs(program["delta_norm"][k] - ref_d[k]) / max(ref_d[k], median_d) for k in counted
    )
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap, "delta_norm_gap": delta_gap}


def compare_training(trials: Sequence[Dict], references: Sequence[Dict],
                     limits: Dict[str, float]) -> Dict[str, List[float]]:
    """The widest gap over the trials compared, beside each limit."""
    worst: Dict[str, float] = {}
    for program, reference in zip(trials, references):
        for name, gap in training_gaps(program, reference).items():
            worst[name] = max(worst.get(name, 0.0), gap)
    return {name: [worst[name], limits[name]] for name in worst}


def compare_reports(teed: Dict[str, List[float]], stored: Dict[str, List[float]]) -> Dict[str, List[float]]:
    """Reports that the store does not give back as they were handed in: for
    each trial, what the tee saw must be the beginning of the stored rows."""
    bad = 0
    for trial, values in teed.items():
        rows = stored.get(trial, [])
        bad += sum(1 for i, v in enumerate(values) if i >= len(rows) or rows[i] != v)
    return {"report_rows_lost_or_changed": [float(bad), 0.0]}


def compare_assignments(assignments: Sequence[Dict[str, str]], space: Dict[str, Dict],
                        fixed: Dict[str, str]) -> Dict[str, List[float]]:
    """Assignments outside the feasible space: a searched parameter beyond its
    bounds, or a one-value parameter that is not that value."""
    bad = 0
    for a in assignments:
        for name, bounds in space.items():
            try:
                ok = float(bounds["min"]) <= float(a[name]) <= float(bounds["max"])
            except (KeyError, ValueError):
                ok = False
            bad += not ok
        bad += sum(1 for name, value in fixed.items() if a.get(name) != str(value))
    return {"assignments_outside_space": [float(bad), 0.0]}


def verdict(checks: Dict[str, List[float]]) -> bool:
    return all(value <= limit for value, limit in checks.values())
