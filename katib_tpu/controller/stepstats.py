"""Controller half of the step-statistics plane (ISSUE 20).

The runtime half (katib_tpu/runtime/stepstats.py) measures each stint: per
step wall durations, throughput volume, recompiles. This plane owns what
happens when a stint ENDS — stint rows written through the observation
pipeline, per-experiment rollups exported on /metrics, and the three
detectors:

- ``RetraceStorm``: one stint recompiled more than
  ``runtime.retrace_storm_threshold`` times past the first compile — the
  classic symptom of a shape-unstable train loop burning its step budget on
  XLA retraces.
- ``GangStraggler``: a packed/fused member's p95 step time exceeds the gang
  median by ``runtime.straggler_ratio`` — the packing plane's first
  slowest-member visibility (Podracer-style schedulers tune off exactly
  this, arXiv:2104.06272).
- ``StepTimeRegression``: a resumed/promoted stint is measurably slower
  than the same trial's prior-stint baseline (read back from the persisted
  perf rows), past ``runtime.step_regression_ratio``.

Constructed only when ``runtime.step_stats`` is on; every consult from the
scheduler is one ``is None`` check when it is off.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..runtime.stepstats import PERF_PREFIX, StepClock, StintSummary, perf_logs

# stint summaries kept per experiment for the /metrics rollups
ROLLUP_STINTS = 512

_P50_ROW = PERF_PREFIX + "stint_step_seconds_p50"


class _ExpRollup:
    __slots__ = (
        "stint_p50s", "stint_p95s", "total_steps", "total_seconds",
        "total_examples", "last_mfu", "device_seconds", "best_objective",
    )

    def __init__(self) -> None:
        self.stint_p50s: deque = deque(maxlen=ROLLUP_STINTS)
        self.stint_p95s: deque = deque(maxlen=ROLLUP_STINTS)
        self.total_steps = 0
        self.total_seconds = 0.0
        self.total_examples = 0.0
        self.last_mfu: Optional[float] = None
        self.device_seconds = 0.0
        self.best_objective: Optional[float] = None


class StepStatsPlane:
    """Per-experiment perf rollups + stint finalization + detectors."""

    def __init__(
        self,
        metrics: Optional[Any] = None,
        events: Optional[Any] = None,
        flush_steps: int = 32,
        retrace_storm_threshold: int = 8,
        straggler_ratio: float = 2.0,
        regression_ratio: float = 1.5,
    ) -> None:
        self.metrics = metrics
        self.events = events
        self.flush_steps = flush_steps
        self.retrace_storm_threshold = retrace_storm_threshold
        self.straggler_ratio = straggler_ratio
        self.regression_ratio = regression_ratio
        self._lock = threading.Lock()
        self._rollups: Dict[str, _ExpRollup] = {}
        self._cost_cache: Dict[str, Any] = {}
        self._device_kind: Optional[str] = None
        if metrics is not None:
            metrics.add_collector(
                self._collect,
                names=(
                    "katib_step_seconds",
                    "katib_trial_throughput",
                    "katib_trial_mfu_ratio",
                    "katib_objective_per_device_second",
                ),
            )

    # -- clock factory -------------------------------------------------------

    def clock_for(self, member_index: Optional[int] = None) -> StepClock:
        return StepClock(flush_steps=self.flush_steps, member_index=member_index)

    # -- stint finalization --------------------------------------------------

    def finalize_stint(
        self,
        exp: Any,
        trial_name: str,
        clock: StepClock,
        store: Any,
        n_devices: int = 1,
        write_rows: bool = True,
    ) -> Optional[StintSummary]:
        """A stint ended (trial finished, rung-paused, early-stopped, ...).

        Writes the stint-level perf rows, fires RetraceStorm and
        StepTimeRegression, and folds the summary into the experiment
        rollup. ``write_rows=False`` skips persistence for stints whose
        rows are about to be discarded anyway (preempt-requeue truncates to
        the last checkpoint; the resumed stint re-measures)."""
        rows, summary = clock.finalize()
        if summary.steps <= 0:
            return None
        mfu_value = self._mfu_for(exp, summary, n_devices)
        if mfu_value is not None and write_rows:
            rows.append(("stint_mfu", mfu_value))
        exp_name = getattr(exp, "name", str(exp))
        baseline = None
        if write_rows and store is not None:
            # prior stint rows identify a resumed/promoted stint — and are
            # the StepTimeRegression baseline (earliest stint = the
            # cheapest-fidelity reference)
            try:
                prior = store.get_observation_log(trial_name, metric_name=_P50_ROW)
            except Exception:
                prior = []
            for log in prior:
                try:
                    baseline = float(log.value)
                    break
                except (TypeError, ValueError):
                    continue
            try:
                store.report_observation_log(trial_name, perf_logs(rows))
                store.flush()  # later stints read these back as baselines
            except Exception:
                pass
        self._detect_retrace_storm(exp_name, trial_name, summary)
        if baseline is not None and baseline > 0 and summary.p50 > 0:
            self._detect_regression(exp_name, trial_name, summary, baseline)
        self._absorb(exp_name, summary, mfu_value)
        return summary

    def finalize_pack(
        self,
        exp: Any,
        trial_names: Sequence[str],
        clocks: Sequence[StepClock],
        store: Any,
        n_devices: int = 1,
        requeued: Sequence[bool] = (),
    ) -> None:
        """Finalize every member's stint, then run the gang-level straggler
        detector over the members that actually stepped."""
        summaries: List[Tuple[str, StintSummary]] = []
        for i, (name, clock) in enumerate(zip(trial_names, clocks)):
            skip = bool(requeued[i]) if i < len(requeued) else False
            s = self.finalize_stint(
                exp, name, clock, store,
                n_devices=max(1, n_devices // max(1, len(trial_names))),
                write_rows=not skip,
            )
            if s is not None:
                summaries.append((name, s))
        if len(summaries) < 2:
            return
        exp_name = getattr(exp, "name", str(exp))
        p95s = sorted(s.p95 for _, s in summaries)
        median = p95s[len(p95s) // 2]
        if median <= 0:
            return
        for name, s in summaries:
            if s.p95 > self.straggler_ratio * median:
                self._warn(
                    exp_name, name, "GangStraggler",
                    f"pack member p95 step time {s.p95:.4f}s exceeds gang "
                    f"median {median:.4f}s by more than "
                    f"{self.straggler_ratio:g}x",
                )

    # -- detectors -----------------------------------------------------------

    def _detect_retrace_storm(
        self, exp_name: str, trial_name: str, summary: StintSummary
    ) -> None:
        if self.metrics is not None and summary.retraces > 0:
            self.metrics.inc(
                "katib_trial_retraces_total", float(summary.retraces),
                experiment=exp_name,
            )
        if summary.retraces > self.retrace_storm_threshold:
            self._warn(
                exp_name, trial_name, "RetraceStorm",
                f"stint recompiled {summary.retraces} times past the first "
                f"compile (threshold {self.retrace_storm_threshold}); the "
                "train loop is likely shape-unstable",
            )

    def _detect_regression(
        self, exp_name: str, trial_name: str, summary: StintSummary, baseline: float
    ) -> None:
        if summary.p50 > self.regression_ratio * baseline:
            self._warn(
                exp_name, trial_name, "StepTimeRegression",
                f"resumed stint p50 step time {summary.p50:.4f}s is more "
                f"than {self.regression_ratio:g}x the trial's prior-stint "
                f"baseline {baseline:.4f}s",
            )

    def _warn(self, exp_name: str, trial_name: str, reason: str, message: str) -> None:
        if self.events is not None:
            self.events.event(
                exp_name, "Trial", trial_name, reason, message, warning=True
            )

    # -- rollups -------------------------------------------------------------

    def _absorb(
        self, exp_name: str, summary: StintSummary, mfu_value: Optional[float]
    ) -> None:
        with self._lock:
            r = self._rollups.setdefault(exp_name, _ExpRollup())
            if summary.p50 > 0:
                r.stint_p50s.append(summary.p50)
                r.stint_p95s.append(summary.p95)
            r.total_steps += summary.steps
            r.total_seconds += summary.seconds
            r.total_examples += summary.examples
            if mfu_value is not None:
                r.last_mfu = mfu_value

    def charge_device_seconds(self, exp_name: str, seconds: float) -> None:
        """Gang-release hook: accumulate device-seconds so the rollup can
        export objective-per-device-second (ROADMAP item 3c's admission
        signal; read-side only, no scheduling behavior change)."""
        if seconds <= 0:
            return
        with self._lock:
            r = self._rollups.setdefault(exp_name, _ExpRollup())
            r.device_seconds += seconds

    def note_objective(self, exp_name: str, value: float, maximize: bool) -> None:
        """Track the experiment's best objective for the per-device-second
        rollup (direction-aware: max for maximize, min for minimize)."""
        with self._lock:
            r = self._rollups.setdefault(exp_name, _ExpRollup())
            if r.best_objective is None:
                r.best_objective = value
            elif maximize:
                r.best_objective = max(r.best_objective, value)
            else:
                r.best_objective = min(r.best_objective, value)

    def forget_experiment(self, exp_name: str) -> None:
        with self._lock:
            self._rollups.pop(exp_name, None)
            self._cost_cache.pop(exp_name, None)

    def _collect(self) -> Dict:
        """Per-scrape gauge recompute (MetricsRegistry.add_collector)."""
        if self.metrics is None:
            return {}
        key = self.metrics.gauge_key
        out: Dict = {}
        with self._lock:
            items = list(self._rollups.items())
        for exp_name, r in items:
            p50s = sorted(r.stint_p50s)
            if p50s:
                out[key("katib_step_seconds", experiment=exp_name, quantile="p50")] = (
                    p50s[len(p50s) // 2]
                )
                out[key("katib_step_seconds", experiment=exp_name, quantile="p95")] = (
                    max(r.stint_p95s)
                )
            if r.total_seconds > 0:
                out[key("katib_trial_throughput", experiment=exp_name)] = (
                    r.total_steps / r.total_seconds
                )
            if r.last_mfu is not None:
                out[key("katib_trial_mfu_ratio", experiment=exp_name)] = r.last_mfu
            if r.best_objective is not None and r.device_seconds > 0:
                out[key("katib_objective_per_device_second", experiment=exp_name)] = (
                    r.best_objective / r.device_seconds
                )
        return out

    # -- MFU plumbing --------------------------------------------------------

    def _mfu_for(
        self, exp: Any, summary: StintSummary, n_devices: int
    ) -> Optional[float]:
        if summary.p50 <= 0:
            return None
        from ..analysis.costmodel import mfu

        return mfu(
            self._cost_for(exp), summary.p50, max(1, n_devices),
            device_kind=self._probe_device_kind(),
        )

    def _cost_for(self, exp: Any) -> Optional[Any]:
        """CostEstimate of one traced step for this experiment's template —
        the same static analysis the PR 7/8 compile plane runs, cached per
        experiment. None when the template has no probe (no MFU then)."""
        name = getattr(exp, "name", str(exp))
        with self._lock:
            if name in self._cost_cache:
                return self._cost_cache[name]
        cost = None
        try:
            from ..analysis.program import cached_analysis

            analysis = cached_analysis(exp.spec)
            cost = getattr(analysis, "cost", None) if analysis is not None else None
        except Exception:
            cost = None
        with self._lock:
            self._cost_cache[name] = cost
        return cost

    def _probe_device_kind(self) -> Optional[str]:
        if self._device_kind is None:
            from ..utils.backend import initialized_local_devices

            # step rows come from in-process trials, so their backend is
            # up; this reader never initializes one itself
            devs = initialized_local_devices()
            self._device_kind = devs[0].device_kind if devs else None
        return self._device_kind
