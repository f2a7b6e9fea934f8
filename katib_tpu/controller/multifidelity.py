"""Multi-fidelity search engine — ASHA/BOHB rungs as a scheduler citizen.

Upstream Katib ships successive halving only as a stateless suggestion
service (suggest/hyperband.py reproduces it exactly): every rung restarts
survivors FROM SCRATCH with a bigger budget parameter, so the
device-seconds spent on the lower rung are thrown away. This module makes
the halving native by reusing machinery the repo already owns:

- **Rungs are fidelity levels over the budget knob** (``resource_name``
  algorithm setting — epochs/examples, classified as a *host* parameter by
  the semantic analyzer), so rung changes never recompile: every rung of a
  sweep shares one dispatch-group key (analysis/program.py ignores
  host-only differences) and therefore one AOT-warmed executable in the
  compile service.
- **A rung boundary is a completion, not a restart**: a trial launched
  with ``resource=r_k`` trains to r_k (resuming its own checkpoint from
  the previous rung through the ordinary ``ctx.checkpoint_store()`` path),
  reports its objective, and is *paused* — a non-victim variant of
  checkpoint-preemption: terminal-looking (EarlyStopped/``RungPaused``) so
  it frees its parallel slot and its devices, but with the observation log
  and checkpoint intact.
- **Promotion is the PBT exploit move across fidelities**: the SAME trial
  is resubmitted with the budget knob raised to r_{k+1} and its checkpoint
  directory re-attached, so the resumed stint continues the same PRNG
  stream and observation log — the PR 2 resume-bit-identical guarantees
  apply unchanged. Non-promoted trials finalize as early-stopped
  (``RungPruned``) with their observations intact.
- **Low-fidelity rungs pack**: same-rung trials share the budget value, so
  pack formation (controller/packing.py keys open packs by the rung's
  budget) can run a whole bottom rung as one vmapped program.
- **Promotions pack too** (ISSUE 13): with
  ``runtime.promotion_dwell_seconds > 0`` same-ladder promotion decisions
  accumulate for a short dwell window and are resubmitted under ONE
  dispatch barrier, so ``plan_packs`` forms vmapped packs at rung 1+
  instead of dispatching each promotion solo. A drain rule flushes the
  buffer the moment nothing is running, so the last stragglers never wait
  out the window. 0 (the default) submits at the decision point,
  byte-identical to the PR 11 behavior.

The promotion rule is asynchronous successive halving (Li et al., ASHA): a
paused trial at rung k is promotable when it ranks in the top
``floor(|rung_k| / eta)`` of every objective recorded at rung k. Decisions
are made at each boundary (scheduler worker thread) and re-checked on
every reconcile (:meth:`MultiFidelityEngine.pump`), which also prunes the
ladder once the sweep drains.

Two algorithms ride the engine (``ENGINE_ALGORITHMS``): ``asha`` (uniform
bottom-rung sampling, PR 11) and ``bohb`` (model-based bottom-rung
sampling — suggest/bohb.py fits a per-rung TPE/KDE over the fold index).
Both support **multi-bracket Hyperband** scheduling: the ``brackets``
algorithm setting builds several ladders with staggered ``min_resource``
(bracket b starts at base rung b) that share one experiment and one
admission budget; the suggester assigns new configurations round-robin by
remaining per-bracket budget (:func:`assign_brackets`), and every bracket
rides the same pause/promote/prune machinery below. The budget knob being
a host param, all brackets still share the single AOT-warmed executable.

Gating: the engine exists only when ``runtime.multifidelity`` is on AND an
experiment declares ``algorithm: asha`` or ``algorithm: bohb``. Hyperband
specs never touch it — the legacy stateless path is preserved
byte-identically.
"""

from __future__ import annotations

import logging
import math
import os
import shutil
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..api.spec import ExperimentSpec, ObjectiveType, ParameterType
from ..api.status import Experiment, Trial, TrialCondition
from ..db.store import ObservationStore, objective_value
from ..earlystop.curves import ObjectiveCurveReader

log = logging.getLogger("katib_tpu.multifidelity")

ALGORITHM_NAME = "asha"
BOHB_ALGORITHM_NAME = "bohb"
# algorithms owned by the engine: both enter every configuration at a
# bracket's bottom rung and ride the pause/promote/prune machinery
ENGINE_ALGORITHMS = frozenset({ALGORITHM_NAME, BOHB_ALGORITHM_NAME})

# Persisted trial labels: the offline `katib-tpu rungs` view and the
# restart rebuild read them back from the state store.
RUNG_LABEL = "katib-tpu/rung"            # current rung index of the trial
PAUSED_LABEL = "katib-tpu/rung-paused"   # present while rung-paused (value: rung)
BRACKET_LABEL = "katib-tpu/bracket"      # hyperband bracket id (absent = 0)

DEFAULT_ETA = 3


@dataclass
class FidelityLadder:
    """The rung ladder of one bracket: budgets r_0 < r_1 < ... < r_top
    over the spec's ``resource_name`` parameter, geometric in ``eta`` and
    clipped to ``max_resource``."""

    resource_name: str
    eta: int
    rungs: List[float]
    integer: bool  # INT resource: budgets truncate like hyperband's

    @classmethod
    def from_spec(cls, spec: ExperimentSpec) -> "FidelityLadder":
        """Build the base (bracket-0) ladder from algorithm settings; raises
        ValueError on a malformed spec (the suggester's
        validate_algorithm_settings surfaces it)."""
        settings = spec.algorithm.settings_dict()
        resource = settings.get("resource_name", "")
        if not resource:
            raise ValueError(
                f"{spec.algorithm.algorithm_name or 'asha'} requires the "
                "resource_name setting"
            )
        param = next((p for p in spec.parameters if p.name == resource), None)
        if param is None:
            raise ValueError(
                f"resource_name {resource!r} must name an experiment parameter"
            )
        if param.parameter_type not in (ParameterType.INT, ParameterType.DOUBLE):
            raise ValueError(
                f"resource parameter {resource!r} must be int or double"
            )
        eta = int(float(settings.get("eta", DEFAULT_ETA)))
        if eta <= 1:
            raise ValueError("eta must be an integer greater than 1")
        fs = param.feasible_space
        lo_default = fs.min if fs.min not in (None, "") else "1"
        hi_default = fs.max if fs.max not in (None, "") else "0"
        min_r = float(settings.get("min_resource", lo_default))
        max_r = float(settings.get("max_resource", hi_default))
        if min_r <= 0:
            raise ValueError("min_resource must be positive")
        if max_r <= min_r:
            raise ValueError(
                f"max_resource ({max_r:g}) must exceed min_resource ({min_r:g})"
            )
        rungs = [min_r]
        while rungs[-1] < max_r:
            rungs.append(min(rungs[-1] * eta, max_r))
        integer = param.parameter_type == ParameterType.INT
        if integer:
            # dedupe after truncation (e.g. min=1, eta=2, max=3 -> 1,2,3)
            seen: List[float] = []
            for r in rungs:
                if not seen or int(r) != int(seen[-1]):
                    seen.append(float(int(r)))
            rungs = seen
        return cls(resource_name=resource, eta=eta, rungs=rungs, integer=integer)

    @property
    def top(self) -> int:
        return len(self.rungs) - 1

    def format(self, r: float) -> str:
        """Budget as the string assigned to the resource parameter (INT
        resources truncate, matching hyperband's _format_budget)."""
        return str(int(r)) if self.integer else repr(float(r))

    def rung_of(self, value: str) -> int:
        """Rung index of a budget assignment: the highest rung whose budget
        does not exceed the value (exact for engine-issued budgets; a
        tolerant floor for hand-written ones)."""
        v = float(value)
        idx = 0
        for i, r in enumerate(self.rungs):
            if v >= r - 1e-9:
                idx = i
        return idx


# -- multi-bracket geometry ----------------------------------------------------


def bracket_count(spec: ExperimentSpec) -> int:
    """The ``brackets`` algorithm setting (default 1). Validation lives in
    the suggester; consumers clamp defensively."""
    raw = spec.algorithm.settings_dict().get("brackets", "1")
    try:
        return max(int(float(raw)), 1)
    except ValueError:
        return 1


def bracket_ladders(spec: ExperimentSpec) -> List[FidelityLadder]:
    """One FidelityLadder per bracket, staggered min_resource: bracket b's
    ladder is the base ladder's rungs[b:], so its bottom rung IS base rung
    b — budgets stay the shared geometric points, and same-budget trials of
    different brackets still share one compiled program. The count is
    clamped so every bracket keeps at least two rungs."""
    base = FidelityLadder.from_spec(spec)
    b = min(bracket_count(spec), max(len(base.rungs) - 1, 1))
    return [
        FidelityLadder(
            resource_name=base.resource_name,
            eta=base.eta,
            rungs=list(base.rungs[i:]),
            integer=base.integer,
        )
        for i in range(b)
    ]


def bracket_quotas(max_trials: int, ladders: Sequence[FidelityLadder]) -> List[int]:
    """Admission split of ``maxTrialCount`` across brackets, Hyperband
    style: bracket b with s_b = top halvings weighs eta^{s_b} / (s_b + 1)
    — the cheap deep-halving bracket admits the most configurations.
    Largest-remainder rounding; every bracket gets at least one admission
    while the budget allows."""
    b = len(ladders)
    if b == 1:
        return [max_trials]
    weights = [
        (ladder.eta ** ladder.top) / (ladder.top + 1) for ladder in ladders
    ]
    total = sum(weights)
    raw = [max_trials * w / total for w in weights]
    counts = [int(r) for r in raw]
    rem = max_trials - sum(counts)
    order = sorted(range(b), key=lambda i: (-(raw[i] - counts[i]), i))
    for i in order[:rem]:
        counts[i] += 1
    for i in range(b):
        if counts[i] == 0:
            donor = counts.index(max(counts))
            if counts[donor] > 1:
                counts[donor] -= 1
                counts[i] += 1
    return counts


def assign_brackets(
    spec: ExperimentSpec,
    trials: Sequence[Trial],
    ladders: Sequence[FidelityLadder],
    n: int,
) -> List[int]:
    """Bracket id for each of ``n`` new admissions: round-robin by
    remaining per-bracket budget (quota minus already-admitted, counted
    from persisted bracket labels), ties to the lower bracket id. With one
    bracket this is a constant-0 list and the caller skips labeling."""
    if len(ladders) == 1:
        return [0] * n
    quotas = bracket_quotas(spec.max_trial_count or n, ladders)
    admitted: Dict[int, int] = {}
    for t in trials:
        b = _bracket_of(t, len(ladders))
        admitted[b] = admitted.get(b, 0) + 1
    out: List[int] = []
    for _ in range(n):
        b = max(
            range(len(ladders)),
            key=lambda i: (quotas[i] - admitted.get(i, 0), -i),
        )
        out.append(b)
        admitted[b] = admitted.get(b, 0) + 1
    return out


def _bracket_of(trial: Trial, n_brackets: int) -> int:
    try:
        b = int(trial.labels.get(BRACKET_LABEL, "0"))
    except ValueError:
        b = 0
    return min(max(b, 0), n_brackets - 1)


class _BracketRungs:
    """Rung tables of one bracket. Not self-locking: the engine's lock
    guards every mutation (caller holds it)."""

    def __init__(self, ladder: FidelityLadder):
        self.ladder = ladder
        # rung index -> {trial name: objective recorded at that boundary}
        self.scores: List[Dict[str, float]] = [dict() for _ in ladder.rungs]
        # rung index -> trials promoted OUT of that rung
        self.promoted: List[set] = [set() for _ in ladder.rungs]


class _ExperimentRungs:
    """Per-experiment state: one _BracketRungs per bracket plus the shared
    paused map. Caller holds the engine lock for every mutation."""

    def __init__(self, ladders: Sequence[FidelityLadder], maximize: bool):
        self.brackets = [_BracketRungs(ladder) for ladder in ladders]
        self.maximize = maximize
        self.paused: Dict[str, Tuple[int, int]] = {}  # name -> (bracket, rung)
        # promotions claimed (out of ``paused`` or the dwell buffer) whose
        # trials are not resubmitted yet: to every other thread such a trial
        # still reads EarlyStopped, i.e. terminal
        self.submitting = 0
        self.done = False


class MultiFidelityEngine:
    """Scheduler-citizen ASHA/BOHB: owns rung records, pause/promote/prune
    per bracket, and the dwell-window promotion buffer.

    Thread model: :meth:`on_rung_boundary` runs on scheduler worker
    threads, :meth:`pump` on the reconcile thread, dwell flushes on either
    plus a wake timer. The engine lock guards its tables only — it is
    never held across scheduler calls (submit / _record_terminal), so the
    only cross-subsystem lock edge is engine -> scheduler."""

    def __init__(
        self,
        state,
        obs_store: ObservationStore,
        events=None,
        metrics=None,
        dwell_seconds: float = 0.0,
        journal=None,
    ):
        self.state = state
        self.obs_store = obs_store
        self.events = events
        self.metrics = metrics
        # recovery journal (controller/recovery.py): promotion batches are
        # journaled before resubmission so the controller-kill chaos grammar
        # has a deterministic kill point at the promotion seam; None = off
        self.journal = journal
        self.dwell_seconds = max(float(dwell_seconds or 0.0), 0.0)
        self._lock = threading.Lock()
        # signalled when an experiment's ``submitting`` count returns to zero
        self._settled = threading.Condition(self._lock)
        self._exps: Dict[str, _ExperimentRungs] = {}
        # dwell buffer: experiment -> [(enqueued_at, name, bracket, rung)]
        self._pending: Dict[str, List[Tuple[float, str, int, int]]] = {}
        self._timers: Dict[str, threading.Timer] = {}

    # -- applicability -------------------------------------------------------

    @staticmethod
    def applies(spec: ExperimentSpec) -> bool:
        return spec.algorithm.algorithm_name in ENGINE_ALGORITHMS

    def _entry(self, exp: Experiment) -> _ExperimentRungs:
        """Get-or-build the experiment's rung tables, rebuilding from
        persisted trial labels + the fold index after a controller restart.
        Must be called WITHOUT the engine lock held (reads the store)."""
        with self._lock:
            st = self._exps.get(exp.name)
        if st is not None:
            return st
        ladders = bracket_ladders(exp.spec)
        maximize = exp.spec.objective.type == ObjectiveType.MAXIMIZE
        st = _ExperimentRungs(ladders, maximize)
        reader = ObjectiveCurveReader(self.obs_store, exp.spec.objective)
        for t in self.state.list_trials(exp.name):
            rung_lbl = t.labels.get(RUNG_LABEL)
            if rung_lbl is None:
                continue
            try:
                k = int(rung_lbl)
            except ValueError:
                continue
            b = _bracket_of(t, len(st.brackets))
            br = st.brackets[b]
            k = min(max(k, 0), br.ladder.top)
            score = reader.boundary_value(t.name)
            if (
                PAUSED_LABEL in t.labels
                and t.condition == TrialCondition.EARLY_STOPPED
                and score is not None
            ):
                br.scores[k][t.name] = score
                st.paused[t.name] = (b, k)
            else:
                # a trial past its bracket's rung 0 was promoted through
                # every lower rung; its per-rung boundary scores are gone,
                # so the rebuild backfills the current folded objective —
                # enough to keep rung sizes and promotion counts consistent
                for j in range(k):
                    if score is not None:
                        br.scores[j].setdefault(t.name, score)
                    br.promoted[j].add(t.name)
                if score is not None and (
                    t.condition == TrialCondition.EARLY_STOPPED or k == br.ladder.top
                ):
                    br.scores[k].setdefault(t.name, score)
        with self._lock:
            return self._exps.setdefault(exp.name, st)

    # -- rung boundary (scheduler worker thread) -----------------------------

    def on_rung_boundary(self, exp: Experiment, trial: Trial, observation, scheduler) -> bool:
        """Consulted by the scheduler when a trial COMPLETED its assigned
        budget. Returns True when the trial was paused at a rung boundary
        (the scheduler then skips normal finalization); False hands the
        trial back to the ordinary Succeeded path (non-engine experiment,
        top-of-ladder completion, or no usable objective)."""
        spec = exp.spec
        if not self.applies(spec):
            return False
        try:
            st = self._entry(exp)
        except Exception:
            log.debug("rung table unavailable for %s", exp.name, exc_info=True)
            return False
        b = _bracket_of(trial, len(st.brackets))
        ladder = st.brackets[b].ladder
        value = trial.assignments_dict().get(ladder.resource_name)
        if value is None:
            return False
        try:
            k = ladder.rung_of(value)
        except ValueError:
            return False
        score = objective_value(observation, spec.objective)
        if score is None or math.isnan(score):
            return False  # MetricsUnavailable classification handles it
        with self._lock:
            if st.done:
                return False
            st.brackets[b].scores[k][trial.name] = score
            if k >= ladder.top:
                # final fidelity: record for the rung view, finalize normally
                st.paused.pop(trial.name, None)
            else:
                st.paused[trial.name] = (b, k)
        self._note_bracket_gauge(exp.name, st)
        if k >= ladder.top:
            trial.labels[RUNG_LABEL] = str(k)
            return False
        # Pause: the non-victim variant of checkpoint-preemption — the trial
        # leaves the device pool terminal-looking (EarlyStopped) but keeps
        # its observation log and checkpoint; a later promotion resubmits it.
        trial.labels[PAUSED_LABEL] = str(k)
        trial.labels[RUNG_LABEL] = str(k)
        trial.set_condition(
            TrialCondition.EARLY_STOPPED,
            "RungPaused",
            f"paused at rung {k} ({ladder.resource_name}="
            f"{ladder.format(ladder.rungs[k])}) awaiting promotion decision"
            + self._bracket_tag(st, b),
        )
        scheduler._record_terminal(exp, trial)
        self._maybe_promote(exp, scheduler)
        return True

    @staticmethod
    def _bracket_tag(st: _ExperimentRungs, b: int) -> str:
        """Bracket suffix for rung events — empty for single-bracket sweeps
        so PR 11 message text stays byte-identical."""
        return f" [bracket {b}]" if len(st.brackets) > 1 else ""

    def _note_bracket_gauge(self, exp_name: str, st: _ExperimentRungs) -> None:
        """katib_bracket_active: brackets that still hold paused or
        dwell-pending members (0 once the ladder drains)."""
        if self.metrics is None:
            return
        with self._lock:
            if st.done:
                live = 0
            else:
                active = {b for b, _ in st.paused.values()}
                active.update(
                    b for _, _, b, _ in self._pending.get(exp_name, ())
                )
                live = len(active)
        self.metrics.set_gauge(
            "katib_bracket_active", float(live), experiment=exp_name
        )

    # -- promotion -----------------------------------------------------------

    def _eligible_locked(self, st: _ExperimentRungs) -> List[Tuple[str, int, int]]:
        """ASHA candidates as (name, bracket, rung), highest rung first
        within each bracket: a paused trial at rung k is promotable while
        it ranks in the top floor(|rung_k| / eta) of every score recorded
        at rung k of its bracket. Caller holds the engine lock."""
        out: List[Tuple[str, int, int]] = []
        for b, br in enumerate(st.brackets):
            for k in range(br.ladder.top - 1, -1, -1):
                records = br.scores[k]
                if not records:
                    continue
                # total promotions out of rung k are capped at the quota:
                # async decisions on a growing rung would otherwise promote
                # every config that was EVER inside the top fraction
                n_promotable = len(records) // br.ladder.eta
                quota_left = n_promotable - len(br.promoted[k])
                if quota_left <= 0:
                    continue
                ranked = sorted(
                    records.items(),
                    key=(
                        (lambda kv: (-kv[1], kv[0]))
                        if st.maximize
                        else (lambda kv: (kv[1], kv[0]))
                    ),
                )
                for name, _ in ranked[:n_promotable]:
                    if quota_left <= 0:
                        break
                    if name in br.promoted[k]:
                        continue
                    if st.paused.get(name) != (b, k):
                        continue  # killed during pause, or still running
                    out.append((name, b, k))
                    quota_left -= 1
        return out

    def _maybe_promote(self, exp: Experiment, scheduler) -> bool:
        """Promote every currently-eligible paused trial. Candidates are
        claimed under the lock (concurrent boundary threads cannot
        double-promote). With no dwell window they submit immediately,
        batched under the scheduler's dispatch barrier; with one, they
        accumulate in the pending buffer until the window expires, the
        sweep goes quiet (drain rule), or the wake timer fires."""
        with self._lock:
            st = self._exps.get(exp.name)
            if st is None or st.done:
                return False
            candidates = self._eligible_locked(st)
            for name, b, k in candidates:
                st.brackets[b].promoted[k].add(name)
                st.paused.pop(name, None)
            if self.dwell_seconds <= 0:
                st.submitting += len(candidates)
        if not candidates:
            if self.dwell_seconds > 0:
                return self._flush_if_due(exp, scheduler)
            return False
        if self.dwell_seconds <= 0:
            return self._submit_batch(exp, st, candidates, scheduler, dwelled=False)
        now = time.time()
        with self._lock:
            self._pending.setdefault(exp.name, []).extend(
                (now, name, b, k) for name, b, k in candidates
            )
        self._note_bracket_gauge(exp.name, st)
        if self._sweep_drained(exp):
            # drain rule: nothing is running AND the admission budget is
            # exhausted, so no same-rung peer can ever join the batch —
            # flushing now beats making the last stragglers wait out the
            # window. A merely-momentary quiet gap (more admissions coming)
            # does NOT flush: the wake timer bounds that wait instead, so a
            # mid-sweep lull cannot split a formable pack.
            self._flush_pending(exp, scheduler)
        else:
            self._arm_timer(exp, scheduler)
        return True

    def _sweep_drained(self, exp: Experiment) -> bool:
        trials = self.state.list_trials(exp.name)
        if any(not t.is_terminal for t in trials):
            return False
        maxt = exp.spec.max_trial_count
        return maxt is None or len(trials) >= maxt

    def _arm_timer(self, exp: Experiment, scheduler) -> None:
        """One wake timer per experiment batch so an expired dwell window
        flushes even if no reconcile or boundary fires meanwhile."""
        with self._lock:
            if exp.name in self._timers:
                return
            batch = self._pending.get(exp.name)
            if not batch:
                return
            delay = max(self.dwell_seconds - (time.time() - batch[0][0]), 0.01)
            timer = threading.Timer(
                delay, self._timer_flush, args=(exp.name, scheduler)
            )
            timer.daemon = True
            self._timers[exp.name] = timer
        timer.start()

    def _timer_flush(self, exp_name: str, scheduler) -> None:
        with self._lock:
            self._timers.pop(exp_name, None)
        if getattr(scheduler, "_shutdown", None) is not None and scheduler._shutdown.is_set():
            return
        exp = self.state.get_experiment(exp_name)
        if exp is not None:
            self._flush_pending(exp, scheduler)

    def _flush_if_due(self, exp: Experiment, scheduler) -> bool:
        """Reconcile-side dwell check: flush when the oldest pending
        promotion has waited out the window or the sweep has drained."""
        with self._lock:
            batch = list(self._pending.get(exp.name, ()))
        if not batch:
            return False
        due = time.time() - batch[0][0] >= self.dwell_seconds
        if due or self._sweep_drained(exp):
            return self._flush_pending(exp, scheduler)
        self._arm_timer(exp, scheduler)
        return False

    def _flush_pending(self, exp: Experiment, scheduler) -> bool:
        """Resubmit the whole pending buffer as ONE batch under the
        dispatch barrier, so pack formation sees every same-rung promotion
        together and rung 1+ dispatches as vmapped packs."""
        with self._lock:
            batch = self._pending.pop(exp.name, [])
            timer = self._timers.pop(exp.name, None)
            st = self._exps.get(exp.name)
            if st is not None:
                st.submitting += len(batch)
        if timer is not None:
            timer.cancel()
        if not batch or st is None:
            return False
        candidates = [(name, b, k) for _, name, b, k in batch]
        if self.metrics is not None:
            self.metrics.set_gauge(
                "katib_promotion_pack_size", float(len(candidates)),
                experiment=exp.name,
            )
        if self.events is not None:
            self.events.event(
                exp.name, "Experiment", exp.name, "PromotionBatched",
                f"resubmitting {len(candidates)} dwell-batched promotion(s) "
                f"under one dispatch barrier "
                f"({', '.join(name for name, _, _ in candidates)})",
            )
        return self._submit_batch(exp, st, candidates, scheduler, dwelled=True)

    def _submit_batch(
        self,
        exp: Experiment,
        st: _ExperimentRungs,
        candidates: Sequence[Tuple[str, int, int]],
        scheduler,
        dwelled: bool,
    ) -> bool:
        """Resubmit claimed promotions. The caller counted them into
        ``st.submitting`` when it claimed them; they are counted out here,
        whatever happens, once every one of them has been handed to the
        scheduler (or given back)."""
        promoted_any = False
        try:
            if self.journal is not None and candidates:
                # intent before action: a crash inside the barrier below
                # leaves the claimed candidates visible to `katib-tpu
                # recover`, and the label rebuild re-derives their paused
                # state on restart
                self.journal.append(
                    "promote", exp.name,
                    trials=[name for name, _, _ in candidates],
                )
            with scheduler.dispatch_barrier():
                for name, b, k in candidates:
                    try:
                        if self._promote_one(
                            exp, name, b, k, st.brackets[b].ladder, scheduler, st
                        ):
                            promoted_any = True
                    except Exception:
                        log.warning(
                            "promotion of trial %s failed", name, exc_info=True
                        )
        finally:
            with self._settled:
                st.submitting -= len(candidates)
                if st.submitting <= 0:
                    self._settled.notify_all()
        return promoted_any or dwelled

    def _trial_checkpoint_dir(self, exp: Experiment, trial: Trial, scheduler) -> Optional[str]:
        """Where the trial's previous stint checkpointed: engine trials
        carry no suggester-provided lineage dir, so ctx.checkpoint_store()
        rooted at the per-trial workdir — stable across stints of the same
        trial name, which is exactly what makes the promotion resume work."""
        root = getattr(scheduler, "workdir_root", None)
        if not root:
            return None
        return os.path.join(root, exp.name, trial.name)

    def _checkpoint_restorable(self, ck_dir: Optional[str]) -> bool:
        """True when the paused stint left a loadable checkpoint at the
        store root. A missing or corrupt checkpoint demotes the promotion
        to a re-run-from-scratch (observation log dropped so the fold never
        mixes two executions)."""
        if not ck_dir or not os.path.isdir(ck_dir):
            return False
        from ..runtime.checkpoints import CheckpointStore

        # two attempts: orbax manager construction can transiently fail when
        # probes interleave with other trials' checkpoint traffic in the same
        # process; genuine corruption fails deterministically on both
        for attempt in (0, 1):
            try:
                store = CheckpointStore(ck_dir)
                step = store.latest_step()
                if step is None:
                    return False
                return store.restore(step=step) is not None
            except Exception:
                if attempt == 0:
                    time.sleep(0.05)
                    continue
                log.warning(
                    "checkpoint under %s is unreadable; promoting from scratch",
                    ck_dir, exc_info=True,
                )
        return False

    def _promote_one(
        self,
        exp: Experiment,
        name: str,
        bracket: int,
        k: int,
        ladder: FidelityLadder,
        scheduler,
        st: Optional[_ExperimentRungs] = None,
    ) -> bool:
        trial = self.state.get_trial(exp.name, name)
        if trial is None:
            return False
        if trial.condition != TrialCondition.EARLY_STOPPED or PAUSED_LABEL not in trial.labels:
            if st is not None and not trial.is_terminal:
                # Mid-transition race: on_rung_boundary registers the pause
                # (under the engine lock) BEFORE it persists the
                # EarlyStopped/RungPaused condition, so a concurrent claimer
                # can reach here while the trial still reads Running.
                # Consuming the claim would lose the promotion forever (the
                # trial ends the sweep stuck RungPaused, outside both the
                # paused map and the prune walk) — un-claim instead so the
                # next boundary/pump retries once the transition lands.
                with self._lock:
                    st.brackets[bracket].promoted[k].discard(name)
                    st.paused[name] = (bracket, k)
                return False
            return False  # killed during pause, or already resumed elsewhere
        next_budget = ladder.format(ladder.rungs[k + 1])
        for a in trial.parameter_assignments:
            if a.name == ladder.resource_name:
                a.value = next_budget
        trial.labels.pop(PAUSED_LABEL, None)
        trial.labels[RUNG_LABEL] = str(k + 1)
        ck_dir = self._trial_checkpoint_dir(exp, trial, scheduler)
        fresh = not self._checkpoint_restorable(ck_dir)
        if fresh:
            # re-run-from-scratch fallback: clear the unusable checkpoint so
            # the trial's restore() finds nothing instead of crashing, and
            # drop the prior stint's rows — the same log-can't-mix-two-
            # executions invariant restart requeues enforce
            if ck_dir:
                shutil.rmtree(ck_dir, ignore_errors=True)
            self.obs_store.delete_observation_log(name)
            ck_dir = None
            # promoted trials never serve as duplicate-reuse sources even
            # without a checkpoint_dir marker (their metrics span rungs)
            trial.labels[scheduler.LINEAGE_LABEL] = "1"
        if self.metrics is not None:
            self.metrics.inc("katib_rung_promotions_total", experiment=exp.name)
        if self.events is not None:
            tag = "" if st is None else self._bracket_tag(st, bracket)
            self.events.event(
                exp.name, "Trial", name, "RungPromoted",
                f"promoted from rung {k} to rung {k + 1} "
                f"({ladder.resource_name}={next_budget})"
                + (
                    "; checkpoint missing or unusable, re-running from scratch"
                    if fresh
                    else ", resuming from checkpoint"
                )
                + tag,
            )
        scheduler.submit(exp, trial, checkpoint_dir=ck_dir)
        return True

    # -- reconcile pump / drain ----------------------------------------------

    def pump(self, exp: Experiment, trials: Sequence[Trial], scheduler) -> bool:
        """One reconcile-side pass: promote newly-eligible paused trials
        (they become active again BEFORE status aggregation can declare the
        experiment complete); once the sweep has drained — every trial
        terminal, the admission budget exhausted, nothing left to promote
        or flush — prune the leftover paused trials and close the ladder.
        Returns True when any trial changed state."""
        if not self.applies(exp.spec):
            return False
        try:
            st = self._entry(exp)
        except Exception:
            return False
        with self._lock:
            if st.done:
                return False
        if self._maybe_promote(exp, scheduler):
            return True
        with self._settled:
            if st.submitting > 0:
                # Another thread (a rung boundary, the dwell timer) claimed
                # promotions and is between the claim and the resubmission —
                # it may be waiting for a checkpoint to be read. Their trials
                # still read terminal in ``trials``, so neither the drain
                # check below nor the caller's status aggregation may run on
                # that list: wait the resubmission out and have the caller
                # list the trials again.
                self._settled.wait_for(lambda: st.submitting <= 0, timeout=60.0)
                return True
        if any(not t.is_terminal for t in trials):
            return False
        with self._lock:
            pending = bool(self._pending.get(exp.name))
        if pending:
            if self._sweep_drained(exp):
                # drain rule: nothing is running and nothing more will be
                # admitted — flush immediately instead of waiting the window
                return self._flush_pending(exp, scheduler)
            return False  # more admissions coming; the wake timer bounds it
        maxt = exp.spec.max_trial_count
        if maxt is not None and len(trials) < maxt:
            return False  # the suggester still has configurations to admit
        return self._prune_leftovers(exp, st)

    def finalize(self, exp: Experiment) -> None:
        """Completion hook (goal reached / budget exhausted): cancel any
        dwell batch — its trials return to the paused set — then prune
        everything still rung-paused so nothing lingers awaiting a
        promotion that will never come."""
        if not self.applies(exp.spec):
            return
        with self._lock:
            st = self._exps.get(exp.name)
            batch = self._pending.pop(exp.name, [])
            timer = self._timers.pop(exp.name, None)
            if st is not None:
                for _, name, b, k in batch:
                    # un-claim: the promotion never happened, so the trial
                    # prunes like any other leftover and the promoted
                    # counts stay truthful
                    st.brackets[b].promoted[k].discard(name)
                    st.paused[name] = (b, k)
        if timer is not None:
            timer.cancel()
        if st is not None:
            self._prune_leftovers(exp, st)

    def _prune_leftovers(self, exp: Experiment, st: _ExperimentRungs) -> bool:
        with self._lock:
            leftovers = sorted(st.paused.items())
            st.paused.clear()
            st.done = True
        pruned = False
        for name, (b, k) in leftovers:
            trial = self.state.get_trial(exp.name, name)
            if trial is None or trial.condition != TrialCondition.EARLY_STOPPED:
                continue
            eta = st.brackets[b].ladder.eta
            tag = self._bracket_tag(st, b)
            trial.labels.pop(PAUSED_LABEL, None)
            trial.set_condition(
                TrialCondition.EARLY_STOPPED,
                "RungPruned",
                f"pruned at rung {k}: outside the top 1/{eta} "
                f"of its rung (observations retained){tag}",
            )
            self.state.update_trial(trial)
            pruned = True
            if self.metrics is not None:
                self.metrics.inc("katib_rung_pruned_total", experiment=exp.name)
            if self.events is not None:
                self.events.event(
                    exp.name, "Trial", name, "RungPruned",
                    f"pruned at rung {k}: outside the top 1/{eta} "
                    f"of its rung{tag}",
                )
        self._note_bracket_gauge(exp.name, st)
        return pruned

    # -- kill-during-pause ---------------------------------------------------

    def kill_paused(self, trial_name: str, scheduler) -> bool:
        """scheduler.kill() hook for trials that are neither queued nor
        running: a rung-paused (or dwell-pending) trial is killed in place
        and permanently removed from its rung's promotion candidates (its
        recorded score still informs the cut for its peers)."""
        exp_name = None
        with self._lock:
            for name, st in self._exps.items():
                if trial_name in st.paused:
                    st.paused.pop(trial_name, None)
                    exp_name = name
                    break
                batch = self._pending.get(name, [])
                kept = [e for e in batch if e[1] != trial_name]
                if len(kept) != len(batch):
                    self._pending[name] = kept
                    exp_name = name
                    break
        if exp_name is None:
            return False
        exp = self.state.get_experiment(exp_name)
        trial = self.state.get_trial(exp_name, trial_name)
        if exp is None or trial is None:
            return False
        trial.labels.pop(PAUSED_LABEL, None)
        trial.set_condition(
            TrialCondition.KILLED, "TrialKilled", "killed while rung-paused"
        )
        self.state.update_trial(trial)
        if self.events is not None:
            self.events.event(
                exp_name, "Trial", trial_name, "TrialKilled",
                "killed while rung-paused",
            )
        from .scheduler import TrialEvent

        scheduler.events.put(TrialEvent(exp_name, trial_name, trial.condition))
        return True

    def forget(self, experiment_name: str) -> None:
        with self._lock:
            self._exps.pop(experiment_name, None)
            self._pending.pop(experiment_name, None)
            timer = self._timers.pop(experiment_name, None)
        if timer is not None:
            timer.cancel()


def pack_rung_key(spec: ExperimentSpec, trial: Trial) -> Optional[str]:
    """Budget value of a multi-fidelity trial, or None for every other
    experiment. Pack formation (controller/packing.py) adds this to the
    open-pack key so members of different rungs never share a vmapped
    program even when semantic analysis has no opinion (no probe): the
    fidelity knob is a host loop count and must be uniform across a pack.
    Brackets share budgets (staggered ladders over the same geometric
    points), so same-budget trials of different brackets still pack."""
    if spec.algorithm.algorithm_name not in ENGINE_ALGORITHMS:
        return None
    resource = spec.algorithm.settings_dict().get("resource_name")
    if not resource:
        return None
    return trial.assignments_dict().get(resource)


def ladder_report(
    spec: ExperimentSpec, trials: Sequence[Trial], store: ObservationStore
) -> Dict[str, Any]:
    """Offline ladder snapshot for `katib-tpu rungs` (and tests): per-
    bracket rung populations, promotions, prunes and per-rung best
    objective, rebuilt purely from persisted trial records + the
    observation store. The legacy top-level ``rungs`` list is bracket 0's
    view (identical to the whole report for single-bracket sweeps);
    ``brackets`` carries every bracket's section."""
    ladders = bracket_ladders(spec)
    maximize = spec.objective.type == ObjectiveType.MAXIMIZE
    reader = ObjectiveCurveReader(store, spec.objective)
    brackets_out: List[Dict[str, Any]] = []
    for b, ladder in enumerate(ladders):
        brackets_out.append(
            {
                "bracket": b,
                "min_resource": ladder.format(ladder.rungs[0]),
                "max_resource": ladder.format(ladder.rungs[-1]),
                "n_rungs": len(ladder.rungs),
                "rungs": [
                    {
                        "rung": k,
                        "budget": ladder.format(r),
                        "population": 0,
                        "running": 0,
                        "paused": 0,
                        "promoted": 0,
                        "pruned": 0,
                        "succeeded": 0,
                        "best": None,
                    }
                    for k, r in enumerate(ladder.rungs)
                ],
            }
        )

    def _rung_index(t: Trial, ladder: FidelityLadder) -> Optional[int]:
        lbl = t.labels.get(RUNG_LABEL)
        if lbl is not None:
            try:
                return min(max(int(lbl), 0), ladder.top)
            except ValueError:
                pass
        value = t.assignments_dict().get(ladder.resource_name)
        if value is None:
            return None
        try:
            return ladder.rung_of(value)
        except ValueError:
            return None

    for t in trials:
        b = _bracket_of(t, len(ladders))
        ladder = ladders[b]
        k = _rung_index(t, ladder)
        if k is None:
            continue
        rungs = brackets_out[b]["rungs"]
        # a trial at rung k passed through (and was promoted out of) every
        # lower rung of its bracket, so it counts toward each rung it
        # trained at
        for j in range(k):
            rungs[j]["population"] += 1
            rungs[j]["promoted"] += 1
        row = rungs[k]
        row["population"] += 1
        if not t.is_terminal:
            row["running"] += 1
        elif t.condition == TrialCondition.SUCCEEDED:
            row["succeeded"] += 1
        elif t.condition == TrialCondition.EARLY_STOPPED:
            if PAUSED_LABEL in t.labels:
                row["paused"] += 1
            else:
                row["pruned"] += 1
        score = reader.boundary_value(t.name)
        if score is not None:
            best = row["best"]
            if best is None or (score > best if maximize else score < best):
                row["best"] = score
    return {
        "experiment": spec.name,
        "resource": ladders[0].resource_name,
        "eta": ladders[0].eta,
        "n_brackets": len(ladders),
        "brackets": brackets_out,
        "rungs": brackets_out[0]["rungs"],
    }
