"""Trial packing — run K compatible HPO trials as one compiled program.

The scheduler half of vmapped trial packing (the runtime half is
katib_tpu.runtime.packed): pack *formation* rules decide which pending
trials may share a device allocation and a compiled train loop, and the
:class:`PackedTrialExecutor` runs a formed pack to completion, producing one
independent :class:`ExecutionResult` per member.

Packability (docs/trial-packing.md):

- the trial template is in-process (``function`` or ``entry_point`` — a
  subprocess/command trial has nothing to vmap) and single-host;
- the experiment opted in (``resources.pack_size > 1``) or the resolved
  trial function declares ``supports_packing = True`` (auto-detection, pack
  size then defaults to :data:`AUTO_PACK_SIZE`);
- every parameter assignment is a runtime scalar (parses as float) — a
  categorical parameter cannot be stacked into the vmapped population;
- members come from the same experiment/template AND the same compile
  fingerprint group: plan_packs keys open packs by (experiment name,
  stable template digest, semantic fingerprint group). The digest replaces
  the old ``id(template)`` key (``id()`` reuse after GC could merge
  distinct templates); the fingerprint group (analysis/program.py) keeps
  members whose *shape-affecting* parameters differ — mismatched avals,
  so no shared executable — in separate packs, upgrading the old "all
  params are floats" heuristic to real program equality. When semantic
  analysis is off or the template has no probe, the digest alone keys the
  pack and behavior matches the old heuristic exactly.

Fallback is strict: a trial that fails any check runs through the existing
``InProcessExecutor`` unchanged, and a *member* failure (ctx.fail_member,
per-member kill, early-stop) fails/finalizes only that member. Only an
exception escaping the pack function itself — one shared program, so there
is genuinely no per-member blame to assign — fails every still-active
member.
"""

from __future__ import annotations

import traceback
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..api.status import Experiment, Trial
from ..db.store import ObservationStore
from ..runtime.metrics import (
    EarlyStopped,
    TrialKilled,
    TrialPreempted,
    set_current_reporter,
)
from ..runtime.packed import PackedTrialContext, PackFrozen
from .executor import (
    ExecutionResult,
    TrialExecution,
    TrialOutcome,
    resolve_entry_point,
)

# Pack size used when packability is auto-detected (supports_packing on the
# trial function) but the spec left resources.pack_size at 1.
AUTO_PACK_SIZE = 8

# Label stamped on every packed member: pack id + occupancy, so the UI and
# postmortems can tell which trials shared a program.
PACK_LABEL = "katib-tpu/pack"


def _resolved_function(exp: Experiment):
    """The in-process callable this template runs, or None (command
    template, or an entry point that fails to import — the latter will fail
    loudly in the normal executor path, not here)."""
    template = exp.spec.trial_template
    if template.command is not None:
        return None
    try:
        return resolve_entry_point(template)
    except Exception:
        return None


def pack_capacity(exp: Experiment) -> int:
    """Effective pack size K for this experiment: a fused population sweep
    packs its whole K-member population into one unit; otherwise the spec
    opt-in wins; otherwise auto-detected packability (supports_packing on
    the trial function) packs at AUTO_PACK_SIZE; else 1 (no packing)."""
    res = exp.spec.trial_template.resources
    if res.num_hosts > 1:
        return 1
    from ..runtime import population as pop

    if pop.fused_applicable(exp.spec) is None:
        try:
            return max(pop.build_program(exp.spec).n_population, 1)
        except Exception:
            pass  # program construction failures surface in the executor
    if res.pack_size > 1:
        return res.pack_size
    fn = _resolved_function(exp)
    if fn is not None and getattr(fn, "supports_packing", False):
        return AUTO_PACK_SIZE
    return 1


def unpackable_reason(exp: Experiment, trial: Trial) -> Optional[str]:
    """None when this trial may join a pack, else a human-readable reason —
    the strict-fallback predicate. Checked per trial because packability
    depends on the *assignments* (stackable scalars), not just the
    template. Program-equality across members is NOT checked here: that is
    plan_packs' fingerprint-group key, which splits shape-affecting value
    groups into separate packs instead of rejecting them."""
    template = exp.spec.trial_template
    if template.command is not None:
        return "command templates run as subprocesses"
    if template.resources.num_hosts > 1:
        return "multi-host trials form their own gang"
    if pack_capacity(exp) <= 1:
        return "experiment did not opt into packing"
    for a in trial.parameter_assignments:
        try:
            float(a.value)
        except (TypeError, ValueError):
            return (
                f"parameter {a.name}={a.value!r} is not a runtime scalar"
            )
    return None


def plan_packs(
    waiting: Sequence[Tuple[Experiment, Trial]],
    warm=None,
) -> List[Tuple[Experiment, List[Trial]]]:
    """Group the waiting queue into dispatch units, preserving order.

    Returns ``[(exp, [trial, ...]), ...]`` where a singleton list is a solo
    dispatch (normal executor) and a longer list is a pack. Members are
    grouped by (experiment name, stable template digest, fingerprint
    group) — mixed templates never pack, and members whose shape-affecting
    parameters differ (distinct compiled programs) never share a pack —
    capped at the experiment's pack capacity K.

    ``warm`` (ISSUE 8): optional ``warm(exp, trial) -> bool`` predicate
    from the AOT compile service. When given, units whose dispatch group
    already has a warm executable are emitted ahead of cold units (stable
    within each side), so pack formation prefers gangs that can start
    without compiling. ``warm=None`` (service disabled) leaves the unit
    order byte-identical to the legacy walk."""
    from ..analysis import program as semantic
    from .multifidelity import pack_rung_key

    units: List[Tuple[Experiment, List[Trial]]] = []
    open_packs: Dict[Tuple, Tuple[int, int]] = {}  # key -> (unit idx, K)
    digests: Dict[str, str] = {}  # experiment -> template digest (one/pass)
    for exp, trial in waiting:
        digest = digests.get(exp.name)
        if digest is None:
            digest = semantic.template_digest(exp.spec.trial_template)
            digests[exp.name] = digest
        try:
            group = semantic.pack_group_key(exp.spec, trial)
        except Exception:
            group = None  # analysis is advisory; formation must not break
        # multi-fidelity rungs never mix in a pack: the budget knob is a
        # host loop count that must be uniform across the vmapped program,
        # even when semantic analysis has no opinion (no probe). None for
        # every non-asha experiment, so legacy keys are unchanged.
        key = (exp.name, digest, group, pack_rung_key(exp.spec, trial))
        if unpackable_reason(exp, trial) is not None:
            units.append((exp, [trial]))
            continue
        k = pack_capacity(exp)
        slot = open_packs.get(key)
        if slot is not None and len(units[slot[0]][1]) < slot[1]:
            units[slot[0]][1].append(trial)
            continue
        units.append((exp, [trial]))
        open_packs[key] = (len(units) - 1, k)
    if warm is not None and len(units) > 1:
        flags = []
        for exp, members in units:
            try:
                flags.append(bool(warm(exp, members[0])))
            except Exception:
                flags.append(False)  # advisory: warmth must not break packs
        if any(flags) and not all(flags):
            units = [u for u, f in zip(units, flags) if f] + [
                u for u, f in zip(units, flags) if not f
            ]
    return units


def stack_assignments(trials: Sequence[Trial]) -> Dict[str, np.ndarray]:
    """Stack K members' scalar assignments into ``{name: float32 [K]}``.
    Members may have different parameter *sets* only if a name is missing
    everywhere or present everywhere (same search space ⇒ always true)."""
    names: List[str] = []
    for t in trials:
        for a in t.parameter_assignments:
            if a.name not in names:
                names.append(a.name)
    out: Dict[str, np.ndarray] = {}
    for name in names:
        col = []
        for t in trials:
            value = t.assignments_dict().get(name)
            if value is None:
                raise ValueError(
                    f"pack member {t.name} is missing parameter {name!r}"
                )
            col.append(float(value))
        out[name] = np.asarray(col, dtype=np.float32)
    return out


class PackedTrialExecutor:
    """Run one formed pack: a single call of the pack-aware trial function
    over the stacked population, then per-member outcome derivation from the
    context's masking state."""

    def __init__(self, obs_store: ObservationStore):
        self.obs_store = obs_store
        self._cache_enabled = False

    def execute(
        self,
        exp: Experiment,
        trials: Sequence[Trial],
        ctx: PackedTrialContext,
        handles: Sequence[TrialExecution],
    ) -> List[ExecutionResult]:
        if not self._cache_enabled:
            self._cache_enabled = True
            try:
                from ..utils.compilation import enable_compilation_cache

                enable_compilation_cache()
            except Exception:
                pass
        fn = resolve_entry_point(exp.spec.trial_template)
        pack_error: Optional[str] = None
        # no contextvar reporter: report_metrics() inside a pack-aware fn
        # would have no member to demux to — the fn must go through ctx
        token = set_current_reporter(None)
        ctx._trace_fn_start()  # compile boundary in the gang trace
        try:
            result = fn(ctx.assignments, ctx)
            if isinstance(result, dict):
                numeric = {
                    k: v
                    for k, v in result.items()
                    if isinstance(v, (int, float, np.ndarray))
                }
                if numeric:
                    ctx.report(**numeric)
        except (PackFrozen, EarlyStopped, TrialKilled, TrialPreempted):
            pass  # every member already carries its own terminal mask
        except Exception:
            # one shared compiled program: an escaping exception has no
            # per-member blame, so every still-ACTIVE member fails; members
            # already frozen (stopped/killed/failed earlier) keep their own
            # outcome — a member failure never fails the pack, but a pack
            # failure necessarily fails its survivors
            pack_error = traceback.format_exc(limit=10)
        finally:
            ctx._trace_fn_end()
            from ..runtime import metrics as _m

            _m._current_reporter.reset(token)

        results: List[ExecutionResult] = []
        for i, (stopped, killed, failed, fail_msg, preempted) in enumerate(
            ctx.member_outcomes()
        ):
            if failed:
                results.append(
                    ExecutionResult(TrialOutcome.FAILED, fail_msg, exit_code=1)
                )
            elif killed:
                results.append(
                    ExecutionResult(TrialOutcome.KILLED, "kill requested")
                )
            elif preempted:
                results.append(
                    ExecutionResult(
                        TrialOutcome.PREEMPTED,
                        "preempted by higher-priority work",
                    )
                )
            elif stopped:
                results.append(ExecutionResult(TrialOutcome.EARLY_STOPPED))
            elif pack_error is not None:
                results.append(
                    ExecutionResult(TrialOutcome.FAILED, pack_error, exit_code=1)
                )
            elif handles[i].kill_requested:
                results.append(
                    ExecutionResult(TrialOutcome.KILLED, "kill requested")
                )
            else:
                results.append(
                    ExecutionResult(TrialOutcome.COMPLETED, exit_code=0)
                )
        return results


def _member_results(
    ctx: PackedTrialContext,
    handles: Sequence[TrialExecution],
    pack_error: Optional[str],
) -> List[ExecutionResult]:
    """Per-member ExecutionResults from the context's terminal masking
    state — shared by PackedTrialExecutor and FusedPopulationExecutor (one
    shared program either way, so the blame rules are identical)."""
    results: List[ExecutionResult] = []
    for i, (stopped, killed, failed, fail_msg, preempted) in enumerate(
        ctx.member_outcomes()
    ):
        if failed:
            results.append(
                ExecutionResult(TrialOutcome.FAILED, fail_msg, exit_code=1)
            )
        elif killed:
            results.append(
                ExecutionResult(TrialOutcome.KILLED, "kill requested")
            )
        elif preempted:
            results.append(
                ExecutionResult(
                    TrialOutcome.PREEMPTED,
                    "preempted by higher-priority work",
                )
            )
        elif stopped:
            results.append(ExecutionResult(TrialOutcome.EARLY_STOPPED))
        elif pack_error is not None:
            results.append(
                ExecutionResult(TrialOutcome.FAILED, pack_error, exit_code=1)
            )
        elif handles[i].kill_requested:
            results.append(
                ExecutionResult(TrialOutcome.KILLED, "kill requested")
            )
        else:
            results.append(
                ExecutionResult(TrialOutcome.COMPLETED, exit_code=0)
            )
    return results


class FusedPopulationExecutor:
    """Run one opted-in population sweep as a single compiled program
    (runtime/population.py): G generations of the K-member population
    execute inside jitted ``lax.scan`` chunks on the pack's ONE gang
    allocation, and only per-generation summaries cross back to the host —
    no per-generation suggestion sync, dispatch walk, thread spawn or DB
    round-trip.

    Invariants carried over from the job-queue drivers:

    - per-generation, per-member objective rows land in the obslog exactly
      as K legacy trials' reports would (one ``report_many`` batch per
      generation via the packed demux), plus population best/median rows
      under the ``<experiment>-population`` pseudo-trial;
    - the carry (with its PRNG key) checkpoints atomically at every chunk
      boundary BEFORE the chunk's rows are demuxed, and the demux progress
      is re-persisted if a preemption freeze interrupts it — metrics are
      durable before the members requeue, and the resumed sweep replays
      only the not-yet-reported generations, then continues the exact key
      stream: bit-identical to an uninterrupted run;
    - membership is masking, not unwinding: kills/preempts freeze members
      through the same PackedTrialContext cascade, and the host-side mask
      is ANDed into the carried ``active`` array at chunk boundaries so a
      killed member stays frozen inside later compiled chunks.
    """

    def __init__(
        self,
        obs_store: ObservationStore,
        chunk_generations: int = 16,
        stream: bool = False,
        compile_service=None,
        metrics=None,
    ):
        self.obs_store = obs_store
        self.chunk_generations = int(chunk_generations)
        self.stream = stream
        self.compile_service = compile_service
        self.metrics = metrics
        self._cache_enabled = False

    def execute(
        self,
        exp: Experiment,
        trials: Sequence[Trial],
        ctx: PackedTrialContext,
        handles: Sequence[TrialExecution],
    ) -> List[ExecutionResult]:
        if not self._cache_enabled:
            self._cache_enabled = True
            try:
                from ..utils.compilation import enable_compilation_cache

                enable_compilation_cache()
            except Exception:
                pass
        pack_error: Optional[str] = None
        token = set_current_reporter(None)
        ctx._trace_fn_start()
        try:
            self._run_sweep(exp, ctx)
        except (PackFrozen, EarlyStopped, TrialKilled, TrialPreempted):
            pass  # members already carry their terminal masks
        except Exception:
            pack_error = traceback.format_exc(limit=10)
        finally:
            ctx._trace_fn_end()
            from ..runtime import metrics as _m

            _m._current_reporter.reset(token)
        return _member_results(ctx, handles, pack_error)

    # -- sweep driving -------------------------------------------------------

    def _run_sweep(self, exp: Experiment, ctx: PackedTrialContext) -> None:
        import time as _time

        import jax

        from ..runtime import population as pop

        spec = exp.spec
        program = pop.build_program(spec)
        total = pop.generation_count(spec, program)
        chunk = self.chunk_generations if self.chunk_generations > 0 else total
        chunk = max(1, min(chunk, total))
        ckdir = next((d for d in ctx.checkpoint_dirs if d), None) or next(
            (w for w in ctx.workdirs if w), None
        )

        resumed = pop.load_sweep_checkpoint(ckdir, program)
        if resumed is not None:
            carry, done, pending, reported = resumed
        else:
            carry = program.init_carry(program.seed)
            done, pending, reported = 0, {}, 0
        carry = self._sync_mask(ctx, carry)

        sink = None
        if self.stream:
            sink = pop.stream_sink(
                exp.name,
                heartbeat=ctx.on_report if ctx.on_report is not None else None,
            )

        # resumed mid-demux: replay the generations the preempted run never
        # got into the obslog, from the checkpointed summaries
        if pending:
            n_pending = len(pending["score"])
            self._demux(
                exp, program, ctx, pending, start=reported,
                ckdir=ckdir, carry=carry, done=done,
            )
            pending = {}

        # AOT warm handoff (compile service prewarmed the fused chunk
        # program at admission); the streamed variant embeds a host
        # callback, so it always compiles through the local jit cache
        warm = None
        if sink is None and self.compile_service is not None:
            try:
                wp = self.compile_service.warm_executable_for_key(
                    pop.fused_group_key(spec, chunk)
                )
                warm = wp.executable if wp is not None else None
            except Exception:
                warm = None

        # at most two scan lengths per sweep (chunk body + tail remainder);
        # jax.jit is lazy, so building both up front traces nothing unused
        jitted: Dict[int, object] = {
            length: jax.jit(pop.build_chunk_fn(program, length, stream=sink))
            for length in pop.chunk_lengths(total - done, chunk)
        }
        while done < total and bool(np.any(ctx.active_mask)):
            length = min(chunk, total - done)
            fn = warm if (warm is not None and length == chunk) else jitted[length]
            t0 = _time.time()
            try:
                carry, ys = fn(carry)
            except Exception:
                if fn is warm:
                    # aval drift between the prewarmed executable and the
                    # live carry: fall back to the inline jit path
                    warm = None
                    carry, ys = jitted[length](carry)
                else:
                    raise
            ys_np = {k: np.asarray(v) for k, v in ys.items()}
            elapsed = _time.time() - t0
            if self.metrics is not None:
                self.metrics.observe(
                    "katib_population_fused_seconds", elapsed,
                    experiment=exp.name,
                )
            ctx.record_stage(
                "population_chunk", t0, _time.time(),
                generations=length, startGeneration=done,
            )
            # step-stats plane: the chunk is the gang's step loop — credit
            # its wall time as `length` steps to every active member's
            # clock (no-op when step stats are off)
            ctx.note_step_seconds(length, elapsed)
            done += length
            # checkpoint BEFORE demux: a preempt mid-demux re-persists the
            # progress counter; resume replays only unreported generations.
            # The notify tells the scheduler every member has a checkpoint,
            # so a preemption (incl. device loss) requeues them with their
            # observation logs KEPT — the resumed sweep extends, never
            # re-reports, and the lineage stays bit-identical.
            if ckdir:
                # the checkpoint declares every earlier chunk's rows
                # reported: make them durable first, or a hard kill after
                # this save loses the rows a write-behind store still held
                store = ctx.reporters[0].store if ctx.reporters else None
                if store is not None:
                    ctx._flush_traced(store)
                pop.save_sweep_checkpoint(ckdir, carry, done, ys_np, 0)
                ctx.notify_checkpoint(done)
            self._demux(
                exp, program, ctx, ys_np, start=0,
                ckdir=ckdir, carry=carry, done=done,
            )
            carry = self._sync_mask(ctx, carry)

        store = ctx.reporters[0].store if ctx.reporters else None
        if store is not None:
            ctx._flush_traced(store)
        if ckdir:
            pop.clear_sweep_checkpoint(ckdir)

    @staticmethod
    def _member_slots(ctx: PackedTrialContext) -> List[int]:
        """Population slot index per pack position (the fused member
        label). A member killed while still PENDING leaves the pack one
        short of the program's K — its slot simply has no pack position
        (it freezes at the first mask sync and reports nothing)."""
        from ..runtime.population import FUSED_LABEL

        return [
            int(labels.get(FUSED_LABEL, pos))
            for pos, labels in enumerate(ctx.member_labels)
        ]

    def _sync_mask(self, ctx: PackedTrialContext, carry):
        """Chunk-boundary mask sync: program-side deactivations become
        host-side early-stops, host-side kills/preempts freeze inside the
        next compiled chunk, and population slots with no pack member
        (killed before dispatch) freeze outright."""
        import jax.numpy as jnp

        slots = self._member_slots(ctx)
        prog_mask = np.asarray(carry["active"]).astype(bool)
        ctx.absorb_population_mask(prog_mask[slots])
        host = np.asarray(ctx.active_mask)
        present = np.zeros(prog_mask.shape[0], dtype=bool)
        present[slots] = host
        combined = prog_mask & present
        if not np.array_equal(combined, prog_mask):
            carry = dict(carry)
            carry["active"] = jnp.asarray(combined)
        return carry

    def _demux(
        self, exp, program, ctx, ys: Dict[str, np.ndarray], start: int,
        ckdir: Optional[str], carry, done: int,
    ) -> None:
        """Per-generation obslog demux of one chunk's summaries: member
        objective rows through the packed report path (kill/preempt
        freezes, early-stop absorption, flush barriers all apply), plus
        population best/median rows under the pseudo-trial. A preemption
        freeze raises PackFrozen out of ctx.report — the progress counter
        is re-persisted first so the resumed sweep replays exactly the
        unreported tail."""
        import time as _time

        from ..db.store import MetricLog
        from ..runtime import population as pop

        scores = ys["score"]
        n = scores.shape[0]
        slots = self._member_slots(ctx)
        store = ctx.reporters[0].store if ctx.reporters else None
        pseudo = f"{exp.name}-population"
        for g in range(start, n):
            ts = _time.time()
            try:
                ctx.report(timestamp=ts, **{program.metric: scores[g][slots]})
            except PackFrozen:
                if ckdir:
                    remaining = {k: v for k, v in ys.items()}
                    pop.save_sweep_checkpoint(
                        ckdir, carry, done, remaining, reported=g + 1
                    )
                raise
            finally:
                if self.metrics is not None:
                    self.metrics.inc(
                        "katib_population_generations_total",
                        experiment=exp.name,
                    )
            if store is not None:
                store.report_many(
                    [
                        (
                            pseudo,
                            [
                                MetricLog(ts, "population-best", str(float(ys["best"][g]))),
                                MetricLog(ts, "population-median", str(float(ys["median"][g]))),
                            ],
                        )
                    ]
                )
