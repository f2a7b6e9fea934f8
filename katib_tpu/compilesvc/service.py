"""CompileService — the controller-side compilation plane.

Compilation used to be a surprise tax inside the first trial's stint: the
executor called the trial function, the function hit ``jax.jit``, and the
gang's chips idled for the 23–51s XLA compile BENCH_r02/r04 measured. This
service makes compilation a *scheduled, cached, observable* resource:

- **Admission-time AOT compile.** When a trial is submitted (and already at
  ``create_experiment`` via :meth:`CompileService.prewarm`), its dispatch
  group's PR 7 :class:`~katib_tpu.analysis.program.ProgramProbe` is queued
  for an ahead-of-time ``jit(fn).trace(*avals).lower().compile()`` on a
  small worker pool — off the dispatch path, so chips never wait on XLA
  when the gate is on. One ``.trace`` serves both the compile fingerprint
  (byte-identical to the analysis fingerprint — same canonical jaxpr) and
  the lowering, so the shared program of an N-trial runtime-scalar sweep is
  traced exactly once in the service.
- **Fingerprint-keyed executable registry.** Entries progress
  ``pending → compiling → warm`` (or ``failed``); the registry is keyed by
  the dispatch-group key on the request path (a dict hit under the
  scheduler's walk) and deduplicated by compile fingerprint across groups
  — two templates lowering to the same program share one executable.
- **Failure quarantine.** A failed compile emits exactly one
  ``CompileFailed`` warning event and the fingerprint is quarantined: it is
  never recompiled per trial; trials fall back to inline compilation in the
  executor (where the real exception surfaces per trial as before).
- **Cost-ordered queue.** Jobs are ordered by the PR 7 cost model's FLOPs,
  biggest first, so the longest compile starts earliest.
- **Timeout + worker-crash isolation.** Each compile runs on an inner
  daemon thread with a per-compile timeout; a wedged XLA (or a crashing
  probe) fails that entry, never the worker pool or the controller.
- **Warm handoff.** In-process trials receive the compiled executable via
  ``ctx.compiled_program`` (scheduler → TrialContext); subprocess and gang
  trials get their warmth via the shared persistent XLA cache
  (utils/compilation.py), which the service's AOT compiles pre-populate.

Observability: ``katib_compile_queue_depth``, ``katib_compile_cache_hit_-
total``/``miss_total``, ``katib_compile_failed_total`` and the
``katib_compile_seconds`` histogram; a ``compile_service`` span joined to
the first requesting trial's trace; ``katib-tpu compile [--url]`` renders
the registry (live via ``/api/compile`` or from the JSON snapshot persisted
under ``<root>/compilesvc/``).

Disabled (``runtime.compile_service=false`` / ``KATIB_TPU_COMPILE_SERVICE=0``)
the controller never constructs the service and every scheduler/packing/
context consult is one ``is None`` check — dispatch is byte-identical to the
legacy path.
"""

from __future__ import annotations

import collections
import json
import logging
import os
import queue
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

log = logging.getLogger("katib_tpu.compilesvc")

STATE_PENDING = "pending"
STATE_COMPILING = "compiling"
STATE_WARM = "warm"
STATE_FAILED = "failed"

QUEUE_DEPTH_METRIC = "katib_compile_queue_depth"
HIT_METRIC = "katib_compile_cache_hit_total"
MISS_METRIC = "katib_compile_cache_miss_total"
FAILED_METRIC = "katib_compile_failed_total"
SECONDS_METRIC = "katib_compile_seconds"

REGISTRY_FILE = "registry.json"

# Process-level executable cache, keyed by compile fingerprint — the
# service-side analogue of the jit cache. Fingerprints are process-stable
# and include donation/statics, so two CompileService instances (repeat
# experiments, multiple controllers, test suites) tracing the same program
# share one executable instead of recompiling it. Bounded; oldest evicted.
_PROCESS_CACHE_MAX = 64
_PROCESS_CACHE: "collections.OrderedDict[str, Tuple[Any, float]]" = (
    collections.OrderedDict()
)
_PROCESS_CACHE_LOCK = threading.Lock()


def clear_process_cache() -> None:
    """Drop the process-level executable cache (test isolation hook)."""
    with _PROCESS_CACHE_LOCK:
        _PROCESS_CACHE.clear()


def _process_cache_get(fingerprint: str):
    with _PROCESS_CACHE_LOCK:
        hit = _PROCESS_CACHE.get(fingerprint)
        if hit is not None:
            _PROCESS_CACHE.move_to_end(fingerprint)
        return hit


def _process_cache_put(fingerprint: str, executable, compile_seconds: float) -> None:
    with _PROCESS_CACHE_LOCK:
        _PROCESS_CACHE[fingerprint] = (executable, compile_seconds)
        _PROCESS_CACHE.move_to_end(fingerprint)
        while len(_PROCESS_CACHE) > _PROCESS_CACHE_MAX:
            _PROCESS_CACHE.popitem(last=False)


@dataclass
class WarmProgram:
    """Handle the scheduler passes to an in-process trial via
    ``ctx.compiled_program``: the AOT-compiled executable for the trial's
    dispatch group plus enough metadata to sanity-check it. ``executable``
    is a ``jax.stages.Compiled`` — call it with concrete arrays matching
    the probe's avals."""

    fingerprint: str
    executable: Any
    target: str
    compile_seconds: float


@dataclass
class CompileEntry:
    """One dispatch group's slot in the registry."""

    key: Any                      # dispatch-group key (analysis/program.py)
    experiment: str               # first requesting experiment
    target: str                   # "module:fn" of the probed entry point
    state: str = STATE_PENDING
    fingerprint: str = ""         # filled by the worker's trace
    cost_flops: float = 0.0       # PR 7 cost model (queue priority)
    submitted_at: float = field(default_factory=time.time)
    started_at: Optional[float] = None
    compiled_at: Optional[float] = None
    compile_seconds: Optional[float] = None
    trials_served: int = 0        # requests answered for this group
    prewarmed: bool = False       # enqueued at admission, before any trial
    error: Optional[str] = None
    executable: Any = None        # in-memory only, never serialized
    # (trace_id, parent_span_id) of the first requesting trial's root span;
    # prewarm entries start without one and adopt the first trial's trace,
    # so the compile_service span joins a real trial trace when possible
    trace: Optional[Tuple[str, str]] = None

    def to_dict(self) -> Dict[str, Any]:
        return {
            "key": _key_str(self.key),
            "experiment": self.experiment,
            "target": self.target,
            "state": self.state,
            "fingerprint": self.fingerprint,
            "costFlops": self.cost_flops,
            "submittedAt": self.submitted_at,
            "startedAt": self.started_at,
            "compiledAt": self.compiled_at,
            "compileSeconds": self.compile_seconds,
            "trialsServed": self.trials_served,
            "prewarmed": self.prewarmed,
            "error": self.error,
            "hasExecutable": self.executable is not None,
        }


def _key_str(key: Any) -> str:
    """Stable human-readable form of a dispatch-group key:
    ``<digest>[name=value,...]``."""
    try:
        digest, values = key
        inner = ",".join(f"{n}={v}" for n, v in values)
        return f"{digest}[{inner}]"
    except Exception:
        return repr(key)


@dataclass
class _Job:
    """One queued compile: everything the worker needs, detached from the
    live Experiment/Trial objects so the queue holds no control-plane
    state."""

    key: Any
    experiment: str
    target: str
    builder: Callable[[Dict[str, str]], Any]   # fn.abstract_program
    assignments: Dict[str, str]
    cost_flops: float


class CompileService:
    """Controller-owned AOT compiler with a fingerprint-keyed registry.

    Thread model: ``request``/``prewarm`` run on control-plane threads
    (submit path, create_experiment); ``state_for_key``/``is_warm``/
    ``warm_executable_for`` run under the scheduler's dispatch lock (they
    take only this service's lock — the scheduler→service lock order is the
    one direction ever used); workers notify listeners *outside* the
    service lock, so a listener re-entering the scheduler cannot form a
    lock-order cycle (verified by the lockgraph stress test).
    """

    # executables kept resident for in-process handoff; metadata is never
    # evicted (the registry is the observability surface), only the
    # executables of the oldest warm entries beyond this cap are dropped —
    # those groups still benefit from the persistent XLA cache
    MAX_RESIDENT_EXECUTABLES = 64

    def __init__(
        self,
        workers: int = 2,
        timeout_seconds: float = 600.0,
        metrics=None,
        events=None,
        tracer=None,
        persist_dir: Optional[str] = None,
    ):
        self.workers = max(int(workers), 1)
        self.timeout_seconds = timeout_seconds
        self.metrics = metrics
        self.events = events
        self.tracer = tracer
        self.persist_dir = persist_dir
        self._lock = threading.Lock()
        self._by_key: Dict[Any, CompileEntry] = {}
        self._by_fingerprint: Dict[str, CompileEntry] = {}
        self._warm_order: List[str] = []  # fingerprints, oldest first
        self._queue: "queue.PriorityQueue" = queue.PriorityQueue()
        self._seq = 0
        self._threads: List[threading.Thread] = []
        self._listeners: List[Callable[[Any], None]] = []
        self._running = False
        # counters surfaced by stats(): every executed compile bumps
        # trace_counter exactly once — the acceptance sweep's assertion that
        # a shared program is traced once *in the service*
        self.trace_counter = 0
        self.compiled_total = 0
        self.hits = 0
        self.misses = 0
        self._cache_enabled = False

    # -- lifecycle -----------------------------------------------------------

    @property
    def active(self) -> bool:
        return self._running

    def start(self) -> None:
        """Spawn the worker pool (idempotent)."""
        with self._lock:
            if self._running:
                return
            self._running = True
        for i in range(self.workers):
            t = threading.Thread(
                target=self._worker_loop, daemon=True, name=f"compile-worker-{i}"
            )
            with self._lock:
                self._threads.append(t)
            t.start()

    def stop(self) -> None:
        """Stop the pool. In-flight compiles finish on their inner daemon
        threads and are discarded; queued jobs are dropped."""
        with self._lock:
            if not self._running:
                return
            self._running = False
            threads = list(self._threads)
            self._threads = []
        for _ in threads:
            self._queue.put((float("inf"), self._next_seq(), None))  # sentinel
        for t in threads:
            t.join(timeout=2.0)
        # final snapshot: request counters (hits/trialsServed) accrued since
        # the last compile transition reach the offline `katib-tpu compile`
        self._persist()

    def add_listener(self, fn: Callable[[Any], None]) -> None:
        """Register a state-transition hook ``fn(group_key)`` — the
        scheduler re-runs its dispatch pass when a program turns warm (or
        fails, releasing any gate hold). Called from worker threads with NO
        service lock held."""
        with self._lock:
            self._listeners.append(fn)

    # -- request path (control-plane threads) --------------------------------

    def request(self, exp, trial, trace: Optional[Tuple[str, str]] = None) -> Optional[Any]:
        """Ask for the trial's dispatch group to be warm. Returns the group
        key (None when the template is unanalyzable — command templates,
        probe-less functions, analysis off). Dict hit after the first
        request per group; a ``failed`` entry is quarantined and never
        re-enqueued."""
        if not self._running:
            return None
        from ..analysis import program as semantic

        try:
            key = semantic.dispatch_group_key(exp.spec, trial)
        except Exception:
            key = None
        if key is None:
            return None
        # resolve the probe/analysis OUTSIDE the service lock: the analysis
        # cache is warm here (dispatch_group_key above consulted it), but a
        # cold cache must never hold this lock through a trace — the
        # scheduler's dispatch walk consults state_for_key under its own lock
        admission = self._resolve_admission(exp.spec)
        job = None
        with self._lock:
            entry = self._by_key.get(key)
            if entry is not None:
                entry.trials_served += 1
                if entry.trace is None and trace is not None:
                    entry.trace = trace  # adopt the first trial's trace
                hit = entry.state == STATE_WARM
            else:
                hit = False
                entry, job = self._admit_locked(
                    key, exp.spec.name, dict(trial.assignments_dict()), trace,
                    admission,
                )
                if entry is not None:
                    entry.trials_served = 1
        self._count_request(exp.name, hit)
        if job is not None:
            self._enqueue(job)
        return key

    def prewarm(self, spec) -> Optional[Any]:
        """Admission-time warm-up: enqueue the spec's *baseline* dispatch
        group before any trial exists, so the first suggestion batch of a
        runtime-scalar sweep already finds its executable compiling (or
        warm). Returns the group key or None."""
        if not self._running:
            return None
        from ..analysis import program as semantic

        try:
            analysis = semantic.cached_analysis(spec)
            if analysis is None or not analysis.analyzable:
                return None
            baseline = semantic.baseline_assignments(spec)
            key = semantic.dispatch_group_key_for_assignments(spec, baseline)
        except Exception:
            return None
        if key is None:
            return None
        admission = self._resolve_admission(spec)
        job = None
        with self._lock:
            entry = self._by_key.get(key)
            if entry is None:
                entry, job = self._admit_locked(
                    key, spec.name, dict(baseline), None, admission
                )
                if entry is not None:
                    entry.prewarmed = True
        if job is not None:
            self._enqueue(job)
        return key

    def request_group(
        self,
        key: Any,
        experiment: str,
        target: str,
        builder: Callable[[Dict[str, str]], Any],
        assignments: Optional[Dict[str, str]] = None,
        cost_flops: float = 0.0,
        trace: Optional[Tuple[str, str]] = None,
    ) -> Optional[Any]:
        """Generic group admission — the fused population runtime (and any
        future non-per-trial program source) registers its program under an
        explicit registry key with its own ProgramProbe builder. Same
        lifecycle as a per-trial dispatch group: pending → compiling →
        warm/failed, fingerprint-deduplicated, cost-ordered, quarantined on
        failure. Returns the key (None when the service is stopped)."""
        if not self._running:
            return None
        job = None
        with self._lock:
            entry = self._by_key.get(key)
            if entry is not None:
                entry.trials_served += 1
                if entry.trace is None and trace is not None:
                    entry.trace = trace
                hit = entry.state == STATE_WARM
            else:
                hit = False
                entry, job = self._admit_locked(
                    key, experiment, dict(assignments or {}), trace,
                    (builder, target, float(cost_flops)),
                )
                if entry is not None:
                    entry.trials_served = 1
        self._count_request(experiment, hit)
        if job is not None:
            self._enqueue(job)
        return key

    def warm_executable_for_key(self, key: Any) -> Optional[WarmProgram]:
        """The compiled executable for an explicit registry key, when warm
        and still resident — the request_group counterpart of
        ``warm_executable_for``."""
        if key is None:
            return None
        with self._lock:
            entry = self._by_key.get(key)
            if (
                entry is None
                or entry.state != STATE_WARM
                or entry.executable is None
            ):
                return None
            return WarmProgram(
                fingerprint=entry.fingerprint,
                executable=entry.executable,
                target=entry.target,
                compile_seconds=entry.compile_seconds or 0.0,
            )

    @staticmethod
    def _resolve_admission(spec) -> Optional[Tuple[Callable, str, float]]:
        """(probe builder, target name, cost FLOPs) for a spec, or None when
        it has no probe. Runs lock-free — the analysis cache consult may
        trace on a cold cache."""
        from ..analysis import program as semantic

        if spec.trial_template.resources.num_hosts > 1:
            # a multi-host gang runs in worker processes that need the chip;
            # compiling its program here would initialize the backend in the
            # controller and take the chip from them
            return None
        builder = semantic.probe_builder_for(spec.trial_template)
        if builder is None:
            return None
        analysis = semantic.cached_analysis(spec)
        target = analysis.target if analysis is not None else "?"
        cost = 0.0
        if analysis is not None and analysis.cost is not None:
            cost = float(analysis.cost.flops)
        return builder, target, cost

    def _admit_locked(
        self, key, experiment: str, assignments: Dict[str, str], trace, admission
    ) -> Tuple[Optional[CompileEntry], Optional[_Job]]:
        """Create the registry entry + job for a new group. Caller holds the
        service lock; ``admission`` was resolved outside it."""
        if admission is None:
            return None, None
        builder, target, cost = admission
        entry = CompileEntry(
            key=key, experiment=experiment, target=target, cost_flops=cost,
            trace=trace,
        )
        self._by_key[key] = entry
        job = _Job(
            key=key,
            experiment=experiment,
            target=target,
            builder=builder,
            assignments=assignments,
            cost_flops=cost,
        )
        return entry, job

    def _next_seq(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

    def _enqueue(self, job: _Job) -> None:
        # cost-ordered: biggest program first (longest compile starts
        # earliest); seq breaks ties in arrival order
        self._queue.put((-job.cost_flops, self._next_seq(), job))
        self._set_queue_gauge()

    def _count_request(self, experiment: str, hit: bool) -> None:
        with self._lock:
            if hit:
                self.hits += 1
            else:
                self.misses += 1
        if self.metrics is not None:
            self.metrics.inc(
                HIT_METRIC if hit else MISS_METRIC, experiment=experiment
            )

    def _set_queue_gauge(self) -> None:
        if self.metrics is not None:
            self.metrics.set_gauge(QUEUE_DEPTH_METRIC, float(self._queue.qsize()))

    # -- consult path (scheduler dispatch lock) ------------------------------

    def state_for_key(self, key) -> Optional[str]:
        """Registry state for one dispatch-group key (dict hit; None =
        unknown group)."""
        if key is None:
            return None
        with self._lock:
            entry = self._by_key.get(key)
            return entry.state if entry is not None else None

    def is_warm(self, spec, trial) -> bool:
        """Warm-executable predicate for dispatch ordering / pack
        preference."""
        from ..analysis import program as semantic

        try:
            key = semantic.dispatch_group_key(spec, trial)
        except Exception:
            return False
        return self.state_for_key(key) == STATE_WARM

    def warm_executable_for(self, spec, trial) -> Optional[WarmProgram]:
        """The compiled executable for this trial's group, when warm and
        still resident — handed to in-process trials via
        ``ctx.compiled_program``."""
        from ..analysis import program as semantic

        try:
            key = semantic.dispatch_group_key(spec, trial)
        except Exception:
            return None
        if key is None:
            return None
        with self._lock:
            entry = self._by_key.get(key)
            if (
                entry is None
                or entry.state != STATE_WARM
                or entry.executable is None
            ):
                return None
            return WarmProgram(
                fingerprint=entry.fingerprint,
                executable=entry.executable,
                target=entry.target,
                compile_seconds=entry.compile_seconds or 0.0,
            )

    # -- worker pool ---------------------------------------------------------

    def _worker_loop(self) -> None:
        while True:
            _, _, job = self._queue.get()
            self._set_queue_gauge()
            if job is None:  # stop sentinel
                return
            if not self._running:
                return
            try:
                self._run_job(job)
            except Exception:
                # worker-crash isolation: a bug in the job plumbing fails
                # that job's entry (below, via _fail) or at worst logs —
                # the pool itself never dies
                log.exception("compile job for %s crashed", job.target)

    def _run_job(self, job: _Job) -> None:
        with self._lock:
            entry = self._by_key.get(job.key)
            if entry is None or entry.state != STATE_PENDING:
                return  # raced with stop/duplicate; nothing to do
            entry.state = STATE_COMPILING
            entry.started_at = time.time()
            trace_ctx = entry.trace
        span = None
        tracer = self.tracer
        if tracer is not None and getattr(tracer, "enabled", False) and trace_ctx:
            trace_id, parent_id = trace_ctx
            span = tracer.start_span(
                "compile_service", job.experiment, trace_id, parent_id,
                attrs={"target": job.target, "costFlops": job.cost_flops},
            )
        box: Dict[str, Any] = {}

        def _do():
            try:
                box["result"] = self._compile_probe(job)
            except BaseException:
                box["error"] = traceback.format_exc(limit=10)

        inner = threading.Thread(
            target=_do, daemon=True, name=f"compile-{job.target}"
        )
        started = time.time()
        inner.start()
        inner.join(self.timeout_seconds)
        if inner.is_alive():
            # wedged XLA / backend init: abandon the inner thread (it is a
            # daemon), quarantine the fingerprint — per-compile timeout is
            # the worker-crash isolation boundary
            self._fail(
                job,
                f"compile exceeded {self.timeout_seconds:.0f}s; "
                "abandoned (fingerprint quarantined)",
            )
            if span is not None:
                tracer.end_span(span, outcome="timeout")
            return
        if "error" in box:
            self._fail(job, box["error"])
            if span is not None:
                tracer.end_span(span, outcome="failed")
            return
        fingerprint, executable, reused = box["result"]
        elapsed = time.time() - started
        if not reused:
            _process_cache_put(fingerprint, executable, elapsed)
        notify = self._finish_warm(job, fingerprint, executable, elapsed, reused)
        if self.metrics is not None and not reused:
            self.metrics.observe(
                SECONDS_METRIC, elapsed, experiment=job.experiment
            )
        if span is not None:
            tracer.end_span(
                span, outcome="warm", fingerprint=fingerprint,
                reusedTwin=reused, compileSeconds=round(elapsed, 4),
            )
        elif tracer is not None and getattr(tracer, "enabled", False):
            # the compile started before any trial requested this group
            # (admission prewarm); if a trial adopted the entry meanwhile,
            # record the measured interval into its trace retroactively
            with self._lock:
                e2 = self._by_key.get(job.key)
                trace_ctx = e2.trace if e2 is not None else None
            if trace_ctx:
                tracer.record_span(
                    "compile_service", job.experiment, trace_ctx[0],
                    trace_ctx[1], start=started, end=time.time(),
                    target=job.target, outcome="warm", fingerprint=fingerprint,
                    reusedTwin=reused, compileSeconds=round(elapsed, 4),
                )
        self._persist()
        if notify:
            self._notify(job.key)

    def _compile_probe(self, job: _Job) -> Tuple[str, Any, bool]:
        """Build the probe and AOT-compile it. One ``.trace`` yields both
        the canonical jaxpr (fingerprint — byte-identical to the analysis
        fingerprint) and the lowering; when an equal fingerprint is already
        warm the twin's executable is reused and ``.compile()`` is skipped.
        Runs on the inner (timeout-bounded) thread."""
        self._ensure_persistent_cache()
        import jax

        from ..analysis import program as semantic

        probe = job.builder(dict(job.assignments))
        jitted = jax.jit(probe.fn, donate_argnums=probe.donate_argnums)
        with self._lock:
            self.trace_counter += 1
        traced = jitted.trace(*probe.args)
        closed = traced.jaxpr
        lower = traced.lower
        fingerprint = semantic.fingerprint_jaxpr(closed, probe)
        with self._lock:
            twin = self._by_fingerprint.get(fingerprint)
            if (
                twin is not None
                and twin.state == STATE_WARM
                and twin.executable is not None
            ):
                return fingerprint, twin.executable, True
        cached = _process_cache_get(fingerprint)
        if cached is not None:
            # another service instance in this process (repeat experiment,
            # second controller) already compiled this exact program
            return fingerprint, cached[0], True
        executable = lower().compile()
        with self._lock:
            self.compiled_total += 1
        return fingerprint, executable, False

    def _ensure_persistent_cache(self) -> None:
        """Point this process at the shared persistent XLA cache before the
        first AOT compile, so subprocess/gang trials (which share the cache
        dir) find the service's compiles warm. Accelerator platforms only —
        same guard as the executors."""
        with self._lock:
            if self._cache_enabled:
                return
            self._cache_enabled = True
        try:
            from ..utils.compilation import enable_compilation_cache

            enable_compilation_cache()
        except Exception:
            pass

    def _finish_warm(
        self, job: _Job, fingerprint: str, executable, elapsed: float, reused: bool
    ) -> bool:
        with self._lock:
            entry = self._by_key.get(job.key)
            if entry is None:
                return False
            entry.state = STATE_WARM
            entry.fingerprint = fingerprint
            entry.compiled_at = time.time()
            entry.compile_seconds = round(elapsed, 4)
            entry.executable = executable
            self._by_fingerprint.setdefault(fingerprint, entry)
            self._warm_order.append(fingerprint)
            self._evict_executables_locked()
        return True

    def _evict_executables_locked(self) -> None:
        """Drop the oldest resident executables beyond the cap (metadata
        stays; those groups still hit the persistent XLA cache). Caller
        holds the service lock."""
        while len(self._warm_order) > self.MAX_RESIDENT_EXECUTABLES:
            old_fp = self._warm_order.pop(0)
            old = self._by_fingerprint.get(old_fp)
            if old is not None:
                old.executable = None

    def _fail(self, job: _Job, error: str) -> None:
        """Quarantine one group's fingerprint: exactly one CompileFailed
        event, never re-enqueued (request() finds the failed entry and
        leaves it alone) — trials fall back to inline compilation."""
        with self._lock:
            entry = self._by_key.get(job.key)
            if entry is None or entry.state == STATE_FAILED:
                return
            entry.state = STATE_FAILED
            entry.error = error.strip().splitlines()[-1][-400:] if error else "?"
        log.warning(
            "AOT compile of %s failed; fingerprint group quarantined "
            "(trials compile inline): %s", job.target, entry.error,
        )
        if self.metrics is not None:
            self.metrics.inc(FAILED_METRIC, experiment=job.experiment)
        if self.events is not None:
            self.events.event(
                job.experiment, "Experiment", job.experiment, "CompileFailed",
                f"AOT compile of {job.target} failed; group quarantined, "
                f"trials fall back to inline compilation: {entry.error}",
                warning=True,
            )
        self._persist()
        self._notify(job.key)

    def _notify(self, key) -> None:
        """Fire the state-transition listeners with NO service lock held —
        a listener re-entering the scheduler must not create a
        service→scheduler lock edge."""
        with self._lock:
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(key)
            except Exception:
                log.debug("compile listener failed", exc_info=True)

    # -- observability -------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "compiled": self.compiled_total,
                "traces": self.trace_counter,
                "hits": self.hits,
                "misses": self.misses,
                "entries": len(self._by_key),
                "queueDepth": self._queue.qsize(),
            }

    def registry_snapshot(self) -> Dict[str, Any]:
        """The ``/api/compile`` + ``katib-tpu compile`` view; also what is
        persisted under ``<root>/compilesvc/registry.json``."""
        with self._lock:
            entries = [e.to_dict() for e in self._by_key.values()]
            stats = {
                "compiled": self.compiled_total,
                "traces": self.trace_counter,
                "hits": self.hits,
                "misses": self.misses,
                "queueDepth": self._queue.qsize(),
            }
        entries.sort(key=lambda e: e["submittedAt"])
        return {"entries": entries, **stats}

    def _persist(self) -> None:
        """Atomic JSON snapshot of the registry so ``katib-tpu compile``
        works offline after the controller exits. Best-effort: persistence
        failure never fails a compile."""
        if not self.persist_dir:
            return
        try:
            snapshot = self.registry_snapshot()
            os.makedirs(self.persist_dir, exist_ok=True)
            path = os.path.join(self.persist_dir, REGISTRY_FILE)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(snapshot, f, indent=2, sort_keys=True)
            os.replace(tmp, path)
        except Exception:
            log.debug("compile registry persist failed", exc_info=True)


def load_persisted_registry(persist_dir: str) -> Optional[Dict[str, Any]]:
    """Offline registry view for the CLI (`katib-tpu compile` without
    --url): the JSON snapshot the service wrote on its last transition."""
    path = os.path.join(persist_dir, REGISTRY_FILE)
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None
