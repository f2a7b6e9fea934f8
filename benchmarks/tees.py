"""What the harness sees of the program: five boundaries, each wrapped where it
is defined and restored on ``close``. Nothing here changes what a call does or
returns.

- ``SuggestionService.sync_assignments``: only a profiler annotation, so that an
  idle gap of the device can be named after it.
- ``InProcessExecutor.execute``: a trial function starts and ends (its name, its
  thread).
- ``parallel.train.make_lm_train_step``: the trial's compiled step with its
  state. The first ``FIRST_STEPS`` steps of every trial are read as they
  finish — the loss, the first gradient's norm per leaf as AdamW got it (from
  its first moment after one step), the norm of the parameters' change per
  leaf — and every later call only counts. That is what ``correct`` compares
  with the plain reference: the object the window drives, not a second one.
- ``TrialContext.report``: each report with the host's clock and the number of
  steps that thread has called.
- ``Trial.set_condition``: a trial reaches a terminal condition.

The compile listener is JAX's own (``jax.monitoring``), as in chip_smoke.py.
Every event carries ``time.time()``, the clock the program's Tracer uses.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from reference_lm import B1, leaf_norms  # one ruler for both sides of the comparison

FIRST_STEPS = 3


@dataclasses.dataclass
class TrialRecord:
    """One trial function's run, as the tees saw it."""

    name: str
    thread: int
    started: float
    ended: Optional[float] = None
    learning_rate: Optional[float] = None
    batch_shape: Optional[Tuple[int, int]] = None
    steps: int = 0
    first_losses: List[float] = dataclasses.field(default_factory=list)
    grad_norm: Optional[Dict[str, float]] = None
    delta_norm: Optional[Dict[str, float]] = None
    # (host time, steps called so far, metrics)
    reports: List[Tuple[float, int, Dict[str, float]]] = dataclasses.field(default_factory=list)
    _p0: Any = None


class CompileMeter:
    """One ``backend_compile_duration`` event per program compiled or fetched
    from the persistent cache, and the cache's own hit and miss events."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.compiles: List[Tuple[float, float]] = []  # (host time at the end, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.time(), seconds))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class Tees:
    def __init__(self) -> None:
        import jax
        import jax.numpy as jnp

        from katib_tpu.api import status
        from katib_tpu.controller import executor, suggestion
        from katib_tpu.parallel import train
        from katib_tpu.runtime import context

        self.records: List[TrialRecord] = []
        self.terminals: List[Tuple[float, str, str]] = []  # (host time, trial, condition)
        self.changed = threading.Condition()
        self._by_thread: Dict[int, TrialRecord] = {}
        self._active = 0
        self._norms = jax.jit(leaf_norms)
        self._delta = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))
        annotate = jax.profiler.TraceAnnotation

        self._patched = [
            (suggestion.SuggestionService, "sync_assignments",
             suggestion.SuggestionService.sync_assignments),
            (executor.InProcessExecutor, "execute", executor.InProcessExecutor.execute),
            (train, "make_lm_train_step", train.make_lm_train_step),
            (context.TrialContext, "report", context.TrialContext.report),
            (status.Trial, "set_condition", status.Trial.set_condition),
        ]
        orig_suggest, orig_execute, orig_build, orig_report, orig_set = (p[2] for p in self._patched)
        tees = self

        def sync_assignments(self_, *args, **kwargs):
            with annotate("bench:suggest"):
                return orig_suggest(self_, *args, **kwargs)

        def execute(self_, exp, trial, ctx, handle):
            with tees.watch(trial.name), annotate("bench:trial_function"):
                return orig_execute(self_, exp, trial, ctx, handle)

        def make_lm_train_step(config, mesh, learning_rate=1e-3, *args, **kwargs):
            with annotate("bench:build_step"):
                params, opt_state, step_fn, put_batch = orig_build(
                    config, mesh, learning_rate, *args, **kwargs
                )
            rec = tees._by_thread.get(threading.get_ident())
            if rec is None:  # built outside a trial: not ours to read
                return params, opt_state, step_fn, put_batch
            rec.learning_rate = float(learning_rate)

            def step(params, opt_state, tokens, *rest):
                n = rec.steps
                rec.steps = n + 1
                if n >= FIRST_STEPS:
                    return step_fn(params, opt_state, tokens, *rest)
                if n == 0:
                    rec.batch_shape = tuple(tokens.shape)
                    rec._p0 = jax.tree.map(jnp.copy, params)
                with annotate("bench:first_steps"):
                    out = step_fn(params, opt_state, tokens, *rest)
                    rec.first_losses.append(float(out[2]))
                    if n == 0:
                        adam = next(
                            s for s in jax.tree_util.tree_leaves(
                                out[1], is_leaf=lambda x: hasattr(x, "mu"))
                            if hasattr(s, "mu")
                        )
                        rec.grad_norm = {
                            k: float(v) / (1.0 - B1)  # AdamW's first moment after one step is (1 - b1) * g
                            for k, v in tees._norms(adam.mu).items()
                        }
                    if n == FIRST_STEPS - 1:
                        rec.delta_norm = {
                            k: float(v) for k, v in tees._delta(out[0], rec._p0).items()
                        }
                        rec._p0 = None
                return out

            return params, opt_state, step, put_batch

        def report(ctx, **metrics):
            rec = tees._by_thread.get(threading.get_ident())
            if rec is not None:
                with tees.changed:
                    rec.reports.append((time.time(), rec.steps, dict(metrics)))
                    tees.changed.notify_all()
            with annotate("bench:report"):
                return orig_report(ctx, **metrics)

        def set_condition(trial, cond, *args, **kwargs):
            was_terminal = trial.is_terminal
            result = orig_set(trial, cond, *args, **kwargs)
            if trial.is_terminal and not was_terminal:
                with tees.changed:
                    if all(name != trial.name for _, name, _ in tees.terminals):
                        tees.terminals.append((time.time(), trial.name, trial.condition.value))
                        tees.changed.notify_all()
            return result

        for (owner, attr, _), new in zip(
            self._patched, (sync_assignments, execute, make_lm_train_step, report, set_condition)
        ):
            setattr(owner, attr, new)

    @contextlib.contextmanager
    def watch(self, name: str):
        """The calling thread runs one trial function inside this block."""
        rec = TrialRecord(name, threading.get_ident(), time.time())
        with self.changed:
            self.records.append(rec)
            self._by_thread[rec.thread] = rec
            self._active += 1
        try:
            yield rec
        finally:
            rec.ended = time.time()
            rec._p0 = None
            with self.changed:
                self._by_thread.pop(rec.thread, None)
                self._active -= 1
                self.changed.notify_all()

    def all_reports(self) -> List[Tuple[float, TrialRecord, int, int]]:
        """(host time, record, steps called, steps since that trial's last
        report), in order of time."""
        out = []
        for rec in self.records:
            before = 0
            for t, steps, _ in list(rec.reports):
                out.append((t, rec, steps, steps - before))
                before = steps
        return sorted(out, key=lambda r: r[0])

    def wait_idle(self, timeout: float) -> bool:
        """Until no trial function is running."""
        deadline = time.time() + timeout
        with self.changed:
            while self._active and time.time() < deadline:
                self.changed.wait(min(1.0, max(0.0, deadline - time.time())))
            return self._active == 0

    def close(self) -> None:
        for owner, attr, orig in self._patched:
            setattr(owner, attr, orig)
