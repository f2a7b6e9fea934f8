"""Multi-host trial worker — one process per host of a gang-scheduled trial.

Launched as ``python -m katib_tpu.runtime.host_worker`` by
``MultiHostExecutor`` (controller/executor.py), this is the TPU-native
equivalent of one worker pod of the reference's distributed trial CRDs
(examples/v1beta1/kubeflow-training-operator/mpijob-horovod.yaml — the
training-operator wires MASTER_ADDR/RANK into pods; here the executor wires
``KATIB_TPU_COORDINATOR/_NUM_PROCESSES/_PROCESS_ID``, read by
``parallel.mesh.initialize_distributed``).

The worker joins the jax.distributed system, resolves the trial's
``entryPoint`` (``module:function``) and calls it with a ``WorkerContext``.
``report()`` prints ``name=value`` lines; the executor collects metrics from
process 0's stdout only, so every worker may report without duplicating
observations (the reference's PrimaryPodLabels semantics).
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List, Optional


class WorkerContext:
    """Duck-typed TrialContext for gang workers (runtime/context.py)."""

    def __init__(
        self,
        trial_name: str,
        experiment_name: str,
        assignments: Dict[str, str],
        workdir: Optional[str],
        checkpoint_dir: Optional[str],
        process_id: int,
        num_processes: int,
    ):
        self.trial_name = trial_name
        self.experiment_name = experiment_name
        self.assignments = assignments
        self.workdir = workdir
        self.checkpoint_dir = checkpoint_dir
        self.process_id = process_id
        self.num_processes = num_processes
        self.topology = os.environ.get("KATIB_TPU_TOPOLOGY")
        self.labels: Dict[str, str] = {}

    def report(self, timestamp: Optional[float] = None, **metrics: float) -> None:
        for name, value in metrics.items():
            print(f"{name}={value}", flush=True)

    def span(self, name: str, **attrs):
        """TrialContext.span where no tracer is bound: gang workers keep no
        spans, so the shared no-op."""
        from ..tracing import _NOOP_CM

        return _NOOP_CM

    def watch_step(self, step_fn):
        """TrialContext.watch_step where no tracer is bound: the identity."""
        return step_fn

    def count(self, **counters: float) -> None:
        """TrialContext.count where no tracer is bound: gang workers keep no
        step ledger, so nothing."""

    def param(self, name: str, default: Optional[str] = None) -> Optional[str]:
        return self.assignments.get(name, default)

    def param_float(self, name: str, default: Optional[float] = None) -> Optional[float]:
        v = self.assignments.get(name)
        return float(v) if v is not None else default

    def param_int(self, name: str, default: Optional[int] = None) -> Optional[int]:
        v = self.assignments.get(name)
        return int(float(v)) if v is not None else default

    def jax_devices(self) -> List[Any]:
        """ALL devices of the gang's distributed system (global view — the
        single-process TrialContext returns the gang-allocated subset).
        Through the one probe (utils/backend.py, KTI304): a worker with no
        backend raises instead of leaving the gang waiting for it."""
        from ..utils.backend import require_devices

        return list(require_devices())

    def mesh(self, axis_names=("data",), shape=None):
        import numpy as np
        from jax.sharding import Mesh

        arr = np.array(self.jax_devices())
        if shape is None and self.topology and len(axis_names) > 1:
            from ..api.spec import parse_topology

            dims = parse_topology(self.topology)
            if dims is not None and len(dims) == len(axis_names):
                shape = tuple(dims)
        if shape is not None:
            arr = arr.reshape(shape)
        elif len(axis_names) > 1:
            raise ValueError(
                "pass shape= for multi-axis meshes (or set "
                "resources.topology with one dim per axis)"
            )
        return Mesh(arr, axis_names)

    def profile(self, enabled: Optional[bool] = None):
        # enabled=None defaults from $KATIB_TPU_PROFILE (stamped on gang
        # workers by the executor) — same contract as TrialContext.profile
        from .profiling import profile_trace

        return profile_trace(self.workdir, enabled=enabled)

    def checkpoint_store(self, subdir=None):
        """Elastic-resume store (see TrialContext.checkpoint_store). On a
        SHARED checkpoint_dir (PBT lineage), non-primary ranks write under a
        rank-<i> subdirectory so concurrent ranks never contend on the same
        checkpoint files; rank 0 owns the lineage root. Per-host workdirs
        are already disjoint."""
        from .checkpoints import store_for

        return store_for(
            self.checkpoint_dir, self.workdir, subdir, rank=self.process_id
        )


def main() -> None:
    from ..parallel.mesh import initialize_distributed

    initialize_distributed()

    entry = os.environ["KATIB_TPU_ENTRY_POINT"]
    mod_name, _, fn_name = entry.partition(":")
    if not fn_name:
        raise SystemExit(f"KATIB_TPU_ENTRY_POINT {entry!r} must be 'module:function'")
    fn = getattr(importlib.import_module(mod_name), fn_name)

    ctx = WorkerContext(
        trial_name=os.environ.get("KATIB_TPU_TRIAL_NAME", ""),
        experiment_name=os.environ.get("KATIB_TPU_EXPERIMENT", ""),
        assignments=json.loads(os.environ.get("KATIB_TPU_ASSIGNMENTS", "{}")),
        workdir=os.environ.get("KATIB_TPU_WORKDIR"),
        checkpoint_dir=os.environ.get("KATIB_TPU_CHECKPOINT_DIR"),
        process_id=int(os.environ.get("KATIB_TPU_PROCESS_ID", "0")),
        num_processes=int(os.environ.get("KATIB_TPU_NUM_PROCESSES", "1")),
    )
    result = fn(ctx.assignments, ctx)
    if isinstance(result, dict):  # parity with InProcessExecutor auto-report
        numeric = {k: v for k, v in result.items() if isinstance(v, (int, float))}
        if numeric:
            ctx.report(**numeric)


if __name__ == "__main__":
    main()
