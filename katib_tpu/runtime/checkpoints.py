"""Checkpoint store — orbax-backed trial state persistence.

Replaces the reference's PVC-based checkpoint flows (SURVEY.md §5):
- PBT exploit/explore copies a parent trial's checkpoint dir
  (pbt/service.py:260-268) — here the same directory contract is used by
  katib_tpu.suggest.pbt, and this module gives trials a typed save/restore
  API on top of it;
- trial elastic resume (restart picks up the latest step).

On TPU, orbax writes sharded arrays directly from device memory per host
(OCDBT); the same API works single-host in tests. Falls back to pickle+numpy
when orbax is unavailable so the framework has no hard dependency.
"""

from __future__ import annotations

import os
import pickle
import threading
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from ..parallel.mesh import distributed_initialized as _dist_init

MAX_TO_KEEP = 3

# orbax keeps process-global state for the save in flight (its temporary
# paths are registered under one operation id per process), so two
# CheckpointManagers saving from two in-process trial threads — even into
# different directories — create and remove each other's
# ``<step>.orbax-checkpoint-tmp``. Every orbax call of this process goes
# through this lock; a save already blocks its trial thread until committed.
_ORBAX_LOCK = threading.RLock()


class CheckpointError(RuntimeError):
    """A save that did not commit. Raised on the trial's own thread so the
    trial fails with the cause instead of resuming later from nothing."""


def _pickle_steps(directory: str) -> List[int]:
    steps = []
    for f in os.listdir(directory):
        if f.startswith("ckpt_") and f.endswith(".pkl"):
            stem = f[len("ckpt_"):-len(".pkl")]
            if stem.isdigit():  # ignore foreign files like ckpt_best.pkl
                steps.append(int(stem))
    return sorted(steps)


def store_for(
    checkpoint_dir: Optional[str],
    workdir: Optional[str],
    subdir: Optional[str] = None,
    rank: int = 0,
) -> "CheckpointStore":
    """Resolve a trial's checkpoint store location — shared by
    TrialContext.checkpoint_store and the gang WorkerContext so the
    precedence rule lives in one place. ``checkpoint_dir`` (the PBT lineage
    dir when the suggester provides one) wins over the workdir. Non-primary
    gang ranks (``rank > 0``) on a SHARED checkpoint_dir get a ``rank-<i>``
    subdirectory: the pickle fallback writes fixed ``ckpt_<step>`` names, so
    concurrent ranks in one directory would truncate each other's files;
    rank 0 keeps the shared root (the lineage contract PBT's exploit copy
    reads). Per-host workdirs are already disjoint, so no suffix there."""
    base = checkpoint_dir or workdir
    if base is None:
        raise ValueError(
            "trial has no checkpoint_dir or workdir (run the experiment "
            "with a root_dir to get per-trial directories)"
        )
    if rank and checkpoint_dir is not None:
        base = os.path.join(base, f"rank-{rank}")
    if subdir:
        base = os.path.join(base, subdir)
    return CheckpointStore(base)


class CheckpointStore:
    """Save/restore a pytree (params, opt state, step...) under a directory."""

    def __init__(self, directory: str, use_orbax: Optional[bool] = None):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        if use_orbax is None:
            try:
                import orbax.checkpoint  # noqa: F401

                use_orbax = True
            except ImportError:
                use_orbax = False
            if use_orbax and _dist_init():
                # Gang workers get INDEPENDENT per-rank stores (store_for:
                # per-host workdirs / rank-<i> subdirs), but orbax's
                # CheckpointManager runs sync_global_processes barriers that
                # assume ONE checkpoint shared by every process — per-rank
                # saves then deadlock or die on a barrier-name mismatch.
                # (is_initialized() inspects only the distributed client; it
                # never initializes the XLA backend.) A future globally-
                # sharded-array checkpoint path should pass use_orbax=True
                # and a shared directory explicitly.
                use_orbax = False
        self.use_orbax = use_orbax

    # -- orbax path ----------------------------------------------------------

    def _manager(self):
        import orbax.checkpoint as ocp

        return ocp.CheckpointManager(
            self.directory, options=ocp.CheckpointManagerOptions(max_to_keep=MAX_TO_KEEP)
        )

    def save(self, step: int, state: Dict[str, Any]) -> None:
        if self.use_orbax:
            import orbax.checkpoint as ocp

            # numpy scalar leaves (np.int32(step)...) -> 0-d ndarrays: newer
            # orbax StandardSave rejects numpy scalar types outright
            state = jax.tree.map(
                lambda x: np.asarray(x) if isinstance(x, np.generic) else x,
                state,
            )
            with _ORBAX_LOCK, self._manager() as mngr:
                mngr.save(step, args=ocp.args.StandardSave(state))
                mngr.wait_until_finished()
                latest = mngr.latest_step()
            if latest is None or latest < step:
                raise CheckpointError(
                    f"checkpoint step {step} did not commit under "
                    f"{self.directory} (latest committed: {latest})"
                )
        else:
            host_state = jax.tree.map(np.asarray, state)
            path = os.path.join(self.directory, f"ckpt_{step}.pkl")
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                pickle.dump({"step": step, "state": host_state}, f)
            os.replace(tmp, path)
            # same retention as the orbax path
            steps = _pickle_steps(self.directory)
            for old in steps[:-MAX_TO_KEEP]:
                try:
                    os.remove(os.path.join(self.directory, f"ckpt_{old}.pkl"))
                except OSError:
                    pass

    def latest_step(self) -> Optional[int]:
        if self.use_orbax:
            with _ORBAX_LOCK, self._manager() as mngr:
                return mngr.latest_step()
        steps = _pickle_steps(self.directory)
        return max(steps) if steps else None

    def restore(self, step: Optional[int] = None, template: Optional[Any] = None) -> Optional[Dict[str, Any]]:
        """Restore state at ``step`` (default latest); None when empty."""
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        if self.use_orbax:
            import orbax.checkpoint as ocp

            with _ORBAX_LOCK, self._manager() as mngr:
                if template is not None:
                    return mngr.restore(step, args=ocp.args.StandardRestore(template))
                # template-less StandardRestore: newer orbax refuses a bare
                # restore() (KeyError: no CheckpointArgs); the explicit empty
                # StandardRestore reconstructs from checkpoint metadata
                return mngr.restore(step, args=ocp.args.StandardRestore())
        path = os.path.join(self.directory, f"ckpt_{step}.pkl")
        with open(path, "rb") as f:
            return pickle.load(f)["state"]
