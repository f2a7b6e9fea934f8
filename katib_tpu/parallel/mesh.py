"""Device mesh construction + multi-host initialization.

The reference delegates distributed training to per-trial K8s CRDs (PyTorchJob
DDP / MPIJob Horovod — SURVEY.md §2.9); the TPU-native equivalent is a named
``jax.sharding.Mesh`` over the trial's gang-allocated chips with XLA
collectives over ICI within a slice and DCN across slices.

Axis convention (the scaling-book recipe):
- ``data``  — batch sharding (DP); gradients all-reduce (psum) over ICI
- ``fsdp``  — parameter/optimizer sharding over the data axis (ZeRO-style)
- ``model`` — tensor parallelism (TP); activations all-gather / reduce-scatter
- ``seq``   — sequence/context parallelism (ring attention over ppermute)
- ``expert``— expert parallelism for MoE layers
- ``pipe``  — pipeline stages
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("pipe", "data", "fsdp", "expert", "seq", "model")


def distributed_initialized() -> bool:
    """Is the jax.distributed client up? Inspects only the distributed
    client, never the XLA backend."""
    import jax

    return bool(jax.distributed.is_initialized())


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bring-up — jax.distributed.initialize on TPU-VM workers.

    Replaces the reference's dependence on the training-operator to wire
    MASTER_ADDR/RANK into PyTorchJob pods: here the trial runtime calls this
    on every host of the slice (no-op when single-process or when JAX already
    auto-detects TPU pod topology).
    """
    import jax

    # NOTE: must not touch jax.process_count()/jax.devices() here — those
    # initialize the XLA backend, after which jax.distributed.initialize()
    # refuses to run. distributed_initialized() inspects only the client.
    if distributed_initialized():
        return
    addr = coordinator_address or os.environ.get("KATIB_TPU_COORDINATOR")
    nproc = num_processes or int(os.environ.get("KATIB_TPU_NUM_PROCESSES", "0"))
    pid = process_id if process_id is not None else int(os.environ.get("KATIB_TPU_PROCESS_ID", "0"))
    if addr and nproc > 1:
        jax.distributed.initialize(
            coordinator_address=addr, num_processes=nproc, process_id=pid
        )


def make_mesh(
    devices: Optional[Sequence[Any]] = None,
    *,
    data: int = -1,
    fsdp: int = 1,
    model: int = 1,
    seq: int = 1,
    expert: int = 1,
    pipe: int = 1,
):
    """Build a named Mesh; ``data=-1`` absorbs the remaining devices.

    Axis order puts ``model`` (highest-bandwidth collectives) innermost so TP
    rides the fastest ICI links, and ``pipe``/``data`` outermost (DCN-friendly)
    — the standard TPU layout.
    """
    from jax.sharding import Mesh

    if devices is None:
        from ..utils.backend import require_devices

        # bounded probe with cached verdict (utils/backend.py): mesh
        # construction on a wedged backend raises fast instead of blocking
        # the caller for minutes (KTI304)
        devices = require_devices()
    n = len(devices)
    sizes = {"pipe": pipe, "data": data, "fsdp": fsdp, "expert": expert, "seq": seq, "model": model}
    fixed = 1
    for name, s in sizes.items():
        if s != -1:
            fixed *= s
    if sizes["data"] == -1:
        if n % fixed != 0:
            raise ValueError(f"{n} devices not divisible by fixed axes product {fixed}")
        sizes["data"] = n // fixed
    total = math.prod(sizes.values())
    if total != n:
        raise ValueError(f"mesh {sizes} needs {total} devices, got {n}")
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, AXIS_ORDER)


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


ACTIVATION_BATCH_AXES = ("data", "fsdp", "expert")


def activation_batch_axes(
    mesh_or_sizes, batch: int, axes: Sequence[str] = ACTIVATION_BATCH_AXES
) -> Tuple[str, ...]:
    """Greedy batch-sharding axes for activations: shard over each of
    data/fsdp/expert in order while ``batch`` divides the running product.

    'expert' acts as pure extra data parallelism OUTSIDE the MoE layers —
    attention and norms never compute redundantly across the expert axis —
    and the MoE dispatch einsum's sharding constraint re-splits tokens
    expert-wise with one all-to-all at the layer boundary (the scaling-book
    EP recipe)."""
    sizes = (
        mesh_or_sizes
        if isinstance(mesh_or_sizes, dict)
        else mesh_axis_sizes(mesh_or_sizes)
    )
    out: List[str] = []
    prod = 1
    for a in axes:
        s = sizes.get(a, 1)
        if s > 1 and batch % (prod * s) == 0:
            out.append(a)
            prod *= s
    return tuple(out)


def batch_spec(batch: Optional[int] = None, mesh=None):
    """Canonical activation sharding: batch over data+fsdp+expert, sequence
    over seq. With ``batch`` and ``mesh`` given, the batch axes are trimmed
    to what the batch size actually divides."""
    from jax.sharding import PartitionSpec as P

    if batch is None or mesh is None:
        return P(ACTIVATION_BATCH_AXES, "seq")
    return P(activation_batch_axes(mesh, batch) or None, "seq")
