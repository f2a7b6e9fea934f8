"""Model parameter initialization as one device program.

Eager ``flax`` ``Module.init`` issues one device dispatch per parameter; a
jitted init is a single computation whose compile the persistent cache keeps
across the trials of a sweep. Every trial entry point should initialize
through this helper rather than calling ``model.init`` eagerly. (What the
difference is worth on a local chip is not measured.)
"""

from __future__ import annotations

import functools

import jax


@functools.lru_cache(maxsize=32)
def _cached_init_fn(model):
    # one jitted init per (hashable) module config: repeated trials of an
    # HPO sweep reuse the same callable and skip the init retrace
    return jax.jit(model.init)


def jitted_init(model, rngs, *args, device=None):
    """``model.init`` as one jitted computation; returns the ``params``
    collection. ``device`` (optional) places the result on a specific device
    via ``jax.default_device`` — arrays stay *uncommitted*, so the steps
    that consume them follow ``jax.default_device`` too (see
    katib_tpu.parallel.train.make_lm_train_step).
    """
    import contextlib

    try:
        fn = _cached_init_fn(model)  # flax Modules with hashable fields
    except TypeError:
        fn = jax.jit(model.init)  # unhashable config: uncached fallback
    ctx = jax.default_device(device) if device is not None else contextlib.nullcontext()
    with ctx:
        return fn(rngs, *args)["params"]
