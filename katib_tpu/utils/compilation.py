"""JAX persistent compilation cache setup.

TPU-native operational win with no reference counterpart: trial processes in
an HPO sweep compile the SAME program shapes over and over (only
hyperparameter *values* differ, and most are baked as runtime scalars, not
shapes). Pointing every trial at a shared on-disk XLA compilation cache turns
the 20-150s first-compile into a cache hit for all subsequent trials —
usually the single largest wall-clock lever for a 50-trial experiment.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# One fixed path when the environment names none: the cache key includes the
# directory, so a path made from tempfile, a pid or the time never hits.
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".katib-tpu",
    "xla-cache",
)
# Persist EVERY compile by default (jax's own default of 1.0s skips
# sub-second programs, which defeats warm-start for small CPU-bench sweeps
# — ISSUE 8 satellite). Operators raise it via the RuntimeConfig field
# `xla_cache_min_compile_seconds` / the env var below when cache-dir churn
# matters more than warm-start.
_DEFAULT_MIN_COMPILE_SECS = 0.0
ENV_MIN_COMPILE_SECS = "KATIB_TPU_XLA_CACHE_MIN_COMPILE_SECONDS"
_initialized = False


def cache_dir() -> str:
    """Where this process keeps its persistent compile cache: JAX's own
    ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed in-checkout path.
    Imports no JAX."""
    return os.environ.get(ENV_CACHE_DIR) or _DEFAULT_DIR


def _accelerator_platform(platforms: str, environ=None, libtpu_present=None) -> bool:
    """Whether the process will (likely) run on an accelerator, decided
    WITHOUT initializing a backend. ``platforms`` is the lowercased
    jax_platforms config/env value ("" = auto-detect). On auto-detect,
    accelerator presence is inferred from ``TPU_NAME`` / an installed libtpu
    — a CPU-only host must not get the SIGILL-prone XLA:CPU cache, and a
    controller that will start trial subprocesses must not take the chip
    just to decide where its cache goes."""
    env = os.environ if environ is None else environ
    if platforms.startswith("cpu"):
        return False
    if platforms:
        return True  # tpu / cuda / ... explicitly selected
    if libtpu_present is None:
        import importlib.util

        libtpu_present = importlib.util.find_spec("libtpu") is not None
    return bool(env.get("TPU_NAME") or libtpu_present)


def accelerator_expected() -> bool:
    """:func:`_accelerator_platform` of this process's own JAX config."""
    import jax

    return _accelerator_platform(
        (jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS") or "").lower()
    )


def min_compile_seconds_from_env(default: float = _DEFAULT_MIN_COMPILE_SECS) -> float:
    """The persisted-entry threshold: RuntimeConfig stamps
    ``xla_cache_min_compile_seconds`` into the environment (so trial
    subprocesses and lazy enables agree); a malformed value keeps the
    default rather than crashing at import."""
    raw = os.environ.get(ENV_MIN_COMPILE_SECS, "")
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def enable_compilation_cache() -> str:
    """Idempotently enable the persistent cache; returns the cache dir.

    Accelerator platforms only: XLA:CPU persists AOT results keyed loosely
    enough that entries written on a host with different CPU features load
    with a SIGILL warning — and CPU compiles are cheap anyway.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it and no
    directory is set from code; otherwise the cache goes to the fixed
    in-checkout path.

    The platform check reads config/env, NEVER ``jax.default_backend()``:
    probing the backend initializes it, and the process that initializes
    the TPU backend owns the chip — a controller whose trials run as
    subprocesses must stay off it."""
    global _initialized
    import jax

    directory = cache_dir()
    if _initialized:
        return directory
    if not accelerator_expected():
        _initialized = True
        return directory
    if not os.environ.get(ENV_CACHE_DIR):
        os.makedirs(directory, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_seconds_from_env()
    )
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _initialized = True
    return directory
