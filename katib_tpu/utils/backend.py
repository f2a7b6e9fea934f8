"""The process's first look at its devices, in one place.

The first ``jax.local_devices()`` of a process initializes the backend. It
is called from several threads that must not each pay for, or each report,
a failure: the telemetry sampler tick, the admission pre-flight, a compile
worker. This module makes that first call once on a helper thread, waits
for it up to ``PROBE_TIMEOUT_SECONDS`` — longer than a cold local TPU
runtime takes to start, so the limit only ends a start-up that will never
finish — and caches the process-wide verdict.

A probe that fails on a host that was going to use an accelerator raises
:class:`BackendUnavailable`: nothing below may carry on without the chip.
Only where the process was held to the CPU anyway (``JAX_PLATFORMS=cpu``:
the tests, and the chaos harness that injects probe failures there) does a
failed probe return None, with one ``BackendInitFailed`` warning event.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, List, Optional

log = logging.getLogger("katib_tpu.backend")

# Starting the local TPU runtime takes a quarter of a minute warm and can
# take minutes cold; a limit below that turns a slow start into "no chip".
PROBE_TIMEOUT_SECONDS = 600.0


class BackendUnavailable(RuntimeError):
    """The accelerator this host was going to use could not be reached."""


_state_lock = threading.Lock()
_BACKEND_OK: Optional[bool] = None  # None = not yet probed this process
_EVENT_EMITTED = False


def initialized_local_devices() -> Optional[List[Any]]:
    """``jax.local_devices()`` if a backend is already up, else None. Looks,
    never initializes: the process that initializes the TPU backend owns the
    chip until it exits, and the readers that call this (telemetry sampler,
    admission pre-flight, step statistics) must never be the one to take it
    — a controller whose trials are subprocesses has to stay off the chip."""
    import sys

    if "jax" not in sys.modules:
        return None
    import jax
    from jax._src import xla_bridge  # no public way to ask without initializing

    if not xla_bridge.backends_are_initialized():
        return None
    return jax.local_devices()


def holds_accelerator() -> bool:
    """True when this process has initialized a non-CPU backend: a child it
    starts cannot have the chip."""
    devices = initialized_local_devices()
    return bool(devices) and devices[0].platform != "cpu"


def reset_probe_state() -> None:
    """Test hook: forget the cached verdict + event dedup."""
    global _BACKEND_OK, _EVENT_EMITTED
    with _state_lock:
        _BACKEND_OK = None
        _EVENT_EMITTED = False


def _emit_failed(events, reason: str) -> None:
    global _EVENT_EMITTED
    with _state_lock:
        if _EVENT_EMITTED:
            return
        _EVENT_EMITTED = True
    log.warning("accelerator backend init/probe failed: %s", reason)
    if events is not None:
        try:
            events.event(
                "", "Controller", "backend", "BackendInitFailed",
                f"backend init/probe failed ({reason}); device telemetry "
                "and capacity detection are off for this CPU-held process",
                warning=True,
            )
        except Exception:
            pass


def bounded_local_devices(
    timeout_seconds: float = PROBE_TIMEOUT_SECONDS,
    retries: int = 2,
    backoff_seconds: float = 1.0,
    events=None,
) -> Optional[List[Any]]:
    """``jax.local_devices()`` with a bounded first init.

    Returns the device list. When the probe fails — ``retries`` attempts of
    at most ``timeout_seconds`` each — on a host that was going to use an
    accelerator, raises :class:`BackendUnavailable`, now and on every later
    call. On a CPU-held process it emits one ``BackendInitFailed`` warning
    event and returns None, now and on every later call. Once a probe
    succeeds, later calls go straight to ``jax.local_devices()`` (the
    backend is initialized; the call is cheap)."""
    global _BACKEND_OK
    with _state_lock:
        verdict = _BACKEND_OK
    if verdict is False:
        return _probe_failed(None, "earlier probe of this process failed")
    if verdict is True:
        import jax

        return jax.local_devices()

    from . import chaos

    last_error = "?"
    for attempt in range(max(int(retries), 1)):
        plan = chaos.active()
        if plan is not None and plan.take_probe_wedge():
            # injected wedge (utils/chaos.py): behave exactly like a probe
            # that hung past its bounded timeout — same error string, same
            # cached-verdict consequences — without burning the wall clock
            last_error = (
                f"probe hung past {timeout_seconds:.0f}s "
                f"(attempt {attempt + 1}; chaos-injected wedge)"
            )
            continue
        box: dict = {}

        def _probe():
            try:
                import jax

                box["devices"] = jax.local_devices()
            except BaseException as e:  # noqa: BLE001 — surfaced as the reason
                box["error"] = f"{type(e).__name__}: {e}"

        t = threading.Thread(target=_probe, daemon=True, name="backend-probe")
        t.start()
        t.join(timeout_seconds)
        if t.is_alive():
            last_error = f"probe hung past {timeout_seconds:.0f}s (attempt {attempt + 1})"
        elif "error" in box:
            last_error = box["error"]
        else:
            with _state_lock:
                _BACKEND_OK = True
            return box["devices"]
        if attempt + 1 < max(int(retries), 1):
            time.sleep(backoff_seconds)
    with _state_lock:
        _BACKEND_OK = False
    return _probe_failed(events, last_error)


def _probe_failed(events, reason: str) -> None:
    """None for a CPU-held process, BackendUnavailable where a chip was
    expected (decided from config/env like the compile cache's switch:
    asking the backend is what just failed)."""
    from .compilation import accelerator_expected

    if accelerator_expected():
        raise BackendUnavailable(
            f"accelerator backend unavailable ({reason}); refusing to carry "
            "on without it. Set JAX_PLATFORMS=cpu to run on the CPU on purpose."
        )
    _emit_failed(events, reason)
    return None


def bounded_devices(
    timeout_seconds: float = PROBE_TIMEOUT_SECONDS,
    retries: int = 2,
    events=None,
) -> Optional[List[Any]]:
    """``jax.devices()`` (the global view) behind the same bounded first
    init and cached verdict as :func:`bounded_local_devices`.

    This is the one sanctioned route to the global device list — the
    analyzer's KTI304 rule flags direct ``jax.devices()`` /
    ``jax.local_devices()`` calls outside this module, so that the first
    probe of a process, its verdict and its one failure report stay in one
    place. Returns None only as :func:`bounded_local_devices` does."""
    if bounded_local_devices(timeout_seconds, retries, events=events) is None:
        return None
    import jax

    return jax.devices()


def require_devices(
    timeout_seconds: float = PROBE_TIMEOUT_SECONDS,
    retries: int = 2,
    events=None,
) -> List[Any]:
    """:func:`bounded_devices` that raises on every host instead of
    returning None — for call sites (mesh construction, worker bootstrap)
    that cannot proceed without a backend."""
    devices = bounded_devices(timeout_seconds, retries, events=events)
    if not devices:
        raise BackendUnavailable(
            "backend unavailable: the probe failed (see the "
            "BackendInitFailed event for the first failure's reason)"
        )
    return devices


def probe_verdict() -> Optional[bool]:
    """The cached process-wide backend verdict: True (healthy), False
    (failed — every probe call short-circuits), or None (not yet probed).
    Read-only view for the device plane's health snapshot."""
    with _state_lock:
        return _BACKEND_OK
