"""Supervised device plane — leased, revocable device sets (ISSUE 12).

The bench trajectory's biggest losses were environmental, not algorithmic:
wedged TPU probes burned 150s×N per round, and a device dying mid-sweep
crashed the whole run. Upstream Katib survives this class of failure
because Kubernetes owns device health and reschedules pods; this module is
the single-process equivalent, promoting PR 8's ``bounded_local_devices``
band-aid into a plane that OWNS backend acquisition and device custody:

- **Acquisition** — :func:`acquire_backend` probes the accelerator backend
  with hard timeouts and a cached process-wide verdict (utils/backend.py),
  consulted by the controller, the bench harness, and the telemetry
  sampler; a wedge costs one bounded timeout per process, never minutes
  per call site.
- **Leases** — the scheduler's :class:`~.scheduler.DeviceAllocator` is
  rebuilt on top of :meth:`DevicePlane.acquire` / :meth:`DevicePlane.release`:
  every gang allocation is a :class:`DeviceLease` (holder, grant time,
  heartbeats) that the plane can revoke. A zombie trial's lease (the old
  ``_quarantined`` counter) now EXPIRES: past ``zombie_lease_seconds`` the
  chips return to the pool with a ``DeviceLeaseRevoked`` event instead of
  being counted forever.
- **Device loss as preemption** — :meth:`lose_device` (probe failure,
  heartbeat miss, an executor surfacing a backend ``XlaRuntimeError``, or
  chaos injection) removes the device from custody and notifies the
  scheduler's loss handler, which converts the holding gang into a
  checkpoint-preemption through the existing PR 2/9 freeze/resume
  machinery: observations flushed, trial requeued, resumed bit-identically
  on surviving devices when a checkpoint exists, clean re-run otherwise.
- **Failover** — when a pool of *abstract* slots drains to nothing the
  plane swaps in the next pool of the failover chain (same-size synthetic
  slots by default) and emits ``BackendFailedOver``. A pool adopted from
  real devices has no default chain: a synthetic slot names no device, so a
  trial placed on one would run untracked on the default backend (chip 0 of
  a TPU host). Losing every real device parks pending work and says so
  (``DevicePoolExhausted``).

Gating: ``runtime.device_plane`` / ``KATIB_TPU_DEVICE_PLANE=0`` removes
the plane entirely — the allocator then runs the legacy free-list path
byte-identically (asserted by tests/test_deviceplane.py).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..utils import chaos
from ..utils.backend import (
    PROBE_TIMEOUT_SECONDS,
    bounded_local_devices,
    probe_verdict,
)

log = logging.getLogger("katib_tpu.deviceplane")

# lease lifecycle states (docs/device-plane.md)
LEASE_ACTIVE = "active"      # holder is running on the devices
LEASE_ZOMBIE = "zombie"      # holder abandoned (kill-grace expired); expiring
LEASE_REVOKED = "revoked"    # plane reclaimed/voided the lease
LEASE_RELEASED = "released"  # holder returned the devices normally

# Backend-error signatures that mean "the devices died under the program",
# not "the trial's own code failed" — an executor traceback matching one of
# these converts the gang into a preemption instead of a terminal failure.
BACKEND_ERROR_MARKERS = (
    "XlaRuntimeError",
    "DEADLINE_EXCEEDED",
    "failed to legalize operation",
    "Device or slice is unhealthy",
    "device is in an invalid state",
    "TPU initialization failed",
    "Unable to initialize backend",
    "Socket closed",
    "slice health check failed",
)


def is_abstract_pool(devices: Sequence[Any]) -> bool:
    """Ints/strings standing for device slots, not devices: they name no
    chip, do not die with the backend, and a number can size them."""
    return all(isinstance(d, (int, str)) for d in devices)


def is_backend_loss(message: Optional[str]) -> bool:
    """Does this executor failure message carry a backend-death signature?
    Conservative by design: only explicit runtime/transport markers match —
    a trial's own ValueError never converts into a preemption."""
    if not message:
        return False
    return any(marker in message for marker in BACKEND_ERROR_MARKERS)


def acquire_backend(
    timeout_seconds: float = PROBE_TIMEOUT_SECONDS,
    retries: int = 2,
    events=None,
) -> Tuple[Optional[List[Any]], str]:
    """Health-probed backend acquisition with a hard timeout and cached
    verdict — the plane's front door, shared by the controller bootstrap,
    ``bench.py`` round acquisition, and the probe subprocess. Returns
    ``(devices, diagnosis)``; devices is None when the probe failed on a
    host that was never going to use an accelerator (the verdict is cached,
    so every later call in this process is an immediate None). On a host
    that has a chip a failed probe raises instead."""
    devices = bounded_local_devices(
        timeout_seconds=timeout_seconds, retries=retries, events=events
    )
    if devices is None:
        return None, (
            "backend probe failed or hung (verdict cached; see the "
            "BackendInitFailed event for the first failure's reason)"
        )
    platform = getattr(devices[0], "platform", "unknown")
    return devices, f"{len(devices)} {platform} device(s)"


@dataclass
class DeviceLease:
    """One revocable custody grant over a device set."""

    lease_id: int
    holder: str                      # dispatch-unit key (first trial's name)
    experiment: str
    devices: List[Any]
    granted_at: float
    state: str = LEASE_ACTIVE
    heartbeats: int = 0
    last_heartbeat: float = 0.0
    expires_at: Optional[float] = None   # zombie reclaim deadline
    lost: List[Any] = field(default_factory=list)  # devices revoked mid-lease
    # chaos schedule attached at grant time (utils/chaos.py)
    chaos_action: Optional[str] = None
    chaos_beats: int = 0
    chaos_pick: int = 0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "leaseId": self.lease_id,
            "holder": self.holder,
            "experiment": self.experiment,
            "devices": [str(d) for d in self.devices],
            "grantedAt": self.granted_at,
            "state": self.state,
            "heartbeats": self.heartbeats,
            "lastHeartbeat": self.last_heartbeat,
            "expiresAt": self.expires_at,
            "lost": [str(d) for d in self.lost],
        }


class DevicePlane:
    """Leased device custody + health supervision for one controller.

    Thread-safety: one internal lock guards pool/lease state. The loss and
    kill handlers are invoked WITHOUT the plane lock held (the scheduler's
    handler takes its own lock and calls back into :meth:`release`-adjacent
    paths), so the only lock edge is scheduler→plane.
    """

    def __init__(
        self,
        events=None,
        metrics=None,
        probe_timeout_seconds: float = 15.0,
        reprobe_interval_seconds: float = 0.0,
        zombie_lease_seconds: float = 60.0,
        heartbeat_timeout_seconds: float = 0.0,
        failover: bool = True,
        persist_dir: Optional[str] = None,
        tick_interval_seconds: float = 1.0,
    ) -> None:
        self.events = events
        self.metrics = metrics
        self.probe_timeout_seconds = probe_timeout_seconds
        self.reprobe_interval_seconds = reprobe_interval_seconds
        self.zombie_lease_seconds = zombie_lease_seconds
        self.heartbeat_timeout_seconds = heartbeat_timeout_seconds
        self.failover_enabled = failover
        self.persist_dir = persist_dir
        self.tick_interval_seconds = tick_interval_seconds
        self._lock = threading.Lock()
        self._free: List[Any] = []
        self._backend = "unattached"
        self._leases: Dict[int, DeviceLease] = {}
        self._device_lease: Dict[Any, DeviceLease] = {}
        self._lease_seq = 0
        self._lost_total = 0
        self._failovers = 0
        self._last_probe = 0.0
        self._loss_handler: Optional[Callable[[List[Any], str], None]] = None
        self._kill_handler: Optional[Callable[[str], None]] = None
        self._pool_changed: Optional[Callable[[], None]] = None
        self._shutdown = threading.Event()
        self._supervisor: Optional[threading.Thread] = None
        # failover chain: (backend name, pool factory) tried in order when
        # the active pool drains to zero live devices. The default chain is
        # installed by adopt_pool; tests/bench may override.
        self._fallbacks: List[Tuple[str, Callable[[], List[Any]]]] = []
        self._exhausted = False  # DevicePoolExhausted said once

    # -- pool bootstrap ------------------------------------------------------

    def adopt_pool(self, devices: Sequence[Any], backend: str = "external") -> None:
        """Take custody of the scheduler's resolved device pool. The plane
        does NOT probe jax here — pool resolution (explicit devices, or the
        legacy abstract slots) stays in the scheduler so plane-on and
        plane-off controllers see identical pools; jax probing is the
        health layer (tick/acquire_backend), not the allocation source."""
        with self._lock:
            self._free = list(devices)
            self._backend = backend
            if not self._fallbacks and is_abstract_pool(self._free):
                # An abstract pool (ints/strings standing for slots) fails
                # over to same-size synthetic slots. Real devices get no
                # such chain: see the module docstring.
                n = max(len(self._free), 1)
                self._fallbacks = [
                    ("cpu-fallback", lambda n=n: [f"cpu-slot-{i}" for i in range(n)])
                ]
        self._persist()

    def set_fallbacks(
        self, fallbacks: Sequence[Tuple[str, Callable[[], List[Any]]]]
    ) -> None:
        with self._lock:
            self._fallbacks = list(fallbacks)

    def set_loss_handler(self, fn: Callable[[List[Any], str], None]) -> None:
        """``fn(devices, reason)`` — called (no plane lock held) when
        devices leave custody while leased; the scheduler converts the
        holding gang into a checkpoint-preemption."""
        self._loss_handler = fn

    def set_kill_handler(self, fn: Callable[[str], None]) -> None:
        """``fn(holder)`` — chaos process-kill injection target."""
        self._kill_handler = fn

    def set_pool_changed_handler(self, fn: Callable[[], None]) -> None:
        """``fn()`` — called after devices re-enter the pool outside the
        normal release path (zombie reclaim, lease revocation, failover),
        so the scheduler re-runs its dispatch pass for waiting gangs."""
        self._pool_changed = fn

    def _notify_pool_changed(self) -> None:
        fn = self._pool_changed
        if fn is not None:
            try:
                fn()
            except Exception:
                log.exception("pool-changed handler failed")

    # -- allocator surface (DeviceAllocator delegates here) ------------------

    def acquire(self, n: int, holder: str = "", experiment: str = "") -> Optional[List[Any]]:
        with self._lock:
            if n > len(self._free):
                return None
            taken, self._free = self._free[:n], self._free[n:]
            self._lease_seq += 1
            lease = DeviceLease(
                lease_id=self._lease_seq,
                holder=holder,
                experiment=experiment,
                devices=list(taken),
                granted_at=time.time(),
                last_heartbeat=time.time(),
            )
            plan = chaos.active()
            if plan is not None:
                scheduled = plan.next_grant()
                if scheduled is not None:
                    lease.chaos_action, lease.chaos_beats, lease.chaos_pick = scheduled
            self._leases[lease.lease_id] = lease
            for d in taken:
                self._device_lease[d] = lease
        if self.metrics is not None:
            self.metrics.inc("katib_device_lease_granted_total")
            self._gauge_leases()
        self._persist()
        return taken

    def release(self, devices: Sequence[Any]) -> List[Any]:
        """Return a gang's devices to the pool. Only devices still in the
        lease's custody come back — revoked/lost members stay gone, and a
        lease the plane already reclaimed (zombie expiry) is a no-op, so
        the late-exiting zombie thread can never double-free chips."""
        returned: List[Any] = []
        with self._lock:
            for d in devices:
                lease = self._device_lease.pop(d, None)
                if lease is None:
                    continue  # reclaimed or lost while leased
                if d not in lease.lost:
                    self._free.append(d)
                    returned.append(d)
                if lease.state in (LEASE_ACTIVE, LEASE_ZOMBIE):
                    lease.state = LEASE_RELEASED
            self._prune_locked()
        if returned and self.metrics is not None:
            self._gauge_leases()
        self._persist()
        return returned

    TERMINAL_LEASES_KEPT = 256

    def _prune_locked(self) -> None:
        """Bound the lease registry: terminal leases beyond the newest
        TERMINAL_LEASES_KEPT are dropped (they exist only for the CLI /
        snapshot history). Caller holds the plane lock."""
        terminal = sorted(
            lid
            for lid, l in self._leases.items()
            if l.state in (LEASE_RELEASED, LEASE_REVOKED)
        )
        excess = max(len(terminal) - self.TERMINAL_LEASES_KEPT, 0)
        for lid in terminal[:excess]:
            del self._leases[lid]

    @property
    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def total(self) -> int:
        """Live devices in custody: free + leased-and-not-lost."""
        with self._lock:
            leased = sum(
                1
                for d, lease in self._device_lease.items()
                if d not in lease.lost
            )
            return len(self._free) + leased

    @property
    def backend(self) -> str:
        with self._lock:
            return self._backend

    # -- zombie leases (the _quarantined reclaim path) -----------------------

    def mark_zombie(self, devices: Sequence[Any], holder: str = "") -> None:
        """An abandoned trial still references these chips: flag its lease
        ZOMBIE with a reclaim deadline. If the worker thread exits first,
        the normal release path runs; past the deadline the plane reclaims
        the chips itself (the old ``_quarantined`` counter leak)."""
        deadline = time.time() + max(self.zombie_lease_seconds, 0.0)
        with self._lock:
            for d in devices:
                lease = self._device_lease.get(d)
                if lease is not None and lease.state == LEASE_ACTIVE:
                    lease.state = LEASE_ZOMBIE
                    lease.expires_at = deadline
        self._persist()

    def zombie_device_count(self) -> int:
        with self._lock:
            return sum(
                len([d for d in l.devices if d not in l.lost])
                for l in self._leases.values()
                if l.state == LEASE_ZOMBIE
            )

    def _reclaim_expired_locked(self, now: float) -> List[DeviceLease]:
        expired = [
            l
            for l in self._leases.values()
            if l.state == LEASE_ZOMBIE
            and l.expires_at is not None
            and now >= l.expires_at
        ]
        for lease in expired:
            lease.state = LEASE_REVOKED
            for d in lease.devices:
                if self._device_lease.get(d) is lease:
                    del self._device_lease[d]
                    if d not in lease.lost:
                        self._free.append(d)
        return expired

    # -- device loss ---------------------------------------------------------

    def lose_device(self, device: Any, reason: str = "injected") -> bool:
        """Remove one device from custody (probe failure, chaos injection,
        executor backend error). A free device just leaves the pool; a
        leased device additionally notifies the loss handler so the holding
        gang preempts. Returns False when the device is unknown (already
        lost, or from a failed-over pool)."""
        handler_args: Optional[Tuple[List[Any], str]] = None
        with self._lock:
            lease = self._device_lease.get(device)
            if lease is not None:
                if device in lease.lost:
                    return False
                lease.lost.append(device)
                handler_args = ([device], reason)
            elif device in self._free:
                self._free.remove(device)
            else:
                return False
            self._lost_total += 1
        log.warning("device %s lost (%s)", device, reason)
        if self.events is not None:
            holder = lease.holder if lease is not None else "(free pool)"
            self.events.event(
                lease.experiment if lease is not None else "",
                "Controller", "deviceplane", "DeviceLost",
                f"device {device} lost ({reason}); held by {holder}",
                warning=True,
            )
        if self.metrics is not None:
            self.metrics.inc("katib_device_lost_total")
            self._gauge_leases()
        if handler_args is not None and self._loss_handler is not None:
            try:
                self._loss_handler(*handler_args)
            except Exception:
                log.exception("device-loss handler failed")
        self._maybe_failover()
        self._persist()
        return True

    def report_executor_failure(self, holder: str, devices: Sequence[Any]) -> bool:
        """An executor surfaced a backend-death signature for this gang:
        mark every still-held device of the allocation lost. Returns True
        when at least one device was in custody (the scheduler then
        converts the failure into a preemption). The loss handler is NOT
        invoked — the failing gang is already unwinding; marking the
        devices keeps them out of the pool at release."""
        lost_any = False
        with self._lock:
            for d in devices:
                lease = self._device_lease.get(d)
                if lease is not None and d not in lease.lost:
                    lease.lost.append(d)
                    self._lost_total += 1
                    lost_any = True
        if lost_any:
            if self.events is not None:
                self.events.event(
                    "", "Controller", "deviceplane", "DeviceLost",
                    f"backend error under {holder}: {len(list(devices))} "
                    "device(s) of its gang marked lost; gang converts to a "
                    "checkpoint-preemption",
                    warning=True,
                )
            if self.metrics is not None:
                self.metrics.inc(
                    "katib_device_lost_total", value=float(len(list(devices)))
                )
                self._gauge_leases()
            self._maybe_failover()
            self._persist()
        return lost_any

    def _maybe_failover(self) -> None:
        """When no live device remains (free or leased), swap in the next
        pool of the failover chain; with no chain left (always the case for
        a pool of real devices) say once that pending work is parked."""
        if not self.failover_enabled:
            return
        with self._lock:
            live = len(self._free) + sum(
                1 for d, l in self._device_lease.items() if d not in l.lost
            )
            if live > 0:
                return
            exhausted = not self._fallbacks
            if exhausted:
                if self._exhausted:
                    return
                self._exhausted = True
                old = self._backend
            else:
                name, factory = self._fallbacks.pop(0)
                try:
                    fresh = list(factory())
                except Exception:
                    log.exception("failover pool factory for %r failed", name)
                    return
                old = self._backend
                self._backend = name
                self._free.extend(fresh)
                self._failovers += 1
        if exhausted:
            log.error(
                "backend %s lost every device and nothing can stand in for "
                "them; pending trials stay queued", old,
            )
            if self.events is not None:
                self.events.event(
                    "", "Controller", "deviceplane", "DevicePoolExhausted",
                    f"backend {old} lost every device and nothing can stand "
                    "in for them; pending trials stay queued until the "
                    "controller is restarted on healthy devices",
                    warning=True,
                )
            return
        log.warning(
            "backend %s lost every device; failed over to %s (%d device(s))",
            old, name, len(fresh),
        )
        if self.events is not None:
            self.events.event(
                "", "Controller", "deviceplane", "BackendFailedOver",
                f"backend {old} lost every device; failed over to {name} "
                f"({len(fresh)} device(s)) — the sweep degrades instead of dying",
                warning=True,
            )
        if self.metrics is not None:
            self.metrics.inc("katib_backend_failover_total")
            self._gauge_leases()
        self._notify_pool_changed()

    # -- heartbeats + chaos triggers -----------------------------------------

    def heartbeat(self, holder: str) -> None:
        """Lease liveness tick, wired into ctx.report via the scheduler.
        Chaos faults scheduled on this lease (revoke/kill after its N-th
        heartbeat) fire here — deterministically, on the holder's own
        report cadence, never on wall clock."""
        fire: Optional[Tuple[str, DeviceLease]] = None
        with self._lock:
            lease = next(
                (
                    l
                    for l in self._leases.values()
                    if l.holder == holder and l.state == LEASE_ACTIVE
                ),
                None,
            )
            if lease is None:
                return
            lease.heartbeats += 1
            lease.last_heartbeat = time.time()
            if lease.chaos_action is not None and lease.heartbeats >= lease.chaos_beats:
                fire = (lease.chaos_action, lease)
                lease.chaos_action = None
        if fire is None:
            return
        action, lease = fire
        if action == chaos.ACTION_REVOKE:
            live = [d for d in lease.devices if d not in lease.lost]
            if live:
                self.lose_device(
                    live[lease.chaos_pick % len(live)], reason="chaos revocation"
                )
        elif action == chaos.ACTION_KILL and self._kill_handler is not None:
            try:
                self._kill_handler(lease.holder)
            except Exception:
                log.exception("chaos kill handler failed")

    # -- supervision ---------------------------------------------------------

    def start(self) -> None:
        if self._supervisor is not None:
            return
        self._supervisor = threading.Thread(
            target=self._run_supervisor, name="deviceplane-supervisor", daemon=True
        )
        self._supervisor.start()

    def stop(self) -> None:
        self._shutdown.set()
        if self._supervisor is not None:
            self._supervisor.join(timeout=5)
            self._supervisor = None

    def _run_supervisor(self) -> None:
        while not self._shutdown.wait(self.tick_interval_seconds):
            try:
                self.tick()
            except Exception:
                log.exception("device plane tick failed")

    def tick(self, now: Optional[float] = None) -> None:
        """One supervision pass: reclaim expired zombie leases, revoke
        heartbeat-missed leases (when the knob is on), and re-probe the
        backend on its interval. Cheap when nothing is due — the default
        1s cadence costs a lock acquisition."""
        now = time.time() if now is None else now
        with self._lock:
            reclaimed = self._reclaim_expired_locked(now)
            missed: List[DeviceLease] = []
            if self.heartbeat_timeout_seconds > 0:
                missed = [
                    l
                    for l in self._leases.values()
                    if l.state == LEASE_ACTIVE
                    and now - l.last_heartbeat > self.heartbeat_timeout_seconds
                ]
        for lease in reclaimed:
            live = [d for d in lease.devices if d not in lease.lost]
            log.warning(
                "zombie lease %d (%s) expired; reclaimed %d device(s)",
                lease.lease_id, lease.holder, len(live),
            )
            if self.events is not None:
                self.events.event(
                    lease.experiment, "Controller", "deviceplane",
                    "DeviceLeaseRevoked",
                    f"zombie lease of {lease.holder} expired after "
                    f"{self.zombie_lease_seconds:.0f}s; {len(live)} device(s) "
                    "reclaimed into the pool",
                    warning=True,
                )
            if self.metrics is not None:
                self.metrics.inc("katib_device_lease_revoked_total")
        for lease in missed:
            self._revoke_lease(lease, reason="lease heartbeat missed")
        if (
            self.reprobe_interval_seconds > 0
            and now - self._last_probe >= self.reprobe_interval_seconds
        ):
            self._last_probe = now
            self._reprobe()
        if reclaimed or missed:
            if self.metrics is not None:
                self._gauge_leases()
            self._notify_pool_changed()
        # heartbeats don't persist (they are per-report hot path); the tick
        # refreshes the offline snapshot once per interval instead
        self._persist()

    def _revoke_lease(self, lease: DeviceLease, reason: str) -> None:
        """Void an ACTIVE lease: its devices count as lost to the holder
        (the loss handler preempts the gang) but return to the pool — the
        hardware is presumed fine, the HOLDER is presumed gone."""
        with self._lock:
            if lease.state != LEASE_ACTIVE:
                return
            lease.state = LEASE_REVOKED
            recovered = []
            for d in lease.devices:
                if self._device_lease.get(d) is lease:
                    del self._device_lease[d]
                    if d not in lease.lost:
                        self._free.append(d)
                        recovered.append(d)
        if self.events is not None:
            self.events.event(
                lease.experiment, "Controller", "deviceplane",
                "DeviceLeaseRevoked",
                f"lease of {lease.holder} revoked ({reason}); "
                f"{len(recovered)} device(s) returned to the pool",
                warning=True,
            )
        if self.metrics is not None:
            self.metrics.inc("katib_device_lease_revoked_total")
        if self._loss_handler is not None:
            try:
                self._loss_handler(list(lease.devices), reason)
            except Exception:
                log.exception("device-loss handler failed")

    def _reprobe(self) -> None:
        """Periodic backend health re-probe. Only meaningful when the pool
        is real accelerator devices AND a probe already succeeded once: a
        previously-healthy backend whose probe now fails means every pooled
        device is gone — lose them all (which triggers failover)."""
        if probe_verdict() is not True:
            return  # never probed / already known dead: nothing to re-check
        try:
            devices, _diag = acquire_backend(
                timeout_seconds=self.probe_timeout_seconds, events=self.events
            )
        except Exception:
            log.exception("backend re-probe failed")
            devices = None
        if devices is not None:
            return
        with self._lock:
            pooled = list(self._free) + [
                d for d, l in self._device_lease.items() if d not in l.lost
            ]
        for d in pooled:
            if not is_abstract_pool([d]):  # abstract slots don't die with jax
                self.lose_device(d, reason="backend re-probe failed")

    # -- observability -------------------------------------------------------

    def _gauge_leases(self) -> None:
        with self._lock:
            active = sum(1 for l in self._leases.values() if l.state == LEASE_ACTIVE)
            zombies = sum(1 for l in self._leases.values() if l.state == LEASE_ZOMBIE)
        self.metrics.set_gauge("katib_device_lease_active", float(active))
        self.metrics.set_gauge("katib_device_lease_zombie", float(zombies))

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            leases = [l.to_dict() for l in self._leases.values()]
            return {
                "backend": self._backend,
                "probeVerdict": {True: "healthy", False: "failed", None: "unprobed"}[
                    probe_verdict()
                ],
                "free": [str(d) for d in self._free],
                "freeCount": len(self._free),
                "lostTotal": self._lost_total,
                "failovers": self._failovers,
                "zombieLeaseSeconds": self.zombie_lease_seconds,
                "heartbeatTimeoutSeconds": self.heartbeat_timeout_seconds,
                "leases": sorted(leases, key=lambda l: l["leaseId"]),
            }

    STATE_FILE = "state.json"

    def _persist(self) -> None:
        """Atomic snapshot under <root>/deviceplane/ so `katib-tpu devices`
        reads lease/health state offline (same pattern as the compile
        registry). Best-effort: persistence must never fail an allocation."""
        if not self.persist_dir:
            return
        try:
            os.makedirs(self.persist_dir, exist_ok=True)
            path = os.path.join(self.persist_dir, self.STATE_FILE)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self.snapshot(), f, indent=2, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            log.debug("device plane snapshot persist failed", exc_info=True)
