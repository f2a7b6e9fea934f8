"""Deterministic fault injection for the supervised device plane (ISSUE 12).

The chaos harness is how the device plane's failure paths stay tested
without real hardware dying on cue: wedged backend probes, mid-sweep device
revocation, and process kills are *scheduled* ahead of time — keyed by the
plane's monotonic lease-grant counter and per-lease heartbeat counts, never
by wall clock or randomness at decision time — so a chaos run is exactly
reproducible.

Activation is gated behind ``KATIB_TPU_CHAOS`` (a directive string) or a
programmatic :func:`install`; when neither is set every hook below is one
``is None`` check. Directive grammar (``;`` or ``,`` separated)::

    KATIB_TPU_CHAOS="seed=7;wedge_probe=2;revoke=3@2;revoke=5;kill=4@1"

- ``seed=N``        — deterministic device choice within a revoked lease
- ``wedge_probe=N`` — the first N backend probe attempts wedge (hang past
                      the bounded timeout, surfacing the cached-verdict
                      path exactly like a runtime that never starts)
- ``revoke=G[@H]``  — the G-th lease granted by the plane loses one device
                      after its H-th heartbeat (default H=1)
- ``kill=G[@H]``    — the G-th lease's holder is hard-killed after its
                      H-th heartbeat (process-death injection; the holder
                      requeues through the normal loss machinery)
- ``kill_controller=N`` — the controller SIGKILLs ITSELF right after its
                      recovery journal's N-th append of this process
                      (controller/recovery.py) — the hard-crash injection
                      the controller-kill chaos harness drives. Counter-
                      keyed like the lease-grant directives: deterministic
                      per controller incarnation, never wall-clock. Only
                      ever set on a subprocess controller (a harness
                      driver, ``bench.py controller_kill_recovery``) —
                      in-process it would kill the test runner.

The same plan object doubles as the standing bench's fault-injection knob:
``bench.py device_chaos_recovery`` installs one programmatically and
asserts zero lost observations across the injected faults.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

ENV_CHAOS = "KATIB_TPU_CHAOS"

# lease-grant actions the plane executes on the scheduled heartbeat
ACTION_REVOKE = "revoke"
ACTION_KILL = "kill"


@dataclass
class ChaosPlan:
    """One deterministic fault schedule. Counters live here (not in the
    plane) so a plan is single-use: re-running a scenario installs a fresh
    plan and replays the identical schedule."""

    seed: int = 0
    wedge_probes: int = 0
    # 1-based lease-grant index -> (action, heartbeat count before it fires)
    grant_actions: Dict[int, Tuple[str, int]] = field(default_factory=dict)
    # 1-based journal-append index at which the controller SIGKILLs itself
    # (0 = off); one-shot, keyed by the RecoveryJournal's per-process counter
    kill_controller: int = 0

    def __post_init__(self) -> None:
        self._lock = threading.Lock()
        self._grants = 0
        self._wedges_left = int(self.wedge_probes)
        self._controller_killed = False

    # -- probe wedging -------------------------------------------------------

    def take_probe_wedge(self) -> bool:
        """True exactly ``wedge_probes`` times: the caller must treat this
        probe attempt as wedged (hung past its bounded timeout)."""
        with self._lock:
            if self._wedges_left > 0:
                self._wedges_left -= 1
                return True
            return False

    # -- lease-grant scheduling ----------------------------------------------

    def next_grant(self) -> Optional[Tuple[str, int, int]]:
        """Advance the grant counter; returns (action, heartbeats, pick)
        when this grant is scheduled for a fault, else None. ``pick`` is
        the deterministic index of the device to revoke within the lease
        (modulo its size, applied by the plane)."""
        with self._lock:
            self._grants += 1
            scheduled = self.grant_actions.get(self._grants)
            if scheduled is None:
                return None
            action, beats = scheduled
            return action, max(int(beats), 1), (self.seed + self._grants)

    @property
    def grants_seen(self) -> int:
        with self._lock:
            return self._grants

    # -- controller-kill scheduling ------------------------------------------

    def take_controller_kill(self, appended: int) -> bool:
        """True exactly once, at (or past — a plan installed mid-flight
        still fires) the scheduled journal append. The caller SIGKILLs the
        process, so "once" only matters for plans consulted in-process by
        tests."""
        with self._lock:
            if self.kill_controller <= 0 or self._controller_killed:
                return False
            if appended < self.kill_controller:
                return False
            self._controller_killed = True
            return True


class ChaosParseError(ValueError):
    pass


def parse_plan(directives: str) -> ChaosPlan:
    """Parse the ``KATIB_TPU_CHAOS`` directive grammar. Unknown or
    malformed directives raise — a typo'd chaos schedule silently doing
    nothing would defeat the test that relies on it."""
    plan = ChaosPlan()
    for raw in directives.replace(",", ";").split(";"):
        item = raw.strip()
        if not item:
            continue
        key, sep, value = item.partition("=")
        key = key.strip().lower()
        if not sep:
            raise ChaosParseError(f"chaos directive {item!r} is not key=value")
        value = value.strip()
        try:
            if key == "seed":
                plan.seed = int(value)
            elif key == "wedge_probe":
                plan.wedge_probes = int(value)
                plan._wedges_left = plan.wedge_probes
            elif key in (ACTION_REVOKE, ACTION_KILL):
                grant, _, beats = value.partition("@")
                plan.grant_actions[int(grant)] = (key, int(beats or "1"))
            elif key == "kill_controller":
                plan.kill_controller = int(value)
            else:
                raise ChaosParseError(f"unknown chaos directive {key!r}")
        except ValueError as e:
            if isinstance(e, ChaosParseError):
                raise
            raise ChaosParseError(f"malformed chaos directive {item!r}: {e}")
    return plan


# -- process-wide installation ------------------------------------------------

_state_lock = threading.Lock()
_PLAN: Optional[ChaosPlan] = None
_ENV_LOADED = False


def install(plan: Optional[ChaosPlan]) -> None:
    """Install (or clear, with None) the active plan programmatically —
    the bench/test entry point; wins over the environment."""
    global _PLAN, _ENV_LOADED
    with _state_lock:
        _PLAN = plan
        _ENV_LOADED = True  # explicit install pins the decision


def reset() -> None:
    """Test hook: forget the installed plan AND the env parse, so the next
    active() re-reads ``KATIB_TPU_CHAOS``."""
    global _PLAN, _ENV_LOADED
    with _state_lock:
        _PLAN = None
        _ENV_LOADED = False


def active() -> Optional[ChaosPlan]:
    """The installed plan, lazily parsed from ``KATIB_TPU_CHAOS`` on first
    consult. None (the overwhelmingly common case) costs one lock-free-ish
    check per call site."""
    global _PLAN, _ENV_LOADED
    with _state_lock:
        if _ENV_LOADED:
            return _PLAN
        _ENV_LOADED = True
        raw = os.environ.get(ENV_CHAOS, "").strip()
        if raw and raw not in ("0", "false", "off"):
            _PLAN = parse_plan(raw)
        return _PLAN
