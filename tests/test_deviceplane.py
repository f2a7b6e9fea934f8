"""Supervised device plane (ISSUE 12): leases, zombie reclaim, device-loss
preemption, backend failover, chaos injection, and legacy byte-identity.

Covers the tentpole contracts of katib_tpu/controller/deviceplane.py plus
the KTI304 analyzer rule and the `katib-tpu devices` CLI. The fused-pack
variant (gang loses a device mid-demux) lives in test_population.py; the
bench-level acceptance scenario is device_chaos_recovery in bench.py.
"""

import json
import os
import threading
import time

import pytest

from katib_tpu.api import (
    AlgorithmSpec,
    ExperimentSpec,
    FeasibleSpace,
    ObjectiveSpec,
    ObjectiveType,
    ParameterSpec,
    ParameterType,
    TrialTemplate,
)
from katib_tpu.api.status import TrialCondition
from katib_tpu.config import KatibConfig
from katib_tpu.controller import deviceplane
from katib_tpu.controller.deviceplane import DevicePlane
from katib_tpu.controller.experiment import ExperimentController
from katib_tpu.controller.events import EventRecorder, MetricsRegistry
from katib_tpu.utils import chaos


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset()
    yield
    chaos.reset()


def _spec(name, fn, n_trials=2, parallel=2, num_devices=1, params=None):
    spec = ExperimentSpec(
        name=name,
        parameters=params
        or [
            ParameterSpec(
                "x", ParameterType.DOUBLE, FeasibleSpace(min="0.1", max="1.0")
            )
        ],
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
        ),
        algorithm=AlgorithmSpec("random", algorithm_settings=[]),
        trial_template=TrialTemplate(function=fn),
        max_trial_count=n_trials,
        parallel_trial_count=parallel,
    )
    spec.trial_template.resources.num_devices = num_devices
    return spec


def _quiet_config(**runtime):
    cfg = KatibConfig()
    cfg.runtime.telemetry = False
    cfg.runtime.compile_service = False
    for k, v in runtime.items():
        setattr(cfg.runtime, k, v)
    return cfg


# ---------------------------------------------------------------------------
# chaos plan parsing + scheduling
# ---------------------------------------------------------------------------


class TestChaosPlan:
    def test_parse_full_grammar(self):
        plan = chaos.parse_plan("seed=7;wedge_probe=2;revoke=3@2,kill=5")
        assert plan.seed == 7
        assert plan.wedge_probes == 2
        assert plan.grant_actions == {3: ("revoke", 2), 5: ("kill", 1)}

    def test_malformed_directives_raise(self):
        with pytest.raises(chaos.ChaosParseError):
            chaos.parse_plan("revoke")
        with pytest.raises(chaos.ChaosParseError):
            chaos.parse_plan("frobnicate=1")
        with pytest.raises(chaos.ChaosParseError):
            chaos.parse_plan("revoke=x@y")

    def test_counters_are_deterministic_and_single_use(self):
        plan = chaos.parse_plan("wedge_probe=1;revoke=2@3")
        assert plan.take_probe_wedge() is True
        assert plan.take_probe_wedge() is False  # credit consumed
        assert plan.next_grant() is None         # grant 1: nothing scheduled
        action, beats, _pick = plan.next_grant()  # grant 2
        assert (action, beats) == ("revoke", 3)
        assert plan.next_grant() is None

    def test_kill_controller_directive(self):
        """ISSUE 14: ``kill_controller=N`` fires exactly once, at (or past)
        the N-th journal append of the process — counter-keyed like the
        lease-grant directives, never wall-clock."""
        plan = chaos.parse_plan("kill_controller=3")
        assert plan.kill_controller == 3
        assert plan.take_controller_kill(1) is False
        assert plan.take_controller_kill(2) is False
        assert plan.take_controller_kill(3) is True
        assert plan.take_controller_kill(4) is False  # one-shot
        # off by default: the plain grammar never kills the controller
        assert chaos.parse_plan("seed=1").take_controller_kill(99) is False
        with pytest.raises(chaos.ChaosParseError):
            chaos.parse_plan("kill_controller=x")

    def test_env_activation_and_reset(self, monkeypatch):
        monkeypatch.setenv(chaos.ENV_CHAOS, "wedge_probe=1")
        chaos.reset()
        plan = chaos.active()
        assert plan is not None and plan.wedge_probes == 1
        chaos.reset()
        monkeypatch.delenv(chaos.ENV_CHAOS)
        assert chaos.active() is None


# ---------------------------------------------------------------------------
# plane-level lease mechanics
# ---------------------------------------------------------------------------


class TestLeases:
    def _plane(self, n=4, **kw):
        events, metrics = EventRecorder(), MetricsRegistry()
        plane = DevicePlane(events=events, metrics=metrics, **kw)
        plane.adopt_pool(list(range(n)))
        return plane, events, metrics

    def test_grant_release_roundtrip(self):
        plane, _, metrics = self._plane()
        taken = plane.acquire(3, holder="t1", experiment="e1")
        assert len(taken) == 3 and plane.free_count == 1 and plane.total == 4
        assert plane.acquire(2) is None  # all-or-nothing
        assert sorted(plane.release(taken)) == sorted(taken)
        assert plane.free_count == 4
        assert 'katib_device_lease_granted_total 1.0' in metrics.render()

    def test_lost_device_never_returns_to_pool(self):
        plane, events, _ = self._plane()
        taken = plane.acquire(2, holder="t1")
        assert plane.lose_device(taken[0], "test loss") is True
        assert plane.lose_device(taken[0], "again") is False  # idempotent
        returned = plane.release(taken)
        assert returned == [taken[1]]
        assert plane.free_count == 3 and plane.total == 3
        assert any(e.reason == "DeviceLost" for e in plane.events.list(""))

    def test_loss_handler_fires_for_leased_devices_only(self):
        plane, _, _ = self._plane()
        seen = []
        plane.set_loss_handler(lambda devs, reason: seen.append((devs, reason)))
        free_device = plane.acquire(1, holder="t1")  # device 0 leased
        plane.lose_device(1, "free-pool loss")       # device 1 is free
        assert seen == []
        plane.lose_device(free_device[0], "leased loss")
        assert seen == [([free_device[0]], "leased loss")]

    def test_zombie_lease_expiry_reclaims_devices(self):
        plane, events, metrics = self._plane(zombie_lease_seconds=0.05)
        reclaim_ping = []
        plane.set_pool_changed_handler(lambda: reclaim_ping.append(1))
        taken = plane.acquire(4, holder="zombie-t")
        plane.mark_zombie(taken, holder="zombie-t")
        assert plane.free_count == 0 and plane.zombie_device_count() == 4
        plane.tick(now=time.time() + 1.0)
        assert plane.free_count == 4
        assert plane.zombie_device_count() == 0
        assert reclaim_ping, "pool-changed handler never fired"
        assert any(
            e.reason == "DeviceLeaseRevoked" for e in events.list("")
        )
        assert "katib_device_lease_revoked_total 1.0" in metrics.render()
        # the zombie thread finally exits: its release is a no-op
        assert plane.release(taken) == []
        assert plane.free_count == 4

    def test_heartbeat_miss_revokes_lease(self):
        plane, events, _ = self._plane(heartbeat_timeout_seconds=0.05)
        lost = []
        plane.set_loss_handler(lambda devs, reason: lost.append(reason))
        plane.acquire(2, holder="quiet-t")
        plane.tick(now=time.time() + 1.0)
        assert plane.free_count == 4  # holder presumed dead, chips recovered
        assert lost and "heartbeat" in lost[0]
        assert any(e.reason == "DeviceLeaseRevoked" for e in events.list(""))

    def test_heartbeats_keep_lease_alive(self):
        plane, _, _ = self._plane(heartbeat_timeout_seconds=10.0)
        plane.acquire(2, holder="alive-t")
        plane.heartbeat("alive-t")
        plane.tick()
        assert plane.free_count == 2  # still held

    def test_failover_swaps_in_fallback_pool(self):
        plane, events, metrics = self._plane(n=2)
        for d in (0, 1):
            plane.lose_device(d, "backend died")
        assert plane.backend == "cpu-fallback"
        assert plane.free_count == 2  # same-size synthetic pool
        assert any(e.reason == "BackendFailedOver" for e in events.list(""))
        assert "katib_backend_failover_total 1.0" in metrics.render()
        # the chain is consumed: a second total loss has nowhere to go
        for d in list(plane.snapshot()["free"]):
            plane.lose_device(d, "fallback died too")
        assert plane.free_count == 0

    def test_real_device_pool_gets_no_synthetic_chain(self):
        """A slot named ``cpu-slot-i`` names no device: a trial placed on one
        would run untracked on the default backend (chip 0 of a TPU host).
        Losing every real device parks the work and says so, once."""

        class Chip:
            platform = "tpu"

        events, metrics = EventRecorder(), MetricsRegistry()
        plane = DevicePlane(events=events, metrics=metrics)
        chips = [Chip(), Chip()]
        plane.adopt_pool(chips, backend="tpu")
        for chip in chips:
            plane.lose_device(chip, "backend died")
        assert plane.backend == "tpu" and plane.free_count == 0
        reasons = [e.reason for e in events.list("")]
        assert "BackendFailedOver" not in reasons
        assert reasons.count("DevicePoolExhausted") == 1
        assert "katib_backend_failover_total 1.0" not in metrics.render()

    def test_failover_disabled_leaves_pool_empty(self):
        plane, events, _ = self._plane(n=1, failover=False)
        plane.lose_device(0, "gone")
        assert plane.free_count == 0
        assert not any(e.reason == "BackendFailedOver" for e in events.list(""))

    def test_chaos_revocation_fires_on_scheduled_heartbeat(self):
        chaos.install(chaos.parse_plan("seed=1;revoke=1@2"))
        plane, events, _ = self._plane()
        taken = plane.acquire(2, holder="t1")
        plane.heartbeat("t1")
        assert plane.total == 4  # beat 1: not yet
        plane.heartbeat("t1")
        assert plane.total == 3  # beat 2: one device revoked
        assert len(plane.release(taken)) == 1
        assert any(
            e.reason == "DeviceLost" and "chaos" in e.message
            for e in events.list("")
        )

    def test_chaos_kill_fires_kill_handler(self):
        chaos.install(chaos.parse_plan("kill=1@1"))
        plane, _, _ = self._plane()
        killed = []
        plane.set_kill_handler(killed.append)
        plane.acquire(1, holder="doomed")
        plane.heartbeat("doomed")
        assert killed == ["doomed"]

    def test_snapshot_persists_atomically(self, tmp_path):
        plane = DevicePlane(persist_dir=str(tmp_path))
        plane.adopt_pool([0, 1])
        plane.acquire(1, holder="t1", experiment="e1")
        with open(tmp_path / DevicePlane.STATE_FILE) as f:
            snap = json.load(f)
        assert snap["freeCount"] == 1
        assert snap["leases"][0]["holder"] == "t1"
        assert snap["leases"][0]["state"] == "active"

    def test_terminal_leases_are_pruned(self):
        plane, _, _ = self._plane(n=1)
        plane.TERMINAL_LEASES_KEPT = 3
        for i in range(10):
            taken = plane.acquire(1, holder=f"t{i}")
            plane.release(taken)
        assert len(plane.snapshot()["leases"]) <= 4


# ---------------------------------------------------------------------------
# backend-loss signatures + bounded acquisition
# ---------------------------------------------------------------------------


class TestBackendAcquisition:
    def test_is_backend_loss_is_conservative(self):
        assert deviceplane.is_backend_loss(
            "jaxlib.xla_extension.XlaRuntimeError: INTERNAL: device lost"
        )
        assert deviceplane.is_backend_loss("DEADLINE_EXCEEDED while fetching")
        assert not deviceplane.is_backend_loss("ValueError: bad hparam")
        assert not deviceplane.is_backend_loss(None)
        assert not deviceplane.is_backend_loss("")

    def test_wedged_probe_is_bounded_and_verdict_cached(self):
        from katib_tpu.utils import backend as backend_mod

        chaos.install(chaos.parse_plan("wedge_probe=4"))
        backend_mod.reset_probe_state()
        events = EventRecorder()
        try:
            t0 = time.time()
            devices, diag = deviceplane.acquire_backend(
                timeout_seconds=30.0, retries=2, events=events
            )
            elapsed = time.time() - t0
            # both attempts wedged (chaos): verdict False, bounded, no hang
            assert devices is None and "probe" in diag
            assert elapsed < 5.0
            assert any(
                e.reason == "BackendInitFailed" for e in events.list("")
            )
            # cached verdict: the second acquisition is an immediate None
            t0 = time.time()
            devices, _ = deviceplane.acquire_backend(timeout_seconds=30.0)
            assert devices is None and time.time() - t0 < 0.1
        finally:
            backend_mod.reset_probe_state()

    def test_wedge_then_recovery_within_retries(self):
        from katib_tpu.utils import backend as backend_mod

        chaos.install(chaos.parse_plan("wedge_probe=1"))
        backend_mod.reset_probe_state()
        try:
            devices, diag = deviceplane.acquire_backend(
                timeout_seconds=30.0, retries=2
            )
            # attempt 1 wedged, attempt 2 reached the (CPU) backend
            assert devices is not None, diag
        finally:
            backend_mod.reset_probe_state()


# ---------------------------------------------------------------------------
# scheduler integration: loss -> preemption -> resume
# ---------------------------------------------------------------------------


INJECT_ONCE = {"done": False}


def _checkpointing_fn(assignments, ctx):
    """6-epoch deterministic curve with per-epoch checkpoints; the first
    execution injects a device loss on its own device after epoch 2."""
    x = float(assignments["x"])
    store = ctx.checkpoint_store()
    restored = store.restore()
    start = int(restored["epoch"]) + 1 if restored else 1
    for epoch in range(start, 7):
        score = x * (1.0 - 0.8 ** epoch)
        store.save(epoch, {"epoch": epoch})
        ctx.report(score=score, epoch=epoch)
        if epoch == 2 and not INJECT_ONCE["done"]:
            INJECT_ONCE["done"] = True
            _checkpointing_fn._plane.lose_device(
                ctx.devices[0], "test injection"
            )


FAIL_ONCE = {"done": False}


def _xla_failing_fn(assignments, ctx):
    if not FAIL_ONCE["done"]:
        FAIL_ONCE["done"] = True
        raise RuntimeError(
            "jaxlib.xla_extension.XlaRuntimeError: INTERNAL: device lost"
        )
    ctx.report(score=1.0)


class TestDeviceLossAsPreemption:
    def test_revoked_device_preempts_and_resumes_from_checkpoint(self, tmp_path):
        INJECT_ONCE["done"] = False
        cfg = _quiet_config(preemption_grace_seconds=5.0)
        c = ExperimentController(
            root_dir=str(tmp_path), devices=list(range(3)), config=cfg
        )
        try:
            _checkpointing_fn._plane = c.device_plane
            c.create_experiment(
                _spec("dl-resume", _checkpointing_fn, n_trials=2, parallel=2)
            )
            exp = c.run("dl-resume", timeout=120)
            assert exp.status.is_succeeded, exp.status.message
            trials = c.state.list_trials("dl-resume")
            assert all(t.condition == TrialCondition.SUCCEEDED for t in trials)
            # zero lost observations: every epoch curve continuous 1..6
            for t in trials:
                steps = [
                    int(float(r.value))
                    for r in c.obs_store.get_observation_log(
                        t.name, metric_name="epoch"
                    )
                ]
                assert steps == list(range(1, 7)), (t.name, steps)
            reasons = [e.reason for e in c.events.list_all()]
            assert "DeviceLost" in reasons
            preempted = [
                e for e in c.events.list("dl-resume")
                if e.reason == "TrialPreempted"
            ]
            assert preempted and "resumes from checkpoint" in preempted[0].message
            # the lost device never came back: pool shrank by exactly one
            assert c.scheduler.allocator.total == 2
        finally:
            c.close()

    def test_xla_runtime_error_converts_to_clean_rerun(self, tmp_path):
        FAIL_ONCE["done"] = False
        c = ExperimentController(
            root_dir=str(tmp_path), devices=list(range(3)),
            config=_quiet_config(),
        )
        try:
            c.create_experiment(
                _spec("dl-xla", _xla_failing_fn, n_trials=1, parallel=1)
            )
            exp = c.run("dl-xla", timeout=120)
            assert exp.status.is_succeeded, exp.status.message
            (trial,) = c.state.list_trials("dl-xla")
            assert trial.condition == TrialCondition.SUCCEEDED
            reasons = [e.reason for e in c.events.list_all()]
            assert "DeviceLost" in reasons
            assert "TrialPreempted" in reasons
            # no checkpoint at the failure: the re-run started clean and the
            # gang's device was retired from the pool
            assert c.scheduler.allocator.total == 2
        finally:
            c.close()

    def test_plain_failure_is_not_converted(self, tmp_path):
        def bad_fn(assignments, ctx):
            raise ValueError("genuinely broken trial code")

        c = ExperimentController(
            root_dir=str(tmp_path), devices=list(range(2)),
            config=_quiet_config(),
        )
        try:
            c.create_experiment(_spec("dl-plain", bad_fn, n_trials=1, parallel=1))
            c.run("dl-plain", timeout=60)
            (trial,) = c.state.list_trials("dl-plain")
            assert trial.condition == TrialCondition.FAILED
            assert "DeviceLost" not in [e.reason for e in c.events.list_all()]
            assert c.scheduler.allocator.total == 2  # nothing retired
        finally:
            c.close()

    def test_whole_backend_loss_fails_over_and_sweep_completes(self, tmp_path):
        def quick_fn(assignments, ctx):
            ctx.report(score=float(assignments["x"]))

        c = ExperimentController(
            root_dir=str(tmp_path), devices=list(range(2)),
            config=_quiet_config(),
        )
        try:
            for d in (0, 1):
                c.device_plane.lose_device(d, "backend died while idle")
            assert c.device_plane.backend == "cpu-fallback"
            c.create_experiment(_spec("dl-failover", quick_fn, n_trials=3, parallel=2))
            exp = c.run("dl-failover", timeout=60)
            assert exp.status.is_succeeded, exp.status.message
            assert "BackendFailedOver" in [e.reason for e in c.events.list_all()]
        finally:
            c.close()


# ---------------------------------------------------------------------------
# zombie quarantine: lease expiry is an actual reclaim path
# ---------------------------------------------------------------------------


class TestZombieReclaim:
    def test_abandoned_trial_devices_are_reclaimed_and_reused(self, tmp_path):
        """The ISSUE 12 satellite: an abandoned zombie trial's devices used
        to be counted in _quarantined forever; with the plane they come
        back at lease expiry and a waiting gang dispatches on them."""
        hang = threading.Event()

        def hanging_fn(assignments, ctx):
            hang.wait(60)  # never reports, never honors the kill

        def quick_fn(assignments, ctx):
            ctx.report(score=1.0)

        cfg = _quiet_config(device_lease_seconds=0.5)
        c = ExperimentController(
            root_dir=str(tmp_path), devices=list(range(2)), config=cfg
        )
        try:
            c.scheduler.KILL_GRACE_SECONDS = 0.2
            spec = _spec("zombie", hanging_fn, n_trials=1, parallel=1, num_devices=2)
            c.create_experiment(spec)
            c.reconcile("zombie")
            deadline = time.time() + 10
            while time.time() < deadline and not c.state.list_trials("zombie"):
                time.sleep(0.02)
            (trial,) = c.state.list_trials("zombie")
            while time.time() < deadline and c.scheduler.allocator.free_count > 0:
                time.sleep(0.02)
            c.scheduler.kill(trial.name)  # ignored -> abandoned after grace
            while time.time() < deadline and c.scheduler.quarantined_count == 0:
                time.sleep(0.05)
            assert c.scheduler.quarantined_count == 2
            # lease expiry reclaims the chips even though the thread lives
            while time.time() < deadline and c.scheduler.allocator.free_count < 2:
                time.sleep(0.05)
            assert c.scheduler.allocator.free_count == 2
            assert c.scheduler.quarantined_count == 0
            assert any(
                e.reason == "DeviceLeaseRevoked" for e in c.events.list_all()
            )
            # and a new gang actually runs on the reclaimed devices
            c.create_experiment(
                _spec("after", quick_fn, n_trials=1, parallel=1, num_devices=2)
            )
            exp = c.run("after", timeout=60)
            assert exp.status.is_succeeded, exp.status.message
        finally:
            hang.set()
            c.close()


# ---------------------------------------------------------------------------
# legacy byte-identity (KATIB_TPU_DEVICE_PLANE=0)
# ---------------------------------------------------------------------------


def _deterministic_fn(assignments, ctx):
    x = float(assignments["x"])
    for epoch in range(1, 4):
        ctx.report(score=x * epoch, epoch=epoch)


class TestLegacyIdentity:
    def _run(self, root, env_off, monkeypatch):
        if env_off:
            monkeypatch.setenv("KATIB_TPU_DEVICE_PLANE", "0")
        else:
            monkeypatch.delenv("KATIB_TPU_DEVICE_PLANE", raising=False)
        c = ExperimentController(root_dir=root, devices=list(range(4)))
        try:
            spec = _spec("legacy-id", _deterministic_fn, n_trials=4, parallel=2)
            spec.algorithm.algorithm_settings = []
            spec.algorithm.algorithm_name = "grid"
            spec.parameters = [
                ParameterSpec(
                    "x", ParameterType.DOUBLE,
                    FeasibleSpace(min="0.1", max="0.4", step="0.1"),
                )
            ]
            c.create_experiment(spec)
            exp = c.run("legacy-id", timeout=120)
            assert exp.status.is_succeeded
            rows = {}
            for t in sorted(c.state.list_trials("legacy-id"), key=lambda t: t.name):
                rows[t.assignments_dict()["x"]] = [
                    (r.metric_name, r.value)
                    for r in c.obs_store.get_observation_log(t.name)
                ]
            return {
                "plane": c.device_plane,
                "scheduler_plane": c.scheduler.device_plane,
                "rows": rows,
                "conditions": sorted(
                    t.condition.value for t in c.state.list_trials("legacy-id")
                ),
                "events": sorted(
                    e.reason
                    for e in c.events.list_all()
                    if e.reason.startswith(("Device", "Backend"))
                ),
            }
        finally:
            c.close()

    def test_env_off_restores_legacy_allocator_byte_identically(
        self, tmp_path, monkeypatch
    ):
        on = self._run(str(tmp_path / "on"), env_off=False, monkeypatch=monkeypatch)
        off = self._run(str(tmp_path / "off"), env_off=True, monkeypatch=monkeypatch)
        # plane off: nothing constructed, no plane events, no state dir
        assert off["plane"] is None and off["scheduler_plane"] is None
        assert off["events"] == []
        assert not os.path.exists(str(tmp_path / "off" / "deviceplane"))
        # plane on (default): constructed and persisted
        assert on["plane"] is not None
        assert os.path.exists(str(tmp_path / "on" / "deviceplane"))
        # identical sweep results either way — the observation rows are
        # byte-identical per assignment, conditions match
        assert on["rows"] == off["rows"]
        assert on["conditions"] == off["conditions"]

    def test_legacy_allocator_semantics_without_plane(self):
        from katib_tpu.controller.scheduler import DeviceAllocator

        alloc = DeviceAllocator(list(range(4)))
        assert alloc.total == 4
        taken = alloc.acquire(3, holder="ignored", experiment="ignored")
        assert taken == [0, 1, 2] and alloc.free_count == 1
        assert alloc.acquire(2) is None
        alloc.release(taken)
        assert alloc.free_count == 4 and alloc.total == 4


# ---------------------------------------------------------------------------
# KTI304: unbounded device probes
# ---------------------------------------------------------------------------


class TestKTI304:
    def test_seeded_violations_are_flagged(self):
        from katib_tpu.analysis.engine import check_source

        src = (
            "import jax\n"
            "def f():\n"
            "    return jax.devices()[0]\n"
            "def g():\n"
            "    return jax.local_devices()\n"
        )
        found = check_source(src, path="katib_tpu/models/example.py")
        assert [f.rule for f in found] == ["KTI304", "KTI304"]
        assert found[0].line == 3 and found[1].line == 5

    def test_backend_module_is_exempt(self):
        from katib_tpu.analysis.engine import check_source

        src = "import jax\ndevs = jax.local_devices()\n"
        assert check_source(src, path="katib_tpu/utils/backend.py") == []

    def test_clean_twin_passes(self):
        from katib_tpu.analysis.engine import check_source

        src = (
            "from katib_tpu.utils.backend import bounded_devices\n"
            "def f():\n"
            "    devices = bounded_devices()\n"
            "    return devices[0] if devices else None\n"
        )
        assert check_source(src, path="katib_tpu/models/example.py") == []


# ---------------------------------------------------------------------------
# CLI: katib-tpu devices
# ---------------------------------------------------------------------------


class TestDevicesCli:
    def test_offline_snapshot_table(self, tmp_path, capsys):
        from katib_tpu import cli

        plane = DevicePlane(persist_dir=str(tmp_path / "deviceplane"))
        plane.adopt_pool(list(range(3)))
        taken = plane.acquire(2, holder="trial-a", experiment="e1")
        plane.heartbeat("trial-a")
        plane.lose_device(taken[0], "test")
        rc = cli.main(["--root", str(tmp_path), "devices"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "backend: external" in out
        assert "trial-a" in out and "active" in out
        assert "lost: 1" in out

    def test_missing_snapshot_errors(self, tmp_path, capsys):
        from katib_tpu import cli

        rc = cli.main(["--root", str(tmp_path), "devices"])
        assert rc == 1
        assert "no persisted device-plane state" in capsys.readouterr().err
