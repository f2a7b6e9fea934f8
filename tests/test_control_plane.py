"""Sharded control plane (ISSUE 15): HTTP/JSON wire protocol, per-experiment
placement leases, replica failover, and the WAL multi-writer store path.

Covers the tentpole's three layers plus the satellites:

- the api.proto-shaped HTTP surface (service/httpapi.py) round-tripping
  through :class:`HttpRemoteObservationStore` with auth, retry/backoff and
  the batched ``ReportManyObservationLogs``;
- the ``report_metrics`` RPC env binding (``KATIB_TPU_RPC_URL``);
- placement: no double-claim between live replicas, capacity bound, fence
  bump on takeover, zombie holders treated dead;
- the SIGKILL failover e2e: one of two REAL replica subprocesses dies
  mid-sweep and its experiments complete on the survivor with zero lost
  observations and rows bit-identical to a fault-free run;
- ``KATIB_TPU_REPLICAS`` unset stays byte-identical to the PR 14 topology
  (root-wide lease + flat journal), asserted by a seeded on-vs-off sweep;
- SQLITE_BUSY hardening: a write landing under a concurrent writer's lock
  retries instead of raising through the durability barrier;
- the ``katib-tpu replicas`` offline CLI and the client router.
"""

import json
import os
import signal
import sqlite3
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from katib_tpu.db.store import MetricLog, SqliteObservationStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRIAL_MODULE = """\
import time

def run_trial(assignments, ctx):
    x = float(assignments["x"])
    for epoch in range(1, {epochs} + 1):
        time.sleep({dwell})
        ctx.report(score=x * (1.0 - 0.8 ** epoch), epoch=epoch)
"""


def _write_trial_module(root, epochs=2, dwell=0.02):
    with open(os.path.join(root, "cp_trial.py"), "w") as f:
        f.write(TRIAL_MODULE.format(epochs=epochs, dwell=dwell))


def _spec(name, n_trials=3, parallel=2):
    step = 0.9 / max(n_trials - 1, 1)
    return {
        "name": name,
        "parameters": [{
            "name": "x", "parameterType": "double",
            "feasibleSpace": {"min": "0.1", "max": "1.0", "step": repr(step)},
        }],
        "objective": {"type": "maximize", "objectiveMetricName": "score"},
        "algorithm": {"algorithmName": "grid"},
        "trialTemplate": {
            "entryPoint": "cp_trial:run_trial",
            "trialParameters": [{"name": "x", "reference": "x"}],
        },
        "maxTrialCount": n_trials,
        "parallelTrialCount": parallel,
        "resumePolicy": "FromVolume",
    }


def _is_done(status_doc):
    if not status_doc:
        return False
    return any(
        c.get("type") in ("Succeeded", "Failed") and c.get("status")
        for c in status_doc.get("status", {}).get("conditions", [])
    )


def _rows_by_x(root, names):
    from katib_tpu.db.state import ExperimentStateStore

    state = ExperimentStateStore(os.path.join(root, "state"))
    store = SqliteObservationStore(os.path.join(root, "observations.db"))
    epochs_by, scores_by = {}, {}
    try:
        for name in names:
            state.load(name)
            for t in state.list_trials(name):
                key = (name, t.assignments_dict()["x"])
                epochs_by[key] = [
                    int(float(r.value))
                    for r in store.get_observation_log(t.name, metric_name="epoch")
                ]
                scores_by[key] = [
                    r.value
                    for r in store.get_observation_log(t.name, metric_name="score")
                ]
    finally:
        store.close()
    return epochs_by, scores_by


# -- wire protocol ------------------------------------------------------------


class TestHttpApi:
    def _serve(self, store=None, token=None, metrics=None):
        from katib_tpu.db.store import InMemoryObservationStore
        from katib_tpu.service.httpapi import serve_api
        from katib_tpu.service.rpc import ApiServicer

        store = store if store is not None else InMemoryObservationStore()
        srv = serve_api(
            ApiServicer(store=store), auth_token=token, metrics=metrics
        )
        return srv, store

    def test_observation_roundtrip_with_batched_report_many(self):
        from katib_tpu.service.httpapi import HttpRemoteObservationStore

        srv, _ = self._serve()
        try:
            remote = HttpRemoteObservationStore(srv.base_url)
            remote.report_observation_log("t1", [MetricLog(1.0, "score", "0.5")])
            remote.report_many([
                ("t1", [MetricLog(2.0, "score", "0.7")]),
                ("t2", [MetricLog(1.5, "loss", "2.0")]),
            ])
            rows = remote.get_observation_log("t1")
            assert [(r.timestamp, r.value) for r in rows] == [(1.0, "0.5"), (2.0, "0.7")]
            folded = remote.folded("t1", ["score"]).metric("score")
            assert (folded.min, folded.max, folded.latest) == ("0.5", "0.7", "0.7")
            assert remote.truncate_observation_log("t1", 1.5) == 1
            assert len(remote.get_observation_log("t1")) == 1
            remote.delete_observation_log("t2")
            assert remote.get_observation_log("t2") == []
        finally:
            srv.shutdown()
            srv.server_close()

    def test_duplicate_batch_is_idempotent(self):
        """At-least-once delivery: a retried ReportMany must not double-
        append (the gRPC receiver's exact-duplicate drop, inherited)."""
        from katib_tpu.service.httpapi import HttpRemoteObservationStore

        srv, store = self._serve()
        try:
            remote = HttpRemoteObservationStore(srv.base_url)
            batch = [("t1", [MetricLog(1.0, "score", "0.5"),
                             MetricLog(2.0, "score", "0.6")])]
            remote.report_many(batch)
            remote.report_many(batch)  # the retry after a lost response
            assert len(store.get_observation_log("t1")) == 2
        finally:
            srv.shutdown()
            srv.server_close()

    def test_auth_token_enforced_and_metrics_recorded(self):
        from katib_tpu.controller.events import MetricsRegistry
        from katib_tpu.service.httpapi import (
            HttpRemoteObservationStore, RpcError,
        )

        reg = MetricsRegistry()
        srv, _ = self._serve(token="sekrit", metrics=reg)
        try:
            bad = HttpRemoteObservationStore(srv.base_url, token="wrong")
            with pytest.raises(RpcError) as ei:
                bad.report_observation_log("t", [MetricLog(1.0, "m", "1")])
            assert ei.value.code == 403
            good = HttpRemoteObservationStore(srv.base_url, token="sekrit")
            good.report_observation_log("t", [MetricLog(1.0, "m", "1")])
            rendered = reg.render()
            assert 'katib_rpc_requests_total{code="200"' in rendered
            assert 'service="DBManager"' in rendered
            assert "katib_rpc_latency_seconds_bucket" in rendered
        finally:
            srv.shutdown()
            srv.server_close()

    def test_unknown_method_is_404_not_retried(self):
        from katib_tpu.service.httpapi import HttpApiClient, RpcError

        srv, _ = self._serve()
        try:
            client = HttpApiClient(srv.base_url, retries=5)
            t0 = time.time()
            with pytest.raises(RpcError) as ei:
                client.call("NoSuchMethod", {})
            assert ei.value.code == 404
            assert time.time() - t0 < 1.0  # 4xx must not burn the backoff
        finally:
            srv.shutdown()
            srv.server_close()

    def test_client_retries_through_server_restart(self):
        """The reference's UNAVAILABLE retry: a replica restarting mid-call
        is re-dialed with backoff instead of failing the report."""
        from katib_tpu.db.store import InMemoryObservationStore
        from katib_tpu.service.httpapi import HttpRemoteObservationStore, serve_api
        from katib_tpu.service.rpc import ApiServicer

        store = InMemoryObservationStore()
        srv, _ = self._serve(store=store)
        port = srv.bound_port
        srv.shutdown()
        srv.server_close()  # the replica is down; the port is free again

        def restart():
            time.sleep(0.4)
            self.later = serve_api(ApiServicer(store=store), port=port)

        t = threading.Thread(target=restart)
        t.start()
        try:
            remote = HttpRemoteObservationStore(f"http://127.0.0.1:{port}")
            remote.report_observation_log("t1", [MetricLog(1.0, "score", "0.5")])
            assert len(store.get_observation_log("t1")) == 1
        finally:
            t.join()
            self.later.shutdown()
            self.later.server_close()

    def test_report_metrics_rpc_env_binding(self, monkeypatch):
        from katib_tpu.runtime.metrics import report_metrics

        srv, store = self._serve(token="tok")
        try:
            monkeypatch.setenv("KATIB_TPU_TRIAL_NAME", "env-rpc-trial")
            monkeypatch.setenv("KATIB_TPU_RPC_URL", srv.base_url)
            monkeypatch.setenv("KATIB_TPU_RPC_TOKEN", "tok")
            # the DB path binding also set: the RPC transport must win
            monkeypatch.setenv("KATIB_TPU_DB_PATH", "/nonexistent/never.db")
            report_metrics(score=0.25)
            rows = store.get_observation_log("env-rpc-trial")
            assert [(r.metric_name, r.value) for r in rows] == [("score", "0.25")]
        finally:
            srv.shutdown()
            srv.server_close()

    def test_grpc_transport_gains_report_many_and_truncate(self):
        from katib_tpu.db.store import InMemoryObservationStore
        from katib_tpu.service.rpc import (
            ApiServicer, RemoteObservationStore, serve,
        )

        store = InMemoryObservationStore()
        server = serve(ApiServicer(store=store), port=0)
        try:
            remote = RemoteObservationStore(
                f"localhost:{server.bound_port}", retries=2, retry_period=0.1
            )
            remote.report_many([
                ("t1", [MetricLog(1.0, "score", "0.5"),
                        MetricLog(2.0, "score", "0.7")]),
            ])
            assert len(store.get_observation_log("t1")) == 2
            assert remote.truncate_observation_log("t1", 1.5) == 1
            assert len(store.get_observation_log("t1")) == 1
            remote.close()
        finally:
            server.stop(None)


# -- store concurrency --------------------------------------------------------


class TestSqliteHardening:
    def test_wal_and_busy_timeout_pragmas(self, tmp_path):
        store = SqliteObservationStore(str(tmp_path / "obs.db"))
        try:
            assert store._conn.execute("PRAGMA journal_mode").fetchone()[0] == "wal"
            assert store._conn.execute("PRAGMA busy_timeout").fetchone()[0] >= 1000
        finally:
            store.close()

    def test_report_many_retries_through_concurrent_writer_lock(self, tmp_path):
        """A concurrent connection holding the write lock used to make the
        group-commit flush raise SQLITE_BUSY through the durability
        barrier; now the write parks and retries until the lock clears."""
        path = str(tmp_path / "obs.db")
        store = SqliteObservationStore(path, busy_timeout_ms=50)
        blocker = sqlite3.connect(path)
        try:
            blocker.execute("BEGIN IMMEDIATE")  # hold the write lock
            done = threading.Event()
            err = []

            def write():
                try:
                    store.report_many(
                        [("t1", [MetricLog(1.0, "score", "0.5")])]
                    )
                except BaseException as e:  # noqa: BLE001
                    err.append(e)
                finally:
                    done.set()

            t = threading.Thread(target=write)
            t.start()
            time.sleep(0.3)  # longer than the 50ms busy window: forces retries
            blocker.rollback()
            assert done.wait(timeout=10), "write never completed"
            t.join()
            assert not err, f"group commit raised through the barrier: {err}"
            assert len(store.get_observation_log("t1")) == 1
        finally:
            blocker.close()
            store.close()


# -- placement ----------------------------------------------------------------


def _replica_controller(root, replicas=2):
    from katib_tpu.config import KatibConfig
    from katib_tpu.controller.experiment import ExperimentController

    cfg = KatibConfig()
    cfg.runtime.replicas = replicas
    cfg.runtime.telemetry = False
    cfg.runtime.compile_service = False
    cfg.runtime.tracing = False
    # two controllers share this PROCESS in the unit tests; recovery off
    # keeps their journals from sharing one pid-derived subdir
    cfg.runtime.recovery = False
    return ExperimentController(root_dir=root, devices=[0, 1], config=cfg)


class TestPlacement:
    def test_no_double_claim_capacity_and_fence_bump(self, tmp_path):
        from katib_tpu.controller.placement import ReplicaManager
        from katib_tpu.controller.recovery import read_lease_path

        root = str(tmp_path)
        a = _replica_controller(root)
        b = _replica_controller(root)
        mgr_a = ReplicaManager(a, "ra", capacity=2, lease_seconds=5.0)
        mgr_b = ReplicaManager(b, "rb", capacity=2, lease_seconds=5.0)
        try:
            assert mgr_a.claim_new("e1")
            assert mgr_a.claim_new("e1")  # idempotent re-claim of our own
            # a live holder blocks the peer
            assert not mgr_b.claim_new("e1")
            assert mgr_a.claim_new("e2")
            # capacity bound
            assert not mgr_a.claim_new("e3")
            assert mgr_b.claim_new("e3")
            # release -> takeable by the peer, fence bumps
            lease_path = os.path.join(root, "placement", "e1.lease")
            fence_before = read_lease_path(lease_path).payload["fence"]
            mgr_a.release("e1")
            assert mgr_b.claim_new("e1")
            view = read_lease_path(lease_path)
            assert view.payload["owner"] == "rb"
            assert view.payload["fence"] == fence_before + 1
            assert view.payload["replica"] == "rb"
        finally:
            mgr_a.stop()
            mgr_b.stop()
            a.close()
            b.close()

    def test_zombie_holder_pid_is_treated_dead(self):
        from katib_tpu.controller.recovery import _pid_alive

        proc = subprocess.Popen([sys.executable, "-c", "pass"])
        try:
            proc.send_signal(signal.SIGKILL)
            deadline = time.time() + 5
            while time.time() < deadline:
                # unreaped: signal-0 still succeeds, /proc says Z
                if not _pid_alive(proc.pid):
                    break
                time.sleep(0.05)
            assert not _pid_alive(proc.pid), "zombie holder reported alive"
        finally:
            proc.wait()
        assert not _pid_alive(proc.pid)

    def test_merged_journal_records_across_replica_subdirs(self, tmp_path):
        from katib_tpu.controller.recovery import (
            RecoveryJournal, journal_dir, merged_journal_records,
            remove_journal_files,
        )

        root = str(tmp_path)
        j1 = RecoveryJournal(journal_dir(root, replica="r1"))
        j2 = RecoveryJournal(journal_dir(root, replica="r2"))
        j1.append("submit", "expA", trial="t1")
        time.sleep(0.01)
        j2.append("terminal", "expA", trial="t1", condition="Succeeded")
        j2.append("submit", "expB", trial="u1")
        records = merged_journal_records(root, "expA")
        assert [r["op"] for r in records] == ["submit", "terminal"]
        assert all(r["_file"] for r in records)
        removed = remove_journal_files([r["_file"] for r in records])
        assert removed == 2
        assert merged_journal_records(root, "expA") == []
        assert len(merged_journal_records(root, "expB")) == 1


class TestRouter:
    def _seed(self, root, replicas, leases):
        pdir = os.path.join(root, "placement")
        rdir = os.path.join(pdir, "replicas")
        os.makedirs(rdir, exist_ok=True)
        now = time.time()
        for rec in replicas:
            rec = dict({"pid": os.getpid(), "renewed": now, "ttl": 10.0,
                        "capacity": 8, "claimed": []}, **rec)
            with open(os.path.join(rdir, rec["replica"] + ".json"), "w") as f:
                json.dump(rec, f)
        for rec in leases:
            payload = dict({
                "owner": rec["replica"], "pid": os.getpid(),
                "state": "active", "fence": 1, "renewed": now,
                "ttl": 10.0,
            }, **rec)
            with open(
                os.path.join(pdir, rec["experiment"] + ".lease"), "w"
            ) as f:
                json.dump(payload, f)

    def test_owner_lookup_and_least_loaded_pick(self, tmp_path):
        from katib_tpu.client.katib_client import ReplicaRouter

        root = str(tmp_path)
        self._seed(
            root,
            replicas=[
                {"replica": "r1", "url": "http://h1", "claimed": ["e1", "e2"]},
                {"replica": "r2", "url": "http://h2", "claimed": ["e3"]},
                # dead replica: stale heartbeat must exclude it
                {"replica": "r3", "url": "http://h3", "claimed": [],
                 "renewed": time.time() - 999},
            ],
            leases=[
                {"experiment": "e1", "replica": "r1", "url": "http://h1"},
                {"experiment": "gone", "replica": "r3", "url": "http://h3",
                 "renewed": time.time() - 999},
            ],
        )
        router = ReplicaRouter(root)
        assert {r["replica"] for r in router.live_replicas()} == {"r1", "r2"}
        assert router.owner_url("e1") == "http://h1"
        assert router.owner_url("gone") is None  # expired lease: unplaced
        assert router.pick_for_create()["replica"] == "r2"

    def test_replicas_cli_offline_table(self, tmp_path, capsys):
        from katib_tpu.cli import main

        root = str(tmp_path)
        self._seed(
            root,
            replicas=[{"replica": "r1", "url": "http://h1", "claimed": ["e1"]}],
            leases=[{"experiment": "e1", "replica": "r1", "url": "http://h1"}],
        )
        assert main(["--root", root, "replicas"]) == 0
        out = capsys.readouterr().out
        assert "r1" in out and "e1" in out and "replicas (1)" in out
        assert main(["--root", root, "replicas", "--format", "json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["replicas"][0]["replica"] == "r1"
        assert doc["leases"][0]["experiment"] == "e1"
        assert doc["leases"][0]["fence"] == 1


# -- end-to-end ---------------------------------------------------------------


def _replica_env(root, replicas, lease_ttl=5.0):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": (
            REPO + os.pathsep + root + os.pathsep + env.get("PYTHONPATH", "")
        ).rstrip(os.pathsep),
        "KATIB_TPU_REPLICAS": str(replicas),
        "KATIB_TPU_REPLICA_CAPACITY": "8",
        "KATIB_TPU_PLACEMENT_LEASE_SECONDS": str(lease_ttl),
        "KATIB_TPU_TELEMETRY": "0",
        "KATIB_TPU_COMPILE_SERVICE": "0",
        "KATIB_TPU_TRACING": "0",
        "KATIB_TPU_OBSLOG_BUFFERED": "0",
    })
    env.pop("KATIB_TPU_CHAOS", None)
    return env


def _spawn_replica(root, rid, env, devices=2):
    out = open(os.path.join(root, f"{rid}.log"), "w")
    return subprocess.Popen(
        [sys.executable, "-m", "katib_tpu.controller.replica",
         "--root", root, "--replica-id", rid, "--devices", str(devices)],
        env=env, stdout=out, stderr=out, text=True,
    ), out


def _stop_all(procs, logs):
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    for f in logs:
        f.close()


class TestFailoverE2E:
    def test_sigkill_failover_completes_on_survivor_bit_identically(self):
        """The satellite's headline test: two replica subprocesses, one is
        SIGKILLed mid-sweep, and the survivor completes its experiments —
        fence bumped, zero lost observations, rows bit-identical to a
        fault-free single-replica run of the same seeded specs."""
        import shutil

        from katib_tpu.client.katib_client import ReplicaRouter

        epochs = 5
        names = ["fo-a", "fo-b"]

        def drive(root, replicas, kill_after_place):
            _write_trial_module(root, epochs=epochs, dwell=0.6)
            env = _replica_env(root, replicas)
            procs, logs = [], []
            try:
                for i in range(replicas):
                    p, out = _spawn_replica(root, f"r{i}", env)
                    procs.append(p)
                    logs.append(out)
                router = ReplicaRouter(root)
                deadline = time.time() + 120
                while len(router.live_replicas()) < replicas:
                    assert time.time() < deadline, f"replicas never joined ({root})"
                    time.sleep(0.2)
                placed = {}
                for name in names:
                    placed[name] = router.create_experiment(_spec(name))["replica"]
                victim_idx = None
                if kill_after_place:
                    # kill the replica that owns the FIRST experiment while
                    # its trials are mid-flight
                    time.sleep(1.0)
                    victim_idx = int(placed[names[0]][1:])
                    procs[victim_idx].send_signal(signal.SIGKILL)
                    procs[victim_idx].wait()
                pending = set(names)
                while pending:
                    assert time.time() < deadline, (
                        f"experiments never completed: {pending} ({root})"
                    )
                    for name in list(pending):
                        if _is_done(router.experiment_status(name)):
                            pending.discard(name)
                    time.sleep(0.3)
                survivors = [
                    f"r{i}" for i in range(replicas) if i != victim_idx
                ]
                failovers = 0
                for row in router.table()["replicas"]:
                    if row.get("replica") in survivors and row.get("alive"):
                        from katib_tpu.service.httpapi import HttpApiClient

                        st = HttpApiClient(row["url"]).replica_status()
                        if st:
                            failovers += int(st.get("failovers", 0))
                return placed, failovers
            finally:
                _stop_all(procs, logs)

        ref_root = tempfile.mkdtemp(prefix="cp-ref-")
        chaos_root = tempfile.mkdtemp(prefix="cp-chaos-")
        try:
            drive(ref_root, replicas=1, kill_after_place=False)
            ref_epochs, ref_scores = _rows_by_x(ref_root, names)
            assert all(
                steps == list(range(1, epochs + 1))
                for steps in ref_epochs.values()
            ), f"fault-free reference lost rows: {ref_epochs}"

            placed, failovers = drive(chaos_root, replicas=2, kill_after_place=True)
            chaos_epochs, chaos_scores = _rows_by_x(chaos_root, names)
            lost = {
                k: v for k, v in chaos_epochs.items()
                if v != list(range(1, epochs + 1))
            }
            assert not lost, f"lost/duplicated observations after failover: {lost}"
            assert chaos_scores == ref_scores, (
                "failed-over rows are not bit-identical to the fault-free run"
            )
            assert failovers >= 1, "survivor recorded no failover"
            # the victim's experiment must have changed owner with a fence bump
            from katib_tpu.controller.recovery import read_lease_path

            view = read_lease_path(
                os.path.join(chaos_root, "placement", names[0] + ".lease")
            )
            assert view.payload["owner"] != placed[names[0]]
            assert view.payload["fence"] >= 2
        finally:
            shutil.rmtree(ref_root, ignore_errors=True)
            shutil.rmtree(chaos_root, ignore_errors=True)


class TestReplicasOffByteIdentity:
    def test_replicas_unset_keeps_single_controller_topology(self, tmp_path):
        """Acceptance: with KATIB_TPU_REPLICAS unset the controller is the
        PR 14 single-writer (root lease taken, flat journal, no placement
        dir), and a seeded sweep produces the same rows the sharded
        1-replica path produces — on-vs-off outcome equality plus topology
        assertions on both sides."""
        import sys as _sys

        from katib_tpu.api.spec import experiment_spec_from_mapping
        from katib_tpu.config import KatibConfig
        from katib_tpu.controller.experiment import ExperimentController

        epochs = 2
        off_root = str(tmp_path / "off")
        on_root = str(tmp_path / "on")
        os.makedirs(off_root)
        os.makedirs(on_root)
        for root in (off_root, on_root):
            _write_trial_module(root, epochs=epochs, dwell=0.01)

        # OFF: a plain controller, default topology (replicas == 0)
        _sys.path.insert(0, off_root)
        try:
            cfg = KatibConfig()
            cfg.runtime.telemetry = False
            cfg.runtime.compile_service = False
            cfg.runtime.tracing = False
            assert cfg.runtime.replicas == 0
            ctrl = ExperimentController(
                root_dir=off_root, devices=[0, 1], config=cfg
            )
            try:
                ctrl.create_experiment(
                    experiment_spec_from_mapping(_spec("seeded"))
                )
                exp = ctrl.run("seeded", timeout=60)
                assert exp.status.is_succeeded
            finally:
                ctrl.close()
        finally:
            _sys.path.remove(off_root)
        # PR 14 topology intact: root-wide lease + flat journal, no placement
        assert os.path.exists(os.path.join(off_root, "state", "controller.lease"))
        jdir = os.path.join(off_root, "journal")
        assert any(fn.endswith(".json") for fn in os.listdir(jdir)), (
            "flat journal layout expected when replicas is unset"
        )
        assert not os.path.exists(os.path.join(off_root, "placement"))

        # ON: the same seeded spec through a 1-replica sharded server
        _sys.path.insert(0, on_root)
        try:
            from katib_tpu.client.katib_client import ReplicaRouter
            from katib_tpu.controller.replica import ReplicaServer

            cfg = KatibConfig()
            cfg.runtime.replicas = 1
            cfg.runtime.telemetry = False
            cfg.runtime.compile_service = False
            cfg.runtime.tracing = False
            cfg.runtime.placement_lease_seconds = 5.0
            srv = ReplicaServer(
                root_dir=on_root, replica_id="r0", devices=[0, 1],
                config=cfg, export_rpc_env=False,
            ).start()
            try:
                router = ReplicaRouter(on_root)
                deadline = time.time() + 60
                while not router.live_replicas():
                    assert time.time() < deadline
                    time.sleep(0.1)
                router.create_experiment(_spec("seeded"))
                while not _is_done(router.experiment_status("seeded")):
                    assert time.time() < deadline, "sharded run never completed"
                    time.sleep(0.2)
            finally:
                srv.stop()
        finally:
            _sys.path.remove(on_root)
        # sharded topology: placement leases + per-replica journal, no root lease
        assert os.path.exists(os.path.join(on_root, "placement", "seeded.lease"))
        assert not os.path.exists(os.path.join(on_root, "state", "controller.lease"))
        assert os.path.isdir(os.path.join(on_root, "journal", "r0"))

        _, off_scores = _rows_by_x(off_root, ["seeded"])
        _, on_scores = _rows_by_x(on_root, ["seeded"])
        assert off_scores == on_scores and off_scores, (
            "replicas on-vs-off rows diverged for the seeded sweep"
        )
