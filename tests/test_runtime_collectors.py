"""Metric collector tests: TEXT/JSON line parsers, tfevent decoding, and the
checkpoint store. Models reference tfevent collector tests
(test/unit/v1beta1/metricscollector) with a hand-encoded event file instead
of checked-in TF fixtures."""

import struct


import numpy as np
import pytest

from katib_tpu.db.store import MetricLog
from katib_tpu.runtime.metrics import parse_json_lines, parse_text_lines
from katib_tpu.runtime.tfevent import collect_tfevent_metrics, read_tfevents

# Fast, capability-representative module: part of the -m smoke tier.
pytestmark = pytest.mark.smoke


# -- minimal protobuf/TFRecord writer (test-side encoder) --------------------

def _varint(v: int) -> bytes:
    out = b""
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _field(num: int, wire: int) -> bytes:
    return _varint((num << 3) | wire)


def _len_field(num: int, payload: bytes) -> bytes:
    return _field(num, 2) + _varint(len(payload)) + payload


def encode_event(wall_time: float, step: int, scalars, use_tensor=False) -> bytes:
    summary = b""
    for tag, value in scalars:
        if use_tensor:
            tensor = _field(1, 0) + _varint(1)  # dtype DT_FLOAT
            tensor += _len_field(5, struct.pack("<f", value))  # packed float_val
            val_msg = _len_field(1, tag.encode()) + _len_field(8, tensor)
        else:
            val_msg = _len_field(1, tag.encode()) + _field(2, 5) + struct.pack("<f", value)
        summary += _len_field(1, val_msg)
    event = _field(1, 1) + struct.pack("<d", wall_time)
    event += _field(2, 0) + _varint(step)
    event += _len_field(5, summary)
    return event


def write_tfrecord(path, events) -> None:
    with open(path, "wb") as f:
        for payload in events:
            f.write(struct.pack("<Q", len(payload)))
            f.write(b"\x00" * 4)  # length crc (not verified)
            f.write(payload)
            f.write(b"\x00" * 4)  # data crc


class TestTfEvent:
    def test_simple_value_scalars(self, tmp_path):
        p = tmp_path / "events.out.tfevents.123.host"
        write_tfrecord(
            p,
            [
                encode_event(100.0, 1, [("accuracy", 0.5), ("loss", 1.2)]),
                encode_event(101.0, 2, [("train/accuracy", 0.7)]),
            ],
        )
        logs = collect_tfevent_metrics(str(tmp_path), ["accuracy"])
        assert [round(float(l.value), 4) for l in logs] == [0.5, 0.7]
        assert all(l.metric_name == "accuracy" for l in logs)

    def test_tensor_scalars_tf2_style(self, tmp_path):
        p = tmp_path / "events.out.tfevents.tf2"
        write_tfrecord(p, [encode_event(50.0, 1, [("accuracy", 0.25)], use_tensor=True)])
        logs = collect_tfevent_metrics(str(tmp_path), ["accuracy", "loss"])
        assert len(logs) == 1 and round(float(logs[0].value), 4) == 0.25

    def test_corrupt_tail_tolerated(self, tmp_path):
        p = tmp_path / "events.out.tfevents.corrupt"
        write_tfrecord(p, [encode_event(1.0, 1, [("m", 1.0)])])
        with open(p, "ab") as f:
            f.write(b"\x99" * 7)  # truncated garbage frame
        assert len(list(read_tfevents(str(p)))) == 1


class TestLineParsers:
    def test_text_default_filter(self):
        lines = ["epoch 1", "accuracy=0.91 loss=0.3", "noise", "accuracy = 0.95"]
        logs = parse_text_lines(lines, ["accuracy", "loss"], base_time=0.0)
        assert [(l.metric_name, l.value) for l in logs] == [
            ("accuracy", "0.91"),
            ("loss", "0.3"),
            ("accuracy", "0.95"),
        ]
        # report order is preserved through synthetic timestamps
        assert logs[0].timestamp < logs[2].timestamp

    def test_text_custom_filter(self):
        lines = ["{metricName: acc, metricValue: 0.85}"]
        logs = parse_text_lines(
            lines, ["acc"], filters=[r"{metricName: ([\w|-]+), metricValue: ((-?\d+)(\.\d+)?)}"]
        )
        assert logs[0].value == "0.85"

    def test_json_lines(self):
        lines = ['{"acc": 0.5, "step": 1}', "not json", '{"acc": "0.9", "timestamp": 42.0}']
        logs = parse_json_lines(lines, ["acc"], base_time=0.0)
        assert [l.value for l in logs] == ["0.5", "0.9"]
        assert logs[1].timestamp == 42.0


class TestPushValidation:
    """Reference sdk utils.validate_metrics_value (utils.py:75-84): the push
    path is numeric-only; strings arrive only via collector filters (the
    darts Best-Genotype flow)."""

    def test_validate_metric_value(self):
        import math

        from katib_tpu.runtime.metrics import validate_metric_value

        # returns the normalized float — the stored form is str(float(v)),
        # so float()-able objects with non-numeric str() stay rankable
        assert validate_metric_value("m", "0.99") == 0.99
        assert validate_metric_value("m", True) == 1.0
        assert validate_metric_value("m", "-3e-4") == -3e-4
        import numpy as np

        assert validate_metric_value("m", np.float32(0.5)) == 0.5
        assert math.isnan(validate_metric_value("m", math.nan))
        for bad in (None, "not-a-number", {}, [0.5], "Genotype(normal=[])"):
            with pytest.raises(ValueError, match="not convertible"):
                validate_metric_value("m", bad)

    def test_report_normalizes_stored_values(self, tmp_path):
        from katib_tpu.db.store import open_store
        from katib_tpu.runtime.metrics import MetricsReporter

        store = open_store(str(tmp_path / "obs.db"), backend="sqlite")
        try:
            MetricsReporter(store=store, trial_name="t1").report(
                **{"acc": "0.25", "flag": True}
            )
            logs = {l.metric_name: l.value for l in store.get_observation_log("t1")}
            assert logs == {"acc": "0.25", "flag": "1.0"}
        finally:
            store.close()

    def test_garbage_push_fails_the_trial(self, tmp_path):
        """A typo'd push value raises inside the trial and the trial FAILS
        with the reason in its message — it must not surface as Succeeded
        with an unrankable objective."""
        from katib_tpu.client import KatibClient, search

        def objective(params):
            import katib_tpu

            katib_tpu.report_metrics({"score": "not-a-number"})

        c = KatibClient(root_dir=str(tmp_path), devices=[0])
        c.tune(
            name="badmetric",
            objective=objective,
            parameters={"x": search.double(min=0.0, max=1.0)},
            objective_metric_name="score",
            max_trial_count=1,
            parallel_trial_count=1,
            max_failed_trial_count=0,
        )
        exp = c.run("badmetric", timeout=60)
        t = c.list_trials("badmetric")[0]
        assert t.condition.value == "Failed"
        assert "not convertible" in t.message
        assert exp.status.condition.value == "Failed"  # maxFailed=0 budget
        c.controller.close()


class TestCheckpointStore:
    @pytest.mark.parametrize("use_orbax", [False, True])
    def test_roundtrip(self, tmp_path, use_orbax):
        if use_orbax:
            pytest.importorskip("orbax.checkpoint")
        from katib_tpu.runtime.checkpoints import CheckpointStore

        store = CheckpointStore(str(tmp_path / "ckpt"), use_orbax=use_orbax)
        state = {"w": np.arange(6, dtype=np.float32).reshape(2, 3), "step": np.int32(7)}
        store.save(1, state)
        store.save(3, {"w": state["w"] * 2, "step": np.int32(9)})
        assert store.latest_step() == 3
        restored = store.restore()
        np.testing.assert_allclose(restored["w"], state["w"] * 2)
        old = store.restore(step=1)
        np.testing.assert_allclose(old["w"], state["w"])

    def test_concurrent_in_process_saves(self, tmp_path):
        """In-process trial threads save at the same time, each into its own
        directory. orbax keeps per-process state for the save in flight, so
        without the store's lock these saves remove each other's temporary
        directories (FileExistsError / ENOENT in its commit thread)."""
        pytest.importorskip("orbax.checkpoint")
        import threading

        from katib_tpu.runtime.checkpoints import CheckpointStore

        errors = []

        def trial(i):
            try:
                store = CheckpointStore(str(tmp_path / f"t{i}"), use_orbax=True)
                for step in range(1, 4):
                    store.save(step, {"epoch": step, "w": np.full((4, 4), float(i))})
                out = store.restore()
                assert int(out["epoch"]) == 3 and float(out["w"][0, 0]) == float(i)
            except Exception as e:  # reported below, on the test thread
                errors.append(f"trial {i}: {type(e).__name__}: {e}")

        threads = [threading.Thread(target=trial, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads), "a save never returned"
        assert not errors, errors

    def test_uncommitted_save_raises(self, tmp_path, monkeypatch):
        """A save that did not commit fails on the trial's own thread."""
        pytest.importorskip("orbax.checkpoint")
        import orbax.checkpoint as ocp

        from katib_tpu.runtime.checkpoints import CheckpointError, CheckpointStore

        store = CheckpointStore(str(tmp_path / "ckpt"), use_orbax=True)
        monkeypatch.setattr(ocp.CheckpointManager, "save", lambda self, *a, **k: False)
        with pytest.raises(CheckpointError, match="did not commit"):
            store.save(1, {"epoch": 1})


class TestPrometheusCollector:
    def test_parse_prometheus_text(self):
        from katib_tpu.runtime.metrics import parse_prometheus_text

        text = (
            "# HELP accuracy model accuracy\n"
            "# TYPE accuracy gauge\n"
            'accuracy{step="5"} 0.93\n'
            "loss 0.12 1700000000\n"
            "other_metric 42\n"
        )
        logs = parse_prometheus_text(text, ["accuracy", "loss"])
        assert {(l.metric_name, l.value) for l in logs} == {("accuracy", "0.93"), ("loss", "0.12")}

    def test_subprocess_prometheus_scrape_e2e(self, tmp_path):
        """Subprocess trial serving /metrics; executor scrapes it
        (reference CollectorKind PrometheusMetric)."""
        import socket

        from katib_tpu.api.spec import (
            AlgorithmSpec,
            CollectorKind,
            ExperimentSpec,
            FeasibleSpace,
            MetricsCollectorSpec,
            ObjectiveSpec,
            ObjectiveType,
            ParameterSpec,
            ParameterType,
            SourceSpec,
            TrialTemplate,
        )
        from katib_tpu.api.status import TrialCondition
        from katib_tpu.controller.experiment import ExperimentController

        with socket.socket() as s:  # pick a free port
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]

        server_py = (
            "import http.server, threading, time, sys\n"
            "class H(http.server.BaseHTTPRequestHandler):\n"
            "    def log_message(self, *a): pass\n"
            "    def do_GET(self):\n"
            "        body = b'accuracy 0.88\\n'\n"
            "        self.send_response(200); self.send_header('Content-Length', str(len(body)))\n"
            "        self.end_headers(); self.wfile.write(body)\n"
            f"srv = http.server.HTTPServer(('127.0.0.1', {port}), H)\n"
            "threading.Thread(target=srv.serve_forever, daemon=True).start()\n"
            "time.sleep(2.5)\n"
        )
        ctrl = ExperimentController(root_dir=str(tmp_path))
        try:
            spec = ExperimentSpec(
                name="prom-e2e",
                parameters=[
                    ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1"))
                ],
                objective=ObjectiveSpec(
                    type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
                ),
                algorithm=AlgorithmSpec("random"),
                trial_template=TrialTemplate(command=["python", "-c", server_py]),
                metrics_collector_spec=MetricsCollectorSpec(
                    collector_kind=CollectorKind.PROMETHEUS,
                    source=SourceSpec(http_port=port),
                ),
                max_trial_count=1,
                parallel_trial_count=1,
            )
            ctrl.create_experiment(spec)
            exp = ctrl.run("prom-e2e", timeout=60)
            trials = ctrl.state.list_trials("prom-e2e")
            assert trials and trials[0].condition == TrialCondition.SUCCEEDED
            m = trials[0].observation.metric("accuracy")
            assert m is not None and m.latest == "0.88"
        finally:
            ctrl.close()
