"""Native C++ metrics tailer: build, parse parity with the Python fallback,
incremental partial-line buffering, and executor integration (the watch loop
that replaced the reference file-metrics-collector sidecar,
file-metricscollector/main.go:336-386)."""

import os

import pytest

from katib_tpu.native.tailer import PyTailer


@pytest.fixture(scope="module")
def native_cls():
    from katib_tpu.native import tailer_available
    from katib_tpu.native.build import build

    build()  # per-target availability decides the skip, not the AND of all
    if not tailer_available():
        pytest.skip("no C++ toolchain / tailer build failed")
    from katib_tpu.native.tailer import NativeTailer

    return NativeTailer


TRICKY = [
    "epoch 1 loss=0.5 acc = 0.9",
    "nothing here",
    "loss=abc acc=",              # unparseable / empty values dropped
    "loss=+1e-3 unwanted=7",
    "acc=-2.5E+1 loss=.5",        # regex allows .5 via (\\.\\d+)
    "a|b-c=1.25",                 # name chars include | and -
    "loss =   3e2 trailing",
    "x" * 500 + " loss=1",        # long line
    '{"json": "looking", "loss": 9}',  # TEXT mode: no = pair, ignored
    "loss=1.5e acc=2.",           # dangling exponent/dot: value stops early
    "loss=+ acc=0.3",             # bare sign: dropped by both tailers
    "µacc=0.9 loss=0.7",          # non-ASCII line: deferred to the py regex
    "…loss=0.6",                  # unicode punctuation boundary before name
]


def _write(path, lines):
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


class TestParity:
    def test_matches_python_fallback(self, native_cls, tmp_path):
        p = str(tmp_path / "out.log")
        _write(p, TRICKY)
        nat = native_cls(p, ["loss", "acc", "a|b-c"])
        py = PyTailer(p, ["loss", "acc", "a|b-c"])
        got_n = nat.poll()
        got_p = py.poll()
        nat.close()
        assert got_n == got_p, f"\nnative: {got_n}\npython: {got_p}"
        # sanity on content, not just parity
        assert ("loss", "0.5", 0) in got_n
        assert ("a|b-c", "1.25", 5) in got_n

    def test_incremental_and_partial_lines(self, native_cls, tmp_path):
        p = str(tmp_path / "out.log")
        nat = native_cls(p, ["loss"])
        assert nat.poll() == []  # file does not exist yet
        with open(p, "w") as f:
            f.write("loss=0.1\nloss=0.")
        assert [(n, v) for n, v, _ in nat.poll()] == [("loss", "0.1")]
        with open(p, "a") as f:
            f.write("25\n")
        got = nat.poll()
        assert [(n, v) for n, v, _ in got] == [("loss", "0.25")]
        # line indices keep increasing across polls (timestamp order)
        assert got[0][2] == 1
        nat.close()

    def test_make_tailer_routing(self, native_cls, tmp_path):
        from katib_tpu.native.tailer import make_tailer

        p = str(tmp_path / "out.log")
        assert isinstance(make_tailer(p, ["m"]), native_cls)
        assert isinstance(make_tailer(p, ["m"], filters=[r"(\w+):(\d+)"]), PyTailer)
        assert isinstance(make_tailer(p, ["m"], json_format=True), PyTailer)

    def test_unicode_metric_name_parity(self, native_cls, tmp_path):
        """Non-ASCII lines are deferred to the Unicode-aware Python regex,
        so Unicode metric names parse identically on both tailers."""
        p = str(tmp_path / "u.log")
        _write(p, ["précision=0.75 loss=0.1", "loss=0.2"])
        nat = native_cls(p, ["précision", "loss"])
        py = PyTailer(p, ["précision", "loss"])
        got_n, got_p = nat.poll(), py.poll()
        nat.close()
        assert got_n == got_p
        assert ("précision", "0.75", 0) in got_n
        assert ("loss", "0.2", 1) in got_n


class TestExecutorIntegration:
    def test_early_stopping_via_native_tailer(self, native_cls, tmp_path):
        """A subprocess trial whose metric plateaus must be early-stopped by
        the watch loop going through the native tailer."""
        import sys

        from katib_tpu.api import (
            AlgorithmSetting, AlgorithmSpec, EarlyStoppingSpec, ExperimentSpec,
            FeasibleSpace, ObjectiveSpec, ObjectiveType, ParameterSpec,
            ParameterType, TrialParameterSpec, TrialTemplate,
        )
        from katib_tpu.api.status import TrialCondition
        from katib_tpu.controller.experiment import ExperimentController

        # good trials (x >= 0.5) improve; bad ones plateau at 0.05 - x/100,
        # strictly declining across the grid so each later bad trial sits
        # strictly below the mean established by earlier ones (comparison is
        # strict LESS — identical plateaus would only trip via float
        # rounding). The stop must come mid-run from the tail loop, i.e.
        # through the native tailer parsing subprocess stdout.
        script = (
            "import time\n"
            "x = float('${trialParameters.x}')\n"
            "for i in range(40):\n"
            "    v = (0.1 + 0.08 * i) if x >= 0.5 else (0.05 - x / 100)\n"
            "    print(f'score={v}', flush=True)\n"
            "    time.sleep(0.05)\n"
        )
        ctrl = ExperimentController(root_dir=str(tmp_path), devices=[0, 1])
        try:
            spec = ExperimentSpec(
                name="native-tail-es",
                parameters=[
                    ParameterSpec(
                        "x",
                        ParameterType.DOUBLE,
                        FeasibleSpace(min="0", max="1", step="0.142"),
                    )
                ],
                objective=ObjectiveSpec(
                    type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
                ),
                algorithm=AlgorithmSpec("grid"),
                early_stopping=EarlyStoppingSpec(
                    algorithm_name="medianstop",
                    algorithm_settings=[
                        AlgorithmSetting("min_trials_required", "2"),
                        AlgorithmSetting("start_step", "3"),
                    ],
                ),
                trial_template=TrialTemplate(
                    command=[sys.executable, "-u", "-c", script],
                    trial_parameters=[TrialParameterSpec(name="x", reference="x")],
                ),
                max_trial_count=8,
                parallel_trial_count=2,
            )
            ctrl.create_experiment(spec)
            exp = ctrl.run("native-tail-es", timeout=180)
            trials = ctrl.state.list_trials("native-tail-es")
            # if the native tailer parsed nothing, every trial would run its
            # full 2s loop and succeed — EARLY_STOPPED proves the watch loop
            # saw the metrics
            assert any(
                t.condition == TrialCondition.EARLY_STOPPED for t in trials
            ), [t.condition for t in trials]
            assert any(t.condition == TrialCondition.SUCCEEDED for t in trials)
            assert exp.status.is_completed
        finally:
            ctrl.close()


def test_tailer_builds_itself_on_first_use(tmp_path, monkeypatch):
    """Git commits no binary: a fresh checkout has no shared object, and
    make_tailer builds it from metrics_tailer.cc rather than behaving
    differently from the tree it was cloned from."""
    import os

    from katib_tpu import native
    from katib_tpu.native import tailer

    target = str(tmp_path / "libmetricstailer.so")
    monkeypatch.setattr(native, "METRICS_TAILER_SO", target)
    monkeypatch.setattr(tailer, "METRICS_TAILER_SO", target)
    monkeypatch.setattr(
        "katib_tpu.native.build._TARGETS", (("metrics_tailer.cc", target),)
    )
    monkeypatch.setattr(tailer, "_build_tried", False)
    monkeypatch.setattr(tailer, "_lib", None)
    log = tmp_path / "out.log"
    log.write_text("accuracy=0.5\n")
    t = tailer.make_tailer(str(log), ["accuracy"])
    assert os.path.exists(target)
    assert isinstance(t, tailer.NativeTailer)
    assert [(n, v) for n, v, _ in t.poll()] == [("accuracy", "0.5")]
    t.close()
