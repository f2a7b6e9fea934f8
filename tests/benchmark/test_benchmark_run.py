"""The command end to end on the CPU at the tiny size: it refuses to report
without a chip; with the look for a chip stubbed it prints a well-formed last
line for a steady and a sweep cell; with the timed path broken underneath,
``correct`` comes out false."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, BENCH)

import run  # noqa: E402

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
MODULES = ["run", "tees", "check", "window", "metrics", "rundata", "experiment", "flops",
           "trace_reduce", "reference_lm", "calibrate"]


def test_without_a_chip_it_refuses_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "tiny.steady", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_nothing_touches_a_backend_at_import():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import importlib\n"
        "for m in %r: importlib.import_module(m)\n"
        "for m in ['readers.' + n[:-3] for n in __import__('os').listdir(%r) if n.endswith('.py')]:\n"
        "    importlib.import_module(m)\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, 'a backend was initialized at import'\n"
    ) % (BENCH, MODULES, os.path.join(BENCH, "readers"))
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]


def _drive(monkeypatch, capsys, workload, seed, seconds):
    monkeypatch.setattr(run, "find_device", lambda chips: dict(DEVICE))
    code = run.main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                     "--trace", "0"])
    out = capsys.readouterr()
    assert code == 0
    last = out.out.strip().splitlines()[-1]
    return json.loads(last), out.err


def _well_formed(result, expect):
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == expect
    for m in result["metrics"].values():
        assert isinstance(m["value"], float) and m["value"] > 0 and m["unit"]
    assert result["device"] == dict(DEVICE, memory_peak_bytes=result["device"]["memory_peak_bytes"])
    assert result["attempted"] > 0
    for name in ("report_rows_lost_or_changed", "assignments_outside_space", "loss_gap",
                 "grad_norm_gap", "delta_norm_gap"):
        assert set(result["checks"][name]) == {"value", "limit"}


def test_steady_cell_prints_a_well_formed_last_line(monkeypatch, capsys):
    # a seed larger than 32 signed bits hold
    result, err = _drive(monkeypatch, capsys, "tiny.steady", 3000000019, 3)
    _well_formed(result, {"train_tokens_per_s", "setup_s"})
    assert result["correct"] is True and result["failed"] == 0
    assert result["window"]["steps"] == 5 * result["window"]["reports"] == 5 * result["attempted"]
    assert "check loss_gap:" in err.strip().splitlines()[-3]


def test_sweep_cell_prints_a_well_formed_last_line(monkeypatch, capsys):
    result, _ = _drive(monkeypatch, capsys, "tiny.sweep", 7, 8)
    _well_formed(result, {"chip_s_per_trial", "setup_s"})
    assert result["correct"] is True
    assert result["attempted"] == result["window"]["trials"] >= 1
    assert result["metrics"]["chip_s_per_trial"]["value"] == pytest.approx(
        result["window"]["seconds"] / result["attempted"])


def _break_the_step(monkeypatch, how):
    """Plant a fault in the program underneath the harness: the builder the
    trial calls hands back a broken step."""
    import jax
    import jax.numpy as jnp

    from katib_tpu.parallel import train

    build = train.make_lm_train_step

    def broken_build(*args, **kwargs):
        params, opt_state, step_fn, put_batch = build(*args, **kwargs)

        def state_unchanged(params, opt_state, *batch):
            copy = jax.tree.map(jnp.copy, (params, opt_state))
            _, _, loss = step_fn(*copy, *batch)
            return params, opt_state, loss

        def half_batch(params, opt_state, *batch):
            half = batch[0].shape[0] // 2
            return step_fn(params, opt_state, *(x[:half] for x in batch))

        return params, opt_state, {"state_unchanged": state_unchanged, "half_batch": half_batch}[how], put_batch

    monkeypatch.setattr(train, "make_lm_train_step", broken_build)


# a seed each: a run's files live under its cell and seed
@pytest.mark.parametrize("how,number,seed", [
    ("state_unchanged", "delta_norm_gap", 11), ("half_batch", "grad_norm_gap", 13)])
def test_a_broken_step_comes_out_as_not_correct(monkeypatch, capsys, how, number, seed):
    _break_the_step(monkeypatch, how)
    result, _ = _drive(monkeypatch, capsys, "tiny.steady", seed, 2)
    assert result["correct"] is False
    assert result["checks"][number]["value"] > result["checks"][number]["limit"]
    assert result["checks"]["report_rows_lost_or_changed"]["value"] == 0


def test_a_report_altered_on_its_way_to_the_store_comes_out_as_not_correct(monkeypatch, capsys):
    from katib_tpu.runtime import context

    report = context.TrialContext.report
    monkeypatch.setattr(
        context.TrialContext, "report",
        lambda ctx, **m: report(ctx, **{k: v + 1.0 for k, v in m.items()}))
    # the harness's tee sits above this: it sees what the trial handed in
    result, _ = _drive(monkeypatch, capsys, "tiny.steady", 12, 2)
    assert result["correct"] is False
    assert result["checks"]["report_rows_lost_or_changed"]["value"] > 0
