"""chip_smoke.py refuses to pass without a TPU.

The script is the proof that the main path runs on the chip; the one thing a
CPU sandbox can check is that it cannot be fooled: held to the CPU it stops
at the *device* phase, exits non-zero and never prints its ``"ok": true``
line. The run on the chip is made through the chip tool, not here."""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(*args, cwd=REPO, script=SCRIPT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, script, *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")]
    return proc, lines, time.time() - t0


def _assert_refused_at_device(proc, lines):
    assert proc.returncode != 0
    assert not any(l.get("ok") is True for l in lines), proc.stdout
    phases = [l.get("phase") for l in lines]
    assert phases == ["device", "failed"], phases
    assert lines[0]["device"]["platform"] == "cpu"
    assert "no TPU" in lines[1]["error"]


def test_fails_at_device_phase_on_cpu():
    proc, lines, seconds = _run()
    _assert_refused_at_device(proc, lines)
    assert seconds < 60, f"took {seconds:.0f}s to refuse"


def test_chips_4_option_parses_and_selects_only_the_four_chip_phase():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    assert chip_smoke.select_phases(4) == ["device", "four_chip"]
    assert chip_smoke.select_phases(1) == ["device", "kernel", "sweep"]
    # the option parses, and the CPU is refused on that path as well
    proc, lines, _ = _run("--chips", "4")
    _assert_refused_at_device(proc, lines)
    bad = subprocess.run(
        [sys.executable, SCRIPT, "--chips", "2"], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=60,
    )
    assert bad.returncode != 0 and '"ok"' not in bad.stdout


def test_alone_without_the_program_it_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    there is no program to drive: non-zero, no result line."""
    import shutil

    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(alone)], cwd=str(tmp_path), env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_tolerances_and_widths_are_the_stated_ones():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    s = chip_smoke.Sizes()
    assert (s.vocab_size, s.embed_dim, s.num_layers, s.num_heads) == (32768, 1024, 8, 16)
    assert (s.batch_size, s.seq_len) == (4, 2048)
    # the kernel phase runs the attention shape of exactly that model
    assert s.attn_shape == (s.batch_size, s.seq_len, s.num_heads, s.embed_dim // s.num_heads)
    assert s.max_trials == 5 and s.num_steps == 20
