"""Build the native components: ``python -m katib_tpu.native.build``."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from . import METRICS_TAILER_SO, NATIVE_DIR, OBSLOG_SO

_TARGETS = (
    ("obslog.cc", OBSLOG_SO),
    ("metrics_tailer.cc", METRICS_TAILER_SO),
)


def _build_one(gxx: str, src: str, out: str, force: bool) -> bool:
    if os.path.exists(out) and not force:
        if os.path.getmtime(out) >= os.path.getmtime(src):
            return True
    # built beside the target and renamed into place: another process that
    # finds the shared object finds a whole one
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = [gxx, "-O2", "-fPIC", "-shared", "-std=c++17", "-o", tmp, src]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, out)
    except subprocess.CalledProcessError as e:
        print(f"native build failed for {src}:\n{e.stderr}", file=sys.stderr)
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return True


def build(force: bool = False) -> bool:
    gxx = shutil.which("g++") or shutil.which("c++")
    if gxx is None:
        print("no C++ compiler found; native components unavailable", file=sys.stderr)
        return False
    ok = True
    for src_name, out in _TARGETS:
        ok = _build_one(gxx, os.path.join(NATIVE_DIR, src_name), out, force) and ok
    return ok


if __name__ == "__main__":
    ok = build(force="--force" in sys.argv)
    print("built" if ok else "build failed:", ", ".join(out for _, out in _TARGETS))
    sys.exit(0 if ok else 1)
