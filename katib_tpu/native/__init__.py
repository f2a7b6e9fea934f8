"""Native (C++) components, consumed via ctypes.

Git commits no binary: the consumers build the shared objects from the
``.cc`` files the first time they need them (``python -m
katib_tpu.native.build`` does the same by hand; g++ -O2 -fPIC -shared).
Where no C++ compiler is at hand they run the pure-Python implementation
and say so.
"""

from __future__ import annotations

import os

NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))
OBSLOG_SO = os.path.join(NATIVE_DIR, "libobslog.so")
METRICS_TAILER_SO = os.path.join(NATIVE_DIR, "libmetricstailer.so")


def obslog_available() -> bool:
    return os.path.exists(OBSLOG_SO)


def tailer_available() -> bool:
    return os.path.exists(METRICS_TAILER_SO)
