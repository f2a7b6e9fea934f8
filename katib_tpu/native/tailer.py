"""Trial-output tailer: native (metrics_tailer.cc via ctypes) with a pure
Python fallback.

The executor's watch loop (SubprocessExecutor._wait) polls every running
trial's stdout/metrics file for `name = value` lines to enforce
early-stopping rules — the in-process equivalent of the reference's
file-metrics-collector sidecar watch
(file-metricscollector/main.go:336-386). With 64 concurrent trials on the
single orchestrator core, reading + regex-scanning in Python is measurable
overhead; the native tailer does the read/split/parse in C++.

``make_tailer`` picks the implementation: native when the collector uses the
default TEXT filter, building the shared object from ``metrics_tailer.cc``
the first time (git commits no binary, so a fresh checkout has none); Python
for custom regex filters and JSON lines, and — said once in the log — where
no C++ compiler is at hand.
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
from typing import List, Optional, Sequence, Tuple

from . import METRICS_TAILER_SO, tailer_available

# (metric_name, raw_value, line_index) — line_index is monotonically
# increasing across polls so callers can synthesize report-order timestamps
Parsed = Tuple[str, str, int]

_lib = None
_build_lock = threading.Lock()
_build_tried = False


def _ensure_built() -> bool:
    """The shared object is there, built now if it was not (once per
    process; a failed build is reported and the Python tailer runs)."""
    global _build_tried
    if tailer_available():
        return True
    with _build_lock:
        if not _build_tried:
            _build_tried = True
            from .build import build

            build()
            if not tailer_available():
                logging.getLogger("katib_tpu.native").warning(
                    "native metrics tailer could not be built; trial output "
                    "is parsed by the Python tailer"
                )
    return tailer_available()


def _load_lib():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(METRICS_TAILER_SO)
        lib.mt_open.restype = ctypes.c_void_p
        lib.mt_open.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.mt_poll.restype = ctypes.POINTER(ctypes.c_char)
        lib.mt_poll.argtypes = [ctypes.c_void_p]
        lib.mt_free.argtypes = [ctypes.POINTER(ctypes.c_char)]
        lib.mt_close.argtypes = [ctypes.c_void_p]
        _lib = lib
    return _lib


class NativeTailer:
    def __init__(self, path: str, metric_names: Sequence[str]):
        self._lib = _load_lib()
        self._names = list(metric_names)
        names = "\x1f".join(metric_names).encode()
        self._handle = self._lib.mt_open(path.encode(), names)

    def poll(self) -> List[Parsed]:
        buf = self._lib.mt_poll(self._handle)
        if not buf:
            return []
        try:
            raw = ctypes.string_at(buf).decode("utf-8", errors="replace")
        finally:
            self._lib.mt_free(buf)
        out: List[Parsed] = []
        # records are framed with '\n' by the C++ side; str.splitlines()
        # would also split on \v, \f, NEL, U+2028/9 inside deferred
        # non-ASCII lines, corrupting their records
        for entry in raw.split("\n"):
            if entry.startswith("\x02"):
                # non-ASCII line deferred by the kernel: parse with the real
                # Unicode-aware regex (same path as PyTailer)
                from ..runtime.metrics import parse_text_lines

                idx_str, _, line = entry[1:].partition("\x1f")
                for log in parse_text_lines([line], self._names):
                    try:
                        float(log.value)
                    except (TypeError, ValueError):
                        continue
                    out.append((log.metric_name, log.value, int(idx_str)))
                continue
            parts = entry.split("\x1f")
            if len(parts) == 3:
                out.append((parts[0], parts[1], int(parts[2])))
        return out

    def close(self) -> None:
        if self._handle:
            self._lib.mt_close(self._handle)
            self._handle = None

    def __del__(self):  # best-effort; executor calls close() explicitly
        try:
            self.close()
        except Exception:
            pass


class PyTailer:
    """Fallback replicating the original executor loop: offset + partial-line
    buffer, parse via runtime.metrics (supports custom filters and JSON)."""

    def __init__(
        self,
        path: str,
        metric_names: Sequence[str],
        filters: Optional[Sequence[str]] = None,
        json_format: bool = False,
    ):
        self._path = path
        self._names = list(metric_names)
        self._filters = list(filters) if filters else None
        self._json = json_format
        self._offset = 0
        self._buffered = ""
        self._line_index = 0

    def poll(self) -> List[Parsed]:
        from ..runtime.metrics import parse_json_lines, parse_text_lines

        if not os.path.exists(self._path):
            return []
        with open(self._path, "r", errors="replace") as f:
            f.seek(self._offset)
            chunk = f.read()
            self._offset = f.tell()
        if not chunk:
            return []
        self._buffered += chunk
        lines = self._buffered.split("\n")
        self._buffered = lines.pop()
        out: List[Parsed] = []
        for line in lines:
            idx = self._line_index
            self._line_index += 1
            if self._json:
                logs = parse_json_lines([line], self._names)
            else:
                logs = parse_text_lines([line], self._names, self._filters)
            for log in logs:
                # tailer contract: values are float-parseable (the regex's
                # value group can match a bare sign; the native tailer
                # rejects those in-kernel, and consumers would skip them)
                try:
                    float(log.value)
                except (TypeError, ValueError):
                    continue
                out.append((log.metric_name, log.value, idx))
        return out

    def close(self) -> None:
        pass


def make_tailer(
    path: str,
    metric_names: Sequence[str],
    filters: Optional[Sequence[str]] = None,
    json_format: bool = False,
):
    """Native tailer for the default-TEXT-filter case; Python otherwise
    (custom filters or JSON lines). Non-ASCII lines are deferred by the
    kernel back to the Unicode-aware Python regex, so Unicode metric names
    and log content parse identically on both paths."""
    if not json_format and not filters and _ensure_built():
        try:
            return NativeTailer(path, metric_names)
        except OSError:
            pass
    return PyTailer(path, metric_names, filters, json_format)
