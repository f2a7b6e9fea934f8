"""From a profiler trace (``*.xplane.pb``) to the numbers the readers use.

``load`` turns the file into plain data (``jax.profiler.ProfileData`` reads it
with nothing but JAX); ``reduce`` works on that plain data alone, so it can be
checked on the small recorded trace beside this file (``trace_sample.json``).

Plain form: ``{"planes": [{"name": str, "lines": [{"name": str, "events":
[[name, start_ns, duration_ns], ...]}]}]}``.

- Device planes are named ``/device:TPU:<n>``; the line ``XLA Ops`` holds one
  event per operation the device ran. Busy time of a device is the *union* of
  those intervals (operations can nest or overlap), cut to the traced window.
- The traced window is the harness's own ``bench:traced_window`` annotation on
  a host thread, which the profiler stamps on the same clock; without it, the
  extent of all events.
- Idle gaps are the complement of the busy union inside the window. A gap is
  named by the innermost harness annotation (``bench:*``, written by tees.py
  around the program's boundaries) that covers half of it or more, else
  ``unattributed``; the 40 longest gaps are summed by name.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

WINDOW_EVENT = "bench:traced_window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def short_name(hlo: str) -> str:
    """The profiler names a device operation by its whole HLO instruction.
    Kept: the instruction's name, the start of its result type, its opcode and,
    for a custom call, the target — ``tpu_custom_call`` is a Pallas kernel."""
    head, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:120]
    opcode = re.search(r"\)?\s([a-z][a-z0-9-]*)\(", rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    parts = [head, rest[:48].strip(), opcode.group(1) if opcode else ""]
    if target:
        parts.append(target.group(1))
    return " ".join(p for p in parts if p)


def load(path: str, keep_host_prefix: str = "bench:") -> Dict[str, Any]:
    """Device planes whole, operations by their short names; of the host planes
    only the harness's annotations."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [
                [short_name(e.name) if device else e.name, int(e.start_ns), int(e.duration_ns)]
                for e in line.events
                if device or e.name.startswith(keep_host_prefix)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        elif end > start:
            out.append((start, end))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def reduce(trace: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """``{"window_s", "busy_s", "devices", "op_seconds": {name: s}, "op_counts":
    {name: n}, "device_ops": [[name, s]...], "idle_gaps": [[name, s]...]}``, or
    None where no operation ran on a device."""
    host_events = [
        (e[0], e[1], e[1] + e[2])
        for p in trace["planes"] if not p["name"].startswith("/device:")
        for line in p["lines"] for e in line["events"]
    ]
    device_planes = [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]
    per_device = []
    for p in device_planes:
        ops = [e for line in p["lines"] if line["name"] == OPS_LINE for e in line["events"]]
        if ops:
            per_device.append(ops)
    if not per_device:
        return None
    windows = [(s, e) for name, s, e in host_events if name == WINDOW_EVENT]
    if windows:
        lo, hi = windows[0]
    else:
        every = [(e[1], e[1] + e[2]) for ops in per_device for e in ops]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    op_seconds: Dict[str, float] = {}
    op_counts: Dict[str, int] = {}
    busy_ns, gaps = 0, []
    for ops in per_device:
        inside = [e for e in ops if e[1] + e[2] > lo and e[1] < hi]
        for name, start, dur in inside:
            op_seconds[name] = op_seconds.get(name, 0.0) + (min(start + dur, hi) - max(start, lo)) / 1e9
            op_counts[name] = op_counts.get(name, 0) + 1
        busy = union(_clip([(e[1], e[1] + e[2]) for e in inside], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    named: Dict[str, float] = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[: 4 * top]:
        # the innermost annotation (the shortest) that covers half of the gap or more
        covering = [
            (he - hs, name) for name, hs, he in host_events
            if name != WINDOW_EVENT and min(he, e) - max(hs, s) >= (e - s) / 2
        ]
        best = min(covering)[1] if covering else "unattributed"
        named[best] = named.get(best, 0.0) + (e - s) / 1e9
    ranked = sorted(op_seconds.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / len(per_device),
        "devices": len(per_device),
        "op_seconds": op_seconds,
        "op_counts": op_counts,
        "device_ops": [[k, v] for k, v in ranked[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(named.items(), key=lambda kv: -kv[1])[:top]],
    }


def describe(path: str) -> None:
    """A look at a trace by hand: every plane and line, its extent, its most
    frequent event names."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            start = min(e.start_ns for e in events)
            end = max(e.start_ns + e.duration_ns for e in events)
            names: Dict[str, int] = {}
            for e in events:
                names[e.name] = names.get(e.name, 0) + 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:8]
            print(f"  line {line.name!r}: {len(events)} events, {start} .. {end} ns, {common}")


def sample(path: str, out: str, window_ns: int = 400_000_000) -> None:
    """A small recorded trace for the tests: the plain form, cut to the first
    ``window_ns`` after the traced window opens."""
    import json

    trace = load(path)
    lo = min(
        (e[1] for p in trace["planes"] for line in p["lines"] for e in line["events"]
         if e[0] == WINDOW_EVENT), default=None)
    if lo is None:
        lo = min(e[1] for p in trace["planes"] for line in p["lines"] for e in line["events"])
    for p in trace["planes"]:
        for line in p["lines"]:
            line["events"] = [
                [e[0], e[1] - lo, min(e[2], window_ns - (e[1] - lo))] for e in line["events"]
                if e[0] == WINDOW_EVENT or lo <= e[1] < lo + window_ns
            ]
            for e in line["events"]:
                if e[0] == WINDOW_EVENT:
                    e[1], e[2] = 0, window_ns
        device = p["name"].startswith("/device:")
        p["lines"] = [line for line in p["lines"]
                      if line["events"] and (not device or line["name"] == OPS_LINE)]
    with open(out, "w") as f:
        json.dump(trace, f, separators=(",", ":"))


if __name__ == "__main__":
    import sys

    if len(sys.argv) == 3 and sys.argv[1] == "describe":
        describe(sys.argv[2])
    elif len(sys.argv) == 4 and sys.argv[1] == "sample":
        sample(sys.argv[2], sys.argv[3])
    else:
        sys.exit("usage: trace_reduce.py describe <xplane.pb> | sample <xplane.pb> <out.json>")
