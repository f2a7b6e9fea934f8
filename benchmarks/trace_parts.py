"""Device time by the train step's parts, from a profiler trace (``*.xplane.pb``).

What ``trace_reduce`` keeps, and besides:

- each device operation's ``op_name``, as a fourth element of its event. The
  program names its parts there (``katib_tpu.tracing.STEP_PARTS``, read by
  ``part_of``). It comes from the device plane's ``tf_op`` stat where the
  profiler writes one, else from the ``Hlo Proto`` of the operation's module
  in the ``/host:metadata`` plane, joined by instruction name; a fusion with
  no ``op_name`` of its own takes its root's. Neither is a second compile.
- the ``XLA Modules`` line of a device plane: one event a program run;
- the program's ``katib:*`` annotations beside the harness's ``bench:*``.

``reduce`` returns every key of ``trace_reduce.reduce`` with its value, the
idle gaps widened (below), and:

- ``part_seconds``: ``{part: [forward_s, backward_s]}``, ``unnamed`` among
  them, over the operations inside whole runs of the step's module (the
  module that runs longest in the window) that lie wholly inside the traced
  window; a device's first and last runs are never whole, since the
  trace's start and end may have cut them. Each instant of a run's busy union is credited once, to the
  innermost operation running then (the latest started), so the parts sum to
  that union. None where no operation carries an ``op_name``.
- ``step_runs``: how many such runs there are (a device's, where there are
  several).
- ``dispatch_lead_us``: the least, over those runs, of a run's start minus
  the start of the ``katib:step`` annotation that dispatched it. Pairs are
  counted from the first ``katib:report``: the k-th annotation that starts
  after it with the k-th run of the step's module that ends after it. Below
  0 the host's and the device's clocks disagree, and the host's events are
  shifted by it before the gaps are named. None without ``katib:step``.
- ``idle_gaps``: a gap that a ``bench:*`` annotation names keeps its name; a
  gap that none names takes the innermost ``katib:*`` annotation that covers
  half of it or more, else ``unattributed``.

    python3 benchmarks/trace_parts.py describe <xplane.pb>
    python3 benchmarks/trace_parts.py parts <xplane.pb>
    python3 benchmarks/trace_parts.py sample <xplane.pb> <out.json>
"""

from __future__ import annotations

import bisect
import heapq
import os
import sys
from typing import Any, Dict, Iterator, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.dirname(HERE), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import trace_reduce  # noqa: E402
from katib_tpu.tracing import STEP_PARTS, part_of  # noqa: E402

MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HOST_PREFIXES = ("bench:", "katib:")
STEP_EVENT, REPORT_EVENT = "katib:step", "katib:report"
UNNAMED = "unnamed"


# -- a protobuf reader for the few messages read here ---------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, span: Optional[Tuple[int, int]] = None) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one message; a length-delimited value is its
    (start, end) in ``buf``, a fixed-width one its bytes."""
    i, end = span if span else (0, len(buf))
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf, span):
    """A map<int64, Message> entry's (key, value span)."""
    key, value = 0, (0, 0)
    for num, v in _fields(buf, span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _planes_metadata(buf: bytes) -> Dict[str, List[Tuple[str, Dict[str, Any]]]]:
    """{plane name: [(event metadata name, {stat name: value})]} of the device
    planes and the metadata plane; lines are skipped unread."""
    out = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, v in _fields(buf, plane):
            if pnum == 2:
                name = _text(buf, v)
            elif pnum == 4:
                events.append(_map_entries(buf, v)[1])
            elif pnum == 5:
                sid, meta = _map_entries(buf, v)
                stat_names[sid] = next((_text(buf, s) for n, s in _fields(buf, meta) if n == 2), "")
        if not (name.startswith("/device:") or name == METADATA_PLANE):
            continue
        rows = []
        for span in events:
            ename, stats = "", {}
            for enum, v in _fields(buf, span):
                if enum == 2:
                    ename = _text(buf, v)
                elif enum == 5:
                    sid, value = 0, None
                    for snum, sv in _fields(buf, v):
                        if snum == 1:
                            sid = sv
                        elif snum == 5:
                            value = _text(buf, sv)
                        elif snum == 6:
                            value = buf[sv[0]:sv[1]]
                        elif snum == 7:
                            value = stat_names.get(sv, "")
                        elif snum in (3, 4):
                            value = sv
                    stats[stat_names.get(sid, str(sid))] = value
            rows.append((ename, stats))
        out[name] = rows
    return out


def hlo_op_names(hlo_proto: bytes) -> Dict[str, str]:
    """{instruction name: op_name} of one module's ``HloProto``; a fusion with
    no op_name takes its fused computation's root's, else the last one named
    in it."""
    module = next((v for n, v in _fields(hlo_proto) if n == 1), None)
    if module is None:
        return {}
    computations = {}
    for num, comp in _fields(hlo_proto, module):
        if num != 3:
            continue
        cid, root, instructions = None, None, []
        for cnum, v in _fields(hlo_proto, comp):
            if cnum == 5:
                cid = v
            elif cnum == 6:
                root = v
            elif cnum == 2:
                name, opcode, op_name, iid, called = "", "", "", None, []
                for inum, iv in _fields(hlo_proto, v):
                    if inum == 1:
                        name = _text(hlo_proto, iv)
                    elif inum == 2:
                        opcode = _text(hlo_proto, iv)
                    elif inum == 35:
                        iid = iv
                    elif inum == 7:
                        op_name = next((_text(hlo_proto, m) for k, m in _fields(hlo_proto, iv)
                                        if k == 2), "")
                    elif inum == 38:
                        if isinstance(iv, tuple):  # packed
                            i = iv[0]
                            while i < iv[1]:
                                c, i = _varint(hlo_proto, i)
                                called.append(c)
                        else:
                            called.append(iv)
                instructions.append((name, op_name, iid, called if opcode == "fusion" else []))
        computations[cid] = (root, instructions)

    def named(cid) -> str:
        root, instructions = computations.get(cid, (None, []))
        by_root = [op for _, op, iid, _ in instructions if iid == root and op]
        inside = [op for _, op, _, _ in instructions if op]
        return (by_root or inside[-1:] or [""])[0]

    out = {}
    for _, instructions in computations.values():
        for name, op_name, _, called in instructions:
            out[name] = op_name or next((n for n in map(named, called) if n), "")
    return out


def _instruction(hlo: str) -> str:
    return hlo.partition(" = ")[0].strip().lstrip("%")


def _base(module: str) -> str:
    return module.split("(", 1)[0]


def load(path: str) -> Dict[str, Any]:
    """``trace_reduce.load``'s plain form, with an ``op_name`` on each event of
    ``XLA Ops`` (``""`` where none is found), the ``XLA Modules`` line, and the
    host's ``bench:*`` and ``katib:*`` annotations."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    metadata = _planes_metadata(raw)
    protos = {name: stats.get("Hlo Proto") for name, stats in metadata.get(METADATA_PLANE, [])}
    by_module: Dict[str, Dict[str, str]] = {}

    def joined(module: str, instruction: str) -> str:
        key = module if module in protos else next(
            (m for m in protos if _base(m) == _base(module)), None)
        if key is None or not protos[key]:
            return ""
        if key not in by_module:
            by_module[key] = hlo_op_names(protos[key])
        return by_module[key].get(instruction, "")

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        stat_op = {name: stats.get("tf_op") for name, stats in metadata.get(plane.name, [])
                   if stats.get("tf_op")}
        lines = []
        runs = sorted((int(e.start_ns), int(e.start_ns) + int(e.duration_ns), e.name)
                      for line in plane.lines if device and line.name == MODULES_LINE
                      for e in line.events)
        starts = [r[0] for r in runs]
        for line in plane.lines:
            if device and line.name == trace_reduce.OPS_LINE:
                events = []
                for e in line.events:
                    start = int(e.start_ns)
                    op_name = stat_op.get(e.name)
                    if op_name is None:
                        k = bisect.bisect_right(starts, start) - 1
                        module = runs[k][2] if k >= 0 and start < runs[k][1] else ""
                        op_name = joined(module, _instruction(e.name))
                    events.append([trace_reduce.short_name(e.name), start, int(e.duration_ns), op_name])
            elif device:
                keep_name = line.name == MODULES_LINE
                events = [[e.name if keep_name else trace_reduce.short_name(e.name),
                           int(e.start_ns), int(e.duration_ns)] for e in line.events]
            else:
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events if e.name.startswith(HOST_PREFIXES)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def innermost_seconds(ops: List[List[Any]]) -> Dict[Tuple[str, bool], float]:
    """{(part, backward): seconds}: each instant of the ops' busy union credited
    once, to the op running then that started last (the shorter on a tie)."""
    bounds = sorted({x for _, s, d, *_ in ops for x in (s, s + d)})
    order = sorted(range(len(ops)), key=lambda i: ops[i][1])
    out: Dict[Tuple[str, bool], float] = {}
    active: List[Tuple[int, int, int]] = []  # (-start, end, index)
    k = 0
    for lo, hi in zip(bounds, bounds[1:]):
        while k < len(order) and ops[order[k]][1] <= lo:
            i = order[k]
            heapq.heappush(active, (-ops[i][1], ops[i][1] + ops[i][2], i))
            k += 1
        while active and active[0][1] <= lo:
            heapq.heappop(active)
        if not active:
            continue
        op = ops[active[0][2]]  # started last; of those, ends first
        key = part_of(op[3] if len(op) > 3 else "")
        out[key] = out.get(key, 0.0) + (hi - lo) / 1e9
    return out


def _window(host_events, per_device) -> Tuple[int, int]:
    windows = [(s, e) for name, s, e in host_events if name == trace_reduce.WINDOW_EVENT]
    if windows:
        return windows[0]
    every = [(e[1], e[1] + e[2]) for ops in per_device for e in ops]
    return min(s for s, _ in every), max(e for _, e in every)


def _plain(trace: Dict[str, Any]) -> Dict[str, Any]:
    """What trace_reduce.reduce reads: events of three elements, the host's
    ``bench:*`` annotations."""
    planes = []
    for p in trace["planes"]:
        device = p["name"].startswith("/device:")
        lines = [{"name": line["name"], "events": [e[:3] for e in line["events"]
                                                   if device or e[0].startswith("bench:")]}
                 for line in p["lines"]]
        planes.append({"name": p["name"], "lines": [line for line in lines if line["events"]]})
    return {"planes": planes}


def reduce(trace: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """``trace_reduce.reduce``'s keys (idle gaps widened) plus ``part_seconds``,
    ``step_runs`` and ``dispatch_lead_us``; None where no operation ran on a device."""
    out = trace_reduce.reduce(_plain(trace), top)
    if out is None:
        return None
    host = [(e[0], e[1], e[1] + e[2]) for p in trace["planes"] if not p["name"].startswith("/device:")
            for line in p["lines"] for e in line["events"]]
    devices = [p for p in trace["planes"] if p["name"].startswith(trace_reduce.DEVICE_PREFIX)]
    per_device, modules = [], []
    for p in devices:
        ops = [e for line in p["lines"] if line["name"] == trace_reduce.OPS_LINE for e in line["events"]]
        if ops:
            per_device.append(ops)
            modules.append(sorted((e[1], e[1] + e[2], e[0]) for line in p["lines"]
                                  if line["name"] == MODULES_LINE for e in line["events"]))
    lo, hi = _window(host, per_device)

    # the step's module: the one whose runs hold most of the window
    held: Dict[str, int] = {}
    for runs in modules:
        for s, e, name in runs:
            held[name] = held.get(name, 0) + max(0, min(e, hi) - max(s, lo))
    step = max(held, key=held.get) if held else None
    # the trace's start and end may cut a device's first and last runs: only runs between two count
    whole = [[(s, e) for before, (s, e, name), after in zip(runs, runs[1:], runs[2:])
              if name == step and max(lo, before[1]) <= s and e <= min(hi, after[0])]
             for runs in modules]

    seconds: Dict[Tuple[str, bool], float] = {}
    named = False
    for ops, runs in zip(per_device, whole):
        ops = sorted(ops, key=lambda e: e[1])
        starts = [e[1] for e in ops]
        for s, e in runs:
            inside = [op for op in ops[bisect.bisect_left(starts, s):bisect.bisect_right(starts, e)]
                      if op[1] + op[2] <= e]
            named = named or any(len(op) > 3 and op[3] for op in inside)
            for key, sec in innermost_seconds(inside).items():
                seconds[key] = seconds.get(key, 0.0) + sec
    n_devices = len(per_device)
    runs_a_device = sum(len(r) for r in whole) / n_devices if n_devices else 0
    parts = None
    if named and runs_a_device:
        parts = {name: [0.0, 0.0] for name in STEP_PARTS + (UNNAMED,)}
        for (name, backward), sec in seconds.items():
            parts[name][int(backward)] += sec / n_devices

    lead = _dispatch_lead(host, modules[0] if modules else [], step, whole[0] if whole else [])
    if lead is not None and lead < 0:
        shift = int(lead * 1e3)
        host = [(name, s + shift, e + shift) for name, s, e in host]
    out.update(
        part_seconds=parts, step_runs=runs_a_device, dispatch_lead_us=lead,
        idle_gaps=_named_gaps(host, per_device, lo, hi, top),
    )
    return out


def _dispatch_lead(host, runs, step, whole) -> Optional[float]:
    steps = sorted(s for name, s, _ in host if name == STEP_EVENT)
    if not steps or not whole:
        return None
    anchor = min((s for name, s, _ in host if name == REPORT_EVENT), default=None)
    if anchor is not None:
        steps = [s for s in steps if s > anchor]
        runs = [r for r in runs if r[1] > anchor]
    kept = set(whole)
    leads = [(r[0] - a) / 1e3 for a, r in zip(steps, (r for r in runs if r[2] == step))
             if (r[0], r[1]) in kept]
    return min(leads) if leads else None


def _named_gaps(host, per_device, lo, hi, top) -> List[List[Any]]:
    """trace_reduce's gaps and names; a gap no ``bench:*`` annotation names
    may take a ``katib:*`` one."""
    gaps = []
    for ops in per_device:
        inside = [e for e in ops if e[1] + e[2] > lo and e[1] < hi]
        busy = trace_reduce.union(trace_reduce._clip([(e[1], e[1] + e[2]) for e in inside], lo, hi))
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    named: Dict[str, float] = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[: 4 * top]:
        best = "unattributed"
        for prefix in ("bench:", "katib:"):
            covering = [(he - hs, name) for name, hs, he in host
                        if name != trace_reduce.WINDOW_EVENT and name.startswith(prefix)
                        and min(he, e) - max(hs, s) >= (e - s) / 2]
            if covering:
                best = min(covering)[1]
                break
        named[best] = named.get(best, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(named.items(), key=lambda kv: -kv[1])[:top]]


def per_step_ms(reduced: Dict[str, Any]) -> Dict[str, List[float]]:
    """``part_seconds`` as ms a step run, forward and backward."""
    runs = reduced.get("step_runs") or 0
    return {name: [1e3 * f / runs, 1e3 * b / runs]
            for name, (f, b) in (reduced.get("part_seconds") or {}).items()} if runs else {}


def describe(path: str) -> None:
    """trace_reduce.describe, then one event's stats on each device line and
    the stat names of each plane's event metadata."""
    from jax.profiler import ProfileData

    trace_reduce.describe(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                first = next(iter(line.events), None)
                if first is not None:
                    print(f"stats {plane.name!r} {line.name!r}: {list(first.stats)[:12]}")
    with open(path, "rb") as f:
        metadata = _planes_metadata(f.read())
    for plane, rows in metadata.items():
        names: Dict[str, int] = {}
        for _, stats in rows:
            for k in stats:
                names[k] = names.get(k, 0) + 1
        example = next((s for _, s in rows if s), {})
        print(f"metadata stats {plane!r}: {len(rows)} entries, {names}, e.g. "
              f"{ {k: (v if isinstance(v, (int, str)) else f'<{len(v)} bytes>') for k, v in example.items()} }"[:600])


def sample(path: str, out: str, window_ns: int = 650_000_000) -> None:
    """A small recorded trace for the tests: ``load``'s form, cut to
    ``window_ns`` around the traced window's first ``katib:report`` (its
    start, where there is none) and shifted to start at 0; of a device plane
    its operations (clipped to the cut) and its module runs (whole ones only)."""
    import json

    trace = load(path)
    host = [e for p in trace["planes"] if not p["name"].startswith("/device:")
            for line in p["lines"] for e in line["events"]]
    window = next((e for e in host if e[0] == trace_reduce.WINDOW_EVENT), None)
    opens = window[1] if window else min(e[1] for e in host)
    report = min((e[1] for e in host if e[0] == REPORT_EVENT and e[1] >= opens), default=None)
    lo = max(opens, report - window_ns // 2) if report is not None else opens
    hi = lo + window_ns
    for p in trace["planes"]:
        device = p["name"].startswith("/device:")
        if device:
            p["lines"] = [line for line in p["lines"] if line["name"] in (trace_reduce.OPS_LINE, MODULES_LINE)]
        for line in p["lines"]:
            whole_only = line["name"] == MODULES_LINE
            kept = []
            for e in line["events"]:
                if e[0] == trace_reduce.WINDOW_EVENT:
                    kept.append([e[0], 0, window_ns] + e[3:])
                elif whole_only:
                    if lo <= e[1] and e[1] + e[2] <= hi:
                        kept.append([e[0], e[1] - lo, e[2]] + e[3:])
                elif e[1] < hi and e[1] + e[2] > lo:
                    start = max(e[1], lo)
                    kept.append([e[0], start - lo, min(e[1] + e[2], hi) - start] + e[3:])
            line["events"] = kept
        p["lines"] = [line for line in p["lines"] if line["events"]]
    trace["planes"] = [p for p in trace["planes"] if p["lines"]]
    with open(out, "w") as f:
        json.dump(trace, f, separators=(",", ":"))


def _main(argv: List[str]) -> int:
    import json

    if len(argv) == 2 and argv[0] == "describe":
        describe(argv[1])
    elif len(argv) == 2 and argv[0] == "parts":
        reduced = reduce(load(argv[1]))
        if reduced is None:
            print("no operation ran on a device")
            return 1
        shown = {k: v for k, v in reduced.items() if k not in ("op_seconds", "op_counts")}
        shown["part_ms_a_step"] = per_step_ms(reduced)
        print(json.dumps(shown))
    elif len(argv) == 3 and argv[0] == "sample":
        sample(argv[1], argv[2])
    else:
        print(__doc__.split("\n\n")[-1].strip(), file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(_main(sys.argv[1:]))
