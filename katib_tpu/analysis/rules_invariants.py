"""Repo-invariant rules (KTI3xx).

These encode contracts PRs 2-5 established across module boundaries —
exactly the kind an innocent-looking local edit silently breaks:

- **KTI301 unflushed-preempt-raise** — ``raise TrialPreempted``/``raise
  TrialKilled`` with no preceding ``flush`` call in the same function. PR
  2/3 invariant: a preempted/killed trial's metrics must be durable before
  the scheduler observes the unwind and requeues it (write-behind buffering
  made "the row was reported" != "the row is persisted").
- **KTI302 uncataloged-metric-or-event** — a metric family emitted via
  ``*.inc/set_gauge/observe`` or an event reason recorded via
  ``recorder.event(...)`` whose string literal is missing from the
  ``_HELP_CATALOG`` / ``EVENT_CATALOG`` tables in ``controller/events.py``.
  The catalogs feed ``# HELP`` exposition lines and the operator docs
  (docs/observability.md); an uncataloged name ships an undocumented
  surface. Dynamic names (f-strings) are skipped — keep them enumerable.
- **KTI303 knob-without-env-override** — a ``RuntimeConfig`` field missing
  from the ``ENV_OVERRIDES`` table in ``config.py``. Every knob must be
  settable without shipping a config file (the reference's env-trumps-
  config layering, consts/const.go:93-103); the table is what load_config
  applies, so membership IS the override.
- **KTI304 unbounded-device-probe** — a direct ``jax.devices()`` /
  ``jax.local_devices()`` call outside ``utils/backend.py``. The first
  such call of a process initializes the backend — it takes the chip for
  this process, and it is the call that fails when there is none;
  ``utils.backend.bounded_devices`` / ``bounded_local_devices`` make that
  first call once, cache the verdict and report a failure once — an
  unguarded call site can take the chip from the process that should
  have it, or fail a second time in a second way.
- **KTI305 nonatomic-json-persist** — a JSON write into a file opened
  ``"w"`` with no ``os.replace`` afterwards in the same function. Every
  persistence path in the repo (state records, checkpoints, snapshots)
  uses the tmp+``os.replace`` idiom so a crash mid-write leaves the
  previous record intact; a bare ``open(path, "w")`` + ``json.dump``
  leaves a truncated file that poisons the next load — exactly the
  checkpoint corruption the crash-tolerant controller (ISSUE 14) cannot
  recover from.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from .common import Finding, RuleContext, dotted_name, literal_str

PREEMPT_EXCEPTIONS = ("TrialPreempted", "TrialKilled")
METRIC_RECEIVERS = ("metrics", "metrics_registry", "registry")
EVENT_RECEIVERS = ("recorder", "events")


def check(tree: ast.Module, ctx: RuleContext) -> List[Finding]:
    out: List[Finding] = []
    out += _unflushed_preempt_raise(tree, ctx)
    out += _uncataloged(tree, ctx)
    if ctx.path.endswith("config.py"):
        out += _knob_without_env(tree, ctx)
    out += _unbounded_device_probe(tree, ctx)
    out += _nonatomic_json_persist(tree, ctx)
    return sorted(set(out), key=Finding.sort_key)


# -- KTI301 ------------------------------------------------------------------

def _unflushed_preempt_raise(tree: ast.Module, ctx: RuleContext) -> List[Finding]:
    out: List[Finding] = []
    for func in ast.walk(tree):
        if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        flush_lines = [
            node.lineno
            for node in ast.walk(func)
            if isinstance(node, ast.Call)
            and (
                (isinstance(node.func, ast.Attribute) and "flush" in node.func.attr)
                or (isinstance(node.func, ast.Name) and "flush" in node.func.id)
            )
        ]
        for node in ast.walk(func):
            if not isinstance(node, ast.Raise) or node.exc is None:
                continue
            exc = node.exc
            name = None
            if isinstance(exc, ast.Call):
                name = dotted_name(exc.func)
            elif isinstance(exc, (ast.Name, ast.Attribute)):
                name = dotted_name(exc)
            if name is None or name.split(".")[-1] not in PREEMPT_EXCEPTIONS:
                continue
            if not any(line < node.lineno for line in flush_lines):
                out.append(
                    Finding(
                        ctx.path, node.lineno, "KTI301",
                        f"raise {name.split('.')[-1]} without a preceding "
                        "obslog flush() in this function — buffered metrics "
                        "must be durable before the scheduler requeues the "
                        "trial (PR 2/3 invariant)",
                    )
                )
    return out


# -- KTI302 ------------------------------------------------------------------

def _receiver_tail(node: ast.AST) -> str:
    """self.metrics_registry -> 'metrics_registry', metrics -> 'metrics'."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _uncataloged(tree: ast.Module, ctx: RuleContext) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not isinstance(node.func, ast.Attribute):
            continue
        method = node.func.attr
        recv = _receiver_tail(node.func.value).lower()
        if (
            ctx.metric_catalog is not None
            and method in ("inc", "set_gauge", "observe")
            and any(r in recv for r in METRIC_RECEIVERS)
            and node.args
        ):
            name = literal_str(node.args[0], ctx.constants)
            if name is not None and name not in ctx.metric_catalog:
                out.append(
                    Finding(
                        ctx.path, node.lineno, "KTI302",
                        f"metric family {name!r} has no _HELP_CATALOG entry "
                        "in controller/events.py — add one (and a line in "
                        "docs/observability.md)",
                    )
                )
        if (
            ctx.event_catalog is not None
            and method == "event"
            and any(r in recv for r in EVENT_RECEIVERS)
            and len(node.args) >= 4
        ):
            reason = literal_str(node.args[3], ctx.constants)
            if reason is not None and reason not in ctx.event_catalog:
                out.append(
                    Finding(
                        ctx.path, node.lineno, "KTI302",
                        f"event reason {reason!r} has no EVENT_CATALOG entry "
                        "in controller/events.py — add one so operators can "
                        "look it up",
                    )
                )
    return out


# -- KTI304 ------------------------------------------------------------------

# the one module allowed to touch the raw probes: it IS the bounded wrapper
DEVICE_PROBE_HOME = "utils/backend.py"
DEVICE_PROBE_CALLS = ("jax.devices", "jax.local_devices")


def _unbounded_device_probe(tree: ast.Module, ctx: RuleContext) -> List[Finding]:
    if ctx.path.replace("\\", "/").endswith(DEVICE_PROBE_HOME):
        return []
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        if name in DEVICE_PROBE_CALLS:
            out.append(
                Finding(
                    ctx.path, node.lineno, "KTI304",
                    f"direct {name}() call — the first probe of a process "
                    "initializes the backend and takes the chip; use "
                    "utils.backend.bounded_devices()/bounded_local_devices() "
                    "(one probe, cached verdict) instead",
                )
            )
    return out


# -- KTI305 ------------------------------------------------------------------

def _is_write_open(call: ast.AST) -> bool:
    """open(path, "w"/"wt"/"w+", ...) — a truncating text open. Read opens
    and binary opens (pickle paths manage their own tmp files) stay out."""
    if not isinstance(call, ast.Call) or dotted_name(call.func) != "open":
        return False
    mode = None
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
        mode = call.args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            mode = kw.value.value
    return isinstance(mode, str) and "w" in mode and "b" not in mode


def _json_write_lines(body: List[ast.stmt]) -> List[int]:
    """Lines inside a with-open("w") body that serialize JSON into the
    handle: ``json.dump(...)`` or ``<f>.write(json.dumps(...))``."""
    out: List[int] = []
    for stmt in body:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name is not None and name.endswith("json.dump"):
                out.append(node.lineno)
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "write"
                and node.args
                and isinstance(node.args[0], ast.Call)
                and (dotted_name(node.args[0].func) or "").endswith("json.dumps")
            ):
                out.append(node.lineno)
    return out


def _nonatomic_json_persist(tree: ast.Module, ctx: RuleContext) -> List[Finding]:
    out: List[Finding] = []
    for func in ast.walk(tree):
        if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        replace_lines = [
            node.lineno
            for node in ast.walk(func)
            if isinstance(node, ast.Call)
            and dotted_name(node.func) in ("os.replace", "os.rename")
        ]
        for node in ast.walk(func):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            if not any(_is_write_open(item.context_expr) for item in node.items):
                continue
            for line in _json_write_lines(node.body):
                if not any(r >= line for r in replace_lines):
                    out.append(
                        Finding(
                            ctx.path, line, "KTI305",
                            "JSON written to an open(.., 'w') handle with no "
                            "os.replace afterwards in this function — a crash "
                            "mid-write corrupts the record; write to "
                            "<path>.tmp and os.replace it into place "
                            "(the repo-wide persistence idiom)",
                        )
                    )
    return out


# -- KTI303 ------------------------------------------------------------------

def _knob_without_env(tree: ast.Module, ctx: RuleContext) -> List[Finding]:
    runtime_cls: Optional[ast.ClassDef] = None
    override_keys: Optional[Set[str]] = None
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "RuntimeConfig":
            runtime_cls = node
        targets: list = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Name) and t.id == "ENV_OVERRIDES" and isinstance(
                node.value, ast.Dict
            ):
                override_keys = {
                    k.value
                    for k in node.value.keys
                    if isinstance(k, ast.Constant) and isinstance(k.value, str)
                }
    if runtime_cls is None:
        return []
    out: List[Finding] = []
    for stmt in runtime_cls.body:
        if not isinstance(stmt, ast.AnnAssign) or not isinstance(stmt.target, ast.Name):
            continue
        field = stmt.target.id
        if override_keys is None or field not in override_keys:
            out.append(
                Finding(
                    ctx.path, stmt.lineno, "KTI303",
                    f"RuntimeConfig.{field} has no ENV_OVERRIDES entry — "
                    "every knob must be overridable via KATIB_TPU_* env "
                    "(config.load_config applies the table)",
                )
            )
    return out
