"""Semantic program analysis — jaxpr-level compile fingerprints (ISSUE 7).

BENCH_r02/r04 measured the e2e as compile-dominated (23–51s XLA compile vs
~2ms steps), and nothing in the repo could *statically* tell whether two
trials will compile to the same program. This module can: it traces a
trial's canonical program with ``jax.eval_shape`` / ``jax.make_jaxpr``
under avals derived from the experiment's search space — **no
compilation, no execution, no devices** (``JAX_PLATFORMS=cpu`` suffices)
— and produces

- a canonical, process-stable **compile fingerprint**: a sha256 over the
  jaxpr's primitives, avals (shape/dtype/weak-type), canonicalized static
  params (nested jaxprs recursed, memory addresses stripped), donation and
  mesh/sharding statics. No ``id()``, no hash-seed dependence — two
  processes tracing the same program agree byte-for-byte;
- a per-parameter classification of each search-space dimension:
  *shape-affecting* (the fingerprint changes when the parameter is
  perturbed at its search-space corners → one recompile per distinct
  value), *runtime-scalar* (fingerprint stable and the value enters the
  program as a traced input → safe to vary under one executable), *host*
  (probe-declared host-side knob: loop counts, data sizes), or *baked*
  (fingerprint stable but the value is NOT a program input — it was
  captured at trace time; varying it silently reuses a stale constant),
  or *fixed* (single-point dimension: it can never vary, so no hazard);
- a cost estimate (analysis/costmodel.py): FLOPs, parameter/activation
  bytes, peak live-aval HBM.

Trial entry points opt in by exposing ``fn.abstract_program(assignments)
-> ProgramProbe`` describing their canonical jitted step abstractly
(models/mnist_cnn.py and models/transformer.py ship probes). Findings are
reported through the PR 6 engine conventions as the KTX4xx family and obey
suppressions.toml / inline ignores / the stable sort.

Control-plane consumers (all best-effort — analysis failure never breaks
scheduling):

- admission pre-flight (controller/experiment.py): reject when the
  predicted peak HBM exceeds device memory, warn near capacity;
- pack formation (controller/packing.py): members group by fingerprint
  instead of ``id(template)``;
- dispatch ordering (controller/scheduler.py): same-fingerprint units run
  consecutively so the first trial's compile warms the cache for the rest
  — the cheap precursor to ROADMAP 1's AOT compile service;
- the ``katib-tpu analyze`` CLI.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from .common import Finding
from .costmodel import CostEstimate, aval_bytes, estimate_cost

CLASS_SHAPE = "shape-affecting"
CLASS_SCALAR = "runtime-scalar"
CLASS_HOST = "host"
CLASS_BAKED = "baked"
CLASS_FIXED = "fixed"  # single-point dimension: cannot vary, so no hazard

# KTX4xx: semantic findings (docs/static-analysis.md "Semantic analysis").
KTX_SUMMARIES = {
    "KTX401": "search parameter baked as a trace-time constant",
    "KTX402": "hyperparameter traced as a weak-typed scalar",
    "KTX403": "aval mismatch across would-be pack members",
    "KTX404": "entry point exposes no abstract program probe",
}


@dataclass
class ProgramProbe:
    """One trial function's canonical program, described abstractly.

    ``fn(*args)`` must be traceable by ``jax.make_jaxpr`` with ``args``
    given as pytrees of ``jax.ShapeDtypeStruct`` — the probe never builds
    real tensors. ``hyperparams`` maps search-space parameter names to the
    traced scalar inputs carrying them (presence = runtime-scalar
    candidate); ``host_params`` names parameters consumed host-side only
    (epoch counts, dataset sizes) so they classify as *host* rather than
    *baked*. ``statics`` is extra fingerprint material that selects a
    different program without changing avals (mesh layout, parallelism
    degrees)."""

    fn: Callable[..., Any]
    args: Tuple[Any, ...]
    params: Any = None                     # model-parameter subtree (byte count)
    hyperparams: Dict[str, Any] = field(default_factory=dict)
    host_params: Set[str] = field(default_factory=set)
    donate_argnums: Tuple[int, ...] = ()
    statics: Dict[str, Any] = field(default_factory=dict)


@dataclass
class ParamReport:
    """Classification of one search-space dimension."""

    name: str
    type: str                  # double | int | discrete | categorical
    cls: str                   # CLASS_* above
    corner_values: List[str]
    distinct_fingerprints: int  # over baseline + corners

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "type": self.type,
            "class": self.cls,
            "cornerValues": list(self.corner_values),
            "distinctFingerprints": self.distinct_fingerprints,
        }


@dataclass
class ExperimentAnalysis:
    """Everything the control plane and the analyze CLI consume."""

    digest: str                 # stable template digest (id()-free)
    target: str                 # "module:fn" or function qualname
    analyzable: bool
    fingerprint: str = ""       # at baseline assignments
    source_path: str = ""       # repo-relative file of the entry point
    source_line: int = 1
    params: List[ParamReport] = field(default_factory=list)
    classes: Dict[str, str] = field(default_factory=dict)
    cost: Optional[CostEstimate] = None
    findings: List[Finding] = field(default_factory=list)
    error: Optional[str] = None

    def shape_affecting(self) -> List[str]:
        return [p.name for p in self.params if p.cls == CLASS_SHAPE]

    def to_dict(self) -> Dict[str, Any]:
        return {
            "digest": self.digest,
            "target": self.target,
            "analyzable": self.analyzable,
            "fingerprint": self.fingerprint,
            "sourcePath": self.source_path,
            "sourceLine": self.source_line,
            "parameters": [p.to_dict() for p in self.params],
            "cost": self.cost.to_dict() if self.cost else None,
            "findings": [f.to_dict() for f in self.findings],
            "error": self.error,
        }


# ---------------------------------------------------------------------------
# Canonical jaxpr serialization + fingerprint (process-stable by design)
# ---------------------------------------------------------------------------

_HEX_ADDR = re.compile(r"0x[0-9a-fA-F]+")


def _canon_aval(aval) -> str:
    import numpy as np

    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dtype is None or shape is None:
        return f"opaque:{type(aval).__name__}"
    w = "w" if getattr(aval, "weak_type", False) else ""
    try:
        name = np.dtype(dtype).name
    except TypeError:
        # jax extended dtypes (typed PRNG keys such as key<fry> appear in
        # any jaxpr whose body calls jax.random) have no numpy equivalent;
        # their str() form is deterministic and impl-qualified
        name = str(dtype)
    return f"{name}[{'x'.join(str(d) for d in shape)}]{w}"


def _canon_value(v) -> str:
    """Canonicalize one static param value: deterministic across processes,
    free of memory addresses and ``id()``-dependent reprs."""
    import numpy as np

    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return repr(v)
    if isinstance(v, np.dtype) or (isinstance(v, type) and issubclass(v, np.generic)):
        return f"dtype:{np.dtype(v).name}"
    if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):  # ClosedJaxpr
        return "{" + _canon_jaxpr_obj(v.jaxpr) + "}"
    if hasattr(v, "eqns"):  # open Jaxpr
        return "{" + _canon_jaxpr_obj(v) + "}"
    if isinstance(v, np.ndarray):
        h = hashlib.sha1(np.ascontiguousarray(v).tobytes()).hexdigest()[:12]
        return f"ndarray:{np.dtype(v.dtype).name}{v.shape}:{h}"
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_canon_value(x) for x in v) + ")"
    if isinstance(v, (set, frozenset)):
        return "{" + ",".join(sorted(_canon_value(x) for x in v)) + "}"
    if isinstance(v, dict):
        return (
            "{"
            + ",".join(f"{k!r}:{_canon_value(x)}" for k, x in sorted(v.items(), key=lambda kv: repr(kv[0])))
            + "}"
        )
    if hasattr(v, "shape") and hasattr(v, "dtype"):  # aval / ShapeDtypeStruct
        return _canon_aval(v)
    cls = type(v).__name__
    if cls == "Mesh" or cls == "AbstractMesh":
        names = tuple(getattr(v, "axis_names", ()))
        shape = getattr(v, "axis_sizes", None) or tuple(
            getattr(v, "shape", {}).values()
        ) if hasattr(v, "shape") else ()
        return f"mesh:{names}:{tuple(shape)}"
    if callable(v):
        return f"fn:{getattr(v, '__module__', '')}.{getattr(v, '__qualname__', cls)}"
    return _HEX_ADDR.sub("0x", repr(v))


def _canon_jaxpr_obj(j) -> str:
    """Deterministic text form of one (open) jaxpr: variables renumbered by
    first appearance, params sorted by key, nested jaxprs recursed."""
    ids: Dict[Any, str] = {}

    def vref(v) -> str:
        if v.__class__.__name__ == "Literal":
            return f"lit({_canon_value(getattr(v, 'val', None))}:{_canon_aval(v.aval)})"
        if v not in ids:
            ids[v] = f"v{len(ids)}"
        return ids[v]

    lines = [
        "in:" + ",".join(f"{vref(v)}:{_canon_aval(v.aval)}" for v in j.invars),
        "const:" + ",".join(f"{vref(v)}:{_canon_aval(v.aval)}" for v in j.constvars),
    ]
    for eqn in j.eqns:
        params = ";".join(
            f"{k}={_canon_value(v)}" for k, v in sorted(eqn.params.items())
        )
        ins = ",".join(vref(v) for v in eqn.invars)
        outs = ",".join(f"{vref(v)}:{_canon_aval(v.aval)}" for v in eqn.outvars)
        lines.append(f"{eqn.primitive.name}[{params}]({ins})->({outs})")
    lines.append("out:" + ",".join(vref(v) for v in j.outvars))
    return "\n".join(lines)


def fingerprint_jaxpr(closed_jaxpr, probe: Optional[ProgramProbe] = None) -> str:
    """The compile fingerprint: sha256 over the canonical jaxpr text plus
    the probe's donation spec and mesh/sharding statics."""
    text = _canon_jaxpr_obj(closed_jaxpr.jaxpr)
    extras = ""
    if probe is not None:
        extras = (
            f"|donate:{tuple(probe.donate_argnums)}"
            f"|statics:{_canon_value(probe.statics)}"
        )
    h = hashlib.sha256((text + extras).encode()).hexdigest()
    return f"ktfp-{h[:20]}"


# ---------------------------------------------------------------------------
# Template digest (the id()-free pack/dispatch grouping key)
# ---------------------------------------------------------------------------

def template_digest(template) -> str:
    """Stable digest of a trial template — replaces the
    ``id(exp.spec.trial_template)`` pack key (``id()`` reuse after GC could
    merge distinct templates). Serializable fields digest via to_dict();
    in-memory functions contribute module/qualname plus their code's
    definition site (two closures of one ``def`` digest identically — they
    share a program shape, which is exactly the packing question)."""
    d = template.to_dict()
    fn = getattr(template, "function", None)
    ident = ""
    if fn is not None:
        code = getattr(fn, "__code__", None)
        ident = f"{getattr(fn, '__module__', '')}.{getattr(fn, '__qualname__', '')}"
        if code is not None:
            ident += f"@{code.co_filename}:{code.co_firstlineno}"
    basis = json.dumps({"template": d, "function": ident}, sort_keys=True, default=str)
    return hashlib.sha1(basis.encode()).hexdigest()[:12]


def _search_signature(spec) -> str:
    basis = json.dumps([p.to_dict() for p in spec.parameters], sort_keys=True)
    return hashlib.sha1(basis.encode()).hexdigest()[:12]


def search_signature(spec) -> str:
    """Public form of the search-space digest — the transfer-HPO matching
    key (ISSUE 10 warm start) shares the exact digest the analysis cache
    already uses, so two experiments warm-start-match iff their parameter
    specs serialize identically."""
    return _search_signature(spec)


# ---------------------------------------------------------------------------
# Search-space probing points
# ---------------------------------------------------------------------------

def baseline_assignments(spec) -> Dict[str, str]:
    """Mid-space assignment for every search dimension (numeric midpoint /
    middle choice) — the anchor the corner perturbations diff against."""
    from ..suggest.internal.search_space import HyperParameter

    out: Dict[str, str] = {}
    for p in spec.parameters:
        hp = HyperParameter.from_spec(p)
        if hp.is_numeric:
            out[p.name] = hp.from_unit(0.5)
        elif hp.choices:
            out[p.name] = hp.choices[len(hp.choices) // 2]
    return out


def corner_values(param_spec) -> List[str]:
    """Search-space corners for one dimension: numeric min/max, first/last
    choice. Perturbing at the corners (vs the baseline) is the decision
    procedure for shape-affecting vs runtime-scalar."""
    from ..suggest.internal.search_space import HyperParameter

    hp = HyperParameter.from_spec(param_spec)
    if hp.is_numeric:
        return [hp.from_unit(0.0), hp.from_unit(1.0)]
    if hp.choices:
        return [hp.choices[0], hp.choices[-1]]
    return []


# ---------------------------------------------------------------------------
# Tracing (eval_shape/make_jaxpr only — no compilation, no devices)
# ---------------------------------------------------------------------------

def trace_probe(probe: ProgramProbe):
    """ClosedJaxpr of the probe's canonical program. Pure abstract
    interpretation: make_jaxpr over ShapeDtypeStruct avals."""
    import jax

    return jax.make_jaxpr(probe.fn)(*probe.args)


def _probe_fingerprint(builder, assignments: Dict[str, str]) -> Tuple[str, Any, ProgramProbe]:
    probe = builder(dict(assignments))
    closed = trace_probe(probe)
    return fingerprint_jaxpr(closed, probe), closed, probe


def _tree_bytes(tree) -> int:
    if tree is None:
        return 0
    import jax

    return sum(aval_bytes(leaf) for leaf in jax.tree_util.tree_leaves(tree))


def _resolve_template_fn(template):
    """The in-process callable of a template, or None (command templates;
    import failures fail loudly in the executor path, not here)."""
    if getattr(template, "command", None) is not None:
        return None
    if getattr(template, "function", None) is not None:
        return template.function
    if getattr(template, "entry_point", None):
        try:
            from ..controller.executor import resolve_entry_point

            return resolve_entry_point(template)
        except Exception:
            return None
    return None


def _fn_location(fn) -> Tuple[str, int]:
    """(repo-relative source path, def line) of the entry point — the
    anchor KTX findings attach to, so inline ignores and suppressions.toml
    entries address them like any AST finding."""
    import inspect

    from .engine import default_repo_root, repo_relative

    try:
        path = inspect.getsourcefile(fn) or "<unknown>"
        line = fn.__code__.co_firstlineno
    except (TypeError, AttributeError):
        return "<unknown>", 1
    if path != "<unknown>":
        path = repo_relative(path, default_repo_root())
    return path, line


def _target_name(template, fn) -> str:
    if getattr(template, "entry_point", None):
        return template.entry_point
    if fn is not None:
        return f"{getattr(fn, '__module__', '?')}:{getattr(fn, '__qualname__', '?')}"
    return "<command template>"


def analyze_spec(spec) -> ExperimentAnalysis:
    """Full semantic analysis of one experiment spec: fingerprint at the
    baseline, per-parameter corner classification, cost model, KTX4xx
    findings. Raises nothing for unanalyzable templates — the result says
    ``analyzable=False`` (with a KTX404 finding when there is an entry
    point that simply lacks a probe)."""
    template = spec.trial_template
    digest = template_digest(template)
    fn = _resolve_template_fn(template)
    builder = getattr(fn, "abstract_program", None) if fn is not None else None
    target = _target_name(template, fn)
    if builder is None:
        findings = []
        if fn is not None:
            path, line = _fn_location(fn)
            findings.append(
                Finding(
                    path, line, "KTX404",
                    f"entry point {target} exposes no abstract program probe "
                    "(fn.abstract_program); semantic analysis skipped — "
                    "fingerprint packing/ordering and HBM pre-flight are "
                    "unavailable for this experiment",
                )
            )
        return ExperimentAnalysis(
            digest=digest, target=target, analyzable=False, findings=findings
        )

    path, line = _fn_location(fn)
    analysis = ExperimentAnalysis(
        digest=digest, target=target, analyzable=True,
        source_path=path, source_line=line,
    )
    try:
        baseline = baseline_assignments(spec)
        base_fp, closed, probe = _probe_fingerprint(builder, baseline)
        analysis.fingerprint = base_fp
        analysis.cost = estimate_cost(closed, param_bytes=_tree_bytes(probe.params))

        findings: List[Finding] = []
        for p in spec.parameters:
            corners = [v for v in corner_values(p) if v != baseline.get(p.name)]
            fps = {base_fp}
            for v in corners:
                assignments = dict(baseline)
                assignments[p.name] = v
                fp, _, _ = _probe_fingerprint(builder, assignments)
                fps.add(fp)
            if not corners:
                # single-point dimension (pinned host knob, one-element
                # list): it can never take another value, so neither the
                # recompile nor the stale-constant hazard can arise
                cls = CLASS_FIXED
            elif len(fps) > 1:
                cls = CLASS_SHAPE
            elif p.name in probe.hyperparams:
                cls = CLASS_SCALAR
                leaf = probe.hyperparams[p.name]
                if getattr(leaf, "weak_type", False):
                    findings.append(
                        Finding(
                            path, line, "KTX402",
                            f"hyperparameter {p.name!r} traces as a "
                            "weak-typed scalar — Python-scalar inputs split "
                            "the jit cache by promotion type, forcing a "
                            "recompile per value mix; pass "
                            "jnp.asarray(v, jnp.float32)",
                        )
                    )
            elif p.name in probe.host_params:
                cls = CLASS_HOST
            else:
                cls = CLASS_BAKED
                findings.append(
                    Finding(
                        path, line, "KTX401",
                        f"search parameter {p.name!r} is baked as a "
                        "trace-time constant: perturbing it changes neither "
                        "the jaxpr nor any program input — every distinct "
                        "value silently reuses an executable holding a stale "
                        "constant (declare it a traced input or a host param "
                        "in the probe)",
                    )
                )
            analysis.params.append(
                ParamReport(
                    name=p.name,
                    type=p.parameter_type.value,
                    cls=cls,
                    corner_values=corners,
                    distinct_fingerprints=len(fps),
                )
            )
            analysis.classes[p.name] = cls

        pack_capable = template.resources.pack_size > 1 or bool(
            getattr(fn, "supports_packing", False)
        )
        shape_params = analysis.shape_affecting()
        if pack_capable and shape_params:
            findings.append(
                Finding(
                    path, line, "KTX403",
                    "pack-enabled experiment has shape-affecting "
                    f"parameter(s) {', '.join(sorted(shape_params))} — "
                    "members with different values have mismatched avals "
                    "and cannot share one vmapped executable; pack "
                    "formation groups by fingerprint, so such sweeps form "
                    "one pack per distinct value",
                )
            )
        analysis.findings = sorted(set(findings), key=Finding.sort_key)
    except Exception as e:  # analysis is advisory: never break the caller
        analysis.analyzable = False
        analysis.error = f"{type(e).__name__}: {e}"
    return analysis


def analyze_entry(target: str, assignments: Optional[Dict[str, str]] = None) -> ExperimentAnalysis:
    """Analyze a bare ``module:fn`` target (no search space): fingerprint +
    cost at the probe's default assignments. Raises ValueError when the
    target cannot be resolved or has no probe."""
    import importlib

    if ":" not in target:
        raise ValueError(f"target {target!r} is neither a spec file nor module:fn")
    mod_name, fn_name = target.split(":", 1)
    try:
        fn = getattr(importlib.import_module(mod_name), fn_name)
    except (ImportError, AttributeError) as e:
        raise ValueError(f"cannot resolve {target!r}: {e}")
    builder = getattr(fn, "abstract_program", None)
    if builder is None:
        raise ValueError(
            f"{target} exposes no abstract_program probe; see "
            "docs/static-analysis.md (Semantic analysis) for the convention"
        )
    path, line = _fn_location(fn)
    fp, closed, probe = _probe_fingerprint(builder, assignments or {})
    return ExperimentAnalysis(
        digest="",
        target=target,
        analyzable=True,
        fingerprint=fp,
        source_path=path,
        source_line=line,
        cost=estimate_cost(closed, param_bytes=_tree_bytes(probe.params)),
    )


# ---------------------------------------------------------------------------
# Cached control-plane entry points (packing, scheduler, admission)
# ---------------------------------------------------------------------------

_CACHE: Dict[str, Optional[ExperimentAnalysis]] = {}
_CACHE_LOCK = threading.Lock()
_ENABLED: Optional[bool] = None  # None = resolve from the environment


def set_enabled(enabled: bool) -> None:
    """Config hook (runtime.semantic_analysis): ExperimentController calls
    this at construction so standalone consumers (packing, scheduler) see
    one switch."""
    global _ENABLED
    _ENABLED = bool(enabled)


def runtime_enabled() -> bool:
    if _ENABLED is not None:
        return _ENABLED
    return os.environ.get("KATIB_TPU_SEMANTIC_ANALYSIS", "1").lower() not in (
        "0", "false", "off",
    )


def clear_cache() -> None:
    with _CACHE_LOCK:
        _CACHE.clear()


def cached_analysis(spec) -> Optional[ExperimentAnalysis]:
    """Best-effort cached analysis of one experiment spec; None when
    analysis is disabled, the template is a command/subprocess, or analysis
    itself failed. The cache key is (template digest, search-space
    signature) so every dispatch-path consult after the first is a dict
    lookup."""
    if not runtime_enabled():
        return None
    template = spec.trial_template
    if getattr(template, "command", None) is not None:
        return None
    try:
        key = f"{template_digest(template)}:{_search_signature(spec)}"
    except Exception:
        return None
    with _CACHE_LOCK:
        if key in _CACHE:
            return _CACHE[key]
    try:
        analysis = analyze_spec(spec)
    except Exception:
        analysis = None
    with _CACHE_LOCK:
        _CACHE[key] = analysis
    return analysis


def _grouping_values(
    analysis: ExperimentAnalysis, trial, classes: Sequence[str]
) -> Tuple[Tuple[str, str], ...]:
    return tuple(
        sorted(
            (a.name, a.value)
            for a in trial.parameter_assignments
            if analysis.classes.get(a.name) in classes
        )
    )


def probe_builder_for(template) -> Optional[Callable[..., ProgramProbe]]:
    """The template's ``fn.abstract_program`` builder, or None (command
    template / no probe). The compile service (compilesvc/service.py) uses
    this to AOT-compile the canonical program it describes."""
    fn = _resolve_template_fn(template)
    return getattr(fn, "abstract_program", None) if fn is not None else None


def pack_group_key(spec, trial):
    """Grouping key for pack formation: template digest + the values of
    every parameter that must be uniform across members (shape-affecting:
    aval mismatch; baked: stale-constant hazard; host: uniform_param
    contract). None = no semantic opinion (analysis off/unavailable)."""
    analysis = cached_analysis(spec)
    if analysis is None or not analysis.analyzable:
        return None
    return (
        analysis.digest,
        _grouping_values(analysis, trial, (CLASS_SHAPE, CLASS_BAKED, CLASS_HOST)),
    )


def dispatch_group_key(spec, trial):
    """Grouping key for dispatch ordering: trials with equal keys compile
    to the same executable, so dispatching them consecutively means the
    first warms the (jit / persistent XLA) cache for the rest. Host-only
    differences share an executable and do NOT split the group."""
    analysis = cached_analysis(spec)
    if analysis is None or not analysis.analyzable:
        return None
    return (analysis.digest, _grouping_values(analysis, trial, (CLASS_SHAPE,)))


def dispatch_group_key_for_assignments(spec, assignments: Dict[str, str]):
    """dispatch_group_key over a bare assignment dict — the compile
    service's admission-time prewarm has no Trial object yet (the baseline
    group is enqueued at create_experiment, before the first suggestion
    batch)."""
    analysis = cached_analysis(spec)
    if analysis is None or not analysis.analyzable:
        return None
    values = tuple(
        sorted(
            (name, value)
            for name, value in assignments.items()
            if analysis.classes.get(name) == CLASS_SHAPE
        )
    )
    return (analysis.digest, values)


def device_capacity_bytes() -> Optional[int]:
    """Accelerator memory per device, when knowable without side effects:
    only if a backend is already up in this process (same rule as
    telemetry.py — the pre-flight never takes the chip) and it reports
    bytes_limit. CPU backends return None."""
    from ..utils.backend import initialized_local_devices

    devices = initialized_local_devices()
    if not devices:
        return None
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    return int(limit) if limit else None


# ---------------------------------------------------------------------------
# Suppression plumbing (KTX findings obey the PR 6 conventions)
# ---------------------------------------------------------------------------

def filter_findings(
    findings: List[Finding], repo_root: Optional[str] = None
) -> Tuple[List[Finding], int]:
    """Apply suppressions.toml + inline ignores to semantic findings,
    exactly as the AST engine does for its own. Returns (kept, n_suppressed)
    with the kept list stably sorted."""
    from .engine import SUPPRESSIONS_TOML, default_repo_root
    from .suppress import apply_suppressions, parse_suppressions_toml

    repo_root = repo_root or default_repo_root()
    suppressions = []
    sup_path = os.path.join(repo_root, SUPPRESSIONS_TOML)
    if os.path.exists(sup_path):
        with open(sup_path) as f:
            suppressions = parse_suppressions_toml(f.read(), source=sup_path)
    sources: Dict[str, List[str]] = {}
    for f2 in findings:
        if f2.path in sources or f2.path == "<unknown>":
            continue
        try:
            with open(os.path.join(repo_root, f2.path), encoding="utf-8") as fh:
                sources[f2.path] = fh.read().splitlines()
        except OSError:
            pass
    kept, n_suppressed = apply_suppressions(findings, suppressions, sources)
    return sorted(kept, key=Finding.sort_key), n_suppressed
