"""Jaxpr-walking cost model — FLOPs, bytes, and a peak-HBM estimate.

The static half of ROADMAP item 1 ("compilation as a first-class
resource"): given the ClosedJaxpr an abstract trace produced
(analysis/program.py — ``jax.make_jaxpr`` under ShapeDtypeStruct avals, no
compilation, no devices), estimate what the program will cost BEFORE any
trial runs:

- **flops** — matmul/conv arithmetic plus elementwise/reduction traffic,
  recursing through pjit/scan/while/cond/custom-call sub-jaxprs (a scan
  body is charged ``length`` times, a while body once per walk — trip
  counts are not statically known and the estimate says so);
- **param/input/output bytes** — from the traced avals;
- **peak_bytes** — resident inputs plus the high-water mark of live
  intermediate avals under a last-use liveness scan. This is a lower
  bound on what XLA will allocate (fusion temporaries and rematerialized
  buffers are invisible pre-compilation), which is exactly the right
  polarity for an admission *reject*: a program whose lower bound already
  exceeds device memory cannot run.

Everything here is pure arithmetic over avals — importable and runnable
with ``JAX_PLATFORMS=cpu`` and no backend warm-up.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

# elementwise primitives charged one op per output element
_ELEMENTWISE = {
    "add", "sub", "mul", "div", "max", "min", "pow", "rem",
    "neg", "sign", "abs", "floor", "ceil", "round",
    "exp", "log", "log1p", "expm1", "tanh", "logistic", "erf", "erf_inv",
    "erfc", "rsqrt", "sqrt", "cbrt", "sin", "cos", "tan",
    "integer_pow", "square", "select_n", "clamp", "nextafter",
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic",
    "eq", "ne", "ge", "gt", "le", "lt", "is_finite",
    "add_any",
}

# reductions charged one op per *input* element
_REDUCTIONS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "cumsum", "cumprod",
    "cummax", "cummin", "reduce_precision",
}

# pure data movement / metadata: zero flops (bytes are covered by liveness)
_FREE = {
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "convert_element_type",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "rev", "gather", "scatter", "scatter-add", "scatter_add", "iota", "copy",
    "device_put", "stop_gradient", "bitcast_convert_type", "split",
    "expand_dims", "real", "imag", "complex", "conj",
}


@dataclass
class CostEstimate:
    """Static cost of one traced program (all estimates, see module doc)."""

    flops: float = 0.0
    param_bytes: int = 0       # model parameter avals (probe-declared subset)
    input_bytes: int = 0       # all program inputs, params included
    output_bytes: int = 0
    peak_bytes: int = 0        # inputs + live-intermediate high-water mark
    eqns: int = 0              # primitive count, sub-jaxprs included
    notes: List[str] = field(default_factory=list)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "flops": self.flops,
            "paramBytes": self.param_bytes,
            "inputBytes": self.input_bytes,
            "outputBytes": self.output_bytes,
            "peakBytes": self.peak_bytes,
            "eqns": self.eqns,
            "notes": list(self.notes),
        }


def aval_bytes(aval) -> int:
    """Size of one aval; abstract tokens/opaque avals count zero."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(math.prod(shape)) * int(dtype.itemsize)
    except (TypeError, ValueError):
        return 0  # polymorphic / dynamic dims: not costable


def _numel(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    try:
        return int(math.prod(shape))
    except (TypeError, ValueError):
        return 0


def _dot_general_flops(eqn) -> float:
    """2·batch·M·N·K from the dimension numbers."""
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    k = math.prod(lhs.shape[d] for d in lc) or 1
    b = math.prod(lhs.shape[d] for d in lb) or 1
    m = math.prod(
        lhs.shape[d] for d in range(len(lhs.shape)) if d not in set(lc) | set(lb)
    ) or 1
    n = math.prod(
        rhs.shape[d]
        for d in range(len(rhs.shape))
        if d not in set(rc) | set(eqn.params["dimension_numbers"][1][1])
    ) or 1
    return 2.0 * b * m * n * k


def _conv_flops(eqn) -> float:
    """2 · out-elements · kernel-spatial · in-channels / groups."""
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval  # kernel
    dn = eqn.params.get("dimension_numbers")
    groups = eqn.params.get("feature_group_count", 1) or 1
    if dn is not None and hasattr(dn, "rhs_spec"):
        rhs_spec = dn.rhs_spec  # (out_ch, in_ch, *spatial) positions
        spatial = math.prod(rhs.shape[d] for d in rhs_spec[2:]) or 1
        in_ch = rhs.shape[rhs_spec[1]]
    else:
        spatial = math.prod(rhs.shape[:-2]) or 1
        in_ch = rhs.shape[-2]
    return 2.0 * _numel(out) * spatial * in_ch / groups


def _sub_jaxprs(eqn) -> List[Tuple[Any, float]]:
    """(jaxpr, multiplier) pairs nested in one eqn's params."""
    name = eqn.primitive.name
    params = eqn.params
    out: List[Tuple[Any, float]] = []
    if name == "scan":
        length = float(params.get("length", 1) or 1)
        out.append((params["jaxpr"], length))
        return out
    if name == "while":
        # trip count unknowable statically: charge one iteration
        out.append((params["cond_jaxpr"], 1.0))
        out.append((params["body_jaxpr"], 1.0))
        return out
    if name == "cond":
        # worst case: the most expensive branch
        return [("__branches__", params.get("branches", ()))]  # handled by caller
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in params and params[key] is not None:
            out.append((params[key], 1.0))
    return out


def _raw_jaxpr(j):
    return getattr(j, "jaxpr", j)  # ClosedJaxpr -> Jaxpr


def _walk_flops(jaxpr, notes: List[str]) -> Tuple[float, int]:
    """(flops, eqn count) for one jaxpr, recursing into sub-jaxprs."""
    flops = 0.0
    eqns = 0
    for eqn in _raw_jaxpr(jaxpr).eqns:
        eqns += 1
        name = eqn.primitive.name
        if name == "dot_general":
            flops += _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            flops += _conv_flops(eqn)
        elif name in _ELEMENTWISE:
            flops += float(sum(_numel(o.aval) for o in eqn.outvars))
        elif name in _REDUCTIONS or name.startswith("reduce_"):
            flops += float(sum(_numel(v.aval) for v in eqn.invars))
        elif name in _FREE:
            pass
        else:
            subs = _sub_jaxprs(eqn)
            if subs and subs[0][0] == "__branches__":
                branch_costs = []
                for br in subs[0][1]:
                    f, e = _walk_flops(br, notes)
                    branch_costs.append((f, e))
                if branch_costs:
                    f, e = max(branch_costs)
                    flops += f
                    eqns += e
            elif subs:
                if eqn.primitive.name == "while":
                    _note_once(notes, "while-loop body charged once (trip count unknown)")
                for sub, mult in subs:
                    f, e = _walk_flops(sub, notes)
                    flops += f * mult
                    eqns += e
            # unknown leaf primitives (collectives, rng, sort, custom calls)
            # cost zero flops — the estimate is a lower bound by design
    return flops, eqns


def _note_once(notes: List[str], msg: str) -> None:
    if msg not in notes:
        notes.append(msg)


def _peak_live_bytes(jaxpr) -> int:
    """High-water mark of live intermediate avals over a linear walk of the
    top-level eqns (sub-jaxpr internals are charged at their call site via
    the call's own outputs — a refinement a future PR can recurse on)."""
    j = _raw_jaxpr(jaxpr)
    def is_var(v) -> bool:
        # Literals carry values, not liveness; DropVars/Vars are hashable
        return hasattr(v, "aval") and v.__class__.__name__ != "Literal"

    last_use: Dict[Any, int] = {}
    n = len(j.eqns)
    for i, eqn in enumerate(j.eqns):
        for v in eqn.invars:
            if is_var(v):
                last_use[v] = i
    for v in j.outvars:
        if is_var(v):
            last_use[v] = n  # program outputs stay live to the end
    live = 0
    peak = 0
    inputs = set(j.invars) | set(j.constvars)
    for i, eqn in enumerate(j.eqns):
        for ov in eqn.outvars:
            live += aval_bytes(ov.aval)
        peak = max(peak, live)
        for v in list(eqn.invars) + list(eqn.outvars):
            if not is_var(v) or v in inputs:
                continue
            if last_use.get(v, -1) == i:
                live -= aval_bytes(v.aval)
                last_use[v] = -1  # freed
    return peak


def estimate_cost(closed_jaxpr, param_bytes: int = 0) -> CostEstimate:
    """Cost one ClosedJaxpr. ``param_bytes`` is the probe-declared model
    parameter subtotal (a subset of input_bytes) so reports can split
    weights from activations."""
    j = closed_jaxpr.jaxpr
    notes: List[str] = []
    flops, eqns = _walk_flops(closed_jaxpr, notes)
    input_bytes = sum(aval_bytes(v.aval) for v in j.invars)
    input_bytes += sum(aval_bytes(getattr(c, "aval", c)) for c in j.constvars)
    output_bytes = sum(aval_bytes(v.aval) for v in j.outvars)
    peak = input_bytes + _peak_live_bytes(closed_jaxpr)
    return CostEstimate(
        flops=flops,
        param_bytes=param_bytes,
        input_bytes=input_bytes,
        output_bytes=output_bytes,
        peak_bytes=peak,
        eqns=eqns,
        notes=notes,
    )


# -- MFU accounting (step-statistics plane, ISSUE 20) -------------------------
#
# Model-FLOPs-utilization = achieved FLOP/s divided by the hardware peak —
# the primary fleet-health ratio of the pjit/TPUv4 paper (arXiv:2204.06514,
# §5: published MFU 39.8%–46.6% for PaLM-class runs; BENCH_r02 hand-computed
# 0.54 for the flash-attention microbench). The numerator comes from the
# same static cost model the compile plane already runs (CostEstimate.flops
# = FLOPs of ONE traced step program); the denominator is the per-chip
# dense peak from the table below times the gang size.

# Dense bf16 peak FLOP/s per chip. TPU numbers are the published per-chip
# peaks (v4 275 TFLOP/s, v5e 197, v5p 459, v6e 918); GPU entries cover the
# common single-host dev boxes. There is no "cpu" entry: a device that is
# not in the table has no peak here and therefore no MFU
# ($KATIB_TPU_PEAK_FLOPS states a calibrated peak for one).
PEAK_FLOPS: Dict[str, float] = {
    "tpu v4": 275e12,
    "tpu v5 lite": 197e12,
    "tpu v5e": 197e12,
    "tpu v5p": 459e12,
    "tpu v6 lite": 918e12,
    "tpu v6e": 918e12,
    "h100": 989e12,
    "a100": 312e12,
}

ENV_PEAK_FLOPS = "KATIB_TPU_PEAK_FLOPS"


def peak_flops_for(device_kind: Optional[str] = None) -> Optional[float]:
    """Per-chip peak FLOP/s for a device kind (jax Device.device_kind, any
    case), from $KATIB_TPU_PEAK_FLOPS when set (operator calibration wins),
    else the table by longest matching key. None when the kind is unknown —
    callers must then skip MFU rather than report a wrong ratio."""
    import os

    env = os.environ.get(ENV_PEAK_FLOPS)
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if not device_kind:
        return None
    kind = device_kind.strip().lower()
    best: Optional[float] = None
    best_len = -1
    for key, peak in PEAK_FLOPS.items():
        if key in kind and len(key) > best_len:
            best, best_len = peak, len(key)
    return best


def mfu(
    cost_estimate: Optional["CostEstimate"],
    step_seconds: float,
    n_devices: int,
    peak: Optional[float] = None,
    device_kind: Optional[str] = None,
) -> Optional[float]:
    """Model-FLOPs-utilization for one step: cost.flops / (step_seconds ×
    n_devices × per-chip peak). None whenever any input is missing or
    degenerate — an absent MFU is better than a fabricated one."""
    if cost_estimate is None or step_seconds <= 0 or n_devices <= 0:
        return None
    flops = float(getattr(cost_estimate, "flops", 0.0) or 0.0)
    if flops <= 0:
        return None
    if peak is None:
        peak = peak_flops_for(device_kind)
    if peak is None or peak <= 0:
        return None
    return flops / (step_seconds * n_devices * peak)
