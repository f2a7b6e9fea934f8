#!/usr/bin/env python3
"""Chip smoke: the main path of katib-tpu, once, on a real TPU.

    python chip_smoke.py            # one chip: device -> kernel -> sweep
    python chip_smoke.py --chips 4  # four chips: device -> four_chip only

One process; it never sets JAX_PLATFORMS. Every phase prints one JSON object
on its own line; the LAST line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only if every phase passed. Any failure — no TPU, a kernel
that gives way to dense attention, a trial that did not succeed, a loss
that is not finite — exits non-zero without that line. Nothing here falls
back to the CPU.

It writes under ``chiprun_out/chip_smoke/`` beside this file and in the
compile cache (``JAX_COMPILATION_CACHE_DIR`` when set, else
``.katib-tpu/xla-cache`` beside this file), nowhere else.

Phases (no option, one chip):
- device: ``jax.devices()`` must be TPU; versions and the cache dir in use.
- kernel: ``flash_attention`` forward + gradients at the LM-large attention
  shape [4, 2048, 16, 64] bf16 causal against ``dense_attention`` in float32
  at matmul precision "highest"; the lowered program must contain
  ``tpu_custom_call`` (neither interpret mode nor the dense branch ran).
- sweep: ExperimentController(devices=jax.local_devices()) -> TPE -> scheduler
  -> in-process executor -> ctx.report -> store, over
  ``katib_tpu.parallel.train:run_lm_trial`` at the LM-large widths ``Sizes``
  states, 5 trials of 20 steps, learning_rate searched log-uniform.

With ``--chips 4``: the same trial at numDevices 4 / tensor_parallel 2 (data 2
x model 2) through the controller with the four real devices as its pool,
then the same assignments at numDevices 1 on one of them; the two loss
curves must agree within LOSS_RTOL, the compiled four-chip step must contain
collectives, and all four devices must hold state.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# -- stated tolerances --------------------------------------------------------
# kernel vs float32 "highest" reference, unit-variance bf16 inputs: the
# kernel's outputs are rounded to bf16 (8 mantissa bits, ~4e-3 relative) and
# its softmax statistics are f32, so absolute errors of a few 1e-2 on values
# of a few units are rounding, and anything near 1e-1 is a wrong kernel.
KERNEL_ATOL = {"o": 3e-2, "dq": 6e-2, "dk": 6e-2, "dv": 6e-2}
# four-chip vs one-chip loss curve, same seed/data/assignments, bf16 matmuls:
# the sharded step reduces in a different order (partial sums per shard, then
# all-reduce), which moves bf16-rounded activations in the last bits and the
# difference compounds over 20 AdamW steps.
LOSS_RTOL = 3e-2
# a device that holds its shard of params + optimizer state of LM-large holds
# hundreds of MiB; a device that was left out holds (almost) nothing
MIN_BYTES_PER_DEVICE = 128 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke runs at. The defaults are the real thing; a rehearsal
    on the CPU imports this module and passes smaller ones."""

    # the LM-large widths (heads of 64)
    vocab_size: int = 32768
    embed_dim: int = 1024
    num_layers: int = 8
    num_heads: int = 16
    seq_len: int = 2048
    batch_size: int = 4
    num_steps: int = 20
    max_trials: int = 5
    # flash kernel shape [B, T, H, D]
    attn_shape: tuple = (4, 2048, 16, 64)


def emit(phase: str, **fields: Any) -> None:
    print(json.dumps({"phase": phase, **fields}, sort_keys=True), flush=True)


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def select_phases(chips: int) -> List[str]:
    """Which phases a run makes. Four chips cost four times as much, so that
    run makes only what exists across chips and what it is compared with."""
    return ["device", "four_chip"] if chips == 4 else ["device", "kernel", "sweep"]


# -- compile accounting ---------------------------------------------------------

class CompileMeter:
    """Counts what JAX itself reports: one backend_compile_duration event per
    program compiled or fetched from the persistent cache, and the cache's
    own hit/miss events."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.compiles = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {
            "compiles": self.compiles,
            "compile_seconds": round(self.compile_seconds, 3),
            "persistent_cache_hits": self.cache_hits,
            "persistent_cache_misses": self.cache_misses,
        }

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: round(now[k] - before[k], 3) for k in now}


# -- phases ---------------------------------------------------------------------

def phase_device(chips: int) -> Dict[str, Any]:
    import importlib.metadata as md

    import jax
    import jaxlib

    from katib_tpu.utils.compilation import ENV_CACHE_DIR, enable_compilation_cache

    # before the first compile of the process: JAX decides once whether the
    # persistent cache is in use
    cache_dir = enable_compilation_cache()
    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    emit(
        "device", device=device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, cache_dir=cache_dir,
        cache_dir_from_env=bool(os.environ.get(ENV_CACHE_DIR)),
        cache_dir_in_jax_config=jax.config.jax_compilation_cache_dir,
    )
    check(d0.platform == "tpu", f"JAX found no TPU: platform is {d0.platform!r}")
    check(
        jax.config.jax_compilation_cache_dir == cache_dir,
        "the compile cache is not where the program says it is",
    )
    if chips == 4:
        check(
            len(jax.local_devices()) >= 4,
            f"--chips 4 needs four local devices, found {len(jax.local_devices())}",
        )
    return device


def phase_kernel(sizes: Sizes, meter: CompileMeter, on_chip: bool = True) -> None:
    import jax
    import jax.numpy as jnp

    from katib_tpu.ops.flash_attention import flash_attention
    from katib_tpu.ops.ring_attention import dense_attention

    t0 = time.time()
    before = meter.snapshot()
    shape = sizes.attn_shape
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (
        jax.random.normal(key, shape, dtype=jnp.float32).astype(jnp.bfloat16)
        for key in (kq, kk, kv, kd)
    )

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, interpret=None if on_chip else True)
        return (o.astype(jnp.float32) * do.astype(jnp.float32)).sum(), o

    def dense_loss(q, k, v):
        o = dense_attention(q, k, v, causal=True)
        return (o * do.astype(jnp.float32)).sum(), o

    flash_vg = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True))
    lowered = flash_vg.lower(q, k, v).as_text()
    kernel_calls = lowered.count("tpu_custom_call")
    if on_chip:
        check(
            kernel_calls >= 3,
            f"flash_attention lowered to {kernel_calls} tpu_custom_call(s): the "
            "Pallas kernels (forward, dq, dk/dv) are not in the program",
        )
    (_, o), (dq, dk, dv) = flash_vg(q, k, v)
    with jax.default_matmul_precision("highest"):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        (_, o_ref), (dq_ref, dk_ref, dv_ref) = jax.jit(
            jax.value_and_grad(dense_loss, argnums=(0, 1, 2), has_aux=True)
        )(*f32)
    errs = {}
    for name, got, ref in (
        ("o", o, o_ref), ("dq", dq, dq_ref), ("dk", dk, dk_ref), ("dv", dv, dv_ref)
    ):
        got32 = got.astype(jnp.float32)
        check(got.shape == tuple(shape), f"{name} has shape {got.shape}, not {shape}")
        check(bool(jnp.isfinite(got32).all()), f"{name} is not finite")
        errs[name] = float(jnp.max(jnp.abs(got32 - ref)))
    emit(
        "kernel", shape=list(shape), dtype="bfloat16", causal=True,
        tpu_custom_calls=kernel_calls, max_abs_err=errs, atol=KERNEL_ATOL,
        reference="dense_attention float32, matmul precision highest",
        wall_seconds=round(time.time() - t0, 3), **meter.since(before),
    )
    for name, err in errs.items():
        check(err <= KERNEL_ATOL[name], f"{name}: max abs error {err} > {KERNEL_ATOL[name]}")


def _one_value(name: str, value: Any) -> Dict[str, Any]:
    return {
        "name": name, "parameterType": "discrete",
        "feasibleSpace": {"list": [str(value)]},
    }


def _lm_spec(
    name: str,
    sizes: Sizes,
    learning_rate: Optional[float],
    algorithm: Dict[str, Any],
    max_trials: int,
    num_devices: int = 1,
    tensor_parallel: int = 1,
) -> Dict[str, Any]:
    """The experiment document a user would write (examples/distributed-lm.json
    is the same shape): the LM widths as one-value parameters."""
    if learning_rate is None:
        lr = {
            "name": "learning_rate", "parameterType": "double",
            "feasibleSpace": {"min": "1e-4", "max": "1e-2", "distribution": "logUniform"},
        }
    else:
        lr = _one_value("learning_rate", learning_rate)
    return {
        "name": name,
        "parameters": [
            lr,
            _one_value("vocab_size", sizes.vocab_size),
            _one_value("embed_dim", sizes.embed_dim),
            _one_value("num_layers", sizes.num_layers),
            _one_value("num_heads", sizes.num_heads),
            _one_value("seq_len", sizes.seq_len),
            _one_value("batch_size", sizes.batch_size),
            _one_value("num_steps", sizes.num_steps),
            _one_value("tensor_parallel", tensor_parallel),
        ],
        "objective": {"type": "minimize", "objectiveMetricName": "loss"},
        "algorithm": algorithm,
        "trialTemplate": {
            "entryPoint": "katib_tpu.parallel.train:run_lm_trial",
            "trialParameters": [],
            "resources": {"numDevices": num_devices, "numHosts": 1},
        },
        "maxTrialCount": max_trials,
        "parallelTrialCount": 1,
        "maxFailedTrialCount": 0,
    }


class ReportTee:
    """Records what each trial handed to ``ctx.report`` — and what the devices
    held at that moment — before the runtime sees it. The store's rows are
    compared with this afterwards."""

    def __init__(self) -> None:
        from katib_tpu.runtime.context import TrialContext

        self.reported: Dict[str, List[Dict[str, float]]] = {}
        self.bytes_in_use: Dict[str, List[List[int]]] = {}
        self._cls = TrialContext
        self._orig = TrialContext.report
        tee = self

        def report(ctx, **metrics):
            import jax

            tee.reported.setdefault(ctx.trial_name, []).append(dict(metrics))
            tee.bytes_in_use.setdefault(ctx.trial_name, []).append(
                [
                    int((d.memory_stats() or {}).get("bytes_in_use", 0))
                    for d in jax.local_devices()
                ]
            )
            return tee._orig(ctx, **metrics)

        TrialContext.report = report

    def close(self) -> None:
        self._cls.report = self._orig


def _run_experiment(ctrl, doc: Dict[str, Any], timeout: float):
    from katib_tpu.api.spec import experiment_spec_from_mapping

    spec = experiment_spec_from_mapping(doc)
    ctrl.create_experiment(spec)
    exp = ctrl.run(spec.name, timeout=timeout)
    trials = ctrl.state.list_trials(spec.name)
    return exp, trials


def _stored_losses(ctrl, trial_name: str):
    return ctrl.obs_store.get_observation_log(trial_name, metric_name="loss")


def _check_trials(ctrl, exp, trials, tee: ReportTee, n: int) -> Dict[str, List[float]]:
    """Every trial succeeded, reported finite losses, and the store gives back
    exactly what was reported. Returns {trial: [loss, ...]} from the store."""
    from katib_tpu.api.status import ExperimentReason, TrialCondition

    check(
        exp.status.reason == ExperimentReason.MAX_TRIALS_REACHED,
        f"experiment ended with {exp.status.reason.value!r} "
        f"({exp.status.message!r}), not MaxTrialsReached",
    )
    check(len(trials) == n, f"{len(trials)} trials, expected {n}")
    curves: Dict[str, List[float]] = {}
    for t in trials:
        check(
            t.condition == TrialCondition.SUCCEEDED,
            f"trial {t.name} is {t.condition.value}: {t.message}",
        )
        rows = _stored_losses(ctrl, t.name)
        stored = [float(r.value) for r in rows]
        reported = [float(m["loss"]) for m in tee.reported.get(t.name, [])]
        check(bool(stored), f"trial {t.name} has no loss rows in the store")
        check(
            stored == reported,
            f"trial {t.name}: store rows {stored} != reported {reported}",
        )
        check(all(math.isfinite(x) for x in stored), f"trial {t.name}: loss not finite")
        curves[t.name] = stored
    return curves


def _step_seconds(ctrl, trial_name: str, report_every: int = 5) -> Optional[float]:
    """Seconds per train step between the second and the last report of a
    trial (each report reads the loss back from the device, so the rows'
    host timestamps bracket finished steps; the first interval holds the
    compile and is left out)."""
    rows = _stored_losses(ctrl, trial_name)
    if len(rows) < 3:
        return None
    steps = (len(rows) - 2) * report_every
    return (rows[-1].timestamp - rows[1].timestamp) / steps


def _peak_bytes() -> List[Optional[int]]:
    import jax

    return [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()
    ]


def _lowered_step_text(sizes: Sizes, devices, tensor_parallel: int, compiled: bool):
    """The trial's own step for these assignments, built by the builder the
    trial uses and lowered here (``run_lm_trial`` keeps its step to itself)."""
    import jax.numpy as jnp
    import numpy as np

    from katib_tpu.models.transformer import TransformerConfig
    from katib_tpu.parallel.mesh import make_mesh
    from katib_tpu.parallel.train import make_lm_train_step

    config = TransformerConfig(
        vocab_size=sizes.vocab_size, embed_dim=sizes.embed_dim,
        num_layers=sizes.num_layers, num_heads=sizes.num_heads,
        max_seq_len=sizes.seq_len,
    )
    mesh = make_mesh(list(devices), model=tensor_parallel)
    params, opt_state, step_fn, put_batch = make_lm_train_step(config, mesh, 1e-3)
    data = np.zeros((sizes.batch_size, sizes.seq_len + 1), dtype=np.int32)
    batch = put_batch(data[:, :-1], data[:, 1:])
    lowered = step_fn.lower(params, opt_state, *batch)
    return lowered.compile().as_text() if compiled else lowered.as_text()


def phase_sweep(sizes: Sizes, meter: CompileMeter, on_chip: bool = True) -> None:
    import jax

    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.suggest import vectorized

    root = os.path.join(OUT_DIR, "sweep")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    t0 = time.time()
    before = meter.snapshot()
    tee = ReportTee()
    ctrl = ExperimentController(root_dir=root, devices=jax.local_devices())
    try:
        doc = _lm_spec(
            "chip-smoke-sweep", sizes, learning_rate=None,
            algorithm={
                "algorithmName": "tpe",
                "algorithmSettings": [
                    {"name": "n_startup_trials", "value": "2"},
                    {"name": "random_state", "value": "0"},
                ],
            },
            max_trials=sizes.max_trials,
        )
        exp, trials = _run_experiment(ctrl, doc, timeout=1000.0)
        wall = time.time() - t0
        curves = _check_trials(ctrl, exp, trials, tee, sizes.max_trials)
        best = exp.status.current_optimal_trial.best_trial_name
        check(bool(best) and best in curves, f"optimal trial not set ({best!r})")
        first, last = curves[best][0], curves[best][-1]
        check(
            last < first,
            f"best trial {best}: loss at the last report {last} is not below "
            f"the first {first}",
        )
        # the jitted TPE kernel really proposed (n_startup_trials=2 of 5)
        tpe_programs = vectorized._tpe_program.cache_info().currsize
        check(tpe_programs >= 1, "the vectorized TPE kernel never ran")
        step_seconds = {t.name: _step_seconds(ctrl, t.name) for t in trials}
        lrs = {
            t.name: t.assignments_dict()["learning_rate"] for t in trials
        }
    finally:
        tee.close()
        ctrl.close()
    sweep_compiles = meter.since(before)
    text = _lowered_step_text(sizes, jax.local_devices()[:1], 1, compiled=False)
    kernel_calls = text.count("tpu_custom_call")
    if on_chip:
        check(
            kernel_calls >= 3 * sizes.num_layers,
            f"the trial's train step lowered to {kernel_calls} tpu_custom_call(s); "
            f"{3 * sizes.num_layers} expected (forward, dq, dk/dv per layer)",
        )
    known = sorted(s for s in step_seconds.values() if s is not None)
    emit(
        "sweep",
        entry_point="katib_tpu.parallel.train:run_lm_trial",
        widths=dataclasses.asdict(sizes),
        algorithm="tpe", n_startup_trials=2, tpe_kernel_programs=tpe_programs,
        reason=exp.status.reason.value, trials=len(trials),
        succeeded=exp.status.trials_succeeded, optimal_trial=best,
        learning_rates=lrs, loss_curves=curves,
        rows_match_reports=True, step_tpu_custom_calls=kernel_calls,
        wall_seconds=round(wall, 3),
        trial_step_seconds=step_seconds,
        trial_step_seconds_median=known[len(known) // 2] if known else None,
        peak_bytes_in_use=_peak_bytes(),
        **sweep_compiles,
    )


def phase_four_chip(sizes: Sizes, meter: CompileMeter, on_chip: bool = True) -> None:
    import jax

    from katib_tpu.controller.experiment import ExperimentController

    devices = jax.local_devices()
    check(len(devices) >= 4, f"four-chip phase needs four devices, found {len(devices)}")
    devices = devices[:4]
    root = os.path.join(OUT_DIR, "four_chip")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    t0 = time.time()
    before = meter.snapshot()
    tee = ReportTee()
    ctrl = ExperimentController(root_dir=root, devices=devices)
    algorithm = {"algorithmName": "random"}
    try:
        results = {}
        for label, num_devices, tp in (("four", 4, 2), ("one", 1, 1)):
            t1 = time.time()
            doc = _lm_spec(
                f"chip-smoke-{label}", sizes, learning_rate=1e-3,
                algorithm=algorithm, max_trials=1,
                num_devices=num_devices, tensor_parallel=tp,
            )
            exp, trials = _run_experiment(ctrl, doc, timeout=1000.0)
            curves = _check_trials(ctrl, exp, trials, tee, 1)
            (name, curve), = curves.items()
            results[label] = {
                "trial": name, "loss_curve": curve,
                "bytes_in_use_at_reports": tee.bytes_in_use[name],
                "step_seconds": _step_seconds(ctrl, name),
                "wall_seconds": round(time.time() - t1, 3),
            }
    finally:
        tee.close()
        ctrl.close()
    four, one = results["four"], results["one"]
    check(
        len(four["loss_curve"]) == len(one["loss_curve"]),
        "the two trials reported a different number of losses",
    )
    rel = [
        abs(a - b) / max(abs(b), 1e-6)
        for a, b in zip(four["loss_curve"], one["loss_curve"])
    ]
    held = [min(col) for col in zip(*four["bytes_in_use_at_reports"])][:4]
    hlo = _lowered_step_text(sizes, devices, 2, compiled=True)
    collectives = {
        c: hlo.count(c)
        for c in ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
                  "all-to-all")
    }
    emit(
        "four_chip", layout={"data": 2, "model": 2}, widths=dataclasses.asdict(sizes),
        four=four, one=one, max_rel_loss_diff=max(rel), loss_rtol=LOSS_RTOL,
        min_bytes_in_use_per_device=held, min_bytes_required=MIN_BYTES_PER_DEVICE,
        collectives_in_compiled_step=collectives,
        step_tpu_custom_calls=hlo.count("tpu_custom_call"),
        peak_bytes_in_use=_peak_bytes(),
        wall_seconds=round(time.time() - t0, 3), **meter.since(before),
    )
    check(max(rel) <= LOSS_RTOL, f"loss curves differ by {max(rel)} > {LOSS_RTOL}")
    check(sum(collectives.values()) > 0, "the compiled four-chip step has no collectives")
    if on_chip:
        check(
            hlo.count("tpu_custom_call") > 0,
            "the compiled four-chip step does not contain the flash kernel",
        )
        check(
            len(held) == 4 and all(b >= MIN_BYTES_PER_DEVICE for b in held),
            f"not every device held state during the four-chip trial: {held}",
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="1 (default): device, kernel, sweep. 4: device and the four-chip "
        "phase only.",
    )
    args = ap.parse_args(argv)
    phases = select_phases(args.chips)
    sizes = Sizes()
    t0 = time.time()
    try:
        device = phase_device(args.chips)
        meter = CompileMeter()
        os.makedirs(OUT_DIR, exist_ok=True)
        for phase in phases[1:]:
            {"kernel": phase_kernel, "sweep": phase_sweep,
             "four_chip": phase_four_chip}[phase](sizes, meter)
    except PhaseFailed as e:
        emit("failed", error=str(e), wall_seconds=round(time.time() - t0, 3))
        return 1
    emit("total", phases=phases, wall_seconds=round(time.time() - t0, 3),
         **meter.snapshot())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
