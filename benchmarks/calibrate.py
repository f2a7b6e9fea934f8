#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, on the chip, at a
cell's own size, in one process.

    python3 benchmarks/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 --out <file.json>

- lower readings: the program — ``run_lm_trial`` itself, three steps, read
  through tees.py exactly as a benchmark run reads it — against the float32
  reference, one learning rate per seed, drawn from the cell's search space;
- upper readings: the *control*, the reference computed in the precision below
  the one the configuration states (float8 operands for bfloat16), and the
  *fault* "half of the batch left out, the mean taken over the rest", each put
  in the program's place against the same float32 reference;
- beside them the reference in bfloat16, the stated precision: how much of the
  program's gap is the precision itself.

The benchmark's own runs never run this; check.py only reads the limits that
were set from its output (in the cell's file, with the readings in PERF.md).
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.dirname(HERE), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def learning_rates(cell, n: int, seed: int = 0):
    import numpy as np

    space = cell["search_space"]["learning_rate"]
    lo, hi = math.log(float(space["min"])), math.log(float(space["max"]))
    draws = np.random.default_rng(seed).uniform(lo, hi, size=n)
    # both ends of the space are always among them
    return [float(space["min"]), float(space["max"])][:n] + [float(math.exp(x)) for x in draws[2:]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true", help="a rehearsal at a tiny size")
    args = ap.parse_args(argv)

    import jax

    import check
    import reference_lm
    import run as harness
    import tees as tees_module
    from experiment import fixed_assignments, load_cell
    from katib_tpu.parallel import train
    from katib_tpu.utils.compilation import enable_compilation_cache

    cell, config = load_cell(args.workload)
    if not args.allow_cpu:
        try:
            harness.find_device(cell["chips"])
        except harness.Refused as e:
            print(f"refused: {e}", file=sys.stderr)
            return 2
    enable_compilation_cache()
    shape = reference_lm.LMShape(
        config["vocab_size"], config["hidden_size"], config["num_hidden_layers"],
        config["num_attention_heads"],
    )
    batch, seq = cell["batch_size"], cell["seq_len"]
    lrs = learning_rates(cell, args.seeds)
    out = {"workload": args.workload, "device": str(jax.devices()[0].device_kind),
           "learning_rates": lrs, "program": [], "reference": []}

    def save():
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    # the program, as a trial runs it
    tees = tees_module.Tees()
    try:
        for i, lr in enumerate(lrs):
            t0 = time.time()
            assignments = dict(fixed_assignments(cell, config), learning_rate=repr(lr),
                               num_steps=str(tees_module.FIRST_STEPS))
            with tees.watch(f"calibrate-{i}") as rec:
                train.run_lm_trial(assignments)
            out["program"].append(
                {"loss": rec.first_losses, "grad_norm": rec.grad_norm, "delta_norm": rec.delta_norm})
            print(f"program lr={lr:.3g}: {rec.first_losses} in {time.time() - t0:.1f} s",
                  file=sys.stderr, flush=True)
            gc.collect()
    finally:
        tees.close()
    out["memory_peak_bytes_program"] = harness.peak_memory_bytes()
    save()

    def follow(label, lrs_, **kwargs):
        ref = reference_lm.Reference(shape, batch, seq, **kwargs)
        rows = []
        for lr in lrs_:
            t0 = time.time()
            rows.append(ref.run(lr))
            print(f"{label} lr={lr:.3g}: {rows[-1]['loss']} in {time.time() - t0:.1f} s",
                  file=sys.stderr, flush=True)
        del ref
        gc.collect()
        return rows

    out["reference"] = follow("reference float32", lrs)
    save()
    few = lrs[: args.control_seeds]
    variants = {
        "reference_bfloat16": dict(precision="bfloat16"),
        "control_float8": dict(precision="float8"),
        "fault_half_batch": dict(rows=max(1, batch // 2)),
        "fault_state_unchanged": dict(frozen=True),
    }
    for label, kwargs in variants.items():
        out[label] = follow(label, few, **kwargs)
        save()

    def gaps(rows):
        return [check.training_gaps(p, r) for p, r in zip(rows, out["reference"])]

    out["gaps"] = {"program": gaps(out["program"]), **{k: gaps(out[k]) for k in variants}}
    out["memory_peak_bytes"] = harness.peak_memory_bytes()
    save()
    for label, rows in out["gaps"].items():
        for name in ("loss_gap", "grad_norm_gap", "delta_norm_gap"):
            values = [g[name] for g in rows]
            print(f"{label:24s} {name:16s} min {min(values):.3e} max {max(values):.3e}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
