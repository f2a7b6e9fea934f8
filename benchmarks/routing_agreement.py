#!/usr/bin/env python3
"""By hand, on the chip, at a sparse cell's own size: how many of the experts
the program's routers choose (bfloat16 products, as the trial builds the model)
differ from the float32 reference's on the first step's forward pass. Routing
is discontinuous: a bfloat16 residual can turn a near-tie. The benchmark's own
runs never run this; PERF.md holds the reading (PR 30).

    python3 benchmarks/routing_agreement.py --workload <cell> --out <file.json> [--allow-cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.dirname(HERE), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true", help="a rehearsal at a tiny size")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference_sparse_lm as ref
    import run as harness
    from experiment import load_cell
    from katib_tpu.models.architecture import architecture_config
    from katib_tpu.models.transformer import TransformerLM
    from katib_tpu.utils.compilation import enable_compilation_cache

    cell, config = load_cell(args.workload)
    if not args.allow_cpu:
        try:
            harness.find_device(cell["chips"])
        except harness.Refused as e:
            print(f"refused: {e}", file=sys.stderr)
            return 2
    enable_compilation_cache()
    m = ref.SparseLM(config)
    tokens, _ = ref.make_batch(m.vocab, cell["batch_size"], cell["seq_len"])
    tokens = jnp.asarray(tokens)
    params = jax.jit(lambda: ref.init_params(m))()  # the trial's own initial parameters, leaf for leaf

    model = TransformerLM(architecture_config(config, cell["seq_len"]))
    _, mutated = jax.jit(lambda p, t: model.apply({"params": p}, t, mutable=["intermediates"]))(params, tokens)
    program = {name: np.asarray(block["experts"]["routing"][0]["chosen"]).reshape(tokens.shape + (-1,))
               for name, block in mutated["intermediates"].items()}
    forward = jax.jit(lambda p, row: ref.row_forward(p, row, m)[1])
    reference = np.stack([np.asarray(forward(params, row)) for row in tokens], axis=1)  # [depth, B, T, k]

    out = {"workload": args.workload, "device": str(jax.devices()[0].device_kind), "layers": {}}
    total = differ = landed_program = landed_reference = 0
    for name in sorted(program):
        mine, theirs = program[name], reference[int(name[len("block"):])]
        # a selection differs when the program chose an expert the reference did not
        missing = ~(mine[..., :, None] == theirs[..., None, :]).any(-1)
        held = (mine >= m.first) & (mine < m.first + m.held)
        out["layers"][name] = {
            "selections": int(mine.size), "differ": int(missing.sum()),
            "differ_on_held_experts": int((missing & held).sum()),
            "tokens_with_a_difference": int(missing.any(-1).sum()),
        }
        total += mine.size
        differ += int(missing.sum())
        landed_program += int(held.sum())
        landed_reference += int(((theirs >= m.first) & (theirs < m.first + m.held)).sum())
    out.update(selections=total, differ=differ, share=differ / total,
               landed_program=landed_program, landed_reference=landed_reference)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
