#!/usr/bin/env python
"""Capture an xplane profile of the large-LM train step on the TPU.

The round-4 bench pins mfu_large at ~0.56; pushing further needs the real
per-op time split, not guesses (a fused-CE kernel was considered and
rejected on FLOP arithmetic — its backward recomputation costs more than
the logits HBM traffic it saves at this config). This script runs the
exact `bench.py` large-LM configuration under ``jax.profiler.trace`` and
leaves the xplane protobufs in a scratch directory (default under /tmp —
binary profiler blobs don't belong in the curated examples/records/; check
in *conclusions*, not traces) for offline analysis; it also prints the
coarse wall-clock split it can measure directly (compile, first step,
steady step).

Usage: python scripts/profile_lm.py [--steps 20] [--size large]
"""

from __future__ import annotations

import argparse
import datetime
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--size", choices=("small", "large"), default="large")
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--tpu", action="store_true",
        help="run on the accelerator backend (default forces CPU)",
    )
    args = ap.parse_args()

    if not args.tpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from katib_tpu.models.transformer import TransformerConfig, bench_lm_config
    from katib_tpu.parallel.mesh import make_mesh
    from katib_tpu.parallel.train import make_lm_train_step
    from katib_tpu.utils.compilation import enable_compilation_cache
    from katib_tpu.utils.timing import host_sync

    enable_compilation_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    cfg, batch, seq, effective = bench_lm_config(args.size, on_tpu)
    config = TransformerConfig(**cfg)
    mesh = make_mesh(jax.devices()[:1])
    params, opt_state, step_fn, put_batch = make_lm_train_step(config, mesh, 1e-3)
    rng = np.random.default_rng(0)
    data = rng.integers(0, config.vocab_size, size=(batch, seq + 1), dtype=np.int32)
    tokens, targets, positions = put_batch(data[:, :-1], data[:, 1:])

    t0 = time.time()
    params, opt_state, loss = step_fn(params, opt_state, tokens, targets, positions)
    host_sync(loss)
    compile_s = time.time() - t0

    # untraced steady-step timing FIRST (the number comparable to bench.py's
    # step_ms) — profiler start/stop and xplane serialization must not be
    # divided into it
    t0 = time.time()
    for _ in range(args.steps):
        params, opt_state, loss = step_fn(
            params, opt_state, tokens, targets, positions
        )
    host_sync(loss)
    steady = (time.time() - t0) / args.steps

    day = datetime.datetime.now().strftime("%Y%m%d")
    trace_dir = args.out or os.path.join(
        tempfile.gettempdir(), "katib_tpu_profiles", f"lm_{effective}_{day}"
    )
    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        for _ in range(args.steps):
            params, opt_state, loss = step_fn(
                params, opt_state, tokens, targets, positions
            )
        host_sync(loss)
    print(f"device={getattr(dev, 'device_kind', dev.platform)} "
          f"config={effective} ({config.num_layers}L {config.embed_dim}d "
          f"V{config.vocab_size} b{batch} T{seq}) "
          f"compile={compile_s:.1f}s untraced_step={steady * 1e3:.2f}ms "
          f"loss={float(loss):.4f}")
    print(f"xplane trace -> {trace_dir}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
