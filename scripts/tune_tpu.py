#!/usr/bin/env python
"""Bounded on-chip tuning sweep.

Two sweeps, both using the honest chained-loop timing recipe from
``katib_tpu.utils.timing`` (one host read per pass, round-trip subtracted):

1. flash-attention forward blocks: (block_q, block_k) grid at the bench
   shape (b4 t2048 h8 d64 bf16 causal), fwd and fwd+bwd — validates (or
   dethrones) the FWD_BLOCK_Q_CAP=512 / FWD_BLOCK_K_CAP=1024 defaults that
   came from the round-4 measured sweep (ops/flash_attention.py:388-392).
2. LM train-step batch size per config: MFU at batch {4,8,16} (small) /
   {2,4,8} (large) — finds the arithmetic-intensity knee of the chip the
   driver actually benches on.

Writes ``examples/records/tpu_tuning_<day>.json``. Read-only with respect
to the framework: it never edits defaults — a human (or the next round)
promotes winners into code with the record as provenance.

Usage: python scripts/tune_tpu.py [--quick] [--out PATH]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _timeit_chained(fn, x0, args, rt_ms: float, n: int, passes: int = 2) -> float:
    """min-of-passes per-call seconds; chains x through so calls serialize."""
    from katib_tpu.utils.timing import host_sync

    host_sync(fn(x0, *args))  # compile + drain
    best = None
    for _ in range(passes):
        t0 = time.time()
        out = x0
        for _ in range(n):
            out = fn(out, *args)
        host_sync(out)
        cur = max((time.time() - t0 - rt_ms / 1e3) / n, 1e-9)
        best = cur if best is None else min(best, cur)
    return best


def sweep_flash(jax, np, rt_ms: float, quick: bool) -> dict:
    import jax.numpy as jnp

    from katib_tpu.ops.flash_attention import flash_attention

    b, t, h, d = 4, 2048, 8, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.bfloat16)

    bqs = (256, 512) if quick else (128, 256, 512, 1024)
    bks = (512, 1024) if quick else (256, 512, 1024, 2048)
    n = 30 if quick else 50
    grid = []
    for bq in bqs:
        for bk in bks:
            if t % bq or t % bk:
                continue
            fwd = jax.jit(
                lambda q, k, v, _bq=bq, _bk=bk: flash_attention(
                    q, k, v, causal=True, block_q=_bq, block_k=_bk
                )
            )

            def loss(q, k, v, _f=fwd):
                return _f(q, k, v).astype(jnp.float32).sum()

            gradq = jax.jit(jax.grad(loss))
            entry = {"block_q": bq, "block_k": bk}
            try:
                entry["fwd_ms"] = _timeit_chained(fwd, q, (k, v), rt_ms, n) * 1e3
                entry["fwd_bwd_ms"] = (
                    _timeit_chained(lambda x, k, v: gradq(x, k, v), q, (k, v), rt_ms, n)
                    * 1e3
                )
            except Exception as e:  # a tile config the VMEM budget rejects
                entry["error"] = f"{type(e).__name__}: {e}"[:160]
            grid.append(entry)
            print(f"  flash {entry}", flush=True)
    ok = [g for g in grid if "fwd_ms" in g]
    return {
        "shape": f"b{b} t{t} h{h} d{d} bf16 causal",
        "grid": grid,
        "best_fwd": min(ok, key=lambda g: g["fwd_ms"]) if ok else None,
        "best_fwd_bwd": min(ok, key=lambda g: g["fwd_bwd_ms"]) if ok else None,
        "current_default": {"block_q": 512, "block_k": 1024},
    }


def sweep_lm_batch(jax, np, rt_ms: float, size: str, quick: bool) -> dict:
    import jax.numpy as jnp

    from katib_tpu.models.transformer import TransformerConfig, bench_lm_config
    from katib_tpu.parallel.mesh import make_mesh
    from katib_tpu.parallel.train import make_lm_train_step
    from katib_tpu.utils.timing import host_sync

    cfg, _, seq, _ = bench_lm_config(size, on_tpu=True)
    if size == "large":
        batches = (2, 4) if quick else (2, 4, 8)
    else:
        batches = (8, 16) if quick else (4, 8, 16)

    config = TransformerConfig(**cfg)
    mesh = make_mesh(jax.devices()[:1])
    results = []
    n = 20 if quick else 30
    for batch in batches:
        params, opt_state, step_fn, put_batch = make_lm_train_step(config, mesh, 1e-3)
        rng = np.random.default_rng(0)
        data = rng.integers(0, config.vocab_size, size=(batch, seq + 1), dtype=np.int32)
        tokens, targets, positions = put_batch(data[:, :-1], data[:, 1:])
        entry = {"batch": batch}
        try:
            state = step_fn(params, opt_state, tokens, targets, positions)
            host_sync(state[2])
            params, opt_state = state[0], state[1]
            best = None
            for _ in range(2):
                t0 = time.time()
                for _ in range(n):
                    state = step_fn(params, opt_state, tokens, targets, positions)
                    params, opt_state = state[0], state[1]
                host_sync(state[2])
                cur = max((time.time() - t0 - rt_ms / 1e3) / n, 1e-9)
                best = cur if best is None else min(best, cur)
            import bench as bench_mod  # same MFU accounting as the driver bench

            n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
            flops_per_step = (
                6 * n_params * batch * seq
                + 12 * config.num_layers * batch * seq * seq * config.embed_dim
            )
            peak = bench_mod._peak_flops(
                getattr(jax.devices()[0], "device_kind", "")
            )
            entry.update(
                step_ms=best * 1e3,
                tokens_per_s=batch * seq / best,
                mfu=round(flops_per_step / best / peak, 4) if peak else None,
            )
        except Exception as e:
            entry["error"] = f"{type(e).__name__}: {e}"[:160]
        results.append(entry)
        print(f"  lm[{size}] {entry}", flush=True)
        del params, opt_state
    # tokens/s orders identically to MFU for a fixed config and stays
    # comparable when the device kind has no known peak (mfu=None)
    ok = [r for r in results if "tokens_per_s" in r]
    return {
        "config": f"{size}: {cfg['embed_dim']}d x {cfg['num_layers']}L, T={seq}",
        "batches": results,
        "best": max(ok, key=lambda r: r["tokens_per_s"]) if ok else None,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller grids/loops")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    force_cpu = os.environ.get("JAX_PLATFORMS", "").startswith("cpu")

    import numpy as np

    import jax

    if force_cpu:
        jax.config.update("jax_platforms", "cpu")

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("refusing to tune on CPU (timings would be meaningless)")
        return 1
    from katib_tpu.utils.compilation import enable_compilation_cache
    from katib_tpu.utils.timing import roundtrip_ms

    enable_compilation_cache()
    rt_ms = roundtrip_ms()
    print(f"device {getattr(dev, 'device_kind', '?')}, roundtrip {rt_ms:.1f}ms",
          flush=True)

    record = {
        "captured_at": datetime.datetime.now().isoformat(timespec="seconds"),
        "device_kind": getattr(dev, "device_kind", "?"),
        "roundtrip_ms": round(rt_ms, 2),
        "quick": args.quick,
    }
    t0 = time.time()
    print("flash forward-block sweep:", flush=True)
    record["flash"] = sweep_flash(jax, np, rt_ms, args.quick)
    print("LM batch sweep (small):", flush=True)
    record["lm_small"] = sweep_lm_batch(jax, np, rt_ms, "small", args.quick)
    print("LM batch sweep (large):", flush=True)
    record["lm_large"] = sweep_lm_batch(jax, np, rt_ms, "large", args.quick)
    record["sweep_wallclock_s"] = round(time.time() - t0, 1)

    day = datetime.datetime.now().strftime("%Y%m%d")
    out = args.out or os.path.join(REPO, "examples", "records", f"tpu_tuning_{day}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"record written to {out}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
