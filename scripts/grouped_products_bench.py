#!/usr/bin/env python3
"""By hand, on the chip: what XLA makes of ``jax.lax.ragged_dot`` against the
Pallas grouped kernels (katib_tpu/ops/grouped_matmul.py) for one routed layer's
products at the benchmark's sparse cell — forward, input gradient, weight
gradient — on the same tile-aligned rows; and the windowed flash kernels at a
few tile sizes. Prints one JSON line; PERF.md (PR 30) holds the readings.

    python3 scripts/grouped_products_bench.py [--tokens 8192] [--out chiprun_out/grouped.json]
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def timed(fn, *args, repeats=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    from katib_tpu.models.transformer import RoutedExpertsConfig, dispatch_plan, route
    from katib_tpu.ops import flash_attention as fa
    from katib_tpu.ops import grouped_matmul as gm

    device = jax.devices()[0]
    result = {"device": device.device_kind, "platform": device.platform, "tokens": args.tokens}
    e, f, held = 2048, 512, 32
    routed = RoutedExpertsConfig(router_width=256, experts_per_token=8, hidden=f, num_experts=held)
    key = jax.random.PRNGKey(0)
    scores = jax.nn.sigmoid(jax.random.normal(key, (args.tokens, 256)))
    _, local, _ = route(scores, routed)
    plan = jax.jit(functools.partial(
        dispatch_plan, held=held, tile=gm.TILE, spare=gm.CHUNK_TILES - 1))(local)
    m = plan["source"].shape[0]
    tiles = int(plan["num_tiles"])
    result.update(rows=m, tiles_in_use=tiles, rows_landed=int(plan["landed"].sum()),
                  load_max=int(plan["load"].max()))
    rows = jax.random.normal(key, (m, e), jnp.bfloat16)
    rows = jnp.where((plan["source"] < args.tokens * 8)[:, None], rows, 0)
    w_in = jax.random.normal(key, (held, e, f), jnp.float32) * 0.02
    w_out = jax.random.normal(key, (held, f, e), jnp.float32) * 0.02
    in_use = jnp.arange(plan["tile_group"].shape[0]) < plan["num_tiles"]
    sizes = jnp.zeros((held,), jnp.int32).at[plan["tile_group"]].add(jnp.where(in_use, gm.TILE, 0))
    tight = plan["load"].astype(jnp.int32)

    def pallas(x, w):
        return gm.grouped_matmul(x, w, plan["tile_group"], plan["num_tiles"])

    def ragged(x, w, group_sizes=sizes):
        return jax.lax.ragged_dot(x, w.astype(x.dtype), group_sizes,
                                  preferred_element_type=jnp.float32).astype(x.dtype)

    for label, w, x in (("in_2048x512", w_in, rows), ("out_512x2048", w_out, rows[:, :f])):
        for name, fn in (("pallas", pallas), ("ragged_dot", ragged)):
            fwd = jax.jit(fn)
            grad_x = jax.jit(jax.grad(lambda x, w: fn(x, w).astype(jnp.float32).sum(), argnums=0))
            grad_w = jax.jit(jax.grad(lambda x, w: fn(x, w).astype(jnp.float32).sum(), argnums=1))
            try:
                result[f"{label}.{name}.fwd_ms"] = timed(fwd, x, w)
                result[f"{label}.{name}.fwd_and_dlhs_ms"] = timed(grad_x, x, w)
                result[f"{label}.{name}.fwd_and_dw_ms"] = timed(grad_w, x, w)
            except Exception as err:  # what the compiler refuses is a reading too
                result[f"{label}.{name}.error"] = repr(err)[:300]
        # the two must agree where rows are in use
        a, b = jax.jit(pallas)(x, w), jax.jit(ragged)(x, w)
        used = jnp.repeat(in_use, gm.TILE)[:, None]
        result[f"{label}.max_abs_difference"] = float(
            jnp.max(jnp.abs(jnp.where(used, a.astype(jnp.float32) - b.astype(jnp.float32), 0))))
    # ragged_dot over the rows packed tight (no padding to tiles): the other layout it could take
    try:
        packed = rows[: args.tokens * 8]
        result["in_2048x512.ragged_dot_tight.fwd_ms"] = timed(
            jax.jit(functools.partial(ragged, group_sizes=tight)), packed, w_in)
    except Exception as err:
        result["in_2048x512.ragged_dot_tight.error"] = repr(err)[:300]

    # the windowed flash kernels, 64 query heads over 8 KV heads, T = tokens, window 512
    t, h, kv, d, window = args.tokens, 64, 8, 128, 512
    q = jax.random.normal(key, (1, t, h, d), jnp.bfloat16)
    k = jax.random.normal(key, (1, t, kv, d), jnp.bfloat16)
    for block in (128, 256, 512):
        for bk in sorted({block, 512}):
            def attend(q, k, v, block=block, bk=bk):
                return fa.flash_attention(q, k, v, causal=True, window=window, block_q=block, block_k=bk)
            label = f"flash_window.bq{block}.bk{bk}"
            try:
                result[f"{label}.fwd_ms"] = timed(jax.jit(attend), q, k, k)
                result[f"{label}.fwd_bwd_ms"] = timed(jax.jit(jax.grad(
                    lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))), q, k, k)
            except Exception as err:
                result[f"{label}.error"] = repr(err)[:300]
    full = lambda q, k, v: fa.flash_attention(q[:, :, :48], k, v, causal=True)
    result["flash_full.48over8.fwd_ms"] = timed(jax.jit(full), q, k, k)
    result["flash_full.48over8.fwd_bwd_ms"] = timed(jax.jit(jax.grad(
        lambda q, k, v: full(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))), q, k, k)
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    print(line)


if __name__ == "__main__":
    main()
