"""Wall-clock measurement of device work from the host.

JAX returns from a jitted call before the device has finished, so a loop
timed without a synchronisation measures the enqueue. On a local chip
``jax.block_until_ready`` is that synchronisation; a device->host read of
one element is another, and it also holds where the array's readiness is
reported by something other than the device: the value isn't available
until the producing computation (and, through data dependencies, everything
it chains from) has run.

The recipe of the hardware-gated perf tests (tests/test_tpu_hardware.py):

1. ``host_sync`` once before starting the clock (drains queued work);
2. chain each iteration's output into the next iteration's input so the
   loop cannot be reordered or deduplicated;
3. ``host_sync`` the final output — one round-trip for the whole loop;
4. subtract ``roundtrip_ms`` (the cost of step 3) and divide by N.
"""

from __future__ import annotations

import time


def host_sync(x) -> float:
    """Force completion with a 1-element device->host read; returns it."""
    import jax.numpy as jnp

    return float(jnp.ravel(x)[0])


def roundtrip_ms(repeats: int = 3) -> float:
    """Per-call dispatch + host-read round-trip latency in milliseconds —
    what step 4 above subtracts."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8,))
    host_sync(f(x))
    t0 = time.time()
    for _ in range(repeats):
        x = f(x)
        host_sync(x)
    return (time.time() - t0) / repeats * 1e3
