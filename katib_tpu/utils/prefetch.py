"""Device-prefetching input pipeline.

TPU-idiomatic double buffering: while the accelerator runs step N, the next
batches are already being transferred. Passing raw numpy into a jitted step
makes the transfer synchronous inside the dispatch, whereas `jax.device_put`
returns immediately and the copy overlaps with compute (its share of a step
on a local chip is not measured). The reference
delegates input pipelines to its trial images (tf.data / torch DataLoader
workers); this is the framework-native equivalent for JAX trials.

``prefetch_to_device(it, size=2)`` wraps any iterator of (pytrees of) numpy
arrays, keeping ``size`` batches in flight on the device (or sharded with
``sharding``). All model trainers consume their epoch iterators through it.
"""

from __future__ import annotations

import collections
import itertools
from typing import Any, Iterable, Iterator, Optional

import jax


def prefetch_to_device(
    iterator: Iterable[Any],
    size: int = 2,
    sharding: Optional[Any] = None,
) -> Iterator[Any]:
    """Yield items of ``iterator`` staged on device ``size`` batches ahead.

    ``sharding`` may be a Device, Sharding, or None (uncommitted placement on
    the default device: the steps that consume it then follow
    ``jax.default_device``, which is how a trial is placed on its chip).
    """
    queue: collections.deque = collections.deque()
    it = iter(iterator)

    def _stage(batch):
        if sharding is None:
            return jax.tree.map(jax.numpy.asarray, batch)
        return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)

    for batch in itertools.islice(it, size):
        queue.append(_stage(batch))
    while queue:
        yield queue.popleft()
        for batch in itertools.islice(it, 1):
            queue.append(_stage(batch))
