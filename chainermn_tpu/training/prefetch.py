"""Device-side input prefetching.

The reference's input story was Chainer's ``MultiprocessIterator`` (host
worker processes); its device transfer happened synchronously inside the
update. This framework's native C++ loader covers the host side
(:mod:`chainermn_tpu.native.data_loader`); this module covers the
device side: keep the next ``size`` batches already submitted for
transfer so the host→HBM copy of batch ``t+1`` overlaps the step running
on batch ``t`` (JAX dispatch is asynchronous — ``device_put`` returns
while the copy is in flight; yielding from a bounded deque gives the
copies a head start without unbounded memory growth).

The classic pattern (flax's ``jax_utils.prefetch_to_device``) adapted to
this framework's batch flow: works on any pytree iterator, optionally
placing to an explicit sharding (multihost global batches pass through
untouched — they are already device-resident).
"""

from __future__ import annotations

import collections
from typing import Any, Iterable, Iterator, Optional

import jax

from chainermn_tpu.observability import train_path
from chainermn_tpu.observability.trace import span

PyTree = Any


class _FeedTotals:
    """What every feed of this process has handed over: plain integers
    that a generator adds to with no lock (the step's path), published
    to the metrics registry by :func:`_collect_feed` when it is read."""

    def __init__(self) -> None:
        self.batches = 0
        self.nbytes = 0
        self.not_ready = 0


_FEED = _FeedTotals()


def _collect_feed(reg) -> None:
    for name, help_, now in (
        (train_path.FEED_BATCHES, "batches the device feed handed over",
         _FEED.batches),
        (train_path.FEED_BYTES, "bytes of the batches handed over",
         _FEED.nbytes),
        (train_path.FEED_NOT_READY,
         "batches whose transfer had not finished when handed over",
         _FEED.not_ready),
    ):
        counter = reg.counter(name, help_)
        counter.inc(max(0.0, now - counter.value()))


def prefetch_to_device(
    iterator: Iterable[PyTree],
    size: int = 2,
    *,
    sharding: Optional[Any] = None,
) -> Iterator[PyTree]:
    """Yield batches from ``iterator`` with up to ``size`` of them already
    submitted to the device.

    Args:
      iterator: yields host-side batch pytrees (numpy or jax arrays; jax
        arrays pass through placement untouched when already committed).
      size: in-flight batch count. 2 = classic double buffering; each
        buffered batch holds HBM for its full pytree, so keep it small.
      sharding: optional ``jax.sharding.Sharding`` (or pytree of them) for
        ``jax.device_put``; default places to the default device (the
        jitted step re-places under its own in_shardings as needed, which
        for host arrays is free — the bytes are already on device).
    """
    if size < 1:
        raise ValueError(f"size must be >= 1, got {size}")

    def put(batch: PyTree) -> PyTree:
        if sharding is not None:
            return jax.device_put(batch, sharding)
        return jax.tree.map(
            lambda leaf: leaf
            if isinstance(leaf, jax.Array)
            else jax.device_put(leaf),
            batch,
        )

    def hand_over(batch: PyTree) -> PyTree:
        leaves = jax.tree.leaves(batch)
        _FEED.batches += 1
        _FEED.nbytes += sum(leaf.nbytes for leaf in leaves)
        _FEED.not_ready += not all(leaf.is_ready() for leaf in leaves)
        return batch

    def gen() -> Iterator[PyTree]:
        queue: collections.deque = collections.deque()
        it = iter(iterator)
        end = object()
        while True:
            while len(queue) < size:
                with span(train_path.FEED_NEXT):
                    host = next(it, end)
                if host is end:
                    while queue:
                        yield hand_over(queue.popleft())
                    return
                with span(train_path.FEED_PUT):
                    queue.append(put(host))
            yield hand_over(queue.popleft())

    from chainermn_tpu.observability.metrics import registry

    registry().register_collect(_collect_feed)

    # Validate eagerly at the call site (a generator function would defer
    # the ValueError to the first next(), far from the faulty argument).
    return gen()


__all__ = ["prefetch_to_device"]
