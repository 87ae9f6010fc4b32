"""Host->device input pipeline with background prefetch.

The reference delegated input loading to Torch's host-side dataset loop
(SURVEY.md §3 C15 — examples drove `nn` modules from Lua-side batches); the
TPU-native equivalent is an async staging pipeline: while the device runs
step N, a background thread stages batch N+1's host arrays onto the mesh
with the training sharding, so the host->device copy overlaps compute
instead of serializing with it.

Usage::

    it = prefetch_to_mesh(batch_iter, mesh, P(("dcn", "ici")), depth=2)
    for xb, yb in it:          # already device-resident, sharded
        state = step(state, xb, yb)

Works on any pytree of numpy arrays per batch.  ``depth`` bounds staged
batches (device memory = depth x batch bytes).  The thread dies with the
iterator (daemon + sentinel), and exceptions in the source iterator re-raise
at the consumer.
"""

from __future__ import annotations

import queue
import threading
import weakref
from typing import Any, Iterable, Iterator, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

PyTree = Any


def prefetch_to_mesh(batches: Iterable[PyTree], mesh: Mesh,
                     spec: PartitionSpec, *, depth: int = 2,
                     specs: Optional[PyTree] = None) -> Iterator[PyTree]:
    """Iterate device-resident, mesh-sharded copies of ``batches``.

    ``spec`` shards every leaf; pass ``specs`` (a pytree of PartitionSpec
    matching the batch structure) for per-leaf shardings instead.
    """
    if depth < 1:
        raise ValueError(f"prefetch depth must be >= 1, got {depth}")

    def put(batch: PyTree) -> PyTree:
        if specs is not None:
            return jax.tree.map(
                lambda leaf, s: jax.device_put(
                    leaf, NamedSharding(mesh, s)),
                batch, specs,
                is_leaf=lambda x: x is None)
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda leaf: jax.device_put(leaf, sharding),
                            batch)

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()

    class _End:
        pass

    class _Error:
        def __init__(self, exc: BaseException):
            self.exc = exc

    def _enqueue(item) -> bool:
        # Bounded put that honors abandonment: an early-closed consumer
        # sets `stop` and the producer exits instead of blocking forever
        # holding device buffers.
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def producer():
        try:
            for batch in batches:
                if not _enqueue(put(batch)):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised at consumer
            _enqueue(_Error(e))
            return
        _enqueue(_End())

    # Plain function, not a generator: depth validation fails at the call
    # site and prefetching starts immediately, not at the first next().
    th = threading.Thread(target=producer, daemon=True,
                          name="torchmpi-prefetch")
    th.start()

    def _abandon():
        # Release the producer and drop staged device buffers.  Idempotent:
        # runs from the generator's finally AND from its GC finalizer.
        stop.set()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass

    def consume() -> Iterator[PyTree]:
        try:
            while True:
                item = q.get()
                if isinstance(item, _End):
                    return
                if isinstance(item, _Error):
                    raise item.exc
                yield item
        finally:
            # Early close (break / exception / GC of the iterator).
            _abandon()

    gen = consume()
    # A never-started generator skips its finally on GC (close() is a no-op
    # before the first next()), which would leave the producer spinning and
    # `depth` batches pinned on device forever.  The finalizer covers that
    # path; it must not reference `gen` itself.
    weakref.finalize(gen, _abandon)
    return gen
