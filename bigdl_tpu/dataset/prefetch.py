"""Host→device prefetch: overlap input pipeline with device compute.

Reference analog: the Engine "io" thread pool + per-thread batch staging in
DistriOptimizer (utils/Engine.scala:218-355, optim/DistriOptimizer.scala:
216-233).  TPU-native: a background thread runs the host-side pipeline
(decode/augment/stack) and issues ``jax.device_put`` ahead of consumption,
so the accelerator never waits on the host — the standard double-buffering
recipe for keeping the MXU fed over a thin host link.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterator, Optional


class _Stop:
    pass


_STOP = _Stop()


def prefetch(iterator: Iterator, buffer_size: int = 2,
             transfer: Optional[Callable] = None) -> Iterator:
    """Wrap ``iterator`` with a background thread + bounded queue.

    ``transfer`` (e.g. a ``jax.device_put`` with a NamedSharding) runs on
    the background thread so H2D DMA overlaps the consumer's step.
    Each batch is one ``input/batch`` span of the producer thread: pulling
    it out of ``iterator`` (``input/stack``), then ``transfer``
    (``input/place``).
    Exceptions in the producer are re-raised at the consumer site.
    """
    q: "queue.Queue" = queue.Queue(maxsize=max(1, buffer_size))
    err = []
    stop = threading.Event()

    def _put(item) -> bool:
        """Bounded put that gives up once the consumer is gone; returns
        False when production should stop (prevents the producer thread —
        and its HBM-resident buffered batches — outliving an abandoned
        consumer, e.g. an infinite train iterator dropped at max_iteration)."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                # a put that got through as the consumer left and drained
                # the queue must not start one more batch
                return not stop.is_set()
            except queue.Full:
                continue
        return False

    def produce():
        from bigdl_tpu.observability import trace

        it = iter(iterator)
        try:
            while True:
                # one root a batch on this thread. The wait on a full
                # queue lies outside it: ``input/batch`` is busy time
                with trace.span("input/batch"):
                    with trace.span("input/stack"):
                        item = next(it, _STOP)
                    if item is not _STOP and transfer is not None:
                        with trace.span("input/place"):
                            item = transfer(item)
                if item is _STOP or not _put(item):
                    return
        except BaseException as e:  # propagate to consumer
            err.append(e)
        finally:
            _put(_STOP)

    t = threading.Thread(target=produce, daemon=True, name="bigdl-prefetch")
    t.start()

    def consume():
        try:
            while True:
                item = q.get()
                if item is _STOP:
                    if err:
                        raise err[0]
                    return
                yield item
        finally:
            # consumer closed/abandoned (GeneratorExit or normal end):
            # release the producer and drop buffered items
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass

    return consume()


def device_prefetch(batch_iterator: Iterator, sharding=None,
                    buffer_size: int = 2) -> Iterator:
    """Prefetch MiniBatch/array batches onto device.

    ``sharding``: an optional ``jax.sharding.Sharding`` for the batch dim
    (data-parallel input placement); None = default device placement.
    """
    import jax

    from bigdl_tpu.dataset.minibatch import MiniBatch

    def put(x):
        return jax.device_put(x, sharding) if sharding is not None else jax.device_put(x)

    def transfer(b):
        if isinstance(b, MiniBatch):
            return MiniBatch([put(x) for x in b.inputs],
                             [put(t) for t in b.targets] or None)
        return jax.tree.map(put, b)

    return prefetch(batch_iterator, buffer_size=buffer_size, transfer=transfer)
