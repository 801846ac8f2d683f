"""Device prefetch for the input pipeline (SURVEY.md §5: the reference has
no data subsystem at all — its README pulls tensors synchronously).

On TPU the host->device batch transfer otherwise sits on the train step's
critical path; staging the next batches from a background thread while the
current step runs hides it entirely (the standard TPU input-pipeline
pattern; jax transfers are thread-safe and async, so the worker only
initiates DMAs — it never blocks on compute).
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator, Optional

import jax

_END = object()


def prefetch_to_device(
    data: Iterator,
    *,
    size: int = 2,
    sharding: Optional[jax.sharding.Sharding] = None,
    metrics_writer=None,
) -> Iterator:
    """Wrap `data` so the next `size` batches are already on device (laid
    out per `sharding` if given — pass the DistributedTrainer's batch
    sharding to stage shards directly on their target devices) while the
    consumer runs.

    Validation and the worker thread start HERE, at the call — prefetching
    begins immediately, and a bad `size` fails at the call site rather
    than deep inside a training loop. Exceptions from `data` propagate to
    the consumer at the point of the failed batch. Dropping the returned
    iterator (the common case: `fit` pulls num_steps batches from an
    infinite dataset and returns) signals the worker to stop and drains
    the staged batches, so neither the thread nor the device buffers
    outlive the consumer.

    The worker's two host phases are spans (tracing.spans.span:
    host_prefetch_next = pulling from the source iterator,
    host_prefetch_stage = initiating the device transfer), each carrying
    the worker's batch count as `step=`, rolled up in a private
    aggregator. The returned iterator's `span_records()` drains that
    aggregator: `fit_loop` calls it at every logging boundary, so a stream
    that never ends still reports. What is left when the stream ends goes
    to `metrics_writer` (or, without one, to the global flight recorder).
    """
    if size < 1:
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    q: queue.Queue = queue.Queue(maxsize=size)
    stop = threading.Event()

    from glom_tpu.tracing.spans import SpanAggregator, span

    spans = SpanAggregator()

    def put(item) -> bool:
        """Blocking put that aborts when the consumer is gone."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            n = 0
            while True:
                with span("host_prefetch_next", aggregator=spans, step=n):
                    batch = next(iter_data, _END)
                if batch is _END:
                    break
                with span("host_prefetch_stage", aggregator=spans, step=n):
                    staged = jax.device_put(batch, sharding)
                if not put(staged):
                    return
                n += 1
        except BaseException as e:  # noqa: BLE001 - relay to the consumer
            put((_END, e))
            return
        put((_END, None))

    iter_data = iter(data)

    def span_records(extra: Optional[dict] = None) -> list:
        return spans.records(extra={**(extra or {}), "source": "prefetch_to_device"})

    def _drain_spans():
        from glom_tpu.tracing.flight import write_or_observe

        for rec in span_records():
            write_or_observe(metrics_writer, rec)

    thread = threading.Thread(target=worker, daemon=True)
    thread.start()

    def drain():
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass

    def gen():
        try:
            while True:
                item = q.get()
                if isinstance(item, tuple) and len(item) == 2 and item[0] is _END:
                    if item[1] is not None:
                        raise item[1]
                    return
                yield item
        finally:
            # Consumer done (exhausted, closed, or GC'd): unblock the
            # worker and drop any staged device buffers promptly. A worker
            # mid-put can still enqueue ONE already-transferred batch after
            # a single drain, so alternate drain/join until it has actually
            # exited (bounded: a worker stuck inside `data` itself is a
            # daemon thread and cannot re-enqueue once stop is set and the
            # final drain has run).
            stop.set()
            deadline = 20  # x 0.1s join timeout = 2s bound
            while True:
                drain()
                thread.join(timeout=0.1)
                if not thread.is_alive() or deadline <= 0:
                    break
                deadline -= 1
            drain()
            _drain_spans()

    return _Prefetched(gen(), span_records)


class _Prefetched:
    """The staged stream: the generator's iteration and clean-up (dropping
    it stops the worker), plus the worker's span rollups."""

    def __init__(self, gen, span_records):
        self._gen = gen
        self.span_records = span_records

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._gen)

    def close(self):
        self._gen.close()
