"""Asynchronous prefetch onto the device mesh: the feed pipeline's engine.

TPU-native equivalent of the reference's endpoint-server file-IO offload
(ENABLE_FILEIO, eplib/eplib.h:51-58 fopen/fread_nb/fwait: a second command
ring lets the server stream files into shared memory while the trainer
computes). Here the "server" is a background thread and the "shared memory"
is device HBM: batches are read/encoded, sharded onto the mesh, and
transferred ahead of use so the training loop never blocks on input.

Depth-N device-side buffering: the queue holds up to ``depth`` batches whose
transfers/decodes are already dispatched — the worker blocks (backpressure)
once that many are in flight, so HBM use is bounded at depth x batch bytes.
Both sides of the queue are accounted: time the CONSUMER blocks on an empty
queue is input stall (the number the feed pipeline exists to drive to zero,
the benchmark's ``input_stall_ms_per_step``), time the WORKER blocks on
a full queue is healthy backpressure. Both land in ``FEED_COUNTERS``.

Failure contract: a worker that dies mid-epoch surfaces its ORIGINAL
exception on the consumer's next ``__next__`` (never a hang on an empty
queue). Failures are classified through ``supervisor.classify`` first:
TRANSIENT source errors (flaky NFS reads, connection resets) retry in place
with exponential backoff under ``MLSL_FEED_RETRIES`` before anything
surfaces — the rung-2 contract of the recovery ladder, applied to the feed.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator, Optional

from mlsl_tpu import chaos
from mlsl_tpu.data.common import env_int as _env_int, retry_or_raise
from mlsl_tpu.log import log_warning, mlsl_assert


class AsyncLoader:
    """Wraps a host batch source with prefetch-to-device.

    source: iterator/callable yielding host batches (any pytree of np
    arrays), or a :class:`mlsl_tpu.data.DeviceFeed` (already-device batches);
    place: fn(host_batch) -> device batch (e.g. trainer.shard_batch);
    None = identity (the source already places);
    depth: batches kept in flight (default ``MLSL_FEED_DEPTH``, 2 = classic
    double buffering);
    retries: TRANSIENT source-read retries per batch (default
    ``MLSL_FEED_RETRIES``).
    """

    def __init__(self, source, place: Optional[Callable] = None,
                 depth: Optional[int] = None,
                 retries: Optional[int] = None,
                 retry_backoff_s: float = 0.05):
        # A DeviceFeed splits its work across the queue: the worker runs the
        # host encode + h2d staging (_prefetch_iter), and the DECODE program
        # is dispatched by the CONSUMER (_consumer_decode) — a background
        # thread must never launch device programs concurrently with the
        # training loop's own dispatches (on the CPU proof mesh that
        # cross-thread interleaving starves the collective rendezvous and
        # wedges the per-layer trainer).
        self._finalize = getattr(source, "_consumer_decode", None)
        # A DeviceFeed source also runs its own data.prefetch injection AND
        # its own TRANSIENT-retry loop per read (see below) — capture the
        # hint before the source is swapped for its wire stream.
        self._inject = getattr(source, "_chaos_site", None) != "data.prefetch"
        if self._finalize is not None and hasattr(source, "_prefetch_iter"):
            mlsl_assert(
                place is None,
                "AsyncLoader: place must be None for a DeviceFeed source — "
                "the feed already places and decodes its batches (got %r)",
                place,
            )
            source = source._prefetch_iter()
        self._source = iter(source) if not callable(source) else None
        self._source_fn = source if callable(source) else None
        self._place = place
        self._depth = max(1, depth if depth is not None
                          else _env_int("MLSL_FEED_DEPTH", 2))
        # Firing the chaos site here too would double every armed plan's hit
        # count, and re-retrying an error the feed already retried would
        # call next() on a generator that just raised — which yields
        # StopIteration and silently truncates the stream instead of
        # surfacing the failure.
        self._retries = (
            (retries if retries is not None
             else _env_int("MLSL_FEED_RETRIES", 2))
            if self._inject else 0
        )
        self._retry_backoff_s = retry_backoff_s
        self._q: queue.Queue = queue.Queue(maxsize=self._depth)
        self._stop = threading.Event()
        self._done = False
        self._exc: Optional[BaseException] = None
        self._batches = 0  # descriptor for the join-timeout warning in close()
        self._stall_s = 0.0          # consumer blocked on empty queue
        self._producer_wait_s = 0.0  # worker blocked on full queue (healthy)
        self._consumed = 0
        self._thread = threading.Thread(
            target=self._worker, daemon=True, name=f"mlsl-prefetch-{id(self):x}"
        )
        self._thread.start()

    def _next_host_batch(self):
        if self._source_fn is not None:
            return self._source_fn()
        return next(self._source)

    def _retry_or_raise(self, e: BaseException, attempt: int) -> int:
        return retry_or_raise(e, attempt, self._retries,
                              self._retry_backoff_s, self._stop.is_set)

    def _read_with_retries(self):
        """One batch read, with the chaos site and the rung-2 retry loop.

        Only re-attemptable reads retry: a CALLABLE source can simply be
        called again, and a chaos-site fault fires before the source is
        touched, so both are safe. A generator/iterator source whose frame
        raised is DEAD — next() on it returns StopIteration, so a "retry"
        would silently truncate the stream instead of surfacing the error;
        its failures propagate immediately with the original exception."""
        attempt = 0
        while True:
            if self._inject and chaos._plans:
                try:
                    chaos.inject("data.prefetch", batch=self._batches)
                except BaseException as e:
                    attempt = self._retry_or_raise(e, attempt)
                    continue
            try:
                return self._next_host_batch()
            except StopIteration:
                raise
            except BaseException as e:
                if self._source_fn is None:
                    raise  # iterator source: not re-attemptable (see above)
                attempt = self._retry_or_raise(e, attempt)

    def _worker(self):
        try:
            while not self._stop.is_set():
                try:
                    host = self._read_with_retries()
                except StopIteration:
                    self._q.put(_SENTINEL)
                    return
                self._batches += 1
                # placement dispatches the transfer asynchronously; holding
                # the resulting arrays in the queue keeps `depth` transfers
                # in flight (device-side buffering, bounded HBM)
                if self._place is None:
                    dev = host
                else:
                    dev = (self._place(*host) if isinstance(host, tuple)
                           else self._place(host))
                t0 = time.perf_counter()
                self._q.put(dev)
                waited = time.perf_counter() - t0
                self._producer_wait_s += waited
                if waited > 1e-4:  # actual backpressure, not queue overhead
                    from mlsl_tpu.core import stats

                    stats.record_feed_wait(waited * 1e3)
        except BaseException as e:  # surface worker failures to the consumer
            self._exc = e
            self._q.put(_SENTINEL)

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._done:
            # stay exhausted instead of blocking on an empty queue forever
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        try:
            item = self._q.get_nowait()
        except queue.Empty:
            # input stall: the training loop is about to wait on its feed
            t0 = time.perf_counter()
            item = self._q.get()
            stall = time.perf_counter() - t0
            self._stall_s += stall
            from mlsl_tpu.core import stats

            stats.record_feed_stall(stall * 1e3)
        if item is _SENTINEL:
            self._done = True
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        self._consumed += 1
        if self._finalize is not None:
            # consumer-thread decode (DeviceFeed): the device program is
            # dispatched here, in deterministic order with the training
            # loop's own dispatches
            item = self._finalize(item)
        return item

    def stats(self) -> dict:
        """Backpressure accounting for this loader: batches produced/consumed,
        consumer input-stall and producer backpressure-wait totals (ms)."""
        return {
            "depth": self._depth,
            "produced": self._batches,
            "consumed": self._consumed,
            "in_flight": self._q.qsize(),
            "stall_ms": self._stall_s * 1e3,
            "producer_wait_ms": self._producer_wait_s * 1e3,
        }

    def close(self) -> None:
        self._stop.set()
        # drain so the worker is not blocked on a full queue
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)
        if self._thread.is_alive():
            # The worker is wedged in the source or the device transfer —
            # abandoning it silently would hide the leak until HBM or file
            # handles run out.
            log_warning(
                "prefetch thread %s still alive after 5s join "
                "(was serving batch %d); abandoning it",
                self._thread.name,
                self._batches,
            )


_SENTINEL = object()
