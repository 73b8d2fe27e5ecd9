"""Request dispatcher: micro-batches concurrent selection requests.

The service answers a *list* of requests in one fused launch per fuse
key; this module supplies the queueing discipline that turns independent
callers into such lists.  :class:`Dispatcher` runs one worker thread that
drains its queue completely on every wakeup — under load the drained
slice *is* the micro-batch, so batching emerges from backpressure rather
than from a timer (an idle server answers single requests immediately;
a busy one amortizes compile-free fused launches over whatever queued).

Serving is deterministic per *(fuse key, batch composition)*: replaying
the same batch yields the same bits, and single-request batches are
pinned bit-identical to the offline reference.  Across *different*
bucket sizes XLA emits distinct programs whose last-bit float drift can
flip a near-tie in the fold argmax, so opportunistic batching may pick
a different equally-valid coreset than one-at-a-time serving would.
Tests pin the deterministic cases: a ``max_batch=1`` dispatcher equals
direct single-request serving exactly, and repeated identical batches
equal each other exactly.

Queue depth at each drain is recorded on the service
(``note_queue_depth``) so the `serve` telemetry track and the manifest's
``queue_depth_max`` reflect real backpressure, not a synthetic load test.

Spans (:class:`repro.engine.telemetry.span`, on the profiler's clock and
in the service's Tracer when it has one): ``serve.submit`` around each
enqueue on the caller's thread; on the worker, ``serve.drain`` from the
moment a request is taken off the queue until the last answer of its
slice is handed back (the idle wait for work is outside it), with one
``serve.reply`` per request, in queue order.  A request's queueing time
is the start of the drain that replies to it less the end of its submit.
"""
from __future__ import annotations

import queue
import threading
from concurrent.futures import Future

from repro.engine.telemetry import span
from repro.serve.service import SelectionRequest, SelectionService


def serve_batch(service: SelectionService, requests) -> list:
    """Synchronous grouping entry point: one call, many requests, answers
    in request order.  Sugar over ``service.serve`` kept for symmetry with
    the threaded path."""
    return service.serve(list(requests))


class Dispatcher:
    """Threaded micro-batching front end over a :class:`SelectionService`.

    ``submit`` returns a ``concurrent.futures.Future`` resolving to the
    request's :class:`SelectionResult`; ``max_batch`` caps how many queued
    requests one fused launch may absorb.  All JAX work stays on the
    single worker thread — callers only build requests and wait.
    """

    def __init__(self, service: SelectionService, max_batch: int = 16):
        assert max_batch >= 1
        self.service = service
        self.max_batch = max_batch
        self._q: queue.Queue = queue.Queue()
        self._stop = object()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="serve-dispatcher")
        self._thread.start()

    def submit(self, req: SelectionRequest) -> Future:
        fut: Future = Future()
        with span("serve.submit", tracer=self.service.tracer):
            self._q.put((req, fut))
        return fut

    def map(self, requests) -> list:
        """Submit many, wait for all; results in request order."""
        futs = [self.submit(r) for r in requests]
        return [f.result() for f in futs]

    def close(self) -> None:
        self._q.put(self._stop)
        self._thread.join()

    # -- worker ------------------------------------------------------------
    def _drain(self, first) -> tuple[list, bool]:
        """The queued slice behind ``first`` (≤ max_batch), plus whether a
        stop token was seen while draining."""
        batch, stopped = [first], False
        while len(batch) < self.max_batch:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is self._stop:
                stopped = True
                break
            batch.append(item)
        return batch, stopped

    def _run(self) -> None:
        tracer = self.service.tracer
        while True:
            item = self._q.get()
            if item is self._stop:
                return
            with span("serve.drain", tracer=tracer) as dr:
                batch, stopped = self._drain(item)
                dr.args["batch"] = len(batch)
                self.service.note_queue_depth(len(batch) + self._q.qsize())
                reqs = [r for r, _f in batch]
                try:
                    results = self.service.serve(reqs)
                    for (_r, fut), res in zip(batch, results):
                        with span("serve.reply", tracer=tracer):
                            fut.set_result(res)
                except BaseException as exc:   # surface to every waiter
                    for _r, fut in batch:
                        if not fut.done():
                            with span("serve.reply", tracer=tracer):
                                fut.set_exception(exc)
            if stopped:
                return
