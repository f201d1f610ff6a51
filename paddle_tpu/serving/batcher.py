"""Dynamic request batching with admission control.

Clipper-style adaptive batching in front of the shape-bucketed engines:
requests queue; the dispatch loop coalesces them into the smallest warm
bucket that covers the backlog, waiting at most ``max_wait_ms`` past the
OLDEST queued request before dispatching a partial batch. Admission is
bounded (``max_queue``) and rejection is a typed error (QueueFullError) —
overload degrades into fast failures, not unbounded latency. Each request
carries a deadline; requests that expire while queued (or after a
fault-injected batch was dropped back) complete with RequestTimeoutError
instead of occupying a bucket row.

The ``fault_hook`` is the test seam: a callable invoked with each formed
batch right before it is handed to the engine. It may sleep (delaying the
batch) or return ``"drop"`` to push the batch back onto the queue front —
simulating a lost dispatch so tests can pin the timeout/retry semantics.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, List, Optional, Sequence

from .. import trace
from .errors import EngineClosedError, QueueFullError, RequestTimeoutError


class Future:
    """Minimal completion handle: ``result(timeout)`` blocks for the
    value or re-raises the failure set by the serving loop."""

    def __init__(self):
        self._done = threading.Event()
        self._result = None
        self._exc: Optional[BaseException] = None

    def set_result(self, value) -> None:
        self._result = value
        self._done.set()

    def set_exception(self, exc: BaseException) -> None:
        self._exc = exc
        self._done.set()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout):
            raise TimeoutError("result not ready")
        if self._exc is not None:
            raise self._exc
        return self._result


class Request:
    """One queued unit of work: an opaque payload plus scheduling state.

    ``span``/``queue_span`` carry the request's trace: the request span
    opens at admission and closes at completion (whichever thread that
    happens on); the queue span covers admission -> dispatch and records
    the queue-wait attribute. Both are None with tracing off.
    """

    __slots__ = ("payload", "meta", "future", "enqueue_t", "deadline",
                 "span", "queue_span", "media")

    def __init__(self, payload: Any, meta: dict,
                 timeout_ms: Optional[float]):
        self.payload = payload
        self.meta = meta
        self.future = Future()
        self.enqueue_t = time.monotonic()
        self.deadline = (self.enqueue_t + timeout_ms / 1e3
                         if timeout_ms else None)
        self.span = None
        self.queue_span = None
        #: what ``submit``'s check and then admission made of the payload's
        #: media (``serving.media.MediaPlan``; None: a prompt of token ids
        #: alone, or one nobody has looked at yet)
        self.media = None

    def expired(self, now: Optional[float] = None) -> bool:
        return (self.deadline is not None
                and (now or time.monotonic()) >= self.deadline)

    def begin_trace(self) -> None:
        """Open the request + queue spans (detached: they cross threads
        and are ended explicitly by the dispatch/completion path). A
        ``traceparent`` key in ``meta`` (injected by the fleet router or
        an upstream HTTP client) RESUMES that trace — the request's
        spans join the caller's trace id instead of starting a fresh
        one; malformed headers fall back to a fresh trace."""
        parent = trace.extract((self.meta or {}).get("traceparent"))
        self.span = trace.start_span(
            "serving/request", detached=True, parent=parent,
            timeout_ms=(None if self.deadline is None
                        else round((self.deadline - self.enqueue_t) * 1e3)))
        if self.span is not None:
            self.queue_span = trace.start_span(
                "serving/queue", parent=self.span, detached=True)

    def mark_dispatched(self, batch_size: int) -> None:
        """Close the queue span, recording the queue wait."""
        wait_s = time.monotonic() - self.enqueue_t
        if self.queue_span is not None:
            self.queue_span.finish(queue_wait_s=round(wait_s, 6),
                                   batch_size=batch_size)
            self.queue_span = None
        if self.span is not None:
            self.span.set_attr("queue_wait_s", round(wait_s, 6))

    def end_trace(self, status: str = "ok", **attrs) -> None:
        """Close the request span (and a still-open queue span) — called
        from whichever thread completes the request."""
        if self.queue_span is not None:
            self.queue_span.finish(status=status)
            self.queue_span = None
        if self.span is not None:
            self.span.finish(status=status, **attrs)
            self.span = None


class DynamicBatcher:
    """Bounded request queue + bucket-deadline batch former.

    buckets: ascending batch-size buckets the engine keeps warm; a batch
    is dispatched once the backlog covers the largest bucket or the
    oldest request has waited ``max_wait_ms``.
    """

    def __init__(self, buckets: Sequence[int] = (1, 2, 4, 8),
                 max_wait_ms: float = 5.0, max_queue: int = 256,
                 default_timeout_ms: Optional[float] = None,
                 metrics=None,
                 fault_hook: Optional[Callable[[List[Request]], Any]] = None):
        if not buckets:
            raise ValueError("need at least one batch bucket")
        self.buckets = sorted(set(int(b) for b in buckets))
        self.max_wait_s = max_wait_ms / 1e3
        self.max_queue = int(max_queue)
        self.default_timeout_ms = default_timeout_ms
        self.metrics = metrics
        self.fault_hook = fault_hook
        self._q: deque = deque()
        self._cond = threading.Condition()
        self._closed = False

    # -- admission ---------------------------------------------------------
    def submit(self, payload: Any, timeout_ms: Optional[float] = None,
               *, media=None, **meta) -> Future:
        """Enqueue a request; raises QueueFullError at capacity (the
        backpressure contract) and EngineClosedError after close().
        ``media``: what ``Server.submit``'s check made of the payload's
        media (``Request.media``)."""
        req = Request(payload, meta,
                      timeout_ms if timeout_ms is not None
                      else self.default_timeout_ms)
        req.media = media
        req.begin_trace()
        with self._cond:
            if self._closed:
                req.end_trace(status="closed")
                raise EngineClosedError("batcher is closed")
            if len(self._q) >= self.max_queue:
                if self.metrics:
                    self.metrics.inc("rejected_queue_full")
                req.end_trace(status="rejected_queue_full")
                raise QueueFullError(
                    f"queue at capacity ({self.max_queue}); retry with "
                    "backoff")
            self._q.append(req)
            if self.metrics:
                self.metrics.inc("requests")
                self.metrics.set_gauge("queue_depth", len(self._q))
            self._cond.notify_all()
        return req.future

    def bucket_for(self, n: int) -> int:
        """Smallest warm bucket covering ``n`` (the largest bucket when
        ``n`` exceeds them all — callers chunk)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    # -- dispatch ----------------------------------------------------------
    def _expire_locked(self, now: float) -> None:
        kept = deque()
        for req in self._q:
            if req.expired(now):
                self._fail_timeout(req)
            else:
                kept.append(req)
        self._q = kept

    def _fail_timeout(self, req: Request) -> None:
        if self.metrics:
            self.metrics.inc("timeouts")
        req.end_trace(status="timeout")
        req.future.set_exception(RequestTimeoutError(
            "request deadline expired before execution"))

    def next_batch(self, max_n: Optional[int] = None,
                   wait_s: Optional[float] = None) -> List[Request]:
        """Form the next batch, blocking up to ``wait_s`` (default: the
        bucket deadline) for work. Returns [] when nothing is ready —
        the serving loop's idle signal, never an error."""
        cap = self.buckets[-1] if max_n is None else min(
            max_n, self.buckets[-1])
        if cap <= 0:
            return []
        with self._cond:
            deadline0 = time.monotonic() + (
                wait_s if wait_s is not None else self.max_wait_s)
            while not self._q and not self._closed:
                remaining = deadline0 - time.monotonic()
                if remaining <= 0:
                    return []
                self._cond.wait(remaining)
            if not self._q:
                return []
            # bucket deadline: measured from the OLDEST request's arrival.
            # wait_s == 0 is the continuous-batching poll: grab whatever
            # is queued NOW (mid-flight joins must not stall decode ticks).
            if wait_s != 0:
                batch_deadline = self._q[0].enqueue_t + self.max_wait_s
                while (len(self._q) < cap and not self._closed
                       and time.monotonic() < batch_deadline):
                    self._cond.wait(batch_deadline - time.monotonic())
            now = time.monotonic()
            self._expire_locked(now)
            batch = []
            while self._q and len(batch) < cap:
                batch.append(self._q.popleft())
            if self.metrics:
                self.metrics.set_gauge("queue_depth", len(self._q))
        if not batch:
            return []
        if self.fault_hook is not None:
            action = self.fault_hook(batch)
            if action == "drop":
                # simulate a lost dispatch: requeue at the FRONT so a
                # later batch retries them (deadlines keep counting down)
                if self.metrics:
                    self.metrics.inc("batches_dropped")
                self.requeue(batch)
                return []
            # a hook that merely slept may have pushed requests past
            # their deadlines — honor them before dispatch
            now = time.monotonic()
            live = [r for r in batch if not r.expired(now)]
            for r in batch:
                if r.expired(now):
                    self._fail_timeout(r)
            batch = live
            if not batch:
                return []
        if self.metrics:
            self.metrics.inc("batches")
            self.metrics.inc("batched_requests", len(batch))
        for req in batch:
            req.mark_dispatched(len(batch))
        return batch

    def requeue(self, requests: List[Request]) -> None:
        """Push requests back to the queue front (oldest first)."""
        with self._cond:
            for req in reversed(requests):
                if req.span is not None and req.queue_span is None:
                    # back in the queue: reopen a queue segment
                    req.queue_span = trace.start_span(
                        "serving/queue", parent=req.span, detached=True,
                        requeued=True)
                self._q.appendleft(req)
            if self.metrics:
                self.metrics.set_gauge("queue_depth", len(self._q))
            self._cond.notify_all()

    def kick(self) -> None:
        """Wake any waiter (used when slots free up mid-wait)."""
        with self._cond:
            self._cond.notify_all()

    @property
    def depth(self) -> int:
        with self._cond:
            return len(self._q)

    def close(self, drain: bool = False) -> None:
        """Stop admitting. Default fails everything still queued;
        ``drain=True`` keeps queued requests so the dispatch loop can
        finish them (the graceful-shutdown path — call again without
        ``drain`` to fail whatever could not be drained in time)."""
        with self._cond:
            self._closed = True
            pending = [] if drain else list(self._q)
            if not drain:
                self._q.clear()
            self._cond.notify_all()
        for req in pending:
            req.end_trace(status="closed")
            req.future.set_exception(EngineClosedError("server stopped"))
