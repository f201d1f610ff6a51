"""Hierarchical span tracer — the Dapper-style backbone of the telemetry
plane.

A ``Span`` is one named, timed region with attributes, a unique id, and a
link to its parent; spans from one logical operation (a serving request, an
executor run) share a ``trace_id``. Nesting is tracked per thread (a
thread-local span stack), so ``with span("a"): with span("b"): ...``
records ``b`` as a child of ``a`` with no plumbing. Cross-thread
operations (a request admitted on an HTTP thread, executed on the dispatch
thread) use *detached* spans: ``start_span(..., detached=True)`` returns a
handle that never touches any stack and is ended explicitly — children on
other threads link to it by passing ``parent=``.

Completed spans land in a bounded ring buffer (oldest fall off — tracing a
long-lived server never grows without bound) and are drained by the
exporters in :mod:`paddle_tpu.trace.export`. Sampling is counter-based and
deterministic (no RNG): with ``sample_rate=r``, an accumulator keeps
exactly the fraction ``r`` of ROOT spans, and an unsampled root suppresses
its entire subtree — children cost one thread-local check, nothing is
recorded.

Levels (the ``--trace_level`` flag / ``trace.enable(level=...)``):
  0  nothing kept — a scoped ``span()`` is one profiler annotation
     (below), every other call a no-op;
  1  span tracing: executor compile/run, serving request/queue/execute,
     trainer iterations;
  2  per-op debug: ``Executor.run`` additionally switches to the
     interpret-mode path (op-by-op host dispatch with per-op spans,
     output stats, and located NaN/Inf diagnosis).

A scoped span is also a profiler annotation: ``span(name, **attrs)``
opens a ``jax.profiler.TraceAnnotation(name, **attrs)`` round the same
region at EVERY level. With no profiler session that records nothing
(about a microsecond a span); under one (``jax.profiler.start_trace`` on
a live server or trainer) the program's spans sit in the xplane's host
plane beside the device ops, on the profiler's clock. ``record()``
(already-timed regions, which is what the level-2 per-op spans are) and
``start_span()`` (detached request spans) stay ring-buffer only.

Cross-process context: ``Tracer.inject()`` renders the current (or a
given) span as a W3C ``traceparent`` header value and
``Tracer.extract()`` parses one back into a :class:`SpanContext` usable
as ``parent=`` — the seam the serving fleet uses to carry ONE trace id
across router attempt -> HTTP hop -> remote replica. Trace ids are
128-bit random (globally unique without coordination, never a
per-process counter) and span ids carry a per-process salt, so journals
from N processes stitch without collisions
(``tools/trace_summary.py --distributed``).
"""
from __future__ import annotations

import itertools
import secrets
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

# Default ring-buffer capacity: generous for a debug session, bounded for
# a long-lived traced server (at ~200 B/span this is ~3 MB).
DEFAULT_CAPACITY = 16384


def _new_trace_id() -> int:
    """Globally-unique 128-bit trace id (W3C forbids all-zero)."""
    return secrets.randbits(128) | 1


class SpanContext:
    """A span reference without the span — what ``extract()`` returns
    for a parent living in ANOTHER process. Carries exactly the two
    fields ``start_span(parent=...)``/``record(parent=...)`` read, so a
    remote parent and a local one are interchangeable."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: int, span_id: int):
        self.trace_id = int(trace_id)
        self.span_id = int(span_id)

    def __repr__(self):
        return (f"SpanContext(trace={self.trace_id:032x}, "
                f"span={self.span_id:016x})")


class Span:
    """One named, timed region. ``start``/``end`` are seconds on the
    tracer's monotonic clock (``perf_counter`` relative to the tracer's
    epoch); ``attrs`` is a plain JSON-safe dict."""

    __slots__ = ("name", "span_id", "parent_id", "trace_id", "start",
                 "end", "attrs", "thread", "_tracer")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 trace_id: int, start: float, thread: int, tracer):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.trace_id = trace_id
        self.start = start
        self.end: Optional[float] = None
        self.attrs: Dict[str, object] = {}
        self.thread = thread
        self._tracer = tracer

    # -- attribute plane ---------------------------------------------------
    def set_attr(self, key: str, value) -> "Span":
        self.attrs[key] = value
        return self

    def set_attrs(self, **kv) -> "Span":
        self.attrs.update(kv)
        return self

    @property
    def duration(self) -> Optional[float]:
        return None if self.end is None else self.end - self.start

    def finish(self, **attrs) -> None:
        """End a detached span (context-managed spans end themselves)."""
        if attrs:
            self.attrs.update(attrs)
        if self._tracer is not None:
            self._tracer._end_span(self)

    def to_dict(self) -> dict:
        return {"name": self.name, "span_id": self.span_id,
                "parent_id": self.parent_id, "trace_id": self.trace_id,
                "start_s": self.start, "end_s": self.end,
                "duration_s": self.duration, "thread": self.thread,
                "attrs": dict(self.attrs)}

    def __repr__(self):
        dur = f"{self.duration * 1e3:.3f}ms" if self.end is not None \
            else "open"
        return (f"Span({self.name!r}, id={self.span_id}, "
                f"parent={self.parent_id}, {dur})")


class _Scope:
    """What ``Tracer.span`` returns: the profiler annotation and, at
    level >= 1, the ring-buffer span of one region. The annotation opens
    first and closes last, so it contains the span by microseconds."""

    __slots__ = ("_tracer", "_name", "_attrs", "_annotation", "_span",
                 "_pushed")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> Optional[Span]:
        self._annotation = TraceAnnotation(self._name, **self._attrs)
        self._annotation.__enter__()
        self._pushed = self._tracer.enabled
        self._span = self._tracer.start_span(self._name, **self._attrs) \
            if self._pushed else None
        return self._span

    def __exit__(self, *exc) -> None:
        if self._pushed:
            self._tracer._pop(self._span)
        self._annotation.__exit__(*exc)


class Tracer:
    """Span factory + bounded completed-span buffer.

    One process-global instance (``get_tracer()``) serves the whole
    stack; tests construct private ones. All public methods are safe to
    call with tracing disabled — they degrade to no-ops returning None.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 sample_rate: float = 1.0, level: int = 0):
        self.level = int(level)
        self.capacity = int(capacity)
        self.sample_rate = float(sample_rate)
        self._buf: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        # span ids: per-process random salt in the high bits + a counter
        # in the low 33, so ids from different processes never collide
        # when their journals are stitched by trace id
        self._span_salt = secrets.randbits(30) << 33
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._sample_acc = 0.0
        self._epoch = time.perf_counter()
        # wall-clock anchor so exports can place spans in absolute time
        self.epoch_unix = time.time()
        self.dropped = 0  # spans suppressed by sampling (roots only)

    # -- state -------------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self.level > 0

    def configure(self, level: Optional[int] = None,
                  sample_rate: Optional[float] = None,
                  capacity: Optional[int] = None) -> "Tracer":
        if level is not None:
            self.level = int(level)
        if sample_rate is not None:
            self.sample_rate = float(sample_rate)
        if capacity is not None and int(capacity) != self.capacity:
            self.capacity = int(capacity)
            with self._lock:
                self._buf = deque(self._buf, maxlen=self.capacity)
        return self

    def _now(self) -> float:
        return time.perf_counter() - self._epoch

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def current_span(self) -> Optional[Span]:
        """Innermost open span on THIS thread (None outside any span or
        under an unsampled root)."""
        st = self._stack()
        return st[-1] if st else None

    def _sampled(self) -> bool:
        """Deterministic counter-based root sampling: keeps exactly the
        configured fraction, no RNG."""
        if self.sample_rate >= 1.0:
            return True
        with self._lock:
            self._sample_acc += self.sample_rate
            if self._sample_acc >= 1.0:
                self._sample_acc -= 1.0
                return True
            self.dropped += 1
            return False

    # -- span lifecycle ----------------------------------------------------
    def start_span(self, name: str, parent: Optional[Span] = None,
                   detached: bool = False, **attrs) -> Optional[Span]:
        """Open a span. Context flows from ``parent`` when given, else
        from this thread's innermost open span. Detached spans skip the
        thread-local stack (cross-thread lifetimes) and must be ended via
        ``span.finish()``. Returns None when tracing is off or the root
        is sampled out."""
        if not self.enabled:
            return None
        if parent is None and not detached:
            st = self._stack()
            if st:
                parent = st[-1]
                if parent is None:  # inside an unsampled subtree
                    st.append(None)
                    return None
        if parent is None and not self._sampled():
            if not detached:
                self._stack().append(None)  # suppress the subtree
            return None
        trace_id = parent.trace_id if parent is not None \
            else _new_trace_id()
        sp = Span(name, self._span_salt | next(self._ids),
                  parent.span_id if parent is not None else None,
                  trace_id, self._now(), threading.get_ident(), self)
        if attrs:
            sp.attrs.update(attrs)
        if not detached:
            self._stack().append(sp)
        return sp

    def _end_span(self, sp: Span) -> None:
        if sp.end is not None:
            return  # idempotent: double-finish records once
        sp.end = self._now()
        with self._lock:
            self._buf.append(sp)

    def _pop(self, sp: Optional[Span]) -> None:
        st = self._stack()
        if st:
            top = st.pop()
            if top is not None:
                self._end_span(top)
        elif sp is not None:
            self._end_span(sp)

    def span(self, name: str, **attrs) -> _Scope:
        """Scoped span: ``with tracer.span("name", k=v) as sp:`` nests
        under the current thread's open span and yields the Span (None
        when disabled or sampled out) so the body can attach attributes.
        At every level the region is also one
        ``jax.profiler.TraceAnnotation(name, **attrs)``, so ``attrs`` are
        counts and short strings, never arrays."""
        return _Scope(self, name, attrs)

    def record(self, name: str, start: float, end: float,
               parent: Optional[Span] = None, **attrs) -> Optional[Span]:
        """Record an already-timed region (``start``/``end`` from
        ``perf_counter``) as a completed span — how batch-level work is
        attributed to each request riding the batch."""
        if not self.enabled:
            return None
        trace_id = parent.trace_id if parent is not None \
            else _new_trace_id()
        sp = Span(name, self._span_salt | next(self._ids),
                  parent.span_id if parent is not None else None,
                  trace_id, start - self._epoch,
                  threading.get_ident(), self)
        sp.attrs.update(attrs)
        sp.end = end - self._epoch
        with self._lock:
            self._buf.append(sp)
        return sp

    # -- cross-process context (W3C trace context) ------------------------
    def inject(self, span: Optional[Span] = None) -> Optional[str]:
        """Render ``span`` (default: this thread's current span) as a
        W3C ``traceparent`` header value, e.g.
        ``00-<32-hex trace id>-<16-hex span id>-01``. Returns None when
        tracing is off or there is no span to propagate — callers simply
        omit the header then."""
        sp = span if span is not None else self.current_span()
        if sp is None:
            return None
        return (f"00-{sp.trace_id & ((1 << 128) - 1):032x}"
                f"-{sp.span_id & ((1 << 64) - 1):016x}-01")

    @staticmethod
    def extract(header: Optional[str]) -> Optional[SpanContext]:
        """Parse a ``traceparent`` header into a :class:`SpanContext`
        usable as ``parent=``. An absent, malformed, all-zero, or
        explicitly-unsampled header yields None (start a fresh local
        trace) — this NEVER raises: a bad header from an arbitrary
        client must not fail the request carrying it."""
        if not header or not isinstance(header, str):
            return None
        parts = header.strip().split("-")
        if len(parts) < 4:
            return None
        ver, tid, sid, flags = parts[0], parts[1], parts[2], parts[3]
        if len(ver) != 2 or len(tid) != 32 or len(sid) != 16 \
                or len(flags) < 2:
            return None
        try:
            ver_i = int(ver, 16)
            tid_i = int(tid, 16)
            sid_i = int(sid, 16)
            flags_i = int(flags[:2], 16)
        except ValueError:
            return None
        if ver_i == 0xFF or tid_i == 0 or sid_i == 0:
            return None
        if not flags_i & 0x01:  # upstream sampled it out: fresh trace
            return None
        return SpanContext(tid_i, sid_i)

    # -- read side ---------------------------------------------------------
    def spans(self) -> List[Span]:
        """Snapshot of the completed-span ring buffer (oldest first)."""
        with self._lock:
            return list(self._buf)

    def drain(self) -> List[Span]:
        """Snapshot AND clear — exporters use this to checkpoint."""
        with self._lock:
            out = list(self._buf)
            self._buf.clear()
        return out

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


# ---------------------------------------------------------------------------
# Process-global tracer + module-level conveniences
# ---------------------------------------------------------------------------
_global_tracer = Tracer()

try:  # seed from the flag plane (--trace_level / PADDLE_TPU_TRACE_LEVEL)
    from ..flags import FLAGS as _FLAGS

    _global_tracer.configure(level=_FLAGS.trace_level,
                             sample_rate=_FLAGS.trace_sample_rate,
                             capacity=_FLAGS.trace_buffer)
except Exception:  # pragma: no cover - flags unavailable standalone
    pass


def get_tracer() -> Tracer:
    return _global_tracer


def enable(level: int = 1, sample_rate: float = 1.0,
           capacity: Optional[int] = None) -> Tracer:
    """Turn on the global tracer (idempotent). Level 1 = span tracing,
    level 2 = additionally switch Executor.run to the per-op interpret
    path. Returns the tracer."""
    return _global_tracer.configure(level=level, sample_rate=sample_rate,
                                    capacity=capacity)


def disable() -> Tracer:
    return _global_tracer.configure(level=0)


def enabled() -> bool:
    return _global_tracer.enabled


def active_level() -> int:
    return _global_tracer.level


def span(name: str, **attrs):
    """Scoped span on the global tracer AND a ``jax.profiler.TraceAnnotation``
    (at every level, so ``jax.profiler.start_trace`` on a live process
    shows it beside the device ops): ``with trace.span("name", k=v) as
    sp:``."""
    return _global_tracer.span(name, **attrs)


def start_span(name: str, parent: Optional[Span] = None,
               detached: bool = False, **attrs) -> Optional[Span]:
    return _global_tracer.start_span(name, parent=parent,
                                     detached=detached, **attrs)


def record(name: str, start: float, end: float,
           parent: Optional[Span] = None, **attrs) -> Optional[Span]:
    return _global_tracer.record(name, start, end, parent=parent, **attrs)


def current_span() -> Optional[Span]:
    return _global_tracer.current_span()


def inject(span: Optional[Span] = None) -> Optional[str]:
    """``traceparent`` header for ``span`` (default: the current span)
    against the global tracer; None when there is nothing to carry."""
    return _global_tracer.inject(span)


def extract(header: Optional[str]) -> Optional[SpanContext]:
    """Parse a ``traceparent`` header into a parent handle (or None) —
    never raises on malformed input."""
    return Tracer.extract(header)
