"""Structured trace spans: lightweight, bounded, reconstructable.

``span(name, **attrs)`` is a context manager that records one event per
exit into a process-wide **ring buffer** (``collections.deque(maxlen)``, so
a long-lived server keeps the most recent window and nothing grows).  Each
event carries:

  * monotonic timestamps (``perf_counter_ns``-based start + duration, µs),
  * a process-unique span id and its **parent id** (a thread-local stack,
    so nested spans — request → dispatch → bucket → kernel — reconstruct
    into a tree even across the stream's background-flush thread, which
    gets its own stack),
  * the caller's attributes (JSON-safe-coerced), plus any added mid-span
    via ``sp.set(...)`` — how the engine attaches "cache hit/miss" after
    the lookup resolves.

When tracing is disabled (the default) ``span()`` returns one shared
no-op context manager: the hot loop pays an attribute read and a branch.

Every live span is also a ``jax.profiler.TraceAnnotation`` named like the
span and carrying its ``span_id``, ``parent_id`` and the attributes given
at entry, so under ``jax.profiler.trace`` the host steps sit on the same
clock as the device timeline and join the ring-buffer events by id.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional

from jax.profiler import TraceAnnotation

from repro.obs import state

#: Default ring capacity — ~a few MB of events at worst, never more.
DEFAULT_CAPACITY = 8192

_ORIGIN_NS = time.perf_counter_ns()
_SEQ = itertools.count(1)
_EVENTS: Deque[dict] = deque(maxlen=DEFAULT_CAPACITY)
_TLS = threading.local()


def _stack() -> List["Span"]:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def _safe(v):
    """JSON-safe attribute value (numpy/jax scalars → python, else str)."""
    if isinstance(v, (int, float, str, bool)) or v is None:
        return v
    try:
        return v.item()
    except (AttributeError, ValueError):
        return str(v)


class _NullSpan:
    """The shared disabled-tracing fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def add(self, **amounts):
        return self


_NULL_SPAN = _NullSpan()


class Span:
    """One live span; use via ``with obs.span("serve.dispatch", ...):``."""

    __slots__ = ("name", "attrs", "id", "parent", "_t0", "_annotation")

    def __init__(self, name: str, attrs: Dict):
        self.name = name
        self.attrs = {k: _safe(v) for k, v in attrs.items()}
        self.id = next(_SEQ)
        self.parent: Optional[int] = None
        self._t0 = 0
        self._annotation = None

    def set(self, **attrs) -> "Span":
        """Attach attributes discovered mid-span (e.g. cache hit/miss)."""
        for k, v in attrs.items():
            self.attrs[k] = _safe(v)
        return self

    def add(self, **amounts) -> "Span":
        """Add to numeric attributes (absent ones start at 0)."""
        for k, v in amounts.items():
            self.attrs[k] = self.attrs.get(k, 0) + _safe(v)
        return self

    def __enter__(self) -> "Span":
        st = _stack()
        self.parent = st[-1].id if st else None
        st.append(self)
        if state.trace_on:
            self._annotation = TraceAnnotation(
                self.name, span_id=self.id, parent_id=self.parent or 0,
                **self.attrs)
            self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur_ns = time.perf_counter_ns() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        _EVENTS.append({
            "name": self.name,
            "id": self.id,
            "parent": self.parent,
            "ts_us": (self._t0 - _ORIGIN_NS) / 1e3,
            "dur_us": dur_ns / 1e3,
            "thread": threading.current_thread().name,
            "attrs": self.attrs,
        })
        return False


def span(name: str, **attrs):
    """A trace span (the shared no-op when tracing is disabled)."""
    if not state.trace_on:
        return _NULL_SPAN
    return Span(name, attrs)


def current_span():
    """The innermost span open on this thread (the shared no-op if none)."""
    st = _stack()
    return st[-1] if st else _NULL_SPAN


def trace_events() -> List[dict]:
    """The buffered events, oldest first (each is a JSON-safe dict)."""
    return list(_EVENTS)


def clear_trace() -> None:
    _EVENTS.clear()


def set_trace_capacity(capacity: int) -> None:
    """Re-bound the ring buffer (drops buffered events)."""
    global _EVENTS
    if capacity < 1:
        raise ValueError("trace capacity must be >= 1")
    _EVENTS = deque(maxlen=int(capacity))


def span_tree(events: Optional[List[dict]] = None) -> Dict[Optional[int],
                                                           List[dict]]:
    """Events grouped by parent id — the reconstruction helper tests and
    trace readers use to walk request → dispatch → kernel chains."""
    by_parent: Dict[Optional[int], List[dict]] = {}
    for ev in (trace_events() if events is None else events):
        by_parent.setdefault(ev["parent"], []).append(ev)
    return by_parent


__all__ = [
    "DEFAULT_CAPACITY", "Span", "span", "current_span",
    "trace_events", "clear_trace", "set_trace_capacity", "span_tree",
]
