"""Program spans: named, timed regions of the host's work, on the
profiler's clock.

``span(name, **attrs)`` marks a region where the program spends host
time: a commit's host snapshot, a flush's fsync, a tick's wait on the
device.  It records only while the JAX profiler is collecting (the
benchmark's ``--trace 1``, ``jax.profiler.start_trace`` or
``jax.profiler.trace``, or a profiler server), and then does two things:

* it opens a ``jax.profiler.TraceAnnotation(name, **attrs)``, so the
  span lands in the same ``.xplane.pb`` as the device's operations and
  an idle gap on the device can be put down to the host work open over
  it;
* on exit it appends one ``Span`` to a bounded in-memory ring, read
  with ``spans(t0, t1)`` on the ``time.perf_counter`` clock.

There is no flag: with the profiler off a span costs one check, and
``span`` returns a shared do-nothing context.

A span's ``parent`` is the span open around it on the same thread; work
handed to another thread through ``carry`` takes the span open at the
hand-off as its parent, so a flush pipeline's writes belong to the
commit that started them.  A span inherits the identifying attributes
``IDS`` (``step``, ``tick``, ``rid``) of its parent unless it sets its
own.  Attributes given as None are left out.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
from jax._src.lib import _profiler

#: True while any profiler session is collecting
_collecting = _profiler.TraceMe.is_enabled

#: attributes that say which unit of work a span belongs to
IDS = ("step", "tick", "rid")

#: records the ring keeps; a serving window of 45 s writes a few thousand
LOG_RECORDS = 1 << 16


class Span(NamedTuple):
    """One finished span: ``t0``/``t1`` are ``time.perf_counter``
    readings; ``parent`` is the ``id`` of the span it ran inside."""
    id: int
    name: str
    t0: float
    t1: float
    parent: Optional[int]
    thread: str
    attrs: Dict[str, Any]


class SpanLog:
    """A bounded ring of finished spans that any thread may append to.
    When full, each append drops the oldest record and counts it."""

    def __init__(self, maxlen: int = LOG_RECORDS):
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self.dropped = 0
        self._dropped_t1 = float("-inf")    # latest end among the dropped

    def append(self, rec: Span):
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
                self._dropped_t1 = max(self._dropped_t1, self._ring[0].t1)
            self._ring.append(rec)

    def spans(self, t0: float, t1: float) -> Optional[List[Span]]:
        """The spans that ran wholly inside ``[t0, t1]``, in the order they
        ended; None when the ring dropped a span that ended at or after
        ``t0``, since the list could then miss some."""
        with self._lock:
            if self._dropped_t1 >= t0:
                return None
            return [s for s in self._ring if t0 <= s.t0 and s.t1 <= t1]


_log = SpanLog()
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class _Off:
    """The span returned while the profiler is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def set(self, **attrs):
        pass


_OFF = _Off()


class _Open:
    """A span being recorded.  ``set`` adds attributes known only once
    the work is done; they reach the log, not the profiler's event."""
    __slots__ = ("name", "attrs", "id", "parent", "t0", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = {k: v for k, v in attrs.items() if v is not None}

    def set(self, **attrs):
        self.attrs.update(attrs)

    def __enter__(self):
        stack = _stack()
        up = stack[-1] if stack else None
        self.parent = None if up is None else up.id
        if up is not None:
            for k in IDS:
                if k in up.attrs and k not in self.attrs:
                    self.attrs[k] = up.attrs[k]
        self.id = next(_ids)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        _stack().pop()
        self._ann.__exit__(*exc)
        _log.append(Span(self.id, self.name, self.t0, t1, self.parent,
                         threading.current_thread().name, self.attrs))
        return False


def span(name: str, /, **attrs):
    """A context manager that records ``name`` while the profiler runs
    (see the module's docstring); with it off, one check."""
    if not _collecting():
        return _OFF
    return _Open(name, attrs)


def carry(fn: Callable) -> Callable:
    """``fn``, to be run on another thread with the span open here as the
    parent of the spans it opens.  With the profiler off, or no span
    open, ``fn`` itself."""
    if not _collecting():
        return fn
    stack = _stack()
    if not stack:
        return fn
    up = stack[-1]

    def carried(*args, **kwargs):
        st = _stack()
        st.append(up)
        try:
            return fn(*args, **kwargs)
        finally:
            st.pop()
    return carried


def spans(t0: float, t1: float) -> Optional[List[Span]]:
    """The recorded spans wholly inside ``[t0, t1]`` (perf_counter), or
    None when the ring dropped records there."""
    return _log.spans(t0, t1)
