"""Spans and counters of the program's own host work, on the profiler's
clock.

Recording is on exactly while a JAX profiler session is active
(``jax.profiler.TraceAnnotation.is_enabled()``); there is no other
switch. While it is on, :func:`span` writes a ``TraceAnnotation`` into
the profiler's trace, so the span lies on the same clock as the
device's events, and appends an :class:`Event` to a bounded in-memory
ring; :func:`count` appends a counter event tagged with the enclosing
span. While it is off, :func:`span` returns one shared no-op context
after that single check, and :func:`count` returns at once.

Spans nest by the thread's own stack: each event carries the id of the
span that encloses it (``parent_id``) and of the outermost one
(``call_id``; a root span is its own call). Times are
``time.perf_counter`` seconds.

The ring belongs to the process, like the profiler session that turns
it on. Read it after a session with :func:`events` and :func:`dropped`,
the only read API.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Iterable, Iterator, NamedTuple

from jax.profiler import TraceAnnotation

CAPACITY = 1 << 18  # events; a 51 s window of ~6,100 small calls fits twice


class Event(NamedTuple):
    """One span (``value`` None) or one counter (``span_id`` None, ``t0``
    == ``t1`` the time it was counted)."""

    name: str
    span_id: int | None
    parent_id: int | None
    call_id: int | None
    t0: float
    t1: float
    value: int | None = None


class Ring:
    """The newest ``capacity`` events; older ones are overwritten and
    counted by :meth:`dropped`."""

    def __init__(self, capacity: int) -> None:
        self._slots: list = [None] * capacity
        self._n = 0
        self._lock = threading.Lock()

    def append(self, event: Event) -> None:
        with self._lock:
            self._slots[self._n % len(self._slots)] = event
            self._n += 1

    def events(self) -> list[Event]:
        """Oldest first."""
        with self._lock:
            cap, n = len(self._slots), self._n
            if n <= cap:
                return self._slots[:n]
            i = n % cap
            return self._slots[i:] + self._slots[:i]

    def dropped(self) -> int:
        return max(0, self._n - len(self._slots))


_RING = Ring(CAPACITY)
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def recording() -> bool:
    """Whether spans and counters are recorded now."""
    return TraceAnnotation.is_enabled()


class _Span:
    __slots__ = ("name", "record", "span_id", "parent_id", "call_id", "t0", "t1", "_ann")

    def __init__(self, name: str, record: bool) -> None:
        self.name = name
        self.record = record

    def __enter__(self) -> "_Span":
        if self.record:
            stack = _stack()
            self.span_id = next(_ids)
            top = stack[-1] if stack else None
            self.parent_id = top.span_id if top else None
            self.call_id = top.call_id if top else self.span_id
            stack.append(self)
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        if self.record:
            self._ann.__exit__(*exc)
            _stack().pop()
            _RING.append(
                Event(self.name, self.span_id, self.parent_id, self.call_id,
                      self.t0, self.t1)
            )
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class _Off:
    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_OFF = _Off()


def span(name: str):
    """A context that records the block it encloses as span ``name``."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Span(name, True)


def timed(name: str) -> _Span:
    """Like :func:`span`, for a block the caller times itself: the
    context always reads the clock and gives ``seconds``, and while
    recording is on the span has that same start and end."""
    return _Span(name, TraceAnnotation.is_enabled())


def count(name: str, n: int) -> None:
    """Record counter ``name`` += ``n`` under the enclosing span."""
    if not TraceAnnotation.is_enabled():
        return
    stack = _stack()
    top = stack[-1] if stack else None
    t = time.perf_counter()
    _RING.append(
        Event(name, None, top.span_id if top else None,
              top.call_id if top else None, t, t, int(n))
    )


def each(name: str, items: Iterable) -> Iterator:
    """Yield ``items``, each ``next()`` of them under span ``name``."""
    it = iter(items)
    end = object()
    while True:
        with span(name):
            item = next(it, end)
        if item is end:
            return
        yield item


def events() -> list[Event]:
    """The recorded events, oldest first."""
    return _RING.events()


def dropped() -> int:
    """How many events the ring overwrote."""
    return _RING.dropped()
