"""In-program spans: where the serving paths spend host time.

A process-wide recorder, **off by default**.  Off, a boundary reads no
clock, takes no lock and stores nothing: :func:`span` tests a
module-level boolean and hands back a shared no-op context manager, so
what remains is the call, its arguments and an empty enter and exit.
On, a boundary reads ``time.monotonic_ns()`` (the clock the
benchmark's client records use) and appends one plain tuple to a
bounded deque; appends are atomic under the GIL, so the hot path takes
no lock, and :func:`drain` makes the :class:`Span` objects.  The oldest
spans fall off once ``capacity`` is reached.

* :func:`span` — a context manager for a span that opens and closes on
  one thread; spans nest through a thread-local stack of open spans.
* :func:`record` — a span that starts on one thread and ends on another
  (a request waiting in a queue); it has no parent and no thread.
* :func:`now` — the clock when on, else ``None``: a site that stamps a
  start for :func:`record` later.
* :func:`enabled` — for a site that does extra work only when traced.

Spans a run records are read with :func:`drain`.  The benchmark puts
them on the device trace's clock (``bench/lib/spans.py``).
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time

DEFAULT_CAPACITY = 1 << 20


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int                   # time.monotonic_ns()
    end_ns: int
    id: int
    parent: int | None = None       # id of the enclosing span
    rid: int | None = None          # request id, where one request owns it
    attrs: dict = dataclasses.field(default_factory=dict)
    thread: int | None = None       # thread ident; None for record()

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


_on = False
_spans: collections.deque = collections.deque(maxlen=DEFAULT_CAPACITY)
_ids = itertools.count(1)
_local = threading.local()


def enable(capacity: int = DEFAULT_CAPACITY) -> None:
    """Start recording into an empty buffer of ``capacity`` spans."""
    global _on, _spans
    _spans = collections.deque(maxlen=capacity)
    _on = True


def disable() -> None:
    """Stop recording; what was recorded stays until :func:`drain`."""
    global _on
    _on = False


def drain() -> list[Span]:
    """Remove and return every recorded span, in the order they closed."""
    out = []
    while True:
        try:
            out.append(Span(*_spans.popleft()))
        except IndexError:
            return out


def enabled() -> bool:
    """Whether spans are being recorded."""
    return _on


def now() -> int | None:
    return time.monotonic_ns() if _on else None


def record(name: str, start_ns: int, end_ns: int, rid: int | None = None,
           **attrs) -> None:
    """Record a span whose ends were stamped on different threads."""
    if _on:
        _spans.append((name, start_ns, end_ns, next(_ids), None, rid, attrs,
                       None))


class _Off:
    """What :func:`span` returns while the recorder is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass

    def discard(self) -> None:
        pass


_OFF = _Off()


class _Open:
    __slots__ = ("name", "rid", "attrs", "start", "id", "parent", "keep")

    def __init__(self, name, rid, attrs):
        self.name, self.rid, self.attrs = name, rid, attrs
        self.keep = True

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self.start = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        end = time.monotonic_ns()
        _local.stack.pop()
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        if self.keep:
            _spans.append((self.name, self.start, end, self.id, self.parent,
                           self.rid, self.attrs, threading.get_ident()))
        return False

    def set(self, **attrs) -> None:
        """Add attributes known only after the span opened."""
        self.attrs.update(attrs)

    def discard(self) -> None:
        """Record nothing for this span (it turned out to do no work)."""
        self.keep = False


def span(name: str, rid: int | None = None, **attrs):
    """``with span("batcher.step") as s: ...`` — one span around the
    block, a child of the span open around it on this thread.  A block
    that raises records the span with the exception's type as
    ``error``."""
    if not _on:
        return _OFF
    return _Open(name, rid, attrs)
