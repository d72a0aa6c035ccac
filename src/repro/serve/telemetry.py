"""In-program spans and counters of the serving loop, on the profiler's clock.

One process-wide :class:`Recorder` (``recorder()``) is always on.  A span

    with telemetry.span("sched.step") as attrs:
        attrs["iteration"] = i

opens a ``jax.profiler.TraceAnnotation`` of the same name, so it lands in a
profiler trace beside the device's programs, and on exit appends one
:class:`Span` record to a bounded in-memory ring.  Times come from
``time.perf_counter``, the clock of the scheduler loop and its clients.

A record keeps its ``parent``: the id of the span that was open around it on
the same thread, so the span that caused a span is known.  A span opened
without a ``uid`` takes its parent's, so the spans of one request share its
uid.  ``count(key)`` adds to the attrs of the nearest open span that carries
``key``, which lets the layers below roll their counts up into the
iteration's ``sched.step`` span.  A span whose name ends in ``.wait`` marks a
blocking device-to-host read and counts itself into ``syncs`` that way.

Read the ring with ``recorder().spans(t0, t1)``; ``self_times`` gives a
span's duration less what its ``.wait`` descendants cover.  DESIGN.md §14 lists
the spans the serving layers emit.
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Recorder", "recorder", "span", "count", "traced",
           "self_times"]

RING = 65536          # records kept; the oldest fall off first
WAIT = ".wait"


class Span(NamedTuple):
    id: int
    name: str
    t0: float
    t1: float
    parent: Optional[int]        # id of the enclosing span, same thread
    uid: Optional[int]           # the request this span worked for
    attrs: Optional[Dict[str, Any]]


class _Open:
    """One span while it is open; ``__enter__`` yields its attrs dict."""
    __slots__ = ("rec", "name", "uid", "id", "parent", "attrs", "t0", "ann",
                 "stack")

    def __init__(self, rec: "Recorder", name: str, uid: Optional[int]):
        self.rec, self.name, self.uid = rec, name, uid

    def __enter__(self) -> Dict[str, Any]:
        rec = self.rec
        stack = rec._stack()
        self.stack = stack
        top = stack[-1] if stack else None
        self.parent = top.id if top is not None else None
        if self.uid is None and top is not None:
            self.uid = top.uid
        self.id = next(rec._ids)
        self.attrs = {}
        stack.append(self)
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self.attrs

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.stack.pop()
        if self.name.endswith(WAIT):
            self.rec.count("syncs")
        self.rec._ring.append(Span(self.id, self.name, self.t0, t1,
                                   self.parent, self.uid, self.attrs or None))


class Recorder:
    """A bounded ring of finished spans plus each thread's open-span stack."""

    def __init__(self, maxlen: int = RING):
        self._ring: deque = deque(maxlen=maxlen)
        self._ids = itertools.count()
        self._local = threading.local()

    def _stack(self) -> List[_Open]:
        s = getattr(self._local, "stack", None)
        if s is None:
            s = self._local.stack = []
        return s

    def span(self, name: str, uid: Optional[int] = None) -> _Open:
        return _Open(self, name, uid)

    def count(self, key: str, n: int = 1) -> None:
        """Add ``n`` to ``key`` of the innermost open span on this thread
        whose attrs carry ``key``; nothing where none does."""
        for o in reversed(self._stack()):
            if key in o.attrs:
                o.attrs[key] += n
                return

    def spans(self, t0: float = float("-inf"),
              t1: float = float("inf")) -> List[Span]:
        """Finished spans that started in ``[t0, t1)``, oldest end first."""
        return [s for s in list(self._ring) if t0 <= s.t0 < t1]

    def clear(self) -> None:
        self._ring.clear()


_RECORDER = Recorder()


def recorder() -> Recorder:
    return _RECORDER


span = _RECORDER.span
count = _RECORDER.count


def traced(name: str):
    """Decorator: run each call of the function inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def self_times(roots: Iterable[Span], records: Iterable[Span]
               ) -> List[float]:
    """Each root's duration less the time its ``.wait`` descendants among
    ``records`` cover (their union, clipped to the root): the host's own
    work in it, with the blocking device-to-host reads taken out."""
    kids: Dict[int, List[Span]] = {}
    for s in records:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append(s)
    out = []
    for root in roots:
        waits, todo = [], [root.id]
        while todo:
            for s in kids.get(todo.pop(), ()):
                if s.name.endswith(WAIT):
                    waits.append((max(s.t0, root.t0), min(s.t1, root.t1)))
                else:
                    todo.append(s.id)
        covered, end = 0.0, root.t0
        for a, b in sorted(waits):
            a = max(a, end)
            if b > a:
                covered += b - a
                end = b
        out.append((root.t1 - root.t0) - covered)
    return out
