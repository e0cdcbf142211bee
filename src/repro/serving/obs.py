"""Spans: named, timed intervals of the engine's host work, always on.

``span(name, **attrs)`` is a context manager.  It times its block on
``time.perf_counter()`` and records ``Span(id, parent, name, t0, t1,
attrs)`` into a bounded ring that keeps the newest 65,536 spans; ``parent``
is the span open around it on the same thread.  The block also runs inside
``jax.profiler.TraceAnnotation(name)``, so under a profiler session the span
lands on the trace's host plane, on the device ops' clock.  Only the name
goes to the profiler: the attrs (counts at the span's boundary) stay in the
ring, and may be added to while the span is open (``sp.attrs[k] = v``).

``totals()`` keeps each name's running count and seconds since the process
started: the operator's cumulative view, which the ring's bound never cuts.

There is no switch.  A span costs two clock reads, a deque append and an
inactive ``TraceMe``: about 3 µs on a TPU v5e host, and an engine step
opens four or five.  One module-level ``TRACER`` serves every engine of
the process and outlives them, so spans can be read after an engine is
freed.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax


class Span(NamedTuple):
    id: int
    parent: Optional[int]          # id of the span open around it, or None
    name: str
    t0: float                      # perf_counter seconds
    t1: float
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class _Open:
    """A span being timed."""
    __slots__ = ("_tracer", "_ann", "id", "parent", "name", "t0", "attrs")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Open":
        stack = self._tracer._stack()
        self.parent = stack[-1] if stack else None
        self.id = next(self._tracer._ids)
        stack.append(self.id)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._tracer._stack().pop()
        self._tracer._close(Span(self.id, self.parent, self.name, self.t0,
                                 t1, self.attrs))
        return False


class Tracer:
    """The span ring, the per-thread stacks of open spans, and the
    per-name totals."""

    def __init__(self, maxlen: int = 65536):
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._totals: Dict[str, List] = {}

    def span(self, name: str, **attrs) -> _Open:
        return _Open(self, name, attrs)

    def _stack(self) -> List[int]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _close(self, sp: Span) -> None:
        self._ring.append(sp)
        with self._lock:
            tot = self._totals.setdefault(sp.name, [0, 0.0])
            tot[0] += 1
            tot[1] += sp.t1 - sp.t0

    def totals(self) -> Dict[str, Tuple[int, float]]:
        """name -> (spans closed, seconds) over the process's life."""
        with self._lock:
            return {k: (n, s) for k, (n, s) in self._totals.items()}

    def spans(self, name: Optional[str] = None,
              since: Optional[float] = None,
              until: Optional[float] = None) -> List[Span]:
        """The ring's spans in the order they closed, named ``name`` (any
        where None), that start at or after ``since`` and end at or before
        ``until``."""
        return [s for s in list(self._ring)
                if (name is None or s.name == name)
                and (since is None or s.t0 >= since)
                and (until is None or s.t1 <= until)]

    def self_time(self, span: Span,
                  among: Optional[Iterable[Span]] = None) -> float:
        """``span``'s seconds less what its children cover (the children
        are looked for in ``among``, the ring where None)."""
        kids = sorted((c.t0, c.t1) for c in
                      (list(self._ring) if among is None else among)
                      if c.parent == span.id)
        covered, end = 0.0, span.t0
        for t0, t1 in kids:
            t0 = max(t0, end)
            if t1 > t0:
                covered += t1 - t0
                end = t1
        return span.seconds - covered


TRACER = Tracer()
span = TRACER.span
