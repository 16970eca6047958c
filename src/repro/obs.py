"""Spans of the volume service's host work, on the profiler's clock.

``span(name, **attrs)`` times a block of host work twice over:

* it enters ``jax.profiler.TraceAnnotation(name, **attrs)``, so while a
  profiler session is active the span lands in the profiler's own trace,
  on the clock of the device's operations (outside a session this costs
  a fraction of a microsecond);
* it appends a ``Span`` record, timed by ``time.perf_counter``, to a
  bounded in-memory ring.  ``parent`` is the ``sid`` of the span that
  encloses it on the same thread, so a reader can subtract children and
  get each span's self time.

``mark(name, t0, t1, **attrs)`` records a span that no single block
covers (a request's time in the queue) in the ring only.  ``spans(t0,
t1)`` reads the records that overlap a window and says whether the ring
dropped any that did.

The serving path's span names are dotted (``engine.tick``,
``executor.fetch``, ...); ``docs/architecture.md`` lists each with what it
covers.  None is ``step``, ``submit`` or ``wait``: the benchmark's trace
reduction takes host spans of exactly those names as its own.
"""

from __future__ import annotations

import itertools
import math
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

# a 51 s window of bulk serving on a TPU v5e, with its warm-up and drain,
# records about 4,700 spans (9 a tick): 2**15 leaves room several times over
RING = 1 << 15


class Span(NamedTuple):
    sid: int
    name: str
    t0: float  # time.perf_counter() seconds
    t1: float
    parent: Optional[int]  # sid of the enclosing span on the same thread
    attrs: Dict[str, Any]


class Recorder:
    """A bounded ring of ``Span`` records with per-thread nesting."""

    def __init__(self):
        self._ring: deque = deque(maxlen=RING)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._dropped_t1: Optional[float] = None  # latest end among evicted records

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _add(self, rec: Span) -> None:
        with self._lock:
            ring = self._ring
            if len(ring) == ring.maxlen:
                t1 = ring[0].t1
                if self._dropped_t1 is None or t1 > self._dropped_t1:
                    self._dropped_t1 = t1
            ring.append(rec)

    def span(self, name: str, **attrs) -> "_Open":
        return _Open(self, name, attrs)

    def mark(self, name: str, t0: float, t1: float, **attrs) -> None:
        """Record [t0, t1] in the ring alone, with no parent: what it
        times started before the block that records it."""
        self._add(Span(next(self._ids), name, t0, t1, None, attrs))

    def spans(self, t0: float = -math.inf, t1: float = math.inf) -> Tuple[List[Span], bool]:
        """(records that overlap [t0, t1] in order of their end, whether
        the ring dropped any record that did)."""
        with self._lock:
            recs = [r for r in self._ring if r.t1 >= t0 and r.t0 <= t1]
            return recs, self._dropped_t1 is not None and self._dropped_t1 >= t0


class _Open:
    """One span while its block runs (``Recorder.span``)."""

    __slots__ = ("rec", "name", "attrs", "sid", "parent", "t0", "annotation")

    def __init__(self, rec: Recorder, name: str, attrs: Dict[str, Any]):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self) -> "_Open":
        self.annotation = TraceAnnotation(self.name, **self.attrs)
        self.annotation.__enter__()
        stack = self.rec._stack()
        self.parent = stack[-1] if stack else None
        self.sid = next(self.rec._ids)
        stack.append(self.sid)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self.rec._stack().pop()
        self.rec._add(Span(self.sid, self.name, self.t0, t1, self.parent, self.attrs))
        self.annotation.__exit__(*exc)


def self_times(records: List[Span]) -> Dict[int, float]:
    """Each record's duration less its recorded children's, by ``sid``."""
    own = {r.sid: r.t1 - r.t0 for r in records}
    for r in records:
        if r.parent in own:
            own[r.parent] -= r.t1 - r.t0
    return own


# the process's recorder: the serving path writes here, readers read here
RECORDER = Recorder()
span = RECORDER.span
mark = RECORDER.mark
spans = RECORDER.spans
