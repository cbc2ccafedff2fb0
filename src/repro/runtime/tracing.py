"""Spans: the serving path's one host timer.

``with span(name, **counts) as sp:`` stamps the block's start and end on
``time.perf_counter()`` and, when the block exits (by raising too), keeps
one ``Span`` in a ring of the last ``RING`` spans this process closed.
``parent`` is the id of the innermost span open on the same thread when
this one opened, so a reader can take a span's self time: its duration
less the part its children cover.  ``counts`` are numbers the span
carries; the body may add to ``sp.counts`` before the block ends.

Each span also opens a ``jax.profiler.TraceAnnotation`` of the same name
(with the counts given at opening), so in a profiler trace it sits on its
thread's host line, on the same clock as the device's "XLA Ops".

Spans are always on; a closed span costs one or two microseconds of host
time.  The ring belongs to the process, so that a reader outside the
program (``bench/program_spans.py``) can take the spans of a run without
being handed the scheduler: ``spans()`` returns a copy, oldest first.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

__all__ = ["RING", "Span", "span", "spans"]

RING = 65_536        # spans kept: about 5 minutes of a serving loop that
#                      closes ~13 spans per ~66-115 ms iteration


class Span(NamedTuple):
    """One closed span; times are ``time.perf_counter()`` seconds."""

    id: int
    name: str
    t0: float
    t1: float
    parent: Optional[int]          # id of the enclosing open span
    counts: Dict[str, float]
    error: Optional[str]           # exception type name if the body raised

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class _Open(threading.local):
    def __init__(self):
        self.stack: List[int] = []     # ids of the spans open on a thread


_ring: collections.deque = collections.deque(maxlen=RING)   # plain tuples
_ids = itertools.count()
_open = _Open()


class span:
    """Context manager that times its block; see the module docstring."""

    __slots__ = ("name", "counts", "id", "parent", "t0", "t1", "_ann")

    def __init__(self, name: str, **counts):
        self.name = name
        self.counts = counts

    def __enter__(self) -> "span":
        stack = _open.stack
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.counts)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, typ, exc, tb) -> bool:
        self.t1 = time.perf_counter()
        self._ann.__exit__(typ, exc, tb)
        stack = _open.stack
        del stack[stack.index(self.id):]
        _ring.append((self.id, self.name, self.t0, self.t1, self.parent,
                      self.counts, None if typ is None else typ.__name__))
        return False

    @property
    def seconds(self) -> float:
        """The closed span's duration."""
        return self.t1 - self.t0


def spans() -> List[Span]:
    """A copy of the ring: the last ``RING`` closed spans, oldest first
    (children close, and so come, before their parents)."""
    return list(map(Span._make, list(_ring)))
