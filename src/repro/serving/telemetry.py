"""Host spans of the serving engine, kept in memory and in profiler traces.

``RECORDER.span(name, **attrs)`` times a block of host code on
``time.perf_counter()`` and keeps it as a ``Span`` in a bounded ring, so a
caller can read what each engine tick did without a profiler. The same
block also enters ``jax.profiler.TraceAnnotation(name)``: in any
``jax.profiler`` trace the span sits on the device trace's own clock,
beside the device ops it launched or waited for.

The recorder is process-wide, as a metrics registry is: the engine writes
to ``RECORDER`` and readers take ``RECORDER.spans``. Recording is always
on; a span costs two clock reads, a profiler annotation and one append.
``ContinuousBatcher.step`` opens ``serve.step`` with the engine's id
(from ``ENGINE_IDS``, so several engines in one process stay apart) and
its tick index, and the spans it opens inside inherit that index
(docs/serving.md, "Tracing", lists every span and counter). A span's
parent is the innermost span open in the process, so spans nest as
intended when engines are stepped from one thread.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import time
from typing import Deque, Iterator, List, Optional, Tuple

import jax

MAXLEN = 65_536
ENGINE_IDS = itertools.count(1)   # one per ContinuousBatcher, from 1


@dataclasses.dataclass(frozen=True)
class Span:
    id: int                   # sequence number, in the order spans opened
    name: str
    t0: float                 # time.perf_counter() seconds
    t1: float
    parent: Optional[int]     # id of the enclosing span, None at the root
    tick: Optional[int]       # engine tick index, shared with the parent's
    attrs: dict


class Recorder:
    """Nested host spans in a ring of at most ``maxlen``: the oldest fall
    out first. Spans are appended as they close, so a child precedes its
    parent in ``spans``."""

    def __init__(self, maxlen: int = MAXLEN) -> None:
        self.spans: Deque[Span] = collections.deque(maxlen=maxlen)
        self._open: List[Tuple[int, Optional[int]]] = []   # (id, tick)
        self._next_id = 0

    @contextlib.contextmanager
    def span(self, name: str, tick: Optional[int] = None,
             **attrs) -> Iterator[None]:
        sid = self._next_id
        self._next_id += 1
        parent, parent_tick = self._open[-1] if self._open else (None, None)
        if tick is None:
            tick = parent_tick
        self._open.append((sid, tick))
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                t1 = time.perf_counter()
                self._open.pop()
                self.spans.append(Span(sid, name, t0, t1, parent, tick,
                                       attrs))


RECORDER = Recorder()
