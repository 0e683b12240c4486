"""Host spans the benchmark records around its calls into each layer.

Every span is kept in memory (name, start, end on ``time.perf_counter``,
attributes) and, when the profiler runs, also written into its trace as a
``TraceAnnotation`` of the same name, so the trace reduction can say what
the host was doing during each idle gap of the device.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Iterator, List


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    attrs: Dict

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Spans:
    """An in-memory span log; one per run."""

    def __init__(self):
        self.items: List[Span] = []
        self._annotation = None

    def annotate_with(self, annotation) -> None:
        """Also emit each span as ``annotation(name)`` (the profiler's)."""
        self._annotation = annotation

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[Span]:
        ctx = (self._annotation(name) if self._annotation
               else contextlib.nullcontext())
        s = Span(name, time.perf_counter(), float("nan"), attrs)
        with ctx:
            try:
                yield s
            finally:
                s.t1 = time.perf_counter()
                self.items.append(s)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.items if s.name == name]

    def total(self, name: str) -> float:
        return sum(s.seconds for s in self.named(name))
