"""The harness's own spans around each call into the program.

A span is kept in memory on the host clock (``perf_counter_ns``) with
its attributes, and, in a traced run, also written into the profiler's
trace as a ``jax.profiler.TraceAnnotation`` of the same name, so the
trace reduction can say what the host was doing in each idle gap.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Iterator, List

import jax


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int
    attrs: Dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: List[Span] = []

    @contextlib.contextmanager
    def __call__(self, name: str, **attrs) -> Iterator[None]:
        note = (
            jax.profiler.TraceAnnotation(name)
            if self.annotate
            else contextlib.nullcontext()
        )
        start = time.perf_counter_ns()
        with note:
            yield
        self.records.append(Span(name, start, time.perf_counter_ns(), attrs))

    def named(self, name: str) -> List[Span]:
        return [s for s in self.records if s.name == name]
