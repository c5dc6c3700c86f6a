"""Host spans the harness records around its calls into the program, on
``time.perf_counter``. While a trace is being taken each span is also a
``jax.profiler.TraceAnnotation``, so the trace's idle gaps can be labelled
by what the host was doing."""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List


@dataclass
class Span:
    name: str
    t0: float
    t1: float
    info: Dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


class Recorder:
    def __init__(self):
        self.spans: List[Span] = []
        self.annotate = False          # True while the profiler runs

    @contextlib.contextmanager
    def span(self, name: str, **info):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        rec = Span(name, t0, t0, dict(info))
        try:
            yield rec
        finally:
            rec.t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.append(rec)

    def within(self, name: str, t0: float, t1: float,
               pred=None) -> List[Span]:
        """Spans named ``name`` that start and end inside [t0, t1]."""
        return [s for s in self.spans if s.name == name and s.t0 >= t0
                and s.t1 <= t1 and (pred is None or pred(s))]
