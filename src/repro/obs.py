"""Host spans of the program's own phases, on the profiler's trace and on
``time.perf_counter``.

``Tracer.span(name, **counts)`` opens a ``jax.profiler.TraceAnnotation``
carrying the counts as arguments (it records nothing unless a profiler
session runs, and then lands on the same clock as the device's
operations), times the span, adds its duration and counts to per-name
totals, and hands it to ``sink`` when one is set. A span whose body
raises counts nothing. The serving engine's per-phase counters are these
totals; device work is scoped separately, by ``jax.named_scope`` in the
model (``attn.*``, ``moe.*``, ``lm.*``)."""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Optional

import jax

# sink(name, t0, t1, counts): t0/t1 on time.perf_counter
Sink = Callable[[str, float, float, Dict[str, int]], None]


class Tracer:
    def __init__(self, sink: Optional[Sink] = None):
        self.sink = sink
        # name -> {"spans": n, "seconds": s, <count>: sum, ...}
        self.totals: Dict[str, Dict[str, float]] = {}

    @contextlib.contextmanager
    def span(self, name: str, **counts: int):
        with jax.profiler.TraceAnnotation(name, **counts):
            t0 = time.perf_counter()
            yield
            t1 = time.perf_counter()
        tot = self.totals.setdefault(name, {"spans": 0, "seconds": 0.0})
        tot["spans"] += 1
        tot["seconds"] += t1 - t0
        for k, v in counts.items():
            tot[k] = tot.get(k, 0) + v
        if self.sink is not None:
            self.sink(name, t0, t1, counts)

    def total(self, name: str, key: str):
        """The sum of ``key`` over the spans named ``name`` (``spans``:
        how many, ``seconds``: their time, else a count)."""
        return self.totals.get(name, {}).get(key, 0)
