"""Host spans around each call into a layer of the program.

A traced run (``--trace 1``) records every span twice: in memory, on the
host's clock, for the per-layer metrics, and as a
``jax.profiler.TraceAnnotation`` in the profiler's trace, on the device
trace's clock, so that idle gaps on the device can be named by what the
host was doing. An untraced run records nothing, so its end-to-end
numbers pay for no span.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict
from typing import Dict, List, Tuple

import jax

PREFIX = "bench."


class Spans:
    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.records: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def _record(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            try:
                yield
            finally:
                t1 = time.perf_counter()
                with self._lock:
                    self.records[name].append((t0, t1))

    def span(self, name: str):
        """Context manager timing one call; a no-op while disabled."""
        if not self.enabled:
            return contextlib.nullcontext()
        return self._record(name)

    def step(self, n: int):
        """Marks one consumer step in the trace."""
        if not self.enabled:
            return contextlib.nullcontext()
        return jax.profiler.StepTraceAnnotation(PREFIX + "step", step_num=n)

    def within(self, name: str, t0: float, t1: float
               ) -> List[Tuple[float, float]]:
        """Spans of ``name`` that started inside [t0, t1]."""
        with self._lock:
            return [s for s in self.records.get(name, ()) if t0 <= s[0] <= t1]
