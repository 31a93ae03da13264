"""Clocks and spans of the benchmark's own: the compile clock, and host
spans that are also written into the profiler's trace."""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Tuple


class CompileClock:
    """Seconds JAX spent in backend compilation (cache reads included)
    and how many programs it compiled, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.total = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.total += secs
            self.count += 1


class SpanLog:
    """Host spans by name, ``(start, end)`` on ``time.perf_counter``.
    While a profiler trace runs, each span is also a ``TraceAnnotation``,
    so that idle gaps of the device can be named after it."""

    def __init__(self):
        self.spans: Dict[str, List[Tuple[float, float]]] = {}
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t = time.perf_counter()
        try:
            yield
        finally:
            self.spans.setdefault(name, []).append((t, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def durations(self, name: str, since: float = 0.0,
                  until: float = float("inf")) -> List[float]:
        return [b - a for a, b in self.spans.get(name, ())
                if a >= since and b <= until]
