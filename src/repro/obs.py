"""Host spans: one ``jax.profiler.TraceAnnotation`` per span, plus a plain
per-owner tally of its host seconds and count.

A span lands on the profiler's clock beside the device ops when a trace is
being taken, and always adds to its owner's tally (``MapperEngine.stats()``
reports the engine's and its scheduler's).  With no profiler running a span
costs one TraceMe check and two ``perf_counter`` calls; there is no switch.

    spans = {}
    with span("engine.serve", spans) as s:
        s.set_metadata(lanes=12, nmax=64, tick=7)
        ...
    spans["engine.serve"]    # {"seconds": ..., "count": 1}
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

__all__ = ["span"]


class span:
    """Context manager: a named host span, tallied into ``totals``."""

    __slots__ = ("name", "totals", "_trace", "_t0")

    def __init__(self, name: str, totals: dict):
        self.name = name
        self.totals = totals

    def __enter__(self) -> "span":
        self._trace = TraceAnnotation(self.name)
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set_metadata(self, **meta) -> None:
        """Attach ``meta`` to the span's trace event; nothing is built
        unless a trace is being taken."""
        if TraceAnnotation.is_enabled():
            self._trace.set_metadata(**meta)

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._trace.__exit__(*exc)
        tally = self.totals.get(self.name)
        if tally is None:
            tally = self.totals[self.name] = {"seconds": 0.0, "count": 0}
        tally["seconds"] += dt
        tally["count"] += 1
