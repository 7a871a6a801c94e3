"""Spans of the serving path: one timed region, read on two clocks.

``Span(stats, counter, name, **meta)`` opens a ``jax.profiler.TraceAnnotation``
named ``name`` (keyword metadata become the event's stats, e.g. ``rid``,
``blk``), and on exit adds the elapsed ``time.perf_counter()`` seconds to
``stats.<counter>``. So every span has a counter that holds its total when
the profiler is off, and lies on the device trace's clock when it is on.
There is no switch: off, a span costs about a microsecond.

Every span name starts with ``serve.``. Each counter has one writer thread
(the run loop, one DMA stream, or the router's monitor), so the add needs
no lock.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

__all__ = ["Span"]


class Span:
    """``with Span(stats, "decode_time", "serve.decode", rows=8): ...``.

    ``track``: an object whose ``phase`` attribute becomes ``(name, start)``
    on entry — how the engine names the loop phase that is open."""

    __slots__ = ("_stats", "_counter", "_name", "_track", "_ann", "_t0")

    def __init__(self, stats, counter: str, name: str, *, track=None,
                 **meta) -> None:
        self._stats = stats
        self._counter = counter
        self._name = name
        self._track = track
        self._ann = TraceAnnotation(name, **meta)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        if self._track is not None:
            self._track.phase = (self._name, self._t0)
        return self

    def __exit__(self, *exc) -> None:
        elapsed = time.perf_counter() - self._t0
        setattr(self._stats, self._counter,
                getattr(self._stats, self._counter) + elapsed)
        self._ann.__exit__(*exc)
