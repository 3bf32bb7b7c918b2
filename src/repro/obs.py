"""Profiler spans at the program's layer boundaries, and a count of the
interpreter's full collections.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: a host span
written into the profiler's own trace, on the clock of the device's ops,
so an idle gap on the device can be charged to the host stage that was
running.  It records only while a profiler session runs; otherwise it
costs about a microsecond.  Names start with ``xtime.``; ``meta``
lands in the trace as the event's stats.  No span is opened per request.

``GC2`` counts the interpreter's generation-2 (full) collections and
their seconds, and opens an ``xtime.gc`` span over each, while at least
one holder (a ``ClusterServer``) is open.
"""

from __future__ import annotations

import gc
import threading
import time


def span(name: str, **meta):
    """A profiler span: ``with span("xtime.score.pad"): ...``."""
    # lazy: score/pipeline.py stays numpy-only at import
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **meta)


class _FullCollections:
    """Generation-2 collections, through one ``gc.callbacks`` entry per
    process that stays installed while any holder is open."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._holders = 0
        self._running = None  # (span, start) of the pass under way
        self.passes = 0
        self.seconds = 0.0

    def hold(self) -> None:
        with self._lock:
            if self._holders == 0:
                gc.callbacks.append(self._on_gc)
            self._holders += 1

    def release(self) -> None:
        with self._lock:
            self._holders -= 1
            if self._holders == 0:
                gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            s = span("xtime.gc")
            s.__enter__()
            self._running = (s, time.perf_counter())
        elif self._running is not None:
            s, t0 = self._running
            self._running = None
            s.__exit__(None, None, None)
            self.passes += 1
            self.seconds += time.perf_counter() - t0


GC2 = _FullCollections()
