"""The cyclic collector, counted by the daemon itself.

Every Python thread of the daemon stands still for a collection, and a
full one over a heap of tens of thousands of pod objects takes a third
of a second: it is the served path's p95.  One ``gc.callbacks`` entry,
installed at daemon start, adds each collection to
``scheduler_gc_pause_seconds_total{generation}`` and
``scheduler_gc_collections_total{generation}``, keeps the longest in
``scheduler_gc_pause_max_seconds``, and makes every full collection the
host event ``kt.gc2`` of a live profiler session, so the stall is an
event in the trace and not a deduction from it.  Two clock reads per
collection; the interpreter never runs two collections at once, so the
state needs no lock.
"""

from __future__ import annotations

import gc
import time

from kubernetes_tpu.utils import metrics, trace

FULL_GENERATION = 2


class GcWatch:
    """The ``gc.callbacks`` entry (phases ``start`` and ``stop``)."""

    def __init__(self) -> None:
        self._started: float | None = None
        self._longest = 0.0
        self._annotation = None
        generations = range(len(gc.get_threshold()))
        self._seconds = [metrics.GC_PAUSE_SECONDS.labels(generation=str(g))
                         for g in generations]
        self._collections = [
            metrics.GC_COLLECTIONS.labels(generation=str(g))
            for g in generations]

    def __call__(self, phase: str, info: dict) -> None:
        generation = info["generation"]
        if phase == "start":
            if generation == FULL_GENERATION:
                self._annotation = trace.annotation("gc2")
                self._annotation.__enter__()
            self._started = time.perf_counter()
            return
        if self._started is None:     # installed inside a collection
            return
        took = time.perf_counter() - self._started
        self._started = None
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        self._seconds[generation].inc(took)
        self._collections[generation].inc()
        if took > self._longest:
            self._longest = took
            metrics.GC_PAUSE_MAX.set(took)


_installed: GcWatch | None = None


def install() -> GcWatch:
    """Install the process's one watch (a second call returns it)."""
    global _installed
    if _installed is None:
        _installed = GcWatch()
        gc.callbacks.append(_installed)
    return _installed

