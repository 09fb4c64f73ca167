"""The cyclic collector of the daemon: counted, and kept off the
long-lived heap.

Every Python thread of the daemon stands still for a collection, and a
full one walks the whole oldest generation: with tens of thousands of
resident pods (a dozen tracked, acyclic objects each) that took a third
of a second every two seconds, and it was the served path's p95.

**Counting.**  One ``gc.callbacks`` entry, installed at daemon start,
adds each collection to ``scheduler_gc_pause_seconds_total{generation}``
and ``scheduler_gc_collections_total{generation}``, keeps the longest in
``scheduler_gc_pause_max_seconds``, and makes every full collection the
host event ``kt.gc2`` of a live profiler session.  The interpreter never
runs two collections at once, so the state needs no lock.

**Tenuring**, from ``GcWatch.baseline()`` on (start-up over: the
daemon's entry point hands it to ``ConfigFactory.run``; nothing else
calls it).  What survives a full collection was just proven reachable;
the same entry's ``stop`` phase moves it to CPython's permanent
generation (``gc.freeze()``), so the next full collection walks only
what was promoted since.  Reference counting still frees a tenured
object with its last reference (a retired pod, a delivered event), so
acyclic data comes back exactly when it did.  A tenured object that later joins an unreachable CYCLE is out
of the collector's sight, and a **major collection** bounds that: once
``gc.get_freeze_count()`` has doubled since the last one (the baseline
is the first), the ``stop`` phase un-tenures everything instead
(``gc.unfreeze()``), the interpreter's next full collection walks the
whole heap (host event ``kt.gc_major``), and its survivors are tenured
again.  The rule reads what the process can observe; there is no switch.
Counters: ``scheduler_gc_tenures_total``,
``scheduler_gc_major_collections_total``, gauge
``scheduler_gc_tenured_objects`` (the count when the rule last had to
read it).
"""

from __future__ import annotations

import gc
import time

from kubernetes_tpu.utils import metrics, trace

FULL_GENERATION = 2
# A major collection is due when the tenured count has grown to this
# multiple of what the last one left.
MAJOR_GROWTH = 2


def tenure() -> int:
    """One full collection, so that no garbage is kept, then everything
    alive leaves the collector's reach.  Returns the tenured count.  For
    a process that tenures at moments it knows itself (the extender:
    start-up, after a cold compile) and installs no watch."""
    gc.collect()
    gc.freeze()
    return gc.get_freeze_count()


class GcWatch:
    """The ``gc.callbacks`` entry (phases ``start`` and ``stop``)."""

    def __init__(self) -> None:
        self._started: float | None = None
        self._longest = 0.0
        self._annotation = None
        self._tenuring = False
        # the next full collection finds nothing tenured: a major one
        self._major_next = False
        # tenured objects after the last major collection
        self._major_level = 0
        # the most that can be tenured now: the last count read plus
        # what every pass since has added
        self._tenured_most = 0
        generations = range(len(gc.get_threshold()))
        self._seconds = [metrics.GC_PAUSE_SECONDS.labels(generation=str(g))
                         for g in generations]
        self._collections = [
            metrics.GC_COLLECTIONS.labels(generation=str(g))
            for g in generations]

    def __call__(self, phase: str, info: dict) -> None:
        generation = info["generation"]
        full = generation == FULL_GENERATION
        if phase == "start":
            if full:
                self._annotation = trace.annotation(
                    "gc_major" if self._major_next else "gc2")
                self._annotation.__enter__()
            self._started = time.perf_counter()
            return
        if self._started is None:     # installed inside a collection
            return
        if full and self._tenuring:
            self._tenure()            # inside the pause it is part of
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

    def _tenure(self) -> None:
        """``stop`` of a full collection: every generation was merged
        into the oldest and what is left of it is reachable."""
        added = len(gc.get_objects(generation=FULL_GENERATION))
        gc.freeze()
        metrics.GC_TENURES.inc()
        # gc.get_freeze_count() walks the whole permanent generation
        # (hundreds of thousands of objects: tens of milliseconds), so
        # it is read only when the count could have doubled:
        # ``_tenured_most`` adds what each pass tenured and knows of no
        # object freed since.
        self._tenured_most += added
        if (not self._major_next
                and self._tenured_most < MAJOR_GROWTH * self._major_level):
            return
        tenured = self._tenured_most = gc.get_freeze_count()
        metrics.GC_TENURED_OBJECTS.set(tenured)
        if self._major_next:
            self._major_next = False
            self._major_level = tenured
            metrics.GC_MAJOR_COLLECTIONS.inc()
        elif tenured >= MAJOR_GROWTH * self._major_level:
            # gc.collect() is a no-op inside a collection: hand the heap
            # back and let the interpreter's next full pass be the major
            # one (it comes within ~a hundred young collections, since
            # the pass that just ended left the oldest generation small).
            gc.unfreeze()
            self._major_next = True

    def baseline(self) -> int:
        """Start-up is over (jax imported, programs compiled, nodes and
        resident pods listed): the first major collection, and from here
        on the survivors of every full collection are tenured.  Returns
        the tenured count.  Should another thread be inside a collection,
        ``gc.collect()`` does nothing, the next full collection is the
        major one and this returns 0."""
        self._tenuring = True
        self._major_next = True
        self._major_level = 0
        gc.unfreeze()
        gc.collect()
        return self._major_level


_installed: GcWatch | None = None


def install() -> GcWatch:
    """Install the process's one watch (a second call returns it)."""
    global _installed
    if _installed is None:
        _installed = GcWatch()
        gc.callbacks.append(_installed)
    return _installed


def uninstall() -> None:
    """Take the watch out and hand the tenured heap back to the
    collector (a test that ran the daemon's ``main()`` in its own
    process leaves nothing behind)."""
    global _installed
    if _installed is not None:
        gc.callbacks.remove(_installed)
        _installed = None
        gc.unfreeze()
