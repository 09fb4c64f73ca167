"""Tenuring of the daemon's long-lived heap (``utils/gcstats.py``).

Counts only, no clocks: what the permanent generation holds, what the
collector still tracks, which counters moved.  Every test installs the
watch itself and uninstalls it, so the pytest process keeps no tenured
heap; automatic collection is off inside a test so that only the
collections it forces run.  The process is pytest's, with whatever
earlier tests left alive in it (threads that still free what the
baseline tenured), so counts are compared with room, and a population
is grown until the rule under test fires and not to a computed size.
"""

import gc
import json
import weakref

import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.utils import gcstats, metrics

# the benchmark's pod (upstream scheduler_perf's pod-default.yaml)
POD = ('{"metadata":{"name":"p-%d","namespace":"bench","uid":"",'
       '"labels":{},"annotations":{}},"spec":{"containers":[{"name":'
       '"pause","image":"kubernetes/pause:go","resources":{"requests":'
       '{"cpu":"100m","memory":"524288000"}},"ports":[{"hostPort":0,'
       '"containerPort":80,"protocol":"TCP"}]}],"nodeName":"n-%d"}}')


# collector-tracked objects of one api.Pod once a full collection has
# untracked its dicts of strings (Pod, Container, ContainerPort, three
# lists: 6), less room for what other threads free meanwhile
TRACKED_A_POD = 5


def make_pods(n: int) -> dict:
    return {i: api.pod_from_json(json.loads(POD % (i, i % 50)))
            for i in range(n)}


class Node:
    """Half of a reference cycle."""

    def __init__(self) -> None:
        self.other = None


def make_cycle() -> Node:
    a, b = Node(), Node()
    a.other, b.other = b, a
    return a


def grow_until(fired, level: int) -> list:
    """Hold more and more pods, a full collection after each lot, until
    ``fired()``; the lots are a quarter of ``level`` tracked objects."""
    lots = []
    for _ in range(40):
        lots.append(make_pods(level // (4 * TRACKED_A_POD) + 1000))
        gc.collect()
        if fired():
            return lots
    raise AssertionError("the population never reached the rule")


def untenured() -> int:
    """What the permanent generation holds in a process that tenures
    nothing: this interpreter's full collection parks a few hundred of
    its own objects there (375 on CPython 3.12.12), so 0 is only read
    straight after ``gc.unfreeze()``."""
    gc.unfreeze()
    gc.collect()
    return gc.get_freeze_count()


@pytest.fixture
def watch():
    """The daemon's watch as its entry point installs it, in a process
    with nothing tenured, taken out again whatever the test did."""
    gcstats.uninstall()           # another test's _status_mux left one
    gc.unfreeze()
    was_enabled = gc.isenabled()
    gc.disable()
    installed = gcstats.install()
    try:
        yield installed
    finally:
        gcstats.uninstall()
        if was_enabled:
            gc.enable()


def test_a_watch_that_was_given_no_baseline_only_counts(watch):
    full = metrics.GC_COLLECTIONS.labels(generation="2")
    n0, t0 = full.value, metrics.GC_TENURES.value
    floor = untenured()
    pods = make_pods(100)
    gc.collect()
    assert full.value == n0 + 2
    assert metrics.GC_TENURES.value == t0
    assert gc.get_freeze_count() == floor
    assert len(pods) == 100


def test_survivors_of_a_full_collection_leave_the_collectors_pass(watch):
    watch.baseline()
    pods = make_pods(5000)
    gc.collect()                              # a forced full collection
    tenured = gc.get_freeze_count()
    assert tenured > 0
    # the tracked objects of every pod (Pod, Container, ContainerPort,
    # three lists) are out of the collector's reach
    assert tenured >= TRACKED_A_POD * len(pods)
    assert len(gc.get_objects()) < tenured / 10


def test_reference_counting_still_frees_a_tenured_pod(watch):
    watch.baseline()
    level = gc.get_freeze_count()
    pods = make_pods(5000)
    gc.collect()
    held = gc.get_freeze_count()
    assert held >= level + TRACKED_A_POD * len(pods)
    sample = weakref.ref(pods[17])
    pods.clear()                              # the pods retire
    assert sample() is None                   # freed with no collection
    assert gc.get_freeze_count() <= held - TRACKED_A_POD * 5000


def test_the_baseline_is_the_first_major_collection(watch):
    t0, m0 = metrics.GC_TENURES.value, metrics.GC_MAJOR_COLLECTIONS.value
    full = metrics.GC_COLLECTIONS.labels(generation="2")
    n0 = full.value
    tenured = watch.baseline()
    assert tenured > 0
    assert abs(tenured - gc.get_freeze_count()) < 100
    assert metrics.GC_MAJOR_COLLECTIONS.value == m0 + 1
    assert metrics.GC_TENURES.value == t0 + 1
    assert full.value == n0 + 1               # a full collection like any
    gc.collect()
    assert metrics.GC_TENURES.value == t0 + 2
    assert metrics.GC_MAJOR_COLLECTIONS.value == m0 + 1
    assert metrics.GC_TENURED_OBJECTS.value == tenured


def test_the_tenured_count_is_read_only_when_it_could_have_doubled(
        watch, monkeypatch):
    """``gc.get_freeze_count()`` walks the permanent generation, tens of
    milliseconds on the daemon's heap: a pass that cannot have doubled
    the count does not pay for it."""
    level = watch.baseline()
    reads = []

    class Gc:
        def __getattr__(self, name):
            if name == "get_freeze_count":
                reads.append(name)
            return getattr(gc, name)

    monkeypatch.setattr(gcstats, "gc", Gc())
    few = make_pods(1000)                     # level is 40,000 or more
    gc.collect()
    assert reads == []
    assert metrics.GC_TENURED_OBJECTS.value == level
    many = grow_until(lambda: reads, level)
    assert len(reads) == 1                    # one walk, when it was due
    assert metrics.GC_TENURED_OBJECTS.value > level
    assert few and many


def test_a_tenured_cycle_waits_for_the_major_collection(watch):
    """The cost of tenuring and its bound: a cycle that becomes garbage
    AFTER it was tenured is out of the collector's sight until the
    tenured count has doubled; then the doubling rule un-tenures the
    heap and the next full collection, which nobody asks to be a major
    one, finds it."""
    level = watch.baseline()
    held = make_cycle()
    cycle = weakref.ref(held)
    gc.collect()                              # the cycle is tenured alive
    del held                                  # and becomes garbage
    majors = metrics.GC_MAJOR_COLLECTIONS.value
    for _ in range(3):
        gc.collect()
    assert cycle() is not None                # full collections miss it
    assert metrics.GC_MAJOR_COLLECTIONS.value == majors
    # the resident population grows until the tenured count has
    # doubled: that pass hands the heap back instead of tenuring
    pods = grow_until(lambda: gc.get_freeze_count() == 0, level)
    assert cycle() is not None
    assert metrics.GC_TENURED_OBJECTS.value >= 2 * level
    assert metrics.GC_MAJOR_COLLECTIONS.value == majors
    gc.collect()                              # the next full collection
    assert cycle() is None
    assert metrics.GC_MAJOR_COLLECTIONS.value == majors + 1
    assert gc.get_freeze_count() > level      # and tenured again
    assert len(pods) > 0
    # the new level is what that collection left: no major one is due
    gc.collect()
    assert metrics.GC_MAJOR_COLLECTIONS.value == majors + 1
    assert gc.get_freeze_count() > 0


def test_a_cycle_dropped_before_its_first_full_collection_is_found(watch):
    """Young and full collections keep reclaiming cyclic garbage that
    was never tenured."""
    watch.baseline()
    cycle = weakref.ref(make_cycle())         # garbage at once
    gc.collect(0)
    assert cycle() is None
    cycle = weakref.ref(make_cycle())
    gc.collect()
    assert cycle() is None


def test_a_major_collection_is_its_own_host_event(watch, monkeypatch):
    names = []

    class Annotation:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(gcstats.trace, "annotation", Annotation)
    watch.baseline()
    gc.collect()
    gc.collect(1)                             # no event for a young one
    assert names == ["gc_major", "gc2"]


def test_uninstall_hands_the_heap_back(watch):
    watch.baseline()
    pods = make_pods(1000)
    gc.collect()
    assert gc.get_freeze_count() > 0
    entries = len(gc.callbacks)
    gcstats.uninstall()
    assert gc.get_freeze_count() == 0
    assert len(gc.callbacks) == entries - 1
    assert watch not in gc.callbacks
    gcstats.uninstall()                       # a second call is nothing
    assert len(gc.callbacks) == entries - 1
    t0 = metrics.GC_TENURES.value
    floor = untenured()
    gc.collect()
    assert gc.get_freeze_count() == floor     # nothing tenures now
    assert metrics.GC_TENURES.value == t0
    assert len(pods) == 1000


def test_the_daemons_metrics_page_carries_the_three_families(watch):
    """Printed from the daemon's start, 0 included: the benchmark's
    ``counter_delta`` reads a difference between two pages."""
    page = metrics.expose_registry()
    for family in ("scheduler_gc_tenures_total",
                   "scheduler_gc_major_collections_total",
                   "scheduler_gc_tenured_objects"):
        assert f"\n{family} " in page, family


def test_the_extender_tenures_through_the_same_module(monkeypatch):
    from kubernetes_tpu.server import extender
    calls = []
    monkeypatch.setattr(extender.gcstats, "tenure",
                        lambda: calls.append("tenure"))
    monkeypatch.setattr(extender, "_heap_frozen", False)
    extender._freeze_baseline_heap()
    extender._freeze_baseline_heap()          # once a process
    extender._refreeze_heap()
    assert calls == ["tenure", "tenure"]
    assert not hasattr(extender, "gc")


def test_tenure_collects_then_freezes():
    gc.unfreeze()
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        cycle = weakref.ref(make_cycle())
        held = make_pods(100)
        count = gcstats.tenure()
        assert cycle() is None                # garbage is not kept
        assert count >= TRACKED_A_POD * len(held)
        assert abs(count - gc.get_freeze_count()) < 1000
    finally:
        gc.unfreeze()
        if was_enabled:
            gc.enable()


def test_only_the_daemons_entry_point_starts_tenuring():
    """``ConfigFactory.run`` calls what it is handed, once start-up is
    over, and knows nothing of the collector; rigs that build a factory
    in-process hand it nothing."""
    import inspect

    from kubernetes_tpu.scheduler import __main__ as daemon
    from kubernetes_tpu.scheduler import factory
    assert "gcstats" not in inspect.getsource(factory)
    assert inspect.getsource(daemon.main).count(
        "factory.run(started=tenure_heap)") == 2


def test_a_profiler_sample_pins_no_stack():
    """``sys._current_frames()`` holds the sampling frame itself, whose
    local is that dict: left in, every sample is a cycle that keeps all
    threads' stacks (a launch's pods and device arrays) alive until the
    cyclic collector runs — garbage that tenuring would then hold until
    a major collection.  What the caller's stack held is freed by
    reference count alone."""
    from kubernetes_tpu.utils import profiler
    sampler = profiler.Profiler()

    def sampled() -> weakref.ref:
        held = Node()
        sampler.sample_once()
        return weakref.ref(held)

    was_enabled = gc.isenabled()
    gc.disable()
    try:
        assert sampled()() is None
    finally:
        if was_enabled:
            gc.enable()
