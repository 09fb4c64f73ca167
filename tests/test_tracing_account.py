"""The daemon's own tracing, where the work happens: the stage spans as
host events of a profiler session, the launch's account (wall, and the
thread's CPU at the same two clock reads), the cache lock's contention by
role, the collector's own counters, a pod's wait for a launch, and the
windowed trace endpoint.  Counts and structure only — never a timing."""

from __future__ import annotations

import gc
import glob
import json
import os
import threading
import time
import types
import urllib.error
import urllib.request

import pytest

from helpers import make_node, make_pod
from kubernetes_tpu.api.types import node_to_json, pod_to_json
from kubernetes_tpu.utils import metrics, threadreg, trace

# The stages a launch is made of (siblings under the batch root), and the
# parts some of them have; launch.unaccounted_ms_mean subtracts the
# leaves (benchmarks/metrics/launch.unaccounted_ms_mean.json).
LEAVES = ("queue_wait", "lock_wait", "snapshot", "compile",
          "transfer.batch", "transfer.rows", "transfer.scatter",
          "transfer.full", "solve", "readback", "gate", "assume")
# The stages on a launch's critical path: the drain thread's, and on a
# drain of two or more chunks the commit worker's from its pick-up to its
# end and the drain thread's wake; launch.unnamed_ms_mean subtracts them
# (benchmarks/metrics/launch.unnamed_ms_mean.json).
SUMMED = ("queue_wait", "lock_wait", "snapshot", "compile", "transfer",
          "pad", "scan_inputs", "solve", "handoff", "readback", "gate",
          "explain", "decisions", "assume", "victims", "preempt",
          "failures", "bind_spawn", "wake")
# Backdated: a wait measured across a gap, which counts no CPU.
WAITS = ("queue_wait", "lock_wait", "assume.lock_wait", "handoff", "wake",
         "commit.window_wait", "launch_total")
# The stages only a commit on the worker has: a drain of one chunk
# commits on the drain thread and crosses no thread.
WORKER_STAGES = ("handoff", "wake", "commit.window_wait")


def _stage_sums() -> dict:
    return {key[0]: child.sum
            for key, child in metrics.STAGE_LATENCY.children().items()}


def _stage_cpu() -> dict:
    return {key[0]: child.value
            for key, child in metrics.STAGE_CPU_SECONDS.children().items()}


def _grew(after: dict, before: dict) -> dict:
    return {name: after[name] - before.get(name, 0.0) for name in after}


def _launches(spans: list) -> list:
    """Per launch (a ``schedule_batch`` root span of the ring): its
    duration, and the summed stages' spans of its trace, from both
    threads."""
    by_trace: dict = {}
    for sp in spans:
        by_trace.setdefault(sp["trace_id"], []).append(sp)
    return [(root["dur_us"], [sp for sp in by_trace[root["trace_id"]]
                              if sp["name"] in SUMMED])
            for root in spans if root["name"] == "schedule_batch"]


def _wait_bound(store, names, timeout=60.0) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all((store.get("pods", f"default/{n}") or {}).get("spec", {})
               .get("nodeName") for n in names):
            return True
        time.sleep(0.02)
    return False


def _xplanes(profile_dir: str) -> list:
    return glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))


def _kt_events(profile_dir: str) -> list:
    """(name, start_ns, end_ns) of every ``kt.*`` host event."""
    from jax.profiler import ProfileData
    (path,) = _xplanes(profile_dir)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.name.startswith("kt."))
    return out


@pytest.fixture(scope="module")
def traced_launch(tmp_path_factory):
    """Tiny launches of the real daemon loop inside a profiler session
    (the first launch, outside it, compiles): the session's ``kt.*``
    events and what the launch added to each stage's histogram."""
    import jax
    from kubernetes_tpu.apiserver.memstore import MemStore
    from kubernetes_tpu.scheduler.factory import ConfigFactory
    profile_dir = str(tmp_path_factory.mktemp("profile"))
    store = MemStore()
    for i in range(8):
        store.create("nodes", node_to_json(make_node(f"tn{i}")))
    factory = ConfigFactory(store).run()
    try:
        for i in range(4):
            store.create("pods", pod_to_json(make_pod(f"warm{i}")))
        assert _wait_bound(store, [f"warm{i}" for i in range(4)])
        factory.daemon.wait_for_binds()
        before, cpu_before = _stage_sums(), _stage_cpu()
        trace.reset()
        jax.profiler.start_trace(profile_dir)
        try:
            # two waves: the former's wait between them begins and ends
            # inside the session
            for wave in ("a", "b"):
                names = [f"traced-{wave}{i}" for i in range(4)]
                for name in names:
                    store.create("pods", pod_to_json(make_pod(name)))
                assert _wait_bound(store, names)
                factory.daemon.wait_for_binds()
        finally:
            jax.profiler.stop_trace()
        after, cpu_after = _stage_sums(), _stage_cpu()
    finally:
        factory.stop()
    return (_kt_events(profile_dir), _grew(after, before),
            _grew(cpu_after, cpu_before), trace.snapshot())


def test_profiler_session_holds_the_launch_and_its_stages(traced_launch):
    events, _grew, _cpu, _spans = traced_launch
    launches = [e for e in events if e[0] == "kt.launch"]
    assert launches, sorted({e[0] for e in events})

    def inside(name: str) -> bool:
        return any(lo <= start and end <= hi
                   for _n, lo, hi in launches
                   for n, start, end in events if n == name)

    for name in ("kt.snapshot", "kt.compile", "kt.transfer",
                 "kt.transfer.batch", "kt.solve", "kt.readback",
                 "kt.device_wait", "kt.gate", "kt.assume"):
        assert inside(name), f"{name} is in no kt.launch"
    assert any(n.startswith("kt.transfer.") and n != "kt.transfer.batch"
               for n, _s, _e in events), "no cluster sync part traced"
    # the former's wait is a host event where it happens, outside the
    # launch (its span is backdated and so cannot be one)
    assert any(n == "kt.queue_wait" for n, _s, _e in events)


def test_leaf_stages_sum_to_no_more_than_launch_total(traced_launch):
    _events, grew, _cpu, _spans = traced_launch
    assert grew.get("launch_total", 0.0) > 0.0
    for name in ("lock_wait", "snapshot", "compile", "transfer.batch",
                 "solve", "readback", "device_wait", "gate", "assume"):
        assert name in grew, f"stage {name} was never observed"
    assert sum(grew.get(name, 0.0) for name in LEAVES) \
        <= grew["launch_total"]
    # a part is inside the stage that holds it
    assert grew["device_wait"] <= grew["readback"]
    assert sum(grew.get(f"transfer.{part}", 0.0)
               for part in ("batch", "rows", "scatter", "full")) \
        <= grew["transfer"]
    assert grew.get("assume.lock_wait", 0.0) <= grew["assume"]


def _inside_a_launch(events: list, name: str) -> bool:
    """Some ``name`` event lies inside a ``kt.launch``: on the drain
    thread, or on the commit worker while the drain waits for it."""
    launches = [(lo, hi) for n, lo, hi in events if n == "kt.launch"]
    return any(lo <= start and end <= hi for lo, hi in launches
               for n, start, end in events if n == name)


def _account_closes(grew: dict, cpu: dict, spans: list) -> None:
    """Every summed stage's wall inside the launches' whole, in sum and
    launch by launch (the spans of each launch's trace); every stage's
    CPU inside its wall; no CPU row for a wait."""
    assert grew.get("launch_total", 0.0) > 0.0
    assert sum(grew.get(name, 0.0) for name in SUMMED) \
        <= grew["launch_total"]
    launches = _launches(spans)
    assert launches
    for whole, parts in launches:
        assert sum(sp["dur_us"] for sp in parts) <= whole + 1.0, \
            sorted((sp["name"], round(sp["dur_us"])) for sp in parts)
    for name, seconds in cpu.items():
        assert seconds <= grew[name] * 1e-6 + 1e-3, name
    for name in WAITS:
        assert name not in cpu, name


def test_new_stages_are_host_events_of_the_launch(traced_launch,
                                                  traced_chunks):
    """The one-chunk launches commit on the drain thread: their commit's
    stages are host events of the launch, and nothing is handed over or
    joined.  The hand-off and the join are a launch of two chunks'."""
    events, _grew, _cpu, _spans = traced_launch
    for name in ("kt.pad", "kt.scan_inputs", "kt.decisions",
                 "kt.bind_spawn", "kt.commit.counter"):
        assert _inside_a_launch(events, name), f"{name} is in no kt.launch"
    for name in ("kt.handoff", "kt.commit.join", "kt.commit.window_wait"):
        assert not any(n == name for n, _s, _e in events), name
    chunk_events = traced_chunks[3]
    for name in ("kt.handoff", "kt.commit.join", "kt.commit.window_wait"):
        assert _inside_a_launch(chunk_events, name), \
            f"{name} is in no kt.launch"


def test_summed_stages_close_the_launch_account(traced_launch,
                                                traced_chunks):
    _events, grew, cpu, spans = traced_launch
    for name in ("pad", "scan_inputs", "decisions", "bind_spawn",
                 "commit.counter"):
        assert grew.get(name, 0.0) > 0.0, f"stage {name} was never observed"
    for name in WORKER_STAGES:
        assert grew.get(name, 0.0) == 0.0, \
            f"a one-chunk launch recorded {name}"
    _account_closes(grew, cpu, spans)
    # the commit's stages read the CPU clock
    assert cpu["decisions"] > 0.0 and cpu["bind_spawn"] > 0.0
    # the thread hops are a launch of two chunks': walls, and no CPU
    chunk_grew, chunk_cpu = traced_chunks[0], traced_chunks[1]
    for name in ("handoff", "wake"):
        assert chunk_grew.get(name, 0.0) > 0.0, \
            f"stage {name} was never observed"
        assert name not in chunk_cpu, name


@pytest.mark.parametrize("enabled", [True, False],
                         ids=["KT_TRACE=1", "KT_TRACE=0"])
def test_stage_cpu_is_read_at_the_wall_clock_reads(monkeypatch, enabled):
    """A stage around a sleep: its wall holds the sleep, its CPU does
    not; a backdated wait adds to the histogram and to no CPU row.  With
    tracing off the CPU clock is read nowhere."""
    monkeypatch.setattr(trace, "_enabled", enabled)
    wall0, cpu0 = _stage_sums(), _stage_cpu()
    with trace.stage("test.sleep"):
        time.sleep(0.05)
    t0 = time.perf_counter()
    time.sleep(0.01)
    trace.record_stage("test.wait", start=t0)
    wall, cpu = _grew(_stage_sums(), wall0), _grew(_stage_cpu(), cpu0)
    assert wall["test.sleep"] >= 50e3
    if enabled:
        assert 0.0 <= cpu["test.sleep"] < 5e-3
    else:
        assert cpu.get("test.sleep", 0.0) == 0.0
    assert wall["test.wait"] >= 10e3
    assert "test.wait" not in _stage_cpu()


@pytest.fixture(scope="module")
def traced_chunks(tmp_path_factory):
    """Launches of the real daemon loop streamed in chunks of 4 (the
    in-flight window of 2 lets the drain thread hand chunk 2 over while
    the worker still commits chunk 1), until one launch ran two chunks,
    inside a profiler session: what the launches added to each stage and
    to each path of the commit, their spans, and the session's ``kt.*``
    events."""
    import jax
    from kubernetes_tpu.apiserver.memstore import MemStore
    from kubernetes_tpu.scheduler.factory import ConfigFactory
    profile_dir = str(tmp_path_factory.mktemp("profile-chunks"))
    store = MemStore()
    for i in range(8):
        store.create("nodes", node_to_json(make_node(f"cn{i}")))
    factory = ConfigFactory(store)
    daemon = factory.daemon
    daemon.STREAM_THRESHOLD = 4
    daemon.stream_chunk = 4
    daemon.pipeline_window = 2
    factory.run()
    try:
        warm = [f"cwarm{i}" for i in range(8)]
        for name in warm:
            store.create("pods", pod_to_json(make_pod(name)))
        assert _wait_bound(store, warm)
        daemon.wait_for_binds()
        before, cpu_before = _stage_sums(), _stage_cpu()
        paths_before = _commit_paths()
        trace.reset()
        jax.profiler.start_trace(profile_dir)
        try:
            for wave in range(20):
                names = [f"chunk{wave}-{i}" for i in range(8)]
                for name in names:
                    store.create("pods",
                                 pod_to_json(make_pod(name, cpu="10m")))
                assert _wait_bound(store, names)
                daemon.wait_for_binds()
                spans = trace.snapshot()
                if any(_chunks(parts) >= 2 for _whole, parts in
                       _launches(spans)):
                    break
        finally:
            jax.profiler.stop_trace()
        after, cpu_after = _stage_sums(), _stage_cpu()
        paths = _grew(_commit_paths(), paths_before)
    finally:
        factory.stop()
    return (_grew(after, before), _grew(cpu_after, cpu_before), spans,
            _kt_events(profile_dir), paths)


def _commit_paths() -> dict:
    return {key[0]: child.value
            for key, child in metrics.COMMIT_LAUNCHES.children().items()}


def _chunks(parts: list) -> int:
    return sum(sp["name"] == "handoff" for sp in parts)


def test_a_chunk_hand_off_waits_for_the_worker_not_its_last_commit(
        traced_chunks):
    """In a launch of two chunks the worker's summed stages follow each
    other: chunk 2's hand-off starts where chunk 1's commit ended (or
    later), never before it, so no commit is counted twice; each thread's
    summed stages stay inside the launch; the wait for the in-flight
    window is ``commit.window_wait``, summed nowhere, counting no CPU."""
    grew, cpu, spans, _events, paths = traced_chunks
    two = [(whole, parts) for whole, parts in _launches(spans)
           if _chunks(parts) >= 2]
    assert two, "no launch ran two chunks"
    # a launch of two chunks commits on the worker
    assert paths.get("worker", 0) >= 1
    for whole, parts in two:
        handoffs = [sp for sp in parts if sp["name"] == "handoff"]
        worker = handoffs[0]["thread"]
        assert {sp["thread"] for sp in handoffs} == {worker}
        mine = sorted((sp for sp in parts if sp["thread"] == worker),
                      key=lambda sp: sp["ts_us"])
        assert any(sp["name"] == "decisions" for sp in mine)
        for prev, nxt in zip(mine, mine[1:]):
            assert nxt["ts_us"] >= prev["ts_us"] + prev["dur_us"] - 1.0, \
                (prev["name"], nxt["name"])
        by_thread: dict = {}
        for sp in parts:
            by_thread[sp["thread"]] = \
                by_thread.get(sp["thread"], 0.0) + sp["dur_us"]
        for total in by_thread.values():
            assert total <= whole + 1.0
    assert grew.get("commit.window_wait", 0.0) >= 0.0
    assert "commit.window_wait" in grew
    assert "commit.window_wait" not in cpu


@pytest.fixture(scope="module")
def traced_failure(tmp_path_factory):
    """A streamed launch whose one pod fits no node, inside a profiler
    session: a priority pod asking for more CPU than a node has, so the
    commit worker explains it, searches victims, and requeues it (a
    first such pod, outside the session, compiles the two passes)."""
    import jax
    import logging
    from kubernetes_tpu.apiserver.memstore import MemStore
    from kubernetes_tpu.scheduler.factory import ConfigFactory
    profile_dir = str(tmp_path_factory.mktemp("profile-failure"))
    store = MemStore()
    for i in range(8):
        store.create("nodes", node_to_json(make_node(f"fn{i}")))
    errors: list = []

    class Errors(logging.Handler):
        def emit(self, record):
            errors.append(record.getMessage())

    handler = Errors(level=logging.ERROR)
    logging.getLogger("kubernetes_tpu").addHandler(handler)
    factory = ConfigFactory(store).run()
    daemon = factory.daemon

    def requeued(key: str) -> bool:
        with daemon._requeue_cv:
            if any(p.key == key for _, _, p in daemon._requeue_heap):
                return True
        return key in daemon.queue

    def fail_one(name: str) -> None:
        pod = make_pod(name, cpu="100")
        pod.priority = 100
        store.create("pods", pod_to_json(pod))
        deadline = time.time() + 120.0
        while not requeued(f"default/{name}"):
            assert time.time() < deadline, f"{name} was never requeued"
            time.sleep(0.02)
        daemon.wait_for_binds()

    try:
        store.create("pods", pod_to_json(make_pod("fits")))
        assert _wait_bound(store, ["fits"])
        fail_one("too-big-warm")
        before, cpu_before = _stage_sums(), _stage_cpu()
        trace.reset()
        jax.profiler.start_trace(profile_dir)
        try:
            fail_one("too-big")
            # the launch that explained the pod, once its drain returned
            # (the ring keeps the last few hundred launches: the pods
            # that fit nowhere are tried again and again)
            deadline = time.time() + 30.0
            while True:
                spans = trace.snapshot()
                if any(any(sp["name"] == "explain" for sp in parts)
                       for _whole, parts in _launches(spans)):
                    break
                assert time.time() < deadline, "no launch explained it"
                time.sleep(0.01)
        finally:
            jax.profiler.stop_trace()
        after, cpu_after = _stage_sums(), _stage_cpu()
        explained = daemon.config.flight_recorder.explain("default/too-big")
    finally:
        factory.stop()
        logging.getLogger("kubernetes_tpu").removeHandler(handler)
    return (_kt_events(profile_dir), _grew(after, before),
            _grew(cpu_after, cpu_before), spans, explained, errors)


def test_failure_path_is_traced_and_its_account_closes(traced_failure):
    events, grew, cpu, spans, explained, errors = traced_failure
    # nothing raised: the explain and the victim passes log an exception
    # they swallow, a crashed drain logs one before it requeues
    assert errors == []
    # the pod went back to the queue through the failure handler (the
    # fixture waited for it there)
    assert explained["result"] == "unschedulable"
    for name in ("explain", "victims", "preempt", "failures", "decisions"):
        assert grew.get(name, 0.0) > 0.0, f"stage {name} was never observed"
        assert _inside_a_launch(events, f"kt.{name}"), name
    # the launch that explained the pod closes its account on its own
    assert any(any(sp["name"] == "explain" for sp in parts)
               for _whole, parts in _launches(spans))
    _account_closes(grew, cpu, spans)


def _contended(role: str) -> float:
    child = metrics.CACHE_LOCK_CONTENDED.children().get((role,))
    return child.value if child is not None else 0


def _waited(role: str) -> float:
    child = metrics.CACHE_LOCK_WAIT_SECONDS.children().get((role,))
    return child.value if child is not None else 0.0


@pytest.mark.parametrize("locktrace_on", [False, True],
                         ids=["plain-lock", "traced-lock"])
def test_handler_behind_a_held_cache_lock_counts_once_under_its_role(
        monkeypatch, locktrace_on):
    """The launch thread holds the lock; a handler thread named like the
    pod reflector blocks behind it.  The clock is read only by a thread
    that has to block, which is also how the test knows it is blocked."""
    from kubernetes_tpu.cache import scheduler_cache as sc
    from kubernetes_tpu.utils import locktrace
    monkeypatch.setattr(locktrace, "_enabled", locktrace_on)
    cache = sc.SchedulerCache()
    cache.add_node(make_node("n0"))
    blocked = threading.Event()
    clock_reads = []

    def clock() -> float:
        clock_reads.append(threading.current_thread().name)
        blocked.set()
        return time.perf_counter()

    monkeypatch.setattr(sc, "_clock", clock)
    before = _contended("reflector-pods"), _waited("reflector-pods")

    # uncontended: the handler's path reads no clock and counts nothing
    cache.add_pod(make_pod("free", node_name="n0"))
    assert clock_reads == []
    assert _contended("reflector-pods") == before[0]

    handler = threading.Thread(
        target=cache.add_pod, args=(make_pod("held", node_name="n0"),),
        name="reflector-pods")
    with cache.lock:
        handler.start()
        assert blocked.wait(10.0), "the handler never reached the lock"
        assert cache.pod_count() == 1       # reentrant for the holder
    handler.join(10.0)
    assert not handler.is_alive()
    assert cache.pod_count() == 2
    assert _contended("reflector-pods") == before[0] + 1
    assert _waited("reflector-pods") > before[1]
    assert clock_reads == ["reflector-pods", "reflector-pods"]


@pytest.mark.parametrize("name,role", [
    ("reflector-pods", "reflector-pods"), ("watch-pods", "watch-pods"),
    ("bind-worker-17", "bind-worker"), ("chunk-commit_0", "chunk-commit"),
    ("Thread-12 (run)", "Thread"), ("bind-batch", "bind-batch"),
    ("7", "7")])
def test_thread_role_collapses_instance_suffixes(name, role):
    assert threadreg.role(name) == role


def test_gc_collect_adds_exactly_one_full_collection():
    from kubernetes_tpu.utils import gcstats
    watch = gcstats.install()
    assert gcstats.install() is watch           # one entry a process
    assert gc.callbacks.count(watch) == 1
    full = metrics.GC_COLLECTIONS.labels(generation="2")
    seconds = metrics.GC_PAUSE_SECONDS.labels(generation="2")
    was_enabled = gc.isenabled()
    gc.disable()        # no automatic collection between the two reads
    try:
        n0, s0 = full.value, seconds.value
        gc.collect()
        assert full.value == n0 + 1
        assert seconds.value > s0
        assert metrics.GC_PAUSE_MAX.value > 0.0
        young = metrics.GC_COLLECTIONS.labels(generation="0")
        y0 = young.value
        gc.collect(0)
        assert young.value == y0 + 1 and full.value == n0 + 1
    finally:
        if was_enabled:
            gc.enable()


def test_pod_wait_is_counted_once_per_pod_at_the_hand_off():
    from kubernetes_tpu.scheduler import pipeline
    pods = [make_pod(f"w{i}") for i in range(3)]
    now = time.perf_counter()
    pods[0]._kt_first_seen = now - 2.0
    pods[1]._kt_first_seen = now - 1.0      # pods[2] carries no stamp
    n0 = metrics.POD_QUEUE_WAIT_PODS.value
    s0 = metrics.POD_QUEUE_WAIT_SECONDS.value
    pipeline._count_pod_waits(pods)
    assert metrics.POD_QUEUE_WAIT_PODS.value == n0 + 2
    assert metrics.POD_QUEUE_WAIT_SECONDS.value - s0 >= 3.0
    assert metrics.POD_QUEUE_WAIT_MAX.value >= 2.0


def _scan_steps(kind: str) -> float:
    child = metrics.SCAN_STEPS.children().get((kind,))
    return child.value if child is not None else 0


@pytest.mark.parametrize("pods,chunk,run,bucket", [
    (5, 64, 8, 64),             # rows 0-4 live: 5 -> 8, one dispatch
    (70, 64, 64 + 8, 2 * 64),   # a full chunk, then 6 live rows of 64
    (64, 64, 64, 64)],          # a bucket filled: the full-length loop
    ids=["few-pods", "two-chunks", "filled"])
def test_scan_steps_count_the_loop_and_the_bucket(pods, chunk, run, bucket):
    """A streamed launch through the engine: ``kind=bucket`` grows by the
    bucket's rows a dispatch, ``kind=run`` by the last live row + 1
    rounded up to 4 — under the bucket unless the launch fills it."""
    from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
    eng = GenericScheduler()
    for i in range(8):
        eng.cache.add_node(make_node(f"sn{i}"))
    before = {kind: _scan_steps(kind) for kind in ("run", "bucket")}
    chunks = list(eng.schedule_batch_stream(
        [make_pod(f"sp{i}") for i in range(pods)], chunk_size=chunk))
    assert sum(len(placed) for _pods, placed in chunks) == pods
    assert _scan_steps("bucket") - before["bucket"] == bucket
    assert _scan_steps("run") - before["run"] == run
    assert (run < bucket) == (pods % chunk != 0)


class _CountingAnnotation:
    built = 0

    def __init__(self, name, **attrs):
        type(self).built += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("enabled,expect_built", [(False, 0), (True, 4)],
                         ids=["KT_TRACE=0", "KT_TRACE=1"])
def test_annotations_follow_kt_trace(monkeypatch, enabled, expect_built):
    monkeypatch.setattr(trace, "_trace_annotation", _CountingAnnotation)
    monkeypatch.setattr(_CountingAnnotation, "built", 0)
    was = trace.enabled()
    trace.set_enabled(enabled)
    try:
        with trace.stage("snapshot"):
            pass
        with trace.span("anything"):
            pass
        trace.begin_span("explicit").end()
        with trace.annotation("queue_wait"):
            pass
        # the backdated form is never a host event
        trace.record_stage("lock_wait", start=time.perf_counter())
    finally:
        trace.set_enabled(was)
    assert _CountingAnnotation.built == expect_built


def test_lowered_module_names_are_the_ones_the_trace_readers_match():
    """``scan.device_us_per_pod`` matches ``solve_scan`` and
    ``scatter.device_us_per_pod`` matches ``kt_scatter_rows`` on the
    profiler's ``XLA Modules`` line, which reads ``jit_<function>``."""
    import jax
    import numpy as np
    from kubernetes_tpu.analysis import xray
    from kubernetes_tpu.engine import solver as sv
    ctx = xray.build_context()
    bucket = xray.canonical_ladder()[0]
    batch = xray.resize_pod_axis(ctx.batch1, bucket)
    counter = jax.ShapeDtypeStruct((), np.uint32)
    live = jax.ShapeDtypeStruct((bucket,), np.bool_)
    scan = sv.Solver._solve_scan.lower(
        ctx.solver, batch, ctx.cluster, counter, None, ctx.flags, None,
        live, None)
    assert "module @jit__solve_scan" in scan.as_text()
    words = sv.rows_layout(sv._cluster_planes(ctx.cluster), 1)[1]
    scatter = sv.ResidentCluster()._scatter_fn().lower(
        ctx.cluster, jax.ShapeDtypeStruct((words,), np.int32), 1)
    assert "module @jit_kt_scatter_rows" in scatter.as_text()


def _get(url: str, timeout: float = 60.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


def test_trace_endpoint_writes_one_xplane_and_refuses_a_second_session(
        monkeypatch, tmp_path):
    from kubernetes_tpu.apiserver.memstore import MemStore
    from kubernetes_tpu.scheduler.__main__ import _status_mux
    from kubernetes_tpu.scheduler.factory import ConfigFactory
    from kubernetes_tpu.utils import profiling
    factory = ConfigFactory(MemStore())
    mux = _status_mux(factory, {"enableProfiling": True}, 0,
                      profile_dir=str(tmp_path))
    url = f"http://127.0.0.1:{mux.server_address[1]}/debug/pprof/trace"
    # The first session stays open until the test lets it go.
    sleeping, release, slept = threading.Event(), threading.Event(), []

    def sleep(seconds: float) -> None:
        slept.append(seconds)
        sleeping.set()
        release.wait(30.0)

    monkeypatch.setattr(profiling, "time", types.SimpleNamespace(
        sleep=sleep, perf_counter=time.perf_counter))
    answers = []
    first = threading.Thread(
        target=lambda: answers.append(_get(url + "?seconds=0.2")))
    try:
        first.start()
        assert sleeping.wait(30.0), "the first session never opened"
        status, body = _get(url + "?seconds=0.2")
        assert status == 409, body
        release.set()
        first.join(60.0)
        assert not first.is_alive()
        (status, body), = answers
        assert status == 200, body
        assert slept == [0.2]
        assert json.loads(body)["dir"] == str(tmp_path)
        assert len(_xplanes(str(tmp_path))) == 1
        assert _get(url + "?seconds=soon")[0] == 400
    finally:
        release.set()
        mux.shutdown()
        mux.server_close()
    # with profiling off the handlers are gone, as the reference's are
    off = _status_mux(factory, {"enableProfiling": False}, 0)
    try:
        assert _get(f"http://127.0.0.1:{off.server_address[1]}"
                    "/debug/pprof/trace?seconds=0")[0] == 404
    finally:
        off.shutdown()
        off.server_close()
