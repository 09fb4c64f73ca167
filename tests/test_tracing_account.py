"""The daemon's own tracing, where the work happens (ISSUE 27): the stage
spans as host events of a profiler session, the launch's account, the
cache lock's contention by role, the collector's own counters, a pod's
wait for a launch, and the windowed trace endpoint.  Counts and
structure only — never a timing."""

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


def _stage_sums() -> dict:
    return {key[0]: child.sum
            for key, child in metrics.STAGE_LATENCY.children().items()}


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
        before = _stage_sums()
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
        after = _stage_sums()
    finally:
        factory.stop()
    grew = {name: after[name] - before.get(name, 0.0) for name in after}
    return _kt_events(profile_dir), grew


def test_profiler_session_holds_the_launch_and_its_stages(traced_launch):
    events, _grew = traced_launch
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
    _events, grew = traced_launch
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
