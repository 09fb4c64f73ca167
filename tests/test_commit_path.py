"""Which thread commits a streamed drain, and where its binds run: a
drain of one chunk commits on the drain thread (no hand-off, no wake),
a drain of several chunks on the overlapped commit worker, and every
bind — of either path and of the single-pod route — on the daemon's
standing binder, which starts no thread per launch."""

from __future__ import annotations

import threading
import time

import pytest

from helpers import make_node, make_pod
from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
from kubernetes_tpu.scheduler.binder import BindConflict, InMemoryBinder
from kubernetes_tpu.scheduler.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.utils import metrics, threadreg, trace

PODS = 12
# The stages only a commit on the worker has.
WORKER_STAGES = ("handoff", "wake", "commit.window_wait")


def _daemon(chunk: int, binder=None, n_nodes: int = 5) -> Scheduler:
    algo = GenericScheduler()
    for i in range(n_nodes):
        algo.cache.add_node(make_node(f"cn{i}", milli_cpu=4000))
    daemon = Scheduler(SchedulerConfig(
        algorithm=algo, binder=binder or InMemoryBinder(), async_bind=True))
    # every drain streams, in chunks of ``chunk`` pods
    daemon.STREAM_THRESHOLD = 1
    daemon.stream_chunk = chunk
    daemon.stream_min_bucket = chunk
    daemon.pipeline_window = 2
    return daemon


def _pods() -> list:
    # requests that differ, so LeastRequested and the tie counter both
    # decide some of the placements
    return [make_pod(f"cp{i:02d}", cpu=f"{100 * (i % 4 + 1)}m")
            for i in range(PODS)]


def _paths() -> dict:
    return {key[0]: child.value
            for key, child in metrics.COMMIT_LAUNCHES.children().items()}


def _drain(chunk: int) -> dict:
    """One streamed drain of ``_pods()`` in chunks of ``chunk``: what it
    assumed (in order), bound and counted, and its launch's stages."""
    daemon = _daemon(chunk)
    algo = daemon.config.algorithm
    assumed: list = []
    real_assume = algo.cache.assume_pods

    def spy_assume(assignments, **kw):
        assumed.extend((pod.key, dest) for pod, dest in assignments)
        return real_assume(assignments, **kw)

    algo.cache.assume_pods = spy_assume
    pods = _pods()
    for pod in pods:
        daemon.enqueue(pod)
    trace.reset()
    before = _paths()
    try:
        assert daemon.schedule_pending(wait_first=False) == PODS
        daemon.wait_for_binds()
        after = _paths()
        spans = trace.snapshot()
        binder = daemon.config.binder
        bound = {pod.key: binder.bound_node(pod.key) for pod in pods}
    finally:
        daemon.stop()
    (root,) = [sp for sp in spans if sp["name"] == "schedule_batch"]
    stages = [sp["name"] for sp in spans
              if sp["trace_id"] == root["trace_id"]]
    return {"assumed": assumed, "bound": bound, "stages": stages,
            "paths": {p: after.get(p, 0) - before.get(p, 0)
                      for p in ("inline", "worker")}}


def test_one_chunk_commits_inline_and_three_chunks_on_the_worker():
    one = _drain(chunk=16)
    three = _drain(chunk=4)
    # the same placements, assumed in the same order, and bound
    assert len(one["assumed"]) == PODS
    assert one["assumed"] == three["assumed"]
    assert one["bound"] == three["bound"]
    assert all(one["bound"].values())
    assert dict(one["assumed"]) == one["bound"]
    assert len(set(one["bound"].values())) > 1
    # one chunk: committed on the drain thread, no thread crossed
    assert one["paths"] == {"inline": 1, "worker": 0}
    for name in WORKER_STAGES:
        assert name not in one["stages"], name
    assert one["stages"].count("decisions") == 1
    assert one["stages"].count("bind_spawn") == 1
    # three chunks: the worker, one hand-off a chunk, one wake
    assert three["paths"] == {"inline": 0, "worker": 1}
    assert three["stages"].count("handoff") == 3
    assert three["stages"].count("commit.window_wait") == 3
    assert three["stages"].count("wake") == 1
    assert three["stages"].count("decisions") == 3


def test_window_zero_commits_several_chunks_inline():
    daemon = _daemon(chunk=4)
    daemon.pipeline_window = 0
    before = _paths()
    try:
        for pod in _pods():
            daemon.enqueue(pod)
        assert daemon.schedule_pending(wait_first=False) == PODS
        daemon.wait_for_binds()
        assert daemon.config.binder.count() == PODS
        assert daemon._commit_pool is None
    finally:
        daemon.stop()
    after = _paths()
    assert after.get("inline", 0) - before.get("inline", 0) == 1
    assert after.get("worker", 0) == before.get("worker", 0)


class _RecordingBinder(InMemoryBinder):
    """Notes the thread of every bind; binds wait for ``gate``."""

    def __init__(self):
        super().__init__()
        self.threads: set = set()
        self.gate = threading.Event()
        self.gate.set()

    def _note(self):
        self.gate.wait(10.0)
        self.threads.add(threading.current_thread())

    def bind(self, pod, node_name):
        self._note()
        super().bind(pod, node_name)

    def bind_many(self, bindings):
        self._note()
        return super().bind_many(bindings)


def _launch(daemon: Scheduler, route: str, name: str) -> None:
    daemon.enqueue(make_pod(name, cpu="10m"))
    if route == "batch":
        assert daemon.schedule_pending(wait_first=False) == 1
    else:
        assert daemon.schedule_one(timeout=1.0)


def _bind_threads() -> list:
    return [t for t in threading.enumerate()
            if threadreg.role(t.name) == "bind-batch"]


@pytest.mark.parametrize("route", ["batch", "single"])
def test_launches_start_no_bind_thread_of_their_own(route):
    """Twenty launches bind on at most the binder's workers; stop()
    leaves none of them alive."""
    binder = _RecordingBinder()
    daemon = _daemon(chunk=4, binder=binder)
    others = set(_bind_threads())
    try:
        for i in range(20):
            _launch(daemon, route, f"{route}-{i}")
            mine = set(_bind_threads()) - others
            assert len(mine) <= Scheduler.BIND_WORKERS
        daemon.wait_for_binds()
        assert binder.count() == 20
        assert 1 <= len(binder.threads) <= Scheduler.BIND_WORKERS
        assert {threadreg.role(t.name) for t in binder.threads} \
            == {"bind-batch"}
    finally:
        daemon.stop()
    assert not any(t.is_alive() for t in binder.threads)


@pytest.mark.parametrize("route", ["batch", "single"])
def test_wait_for_binds_returns_once_every_bind_landed(route):
    binder = _RecordingBinder()
    binder.gate.clear()
    daemon = _daemon(chunk=4, binder=binder)
    try:
        for i in range(3):
            _launch(daemon, route, f"held-{route}-{i}")
        waiter = threading.Thread(target=daemon.wait_for_binds,
                                  name="test-waiter", daemon=True)
        waiter.start()
        waiter.join(0.2)
        assert waiter.is_alive() and binder.count() == 0
        binder.gate.set()
        waiter.join(10.0)
        assert not waiter.is_alive()
        assert binder.count() == 3
    finally:
        binder.gate.set()
        daemon.stop()


class _ConflictBinder(InMemoryBinder):
    def bind(self, pod, node_name):
        raise BindConflict(f"pod {pod.key} is already assigned")

    def bind_many(self, bindings):
        return [(pod, BindConflict(f"pod {pod.key} is already assigned"))
                for pod, _ in bindings]


@pytest.mark.parametrize("route", ["batch", "single"])
def test_a_conflict_in_the_binder_forgets_and_requeues(route):
    daemon = _daemon(chunk=4, binder=_ConflictBinder())
    try:
        _launch(daemon, route, f"taken-{route}")
        daemon.wait_for_binds()
        key = f"default/taken-{route}"
        assert daemon.config.algorithm.cache.pod_count() == 0
        deadline = time.monotonic() + 5.0
        while True:
            with daemon._requeue_cv:
                heap = {p.key for _, _, p in daemon._requeue_heap}
            if key in heap or key in daemon.queue:
                break
            assert time.monotonic() < deadline, "the pod was not requeued"
            time.sleep(0.01)
        events = daemon.config.recorder.events(key)
        assert events and events[-1].reason == "FailedScheduling"
    finally:
        daemon.stop()
