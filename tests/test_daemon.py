"""Scheduler daemon tests: queue, backoff, assume/bind state machine,
events, metrics (scheduler.go:93-154, factory.go:512-688)."""

from __future__ import annotations

import time

import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
from kubernetes_tpu.scheduler.backoff import PodBackoff
from kubernetes_tpu.scheduler.binder import BindConflict, InMemoryBinder
from kubernetes_tpu.scheduler.queue import FIFO
from kubernetes_tpu.scheduler.scheduler import Scheduler, SchedulerConfig

from helpers import make_node, make_pod


def _scheduler(n_nodes=3, **cfg):
    algo = GenericScheduler()
    for i in range(n_nodes):
        algo.cache.add_node(make_node(f"n{i}"))
    config = SchedulerConfig(algorithm=algo, async_bind=False, **cfg)
    return Scheduler(config)


class TestFIFO:
    def test_fifo_order_and_update_in_place(self):
        q = FIFO()
        a, b = make_pod("a"), make_pod("b")
        q.add(a)
        q.add(b)
        a2 = make_pod("a")
        a2.labels["v"] = "2"
        q.update(a2)  # same key: replaces value, keeps position
        got = q.pop()
        assert got.name == "a" and got.labels.get("v") == "2"
        assert q.pop().name == "b"

    def test_delete_skipped_at_pop(self):
        q = FIFO()
        q.add(make_pod("a"))
        q.add(make_pod("b"))
        q.delete("default/a")
        assert q.pop().name == "b"

    def test_pop_timeout(self):
        q = FIFO()
        assert q.pop(timeout=0.05) is None

    def test_pop_all_drains(self):
        q = FIFO()
        for i in range(5):
            q.add(make_pod(f"p{i}"))
        got = q.pop_all()
        assert [p.name for p in got] == [f"p{i}" for i in range(5)]
        assert len(q) == 0


class TestBackoff:
    def test_exponential_growth_capped(self):
        clock = [0.0]
        b = PodBackoff(now=lambda: clock[0])
        got = [b.get_backoff("default/p") for _ in range(8)]
        assert got == [1, 2, 4, 8, 16, 32, 60, 60]

    def test_gc_resets_idle_entries(self):
        clock = [0.0]
        b = PodBackoff(now=lambda: clock[0])
        b.get_backoff("default/p")
        clock[0] += 61
        b.gc()
        assert b.get_backoff("default/p") == 1.0


class TestScheduleOne:
    def test_bind_and_event(self):
        s = _scheduler()
        pod = make_pod("p1")
        s.enqueue(pod)
        assert s.schedule_one(timeout=0.1)
        binder = s.config.binder
        assert binder.bound_node("default/p1") is not None
        evs = s.config.recorder.events("default/p1")
        assert evs and evs[-1].reason == "Scheduled"
        assert s.config.metrics.e2e_scheduling_latency.count == 1

    def test_assumed_pod_visible_to_next_decision(self):
        # The assumed pod occupies capacity before the watch confirms
        # (cache.go:107): a second large pod must go elsewhere.
        algo = GenericScheduler()
        algo.cache.add_node(make_node("n0", milli_cpu=1000))
        algo.cache.add_node(make_node("n1", milli_cpu=1000))
        s = Scheduler(SchedulerConfig(algorithm=algo, async_bind=False))
        s.enqueue(make_pod("p1", cpu="800m"))
        s.enqueue(make_pod("p2", cpu="800m"))
        assert s.schedule_one(0.1) and s.schedule_one(0.1)
        binder = s.config.binder
        assert binder.bound_node("default/p1") != binder.bound_node("default/p2")

    def test_unschedulable_gets_event_and_requeue(self):
        s = _scheduler(n_nodes=1)
        s.config.algorithm.cache.add_node(
            make_node("full", milli_cpu=100))
        pod = make_pod("big", cpu="64")
        s.enqueue(pod)
        assert s.schedule_one(timeout=0.1)
        evs = s.config.recorder.events("default/big")
        assert evs and evs[-1].reason == "FailedScheduling"
        # Requeued after ~1s backoff.
        time.sleep(1.2)
        assert len(s.queue) == 1

    @pytest.mark.parametrize("pending", [True, False])
    def test_backoff_requeues_only_a_pod_that_is_still_pending(self, pending):
        # factory.go:536-549: after the backoff the pod is looked at again
        # and requeued only while it is unassigned; one that was bound (or
        # deleted) meanwhile is dropped, not scheduled a second time.
        asked = []
        s = _scheduler(n_nodes=1)
        s.config.still_pending = lambda pod: asked.append(pod.key) or pending
        s.enqueue(make_pod("big", cpu="64"))
        assert s.schedule_one(timeout=0.1)
        time.sleep(1.3)
        assert asked == ["default/big"]
        assert len(s.queue) == (1 if pending else 0)
        assert ("default/big" in s._first_seen) == pending

    def test_bind_conflict_forgets_assumed_pod(self):
        class RejectingBinder(InMemoryBinder):
            def bind(self, pod, node_name):
                raise BindConflict("already bound")

        algo = GenericScheduler()
        algo.cache.add_node(make_node("n0"))
        s = Scheduler(SchedulerConfig(algorithm=algo,
                                      binder=RejectingBinder(),
                                      async_bind=False))
        s.enqueue(make_pod("p1"))
        assert s.schedule_one(timeout=0.1)
        # ForgetPod ran: the pod no longer occupies cache state.
        assert algo.cache.pod_count() == 0
        evs = s.config.recorder.events("default/p1")
        assert evs and evs[-1].reason == "FailedScheduling"

    @pytest.mark.parametrize("error, written", [
        (BindConflict("already assigned to node n0"), []),
        (RuntimeError("connection reset"), ["default/p1"])])
    def test_a_bind_conflict_writes_no_unschedulable_condition(
            self, error, written):
        # A 409 says the pod IS assigned: PodScheduled=False on it would
        # be false; any other bind error still updates the condition.
        class FailingBinder(InMemoryBinder):
            def bind(self, pod, node_name):
                raise error

        updates = []
        algo = GenericScheduler()
        algo.cache.add_node(make_node("n0"))
        s = Scheduler(SchedulerConfig(
            algorithm=algo, binder=FailingBinder(), async_bind=False,
            condition_updater=lambda pod, *_: updates.append(pod.key)))
        s.enqueue(make_pod("p1"))
        assert s.schedule_one(timeout=0.1)
        assert updates == written

    def test_multi_scheduler_annotation_dispatch(self):
        s = _scheduler()
        other = make_pod("other")
        other.annotations[api.SCHEDULER_NAME_ANNOTATION_KEY] = "my-scheduler"
        s.enqueue(other)  # not responsible: dropped
        assert len(s.queue) == 0
        mine = make_pod("mine")
        s.enqueue(mine)
        assert len(s.queue) == 1


class TestBatchedDrain:
    def test_schedule_pending_places_all(self):
        s = _scheduler(n_nodes=4)
        for i in range(12):
            s.enqueue(make_pod(f"p{i}"))
        assert s.schedule_pending() == 12
        assert s.config.binder.count() == 12
        # Spread over all nodes by LeastRequested.
        nodes = {s.config.binder.bound_node(f"default/p{i}")
                 for i in range(12)}
        assert len(nodes) == 4

    def test_run_loop_drains_queue(self):
        s = _scheduler(n_nodes=2)
        t = s.run(batched=True)
        for i in range(6):
            s.enqueue(make_pod(f"p{i}"))
        deadline = time.time() + 10
        while s.config.binder.count() < 6 and time.time() < deadline:
            time.sleep(0.05)
        s.stop()
        assert s.config.binder.count() == 6

    def test_metrics_exposition_format(self):
        s = _scheduler()
        s.enqueue(make_pod("p1"))
        s.schedule_one(timeout=0.1)
        text = s.config.metrics.expose()
        assert "scheduler_e2e_scheduling_latency_microseconds_bucket" in text
        assert 'le="1000"' in text and 'le="+Inf"' in text

class TestFlightRecorderPersistence:
    def test_ring_survives_a_scheduler_bounce(self, tmp_path,
                                              monkeypatch):
        """ISSUE 7 satellite: the decision ring dumps to KT_FLIGHT_DIR
        on graceful shutdown and reloads on startup, so `kubectl explain
        pod` keeps answering across a restart — with batch ids
        continuing past the reloaded maximum."""
        monkeypatch.setenv("KT_FLIGHT_DIR", str(tmp_path))
        s = _scheduler()
        s.enqueue(make_pod("fp1"))
        assert s.schedule_one(timeout=0.1)
        first = s.config.flight_recorder.explain("default/fp1")
        assert first and first["result"] == "scheduled"
        s.stop()  # dumps the ring
        assert (tmp_path / "flight_ring.json").exists()
        # The "restarted" daemon: a fresh config auto-loads the dump.
        s2 = _scheduler()
        again = s2.config.flight_recorder.explain("default/fp1")
        assert again and again["node"] == first["node"]
        assert again["batch_id"] == first["batch_id"]
        # New decisions mint ids PAST the reloaded ones.
        s2.enqueue(make_pod("fp2"))
        assert s2.schedule_one(timeout=0.1)
        newer = s2.config.flight_recorder.explain("default/fp2")
        assert newer["batch_id"] > first["batch_id"]

    def test_abandon_skips_the_dump_and_missing_dump_is_fine(
            self, tmp_path, monkeypatch):
        """SIGKILL-style abandon must not pretend to be a graceful
        shutdown (no dump); startup with no dump present is a no-op."""
        monkeypatch.setenv("KT_FLIGHT_DIR", str(tmp_path))
        s = _scheduler()
        s.enqueue(make_pod("fa1"))
        assert s.schedule_one(timeout=0.1)
        s.abandon()
        assert not (tmp_path / "flight_ring.json").exists()
        s2 = _scheduler()  # loads nothing, works normally
        assert s2.config.flight_recorder.explain("default/fa1") is None

    def test_torn_dump_never_blocks_startup(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KT_FLIGHT_DIR", str(tmp_path))
        (tmp_path / "flight_ring.json").write_text("{not json")
        s = _scheduler()
        s.enqueue(make_pod("ft1"))
        assert s.schedule_one(timeout=0.1)
        assert s.config.flight_recorder.explain("default/ft1")
        # Valid JSON of the wrong shape must not block startup either.
        (tmp_path / "flight_ring.json").write_text(
            '{"records": [{"batch_id": null}, "not-a-dict"]}')
        s2 = _scheduler()
        s2.enqueue(make_pod("ft2"))
        assert s2.schedule_one(timeout=0.1)


class TestDrainPadding:
    def test_padding_is_decision_neutral(self):
        """schedule_pending pads small drains to power-of-two buckets;
        pad pods are infeasible everywhere and must not change any real
        pod's placement (tie counter bumps only on success)."""
        from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
        algo = GenericScheduler()
        for i in range(5):
            algo.cache.add_node(make_node(f"n{i}", milli_cpu=2000))
        pods = [make_pod(f"q{i}", cpu="300m") for i in range(11)]
        bare = algo.schedule_batch([make_pod(f"q{i}", cpu="300m")
                                    for i in range(11)])
        s = _scheduler(n_nodes=0)
        for i in range(5):
            s.config.algorithm.cache.add_node(make_node(f"n{i}",
                                                        milli_cpu=2000))
        for p in pods:
            s.enqueue(p)
        assert s.schedule_pending() == 11  # 11 -> padded to 16 internally
        binder = s.config.binder
        got = [binder.bound_node(f"default/q{i}") for i in range(11)]
        assert got == bare
        # No pad pod leaked into the binder or the cache.
        assert binder.count() == 11
        assert s.config.algorithm.cache.pod_count() == 11
