"""Serving-path tests (ISSUE 8): deadline batch formation, the unified
DrainPipeline entry path, per-decision latency metrics, and the arrival
generators behind the SERVING artifact."""

from __future__ import annotations

import time

from kubernetes_tpu.api import types as api
from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
from kubernetes_tpu.scheduler import batchformer
from kubernetes_tpu.scheduler.batchformer import (BatchFormer, first_seen,
                                                  stamp_first_seen)
from kubernetes_tpu.scheduler.binder import InMemoryBinder
from kubernetes_tpu.scheduler.queue import FIFO
from kubernetes_tpu.scheduler.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.utils import metrics

from helpers import make_node, make_pod


def _daemon(n_nodes: int = 4, **cfg) -> Scheduler:
    algo = GenericScheduler()
    for i in range(n_nodes):
        algo.cache.add_node(make_node(f"n{i}"))
    return Scheduler(SchedulerConfig(algorithm=algo,
                                     binder=InMemoryBinder(),
                                     async_bind=False, **cfg))


def _former(queue, ladder=(16, 32, 64), chunk=64, cap=64,
            deadline_s=0.0) -> BatchFormer:
    f = BatchFormer(queue=queue, ladder_fn=lambda: list(ladder),
                    chunk_fn=lambda: chunk, cap_fn=lambda: cap)
    f.deadline_s = deadline_s
    return f


class TestBatchFormer:
    def test_deadline_off_solves_whatever_arrived(self):
        q = FIFO()
        for i in range(5):
            q.add(make_pod(f"im{i}"))
        t0 = time.perf_counter()
        batch = _former(q).form(wait_first=False)
        assert len(batch.pods) == 5
        assert time.perf_counter() - t0 < 0.05  # no linger
        assert not batch.deadline_missed

    def test_lone_pod_exits_at_the_idle_window_not_the_deadline(self):
        q = FIFO()
        q.add(make_pod("lone"))
        f = _former(q, deadline_s=1.0)
        t0 = time.perf_counter()
        batch = f.form(wait_first=False)
        waited = time.perf_counter() - t0
        assert [p.name for p in batch.pods] == ["lone"]
        # The stream is silent: the former hands off after the idle
        # window (~60 ms), never burning the whole 1 s deadline —
        # lingering past a quiet stream is latency that cannot grow
        # the batch.
        assert waited < 0.5
        assert waited >= batchformer.IDLE_WINDOW_S * 0.8
        assert not batch.deadline_missed

    def test_deadline_still_bounds_a_live_trickle(self):
        """A stream that keeps landing pods inside the idle window
        coalesces until the DEADLINE, not forever."""
        q = FIFO()
        q.add(make_pod("t-first"))
        f = _former(q, ladder=(64,), chunk=64, deadline_s=0.1)
        stop = time.perf_counter() + 1.0
        seq = [0]

        import threading

        def feeder():
            while time.perf_counter() < stop:
                seq[0] += 1
                q.add(make_pod(f"t-feed{seq[0]}"))
                time.sleep(0.01)  # well inside the idle window

        th = threading.Thread(target=feeder, daemon=True)
        th.start()
        t0 = time.perf_counter()
        batch = f.form(wait_first=False)
        waited = time.perf_counter() - t0
        assert 0.08 <= waited <= 0.4  # the deadline, not the feeder's 1 s
        assert len(batch.pods) > 3    # it coalesced while waiting
        th.join(timeout=2)

    def test_burst_exits_early_at_the_chunk_cap(self):
        q = FIFO()
        for i in range(70):
            q.add(make_pod(f"b{i}"))
        f = _former(q, chunk=64, deadline_s=5.0)
        t0 = time.perf_counter()
        batch = f.form(wait_first=False)
        # pop_all drained everything before the linger loop; the cap
        # bounds further waiting, so a full burst never burns 5 s.
        assert len(batch.pods) == 70
        assert time.perf_counter() - t0 < 1.0

    def test_target_adapts_down_on_trickle_and_up_on_burst(self):
        q = FIFO()
        f = _former(q, ladder=(16, 32, 64), deadline_s=0.02)
        f._target = 32
        q.add(make_pod("t0"))
        f.form(wait_first=False)  # deadline fires with 1 < 32
        assert f.target == 16
        for i in range(40):
            q.add(make_pod(f"bb{i}"))
        f.form(wait_first=False)  # 40 >= 16: grow one step
        assert f.target == 32

    def test_target_is_always_a_warm_bucket(self):
        f = _former(FIFO(), ladder=(16, 32, 64), chunk=32)
        # Buckets above the chunk are unreachable targets.
        assert f._buckets() == [16, 32]
        assert f.target in (16, 32)

    def test_deadline_miss_counter_on_overrun(self):
        class SlowQueue:
            def __init__(self):
                self.pod = make_pod("slow")
                self.calls = 0

            def degraded(self):
                return False

            def pop_all(self, wait_first=True, timeout=None):
                if self.calls == 0:
                    self.calls += 1
                    return [self.pod]
                time.sleep(0.06)  # GIL-hog analogue: top-up overruns
                return []

        before = metrics.BATCH_DEADLINE_MISSES.value
        f = _former(SlowQueue(), deadline_s=0.02)
        batch = f.form(wait_first=False)
        assert batch.deadline_missed
        assert metrics.BATCH_DEADLINE_MISSES.value == before + 1

    def test_formation_latency_histogram_records(self):
        before = metrics.BATCH_FORMATION_LATENCY.count
        q = FIFO()
        q.add(make_pod("fl"))
        _former(q, deadline_s=0.01).form(wait_first=False)
        assert metrics.BATCH_FORMATION_LATENCY.count == before + 1

    def test_deadline_parses_milliseconds_and_is_off_otherwise(
            self, monkeypatch):
        monkeypatch.setenv("KT_BATCH_DEADLINE_MS", "250")
        assert batchformer._env_deadline_s() == 0.25
        for off in ("", "soon", "-5"):
            monkeypatch.setenv("KT_BATCH_DEADLINE_MS", off)
            assert batchformer._env_deadline_s() == 0.0
        monkeypatch.delenv("KT_BATCH_DEADLINE_MS", raising=False)
        assert batchformer._env_deadline_s() == 0.0

    def test_first_seen_stamp_survives_requeue(self):
        pod = make_pod("fs")
        stamp_first_seen(pod)
        t0 = first_seen(pod)
        time.sleep(0.01)
        stamp_first_seen(pod)  # the requeue path re-stamps idempotently
        assert first_seen(pod) == t0


class TestDeadlineEdgeCases:
    def test_deadline_never_splits_a_held_gang(self):
        """The deadline firing mid-hold must not pull an incomplete
        gang into the batch: held members are invisible to the former
        until the queue releases the gang whole."""
        q = FIFO()
        for i in range(2):
            m = make_pod(f"g-m{i}")
            m.annotations["scheduling.kt.io/gang"] = "g"
            m.annotations["scheduling.kt.io/gang-size"] = "3"
            q.add(m)
        q.add(make_pod("solo"))
        f = _former(q, deadline_s=0.03)
        batch = f.form(wait_first=False)
        assert [p.name for p in batch.pods] == ["solo"]
        assert q.held_gangs() == {"g": 2}
        # Completing the gang releases every member into ONE batch.
        m = make_pod("g-m2")
        m.annotations["scheduling.kt.io/gang"] = "g"
        m.annotations["scheduling.kt.io/gang-size"] = "3"
        q.add(m)
        batch = f.form(wait_first=False)
        assert sorted(p.name for p in batch.pods) == \
            ["g-m0", "g-m1", "g-m2"]

    def test_degradation_wins_over_the_deadline(self):
        """Past the watermark the former must shed immediately — one
        largest-warmed-bucket chunk, no lingering."""
        q = FIFO(high_watermark=8)
        for i in range(20):
            q.add(make_pod(f"dg{i}"))
        assert q.degraded()
        before = metrics.DEGRADED_DRAINS.value
        formed_before = metrics.BATCH_FORMATION_LATENCY.count
        f = _former(q, cap=8, deadline_s=5.0)
        t0 = time.perf_counter()
        batch = f.form(wait_first=False)
        assert batch.degraded
        assert len(batch.pods) == 8
        assert time.perf_counter() - t0 < 0.5  # no 5 s linger
        assert metrics.DEGRADED_DRAINS.value == before + 1
        # A degraded formation still counts in the formation histogram
        # (formation count == drain count must hold under shedding).
        assert metrics.BATCH_FORMATION_LATENCY.count == formed_before + 1

    def test_single_pod_binds_within_twice_the_deadline_on_floor_bucket(
            self):
        """A lone serving arrival must bind within 2x the declared
        deadline, solved on the pre-warmed floor bucket."""
        daemon = _daemon(n_nodes=6)
        daemon.STREAM_THRESHOLD = 64
        daemon.stream_chunk = 64
        daemon.stream_min_bucket = 16
        # Warm the floor bucket off the clock (prewarm's job in a rig).
        warm = [make_pod(f"w{i}", cpu="50m") for i in range(3)]
        for p in warm:
            daemon.enqueue(p)
        daemon.schedule_pending(wait_first=False)
        deadline_s = 0.5
        daemon.pipeline.former.deadline_s = deadline_s
        loop = daemon.run(batched=True)
        try:
            pod = make_pod("lone-arrival", cpu="50m")
            t0 = time.perf_counter()
            daemon.enqueue(pod)
            bound_at = None
            while time.perf_counter() - t0 < 4 * deadline_s:
                if daemon.config.binder.bound_node("default/lone-arrival"):
                    bound_at = time.perf_counter()
                    break
                time.sleep(0.005)
            assert bound_at is not None, "lone pod never bound"
            assert bound_at - t0 <= 2 * deadline_s, \
                f"bound after {bound_at - t0:.3f}s > 2x deadline"
            # The floor bucket carried it (adaptive target never left
            # the warm ladder).
            assert daemon.pipeline.former.target in \
                daemon.effective_ladder()
        finally:
            daemon.stop()
            loop.join(timeout=2)


class TestUnifiedDrainPath:
    def test_schedule_pending_is_the_only_drain_entry(self):
        """The daemon has exactly one batched drain path: pipeline.drain.
        The pre-pipeline per-mode control flows are gone from the
        daemon."""
        daemon = _daemon()
        assert not hasattr(daemon, "_solve_drain")
        assert not hasattr(daemon, "_schedule_pending_stream")
        assert not hasattr(daemon, "_commit_chunk")
        calls = []
        daemon.pipeline.drain = lambda wait_first=True, timeout=None: \
            calls.append((wait_first, timeout)) or 7
        assert daemon.schedule_pending(wait_first=False, timeout=0.1) == 7
        assert calls == [(False, 0.1)]

    def test_all_three_modes_route_through_the_pipeline(self):
        """One-shot (gang), streamed, and joint drains all flow through
        DrainPipeline._solve — no daemon-level mode forks."""
        from kubernetes_tpu.utils import featuregate
        daemon = _daemon(n_nodes=6)
        daemon.STREAM_THRESHOLD = 8
        daemon.stream_chunk = 8
        daemon.stream_min_bucket = 8
        seen_modes = []
        real_stream = daemon.pipeline._solve_stream
        real_oneshot = daemon.pipeline._solve_oneshot

        def spy_stream(pods, **kw):
            seen_modes.append("stream")
            return real_stream(pods, **kw)

        def spy_oneshot(pods, **kw):
            seen_modes.append(
                "joint" if kw.get("joint") else
                "gang" if kw.get("gangs") else "oneshot")
            return real_oneshot(pods, **kw)

        daemon.pipeline._solve_stream = spy_stream
        daemon.pipeline._solve_oneshot = spy_oneshot
        # Streamed drain.
        for i in range(10):
            daemon.enqueue(make_pod(f"sm{i}", cpu="50m"))
        daemon.schedule_pending(wait_first=False)
        # Gang drain -> one-shot.
        for i in range(2):
            m = make_pod(f"ug-m{i}", cpu="50m")
            m.annotations["scheduling.kt.io/gang"] = "ug"
            m.annotations["scheduling.kt.io/gang-size"] = "2"
            daemon.enqueue(m)
        daemon.schedule_pending(wait_first=False)
        # Joint drain.
        old_gate = featuregate.DEFAULT_FEATURE_GATE
        featuregate.set_default(
            featuregate.FeatureGate({"JointSolver": True}))
        try:
            daemon.enqueue(make_pod("jt0", cpu="50m"))
            daemon.schedule_pending(wait_first=False)
        finally:
            featuregate.set_default(old_gate)
        daemon.wait_for_binds()
        assert seen_modes == ["stream", "gang", "joint"]
        assert daemon.config.binder.count() == 13

    def test_pipeline_crash_handler_requeues(self):
        """The crash-requeue contract moved with the control flow: a
        solve that raises requeues every untracked pod through the
        backoff path."""
        from kubernetes_tpu.scheduler.backoff import PodBackoff
        daemon = _daemon()
        daemon.backoff = PodBackoff(default_duration=0.01,
                                    max_duration=0.05)

        def boom(*a, **kw):
            raise RuntimeError("injected solve crash")

        daemon.config.algorithm.schedule_batch = boom
        daemon.config.algorithm.schedule_batch_stream = boom
        daemon.enqueue(make_pod("crash1"))
        assert daemon.schedule_pending(wait_first=False) == 1
        errors = daemon.config.metrics.scheduling_attempts \
            .labels(result="error").value
        assert errors >= 1
        # The requeue worker puts it back on the queue after backoff.
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and len(daemon.queue) == 0:
            time.sleep(0.01)
        assert len(daemon.queue) == 1
        daemon.stop()


class TestDecisionLatencyMetric:
    def test_bind_ack_records_e2e_decision_latency(self):
        before = metrics.E2E_DECISION_LATENCY.count
        daemon = _daemon()
        for i in range(3):
            daemon.enqueue(make_pod(f"dl{i}", cpu="50m"))
        daemon.schedule_pending(wait_first=False)
        daemon.wait_for_binds()
        assert metrics.E2E_DECISION_LATENCY.count == before + 3
        # Per-pod values, not amortized: the sum is >= 3 distinct waits.
        assert metrics.E2E_DECISION_LATENCY.sum > 0

    def test_single_pod_path_records_too(self):
        before = metrics.E2E_DECISION_LATENCY.count
        daemon = _daemon()
        daemon.enqueue(make_pod("one-dl", cpu="50m"))
        assert daemon.schedule_one(timeout=0.1)
        daemon.wait_for_binds()
        assert metrics.E2E_DECISION_LATENCY.count == before + 1

    def test_watch_redelivery_does_not_reset_the_clock(self):
        """A MODIFIED watch event (e.g. the scheduler's own condition
        write) delivers a FRESH pod object; the first-seen registry
        must keep the ORIGINAL admission time for the key, or retried
        tail pods — exactly what the SLO histogram exists to measure —
        report only their final attempt's latency."""
        daemon = _daemon()
        first = make_pod("redeliver", cpu="50m")
        daemon.enqueue(first)
        t0 = first._kt_first_seen
        time.sleep(0.02)
        fresh = make_pod("redeliver", cpu="50m")  # a new object, same key
        daemon.enqueue(fresh)
        assert fresh._kt_first_seen == t0
        # Binding clears the registry entry for the key.
        daemon.schedule_pending(wait_first=False)
        daemon.wait_for_binds()
        assert "default/redeliver" not in daemon._first_seen


class TestArrivalGenerators:
    def test_poisson_is_deterministic_and_rate_shaped(self):
        from kubernetes_tpu.perf import serving
        a = serving.poisson_arrivals(100.0, 5.0, seed=3)
        b = serving.poisson_arrivals(100.0, 5.0, seed=3)
        assert a == b
        assert all(n == 1 for _, n in a)
        assert 250 < len(a) < 750  # ~500 expected
        assert all(0 <= t < 5.0 for t, _ in a)

    def test_burst_replay_uses_the_recorded_trace(self):
        from kubernetes_tpu.perf import serving
        events = serving.burst_arrivals()
        assert events == [(t, n) for t, n in
                          serving.RECORDED_BURST_TRACE]
        half = serving.burst_arrivals(scale=0.5)
        assert sum(n for _, n in half) < sum(n for _, n in events)

    def test_ramp_rate_grows(self):
        from kubernetes_tpu.perf import serving
        events = serving.ramp_arrivals(10.0, 100.0, 4.0, tick_s=0.5)
        counts = [n for _, n in events]
        assert counts[-1] > counts[0]

    def test_load_trace_roundtrip(self, tmp_path):
        import json

        from kubernetes_tpu.perf import serving
        p = tmp_path / "trace.json"
        p.write_text(json.dumps([[0.0, 5], [1.5, 10]]))
        assert serving.load_trace(str(p)) == [(0.0, 5), (1.5, 10)]


def test_serving_smoke_over_http_rig():
    """A seconds-long serving run through the REAL rig (HTTP apiserver +
    full daemon + deadline micro-batching): every pod binds and the
    artifact row carries the latency/SLO fields the ratchet reads."""
    from kubernetes_tpu.perf import serving
    row = serving.run_workload(
        "poisson", serving.poisson_arrivals(30.0, 2.0, seed=5),
        n_nodes=20, deadline_ms=100.0, slo_ms=5000.0,
        attainment_floor_pct=90.0, stream_chunk=512, quiet=True)
    assert row["unbound"] == 0
    assert row["bound"] == row["pods"] > 0
    assert row["latency_ms"]["p99"] >= row["latency_ms"]["p50"] > 0
    assert row["slo"]["attainment_pct"] >= 90.0
    assert row["batches_formed"] > 0
