"""Churn & recovery (ISSUE 7 tentpole): crash-safe scheduler restart
(scheduler/recovery.py + Scheduler.abandon), the resident-state
invariant checker (cache/verifier.py), bounded-queue degradation
(queue high watermark + largest-bucket drains), and a miniature churn
soak through the real chaos rig (perf/soak.py)."""

from __future__ import annotations

import time

from kubernetes_tpu.api import types as api
from kubernetes_tpu.apiserver.memstore import MemStore
from kubernetes_tpu.cache.verifier import Verifier
from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
from kubernetes_tpu.scheduler.backoff import PodBackoff
from kubernetes_tpu.scheduler.binder import InMemoryBinder
from kubernetes_tpu.scheduler.factory import ConfigFactory
from kubernetes_tpu.scheduler.queue import FIFO
from kubernetes_tpu.scheduler.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.utils import metrics

from helpers import make_node, make_pod


def _node_json(name: str, cpu: str = "32") -> dict:
    return {"metadata": {"name": name,
                         "labels": {"kubernetes.io/hostname": name}},
            "status": {"allocatable": {"cpu": cpu, "memory": "64Gi",
                                       "pods": "110"},
                       "conditions": [{"type": "Ready",
                                       "status": "True"}]}}


def _pod_json(name: str, cpu: str = "100m") -> dict:
    return {"metadata": {"name": name, "namespace": "default"},
            "spec": {"containers": [{
                "name": "c", "resources": {"requests": {"cpu": cpu}}}]}}


def _daemon(n_nodes: int = 4, **queue_kw) -> Scheduler:
    algo = GenericScheduler()
    for i in range(n_nodes):
        algo.cache.add_node(make_node(f"n{i}"))
    d = Scheduler(SchedulerConfig(algorithm=algo, binder=InMemoryBinder(),
                                  async_bind=False))
    for k, v in queue_kw.items():
        setattr(d.queue, k, v)
    return d


# -- bounded-queue degradation ----------------------------------------------

class TestDegradation:
    def test_watermark_flips_degraded_and_gauge(self):
        d = _daemon(high_watermark=5)
        assert not d.queue.degraded()
        for i in range(5):
            d.enqueue(make_pod(f"w{i}"))
        assert d.queue.degraded()
        assert d.config.metrics.queue_degraded.value == 1.0
        assert d.config.metrics.queue_high_watermark.value == 5
        exposed = d.config.metrics.expose()
        assert "scheduler_queue_degraded 1" in exposed
        assert "scheduler_queue_high_watermark 5" in exposed

    def test_degraded_drain_caps_batch_at_largest_warmed_bucket(self):
        d = _daemon(n_nodes=6, high_watermark=4)
        d.STREAM_THRESHOLD = 8
        d.stream_chunk = 8
        d.stream_min_bucket = 8
        assert d.degraded_drain_cap() == 8
        before = metrics.DEGRADED_DRAINS.value
        for i in range(20):
            d.enqueue(make_pod(f"dg{i}", cpu="50m"))
        popped = d.schedule_pending(wait_first=False)
        assert popped == 8  # one largest-bucket chunk, not the storm
        assert len(d.queue) == 12
        assert metrics.DEGRADED_DRAINS.value > before
        # Iterating drains the backlog; below the watermark the drain
        # reverts to pop-everything.
        while len(d.queue):
            d.schedule_pending(wait_first=False)
        d.wait_for_binds()
        assert d.config.binder.count() == 20

    def test_degraded_mode_bypasses_gang_hold(self):
        q = FIFO(high_watermark=3)
        for i in range(3):
            q.add(make_pod(f"f{i}"))
        assert q.degraded()
        member = make_pod("g-m0")
        member.annotations["scheduling.kt.io/gang"] = "g"
        member.annotations["scheduling.kt.io/gang-size"] = "4"
        q.add(member)
        # Not held: flows straight through (the solver's all-or-nothing
        # reduction still protects atomicity at admission).
        assert q.held_gangs() == {}
        assert "default/g-m0" in q

    def test_gang_hold_intact_below_watermark(self):
        q = FIFO(high_watermark=100)
        member = make_pod("g2-m0")
        member.annotations["scheduling.kt.io/gang"] = "g2"
        member.annotations["scheduling.kt.io/gang-size"] = "2"
        q.add(member)
        assert q.held_gangs() == {"g2": 1}

    def test_pop_some_bounds_and_preserves_priority_order(self):
        q = FIFO(high_watermark=0)
        low, high = make_pod("low"), make_pod("high")
        high.annotations["scheduling.kt.io/priority"] = "10"
        q.add(low)
        q.add(high)
        got = q.pop_some(1, wait_first=False)
        assert [p.name for p in got] == ["high"]
        assert len(q) == 1

    def test_peak_depth_tracked(self):
        q = FIFO(high_watermark=0)
        for i in range(7):
            q.add(make_pod(f"pk{i}"))
        q.pop_all(wait_first=False)
        assert q.peak_depth == 7


# -- crash-safe restart ------------------------------------------------------

class TestRestartRecovery:
    def _control_plane(self, n_nodes=4, n_pods=0):
        store = MemStore()
        for i in range(n_nodes):
            store.create("nodes", _node_json(f"rn{i}"))
        for i in range(n_pods):
            store.create("pods", _pod_json(f"rp{i}"))
        return store

    def _factory(self, store):
        f = ConfigFactory(store)
        f.daemon.backoff = PodBackoff(default_duration=0.05,
                                      max_duration=0.5)
        return f

    def _wait_all_bound(self, store, timeout=30.0) -> list[dict]:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            objs, _ = store.list("pods")
            if objs and all((o.get("spec") or {}).get("nodeName")
                            for o in objs):
                return objs
            time.sleep(0.05)
        raise AssertionError("pods did not all bind")

    def test_mid_drain_kill_no_strand_no_double_bind(self):
        """SIGKILL between solve and bind: the replacement incarnation
        reconciles (relist -> re-adopt/requeue/expire), resumes the
        drain, and every pod lands exactly once."""
        store = self._control_plane(n_pods=0)
        f1 = self._factory(store)
        f1.run()
        # Track every nodeName transition: a bound pod moving nodes
        # would be the double-bind the CAS + recovery must prevent.
        transitions: dict[str, list[str]] = {}
        w = store.watch(["pods"], from_rv=0)
        for i in range(16):
            store.create("pods", _pod_json(f"kp{i}"))
        time.sleep(0.1)  # mid-drain: some pods popped, not all bound
        f1.abandon()
        f2 = self._factory(store)
        f2.run()
        assert f2.last_recovery is not None
        assert f2.last_recovery["pods_listed"] == 16
        objs = self._wait_all_bound(store)
        assert len(objs) == 16
        while True:
            ev = w.next(timeout=0.2)
            if ev is None:
                break
            node = (ev.object.get("spec") or {}).get("nodeName") or ""
            if node:
                transitions.setdefault(ev.key, [])
                if not transitions[ev.key] or \
                        transitions[ev.key][-1] != node:
                    transitions[ev.key].append(node)
        w.stop()
        double = {k: v for k, v in transitions.items() if len(v) > 1}
        assert double == {}, f"pods re-bound to different nodes: {double}"
        # No orphaned assumes once the confirm stream quiesces.
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and any(
                a for _k, _n, a in f2.algorithm.cache.tracked_pods()):
            time.sleep(0.05)
        assert not any(a for _k, _n, a
                       in f2.algorithm.cache.tracked_pods())
        f2.stop()

    def test_reconcile_expires_stale_assume_and_requeues(self):
        """A pod the dead incarnation assumed but never bound must not
        strand: reconcile forgets the stale assume and the pod requeues
        (here the stale state is injected directly into a fresh
        daemon's cache to isolate the reconciler)."""
        from kubernetes_tpu.scheduler import recovery
        store = self._control_plane(n_pods=2)
        f = self._factory(store)
        # Simulate pre-crash residue BEFORE the loop starts: rp0 assumed
        # but unbound at the apiserver, plus a ghost pod the apiserver
        # never heard of.
        stale = api.pod_from_json(store.get("pods", "default/rp0"))
        f.algorithm.cache.add_node(
            api.node_from_json(store.get("nodes", "rn0")))
        f.algorithm.cache.assume_pod(stale, "rn0")
        ghost = make_pod("ghost", node_name="rn0")
        f.algorithm.cache.add_pod(ghost)
        report = recovery.reconcile(f.daemon, store)
        assert report["expired"] == 1      # the stale assume
        assert report["removed"] == 1      # the ghost
        assert report["requeued"] == 2     # rp0 + rp1 back on the queue
        assert "default/rp0" in f.daemon.queue
        assert not f.algorithm.cache.contains("default/ghost")
        assert not f.algorithm.cache.is_assumed("default/rp0")

    def test_reconcile_readopts_bound_pods(self):
        from kubernetes_tpu.scheduler import recovery
        store = self._control_plane(n_pods=0)
        store.create("pods", _pod_json("bp0"))
        store.bind("default", "bp0", "rn1")
        d = _daemon(n_nodes=0)
        report = recovery.reconcile(d, store)
        assert report["readopted"] == 1
        assert d.config.algorithm.cache.contains("default/bp0")
        assert not d.config.algorithm.cache.is_assumed("default/bp0")

    def test_reconcile_readopts_pod_tracked_on_wrong_node(self):
        """A lost watch event can leave a pod tracked on node Y while
        the apiserver has it bound to X — reconcile must move the
        attachment (and its capacity accounting), not skip it because
        the key already exists."""
        from kubernetes_tpu.scheduler import recovery
        store = self._control_plane(n_pods=0)
        store.create("pods", _pod_json("wn0"))
        store.bind("default", "wn0", "rn1")
        d = _daemon(n_nodes=0)
        wrong = make_pod("wn0", node_name="rn3")
        d.config.algorithm.cache.add_pod(wrong)
        report = recovery.reconcile(d, store)
        assert report["readopted"] == 1
        assert d.config.algorithm.cache.get_pod(
            "default/wn0").node_name == "rn1"

    def test_reconcile_reseeds_resident_mirror(self):
        """Recovery must invalidate the device mirror AND mark the cache
        for a full rebuild, so the first post-restart drain re-uploads
        epoch-consistent state."""
        from kubernetes_tpu.scheduler import recovery
        store = self._control_plane(n_pods=0)
        d = _daemon(n_nodes=4)
        algo = d.config.algorithm
        algo.schedule_batch([make_pod("warm", cpu="50m")])
        assert algo.resident.dc is not None
        epoch_before = algo.cache.tensor_epoch
        recovery.reconcile(d, store)
        assert algo.resident.dc is None
        algo.schedule_batch([make_pod("post", cpu="50m")])
        assert algo.cache.tensor_epoch > epoch_before


# -- resident-state invariant checker ---------------------------------------

class TestVerifier:
    def _engine(self, n_nodes=6) -> GenericScheduler:
        algo = GenericScheduler()
        for i in range(n_nodes):
            algo.cache.add_node(make_node(f"vn{i}"))
        return algo

    def test_clean_state_passes(self):
        algo = self._engine()
        algo.schedule_batch([make_pod(f"vc{i}", cpu="50m")
                             for i in range(4)])
        v = Verifier(algo.cache, resident=algo.resident)
        assert v.verify_once() == []
        assert v.passes == 1

    def test_corrupt_aggregate_row_is_flagged_and_healed(self):
        algo = self._engine()
        algo.schedule_batch([make_pod("va0", cpu="50m")])
        before = metrics.CACHE_INVARIANT_VIOLATIONS.value
        with algo.cache.lock:
            algo.cache._agg.requested[0, 0] += 13
        v = Verifier(algo.cache, resident=algo.resident)
        viol = v.verify_once()
        # The corrupted HOST row necessarily also disagrees with the
        # (correct) device copy, so a device_row finding may ride along.
        assert any(x.kind == "aggregates" for x in viol)
        assert metrics.CACHE_INVARIANT_VIOLATIONS.value > before
        # Self-heal: the forced re-snapshot rebuilt the aggregates.
        assert v.verify_once() == []

    def test_corrupt_device_row_is_flagged_and_healed(self):
        import jax.numpy as jnp  # noqa: F401 — .at[] below needs jax
        algo = self._engine()
        # A drain syncs the mirror; an in-place device corruption is the
        # drift the dirty-row protocol could otherwise hide forever.
        daemon = Scheduler(SchedulerConfig(algorithm=algo,
                                           binder=InMemoryBinder(),
                                           async_bind=False))
        for i in range(4):
            daemon.enqueue(make_pod(f"vd{i}", cpu="50m"))
        daemon.schedule_pending(wait_first=False)
        daemon.wait_for_binds()
        # A second drain scatters the assumes' dirty rows into the
        # mirror; corrupt a row with NO pending deltas (the checker
        # rightly skips dirty rows — their divergence is legitimate).
        algo.schedule_batch([make_pod("vd-flush", cpu="50m")])
        with algo.cache.lock:
            assert algo.resident.in_sync(algo.cache._nt,
                                         algo.cache.space,
                                         algo.cache.tensor_epoch)
            row = min(set(range(6)) - algo.cache._dirty_rows)
        dc = algo.resident.dc
        if hasattr(dc, "res16"):  # narrow wire form: requested cpu = col 3
            algo.resident.dc = dc._replace(
                res16=dc.res16.at[row, 3].add(999))
        else:
            algo.resident.dc = dc._replace(
                requested=dc.requested.at[row, 0].add(999))
        # (every row of the node axis sampled: 6 live of 128)
        v = Verifier(algo.cache, resident=algo.resident, sample=128)
        viol = v.verify_once()
        assert any(x.kind == "device_row" for x in viol)
        assert algo.resident.dc is None  # heal invalidated the mirror
        algo.schedule_batch([make_pod("vd-post", cpu="50m")])
        assert v.verify_once() == []

    def test_out_of_sync_mirror_is_not_a_violation(self):
        """A mirror legitimately awaiting a full re-upload (epoch moved)
        must be skipped, not flagged."""
        algo = self._engine()
        algo.schedule_batch([make_pod("vo0", cpu="50m")])
        algo.cache.add_node(make_node("joiner"))  # a dirty row pending
        algo.cache.force_resnapshot()             # an epoch bump pending
        v = Verifier(algo.cache, resident=algo.resident)
        assert v.verify_once() == []

    def test_apiserver_ghost_is_flagged_after_grace_and_repaired(self):
        store = MemStore()
        store.create("nodes", _node_json("an0"))
        algo = self._engine(n_nodes=1)
        # Cache believes a pod is confirmed-bound; apiserver never heard
        # of it — persistent across the grace re-read, so a violation.
        ghost = make_pod("aghost", node_name="vn0")
        algo.cache.add_pod(ghost)
        v = Verifier(algo.cache, resident=algo.resident,
                     truth=lambda: store.list("pods")[0], grace_s=0.05)
        viol = v.verify_once()
        assert any(x.kind == "apiserver" for x in viol)
        assert not algo.cache.contains("default/aghost")  # repaired
        assert v.verify_once() == []

    def test_apiserver_missing_bound_pod_is_flagged_and_adopted(self):
        store = MemStore()
        store.create("nodes", _node_json("an1"))
        store.create("pods", _pod_json("abound"))
        store.bind("default", "abound", "vn0")
        algo = self._engine(n_nodes=1)
        v = Verifier(algo.cache, resident=algo.resident,
                     truth=lambda: store.list("pods")[0], grace_s=0.05)
        viol = v.verify_once()
        assert any(x.kind == "apiserver" for x in viol)
        assert algo.cache.contains("default/abound")
        assert v.verify_once() == []

    def test_wrong_node_drift_is_flagged_and_converges(self):
        """Cache says node A, apiserver says node B: the violation must
        fire once, the repair must MOVE the pod (not skip it because
        the key exists), and the next pass must be clean — a heal loop
        that never converges would re-pay a full re-upload every
        period forever."""
        store = MemStore()
        store.create("nodes", _node_json("an2"))
        store.create("pods", _pod_json("moved"))
        store.bind("default", "moved", "vn1")
        algo = self._engine(n_nodes=2)
        algo.cache.add_pod(make_pod("moved", node_name="vn0"))
        v = Verifier(algo.cache, resident=algo.resident,
                     truth=lambda: store.list("pods")[0], grace_s=0.05)
        viol = v.verify_once()
        assert any(x.kind == "apiserver" and "cached on" in x.detail
                   for x in viol)
        assert algo.cache.get_pod("default/moved").node_name == "vn1"
        assert v.verify_once() == []

    def test_assumed_pod_is_not_apiserver_drift(self):
        """An optimistically assumed pod whose bind is in flight is the
        normal state machine, not drift."""
        store = MemStore()
        store.create("pods", _pod_json("inflight"))
        algo = self._engine(n_nodes=1)
        pod = make_pod("inflight")
        algo.cache.assume_pod(pod, "vn0")
        v = Verifier(algo.cache, resident=algo.resident,
                     truth=lambda: store.list("pods")[0], grace_s=0.05)
        assert [x for x in v.verify_once()
                if x.kind == "apiserver"] == []


# -- miniature churn soak through the real rig -------------------------------

def test_mini_soak_smoke():
    """The composed scenario end-to-end at toy scale: chaos rules on,
    storm past the watermark, rolling updates, node drain/fail/re-add
    with changed capacity, mid-drain kill + recovery — zero invariant
    violations, zero double-binds, bounded queue, 100% restart
    parity."""
    from kubernetes_tpu.perf.soak import run_soak
    rec = run_soak(n_nodes=10, duration_s=2.0, seed_pods=30,
                   storm_pods=80, rolling_waves=1, wave_size=15,
                   drain_nodes=2, kill_burst=40, high_watermark=40,
                   stream_chunk=256, heartbeat_period=0.5,
                   verify_period=0.5, settle_timeout=120,
                   parity_samples=8, quiet=True)
    assert rec["invariant_violations"] == 0, \
        rec["invariant_violations_by_kind"]
    assert rec["reconciliation"]["double_binds"] == 0
    assert rec["reconciliation"]["stranded_pending"] == 0
    assert rec["reconciliation"]["orphaned_assumes"] == 0
    assert rec["queue_depth"]["monotonic_growth"] is False
    assert rec["restart"]["killed_mid_drain"] is True
    assert rec["restart_parity"]["decision_parity_pct"] == 100.0
    assert rec["scale"]["pods_scheduled_total"] >= 30
    assert rec["verifier_passes"] >= 1
