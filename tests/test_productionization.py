"""Extender client, leader election, and policy-schema compatibility."""

from __future__ import annotations

import json
import threading
import time

import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.policy import (ExtenderConfig, Policy, PredicateSpec,
                                       PrioritySpec, policy_from_json)
from kubernetes_tpu.engine.generic_scheduler import FitError, GenericScheduler
from kubernetes_tpu.server.extender import serve
from kubernetes_tpu.utils.leaderelection import InMemoryLock, LeaderElector

from helpers import make_node, make_pod


@pytest.fixture(scope="module")
def extender_port():
    # A second engine instance serves as the extender — the dogfood loop:
    # scheduler-with-extender-config delegates to the TPU extender server.
    server = serve(port=0)
    port = server.server_address[1]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield port
    server.shutdown()


class TestExtenderClient:
    def _engine(self, port, weight=1):
        policy = Policy(
            predicates=[PredicateSpec("PodFitsResources"),
                        PredicateSpec("MatchNodeSelector")],
            priorities=[PrioritySpec("LeastRequestedPriority", 1)],
            extenders=[ExtenderConfig(
                url_prefix=f"http://127.0.0.1:{port}/scheduler",
                filter_verb="filter", prioritize_verb="prioritize",
                weight=weight, api_version="v1")])
        return GenericScheduler(policy=policy)

    def test_extender_filter_restricts(self, extender_port):
        # The remote extender runs the default provider, which includes
        # taints; the local policy does NOT.  A tainted node passes local
        # predicates but is filtered by the extender.
        s = self._engine(extender_port)
        s.cache.add_node(make_node("plain"))
        s.cache.add_node(make_node(
            "tainted",
            taints=[{"key": "dedicated", "value": "x",
                     "effect": "NoSchedule"}]))
        got = [s.schedule(make_pod(f"p{i}")) for i in range(4)]
        assert set(got) == {"plain"}

    def test_extender_all_filtered_is_fit_error(self, extender_port):
        s = self._engine(extender_port)
        s.cache.add_node(make_node(
            "tainted",
            taints=[{"key": "dedicated", "value": "x",
                     "effect": "NoSchedule"}]))
        with pytest.raises(FitError):
            s.schedule(make_pod("p"))

    def test_extender_unreachable_fails_pod(self):
        s = self._engine(1)  # nothing listens on port 1
        s.cache.add_node(make_node("n0"))
        from kubernetes_tpu.engine.extender_client import ExtenderError
        with pytest.raises(ExtenderError):
            s.schedule(make_pod("p"))


class TestLeaderElection:
    def test_single_candidate_acquires(self):
        lock = InMemoryLock()
        e = LeaderElector(lock=lock, identity="a")
        assert e.try_acquire_or_renew()
        assert e.is_leader()

    def test_second_candidate_blocked_until_lease_expiry(self):
        clock = [0.0]
        lock = InMemoryLock()
        a = LeaderElector(lock=lock, identity="a", now=lambda: clock[0])
        b = LeaderElector(lock=lock, identity="b", now=lambda: clock[0])
        assert a.try_acquire_or_renew()
        assert not b.try_acquire_or_renew()
        # Holder renews: lease stays with a.
        clock[0] += 10
        assert a.try_acquire_or_renew()
        clock[0] += 12
        assert not b.try_acquire_or_renew()  # observes the renewal first
        # a dies; lease expires 15s after b's last observation.
        clock[0] += 16
        assert b.try_acquire_or_renew()
        assert b.is_leader()
        raw, _ = lock.get()
        assert json.loads(raw)["leaderTransitions"] == 1

    def test_cas_conflict_loses(self):
        lock = InMemoryLock()
        a = LeaderElector(lock=lock, identity="a")
        b = LeaderElector(lock=lock, identity="b")
        assert a.try_acquire_or_renew()
        # b read a stale version: CAS must fail.
        raw, version = lock.get()
        assert not lock.update("junk", version - 1)

    def test_run_loop_leads_and_stops(self):
        lock = InMemoryLock()
        led = threading.Event()
        e = LeaderElector(lock=lock, identity="a", retry_period=0.02,
                          on_started_leading=led.set)
        t = e.run()
        assert led.wait(timeout=5)
        assert e.is_leader()
        e.stop()
        t.join(timeout=5)


class TestPolicySchemaCompat:
    """Pins the v1 policy JSON schema (the compatibility_test.go analogue):
    every documented predicate/priority name and argument must round-trip."""

    FULL_POLICY = """
    {
      "kind": "Policy", "apiVersion": "v1",
      "predicates": [
        {"name": "PodFitsPorts"},
        {"name": "PodFitsResources"},
        {"name": "NoDiskConflict"},
        {"name": "NoVolumeZoneConflict"},
        {"name": "MatchNodeSelector"},
        {"name": "HostName"},
        {"name": "MaxEBSVolumeCount"},
        {"name": "MaxGCEPDVolumeCount"},
        {"name": "MatchInterPodAffinity"},
        {"name": "CheckNodeMemoryPressure"},
        {"name": "CheckNodeDiskPressure"},
        {"name": "PodToleratesNodeTaints"},
        {"name": "GeneralPredicates"},
        {"name": "TestLabelsPresence",
         "argument": {"labelsPresence": {"labels": ["region"],
                                         "presence": true}}},
        {"name": "TestServiceAffinity",
         "argument": {"serviceAffinity": {"labels": ["region"]}}}
      ],
      "priorities": [
        {"name": "EqualPriority", "weight": 2},
        {"name": "LeastRequestedPriority", "weight": 2},
        {"name": "MostRequestedPriority", "weight": 2},
        {"name": "BalancedResourceAllocation", "weight": 2},
        {"name": "SelectorSpreadPriority", "weight": 2},
        {"name": "ServiceSpreadingPriority", "weight": 2},
        {"name": "NodeAffinityPriority", "weight": 2},
        {"name": "TaintTolerationPriority", "weight": 2},
        {"name": "InterPodAffinityPriority", "weight": 2},
        {"name": "TestLabelPreference",
         "weight": 2,
         "argument": {"labelPreference": {"label": "bar",
                                          "presence": true}}},
        {"name": "TestServiceAntiAffinity",
         "weight": 2,
         "argument": {"serviceAntiAffinity": {"label": "zone"}}}
      ],
      "extenders": [
        {"urlPrefix": "http://127.0.0.1:12346/scheduler",
         "apiVersion": "v1", "filterVerb": "filter",
         "prioritizeVerb": "prioritize", "weight": 5,
         "enableHttps": false, "httpTimeout": 5000000000}
      ]
    }
    """

    def test_full_policy_round_trip(self):
        p = policy_from_json(self.FULL_POLICY)
        names = [x.name for x in p.predicates]
        assert "GeneralPredicates" in names
        lp = next(x for x in p.predicates if x.name == "TestLabelsPresence")
        assert lp.labels == ("region",) and lp.presence is True
        sa = next(x for x in p.predicates if x.name == "TestServiceAffinity")
        assert sa.affinity_labels == ("region",)
        assert all(s.weight == 2 for s in p.priorities)
        pref = next(s for s in p.priorities
                    if s.name == "TestLabelPreference")
        assert pref.label == "bar" and pref.presence is True
        saa = next(s for s in p.priorities
                   if s.name == "TestServiceAntiAffinity")
        assert saa.anti_affinity_label == "zone"
        ext = p.extenders[0]
        assert ext.url_prefix.endswith("/scheduler")
        assert ext.http_timeout_s == 5.0
        assert ext.weight == 5

    def test_wire_round_trip_pod_node(self):
        pod = make_pod("rt", cpu="250m", memory="1Gi",
                       labels={"app": "x"}, host_ports=[8080],
                       node_selector={"disk": "ssd"})
        d = api.pod_to_json(pod)
        back = api.pod_from_json(d)
        assert back.key == pod.key
        assert back.resource_request() == pod.resource_request()
        assert back.used_host_ports() == pod.used_host_ports()
        assert back.node_selector == pod.node_selector

        node = make_node("nd", milli_cpu=4000, labels={"z": "1"},
                         taints=[{"key": "k", "value": "v",
                                  "effect": "NoSchedule"}])
        back_n = api.node_from_json(api.node_to_json(node))
        assert back_n.name == node.name
        assert back_n.allocatable_milli_cpu == node.allocatable_milli_cpu
        assert back_n.allocatable_memory == node.allocatable_memory
        assert [t.key for t in back_n.taints()] == ["k"]
        assert back_n.is_ready() == node.is_ready()

class TestObservability:
    def test_trace_window_writes_profile(self, tmp_path, monkeypatch):
        """A windowed jax.profiler session over running solves (the TPU
        pprof analogue, SURVEY §5 tracing row): one .xplane.pb that
        holds the solves' own stages as host events."""
        import types
        from jax.profiler import ProfileData
        from kubernetes_tpu.utils import profiling
        from helpers import make_node, make_pod
        eng = GenericScheduler()
        for i in range(4):
            eng.cache.add_node(make_node(f"n{i}"))
        eng.schedule_batch([make_pod("p1"), make_pod("p2")])   # compiles
        solved, stop = threading.Event(), threading.Event()

        def solve_on():
            while not stop.is_set():
                eng.schedule_batch([make_pod("p1"), make_pod("p2")])
                solved.set()

        def until_a_whole_solve_ran(_seconds: float) -> None:
            for _ in range(2):      # the first may have begun before
                solved.clear()
                assert solved.wait(30.0)

        monkeypatch.setattr(profiling, "time", types.SimpleNamespace(
            sleep=until_a_whole_solve_ran, perf_counter=time.perf_counter))
        worker = threading.Thread(target=solve_on)
        worker.start()
        try:
            written = profiling.trace_window(str(tmp_path), 0.1)
        finally:
            stop.set()
            worker.join(30.0)
        assert not worker.is_alive()
        assert written["dir"] == str(tmp_path)
        found = list(tmp_path.rglob("*.xplane.pb"))
        assert len(found) == 1, f"profile artifacts under {tmp_path}"
        names = {e.name for plane in ProfileData.from_file(
            str(found[0])).planes for line in plane.lines
            for e in line.events}
        assert {"kt.snapshot", "kt.compile", "kt.solve"} <= names

    def test_thread_stacks_dump(self):
        from kubernetes_tpu.utils.profiling import thread_stacks
        text = thread_stacks()
        assert "MainThread" in text and "thread_stacks" in text
