"""Table-driven predicate tests.

Case shapes mirror the reference's predicates_test.go tables (expectations
re-derived from the documented semantics, not ported code): construct pods +
nodes in memory, compile to tensors, assert the [P,N] masks.
"""

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.policy import Policy, PredicateSpec
from kubernetes_tpu.cache.scheduler_cache import SchedulerCache
from kubernetes_tpu.engine import solver as sv
from kubernetes_tpu.features import batch as fb

from helpers import make_node, make_pod


def masks_for(pods, nodes, existing=None, predicates=None):
    """Compile and return dict name -> [P,N] numpy mask."""
    cache = SchedulerCache()
    for nd in nodes:
        cache.add_node(nd)
    for pod, node_name in existing or []:
        pod.node_name = node_name
        cache.add_pod(pod)
    nt, agg, ep, nds = cache.snapshot()
    batch = fb.compile_batch(pods, nt, cache.space, ep=ep, nodes=nds)
    policy = Policy(predicates=[PredicateSpec(n) for n in predicates]) \
        if predicates else None
    from kubernetes_tpu.api.policy import default_provider
    solver = sv.Solver(policy or default_provider())
    db = sv.device_batch(batch)
    dc = sv.device_cluster(nt, agg, cache.space)
    # the fleet's rows are the first of the node axis' capacity
    return {k: np.asarray(v)[:, :len(nodes)]
            for k, v in solver.masks(db, dc).items()}


class TestPodFitsResources:
    def test_fits_when_empty(self):
        m = masks_for([make_pod(cpu="1", memory="1Gi")],
                      [make_node("n1", milli_cpu=2000, memory=4 * 1024**3)])
        assert m["PodFitsResources"][0, 0]

    def test_cpu_exceeded(self):
        m = masks_for(
            [make_pod(cpu="3")],
            [make_node("n1", milli_cpu=4000)],
            existing=[(make_pod(cpu="2"), "n1")])
        assert not m["PodFitsResources"][0, 0]

    def test_memory_exceeded(self):
        m = masks_for(
            [make_pod(memory="3Gi")],
            [make_node("n1", memory=4 * 1024**3)],
            existing=[(make_pod(memory="2Gi"), "n1")])
        assert not m["PodFitsResources"][0, 0]

    def test_exact_fit_ok(self):
        # allocatable < request + requested must FAIL; == must PASS.
        m = masks_for(
            [make_pod(cpu="2")],
            [make_node("n1", milli_cpu=4000)],
            existing=[(make_pod(cpu="2"), "n1")])
        assert m["PodFitsResources"][0, 0]

    def test_zero_request_always_fits_resources(self):
        m = masks_for(
            [make_pod()],  # no requests at all
            [make_node("n1", milli_cpu=1000)],
            existing=[(make_pod(cpu="1"), "n1")])
        assert m["PodFitsResources"][0, 0]

    def test_pod_count_applies_even_to_zero_request(self):
        # predicates.go:451-453 runs before the zero-request early return.
        m = masks_for(
            [make_pod()],
            [make_node("n1", pods=1)],
            existing=[(make_pod(), "n1")])
        assert not m["PodFitsResources"][0, 0]

    def test_gpu(self):
        m = masks_for(
            [make_pod(gpu=1)],
            [make_node("n1", gpu=1), make_node("n2", gpu=0)])
        assert m["PodFitsResources"][0, 0]
        assert not m["PodFitsResources"][0, 1]


class TestPodFitsHost:
    def test_no_constraint(self):
        m = masks_for([make_pod()], [make_node("n1"), make_node("n2")])
        assert m["PodFitsHost"].all()

    def test_pinned(self):
        m = masks_for([make_pod(node_name="n2")],
                      [make_node("n1"), make_node("n2")])
        assert list(m["PodFitsHost"][0]) == [False, True]

    def test_unknown_node(self):
        m = masks_for([make_pod(node_name="ghost")],
                      [make_node("n1"), make_node("n2")])
        assert not m["PodFitsHost"].any()


class TestPodFitsHostPorts:
    def test_no_conflict(self):
        m = masks_for([make_pod(host_ports=[8080])],
                      [make_node("n1")],
                      existing=[(make_pod(host_ports=[9090]), "n1")])
        assert m["PodFitsHostPorts"][0, 0]

    def test_conflict(self):
        m = masks_for([make_pod(host_ports=[8080])],
                      [make_node("n1"), make_node("n2")],
                      existing=[(make_pod(host_ports=[8080]), "n1")])
        assert not m["PodFitsHostPorts"][0, 0]
        assert m["PodFitsHostPorts"][0, 1]


class TestMatchNodeSelector:
    def test_node_selector(self):
        m = masks_for(
            [make_pod(node_selector={"disk": "ssd"})],
            [make_node("n1", labels={"disk": "ssd"}),
             make_node("n2", labels={"disk": "hdd"}),
             make_node("n3")])
        assert list(m["MatchNodeSelector"][0]) == [True, False, False]

    def test_required_affinity_in(self):
        aff = {"nodeAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": {
            "nodeSelectorTerms": [{"matchExpressions": [
                {"key": "zone", "operator": "In", "values": ["a", "b"]}]}]}}}
        m = masks_for(
            [make_pod(affinity=aff)],
            [make_node("n1", labels={"zone": "a"}),
             make_node("n2", labels={"zone": "c"})])
        assert list(m["MatchNodeSelector"][0]) == [True, False]

    def test_required_affinity_notin_absent_key_matches(self):
        aff = {"nodeAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": {
            "nodeSelectorTerms": [{"matchExpressions": [
                {"key": "zone", "operator": "NotIn", "values": ["a"]}]}]}}}
        m = masks_for(
            [make_pod(affinity=aff)],
            [make_node("n1", labels={"zone": "a"}),
             make_node("n2", labels={"zone": "b"}),
             make_node("n3")])  # no zone label: NotIn matches
        assert list(m["MatchNodeSelector"][0]) == [False, True, True]

    def test_exists_and_doesnotexist(self):
        aff = {"nodeAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": {
            "nodeSelectorTerms": [{"matchExpressions": [
                {"key": "gpu", "operator": "Exists"},
                {"key": "retiring", "operator": "DoesNotExist"}]}]}}}
        m = masks_for(
            [make_pod(affinity=aff)],
            [make_node("n1", labels={"gpu": "yes"}),
             make_node("n2", labels={"gpu": "yes", "retiring": "soon"}),
             make_node("n3")])
        assert list(m["MatchNodeSelector"][0]) == [True, False, False]

    def test_empty_terms_match_nothing(self):
        # predicates.go:520-525 cases 3/5.
        aff = {"nodeAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": {
            "nodeSelectorTerms": []}}}
        m = masks_for([make_pod(affinity=aff)], [make_node("n1")])
        assert not m["MatchNodeSelector"].any()

    def test_terms_are_ored(self):
        aff = {"nodeAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": {
            "nodeSelectorTerms": [
                {"matchExpressions": [{"key": "a", "operator": "Exists"}]},
                {"matchExpressions": [{"key": "b", "operator": "Exists"}]}]}}}
        m = masks_for(
            [make_pod(affinity=aff)],
            [make_node("n1", labels={"a": "1"}),
             make_node("n2", labels={"b": "1"}),
             make_node("n3", labels={"c": "1"})])
        assert list(m["MatchNodeSelector"][0]) == [True, True, False]

    def test_gt_lt(self):
        aff = {"nodeAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": {
            "nodeSelectorTerms": [{"matchExpressions": [
                {"key": "cores", "operator": "Gt", "values": ["8"]}]}]}}}
        m = masks_for(
            [make_pod(affinity=aff)],
            [make_node("n1", labels={"cores": "16"}),
             make_node("n2", labels={"cores": "4"}),
             make_node("n3", labels={"cores": "notanumber"}),
             make_node("n4")])
        assert list(m["MatchNodeSelector"][0]) == [True, False, False, False]


class TestTaints:
    def test_untolerated_taint_blocks(self):
        m = masks_for(
            [make_pod()],
            [make_node("n1", taints=[{"key": "dedicated", "value": "gpu",
                                      "effect": "NoSchedule"}]),
             make_node("n2")])
        assert list(m["PodToleratesNodeTaints"][0]) == [False, True]

    def test_tolerated_equal(self):
        m = masks_for(
            [make_pod(tolerations=[{"key": "dedicated", "operator": "Equal",
                                    "value": "gpu", "effect": "NoSchedule"}])],
            [make_node("n1", taints=[{"key": "dedicated", "value": "gpu",
                                      "effect": "NoSchedule"}])])
        assert m["PodToleratesNodeTaints"][0, 0]

    def test_tolerated_exists(self):
        m = masks_for(
            [make_pod(tolerations=[{"key": "dedicated", "operator": "Exists",
                                    "effect": "NoSchedule"}])],
            [make_node("n1", taints=[{"key": "dedicated", "value": "anything",
                                      "effect": "NoSchedule"}])])
        assert m["PodToleratesNodeTaints"][0, 0]

    def test_wrong_value_not_tolerated(self):
        m = masks_for(
            [make_pod(tolerations=[{"key": "dedicated", "operator": "Equal",
                                    "value": "db", "effect": "NoSchedule"}])],
            [make_node("n1", taints=[{"key": "dedicated", "value": "gpu",
                                      "effect": "NoSchedule"}])])
        assert not m["PodToleratesNodeTaints"][0, 0]

    def test_toleration_less_pod_rejected_even_on_prefer_only_taints(self):
        # tolerationsToleratesTaints (predicates.go:1099-1101): a non-empty
        # taint list — even all-PreferNoSchedule — is not tolerated by an
        # empty toleration list.
        m = masks_for(
            [make_pod()],
            [make_node("n1", taints=[{"key": "soft", "value": "x",
                                      "effect": "PreferNoSchedule"}])])
        assert not m["PodToleratesNodeTaints"][0, 0]

    def test_prefer_no_schedule_skipped_when_pod_has_any_toleration(self):
        # With a non-empty toleration list, PreferNoSchedule taints are
        # skipped in the matching loop (predicates.go:1105-1108) — even an
        # unrelated toleration suffices.
        m = masks_for(
            [make_pod(tolerations=[{"key": "unrelated", "operator": "Exists",
                                    "effect": "NoSchedule"}])],
            [make_node("n1", taints=[{"key": "soft", "value": "x",
                                      "effect": "PreferNoSchedule"}])])
        assert m["PodToleratesNodeTaints"][0, 0]

    def test_empty_effect_toleration_matches_any_effect(self):
        m = masks_for(
            [make_pod(tolerations=[{"key": "k", "operator": "Exists"}])],
            [make_node("n1", taints=[{"key": "k", "value": "v",
                                      "effect": "NoSchedule"}])])
        assert m["PodToleratesNodeTaints"][0, 0]


class TestNodeConditions:
    def test_memory_pressure_blocks_best_effort_only(self):
        nodes = [make_node("n1", conditions=[("Ready", "True"),
                                             ("MemoryPressure", "True")])]
        best_effort = make_pod()  # no requests/limits
        burstable = make_pod(cpu="100m")
        m = masks_for([best_effort, burstable], nodes)
        assert not m["CheckNodeMemoryPressure"][0, 0]
        assert m["CheckNodeMemoryPressure"][1, 0]

    def test_disk_pressure_blocks_all(self):
        nodes = [make_node("n1", conditions=[("Ready", "True"),
                                             ("DiskPressure", "True")])]
        m = masks_for([make_pod(cpu="1")], nodes)
        assert not m["CheckNodeDiskPressure"][0, 0]


class TestNoDiskConflict:
    def test_gce_rw_conflict(self):
        vol = api.Volume(name="v", gce_pd_name="disk1")
        m = masks_for(
            [make_pod(volumes=[vol])],
            [make_node("n1"), make_node("n2")],
            existing=[(make_pod(volumes=[vol]), "n1")])
        assert not m["NoDiskConflict"][0, 0]
        assert m["NoDiskConflict"][0, 1]

    def test_gce_both_readonly_ok(self):
        ro = api.Volume(name="v", gce_pd_name="disk1", gce_read_only=True)
        m = masks_for(
            [make_pod(volumes=[ro])],
            [make_node("n1")],
            existing=[(make_pod(volumes=[ro]), "n1")])
        assert m["NoDiskConflict"][0, 0]

    def test_ebs_conflicts_even_readonly(self):
        # predicates.go:116-120: EBS has no read-only escape.
        a = api.Volume(name="v", aws_ebs_id="vol-1", aws_read_only=True)
        m = masks_for(
            [make_pod(volumes=[a])],
            [make_node("n1")],
            existing=[(make_pod(volumes=[a]), "n1")])
        assert not m["NoDiskConflict"][0, 0]

    def test_rbd_shared_monitor_conflict(self):
        v1 = api.Volume(name="v", rbd_key="mon1,mon2#pool#img")
        v2 = api.Volume(name="v", rbd_key="mon2,mon3#pool#img")
        m = masks_for(
            [make_pod(volumes=[v1])],
            [make_node("n1")],
            existing=[(make_pod(volumes=[v2]), "n1")])
        assert not m["NoDiskConflict"][0, 0]

    def test_different_disk_no_conflict(self):
        m = masks_for(
            [make_pod(volumes=[api.Volume(name="v", gce_pd_name="disk2")])],
            [make_node("n1")],
            existing=[(make_pod(volumes=[api.Volume(name="v", gce_pd_name="disk1")]),
                       "n1")])
        assert m["NoDiskConflict"][0, 0]
