"""Priority score parity tests — expected values hand-computed from the
reference formulas (priorities.go, selector_spreading.go, node_affinity.go,
taint_toleration.go), the same style as priorities_test.go's exact
HostPriorityList assertions."""

import numpy as np

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.policy import Policy, PrioritySpec
from kubernetes_tpu.cache.scheduler_cache import SchedulerCache
from kubernetes_tpu.engine import solver as sv
from kubernetes_tpu.engine.generic_scheduler import Listers
from kubernetes_tpu.features import batch as fb

from helpers import make_node, make_pod

GI = 1024**3


def scores_for(pods, nodes, priority, existing=None, listers=None, weight=1):
    cache = SchedulerCache()
    for nd in nodes:
        cache.add_node(nd)
    for pod, node_name in existing or []:
        pod.node_name = node_name
        cache.add_pod(pod)
    nt, agg, ep, nds = cache.snapshot()
    li = listers or Listers()
    batch = fb.compile_batch(pods, nt, cache.space, ep=ep, nodes=nds,
                             spread_selectors=li.spread_selectors,
                             controller_refs=li.controller_refs)
    solver = sv.Solver(Policy(priorities=[PrioritySpec(priority, weight)]))
    db = sv.device_batch(batch)
    dc = sv.device_cluster(nt, agg, cache.space)
    _, scores = solver.evaluate(db, dc)
    # the fleet's rows are the first of the node axis' capacity
    return np.asarray(scores)[:, :len(nodes)]


class TestLeastRequested:
    def test_empty_node_with_explicit_requests(self):
        # cpu: (4000-1000)*10/4000 = 7 (int div); mem: (8Gi-2Gi)*10/8Gi = 7
        # score = (7+7)/2 = 7
        s = scores_for([make_pod(cpu="1", memory=2 * GI)],
                       [make_node("n1", milli_cpu=4000, memory=8 * GI)],
                       "LeastRequestedPriority")
        assert s[0, 0] == 7

    def test_nonzero_defaults_for_unset_requests(self):
        # Unset requests count as 100m / 200Mi (non_zero.go:46-47).
        # cpu: (1000-100)*10/1000 = 9; mem: (1024Mi-200Mi)*10/1024Mi
        #   = (1024-200)*10//1024 = 8  -> (9+8)/2 = 8 (int div)
        s = scores_for([make_pod()],
                       [make_node("n1", milli_cpu=1000, memory=1 * GI)],
                       "LeastRequestedPriority")
        assert s[0, 0] == 8

    def test_existing_load_counts(self):
        # existing pod 2000m/4Gi on 4000m/8Gi node; new pod 1000m/2Gi:
        # cpu: (4000-3000)*10/4000 = 2; mem: (8-6)*10/8 = 2 -> 2
        s = scores_for([make_pod(cpu="1", memory=2 * GI)],
                       [make_node("n1", milli_cpu=4000, memory=8 * GI)],
                       "LeastRequestedPriority",
                       existing=[(make_pod(cpu="2", memory=4 * GI), "n1")])
        assert s[0, 0] == 2

    def test_overcommit_scores_zero(self):
        s = scores_for([make_pod(cpu="5", memory=GI)],
                       [make_node("n1", milli_cpu=4000, memory=8 * GI)],
                       "LeastRequestedPriority")
        # cpu requested > capacity -> 0; mem (8-1)*10/8 = 8 -> (0+8)/2 = 4
        assert s[0, 0] == 4

    def test_zero_capacity(self):
        s = scores_for([make_pod(cpu="1", memory=GI)],
                       [make_node("n1", milli_cpu=0, memory=0)],
                       "LeastRequestedPriority")
        assert s[0, 0] == 0


class TestMostRequested:
    def test_basic(self):
        # cpu: 3000*10/4000 = 7; mem: 6Gi*10/8Gi = 7 -> 7
        s = scores_for([make_pod(cpu="1", memory=2 * GI)],
                       [make_node("n1", milli_cpu=4000, memory=8 * GI)],
                       "MostRequestedPriority",
                       existing=[(make_pod(cpu="2", memory=4 * GI), "n1")])
        assert s[0, 0] == 7


class TestBalancedResourceAllocation:
    def test_perfectly_balanced(self):
        # cpuFrac = 2000/4000 = .5, memFrac = 4Gi/8Gi = .5 -> 10
        s = scores_for([make_pod(cpu="2", memory=4 * GI)],
                       [make_node("n1", milli_cpu=4000, memory=8 * GI)],
                       "BalancedResourceAllocation")
        assert s[0, 0] == 10

    def test_imbalanced(self):
        # cpuFrac = 3000/4000 = .75, memFrac = 2Gi/8Gi = .25
        # 10 - |.5|*10 = 5
        s = scores_for([make_pod(cpu="3", memory=2 * GI)],
                       [make_node("n1", milli_cpu=4000, memory=8 * GI)],
                       "BalancedResourceAllocation")
        assert s[0, 0] == 5

    def test_over_capacity_zero(self):
        s = scores_for([make_pod(cpu="5", memory=GI)],
                       [make_node("n1", milli_cpu=4000, memory=8 * GI)],
                       "BalancedResourceAllocation")
        assert s[0, 0] == 0


class TestNodeAffinityPriority:
    AFF = {"nodeAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": 2, "preference": {"matchExpressions": [
            {"key": "zone", "operator": "In", "values": ["a"]}]}},
        {"weight": 3, "preference": {"matchExpressions": [
            {"key": "disk", "operator": "In", "values": ["ssd"]}]}}]}}

    def test_weighted_normalized(self):
        s = scores_for(
            [make_pod(affinity=self.AFF)],
            [make_node("n1", labels={"zone": "a", "disk": "ssd"}),  # 5 -> 10
             make_node("n2", labels={"zone": "a"}),                  # 2 -> 4
             make_node("n3", labels={"disk": "ssd"}),                # 3 -> 6
             make_node("n4")],                                       # 0
            "NodeAffinityPriority")
        assert list(s[0]) == [10, 4, 6, 0]

    def test_no_affinity_all_zero(self):
        s = scores_for([make_pod()], [make_node("n1")], "NodeAffinityPriority")
        assert s[0, 0] == 0


class TestTaintTolerationPriority:
    def test_intolerable_prefer_taints(self):
        soft = [{"key": "soft", "value": "x", "effect": "PreferNoSchedule"}]
        s = scores_for(
            [make_pod()],
            [make_node("n1", taints=soft), make_node("n2")],
            "TaintTolerationPriority")
        # n1: 1 intolerable (max) -> (1 - 1/1)*10 = 0; n2: 0 -> 10
        assert list(s[0]) == [0, 10]

    def test_all_tolerated(self):
        soft = [{"key": "soft", "value": "x", "effect": "PreferNoSchedule"}]
        s = scores_for(
            [make_pod(tolerations=[{"key": "soft", "operator": "Exists",
                                    "effect": "PreferNoSchedule"}])],
            [make_node("n1", taints=soft), make_node("n2")],
            "TaintTolerationPriority")
        assert list(s[0]) == [10, 10]


class TestSelectorSpread:
    def test_spreads_by_service(self):
        svc = api.Service(name="s", selector={"app": "web"})
        listers = Listers(services=[svc])
        s = scores_for(
            [make_pod(labels={"app": "web"})],
            [make_node("n1"), make_node("n2"), make_node("n3")],
            "SelectorSpreadPriority",
            existing=[(make_pod(labels={"app": "web"}), "n1"),
                      (make_pod(labels={"app": "web"}), "n1"),
                      (make_pod(labels={"app": "web"}), "n2")],
            listers=listers)
        # counts: n1=2 (max), n2=1, n3=0
        # scores: 10*(2-2)/2=0, 10*(2-1)/2=5, 10*2/2=10
        assert list(s[0]) == [0, 5, 10]

    def test_no_selectors_all_ten(self):
        s = scores_for([make_pod(labels={"app": "web"})],
                       [make_node("n1"), make_node("n2")],
                       "SelectorSpreadPriority")
        assert list(s[0]) == [10, 10]

    def test_different_namespace_ignored(self):
        svc = api.Service(name="s", selector={"app": "web"})
        listers = Listers(services=[svc])
        s = scores_for(
            [make_pod(labels={"app": "web"})],
            [make_node("n1"), make_node("n2")],
            "SelectorSpreadPriority",
            existing=[(make_pod(labels={"app": "web"}, namespace="other"), "n1")],
            listers=listers)
        assert list(s[0]) == [10, 10]

    def test_deleted_pods_ignored(self):
        svc = api.Service(name="s", selector={"app": "web"})
        listers = Listers(services=[svc])
        s = scores_for(
            [make_pod(labels={"app": "web"})],
            [make_node("n1"), make_node("n2")],
            "SelectorSpreadPriority",
            existing=[(make_pod(labels={"app": "web"}, deleted=True), "n1"),
                      (make_pod(labels={"app": "web"}), "n2")],
            listers=listers)
        # only n2's pod counts: n1 -> 10, n2 -> 0
        assert list(s[0]) == [10, 0]

    def test_zone_blending(self):
        svc = api.Service(name="s", selector={"app": "web"})
        listers = Listers(services=[svc])
        za = {api.ZONE_LABEL: "a"}
        zb = {api.ZONE_LABEL: "b"}
        s = scores_for(
            [make_pod(labels={"app": "web"})],
            [make_node("n1", labels=za), make_node("n2", labels=za),
             make_node("n3", labels=zb)],
            "SelectorSpreadPriority",
            existing=[(make_pod(labels={"app": "web"}), "n1")],
            listers=listers)
        # node counts: n1=1 (max 1), zone counts: a=1, b=0 (max 1)
        # n1: node 0, zone 0 -> 0*(1/3) + (2/3)*0 = 0
        # n2: node 10*(1-0)/1=10, zone 0 -> 10/3 + 0 = 3.33 -> 3
        # n3: node 10, zone 10 -> 10/3 + 20/3 = 10
        assert list(s[0]) == [0, 3, 10]


class TestImageLocality:
    def test_buckets(self):
        mb = 1024 * 1024
        nodes = [
            make_node("n1", images=[(["img1"], 140 * mb)]),
            make_node("n2", images=[(["img1"], 500 * mb)]),
            make_node("n3", images=[(["img1"], 2000 * mb)]),
            make_node("n4", images=[(["img1"], 10 * mb)]),  # below min -> 0
            make_node("n5"),
        ]
        s = scores_for([make_pod(images=["img1"])], nodes,
                       "ImageLocalityPriority")
        # (10*(140-23))/977 + 1 = 2 ; (10*(500-23))/977+1 = 5 ; >=1000 -> 10
        assert list(s[0]) == [2, 5, 10, 0, 0]

    def test_sums_across_containers(self):
        mb = 1024 * 1024
        nodes = [make_node("n1", images=[(["a"], 300 * mb), (["b"], 300 * mb)])]
        s = scores_for([make_pod(images=["a", "b"])], nodes,
                       "ImageLocalityPriority")
        # sum 600MB: (10*(600-23))/977 + 1 = 6
        assert s[0, 0] == 6


class TestNodePreferAvoid:
    def test_avoid_annotation(self):
        import json
        rc = api.ReplicationController(name="rc1", selector={"app": "web"})
        avoid = {"preferAvoidPods": [{"podSignature": {"podController": {
            "kind": "ReplicationController", "uid": "default/rc1"}}}]}
        nodes = [make_node("n1", annotations={
            api.PREFER_AVOID_PODS_ANNOTATION_KEY: json.dumps(avoid)}),
            make_node("n2")]
        listers = Listers(controllers=[rc])
        s = scores_for([make_pod(labels={"app": "web"})], nodes,
                       "NodePreferAvoidPodsPriority", listers=listers,
                       weight=10000)
        assert list(s[0]) == [0, 100000]

    def test_no_controller_all_ten(self):
        s = scores_for([make_pod()], [make_node("n1")],
                       "NodePreferAvoidPodsPriority")
        assert s[0, 0] == 10
