"""Upstream's MixedSchedulingBasePod cluster (``benchmarks/configs/
mixedaffinity-5000n.json``): five pod templates — no term, a REQUIRED
zone affinity, a REQUIRED hostname anti-affinity, a PREFERRED hostname
affinity and a PREFERRED hostname anti-affinity — side by side.

(a) the engine against the benchmark's plain reference
    (``benchmarks/references/mixedaffinity.py``, loaded by path) on
    seeded tiny clusters: launches through the streamed scan and the
    serial ``schedule()`` route, every answer inside ``best_nodes``, on
    one zone (the configuration) and on three (so that the blue term can
    fail);
(b) ``ResidentAffinity``'s kept planes against the build from nothing
    under add / assume / forget / delete with the five templates, and
    what it keeps for them: 4 match signatures, 5 label templates;
(c) the counters the benchmark's ``affinity.*`` metrics read;
(d) prewarm on a cluster that holds the five templates, then a first
    live launch of base pods only and a second of all five: no compile.
"""

from __future__ import annotations

import importlib.util
import json
import os

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.cache.scheduler_cache import SchedulerCache
from kubernetes_tpu.engine import devicestats
from kubernetes_tpu.engine import solver as sv
from kubernetes_tpu.engine.generic_scheduler import FitError, GenericScheduler
from kubernetes_tpu.utils import metrics

from test_affinity_resident import _assert_tables_equal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATTERN = ["base", "blue", "green", "red", "yellow", "base", "blue", "green",
           "red", "yellow", "base"]
GROUPS = ("base", "blue", "green", "red", "yellow")


def _load(directory: str, word: str):
    path = os.path.join(REPO, "benchmarks", directory, word + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{word}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _cluster(seed: int, n_nodes: int, n_zones: int, node_pods: int = 110,
             pattern=PATTERN):
    """``(reference, nodes, pods)`` of the benchmark's shapes."""
    shapes = _load("shapes", "mixedaffinity")
    ref = _load("references", "mixedaffinity")
    nodes_spec = {"count": n_nodes, "profile": "uniform", "milli_cpu": 4000,
                  "memory": 32 * 1024 ** 3, "pods": node_pods,
                  "n_zones": n_zones}
    nodes = shapes.Nodes(nodes_spec, seed)
    pods = shapes.Pods({"milli_cpu": 100, "memory": 500 * 1024 ** 2,
                        "pattern": pattern}, seed, nodes_spec)
    pods.grow(2000)
    return ref, nodes, pods


def _make(pods, i: int) -> api.Pod:
    return api.pod_from_json(json.loads(pods.json_bytes(i)))


def _engine(nodes) -> GenericScheduler:
    s = GenericScheduler()
    assert s.guard.mode == "device"
    for obj in nodes.to_json():
        s.cache.add_node(api.node_from_json(obj))
    return s


def _node_index(name: str) -> int:
    return int(name[len("node-"):])


class _Replay:
    """The reference beside the engine: every placement has to be one of
    its ``best_nodes`` on the state the pods before it left; a pod with
    no placement has to fit nowhere."""

    def __init__(self, ref, nodes, pods, engine):
        self.ref, self.pods, self.engine = ref, pods, engine
        self.state = ref.State(nodes, pods)
        self.bound: dict[int, api.Pod] = {}
        self.unplaced = {g: 0 for g in GROUPS}
        self.placed = {g: 0 for g in GROUPS}
        self.kept_out_by_a_term = {g: 0 for g in GROUPS}

    def resident(self, count: int) -> None:
        for i in range(count):
            best = self.ref.best_nodes(self.state, i)
            if not len(best):
                continue
            node = int(best[i % len(best)])
            pod = _make(self.pods, i)
            pod.node_name = f"node-{node}"
            self.engine.cache.add_pod(pod)
            self.state.add(i, node)
            self.bound[i] = pod

    def check(self, i: int, pod: api.Pod, chosen) -> bool:
        group = GROUPS[int(self.pods.group[i])]
        best = self.ref.best_nodes(self.state, i)
        if chosen is None:
            assert len(best) == 0, (i, group, best)
            self.unplaced[group] += 1
            if self.ref.base.fits(self.state, i).any():   # room there was
                self.kept_out_by_a_term[group] += 1
            return False
        node = _node_index(chosen)
        assert node in best, (i, group, node, best,
                              self.ref.score_gap(self.state, i, node))
        assert not any(self.ref.broken(self.state, i, node).values())
        self.state.add(i, node)
        self.bound[i] = pod
        self.placed[group] += 1
        return True

    def retire(self, rng, count: int) -> None:
        for i in rng.permutation(sorted(self.bound))[:count].tolist():
            pod = self.bound.pop(i)
            self.engine.cache.remove_pod(pod)
            self.state.add(i, _node_index(pod.node_name), -1)


# -- (a) the engine against the plain reference ------------------------------

@pytest.mark.parametrize("route", ["stream", "serial"])
@pytest.mark.parametrize("n_zones,node_pods", [(1, 110), (3, 6)])
@pytest.mark.parametrize("seed", [11, 2147483659])
def test_engine_answers_lie_in_the_references_best_nodes(seed, n_zones,
                                                         node_pods, route):
    """24 nodes, 44 resident pods, then launches of the five templates
    with retirements between.  On three zones with 6 pods a node the blue
    pods' zone fills up and a blue pod is unschedulable in both; on one
    zone every node is in reach.  Inside a launch a red pod placed by
    step ``i`` counts for step ``i + 1`` (the replay adds each placement
    before it asks for the next)."""
    ref, nodes, pods = _cluster(seed, 24, n_zones, node_pods)
    engine = _engine(nodes)
    replay = _Replay(ref, nodes, pods, engine)
    replay.resident(44)
    rng = np.random.RandomState(seed % (2 ** 32))
    nxt = 44
    for round_ in range(5):
        size = 32 if route == "stream" else 22
        batch = [_make(pods, nxt + k) for k in range(size)]
        if route == "stream":
            (_chunk, got), = engine.schedule_batch_stream(batch,
                                                          chunk_size=32)
            placed = [(pod, chosen) for k, (pod, chosen) in enumerate(
                zip(batch, got)) if replay.check(nxt + k, pod, chosen)]
            engine.cache.assume_pods(placed)
        else:
            for k, pod in enumerate(batch):
                try:
                    chosen = engine.schedule(pod)
                except FitError:
                    chosen = None
                if replay.check(nxt + k, pod, chosen):
                    engine.cache.assume_pod(pod, chosen)
        nxt += size
        replay.retire(rng, int(rng.randint(0, 6)))
    assert replay.placed["red"] and replay.placed["yellow"]
    assert replay.placed["blue"] and replay.placed["green"]
    # the zone of the first blue pod (8 nodes x 6 pods, shared with the
    # other templates) fills up before the fleet does: blue pods were
    # kept out by their term while other nodes had room; on one zone
    # the term keeps nobody out
    assert (replay.kept_out_by_a_term["blue"] > 0) == (n_zones == 3)
    assert not replay.kept_out_by_a_term["base"]
    # the reds gathered: some node holds several, as the score wants
    red = GROUPS.index("red")
    assert replay.state.held[red].max() >= 3
    assert replay.state.held[GROUPS.index("green")].max() == 1


# -- (b) the kept planes ------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kept_planes_equal_from_nothing_under_the_five_templates(seed):
    """add / assume (bulk and single) / forget / delete of the five
    templates on three zones: zone key beside hostname keys, preferred
    weights of both signs, the hard weight.  After every few steps the
    tables a launch would read equal ``compile_affinity`` from nothing,
    and nothing was rebuilt after the first launch."""
    _ref, nodes, pods = _cluster(seed, 9, 3)
    rng = np.random.RandomState(seed)
    cache = SchedulerCache()
    for obj in nodes.to_json():
        cache.add_node(api.node_from_json(obj))
    names = [f"node-{i}" for i in range(nodes.n)]
    tracked: dict[str, api.Pod] = {}
    assumed: set[str] = set()
    seq = 0

    def fresh(count: int) -> list[api.Pod]:
        nonlocal seq
        out = [_make(pods, seq + k) for k in range(count)]
        seq += count
        return out

    _assert_tables_equal(cache, fresh(11))       # registers the signatures
    rebuilds = metrics.AFFINITY_TABLE_REBUILDS.value
    for step in range(100):
        op = rng.choice(["add", "assume", "assume_bulk", "forget", "delete",
                         "confirm"], p=[.25, .15, .15, .1, .25, .1])
        if op == "add":
            pod, = fresh(1)
            pod.node_name = names[rng.randint(len(names))]
            cache.add_pod(pod)
            tracked[pod.key] = pod
        elif op == "assume":
            pod, = fresh(1)
            cache.assume_pod(pod, names[rng.randint(len(names))])
            tracked[pod.key] = pod
            assumed.add(pod.key)
        elif op == "assume_bulk":
            batch = fresh(int(rng.randint(1, 6)))
            cache.assume_pods([(p, names[rng.randint(len(names))])
                               for p in batch])
            for p in batch:
                tracked[p.key] = p
                assumed.add(p.key)
        elif op == "forget" and assumed:
            key = sorted(assumed)[rng.randint(len(assumed))]
            cache.forget_pod(tracked.pop(key))
            assumed.discard(key)
        elif op == "confirm" and assumed:
            key = sorted(assumed)[rng.randint(len(assumed))]
            assert cache.confirm_assumed(key, tracked[key].node_name)
            assumed.discard(key)
        elif op == "delete" and tracked:
            key = sorted(tracked)[rng.randint(len(tracked))]
            cache.remove_pod(tracked.pop(key))
            assumed.discard(key)
        if step % 5 == 4:
            _assert_tables_equal(cache, fresh(int(rng.randint(1, 12))))
    _assert_tables_equal(cache, fresh(11))
    assert metrics.AFFINITY_TABLE_REBUILDS.value == rebuilds
    assert cache.affinity_planes_drift() == []


def test_what_the_deployment_keeps_four_match_signatures_five_templates():
    """The first size for the bound ROADMAP.md Reach A3 asks for
    (``ResidentAffinity._matched``'s docstring states it): upstream's
    five templates keep 4 match, 1 decl and 3 sym signatures and 5
    entries in the label-template memo, however many pods come and go."""
    _ref, nodes, pods = _cluster(3, 12, 1)
    cache = SchedulerCache()
    for obj in nodes.to_json():
        cache.add_node(api.node_from_json(obj))
    for i in range(33):
        pod = _make(pods, i)
        pod.node_name = f"node-{i % nodes.n}"
        cache.add_pod(pod)
    for launch in range(3):
        _assert_tables_equal(
            cache, [_make(pods, 100 + 11 * launch + k) for k in range(11)])
        for k in range(11):                # every template comes and goes
            extra = _make(pods, 220 + 11 * launch + k)
            cache.assume_pod(extra, f"node-{k}")
            if k % 2:
                cache.forget_pod(extra)
    aff = cache.affinity_tables()
    assert (len(aff.match.rows), len(aff.decl.rows), len(aff.sym.rows)) \
        == (4, 1, 3)
    assert len(aff._match_memo) == 5
    assert {sig.key for sig in aff.match.rows} == {
        api.HOSTNAME_LABEL, "topology.kubernetes.io/zone"}
    assert sorted(sig.weight for sig in aff.sym.rows) == [-1, 1, 1]
    assert len(aff._declared_memo) == 4         # one text a coloured template


# -- (c) the counters ----------------------------------------------------------

def test_an_update_writes_one_cell_a_plane_on_the_zone_key_too():
    """``scheduler_affinity_plane_cells_total``: a coloured pod writes one
    element of two planes at the attach and again at the detach — blue
    its zone's count in the match and the sym plane (kept per DOMAIN: a
    zone's nodes all read the same), green, red and yellow their node's
    element; a base pod nothing."""
    _ref, nodes, pods = _cluster(5, 12, 3)       # 4 nodes a zone
    cache = SchedulerCache()
    for obj in nodes.to_json():
        cache.add_node(api.node_from_json(obj))
    _assert_tables_equal(cache, [_make(pods, k) for k in range(11)])
    cells, updates = metrics.AFFINITY_PLANE_CELLS, \
        metrics.AFFINITY_TABLE_ROW_UPDATES
    for group, expect in (("base", 0), ("blue", 2), ("green", 2),
                          ("red", 2), ("yellow", 2)):
        pod = _make(pods, 99 + PATTERN.index(group))
        pod.node_name = "node-5"
        c0, u0 = cells.value, updates.value
        cache.add_pod(pod)
        assert cells.value - c0 == expect, group
        assert updates.value - u0 == (1 if expect else 0), group
        cache.remove_pod(pod)
        assert cells.value - c0 == 2 * expect, group
        assert updates.value - u0 == (2 if expect else 0), group
    aff = cache.affinity_tables()
    zone_rows = [(planes, sig) for planes in (aff.match, aff.sym)
                 for sig in planes.rows if sig.key.endswith("/zone")]
    assert len(zone_rows) == 1          # blue's match row; no blue pod left
    blue = _make(pods, 99 + PATTERN.index("blue"))
    blue.node_name = "node-5"                    # zone 2: nodes 2, 5, 8, 11
    cache.add_pod(blue)
    for planes in (aff.match, aff.sym):
        (sig,) = [s for s in planes.rows if s.key.endswith("/zone")]
        assert planes.cnt[planes.rows[sig]].tolist() == [0, 0, 1, 0]
        assert aff.node_row(planes, sig)[:12].tolist() == [0, 0, 1] * 4


def test_launch_counters_follow_the_pinned_flag_and_the_tables():
    """``scheduler_affinity_priority_pods_total`` counts every live pod of
    a launch once the priority flag is pinned — a launch of base pods
    included — and none before; ``..._launch_signatures_total`` the rows
    each compile handed over, padding apart."""
    _ref, nodes, pods = _cluster(7, 16, 1)
    engine = _engine(nodes)
    scored = metrics.AFFINITY_PRIORITY_PODS
    sigs = {family: metrics.AFFINITY_LAUNCH_SIGNATURES.labels(family=family)
            for family in ("match", "decl", "sym")}

    def launch(indices):
        before = scored.value, {f: c.value for f, c in sigs.items()}
        batch = [_make(pods, i) for i in indices]
        (_chunk, got), = engine.schedule_batch_stream(batch, chunk_size=16)
        engine.cache.assume_pods(
            [(p, n) for p, n in zip(batch, got) if n is not None])
        return scored.value - before[0], \
            {f: c.value - before[1][f] for f, c in sigs.items()}

    base = [i for i in range(200) if PATTERN[i % 11] == "base"]
    green = [i for i in range(200) if PATTERN[i % 11] == "green"]
    assert launch(base[:5]) == (0, {"match": 0, "decl": 0, "sym": 0})
    # required terms only: the predicate's flag, not the priority's
    assert launch(green[:3]) == (0, {"match": 1, "decl": 1, "sym": 0})
    assert not engine._flags_seen.any_affinity_prio
    got = launch(range(300, 311))               # all five templates
    assert got == (11, {"match": 4, "decl": 1, "sym": 3})
    assert engine._flags_seen.any_affinity_prio
    # pinned: base pods alone now ride the same program, and the rows
    # are what the RESIDENT pods declare (no match row: no own term)
    assert launch(base[5:9]) == (4, {"match": 0, "decl": 1, "sym": 3})
    assert sv.Solver.for_policy(engine.policy).scores_affinity(
        engine._flags_seen)


# -- (d) prewarm ---------------------------------------------------------------

def test_prewarm_on_the_five_templates_leaves_nothing_to_compile():
    """A daemon that finds the five templates resident at start prewarms
    with one sample a coloured template: both affinity flags are pinned
    and the signature axes stand at the live capacities (4 / 1 / 4)
    before the first live launch.  A first launch of base pods only and a
    second of all five then compile nothing, and the first runs the very
    program the second runs."""
    from kubernetes_tpu.apiserver.memstore import MemStore
    from kubernetes_tpu.scheduler.binder import InMemoryBinder
    from kubernetes_tpu.scheduler.factory import ConfigFactory
    _ref, nodes, pods = _cluster(9, 16, 1)
    factory = ConfigFactory(MemStore())
    alg, daemon = factory.algorithm, factory.daemon
    daemon.config.binder = InMemoryBinder()
    daemon.config.async_bind = False
    daemon.STREAM_THRESHOLD = 16
    daemon.stream_chunk = 16
    daemon.stream_min_bucket = 8
    for obj in nodes.to_json():
        alg.cache.add_node(api.node_from_json(obj))
    for i in range(22):
        pod = _make(pods, i)
        pod.node_name = f"node-{i % nodes.n}"
        alg.cache.add_pod(pod)
    samples = factory._prewarm_samples()
    assert sorted(p.labels.get("color") for p in samples) == \
        ["blue", "green", "red", "yellow"]
    assert daemon.prewarm(sample_pods=samples)
    flags = alg._flags_seen
    assert flags.any_affinity_pred and flags.any_affinity_prio
    assert [alg._axis_caps[a] for a in ("aff_sm", "aff_sd", "aff_sy")] \
        == [4, 1, 4]
    scored0 = metrics.AFFINITY_PRIORITY_PODS.value
    base = [i for i in range(100, 200) if PATTERN[i % 11] == "base"]
    with devicestats.watchdog_window() as compiles:
        for indices in (base[:6], range(220, 231)):
            for i in indices:
                daemon.enqueue(_make(pods, i))
            daemon.schedule_pending(wait_first=False)
            daemon.wait_for_binds()
        assert compiles() == 0
    assert alg._flags_seen == flags
    assert [alg._axis_caps[a] for a in ("aff_sm", "aff_sd", "aff_sy")] \
        == [4, 1, 4]
    assert metrics.AFFINITY_PRIORITY_PODS.value - scored0 == 17
    assert len(daemon.config.binder._bound) == 17
