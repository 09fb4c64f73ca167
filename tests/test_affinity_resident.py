"""The resident side of the inter-pod affinity tables, kept between
launches (``features/affinity.py ResidentAffinity``, owned by the cache):

(a) the program against the benchmark's plain reference
    (``benchmarks/references/interpod.py``, loaded by path) on seeded
    data: green pods one by one and in batches, retirements between;
(b) the kept tables equal ``compile_affinity`` from nothing, to the
    element, after a seeded sequence of cache operations;
(c) ``ConfigFactory``'s prewarm sample carries the affinity flag when,
    and only when, the cache holds pods with affinity.
"""

from __future__ import annotations

import importlib.util
import json
import os

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.cache.scheduler_cache import SchedulerCache
from kubernetes_tpu.engine import solver as sv
from kubernetes_tpu.engine.generic_scheduler import FitError, GenericScheduler
from kubernetes_tpu.features import affinity as fa
from kubernetes_tpu.features import batch as fb
from kubernetes_tpu.utils import metrics

from helpers import make_node, make_pod

HOST, ZONE = api.HOSTNAME_LABEL, api.ZONE_LABEL
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _term(selector: dict, topo: str, namespaces=None) -> dict:
    term = {"labelSelector": {"matchLabels": selector}, "topologyKey": topo}
    if namespaces is not None:
        term["namespaces"] = namespaces
    return term


def _weighted(term: dict, weight: int) -> dict:
    return {"weight": weight, "podAffinityTerm": term}


# Pod templates of the sequence: hostname and zone keys, required and
# preferred terms, affinity and anti-affinity, an empty topology key, two
# namespaces; six distinct signatures and a plain pod.
TEMPLATES = [
    dict(labels={"color": "green"}, affinity={"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [
            _term({"color": "green"}, HOST, ["default"])]}}),
    dict(labels={"color": "blue"}, affinity={"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [
            _term({"color": "blue"}, ZONE)]}}),
    dict(labels={"app": "web"}, affinity={"podAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [
            _term({"app": "db"}, ZONE)]}}),
    dict(labels={"app": "db"}, affinity={
        "podAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
            _weighted(_term({"app": "web"}, HOST), 5)]},
        "podAntiAffinity": {
            "preferredDuringSchedulingIgnoredDuringExecution": [
                _weighted(_term({"color": "green"}, ZONE), 3)]}}),
    dict(labels={"app": "cache"}, namespace="other", affinity={
        "podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                _term({"app": "cache"}, "")]}}),
    dict(labels={"app": "plain"}),
]


def _node(i: int, zone: str | None) -> api.Node:
    labels = {HOST: f"n{i}"}
    if zone:
        labels[ZONE] = zone
    return make_node(f"n{i}", labels=labels)


def _pod(rng, seq: int) -> api.Pod:
    t = TEMPLATES[rng.randint(len(TEMPLATES))]
    return make_pod(name=f"p{seq}", cpu="100m", memory="64Mi", **t)


def _assert_tables_equal(cache: SchedulerCache, batch: list[api.Pod],
                         hard_weight: int = 1) -> None:
    """The kept path and the from-nothing path, same snapshot, same
    batch: every array of AffinityTensors equal to the element."""
    with cache.lock:
        nt, _agg, ep, nodes = cache.snapshot()
        kept = fa.compile_affinity(
            batch, (), ep, nodes, nt.n, cache.space, hard_weight,
            resident=cache.affinity_tables())
        fresh = fa.compile_affinity(
            batch, cache.affinity_pods(), ep, nodes, nt.n, cache.space,
            hard_weight)
    for name, a, b in zip(fa.AffinityTensors._fields, kept, fresh):
        if name == "has_any":
            assert a == b
            continue
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a, b), name


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_kept_tables_equal_from_nothing_after_any_sequence(seed):
    """add / assume (bulk and single) / forget / delete / confirm /
    node add / node remove / node relabel, seeded; after every few steps
    and at the end the tables a launch would read are compared with
    ``compile_affinity`` from nothing."""
    rng = np.random.RandomState(seed)
    cache = SchedulerCache()
    zones = ["z1", "z2", "z3", None]
    n_nodes = 9
    for i in range(n_nodes):
        cache.add_node(_node(i, zones[i % 4]))
    tracked: dict[str, api.Pod] = {}      # key -> pod, as the cache has it
    assumed: set[str] = set()
    seq = 0
    rebuilds0 = metrics.AFFINITY_TABLE_REBUILDS.value
    for step in range(120):
        op = rng.choice(["add", "assume", "assume_bulk", "forget", "delete",
                         "confirm", "node_add", "node_remove",
                         "node_relabel"],
                        p=[.22, .14, .12, .08, .22, .08, .05, .04, .05])
        names = [n.name for n in cache.nodes()]
        if op == "add":
            pod = _pod(rng, seq)
            seq += 1
            pod.node_name = names[rng.randint(len(names))]
            cache.add_pod(pod)
            tracked[pod.key] = pod
        elif op == "assume":
            pod = _pod(rng, seq)
            seq += 1
            cache.assume_pod(pod, names[rng.randint(len(names))])
            tracked[pod.key] = pod
            assumed.add(pod.key)
        elif op == "assume_bulk":
            pods = [_pod(rng, seq + k) for k in range(rng.randint(1, 5))]
            seq += len(pods)
            cache.assume_pods([(p, names[rng.randint(len(names))])
                               for p in pods])
            for p in pods:
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
        elif op == "node_add":
            cache.add_node(_node(n_nodes, zones[rng.randint(4)]))
            n_nodes += 1
        elif op == "node_remove" and len(names) > 4:
            cache.remove_node(names[rng.randint(len(names))])
        elif op == "node_relabel":
            i = int(names[rng.randint(len(names))][1:])
            cache.update_node(_node(i, zones[rng.randint(4)]))
        if step % 5 == 4:
            _assert_tables_equal(
                cache, [_pod(rng, 10_000 + step + k) for k in range(6)])
    _assert_tables_equal(cache, [make_pod(**t) for t in TEMPLATES])
    _assert_tables_equal(cache, [make_pod(**t) for t in TEMPLATES],
                         hard_weight=0)
    # the kept planes were built from nothing only when the node rows or
    # their labels changed (or for the other weight), never per launch
    assert metrics.AFFINITY_TABLE_REBUILDS.value > rebuilds0


def _absent_key_templates(key: str) -> list[dict]:
    """Required and preferred terms of both kinds on ``key``."""
    sel = {"app": "db"}
    return [
        dict(labels={"app": "db"}, affinity={"podAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                _term(sel, key)]}}),
        dict(labels={"app": "web"}, affinity={"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                _term(sel, key)]}}),
        dict(labels={"app": "db"}, affinity={
            "podAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    _weighted(_term(sel, key), 4)]},
            "podAntiAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    _weighted(_term({"app": "web"}, key), 2)]}}),
    ]


@pytest.mark.parametrize("key", ["example.com/rack", ZONE])
@pytest.mark.parametrize("first", ["batch", "resident"])
def test_a_key_no_node_carries_keeps_zero_rows(key, first):
    """A term on a topology key the fleet lacks (a zone term on a
    zone-less fleet) has no domain: its kept rows stay zero and equal the
    build from nothing, whether a batch registers the signature against
    resident pods (``match_row``'s pass) or a resident pod declares it,
    through add / assume / delete."""
    templates = _absent_key_templates(key)
    cache = SchedulerCache()
    for i in range(5):
        cache.add_node(_node(i, None))
    batch = [make_pod(name=f"b{k}", **t) for k, t in enumerate(templates)]
    if first == "batch":
        for i in range(3):                 # matched by the batch's terms
            cache.add_pod(make_pod(name=f"db{i}", node_name=f"n{i}",
                                   labels={"app": "db"}))
        _assert_tables_equal(cache, batch)
    resident = []
    for k, t in enumerate(templates * 2):
        pod = make_pod(name=f"r{k}", **t)
        if k % 2:
            cache.assume_pod(pod, f"n{k % 5}")
        else:
            pod.node_name = f"n{k % 5}"
            cache.add_pod(pod)
        resident.append(pod)
        _assert_tables_equal(cache, batch)
    aff = cache.affinity_tables()
    for planes in (aff.match, aff.decl, aff.sym):
        assert planes.rows
        for sig, r in planes.rows.items():
            assert sig.key == key and not planes.cnt[r].any()
            assert not aff.node_row(planes, sig).any()
    assert cache.affinity_planes_drift() == []
    for pod in resident:
        cache.remove_pod(pod)
        _assert_tables_equal(cache, batch)
    assert not aff.decl.rows and not aff.sym.rows


def test_engine_answers_terms_on_a_key_no_node_carries():
    """On the launch path: a required affinity term on a key the fleet
    lacks finds no node in its target's topology (unschedulable), a
    required anti-affinity or a preferred term on it keeps nothing out."""
    key = "example.com/rack"
    need, avoid, prefer = _absent_key_templates(key)
    for route in ("batch", "serial"):
        s = GenericScheduler()
        for i in range(4):
            s.cache.add_node(_node(i, None))
        s.cache.add_pod(make_pod(name="db0", node_name="n0",
                                 labels={"app": "db"}))
        pods = [make_pod(name="need", cpu="100m", memory="64Mi", **need),
                make_pod(name="avoid", cpu="100m", memory="64Mi", **avoid),
                make_pod(name="prefer", cpu="100m", memory="64Mi", **prefer)]
        if route == "batch":
            got = s.schedule_batch(pods)
        else:
            got = []
            for pod in pods:
                try:
                    got.append(s.schedule(pod))
                except FitError:
                    got.append(None)
        assert got[0] is None, route
        assert got[1] is not None and got[2] is not None, route


def test_a_launch_follows_the_batch_not_the_resident_population():
    """Steady state: no node event, one signature.  Launch after launch
    neither builds from nothing nor passes over the resident pods again;
    each attach / detach of a green pod is one row update, a plain pod's
    is none."""
    cache = SchedulerCache()
    for i in range(16):
        cache.add_node(_node(i, "z1"))
    green = TEMPLATES[0]
    resident = []
    for i in range(8):
        pod = make_pod(name=f"g{i}", node_name=f"n{i}", **green)
        cache.add_pod(pod)
        resident.append(pod)
    _assert_tables_equal(cache, [make_pod(name="b0", **green)])
    rebuilds = metrics.AFFINITY_TABLE_REBUILDS.value
    updates = metrics.AFFINITY_TABLE_ROW_UPDATES.value
    for launch in range(3):
        cache.remove_pod(resident.pop(0))
        cache.assume_pods([(make_pod(name=f"a{launch}", **green),
                            f"n{8 + launch}")])
        plain = make_pod(name=f"plain{launch}", node_name="n15")
        cache.add_pod(plain)
        cache.remove_pod(plain)
        _assert_tables_equal(cache, [make_pod(name=f"b{launch + 1}", **green)])
    assert metrics.AFFINITY_TABLE_REBUILDS.value == rebuilds
    assert metrics.AFFINITY_TABLE_ROW_UPDATES.value == updates + 6
    aff = cache.affinity_tables()
    assert len(aff.match.rows) == 1 and len(aff.decl.rows) == 1
    assert int(aff.match.total[aff.match.rows[next(iter(aff.match.rows))]]) == 8
    assert metrics.AFFINITY_RESIDENT_PODS.value == 8


# -- (a) the program against the benchmark's plain reference -----------------

def _load(directory: str, word: str):
    path = os.path.join(REPO, "benchmarks", directory, word + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{directory}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _fit_mask(s: GenericScheduler, pod: api.Pod) -> np.ndarray:
    """The program's own fit mask for one pod, off the device engine."""
    batch, db, dc, _nt = s._compile([pod])
    feasible, _scores = s.solver.evaluate(db, dc, s._pinned_flags(batch))
    mask = np.asarray(feasible[0])
    live = s.cache.node_count()       # the fleet's rows; none past them fits
    assert not mask[live:].any()
    return mask[:live]


@pytest.mark.parametrize("seed", [11, 2147483659])
def test_program_equals_the_plain_reference_on_green_pods(seed):
    """64 nodes of the benchmark's ``interpod`` shapes; green pods one by
    one and in batches through GenericScheduler on the device engine,
    random retirements between: every fit mask equals the reference's,
    every choice lies in its ``best_nodes``, and a pod that finds every
    node taken is unschedulable in both."""
    shapes, ref = _load("shapes", "interpod"), _load("references", "interpod")
    n = 64
    nodes_spec = {"count": n, "profile": "uniform", "milli_cpu": 4000,
                  "memory": 32 * 1024 ** 3, "pods": 110}
    nodes = shapes.Nodes(nodes_spec, seed)
    pods = shapes.Pods({"milli_cpu": 100, "memory": 500 * 1024 ** 2},
                       seed, nodes_spec)
    pods.grow(400)
    s = GenericScheduler()
    assert s.guard.mode == "device"
    for obj in nodes.to_json():
        s.cache.add_node(api.node_from_json(obj))
    state = ref.State(nodes, pods)
    rng = np.random.RandomState(seed % (2 ** 32))
    bound: dict[int, api.Pod] = {}         # pod index -> the cache's object
    nxt = 0

    def make(i: int) -> api.Pod:
        return api.pod_from_json(json.loads(pods.json_bytes(i)))

    def check(i: int, pod: api.Pod, chosen) -> None:
        best = ref.best_nodes(state, i)
        if chosen is None:
            assert len(best) == 0, (i, best)
            return
        node = int(chosen[len("node-"):])
        assert node in best, (i, node, best)
        state.add(i, node)
        bound[i] = pod

    def retire(count: int) -> None:
        for i in rng.permutation(sorted(bound))[:count].tolist():
            pod = bound.pop(i)
            s.cache.remove_pod(pod)
            state.add(i, int(pod.node_name[len("node-"):]), -1)

    unschedulable = 0
    for round_ in range(14):
        # one by one, the fit mask compared before each decision
        for _ in range(3):
            pod = make(nxt)
            assert np.array_equal(_fit_mask(s, make(nxt)),
                                  ref.fits(state, nxt)), nxt
            try:
                chosen = s.schedule(pod)
            except Exception as err:      # FitError: fits nowhere
                assert type(err).__name__ == "FitError"
                chosen = None
            check(nxt, pod, chosen)
            if chosen is not None:
                s.cache.assume_pod(pod, chosen)
            else:
                unschedulable += 1
            nxt += 1
        # a batch: placements inside it repel the pods after them
        size = int(rng.choice([8, 16]))      # two compiled batch shapes
        batch = [make(nxt + k) for k in range(size)]
        got = s.schedule_batch(batch)
        placed = []
        for k, (pod, chosen) in enumerate(zip(batch, got)):
            check(nxt + k, pod, chosen)
            if chosen is not None:
                placed.append((pod, chosen))
            else:
                unschedulable += 1
        s.cache.assume_pods(placed)
        nxt += size
        retire(int(rng.randint(0, 12)) if round_ % 4 != 3 else 0)
    assert not (state.held > 1).any()
    assert unschedulable > 0 and len(bound) > 40      # the fleet filled up
    assert np.array_equal(_fit_mask(s, make(nxt)), ref.fits(state, nxt))


# -- (c) the prewarm sample -------------------------------------------------

def _factory_with(resident: list[api.Pod], pending: list[api.Pod]):
    from kubernetes_tpu.apiserver.memstore import MemStore
    from kubernetes_tpu.scheduler.factory import ConfigFactory
    factory = ConfigFactory(MemStore())
    for i in range(4):
        factory.algorithm.cache.add_node(_node(i, "z1"))
    for pod in resident:
        factory.algorithm.cache.add_pod(pod)
    for pod in pending:
        factory.daemon.queue.add(pod)
    return factory


def _sample_flags(factory) -> sv.BatchFlags:
    """Flags of the batch prewarm would trace with the factory's sample
    (padded with the minimal pod, as ``Scheduler.prewarm`` pads it)."""
    pods = factory._prewarm_samples() + [
        api.Pod(name="__warm-0", namespace="__warm__")]
    batch, _db, _dc, _nt = factory.algorithm._compile(pods, host_only=True)
    return sv.batch_flags(batch)


@pytest.mark.parametrize("where", ["resident", "pending"])
def test_prewarm_sample_carries_the_affinity_flag(where):
    green = TEMPLATES[0]
    pods = [make_pod(name=f"g{i}", cpu="100m", memory="500Mi", **green)
            for i in range(3)]
    if where == "resident":
        for i, pod in enumerate(pods):
            pod.node_name = f"n{i}"
        factory = _factory_with(pods, [])
    else:
        factory = _factory_with([], pods)
    samples = factory._prewarm_samples()
    assert len(samples) == 1                    # one per distinct template
    sample = samples[0]
    assert sample.labels == pods[0].labels and not sample.node_name
    assert sample.annotations == pods[0].annotations
    assert sample.containers[0].requests == pods[0].containers[0].requests
    flags = _sample_flags(factory)
    assert flags.any_affinity_pred and not flags.any_affinity_prio


def test_prewarm_sample_without_affinity_pods_is_plain():
    plain = make_pod(name="plain", node_name="n0", labels={"app": "x"})
    factory = _factory_with([plain], [make_pod(name="q")])
    assert factory._prewarm_samples() == []
    flags = _sample_flags(factory)
    assert not flags.any_affinity_pred and not flags.any_affinity_prio


# -- the start waits for its lists ------------------------------------------

class _FakeReflector:
    """Synced from its ``synced_after``-th wait on; a wait that is not
    answered takes its whole timeout of the (fake) clock."""

    def __init__(self, kind: str, synced_after: int, clock: list):
        self.kind, self.left, self.waits = kind, synced_after, 0
        self.clock = clock

    def wait_for_sync(self, timeout: float = 10.0) -> bool:
        self.waits += 1
        self.left -= 1
        if self.left < 0:
            return True
        self.clock[0] += timeout
        return False


@pytest.mark.parametrize("kinds,rounds_late,warned,waits,waited_s", [
    (["nodes"], 0, False, 1, 0.0),       # in at once
    (["pods"], 2, False, 3, 20.0),       # two rounds late, then synced
    (["nodes"], 99, True, 4, 40.0),      # never: goes on, and says so
    (["services"], 99, True, 1, 10.0),   # not waited for beyond one round
    (["nodes", "pods", "services"], 99, True, 4, 40.0),   # ONE deadline
])
def test_start_waits_for_node_and_pod_lists(monkeypatch, kinds, rounds_late,
                                            warned, waits, waited_s):
    import logging
    import types
    from kubernetes_tpu.scheduler import factory as factory_module
    clock = [1000.0]
    monkeypatch.setattr(factory_module, "time", types.SimpleNamespace(
        monotonic=lambda: clock[0]))     # the factory's name only
    factory = _factory_with([], [])
    factory.SYNC_WAIT_S = 40.0
    late = [_FakeReflector(kind, rounds_late, clock) for kind in kinds]
    factory._reflectors = [_FakeReflector("replicasets", 0, clock)] + late
    records: list[logging.LogRecord] = []
    handler = logging.Handler()
    handler.emit = records.append
    logger = logging.getLogger("kubernetes_tpu.factory")
    logger.addHandler(handler)
    level = logger.level
    logger.setLevel(logging.INFO)
    try:
        factory._wait_for_first_lists()
    finally:
        logger.removeHandler(handler)
        logger.setLevel(level)
    warnings = [r.getMessage() for r in records
                if r.levelno >= logging.WARNING]
    if warned:
        assert len(warnings) == 1 and all(k in warnings[0] for k in kinds)
        assert "4 nodes, 0 pods cached, 0 pending" in warnings[0]
    else:
        assert not warnings
        assert any("reflectors synced (4 nodes cached)" in r.getMessage()
                   for r in records)
    assert late[0].waits == waits
    assert clock[0] - 1000.0 == waited_s
    # waiting never builds the node tensors from a partial list
    assert factory.algorithm.cache._nt is None


def test_verifier_counts_and_heals_a_drifted_plane():
    """A kept plane that no longer equals the build from nothing is a
    counted invariant violation, healed by the full re-snapshot."""
    from kubernetes_tpu.cache.verifier import Verifier
    cache = SchedulerCache()
    for i in range(6):
        cache.add_node(_node(i, "z1"))
    for i in range(3):
        cache.add_pod(make_pod(name=f"g{i}", node_name=f"n{i}",
                               **TEMPLATES[0]))
    _assert_tables_equal(cache, [make_pod(name="b", **TEMPLATES[0])])
    verifier = Verifier(cache)
    rebuilds = metrics.AFFINITY_TABLE_REBUILDS.value
    assert verifier.verify_once() == []
    assert metrics.AFFINITY_TABLE_REBUILDS.value == rebuilds   # not counted
    aff = cache.affinity_tables()
    aff.decl.cnt[next(iter(aff.decl.rows.values()))][4] += 1   # drift
    found = verifier.verify_once()
    assert [v.kind for v in found] == ["affinity_planes"]
    _assert_tables_equal(cache, [make_pod(name="b2", **TEMPLATES[0])])
    assert verifier.verify_once() == []


def test_drift_check_covers_match_planes_and_touches_no_cache_state():
    """The verifier's ground truth covers the match family too (the
    signatures batches registered), is built outside the cache's own
    arrays, and a cache whose node rows are stale is left alone: the
    check builds no tensors and grows none."""
    cache = SchedulerCache()
    for i in range(4):
        cache.add_node(_node(i, "z1"))
    assert cache.affinity_planes_drift() == [] and cache._nt is None
    for i in range(4):
        cache.add_pod(make_pod(name=f"r{i}", node_name=f"n{i}",
                               labels={"app": f"a{i % 3}"}))

    def batch(k: int) -> list[api.Pod]:
        return [make_pod(name=f"b{k}", labels={"app": f"a{k}"}, affinity={
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    _term({"app": f"a{k}"}, HOST)]}})]

    for k in range(3):
        _assert_tables_equal(cache, batch(k))
    aff = cache.affinity_tables()
    assert len(aff.match.rows) == 3
    labels, rebuilds = cache._ep.labels, metrics.AFFINITY_TABLE_REBUILDS.value
    updates = metrics.AFFINITY_TABLE_ROW_UPDATES.value
    assert cache.affinity_planes_drift() == []
    sig, row = next(iter(aff.match.rows.items()))
    aff.match.total[row] += 1                                  # drift
    assert cache.affinity_planes_drift() == [f"match plane of {sig}"]
    aff.match.total[row] -= 1
    assert cache.affinity_planes_drift() == []
    assert cache._ep.labels is labels
    assert metrics.AFFINITY_TABLE_REBUILDS.value == rebuilds
    assert metrics.AFFINITY_TABLE_ROW_UPDATES.value == updates
    cache.add_node(_node(9, "z2"))                 # planes invalidated
    assert cache.affinity_planes_drift() == []
