"""The node axis has a capacity (ISSUE 36): a node that joins takes a free
row, a node that leaves frees its row, and neither rebuilds the node
tensors, re-uploads the fleet or mints an XLA shape.

(a) after a seeded sequence of node and pod events the cache's live rows
equal a fresh compile of the same live objects, and free rows read as
free; (b) the engine on such a cache decides as the pure-Python oracle
does over the live nodes (the serial ``schedule()`` route and the scan);
(c) a pod lands on a re-added node and none on a removed one; (d) inside
a capacity nothing of the device protocol moves but a dirty row; (e) the
join that finds no free row grows by tiles, once; (f) a node removed
with its pods on it; (g) ``capacity(n)``; (h) the verifier."""

from __future__ import annotations

import numpy as np
import pytest

from kubernetes_tpu import oracle
from kubernetes_tpu.api import types as api
from kubernetes_tpu.cache.scheduler_cache import SchedulerCache
from kubernetes_tpu.cache.verifier import Verifier
from kubernetes_tpu.engine.generic_scheduler import (FitError,
                                                     GenericScheduler,
                                                     Listers)
from kubernetes_tpu.features import compiler as fc
from kubernetes_tpu.scheduler.binder import InMemoryBinder
from kubernetes_tpu.scheduler.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.utils import metrics

from helpers import make_node, make_pod
from test_parity import _rand_cluster, _rand_pending

SEEDS = [3, 17, 2147483659]


# -- a seeded history of node and pod events ---------------------------------

class _History:
    """A cache and the plain model of what it should hold: the live
    nodes by name and the bound pods, after the same events."""

    def __init__(self, seed: int, n_nodes: int = 14, n_existing: int = 20):
        self.rng = rng = np.random.RandomState(seed % (2 ** 32))
        nodes, existing, self.services, self.controllers = _rand_cluster(
            rng, n_nodes=n_nodes, n_existing=n_existing)
        # a second fleet to join from, under names of its own
        spare, _, _, _ = _rand_cluster(rng, n_nodes=n_nodes, n_existing=0)
        for i, nd in enumerate(spare):
            nd.name = f"j{i}"
            nd.labels[api.HOSTNAME_LABEL] = nd.name
        self.spare = spare
        self.cache = SchedulerCache()
        self.live: dict[str, api.Node] = {}
        self.bound: dict[str, api.Pod] = {}
        self.seq = 0
        for nd in nodes:
            self.add_node(nd)
        for pod in existing:
            self.bind(pod, pod.node_name)
        self.cache.snapshot()           # the tensors are built: rows from here

    # events, each on the cache and on the model
    def add_node(self, nd: api.Node) -> None:
        self.cache.add_node(nd)
        self.live[nd.name] = nd

    def remove_node(self, name: str, drain: bool = True) -> None:
        if drain:
            for key in [k for k, p in self.bound.items()
                        if p.node_name == name]:
                self.retire(key)
        self.cache.remove_node(name)
        del self.live[name]

    def update_node(self, nd: api.Node) -> None:
        self.cache.update_node(nd)
        self.live[nd.name] = nd

    def bind(self, pod: api.Pod, node: str) -> None:
        pod.node_name = node
        self.cache.add_pod(pod)
        self.bound[pod.key] = pod

    def retire(self, key: str) -> None:
        self.cache.remove_pod(self.bound.pop(key))

    def random_event(self) -> str:
        rng = self.rng
        kind = rng.choice(["join", "leave", "update", "bind", "retire"],
                          p=[0.25, 0.2, 0.15, 0.25, 0.15])
        names = sorted(self.live)
        if kind == "join" and self.spare:
            self.add_node(self.spare.pop())
        elif kind == "leave" and len(names) > 4:
            self.remove_node(names[rng.randint(len(names))])
        elif kind == "update" and names:
            old = self.live[names[rng.randint(len(names))]]
            labels = dict(old.labels)
            if rng.rand() < 0.5:
                labels["disk"] = str(rng.choice(["ssd", "hdd"]))
            self.update_node(make_node(
                old.name, milli_cpu=int(rng.choice([2000, 4000, 8000])),
                memory=old.allocatable_memory, pods=old.allocatable_pods,
                labels=labels,
                conditions=[("Ready", "True" if rng.rand() > 0.15
                             else "False")]))
        elif kind == "bind" and names:
            self.seq += 1
            pod = make_pod(f"ev-{self.seq}", cpu="100m", memory="64Mi",
                           labels={"app": f"app{rng.randint(4)}"},
                           host_ports=[9000 + self.seq]
                           if rng.rand() < 0.2 else None)
            self.bind(pod, names[rng.randint(len(names))])
        elif kind == "retire" and self.bound:
            keys = sorted(self.bound)
            self.retire(keys[rng.randint(len(keys))])
        return str(kind)

    def cluster(self) -> oracle.ClusterState:
        """What the oracle reads: the live nodes and the bound pods."""
        return oracle.ClusterState(
            nodes=list(self.live.values()), pods=list(self.bound.values()),
            services=self.services, controllers=self.controllers)

    def engine(self) -> GenericScheduler:
        return GenericScheduler(cache=self.cache, listers=Listers(
            services=list(self.services),
            controllers=list(self.controllers)))


def _assert_rows_equal_a_fresh_compile(h: _History) -> None:
    nt, agg, _ep, rows = h.cache.snapshot()
    assert nt.n == len(rows)
    live_rows = {name: i for i, name in enumerate(nt.names)
                 if name is not None}
    assert live_rows == nt.name_to_idx
    assert set(live_rows) == set(h.live)
    assert [nd.name for nd in h.cache.nodes()] == [
        name for name in nt.names if name is not None]
    # a fresh compile of the same live objects, in row order, and a bulk
    # attach of the same pods (the same vocabularies: same column ids)
    order = [name for name in nt.names if name is not None]
    fresh = fc.compile_nodes([h.live[name] for name in order], h.cache.space)
    fagg = fc.empty_aggregates(len(order), h.cache.space)
    pods = [p for p in h.bound.values() if p.node_name in live_rows]
    if pods:
        fagg = fc.add_pods_to_aggregates_bulk(
            fagg, [order.index(p.node_name) for p in pods], pods,
            h.cache.space)
    idx = np.asarray([live_rows[name] for name in order], np.int64)
    for field in ("alloc", "labels", "taints_nosched", "taints_prefer",
                  "mem_pressure", "disk_pressure", "schedulable",
                  "image_kib", "topo_val"):
        have, want = getattr(nt, field)[idx], getattr(fresh, field)
        if have.ndim > 1:
            have = have[:, :want.shape[1]]
        assert np.array_equal(have, want), field
    for field in ("requested", "nonzero", "ports_used", "vol_any", "vol_rw"):
        have, want = getattr(agg, field)[idx], getattr(fagg, field)
        if have.ndim > 1:
            width = min(have.shape[1], want.shape[1])
            assert not have[:, width:].any() and not want[:, width:].any()
            have, want = have[:, :width], want[:, :width]
        assert np.array_equal(have, want), field
    # free rows read as a node no pod fits
    free = np.asarray(sorted(nt.free), np.int64)
    assert sorted(nt.free) == [i for i, name in enumerate(nt.names)
                               if name is None]
    assert len(free) == nt.n - len(h.live) > 0
    assert not nt.schedulable[free].any() and not nt.alloc[free].any()
    assert not nt.labels[free].any() and (nt.topo_val[free] == -1).all()
    assert not nt.taints_nosched[free].any()
    assert not agg.requested[free].any() and not agg.nonzero[free].any()
    assert not agg.ports_used[free].any()
    assert all(rows[i] is fc.FREE_NODE for i in free.tolist())
    assert all(rows[live_rows[name]] is h.live[name] for name in order)


# -- (a) incremental rows against a fresh compile -----------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_live_rows_equal_a_fresh_compile_after_a_seeded_history(seed):
    h = _History(seed)
    epoch, rebuilds = h.cache.tensor_epoch, h.cache.stats["rebuilds"]
    seen = set()
    for step in range(120):
        seen.add(h.random_event())
        if step % 30 == 29:
            _assert_rows_equal_a_fresh_compile(h)
    assert seen == {"join", "leave", "update", "bind", "retire"}
    _assert_rows_equal_a_fresh_compile(h)
    assert h.cache.affinity_planes_drift() == []
    # all of it inside the capacity: no rebuild, no epoch bump
    assert (h.cache.tensor_epoch, h.cache.stats["rebuilds"]) == (
        epoch, rebuilds)


def test_a_join_takes_the_lowest_free_row():
    cache = SchedulerCache()
    for i in range(6):
        cache.add_node(make_node(f"n{i}"))
    nt = cache.snapshot()[0]
    for name in ("n4", "n1", "n3"):
        cache.remove_node(name)
    for name, row in (("a", 1), ("b", 3), ("c", 4), ("d", 6)):
        cache.add_node(make_node(name))
        assert nt.name_to_idx[name] == row and nt.names[row] == name
    assert cache.snapshot()[0] is nt and cache.node_count() == 7


# -- (b) the engine on a churned cache against the oracle ---------------------

def _decide(eng: GenericScheduler, pod: api.Pod):
    try:
        return eng.schedule(pod)
    except FitError:
        return None


@pytest.mark.parametrize("seed", SEEDS)
def test_serial_route_decides_as_the_oracle_over_the_live_nodes(seed):
    """``schedule()`` pod after pod, a node event between every few:
    each choice lies in the oracle's argmax set over the live nodes (so
    it has the reference's best score), and a pod the oracle fits
    nowhere fits nowhere."""
    h = _History(seed)
    for _ in range(40):
        h.random_event()
    eng = h.engine()
    placed = 0
    for i in range(30):
        pod = _rand_pending(h.rng, i)
        want = oracle.schedule(pod, h.cluster())
        got = _decide(eng, pod)
        if got is None:
            assert not want, (i, want)
        else:
            assert got in want, (i, got, sorted(want))
            assert got in h.live
            h.bind(pod, got)
            placed += 1
        if i % 3 == 2:
            h.random_event()
    assert placed >= 10
    _assert_rows_equal_a_fresh_compile(h)


@pytest.mark.parametrize("seed", SEEDS)
def test_scan_decides_as_the_serial_route_on_a_churned_cache(seed):
    """One launch of the scan over a cache with re-used and free rows
    against serial ``schedule()`` on a twin with the same history (the
    scan's reference since PR 32): the same node for every pod."""
    def scenario():
        h = _History(seed)
        for _ in range(60):
            h.random_event()
        return h, h.engine(), [_rand_pending(h.rng, i) for i in range(24)]

    h, eng, pods = scenario()
    twin_h, twin, twin_pods = scenario()
    assert list(h.cache.snapshot()[0].names) == list(
        twin_h.cache.snapshot()[0].names)
    assert None in h.cache.snapshot()[0].names[:len(h.live)]  # holes
    eng.last_node_index = twin.last_node_index = np.uint32(5)
    scan = eng.schedule_batch(pods)
    serial = []
    for pod in twin_pods:
        host = _decide(twin, pod)
        if host is not None:
            twin_h.bind(pod, host)
        serial.append(host)
    assert scan == serial
    assert all(host is None or host in h.live for host in scan)
    assert sum(host is not None for host in scan) >= 8


# -- (c) re-added and removed nodes -------------------------------------------

def test_a_pod_lands_on_a_readded_node_and_none_on_a_removed_one():
    eng = GenericScheduler()
    for i in range(3):
        eng.cache.add_node(make_node(f"n{i}", milli_cpu=1000))
    fill = [make_pod(f"fill{i}", cpu="900m") for i in range(3)]
    for pod, dest in zip(fill, eng.schedule_batch(fill)):
        eng.cache.assume_pod(pod, dest)
    on_n1 = [p for p in fill if p.node_name == "n1"]
    for pod in on_n1:
        eng.cache.remove_pod(pod)
    eng.cache.remove_node("n1")
    # n1 had the only room; it is gone
    assert eng.schedule_batch([make_pod("early", cpu="500m")]) == [None]
    with pytest.raises(FitError) as err:
        eng.schedule(make_pod("early2", cpu="500m"))
    assert "n1" not in err.value.failed_predicates
    assert set(err.value.failed_predicates) == {"n0", "n2"}
    eng.cache.add_node(make_node("n1", milli_cpu=8000))
    assert eng.cache.snapshot()[0].name_to_idx["n1"] == 1    # its old row
    assert eng.schedule_batch([make_pod("big", cpu="4")]) == ["n1"]
    assert eng.schedule(make_pod("big2", cpu="4")) == "n1"
    eng.cache.add_node(make_node("n9", milli_cpu=16000))
    assert eng.cache.snapshot()[0].name_to_idx["n9"] == 3    # past the fleet
    assert eng.schedule_batch([make_pod("huge", cpu="12")]) == ["n9"]
    explained = eng.explain_failures([make_pod("never", cpu="64")])
    (detail,) = explained.values()
    assert detail["nodes_considered"] == 4
    assert all(top["node"] in {"n0", "n1", "n2", "n9"}
               for top in detail["top_scores"][:4])


# -- a row that changes hands while a launch is in flight ---------------------

@pytest.mark.parametrize("route", ["scan", "serial"])
@pytest.mark.parametrize("joiner", [False, True])
def test_a_row_moved_in_flight_reads_as_the_node_the_scan_saw(
        monkeypatch, route, joiner):
    """Between ``_compile`` (the cache lock let go) and the readback the
    chosen node leaves and, with ``joiner``, another takes its row: the
    decision names the node that was evaluated — never the newcomer,
    never no node — and no invariant violation is counted."""
    eng = GenericScheduler()
    eng.cache.add_node(make_node("full", milli_cpu=100))
    eng.cache.add_node(make_node("roomy", milli_cpu=8000))
    # The launch under test reads a mirror that a scatter wrote: on the
    # CPU backend the FIRST upload may alias the host arrays (no copy),
    # and the rows written below would then show through to the solve.
    warm = make_pod("warm", cpu="50m")
    eng.cache.assume_pod(warm, eng.schedule_batch([warm])[0])
    compile_ = eng._compile

    def compile_then_churn(*args, **kwargs):
        out = compile_(*args, **kwargs)
        assert eng.resident.stats["row_syncs"] == 1
        eng.cache.remove_node("roomy")
        live = eng.cache.snapshot()[0]
        assert live.names[1] is None
        if joiner:
            eng.cache.add_node(make_node("tiny", milli_cpu=100))
            assert live.names[1] == "tiny"      # the lowest free row
        return out

    monkeypatch.setattr(eng, "_compile", compile_then_churn)
    lost = metrics.CACHE_INVARIANT_VIOLATIONS.labels(kind="free_row")
    counted = lost.value
    pod = make_pod("p", cpu="4")
    dest = eng.schedule(pod) if route == "serial" \
        else eng.schedule_batch([pod])[0]
    assert dest == "roomy"
    assert lost.value == counted
    # the stale decision is assumed as any bind to a node not here: it
    # waits for a join under the name and charges no row meanwhile
    eng.cache.assume_pod(pod, dest)
    assert eng.cache.snapshot()[1].requested[1].tolist()[0] == 0
    assert Verifier(eng.cache, resident=eng.resident).verify_once() == []


# -- (d) inside a capacity nothing moves but a dirty row ----------------------

def test_join_and_removal_inside_a_capacity_scatter_and_compile_nothing():
    algo = GenericScheduler()
    for i in range(20):
        algo.cache.add_node(make_node(f"n{i}", milli_cpu=1000))
    daemon = Scheduler(SchedulerConfig(algorithm=algo,
                                       binder=InMemoryBinder(),
                                       async_bind=False))
    assert daemon.prewarm()             # traces at the capacity; arms

    def drain(*pods):
        for pod in pods:
            daemon.enqueue(pod)
        daemon.schedule_pending(wait_first=False)
        daemon.wait_for_binds()

    # the fleet filled (one 900m pod a node) and both templates seen
    drain(*[make_pod(f"fill{i}", cpu="900m") for i in range(20)])
    drain(make_pod("small", cpu="20m"))
    cache, resident = algo.cache, algo.resident
    epoch, sig = cache.tensor_epoch, resident._sig
    node_epoch = cache.node_epoch
    compiles = metrics.POST_PREWARM_COMPILES.value
    rebuilds, stats = cache.stats["rebuilds"], dict(resident.stats)
    events = metrics.CACHE_NODE_EVENTS

    def row_events() -> int:
        return events.labels(event="added", path="row").value \
            + events.labels(event="removed", path="row").value

    counted, rounds = row_events(), 3
    for k in range(rounds):
        cache.add_node(make_node(f"joiner{k}", milli_cpu=4000))
        drain(make_pod(f"j{k}", cpu="900m"))    # room on the joiner alone
        pod = cache.get_pod(f"default/j{k}")
        assert pod is not None and pod.node_name == f"joiner{k}"
        cache.remove_pod(pod)
        cache.remove_node(f"joiner{k}")
        drain(make_pod(f"after{k}", cpu="20m"))
        assert cache.get_pod(f"default/after{k}").node_name.startswith("n")
    assert (cache.tensor_epoch, resident._sig) == (epoch, sig)
    assert resident.signature(cache.snapshot()[0], cache.space)[0] == 128
    assert cache.node_epoch == node_epoch + 2 * rounds
    assert cache.stats["rebuilds"] == rebuilds
    assert resident.stats["full_syncs"] == stats["full_syncs"]
    assert resident.stats["row_syncs"] >= stats["row_syncs"] + 2 * rounds
    assert metrics.POST_PREWARM_COMPILES.value == compiles
    assert row_events() == counted + 2 * rounds
    assert cache.node_rows() == (128, 108)


# -- (e) the join that finds no free row --------------------------------------

def test_the_join_that_finds_no_free_row_grows_by_tiles_once():
    eng = GenericScheduler()
    for i in range(126):
        eng.cache.add_node(make_node(f"n{i}", milli_cpu=1000))
    assert eng.schedule_batch([make_pod("warm", cpu="100m")])[0]
    cache, resident = eng.cache, eng.resident
    assert cache.node_rows() == (128, 2)
    grow = metrics.CACHE_NODE_EVENTS.labels(event="added", path="grow")
    grown, epoch, fulls = grow.value, cache.tensor_epoch, \
        resident.stats["full_syncs"]
    cache.add_node(make_node("n126"))
    cache.add_node(make_node("n127"))
    assert (grow.value, cache.tensor_epoch) == (grown, epoch)
    assert cache.node_rows() == (128, 0)
    cache.add_node(make_node("big", milli_cpu=64000))   # no row is free
    assert (grow.value, cache.tensor_epoch) == (grown + 1, epoch + 1)
    assert cache.node_rows() == (256, 127)
    assert cache.stats["rebuilds"] == 1             # grown, not rebuilt
    assert eng.schedule_batch([make_pod("only-big", cpu="32")]) == ["big"]
    assert resident.stats["full_syncs"] == fulls + 1
    assert resident._sig[0] == 256
    cache.add_node(make_node("next"))               # a row again: no growth
    assert (grow.value, cache.tensor_epoch) == (grown + 1, epoch + 1)
    nt = cache.snapshot()[0]
    assert (nt.name_to_idx["big"], nt.name_to_idx["next"]) == (128, 129)
    assert Verifier(cache, resident=resident, sample=256).verify_once() == []


# -- (f) a node removed with its pods on it -----------------------------------

def test_a_node_removed_with_pods_frees_its_row_and_their_deletes_follow():
    eng = GenericScheduler()
    for i in range(4):
        eng.cache.add_node(make_node(f"n{i}", milli_cpu=2000))
    pods = [make_pod(f"p{i}", cpu="600m", labels={"app": "web"})
            for i in range(8)]
    for pod, dest in zip(pods, eng.schedule_batch(pods)):
        eng.cache.assume_pod(pod, dest)
    cache = eng.cache
    nt, agg, ep, _rows = cache.snapshot()
    row = nt.name_to_idx["n2"]
    left = [p for p in pods if p.node_name == "n2"]
    assert len(left) == 2 and agg.requested[row, 0] == 1200
    epoch, rebuilds = cache.tensor_epoch, cache.stats["rebuilds"]
    cache.remove_node("n2")
    assert (cache.tensor_epoch, cache.stats["rebuilds"]) == (epoch, rebuilds)
    assert nt.names[row] is None and not agg.requested[row].any()
    assert all(p.key not in ep.key_to_slot for p in left)
    assert cache.pod_count() == 8               # still tracked
    assert "n2" not in eng.schedule_batch(
        [make_pod(f"q{i}", cpu="600m") for i in range(4)])
    v = Verifier(cache, resident=eng.resident, sample=128)
    assert v.verify_once() == []
    # one of them is deleted; the node comes back before the other is
    cache.remove_pod(left[0])
    cache.add_node(make_node("n2", milli_cpu=2000))
    assert nt.name_to_idx["n2"] == row
    assert agg.requested[row].tolist()[0] == 600    # the pod still on it
    assert left[1].key in ep.key_to_slot
    cache.remove_pod(left[1])
    assert not agg.requested[row].any() and cache.pod_count() == 6
    assert (cache.tensor_epoch, cache.stats["rebuilds"]) == (epoch, rebuilds)
    assert v.verify_once() == []


def test_a_pod_bound_ahead_of_its_node_is_attached_when_the_node_joins():
    cache = SchedulerCache()
    cache.add_node(make_node("n0"))
    nt, agg, _ep, _rows = cache.snapshot()
    early = make_pod("early", cpu="700m", node_name="late")
    cache.add_pod(early)
    assert cache.stats["rebuilds"] == 1 and not cache._dirty_nodes
    cache.add_node(make_node("late"))
    assert agg.requested[nt.name_to_idx["late"], 0] == 700
    assert cache.stats["rebuilds"] == 1
    assert Verifier(cache).verify_once() == []


# -- (g) the capacity ----------------------------------------------------------

@pytest.mark.parametrize("n, rows", [(0, 128), (1, 128), (127, 128),
                                     (128, 256), (1000, 1024),
                                     (5000, 5120)])
def test_capacity_is_whole_tiles_with_a_row_to_spare(n, rows):
    assert fc.capacity(n) == rows
    assert rows % fc.NODE_TILE == 0 and 0 < rows - n <= fc.NODE_TILE


# -- (h) the verifier ----------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS[:2])
def test_verifier_finds_nothing_across_a_history_and_a_planted_fault(seed):
    h = _History(seed)
    eng = h.engine()
    v = Verifier(h.cache, resident=eng.resident, sample=128, heal=False)
    for step in range(60):
        h.random_event()
        if step % 10 == 9:
            eng.schedule_batch([make_pod(f"s{seed % 97}-{step}",
                                         cpu="50m")])   # syncs the mirror
            assert v.verify_once() == [], step
    nt, agg, _ep, _rows = h.cache.snapshot()
    row = sorted(nt.name_to_idx.values())[1]
    nt.alloc[row, 0] += 7                      # a live row, off its node
    found = v.verify_once()
    assert [x.kind for x in found if x.kind == "node_rows"], found
    assert nt.names[row] in found[0].detail
    nt.alloc[row, 0] -= 7
    agg.requested[nt.free[0], 0] = 5           # a free row that holds pods
    kinds = {x.kind for x in v.verify_once()}
    assert {"node_rows", "aggregates"} <= kinds
    agg.requested[nt.free[0], 0] = 0
    assert v.verify_once() == []


# -- /debug/vars reads counts ---------------------------------------------------

def test_debug_vars_reads_node_counts_and_builds_no_tensor():
    import json
    import urllib.request

    from kubernetes_tpu.apiserver.memstore import MemStore
    from kubernetes_tpu.scheduler.__main__ import _status_mux
    from kubernetes_tpu.scheduler.factory import ConfigFactory

    factory = ConfigFactory(MemStore())         # not run: nothing is built
    cache = factory.algorithm.cache
    for i in range(5):
        cache.add_node(make_node(f"n{i}"))
    assert cache._nt is None
    server = _status_mux(factory, {}, 0)
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/vars", timeout=10) as r:
            page = json.loads(r.read())
    finally:
        server.shutdown()
        server.server_close()
    assert (page["cachedNodes"], page["nodeCapacity"],
            page["nodeRowsFree"]) == (5, 0, 0)
    assert cache._nt is None                    # still unbuilt
    cache.snapshot()
    assert cache.node_rows() == (128, 123)


# -- what the deployment's first chip run found -------------------------------

def test_pruning_the_first_seen_registry_survives_a_bind_threads_pop():
    """The pending pods' reflector prunes ``Scheduler._first_seen`` once
    a backlog passes 65,536 keys, while the bind threads pop theirs: a
    dict that changes size under the walk used to kill that thread, and
    with it every pod still to come (ISSUE 36's run of the new cell on
    the parent's program, behind a 57,000-pod backlog)."""
    algo = GenericScheduler()
    algo.cache.add_node(make_node("n0"))
    daemon = Scheduler(SchedulerConfig(algorithm=algo,
                                       binder=InMemoryBinder(),
                                       async_bind=False))
    daemon._first_seen = {f"default/gone-{i}": float(i) for i in range(64)}
    kept = make_pod("kept", cpu="100m")
    daemon.queue.add(kept)
    daemon._first_seen[kept.key] = 1.0
    contains = algo.cache.contains

    acked = iter(range(63, -1, -1))

    def popping(key: str) -> bool:      # a bind thread acks meanwhile
        daemon._first_seen.pop(f"default/gone-{next(acked, -1)}", None)
        return contains(key)

    algo.cache.contains = popping
    daemon._prune_first_seen()
    assert list(daemon._first_seen) == [kept.key]


# -- the spans of a node event --------------------------------------------------

def test_a_profiler_session_holds_the_node_events_and_the_rebuild(tmp_path):
    """``kt.node_event`` around every node event from its road's choice
    on, ``kt.cache_rebuild`` where ``_ensure_tensors`` rebuilds: host
    events of a live ``jax.profiler`` session, on the device trace's
    clock (what a traced run's ``idle_gaps`` are labelled with)."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    cache = SchedulerCache()
    for i in range(6):
        cache.add_node(make_node(f"n{i}"))
    cache.snapshot()
    jax.profiler.start_trace(str(tmp_path))
    try:
        cache.add_node(make_node("joiner"))
        cache.remove_node("n3")
        cache.update_node(make_node("n4", milli_cpu=8000))
        cache.remove_node("never-heard-of")       # no event: nothing to do
        cache.force_resnapshot()
        cache.snapshot()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    names = [e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("kt.")]
    assert names.count("kt.node_event") == 3
    assert names.count("kt.cache_rebuild") == 1
