"""The tables a launch's feature build keeps between launches
(``features/plan.py``; the engine's ``FeaturePlan`` under the cache's
``node_epoch``).

A driven sequence of launches against ONE cache: pod events only, then
one invalidating cause — and after every launch the kept build has to
equal a build from nothing on the same snapshot (``_features(plan=None)``:
``compile_volsvc`` + ``compile_batch`` + ``apply_caps``) leaf for leaf,
dtype and shape included, while ``scheduler_feature_plan_total`` reads
the expected ``hit`` / ``miss{cause}``.  Beside it: the five
``mixedaffinity`` templates in shuffled order (the granularity is the
template, not the batch's tuple of them), the padded stream path (the
fill is one pod with one key; its answers are the unpadded solve's), and
the cache's three counters."""

from __future__ import annotations

import random

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.engine.generic_scheduler import GenericScheduler, Listers
from kubernetes_tpu.features import batch as fb
from kubernetes_tpu.features import compiler as fc
from kubernetes_tpu.features import plan as fplan
from kubernetes_tpu.utils import metrics

from helpers import make_node, make_pod

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
N_NODES = 12


def _node(i: int, **kw) -> api.Node:
    labels = {HOST: f"node-{i}", ZONE: "zone1", "pool": f"p{i % 3}"}
    labels.update(kw.pop("labels", {}))
    return make_node(f"node-{i}", labels=labels, **kw)


def _engine(listers: Listers | None = None) -> GenericScheduler:
    s = GenericScheduler(listers=listers or Listers())
    for i in range(N_NODES):
        s.cache.add_node(_node(i))
    for i in range(2 * N_NODES):
        pod = make_pod(cpu="100m", memory="64Mi", labels={"app": "web"})
        pod.node_name = f"node-{i % N_NODES}"
        s.cache.add_pod(pod)
    return s


def _leaves(batch: fb.PodBatch) -> dict[str, np.ndarray]:
    out = {}
    for name in batch.__dataclass_fields__:
        value = getattr(batch, name)
        if name == "pods":
            continue
        if name in ("aff", "volsvc"):
            for leaf in value._fields:
                out[f"{name}.{leaf}"] = np.asarray(getattr(value, leaf))
        else:
            out[name] = np.asarray(value)
    return out


def _assert_equal(kept: fb.PodBatch, fresh: fb.PodBatch) -> None:
    a, b = _leaves(kept), _leaves(fresh)
    assert a.keys() == b.keys()
    for name in a:
        assert a[name].dtype == b[name].dtype, name
        assert a[name].shape == b[name].shape, name
        assert np.array_equal(a[name], b[name]), name


def _counts() -> dict[str, float]:
    out = {"hit": metrics.FEATURE_PLAN.labels(result="hit", cause="").value}
    for cause in fplan.CAUSES:
        out[cause] = metrics.FEATURE_PLAN.labels(result="miss",
                                                 cause=cause).value
    return out


def _launch(s: GenericScheduler, pods: list[api.Pod], expect: str,
            pad_to: int = 0) -> fb.PodBatch:
    """One launch's feature build through the engine's plan: equal to the
    build from nothing on the same snapshot and the same caps, and counted
    as ``expect`` ("hit" or a miss's cause) and nothing else."""
    batch_pods = fb.pad_pods(pods, pad_to) if pad_to else list(pods)
    caps = dict(s._axis_caps)
    before = _counts()
    kept = s._compile(batch_pods, host_only=True)[0]
    after = _counts()
    moved = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    assert moved == {expect: 1}, (moved, expect)
    with s.cache.lock:
        nt, _agg, ep, nodes = s.cache.snapshot()
        fresh = s._features(batch_pods, nt, ep, nodes, None, caps)
    _assert_equal(kept, fresh)
    assert caps == s._axis_caps     # both grew the caps the same way
    return kept


def _web(k: int = 3) -> list[api.Pod]:
    return [make_pod(cpu="100m", memory="64Mi", labels={"app": "web"},
                     images=["pause"]) for _ in range(k)]


def _pod_events(s: GenericScheduler, pods: list[api.Pod]) -> None:
    """What a launch's pods and a retirement do to the cache: assumes, a
    confirm, a delete — no node event."""
    s.cache.assume_pods([(p, f"node-{i % N_NODES}")
                         for i, p in enumerate(pods)])
    s.cache.confirm_assumed(pods[0].key, pods[0].node_name)
    s.cache.remove_pod(pods[0])
    s.cache.forget_pod(pods[1])


def _warm(s: GenericScheduler) -> None:
    """First launch (everything built), then pod-only steps: hits."""
    _launch(s, _web(), "node_epoch", pad_to=8)
    for _ in range(2):
        pods = _web()
        _launch(s, pods, "hit", pad_to=8)
        _pod_events(s, pods)


# -- one case per cause -------------------------------------------------------

def _label_change(s):
    s.cache.update_node(_node(3, labels={"pool": "moved"}))


def _ready_flip(s):
    s.cache.update_node(_node(4, conditions=[("Ready", "False")]))


def _taint_added(s):
    s.cache.update_node(_node(5, taints=[
        {"key": "dedicated", "value": "db", "effect": "NoSchedule"}]))


def _node_added(s):
    s.cache.add_node(_node(N_NODES))


def _node_removed(s):
    s.cache.remove_node("node-7")


def _topo_key(s):
    s.cache.ensure_topo_key("rack")


def _same_object_mutated(s):
    node = s.cache.nodes()[2]
    node.labels["pool"] = "in-place"
    s.cache.update_node(node)


def _avoid_annotation(s):
    s.cache.update_node(_node(6, annotations={
        api.PREFER_AVOID_PODS_ANNOTATION_KEY:
        '{"preferAvoidPods": [{"podSignature": {"podController": '
        '{"kind": "ReplicationController", "uid": "default/rc-web"}}}]}'}))


@pytest.mark.parametrize("event", [
    _label_change, _ready_flip, _taint_added, _node_added, _node_removed,
    _topo_key, _same_object_mutated, _avoid_annotation],
    ids=lambda f: f.__name__.lstrip("_"))
def test_node_event_drops_the_plan_once(event):
    s = _engine(Listers(controllers=[api.ReplicationController(
        name="rc-web", selector={"app": "web"})]))
    _warm(s)
    epoch, generation = s.cache.node_epoch, s.cache.generation
    event(s)
    assert s.cache.generation > generation
    _launch(s, _web(), "node_epoch", pad_to=8)
    assert s.cache.node_epoch > epoch
    # ... and the plan built then is kept again
    pods = _web()
    _launch(s, pods, "hit", pad_to=8)
    _pod_events(s, pods)
    _launch(s, _web(), "hit", pad_to=8)


def test_status_heartbeat_that_changes_nothing_keeps_the_plan():
    """An ``update_node`` with an equal node (``api.Node`` holds only what
    the features read) moves ``generation`` and dirties the row, not the
    node epoch; the equal object takes its twin's place in the kept
    list."""
    s = _engine()
    _warm(s)
    epoch, generation = s.cache.node_epoch, s.cache.generation
    twin = _node(3)
    s.cache.update_node(twin)
    assert s.cache.node_epoch == epoch
    assert s.cache.generation == generation + 1
    assert s.cache.nodes()[3] is twin
    _launch(s, _web(), "hit", pad_to=8)


def test_pod_events_never_move_the_node_epoch():
    s = _engine()
    _warm(s)
    epoch, tensor_epoch = s.cache.node_epoch, s.cache.tensor_epoch
    nodes = s.cache.nodes()
    for _ in range(3):
        pods = _web(4)
        _pod_events(s, pods)
        s.cache.add_pod(pods[2])
        s.cache.cleanup_expired()
    assert (s.cache.node_epoch, s.cache.tensor_epoch) == (epoch, tensor_epoch)
    # the node lists are made once per epoch and handed out: the live
    # nodes, and the node of every row (free rows after the fleet's)
    assert s.cache.nodes() is nodes
    rows = s.cache.snapshot()[3]
    assert s.cache.snapshot()[3] is rows
    assert rows[:len(nodes)] == nodes and len(rows) == s.cache.snapshot()[0].n
    assert all(nd is fc.FREE_NODE for nd in rows[len(nodes):])


def test_new_template_then_kept():
    s = _engine()
    _warm(s)
    green = [make_pod(cpu="200m", memory="64Mi", labels={"color": "green"},
                      node_selector={"pool": "p1"}) for _ in range(2)]
    _launch(s, _web() + green, "template_new", pad_to=8)
    more = [make_pod(cpu="200m", memory="64Mi", labels={"color": "green"},
                     node_selector={"pool": "p1"}) for _ in range(3)]
    kept = _launch(s, more + _web(), "hit", pad_to=8)   # another order
    assert kept.sel_required.shape[0] == 2
    assert not kept.sel_required.flags.writeable        # a kept stack


@pytest.mark.parametrize("grow", ["ports", "images"])
def test_vocabulary_growth_drops_the_rows(grow):
    """A template whose ports / images push a vocabulary past its
    capacity changes the WIDTH of every kept row: the rows go (cause
    ``vocab``), the fleet's tables stay."""
    s = _engine()
    _warm(s)
    width = getattr(s.cache.space, grow).capacity
    if grow == "ports":
        wide = make_pod(cpu="100m", host_ports=list(range(9000,
                                                          9001 + width)))
    else:
        wide = make_pod(cpu="100m",
                        images=[f"img-{i}" for i in range(width + 1)])
    fleet = s._plan.fleet
    kept = _launch(s, _web() + [wide], "vocab", pad_to=8)
    assert getattr(s.cache.space, grow).capacity == 2 * width
    assert getattr(kept, grow).shape[1] == 2 * width
    assert s._plan.fleet is fleet
    _launch(s, _web(), "hit", pad_to=8)


@pytest.mark.parametrize("what", ["service", "controller"])
def test_lister_appended_in_place_is_asked_every_launch(what):
    """The listers are plain lists the factory's handlers mutate in
    place: they are asked once per template per launch, and what is kept
    from them is keyed by their answer.  The launch after an append is a
    hit of the plan AND sees the new object."""
    listers = Listers()
    s = _engine(listers)
    s.cache.update_node(_node(6, annotations={
        api.PREFER_AVOID_PODS_ANNOTATION_KEY:
        '{"preferAvoidPods": [{"podSignature": {"podController": '
        '{"kind": "ReplicationController", "uid": "default/rc-web"}}}]}'}))
    _warm(s)
    before = _launch(s, _web(), "hit", pad_to=8)
    if what == "service":
        listers.services.append(api.Service(name="web",
                                            selector={"app": "web"}))
    else:
        listers.controllers.append(api.ReplicationController(
            name="rc-web", selector={"app": "web"}))
    after = _launch(s, _web(), "hit", pad_to=8)
    # the resident web pods are counted now: 2 a node, and the two that
    # the warm-up's launches left assumed
    assert before.spread_node_counts.sum() == 0
    assert after.spread_node_counts.sum() == 2 * N_NODES + 2
    assert bool(after.spread_incr[0, 0]) and not after.spread_incr[-1].any()
    if what == "controller":
        assert after.avoid_rows.shape[0] == 2 and after.avoid_rows[1, 6]
        assert after.avoid_rows.sum() == 1
        assert after.avoid_group[:3].tolist() == [1, 1, 1]
    _launch(s, _web(), "hit", pad_to=8)


def test_volume_takes_todays_code_and_says_so():
    """Volume / service tables are kept in their neutral form only: a
    batch with a volume, or a fleet that holds one, builds them per
    launch (``not_neutral``) — the template rows are kept all the same."""
    s = _engine()
    _warm(s)
    def vol():
        return make_pod(cpu="100m", memory="64Mi",
                        volumes=[api.Volume(gce_pd_name="pd-1")])
    _launch(s, _web() + [vol()], "template_new", pad_to=8)
    held = vol()
    _launch(s, [held] + _web(), "not_neutral", pad_to=8)
    _launch(s, _web(), "hit", pad_to=8)         # none in batch or fleet
    s.cache.assume_pod(held, "node-1")
    kept = _launch(s, _web(), "not_neutral", pad_to=8)
    assert kept.volsvc.pd_node_gce[1].any()
    s.cache.forget_pod(held)
    _launch(s, _web(), "hit", pad_to=8)


# -- the five mixedaffinity templates, in any order ---------------------------

def _term(kind: str, color: str, key: str, preferred: bool) -> dict:
    term = {"labelSelector": {"matchLabels": {"color": color}},
            "topologyKey": key}
    if preferred:
        return {kind: {"preferredDuringSchedulingIgnoredDuringExecution": [
            {"weight": 1, "podAffinityTerm": term}]}}
    return {kind: {"requiredDuringSchedulingIgnoredDuringExecution": [term]}}


MIXED = {
    "base": ({}, None),
    "blue": ({"color": "blue"}, _term("podAffinity", "blue", ZONE, False)),
    "green": ({"color": "green", "name": "test"},
              _term("podAntiAffinity", "green", HOST, False)),
    "red": ({"color": "red"}, _term("podAffinity", "red", HOST, True)),
    "yellow": ({"color": "yellow"},
               _term("podAntiAffinity", "yellow", HOST, True)),
}


def _mixed(color: str) -> api.Pod:
    labels, affinity = MIXED[color]
    return make_pod(cpu="100m", memory="500Mi", labels=labels,
                    affinity=affinity, host_ports=[], images=["pause"])


def test_five_templates_in_shuffled_order_hit_like_one():
    s = _engine()
    _launch(s, [_mixed("base")], "node_epoch", pad_to=16)
    _launch(s, [_mixed(c) for c in ("blue", "base", "green")],
            "template_new", pad_to=16)
    _launch(s, [_mixed(c) for c in ("yellow", "red", "green", "blue")],
            "template_new", pad_to=16)
    assert len(s._plan.meta) == 6           # the five and the pad pod
    rng = random.Random(35)
    node = 0
    for _ in range(8):
        colors = [rng.choice(list(MIXED)) for _ in range(rng.randint(2, 9))]
        rng.shuffle(colors)
        pods = [_mixed(c) for c in colors]
        kept = _launch(s, pods, "hit", pad_to=16)
        assert kept.request.shape[0] == 16
        # the launch's pods become resident: the affinity tables follow
        # them per launch, the plan does not move
        for pod in pods:
            s.cache.assume_pod(pod, f"node-{node % N_NODES}")
            node += 1
    assert len(s._plan.meta) == 6


# -- the padded paths ---------------------------------------------------------

def test_the_fill_is_one_pod_with_one_key():
    pods = _web(3)
    padded = fb.pad_pods(pods, 8)
    assert padded[:3] == pods and len(padded) == 8
    assert all(p is fb.PAD_POD for p in padded[3:])
    assert fb.pad_pods(pods, 3) == pods
    s = _engine()
    kept = _launch(s, pods, "node_epoch", pad_to=8)
    assert len(s._plan.meta) == 2 and kept.request.shape == (8, 4)
    assert not kept.request[3:, :3].any() and kept.request[:3, 0].all()
    # a pad of the older kind (its own object, another name) shares the key
    old = api.Pod(name="__pad-7", namespace="__pad__")
    assert fb.pod_template_key(old) == fb.pod_template_key(fb.PAD_POD)
    _launch(s, _web(2) + [old] + [fb.PAD_POD] * 5, "hit")


def _placements(stream) -> list:
    out = []
    for _chunk, placed in stream:
        out.extend(placed)
    return out


def test_padded_stream_answers_are_the_unpadded_solves():
    def pods():
        return [make_pod(name=f"w-{i}", cpu="500m", memory="256Mi",
                         labels={"app": "web"},
                         node_selector={"pool": "p1"} if i % 3 == 0 else None)
                for i in range(11)]
    padded, plain = _engine(), _engine()
    for s in (padded, plain):       # both have launched before: warm plans
        s.schedule_batch(_web(2))
    before = _counts()
    got = _placements(padded.schedule_batch_stream(pods(), chunk_size=8))
    want = plain.schedule_batch(pods())
    assert got == want and None not in got
    assert sum(n in ("node-1", "node-4", "node-7", "node-10")
               for n in got[::3]) == 4
    moved = {k: v - before[k] for k, v in _counts().items()
             if v != before[k]}
    assert moved == {"template_new": 2}     # one per engine: the selector
    before = _counts()
    again = _placements(padded.schedule_batch_stream(pods(), chunk_size=8))
    assert len(again) == 11
    assert {k: v - before[k] for k, v in _counts().items()
            if v != before[k]} == {"hit": 1}


# -- what the plan is and shows -----------------------------------------------

def test_template_cap_drops_the_rows_not_the_fleet():
    s = _engine()
    _warm(s)
    fleet = s._plan.fleet
    many = [make_pod(cpu="100m", labels={"pod-name": f"sts-{i}"})
            for i in range(fplan.TEMPLATE_CAP + 1)]
    _launch(s, many, "template_new")
    assert len(s._plan.meta) > fplan.TEMPLATE_CAP
    _launch(s, _web(), "template_new", pad_to=8)    # the rows went
    assert len(s._plan.meta) == 2 and s._plan.fleet is fleet
    _launch(s, _web(), "hit", pad_to=8)


def test_report_and_debug_vars_payload():
    s = _engine()
    _warm(s)
    report = s.plan_report()
    assert report["nodeEpoch"] == s.cache.node_epoch
    assert report["hits"] == 2 and report["misses"]["node_epoch"] == 1
    assert report["templates"] == 2 and report["volsvcKept"] == 1


def test_kept_arrays_are_read_only_and_per_pod_leaves_are_not():
    s = _engine()
    _warm(s)
    batch = _launch(s, _web(), "hit", pad_to=8)
    for name in ("sel_required", "sel_pref_counts", "avoid_rows",
                 "node_zone_id", "nz_templates", "spread_node_counts"):
        with pytest.raises(ValueError):
            getattr(batch, name)[...] = 0
    assert not batch.volsvc.vz_mask.flags.writeable
    batch.request[0, 0] = 7             # a gather: the launch's own copy
    assert _launch(s, _web(), "hit", pad_to=8).request[0, 0] == 100
