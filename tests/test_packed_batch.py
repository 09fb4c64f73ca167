"""The pod batch's wire form (engine/solver.py ``PackedBatch``): ONE
packed carrier crosses to the device where ~67 small arrays did (three
per-dtype buffers between PR 31 and PR 33), with the chunk's live mask,
the tie counter and the topology planes inside.  The
same values in the same dtypes have to reach the same program, so every
case here is an equality, never a tolerance."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.policy import (PredicateSpec, PrioritySpec,
                                       default_provider)
from kubernetes_tpu.engine import solver as sv
from kubernetes_tpu.engine.generic_scheduler import GenericScheduler, Listers
from kubernetes_tpu.utils import metrics

from helpers import make_node, make_pod

N_NODES = 24


def _policy():
    """The DefaultProvider plus the three policy-argument families
    (service anti-affinity, node-label predicate and priority), so the
    volsvc tables of all of them carry rows."""
    p = default_provider()
    p.predicates = p.predicates + [
        PredicateSpec("NodeLabel", labels=("rack",), presence=True)]
    p.priorities = p.priorities + [
        PrioritySpec("ServiceAntiAffinityPriority", 1,
                     anti_affinity_label="rack"),
        PrioritySpec("NodeLabelPriority", 1, label="ssd", presence=True)]
    return p


def _rig(policy=None) -> GenericScheduler:
    eng = GenericScheduler(
        policy=policy if policy is not None else _policy(),
        listers=Listers(services=[
            api.Service(name="web", selector={"app": "web"})]))
    for i in range(N_NODES):
        labels = {api.HOSTNAME_LABEL: f"n{i}", "rack": f"r{i % 3}",
                  api.ZONE_LABEL: f"z{i % 2}"}
        if i % 4 == 0:
            labels["ssd"] = "true"
        eng.cache.add_node(make_node(f"n{i}", labels=labels))
    return eng


def _term(kind: str, required: bool, labels: dict, key: str) -> dict:
    term = {"labelSelector": {"matchLabels": labels}, "topologyKey": key}
    if required:
        return {kind: {
            "requiredDuringSchedulingIgnoredDuringExecution": [term]}}
    return {kind: {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": 5, "podAffinityTerm": term}]}}


# One pod factory per content family the batch's leaves carry.
FAMILIES = {
    "plain": lambda i: make_pod(f"plain-{i}", cpu="100m", memory="500Mi"),
    "ports": lambda i: make_pod(f"ports-{i}", cpu="100m",
                                host_ports=[30000 + i % 3]),
    "volumes": lambda i: make_pod(
        f"vol-{i}", cpu="100m", volumes=[api.Volume(
            name="d", rbd_key=f"mon#pool#img{i % 4}",
            rbd_read_only=bool(i % 2))]),
    "ebs_gce": lambda i: make_pod(
        f"pd-{i}", cpu="100m", volumes=[
            api.Volume(name="e", aws_ebs_id=f"vol-{i}"),
            api.Volume(name="g", gce_pd_name=f"pd-{i}")]),
    "required_affinity": lambda i: make_pod(
        f"aff-{i}", cpu="100m", labels={"team": "a"},
        affinity=_term("podAffinity", True, {"team": "a"}, api.ZONE_LABEL)),
    "preferred_affinity": lambda i: make_pod(
        f"pref-{i}", cpu="100m", labels={"team": "b"},
        affinity=_term("podAffinity", False, {"app": "web"},
                       api.ZONE_LABEL)),
    "spread_zones": lambda i: make_pod(
        f"web-{i}", cpu="100m", labels={"app": "web"}),
    # upstream's SchedulingPodAntiAffinity pod: one required hostname
    # anti-affinity term against its own colour (interpod-5000n)
    "interpod": lambda i: make_pod(
        f"green-{i}", cpu="100m", labels={"color": "green"},
        affinity=_term("podAntiAffinity", True, {"color": "green"},
                       api.HOSTNAME_LABEL)),
}


# The families on which engine/hostsolver.py places what the device scan
# places (on in-batch affinity and zone-spread dynamics the fallback
# engine differs from the scan, on the parent commit as on this one).
HOST_PARITY = ("plain", "ports", "volumes", "ebs_gce")


def _pods(family: str, n: int) -> list[api.Pod]:
    mixes = {"everything": sorted(FAMILIES), "host_mix": HOST_PARITY}
    if family in mixes:
        makers = [FAMILIES[f] for f in mixes[family]]
        return [makers[i % len(makers)](i) for i in range(n)]
    return [FAMILIES[family](i) for i in range(n)]


def _leaves(b: sv.DeviceBatch) -> dict:
    return dict(zip(sv._BATCH_PATHS, sv._batch_leaves(b)))


def _assert_same_batch(got: sv.DeviceBatch, want: sv.DeviceBatch) -> None:
    got, want = _leaves(got), _leaves(want)
    assert got.keys() == want.keys()
    for path, w in want.items():
        g = np.asarray(got[path])
        assert g.dtype == np.asarray(w).dtype, path
        assert g.shape == np.asarray(w).shape, path
        np.testing.assert_array_equal(g, w, err_msg=path)


# -- pack -> unpack ----------------------------------------------------------

@pytest.mark.parametrize("family", sorted(FAMILIES) + ["everything"])
def test_pack_unpack_gives_back_every_leaf(family):
    eng = _rig()
    # a resident peer, so the affinity / spread / saa tables hold counts
    peer = make_pod("peer", labels={"app": "web", "team": "a",
                                    "color": "green"})
    peer.node_name = "n1"
    eng.cache.add_pod(peer)
    batch, hb, _hc, _nt = eng._compile(_pods(family, 8), host_only=True)
    if family != "plain":
        flags = sv.batch_flags(batch)
        want_on = {"ports": flags.any_ports, "volumes": flags.any_volumes,
                   "ebs_gce": flags.any_ebs and flags.any_gce,
                   "required_affinity": flags.any_affinity_pred,
                   "preferred_affinity": flags.any_affinity_prio,
                   "spread_zones": flags.any_spread_zones and flags.any_saa,
                   "interpod": flags.any_affinity_pred,
                   "everything": all(flags)}
        assert want_on[family], f"{family}: the batch lacks its content"
    packed = sv.pack_batch(hb)
    assert packed.buffer.dtype == np.int32 and packed.buffer.ndim == 1
    assert len(jax.tree_util.tree_leaves(packed)) == 1
    _assert_same_batch(sv.unpack_batch(packed), hb)
    # and through a jit, where the entrypoints unpack it
    _assert_same_batch(jax.jit(sv.unpack_batch)(jax.device_put(packed)), hb)


def test_node_label_rows_cross_the_wire():
    eng = _rig()
    _batch, hb, _hc, _nt = eng._compile(_pods("plain", 2), host_only=True)
    vs = sv.unpack_batch(sv.pack_batch(hb)).volsvc
    # (the fleet's rows; the free rows of the node axis carry no label)
    assert np.asarray(vs.nl_pred_row)[:N_NODES].all()   # every node: a rack
    assert not np.asarray(vs.nl_pred_row)[N_NODES:].any()
    assert np.asarray(vs.nl_prio_rows).sum() == N_NODES // 4   # ssd nodes
    assert np.asarray(vs.saa_labeled)[..., :N_NODES].all()


@pytest.mark.parametrize("start,stop,real", [(0, 8, 8), (8, 16, 8),
                                             (16, 24, 3)])
def test_pod_axis_slices_with_pad_rows(start, stop, real):
    """The streamed drain's chunks: a pod-axis slice of the padded host
    batch, the chunk's live mask, the counter on the first chunk only."""
    eng = _rig()
    pods = _pods("everything", 19) + [
        api.Pod(name=f"__pad-{i}", namespace="__pad__") for i in range(5)]
    _batch, hb, _hc, _nt = eng._compile(pods, host_only=True)
    live = np.zeros(24, bool)
    live[:19] = True
    chunk = sv.slice_pod_axis(hb, start, stop)
    counter = np.uint32(4_000_000_123) if start == 0 else None
    packed = sv.pack_batch(chunk, live=live[start:stop], counter=counter)
    db, k, sb, lv, em = sv.unpack_launch(packed, None, None, None, None)
    _assert_same_batch(db, chunk)
    assert int(np.asarray(lv).sum()) == real
    np.testing.assert_array_equal(np.asarray(lv), live[start:stop])
    assert sb is None and em is None
    if start == 0:
        # over 2**31: the counter's bits ride the int32 carrier as they are
        assert np.asarray(k).dtype == np.uint32
        assert int(k) == 4_000_000_123
    else:
        assert k is None


def test_planes_ride_the_buffers_and_given_arguments_stand():
    eng = _rig()
    _batch, hb, _hc, _nt = eng._compile(_pods("plain", 4), host_only=True)
    rng = np.random.RandomState(3)
    mask = rng.rand(4, N_NODES) < 0.5
    bias = rng.rand(4, N_NODES).astype(np.float32)
    packed = sv.pack_batch(hb, live=np.ones(4, bool), counter=np.uint32(7),
                           extra_mask=mask, score_bias=bias)
    assert len(jax.tree_util.tree_leaves(packed)) == 1
    _db, k, sb, lv, em = sv.unpack_launch(packed, None, None, None, None)
    np.testing.assert_array_equal(np.asarray(em), mask)
    np.testing.assert_array_equal(np.asarray(sb), bias)
    assert np.asarray(sb).dtype == np.float32 and int(k) == 7
    # an argument given outright is not overwritten from the buffers
    _db, k2, sb2, _lv, _em = sv.unpack_launch(
        packed, np.uint32(9), bias * 2, None, None)
    assert int(k2) == 9
    np.testing.assert_array_equal(np.asarray(sb2), bias * 2)


def test_unpack_is_the_identity_on_a_device_batch():
    eng = _rig()
    _batch, hb, _hc, _nt = eng._compile(_pods("plain", 2), host_only=True)
    assert sv.unpack_batch(hb) is hb
    k = np.uint32(1)
    assert sv.unpack_launch(hb, k, None, None, None) == \
        (hb, k, None, None, None)


def test_layout_follows_shapes_alone_and_a_strange_dtype_is_refused():
    eng = _rig()
    _b, hb_a, _hc, _nt = eng._compile(_pods("plain", 4), host_only=True)
    _b, hb_b, _hc, _nt = eng._compile(_pods("plain", 4), host_only=True)
    hb_b = hb_b._replace(request=hb_b.request + 1)
    assert sv.pack_batch(hb_a).layout == sv.pack_batch(hb_b).layout
    assert hash(sv.pack_batch(hb_a).layout) == \
        hash(sv.pack_batch(hb_b).layout)
    paths = [e[0] for e in
             sv.pack_batch(hb_a, live=np.ones(4, bool)).layout[0]]
    assert paths[:len(sv._BATCH_PATHS)] == list(sv._BATCH_PATHS)
    assert paths[len(sv._BATCH_PATHS):] == ["live"]
    with pytest.raises(TypeError, match="request"):
        sv.pack_batch(hb_a._replace(request=hb_a.request.astype(np.int64)))


# -- the engine's paths ------------------------------------------------------

def _drain(eng: GenericScheduler, pods: list, chunk: int) -> list:
    placed: list = []
    for _chunk_pods, placements in eng.schedule_batch_stream(
            pods, chunk_size=chunk):
        placed.extend(placements)
    return placed


@pytest.mark.parametrize("chunks", [1, 2, 3])
@pytest.mark.parametrize("family", ["plain", "host_mix", "everything"])
def test_stream_places_what_oneshot_and_the_host_solver_place(chunks,
                                                              family):
    """The counter's way through the buffer and across chunks: the
    streamed drain over 1, 2 and 3 chunks, the one-shot solve and
    engine/hostsolver.py (where it has the scan's dynamics: HOST_PARITY)
    agree on every placement and leave the same ``last_node_index`` —
    started from a counter over 2**31."""
    n_pods, chunk = 21, {1: 32, 2: 16, 3: 8}[chunks]
    start = np.uint32(3_000_000_001)
    placed, counters = [], []
    modes = ("stream", "oneshot") + (
        () if family == "everything" else ("host",))
    for mode in modes:
        eng = _rig()
        eng.last_node_index = start
        pods = _pods(family, n_pods)
        if mode == "stream":
            placed.append(_drain(eng, pods, chunk))
        elif mode == "oneshot":
            placed.append(eng.schedule_batch(pods))
        else:
            placed.append(eng.schedule_batch_host(pods))
        counters.append(int(eng.last_node_index))
    assert all(p == placed[0] for p in placed[1:])
    assert all(k == counters[0] for k in counters[1:])
    assert sum(p is not None for p in placed[0]) >= n_pods // 2
    assert counters[0] != int(start)


def test_padded_oneshot_and_joint_carry_live_and_counter_in_the_batch():
    pods_a, pods_b = _pods("plain", 5), _pods("plain", 5)
    a, b = _rig(), _rig()
    assert a.schedule_batch(pods_a) == b.schedule_batch(pods_b, pad_to=8)
    assert int(a.last_node_index) == int(b.last_node_index) > 0
    j = _rig()
    got = j.schedule_batch(_pods("plain", 5), joint=True, pad_to=8)
    assert len(got) == 5 and None not in got


def _count(family, cause: str) -> float:
    child = family.children().get((cause,))
    return float(child.value) if child is not None else 0.0


def _arrays(cause: str = "batch") -> float:
    return _count(metrics.DEVICE_TRANSFER_ARRAYS, cause)


def _bytes(cause: str = "batch") -> float:
    return _count(metrics.DEVICE_TRANSFER_BYTES, cause)


def test_one_launch_hands_the_runtime_one_array():
    eng = _rig()
    before, bytes_before = _arrays(), _bytes()
    assert None not in _drain(eng, _pods("plain", 6), 8)
    assert _arrays() - before == 1         # one chunk, one carrier
    assert _bytes() > bytes_before
    before = _arrays()
    _drain(eng, _pods("plain", 20), 8)     # three chunks, three uploads
    assert _arrays() - before == 3
    before = _arrays()
    eng.schedule_batch(_pods("plain", 6))
    assert _arrays() - before == 1
    before = _arrays()
    eng.schedule(make_pod("single", cpu="100m"))
    assert _arrays() - before == 1
    # the cluster's uploads count their arrays too
    assert _arrays("full_upload") > 0


@pytest.mark.parametrize("family", ["plain", "host_mix"])
def test_placements_agree_across_a_launch_that_scatters(family):
    """The rows' way through their one buffer: a first wave lands, so the
    next launch's sync scatters its rows; the streamed drain, the
    one-shot solve and serial ``schedule()`` (every call after the first
    a launch that scatters the row before it) then place a second wave
    alike, and every launch that scattered handed the runtime ONE array
    for it."""
    from kubernetes_tpu.engine.generic_scheduler import FitError
    placed, counters = [], []
    for mode in ("stream", "oneshot", "serial"):
        eng = _rig()
        eng.last_node_index = np.uint32(3_000_000_001)
        pods = _pods(family, 15)     # 5 rows of 24 dirty: under N/4
        for pod, dest in zip(pods[:5], eng.schedule_batch(pods[:5])):
            assert dest is not None
            pod.node_name = dest
            eng.cache.add_pod(pod)
        syncs, arrays = eng.resident.stats["row_syncs"], _arrays("scatter")
        if mode == "stream":
            got = _drain(eng, pods[5:], 8)
        elif mode == "oneshot":
            got = eng.schedule_batch(pods[5:])
        else:
            got = []
            for pod in pods[5:]:
                try:
                    got.append(eng.schedule(pod))
                except FitError:
                    got.append(None)
                    continue
                pod.node_name = got[-1]
                eng.cache.add_pod(pod)
        scattered = eng.resident.stats["row_syncs"] - syncs
        assert scattered == (sum(g is not None for g in got)
                             if mode == "serial" else 1)
        assert _arrays("scatter") - arrays == scattered
        assert eng.resident.stats["full_syncs"] == 1
        placed.append(got)
        counters.append(int(eng.last_node_index))
    assert placed[0] == placed[1] == placed[2]
    assert counters[0] == counters[1] == counters[2]
    assert sum(p is not None for p in placed[0]) >= 5


def test_equal_shapes_and_different_content_hit_one_program():
    eng = _rig(default_provider())
    _drain(eng, _pods("plain", 6), 8)             # traces scan_first@8
    scan = sv.Solver._solve_scan
    size = scan._cache_size()
    other = [make_pod(f"other-{i}", cpu=f"{50 * (i + 1)}m",
                      memory=f"{64 * (i + 1)}Mi") for i in range(5)]
    assert None not in _drain(eng, other, 8)
    assert scan._cache_size() == size


def test_the_metric_family_is_on_the_page_by_cause():
    eng = _rig()
    _drain(eng, _pods("plain", 3), 8)
    page = metrics.expose_registry()
    assert 'scheduler_device_transfer_arrays_total{cause="batch"}' in page
    assert 'scheduler_device_transfer_bytes_total{cause="batch"}' in page
