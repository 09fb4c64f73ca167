"""The sequential scan (``Solver._solve_scan``) + the narrow dtype policy.

Parity contract: the scan (sparse commits, template-factored scores,
one select per step) must be DECISION-IDENTICAL to the serial route —
``GenericScheduler.schedule()`` pod after pod with the cache updated in
between, which shares no line with the scan (``Solver.evaluate`` +
``combine.select_hosts``; held score for score to the pure-Python
oracle by tests/test_parity.py) — to the NumPy host engine, and to
itself across live-mask padding, topology constraint planes and chunked
carry.  The narrow dtype policy must be value-lossless, with the int16
gate falling back to int32 at capacity limits instead of wrapping."""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from kubernetes_tpu import oracle
from kubernetes_tpu.api import types as api
from kubernetes_tpu.engine import solver as sv
from kubernetes_tpu.engine.generic_scheduler import (FitError,
                                                     GenericScheduler)
from kubernetes_tpu.ops import combine
from kubernetes_tpu.perf import synth

from helpers import make_node, make_pod

COUNTER = 5  # the tie counter every run starts from


def _rig(profile: str, n_nodes: int = 48):
    eng, _ = synth.make_rig(n_nodes, 0, profile=profile)
    return eng


def _solve(solver, db, dc, flags, p: int, **kw) -> tuple:
    """(choices [P], counter, requested [N,4], nonzero [N,2]) of
    ``solve_sequential_packed`` over a batch of ``p`` rows."""
    n = sv.cluster_nodes(dc)
    packed = np.asarray(solver.solve_sequential_packed(
        db, dc, jnp.uint32(COUNTER), flags, **kw))
    return (packed[:p], int(packed[p]),
            packed[p + 1:p + 1 + 4 * n].reshape(n, 4),
            packed[p + 1 + 4 * n:].reshape(n, 2))


def _assert_scan_matches_serial(eng: GenericScheduler, twin: GenericScheduler,
                                pods: list, twin_pods: list) -> None:
    """The scan over ``pods`` on ``eng`` against serial ``schedule()`` of
    the equal ``twin_pods`` on the equal rig ``twin``: choices, the tie
    counter, and the final aggregates bit for bit."""
    batch, db, dc, nt = eng._compile(pods)
    choices, counter, requested, nonzero = _solve(
        eng.solver, db, dc, sv.batch_flags(batch), len(pods))
    scan = [nt.names[c] if c >= 0 else None for c in choices]

    twin.last_node_index = np.uint32(COUNTER)
    serial = []
    for pod in twin_pods:
        try:
            host = twin.schedule(pod)
        except FitError:
            serial.append(None)
            continue
        pod.node_name = host
        twin.cache.add_pod(pod)
        serial.append(host)
    diverged = [i for i, (a, b) in enumerate(zip(scan, serial)) if a != b]
    assert not diverged, (
        f"first diverging pod {diverged[0]} ({pods[diverged[0]].key}): "
        f"scan {scan[diverged[0]]} serial {serial[diverged[0]]}")
    assert counter == int(twin.last_node_index)
    with twin.cache.lock:
        t_nt, t_agg, _ep, _nodes = twin.cache.snapshot()
    assert list(t_nt.names) == list(nt.names)
    assert np.array_equal(requested, t_agg.requested)
    assert np.array_equal(nonzero, t_agg.nonzero)


@pytest.mark.parametrize("profile", ["uniform", "mixed", "rich"])
def test_scan_matches_serial_schedule(profile):
    """Choices, tie counter, AND final aggregates equal the serial route
    across the full per-profile feature surface (rich exercises ports,
    volumes, EBS, inter-pod affinity and tolerations in-scan)."""
    _assert_scan_matches_serial(
        _rig(profile), _rig(profile),
        synth.make_pods(160, profile=profile, n_services=4),
        synth.make_pods(160, profile=profile, n_services=4))


def test_dead_rows_are_inert_and_topo_planes_flow_through():
    """Gang-padding (dead live rows) and the workload-constraint planes
    (extra_mask / score_bias): the 96-row run with 26 dead rows equals
    the 70-row run on the planes' first 70 rows."""
    eng = _rig("mixed")
    pods = synth.make_pods(96, profile="mixed", n_services=4)
    batch, db, dc, nt = eng._compile(pods)
    flags = sv.batch_flags(batch)
    rng = np.random.RandomState(3)
    n = sv.cluster_nodes(dc)
    live = np.ones(96, bool)
    live[70:] = False  # padded gang tail
    em = rng.rand(96, n) > 0.05
    bias = rng.randint(0, 5, (96, n)).astype(np.float32)
    padded = _solve(
        eng.solver, db, dc, flags, 96, live=jnp.asarray(live),
        extra_mask=jnp.asarray(em), score_bias=jnp.asarray(bias))
    db70 = jax.device_put(sv.slice_pod_axis(sv.host_batch(batch), 0, 70))
    exact = _solve(
        eng.solver, db70, dc, flags, 70, extra_mask=jnp.asarray(em[:70]),
        score_bias=jnp.asarray(bias[:70]))
    assert np.array_equal(padded[0][:70], exact[0])
    assert (padded[0][:70] >= 0).any()
    # Dead rows place nothing and bump no counter.
    assert (padded[0][70:] == -1).all()
    assert padded[1] == exact[1]
    assert np.array_equal(padded[2], exact[2])
    assert np.array_equal(padded[3], exact[3])


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_carry_matches_oneshot(chunk):
    """Ladder-bucket chunking with carried state equals the one-shot
    solve."""
    eng = _rig("mixed")
    pods = synth.make_pods(128, profile="mixed", n_services=4)
    batch, db, dc, nt = eng._compile(pods)
    flags = sv.batch_flags(batch)
    hb = sv.host_batch(batch)
    one = _solve(eng.solver, db, dc, flags, 128)[0]
    counter = jnp.uint32(COUNTER)
    carry = None
    outs = []
    for start in range(0, 128, chunk):
        db_k = jax.device_put(sv.slice_pod_axis(hb, start, start + chunk))
        ch, counter, carry = eng.solver._solve_scan(
            db_k, dc, counter, None, flags, carry, None, None)
        outs.append(np.asarray(ch))
    assert np.array_equal(np.concatenate(outs), one)


# -- the loop's bound follows the live rows (PR 37) ----------------------

def _full_length(step, init, xs, p, live):
    """The reference: the fixed-length scan the program ran before the
    loop's bound followed the live rows — every row of the bucket is
    stepped, dead or not."""
    return jax.lax.scan(step, init, xs, unroll=sv.SCAN_UNROLL)


def _reference_scan(self, *args):
    """``Solver._solve_scan``'s own trace — the same hoisted planes, the
    same ``step``, the same init and xs — under the full-length
    reference loop."""
    mine = sv.run_live_steps
    sv.run_live_steps = _full_length
    try:
        return sv.Solver._solve_scan.__wrapped__(self, *args)
    finally:
        sv.run_live_steps = mine


_reference_scan = jax.jit(_reference_scan, static_argnums=(0, 5))


def _prefix(p: int, live: int) -> np.ndarray:
    mask = np.zeros(p, bool)
    mask[:live] = True
    return mask


def _hole(p: int) -> np.ndarray:
    """live, dead, live: the bound is the LAST live row, the dead rows
    before it run as the inert steps they are."""
    mask = np.zeros(p, bool)
    mask[:9] = True
    mask[40:53] = True
    return mask


def _bits(x) -> tuple:
    x = np.asarray(x)
    return x.dtype, x.shape, x.tobytes()


# (profile, rows of the batch, live mask or None, chunk or None, family)
LOOP_CASES = {
    **{f"prefix-{k}-of-256": ("mixed", 256, _prefix(256, k), None, None)
       for k in (0, 1, 3, 4, 5, 30, 256)},
    "hole-live-dead-live": ("mixed", 256, _hole(256), None, None),
    "live-none": ("mixed", 256, None, None, None),
    "rows-not-a-multiple-of-the-unroll": (
        "mixed", 70, _prefix(70, 33), None, None),
    "rows-not-a-multiple-live-none": ("mixed", 70, None, None, None),
    "two-chunks-second-mostly-padding": (
        "mixed", 128, _prefix(128, 70), 64, None),
    "affinity-families-in-the-carry": (
        "rich", 96, _prefix(96, 41), None, "track_affinity"),
    "spread-families-in-the-carry": (
        "mixed", 96, _prefix(96, 41), None, "track_spread"),
}


@pytest.mark.parametrize("case", list(LOOP_CASES))
def test_live_bounded_loop_is_bit_equal_to_the_full_length_scan(case):
    """For every live mask the loop that stops at its last live row
    returns what the full-length scan of the same ``step`` returns:
    choices, the tie counter and every key of the final state bit for
    bit; dead rows read -1."""
    profile, p, live, chunk, family = LOOP_CASES[case]
    eng = _rig(profile)
    pods = synth.make_pods(p, profile=profile, n_services=4)
    batch, _db, dc, _nt = eng._compile(pods)
    flags = sv.batch_flags(batch)
    if family is not None:
        assert getattr(eng.solver._scan_families(flags), family)
    hb = sv.host_batch(batch)
    chunk = chunk or p
    counters = {"new": jnp.uint32(COUNTER), "ref": jnp.uint32(COUNTER)}
    carries = {"new": None, "ref": None}
    placed = 0
    for start in range(0, p, chunk):
        db_k = jax.device_put(sv.slice_pod_axis(hb, start, start + chunk))
        live_k = None if live is None else live[start:start + chunk]
        out = {}
        # the reference first: the program's own call donates its carry
        for side, scan in (("ref", _reference_scan),
                           ("new", sv.Solver._solve_scan)):
            ch, counters[side], carries[side] = scan(
                eng.solver, db_k, dc, counters[side], None, flags,
                carries[side],
                None if live_k is None else jnp.asarray(live_k), None)
            out[side] = (np.asarray(ch), int(counters[side]),
                         {k: _bits(v) for k, v in carries[side].items()})
        assert out["new"][0].dtype == np.int32
        assert np.array_equal(out["new"][0], out["ref"][0])
        assert out["new"][1] == out["ref"][1]
        assert out["new"][2].keys() == out["ref"][2].keys()
        for key in out["ref"][2]:
            assert out["new"][2][key] == out["ref"][2][key], key
        if live_k is not None:
            assert (out["new"][0][~live_k] == -1).all()
        placed += int((out["new"][0] >= 0).sum())
    assert placed > 0 or (live is not None and not live.any())


@pytest.mark.parametrize("p,live", [
    (256, _prefix(256, 0)), (256, _prefix(256, 1)), (256, _prefix(256, 4)),
    (256, _prefix(256, 5)), (256, _prefix(256, 30)),
    (256, _prefix(256, 256)), (256, _hole(256)), (256, None),
    (70, _prefix(70, 33)), (70, None)],
    ids=["prefix-0", "prefix-1", "prefix-4", "prefix-5", "prefix-30",
         "full", "hole", "live-none", "odd-rows", "odd-rows-live-none"])
def test_loop_steps_the_rows_to_the_last_live_one_and_no_more(p, live):
    """``run_live_steps`` over a step that counts itself: the steps run
    are the last live row + 1 rounded up to whole iterations (4 where 4
    divides the rows, else 1) — what ``scan_steps`` tells the account —
    and the rows never stepped read -1."""
    def step(state, xs):
        return {"steps": state["steps"] + 1}, xs["row"]

    final, choices = jax.jit(
        lambda live: sv.run_live_steps(
            step, {"steps": jnp.int32(0)},
            {"row": jnp.arange(p, dtype=jnp.int32)}, p, live))(
        None if live is None else jnp.asarray(live))
    last = p if live is None else \
        (int(np.flatnonzero(live)[-1]) + 1 if live.any() else 0)
    unroll = 4 if p % 4 == 0 else 1
    ran = -(-last // unroll) * unroll
    assert int(final["steps"]) == ran == sv.scan_steps(live, p)
    assert np.array_equal(
        np.asarray(choices),
        np.where(np.arange(p) < ran, np.arange(p), -1))


def test_scan_matches_host_engine_drain():
    """The NumPy fallback engine and the device drain assign the same
    nodes for the same queue (the guard's breaker swap must not move
    decisions).  Uniform profile: the host engine's mixed-profile tie
    ordering diverges from the device scan (its contract is oracle
    parity, pinned in test_device_faults; ROADMAP names the debt)."""
    eng = _rig("uniform", n_nodes=24)
    pods = synth.make_pods(60, profile="uniform")
    dev = eng.schedule_batch(list(pods))
    eng2, _ = synth.make_rig(24, 0, profile="uniform")
    host = eng2.schedule_batch_host(list(pods))
    assert dev == host


def test_preemption_decisions_match_the_oracle():
    """The preemption path (masks + victim solve + overlays) against the
    pure-Python ``oracle.preempt``: three priority pods in one call, each
    decision replayed into the oracle's cluster before the next."""
    eng = GenericScheduler()
    nodes = [make_node(f"pn{i}", milli_cpu=1000) for i in range(8)]
    cluster = oracle.ClusterState(nodes=nodes)
    for i, node in enumerate(nodes):
        eng.cache.add_node(node)
        victim = make_pod(f"v{i}", cpu="800m")
        victim.node_name = node.name
        eng.cache.add_pod(victim)
        cluster.pods.append(victim)
    high = []
    for i in range(3):
        p = make_pod(f"h{i}", cpu="500m")
        p.annotations[api.PRIORITY_ANNOTATION_KEY] = "100"
        high.append(p)
    by_key = {p.key: p for p in high}
    decisions = eng.find_preemptions(list(high))
    assert [d.pod_key for d in decisions] == [p.key for p in high]
    assert any(d.victims for d in decisions)
    for d in decisions:
        pod = by_key[d.pod_key]
        assert (d.node, len(d.victims), d.prio_cost) == \
            oracle.preempt(pod, cluster)
        assert {q.node_name for q in cluster.pods
                if q.key in d.victims} <= {d.node}
        cluster.pods = [q for q in cluster.pods if q.key not in d.victims]
        pod.node_name = d.node
        cluster.pods.append(pod)


def test_select_host_matches_reference_semantics():
    """select_host implements selectHost's round-robin tie-break: the
    ``counter % n_ties``-th feasible max-score node in index order, with
    the modulo in uint32 (counters past 2^31 must not go negative)."""
    rng = np.random.RandomState(11)
    for trial in range(25):
        n = int(rng.choice([8, 33, 128]))
        scores = rng.randint(0, 4, n).astype(np.float32)
        mask = rng.rand(n) > 0.4
        masked = jnp.asarray(np.where(mask, scores, -np.inf))
        count = int(rng.randint(0, 7)) + (2 ** 31 if trial % 2 else 0)
        cx, ax = combine.select_host(masked, jnp.uint32(count))
        assert bool(ax) == bool(mask.any())
        # Reference semantics, computed independently.
        if not mask.any():
            assert int(cx) == -1
        else:
            mx = scores[mask].max()
            ties = np.flatnonzero(mask & (scores == mx))
            assert int(cx) == ties[count % len(ties)]


# -- narrow dtype policy -------------------------------------------------

def test_narrow_cluster_roundtrip_is_lossless():
    eng = _rig("mixed")
    synthetic = synth.make_pods(24, profile="mixed", n_services=4)
    for pod, dest in zip(synthetic, eng.schedule_batch(synthetic)):
        if dest:
            pod.node_name = dest
            eng.cache.add_pod(pod)
    with eng.cache.lock:
        nt, agg, ep, nodes = eng.cache.snapshot()
        hc = sv._host_cluster(nt, agg, eng.cache.space)
    policy = sv.narrow_policy(nt, agg, eng.cache.space)
    assert policy.res == "int16"
    wide = sv.widen_cluster(sv.narrow_cluster(hc, policy))
    for field, a, b in zip(sv.DeviceCluster._fields, hc, wide):
        assert np.array_equal(np.asarray(a), np.asarray(b)), field


def test_int16_gate_falls_back_instead_of_wrapping():
    """A node AT int16 capacity limits must not wrap: the range gate
    widens the signature to int32 and the solve still sees exact
    values."""
    eng = GenericScheduler()
    # 64-core node: 64000 milli-CPU is past the int16 gate.
    eng.cache.add_node(make_node("big", milli_cpu=64000,
                                 memory=128 * 1024 ** 3, pods=110))
    with eng.cache.lock:
        nt, agg, ep, nodes = eng.cache.snapshot()
    policy = sv.narrow_policy(nt, agg, eng.cache.space)
    assert policy.res == "int32"
    dest = eng.schedule_batch([make_pod("wide-pod", cpu="50000m")])
    assert dest == ["big"]
    res = sv.widen_cluster(eng.resident.dc)
    assert int(np.asarray(res.alloc)[0, 0]) == 64000


def test_int16_gate_headroom_near_limit():
    """Just UNDER the gate stays int16 and still never wraps: the gate
    reserves headroom for a full pod-count worth of nonzero defaults."""
    eng = GenericScheduler()
    eng.cache.add_node(make_node("edge", milli_cpu=31000,
                                 memory=8 * 1024 ** 3, pods=4))
    pods = [make_pod(f"e{i}", cpu="7000m") for i in range(4)]
    assert eng.schedule_batch(pods) == ["edge"] * 4
    with eng.cache.lock:
        nt, agg, ep, nodes = eng.cache.snapshot()
    policy = sv.narrow_policy(nt, agg, eng.cache.space)
    assert policy.res == "int16"
    # Mirror the binds and verify the device copy reads back exact.
    for i, pod in enumerate(pods):
        pod.node_name = "edge"
        eng.cache.add_pod(pod)
    eng.schedule_batch([make_pod("probe")])  # forces a sync
    rows = eng.resident.readback_rows([0])
    # 4 x 7000m requested, exact through the int16 wire; the nonzero
    # plane additionally carries the best-effort probe's 100m default.
    assert int(rows["requested"][0, 0]) == 4 * 7000
    assert int(rows["nonzero"][0, 0]) == 4 * 7000


def test_dyn_template_cap_falls_back_to_inscan_path():
    """More distinct nonzero templates than KT_DYN_TEMPLATES compiles
    the template table away (shape 0) — and decisions still match the
    serial route."""
    rng = np.random.RandomState(5)
    shapes = [(int(rng.randint(1, 200)), int(rng.randint(1, 200)))
              for _ in range(sv.DYN_TEMPLATE_CAP + 40)]

    def pods():
        return [make_pod(f"t{i}", cpu=f"{cpu}m", memory=f"{mem}Mi")
                for i, (cpu, mem) in enumerate(shapes)]

    eng = _rig("uniform", n_nodes=16)
    batch, _db, _dc, _nt = eng._compile(pods())
    assert batch.nz_templates.shape[0] == 0
    _assert_scan_matches_serial(eng, _rig("uniform", n_nodes=16),
                                pods(), pods())
