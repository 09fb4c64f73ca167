"""Fused solve kernel + narrow dtype policy (ISSUE 15).

Parity contract: the fused scan body (KT_FUSED default, sparse commits +
template-factored scores + fused select) must be DECISION-IDENTICAL to
the legacy scan body, the NumPy host engine, and (transitively, via
tests/test_parity.py's oracle suite which runs against the fused
default) the pure-Python oracle — across ladder buckets, gang-style
live-mask padding, topology constraint planes, chunked carry, and the
preemption path.  The narrow dtype policy must be value-lossless, with
the int16 gate falling back to int32 at capacity limits instead of
wrapping."""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.engine import fused as fused_mod
from kubernetes_tpu.engine import solver as sv
from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
from kubernetes_tpu.perf import synth

from helpers import make_node, make_pod


def _legacy_solver(eng: GenericScheduler) -> sv.Solver:
    s = sv.Solver(eng.policy, fused=False)
    s.extra = dict(eng.solver.extra)
    return s


def _rig(profile: str, n_nodes: int = 48):
    eng, _ = synth.make_rig(n_nodes, 0, profile=profile)
    assert eng.solver._fused, "KT_FUSED default expected in tier-1"
    return eng


def _packed(solver, db, dc, flags, counter=5, **kw):
    out = solver.solve_sequential_packed(db, dc, jnp.uint32(counter),
                                        flags, **kw)
    return np.asarray(out)


@pytest.mark.parametrize("profile", ["uniform", "mixed", "rich"])
def test_fused_vs_legacy_exact_parity(profile):
    """Choices, tie counter, AND final aggregates bit-equal across the
    full per-profile feature surface (rich exercises ports, volumes,
    EBS, inter-pod affinity and tolerations in-scan)."""
    eng = _rig(profile)
    pods = synth.make_pods(160, profile=profile, n_services=4)
    batch, db, dc, nt = eng._compile(pods)
    flags = sv.batch_flags(batch)
    f = _packed(eng.solver, db, dc, flags)
    l = _packed(_legacy_solver(eng), db, dc, flags)
    assert np.array_equal(f, l)


def test_fused_parity_with_live_mask_and_topo_planes():
    """Gang-padding (dead live rows) and the workload-constraint planes
    (extra_mask / score_bias) flow through the fused body unchanged."""
    eng = _rig("mixed")
    pods = synth.make_pods(96, profile="mixed", n_services=4)
    batch, db, dc, nt = eng._compile(pods)
    flags = sv.batch_flags(batch)
    rng = np.random.RandomState(3)
    n = sv.cluster_nodes(dc)
    live = np.ones(96, bool)
    live[70:] = False  # padded gang tail
    em = jnp.asarray(rng.rand(96, n) > 0.05)
    bias = jnp.asarray((rng.randint(0, 5, (96, n))).astype(np.float32))
    kw = dict(live=jnp.asarray(live), extra_mask=em, score_bias=bias)
    f = _packed(eng.solver, db, dc, flags, **kw)
    l = _packed(_legacy_solver(eng), db, dc, flags, **kw)
    assert np.array_equal(f, l)
    # Dead rows place nothing and bump no counter.
    assert (f[70:96] == -1).all()


@pytest.mark.parametrize("chunk", [16, 64])
def test_fused_chunked_carry_matches_oneshot(chunk):
    """Ladder-bucket chunking with carried state equals the one-shot
    solve, for both bodies."""
    eng = _rig("mixed")
    pods = synth.make_pods(128, profile="mixed", n_services=4)
    batch, db, dc, nt = eng._compile(pods)
    flags = sv.batch_flags(batch)
    hb = sv.host_batch(batch)
    one = _packed(eng.solver, db, dc, flags)[:128]

    def chunked(solver):
        counter = jnp.uint32(5)
        carry = None
        outs = []
        for start in range(0, 128, chunk):
            db_k = jax.device_put(sv.slice_pod_axis(hb, start,
                                                    start + chunk))
            ch, counter, carry = solver._solve_scan(
                db_k, dc, counter, None, flags, carry, None, None)
            outs.append(np.asarray(ch))
        return np.concatenate(outs)

    assert np.array_equal(chunked(eng.solver), one)
    assert np.array_equal(chunked(_legacy_solver(eng)), one)


def test_fused_matches_host_engine_drain():
    """The NumPy fallback engine and the fused device drain assign the
    same nodes for the same queue (the guard's breaker swap must not
    move decisions).  Uniform profile: the host engine's mixed-profile
    tie ordering diverges from the device scan with or without the
    fused body (pre-existing; its contract is oracle parity, pinned in
    test_device_faults), so this pins exactly the surface the fused
    rewrite could have moved."""
    eng = _rig("uniform", n_nodes=24)
    pods = synth.make_pods(60, profile="uniform")
    dev = eng.schedule_batch(list(pods))
    eng2, _ = synth.make_rig(24, 0, profile="uniform")
    host = eng2.schedule_batch_host(list(pods))
    assert dev == host


def test_preemption_decisions_identical_across_bodies(monkeypatch):
    """The preemption path (masks + victim solve + overlays) is
    body-independent: KT_FUSED on/off nominate the same victims."""
    def build():
        eng = GenericScheduler()
        for i in range(8):
            eng.cache.add_node(make_node(f"pn{i}", milli_cpu=1000))
        for i in range(8):
            victim = make_pod(f"v{i}", cpu="800m")
            victim.node_name = f"pn{i}"
            eng.cache.add_pod(victim)
        return eng

    def high_pod(i: int) -> api.Pod:
        p = make_pod(f"h{i}", cpu="500m")
        p.annotations[api.PRIORITY_ANNOTATION_KEY] = "100"
        return p

    eng = build()
    high = [high_pod(i) for i in range(3)]
    d_fused = eng.find_preemptions(list(high))
    eng2 = build()
    eng2.solver = _legacy_solver(eng2)
    d_legacy = eng2.find_preemptions(list(high))
    assert [(d.pod_key, d.node, d.victims) for d in d_fused] == \
        [(d.pod_key, d.node, d.victims) for d in d_legacy]
    assert d_fused, "expected at least one preemption decision"


def test_select_matches_reference_semantics():
    """select_xla implements selectHost's round-robin tie-break: the
    ``counter % n_ties``-th feasible max-score node in index order, with
    the modulo in uint32 (counters past 2^31 must not go negative)."""
    rng = np.random.RandomState(11)
    for trial in range(25):
        n = int(rng.choice([8, 33, 128]))
        scores = rng.randint(0, 4, n).astype(np.float32)
        mask = rng.rand(n) > 0.4
        masked = jnp.asarray(np.where(mask, scores, -np.inf))
        count = int(rng.randint(0, 7)) + (2 ** 31 if trial % 2 else 0)
        cx, ax = fused_mod.select_xla(masked, jnp.uint32(count))
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
    policy = sv.narrow_policy(nt, agg, eng.cache.space, mode="narrow")
    assert policy is not None and policy.res == "int16"
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
    policy = sv.narrow_policy(nt, agg, eng.cache.space, mode="narrow")
    assert policy is not None and policy.res == "int32"
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
    policy = sv.narrow_policy(nt, agg, eng.cache.space, mode="narrow")
    assert policy is not None and policy.res == "int16"
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
    legacy body."""
    eng = _rig("uniform", n_nodes=16)
    rng = np.random.RandomState(5)
    pods = [make_pod(f"t{i}", cpu=f"{int(rng.randint(1, 200))}m",
                     memory=f"{int(rng.randint(1, 200))}Mi")
            for i in range(sv.DYN_TEMPLATE_CAP + 40)]
    batch, db, dc, nt = eng._compile(pods)
    assert batch.nz_templates.shape[0] == 0
    flags = sv.batch_flags(batch)
    f = _packed(eng.solver, db, dc, flags)
    l = _packed(_legacy_solver(eng), db, dc, flags)
    assert np.array_equal(f, l)
