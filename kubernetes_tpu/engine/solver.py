"""The device solver: policy -> jitted mask/score/assign computation.

Two entry points:

``evaluate``
    One-shot batched evaluation of every (pod, node) pair against the
    *current* cluster state — the tensor equivalent of running the
    reference's findNodesThatFit + PrioritizeNodes once per pod
    (generic_scheduler.go:145-314), for the whole batch at once.  Used by the
    extender Filter/Prioritize verbs and as the building block of the solvers.

``solve_sequential``
    Greedy sequential assignment as one on-device loop over the batch's rows
    (``run_live_steps``: a scan that stops at its last live row): pods are
    placed in queue order and every placement updates device-resident
    aggregates (requested resources, host ports, volume mounts, spreading
    counts) before the next pod is scored — bit-for-bit the visibility the
    reference's scheduler gets through its assumed-pod cache
    (scheduler.go:116-120, cache.go:107).  The expensive O(P*N*V)
    contractions are hoisted out of the scan (they are placement-invariant);
    only O(N) resource math recomputes per step.

Both are pure jit-compatible functions of arrays; the node axis may be
sharded across a mesh (see kubernetes_tpu.parallel).
"""

from __future__ import annotations

import functools
import itertools
import math
import os
import threading
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.utils import knobs
from kubernetes_tpu.utils.trace import stage
from kubernetes_tpu.api.policy import (DEFAULT_MAX_EBS_VOLUMES,
                                       DEFAULT_MAX_GCE_PD_VOLUMES, Policy,
                                       canonical_predicate_name,
                                       canonical_priority_name,
                                       expand_predicates)
from kubernetes_tpu.features.affinity import AffinityTensors
from kubernetes_tpu.features.batch import PodBatch
from kubernetes_tpu.features.compiler import (FeatureSpace, NodeAggregates,
                                              NodeTensors, RES_CPU, RES_MEM,
                                              RES_PODS)
from kubernetes_tpu.ops import (combine, interpod, predicates as pr,
                                priorities as prio)

# Predicates whose masks do not depend on in-batch placements.
STATIC_PREDICATES = ("PodFitsHost", "MatchNodeSelector", "HostName",
                     "PodToleratesNodeTaints", "CheckNodeMemoryPressure",
                     "CheckNodeDiskPressure", "NewNodeLabelPredicate",
                     "NoVolumeZoneConflict", "ServiceAffinity")
# Implemented dynamic predicates (masks read in-batch placement state).
DYNAMIC_PREDICATES = ("PodFitsResources", "PodFitsHostPorts", "PodFitsPorts",
                      "NoDiskConflict", "MatchInterPodAffinity",
                      "MaxEBSVolumeCount", "MaxGCEPDVolumeCount")
PASSTHROUGH_PREDICATES = ()

STATIC_PRIORITIES = ("NodeAffinityPriority", "TaintTolerationPriority",
                     "ImageLocalityPriority", "NodePreferAvoidPodsPriority",
                     "EqualPriority", "NodeLabelPriority")
DYNAMIC_PRIORITIES = ("LeastRequestedPriority", "MostRequestedPriority",
                      "BalancedResourceAllocation", "SelectorSpreadPriority",
                      "ServiceSpreadingPriority", "InterPodAffinityPriority",
                      "ServiceAntiAffinityPriority")
PASSTHROUGH_PRIORITIES = ()

# Steps per iteration of the sequential solve's loop: running several
# amortizes loop control and xs slicing; compile time grows with the
# factor.
SCAN_UNROLL = 4
# Cap on distinct nonzero-request templates factored out of the scan.
DYN_TEMPLATE_CAP = knobs.get_int("KT_DYN_TEMPLATES")


def scan_unroll(p: int) -> int:
    """Steps one iteration of the scan's loop runs over a ``p``-row batch:
    ``SCAN_UNROLL`` where it divides ``p`` (every ladder bucket), else 1."""
    return SCAN_UNROLL if p % SCAN_UNROLL == 0 else 1


def scan_steps(live: np.ndarray | None, p: int) -> int:
    """Steps ``run_live_steps`` runs over a ``p``-row batch with this live
    mask — the host's mirror of the bound the device reads, for the
    account (``scheduler_scan_steps_total``): the last live row + 1,
    rounded up to whole iterations; every row where there is no mask."""
    if live is None:
        return p
    rows = np.flatnonzero(live)
    unroll = scan_unroll(p)
    return (-(-(int(rows[-1]) + 1) // unroll) * unroll) if rows.size else 0


def run_live_steps(step: Any, init: dict, xs: dict, p: int,
                   live: jnp.ndarray | None
                   ) -> tuple[dict, jnp.ndarray]:
    """``lax.scan(step, init, xs)`` over the rows that can place a pod:
    the loop stops after the last live row, its bound read on the device
    from the mask the launch already carries, so a launch of 30 pods in
    a 256-row bucket runs 32 steps.  Rows past the bound are never
    stepped — a dead row returns the state it got and chooses -1
    (``combine.select_host`` on an all-infeasible row), which is what
    the preallocated output holds — and dead rows before it (a mask
    with a hole, the round-up to whole iterations) run as the inert
    steps they are, so choices, counter and final state are bit-equal
    to the full-length scan's for every mask.  ``live=None`` steps all
    ``p`` rows through the same loop.  Returns (final state, choices
    [p] int32)."""
    unroll = scan_unroll(p)
    if live is None:
        n_rows = jnp.int32(p)
    else:
        n_rows = jnp.max(jnp.where(
            live, jnp.arange(1, p + 1, dtype=jnp.int32), 0), initial=0)
    n_iters = (n_rows + (unroll - 1)) // unroll
    # The body is lax.scan's own for unroll steps an iteration: every xs
    # leaf as [iterations, unroll, ...] indexed on its leading axis (one
    # aligned block an iteration, no negative-index fix-up), the block's
    # steps a fully unrolled lax.scan (no inner loop), the choices written
    # back a block at a time.  lax.scan evaluates step as one closed jaxpr
    # a step, which XLA fuses as it did under the fixed-length scan; with
    # step traced inline four times a filled bucket cost 7-60 % more
    # (PERF.md section 6, PR 37).
    blocks = jax.tree_util.tree_map(
        lambda x: x.reshape(p // unroll, unroll, *x.shape[1:]), xs)

    def body(val):
        i, state, choices = val
        block = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(
                x, i, keepdims=False, allow_negative_indices=False), blocks)
        state, picked = jax.lax.scan(step, state, block, unroll=True)
        return i + 1, state, jax.lax.dynamic_update_index_in_dim(
            choices, picked, i, 0, allow_negative_indices=False)

    _, final, choices = jax.lax.while_loop(
        lambda val: val[0] < n_iters, body,
        (jnp.int32(0), init,
         jnp.full((p // unroll, unroll), -1, jnp.int32)))
    return final, choices.reshape(p)


class DeviceAffinity(NamedTuple):
    """AffinityTensors' array fields as device arrays (features/affinity.py
    documents each; host-only fields n_default/has_any are dropped)."""

    node_dom: jnp.ndarray
    match_key: jnp.ndarray
    match_cnt: jnp.ndarray
    match_total: jnp.ndarray
    match_src: jnp.ndarray
    aff_need: jnp.ndarray
    aff_self: jnp.ndarray
    anti_need: jnp.ndarray
    pref_w: jnp.ndarray
    decl_key: jnp.ndarray
    decl_reach: jnp.ndarray
    decl_match: jnp.ndarray
    decl_src: jnp.ndarray
    sym_key: jnp.ndarray
    sym_w: jnp.ndarray
    sym_cnt: jnp.ndarray
    sym_match: jnp.ndarray
    sym_src: jnp.ndarray


class DeviceVolSvc(NamedTuple):
    """VolSvcTensors as device arrays (features/volumes.py documents each)."""

    pd_pod_ebs: jnp.ndarray
    pd_node_ebs: jnp.ndarray
    pd_extra_ebs: jnp.ndarray
    pd_node_extra_ebs: jnp.ndarray
    pd_node_err_ebs: jnp.ndarray
    pd_pod_gce: jnp.ndarray
    pd_node_gce: jnp.ndarray
    pd_extra_gce: jnp.ndarray
    pd_node_extra_gce: jnp.ndarray
    pd_node_err_gce: jnp.ndarray
    vz_group: jnp.ndarray
    vz_mask: jnp.ndarray
    sa_group: jnp.ndarray
    sa_mask: jnp.ndarray
    saa_group: jnp.ndarray
    saa_src: jnp.ndarray
    saa_dom: jnp.ndarray
    saa_labeled: jnp.ndarray
    saa_cnt: jnp.ndarray
    saa_num: jnp.ndarray
    nl_pred_row: jnp.ndarray
    nl_prio_rows: jnp.ndarray


class DeviceBatch(NamedTuple):
    """PodBatch as device arrays (order mirrors features.batch.PodBatch)."""

    request: jnp.ndarray
    zero_request: jnp.ndarray
    nonzero: jnp.ndarray
    best_effort: jnp.ndarray
    host_idx: jnp.ndarray
    ports: jnp.ndarray
    vol_ro: jnp.ndarray
    vol_rw: jnp.ndarray
    tol_nosched: jnp.ndarray
    tol_prefer: jnp.ndarray
    has_tolerations: jnp.ndarray
    images: jnp.ndarray
    sel_group: jnp.ndarray
    sel_required: jnp.ndarray
    sel_pref_counts: jnp.ndarray
    spread_group: jnp.ndarray
    spread_node_counts: jnp.ndarray
    spread_zone_counts: jnp.ndarray
    spread_has_zones: jnp.ndarray
    spread_incr: jnp.ndarray
    node_zone_id: jnp.ndarray
    avoid_group: jnp.ndarray
    avoid_rows: jnp.ndarray
    nz_tmpl_idx: jnp.ndarray
    nz_templates: jnp.ndarray
    aff: DeviceAffinity
    volsvc: DeviceVolSvc


class BatchFlags(NamedTuple):
    """Content-derived specialization for the sequential scan (hashable, a
    static jit argument).  The reference pays only for predicates whose
    inputs exist (e.g. a pod with no ports never walks the port loop,
    predicates.go:727-741); the tensor scan gets the same effect by
    compiling away whole dynamic-state families the batch provably cannot
    touch — a no-port batch keeps ``ports_used`` constant and conflict-free,
    so neither the check nor the state update belongs in the loop body."""

    any_ports: bool
    any_volumes: bool
    any_ebs: bool
    any_gce: bool
    any_affinity_pred: bool   # aff_need/anti_need/decl_match content
    any_affinity_prio: bool   # pref_w/sym content
    any_spread: bool          # spread_incr content (placements move counts)
    any_spread_zones: bool    # some spread group blends zone counts
    any_saa: bool             # saa_src content (placements move peer counts)


def batch_flags(b: "PodBatch | DeviceBatch | PackedBatch") -> BatchFlags:
    """Derive BatchFlags from a PodBatch (host numpy — call before
    device transfer; also works on a DeviceBatch, or its wire form, at
    the cost of syncs)."""
    if isinstance(b, PackedBatch):
        b = unpack_batch(b)
    a, vs = b.aff, b.volsvc
    return BatchFlags(
        any_ports=bool(np.asarray(b.ports).any()),
        any_volumes=bool(np.asarray(b.vol_ro).any()
                         or np.asarray(b.vol_rw).any()),
        any_ebs=bool(np.asarray(vs.pd_pod_ebs).any()
                     or np.asarray(vs.pd_extra_ebs).any()),
        any_gce=bool(np.asarray(vs.pd_pod_gce).any()
                     or np.asarray(vs.pd_extra_gce).any()),
        any_affinity_pred=bool(np.asarray(a.aff_need).any()
                               or np.asarray(a.anti_need).any()
                               or np.asarray(a.decl_match).any()),
        any_affinity_prio=bool(np.asarray(a.pref_w).any()
                               or (np.asarray(a.sym_match).any()
                                   and np.asarray(a.sym_w).any())),
        # any_spread is force-on: measured on v5e, a scan whose carried state
        # shrinks to just [N,4]+[N,2] falls out of XLA's fast loop regime
        # (~3.4s vs ~0.75s for 30k steps); keeping the [S,N] spread counts
        # carried (numerically a no-op when spread_incr is all-false) keeps
        # the fast schedule and costs ~5% per step.
        any_spread=True,
        any_spread_zones=bool(np.asarray(b.spread_has_zones).any()
                              or np.asarray(b.spread_zone_counts).any()),
        any_saa=bool(np.asarray(vs.saa_src).any()))


ALL_ON_FLAGS = BatchFlags(*([True] * 9))


class ScanFamilies(NamedTuple):
    """What one compiled scan checks, scores and carries per step: the
    policy's dynamic predicates and priorities, less the families whose
    inputs the batch's BatchFlags rule out.  Settled at trace time
    (``Solver._scan_families``); everything left out is hoisted to a
    batch-start plane, exact because its state cannot move mid-scan."""

    resources: bool
    ports: bool
    volumes: bool
    interpod: bool
    max_ebs: bool
    max_gce: bool
    in_scan_preds: frozenset    # predicate names the step evaluates
    static_prios: tuple         # (name, weight, aux) hoisted
    dynamic_prios: tuple        # (name, weight, aux) scored per step
    track_affinity: bool
    track_spread: bool
    track_spread_zones: bool
    track_saa: bool


class DeviceCluster(NamedTuple):
    schedulable: jnp.ndarray    # [N] bool — getNodeConditionPredicate
    alloc: jnp.ndarray          # [N,4] int32
    requested: jnp.ndarray      # [N,4] int32
    nonzero: jnp.ndarray        # [N,2] int32
    ports_used: jnp.ndarray     # [N,C] bool
    vol_any: jnp.ndarray        # [N,W] bool
    vol_rw: jnp.ndarray         # [N,W] bool
    taints_nosched: jnp.ndarray  # [N,T] bool
    taints_prefer: jnp.ndarray  # [N,T] bool
    has_taints: jnp.ndarray     # [N] bool — any taint incl. PreferNoSchedule
    mem_pressure: jnp.ndarray   # [N] bool
    disk_pressure: jnp.ndarray  # [N] bool
    image_kib: jnp.ndarray      # [N,I] int32
    # Topology tensor (engine/workloads/topology.py): per node, the
    # compact domain id of each interned topology label key (-1 = node
    # lacks the label).  The (nodes x topology_domains) one-hot planes the
    # spread kernels consume expand from these ids on device; the ids ride
    # the same dirty-row scatter protocol as every other cluster column.
    topo_dom: jnp.ndarray       # [N,K] int32


class NarrowCluster(NamedTuple):
    """The wire/residency form of DeviceCluster: the int32 resource
    planes are re-laid as a range-gated int16 matrix plus an always-int32
    memory matrix (node memory in MiB routinely exceeds int16 — 32 GiB
    is already 32768), the three pressure/taint bits pack into one uint8
    plane, and the id planes (topology domains, image KiB) narrow to
    int16 when their value ranges allow.  ``widen_cluster`` reconstructs
    the exact DeviceCluster at the top of every jitted entrypoint, so
    all solve arithmetic stays int32 — the narrowing changes transfer
    bytes and HBM residency, never a decision."""

    schedulable: jnp.ndarray    # [N] bool
    res16: jnp.ndarray          # [N,7] i16 (range-gated; else i32):
    #                             alloc cpu/gpu/pods, requested
    #                             cpu/gpu/pods, nonzero cpu
    mem32: jnp.ndarray          # [N,3] i32: alloc/requested/nonzero MiB
    ports_used: jnp.ndarray     # [N,C] bool
    vol_any: jnp.ndarray        # [N,W] bool
    vol_rw: jnp.ndarray         # [N,W] bool
    taints_nosched: jnp.ndarray  # [N,T] bool
    taints_prefer: jnp.ndarray   # [N,T] bool
    flags8: jnp.ndarray         # [N] u8: bit0 has_taints, bit1
    #                             mem_pressure, bit2 disk_pressure
    image_kib: jnp.ndarray      # [N,I] i16 (range-gated; else i32)
    topo_dom: jnp.ndarray       # [N,K] i16 (range-gated; else i32)


class DtypePolicy(NamedTuple):
    """Per-signature storage dtypes for the narrow cluster planes —
    chosen from actual value ranges so int16 can never wrap (the
    overflow-guard tests pin the fallback at the limits)."""

    res: str    # "int16" | "int32"
    img: str
    topo: str


# Gate threshold: int16 max minus the largest single-step aggregate
# delta the scan can commit (one pod's nonzero default); values proven
# below this can accumulate one more placement without wrapping.
_I16_GATE = 32000


def _res_cols(alloc: np.ndarray, requested: np.ndarray,
              nonzero: np.ndarray) -> np.ndarray:
    """The seven range-gated resource columns of ``NarrowCluster.res16``
    as one int32 matrix: alloc cpu/gpu/pods, requested cpu/gpu/pods,
    nonzero cpu."""
    alloc, requested = np.asarray(alloc), np.asarray(requested)
    # basic slices (views) of columns 0, 2, 3: one copy, the concatenate
    return np.concatenate(
        [alloc[:, :1], alloc[:, 2:], requested[:, :1], requested[:, 2:],
         np.asarray(nonzero)[:, :1]], axis=1)


def _range_policy(res: np.ndarray, image_kib: np.ndarray,
                  space: "FeatureSpace") -> DtypePolicy:
    """The narrowest policy THESE rows allow (``res`` = ``_res_cols`` of
    them): the one range proof, read over the fleet at a full upload and
    over the dirty rows at a scatter."""
    ok = not res.size or (int(res.min()) >= 0
                          and int(res.max()) < _I16_GATE)
    img_max = int(image_kib.max()) if image_kib.size else 0
    return DtypePolicy(
        res="int16" if ok else "int32",
        img="int16" if img_max < _I16_GATE else "int32",
        topo="int16" if len(space.topo_vals) < _I16_GATE else "int32")


def narrow_policy(nt: "NodeTensors", agg: "NodeAggregates",
                  space: "FeatureSpace") -> DtypePolicy:
    """The dtype policy for THIS host state.  Range checks read the live
    arrays, so adversarial states — overcommitted aggregates ingested
    from a relist, a 64-core node — fall back to int32 for that
    signature instead of wrapping.  A walk of the whole fleet: the
    resident mirror pays it at a full upload only, and between two of
    them re-proves the policy from the rows it is about to scatter
    (``ResidentCluster._gather_rows``)."""
    return _range_policy(_res_cols(nt.alloc, agg.requested, agg.nonzero),
                         nt.image_kib, space)


def policy_holds(kept: DtypePolicy, need: DtypePolicy) -> bool:
    """True when planes stored under ``kept`` can take rows that need
    ``need``: no plane is narrower than its rows ask.  (A plane kept
    wider than they ask stays wide until the next full upload.)"""
    return all(k == n or k == "int32" for k, n in zip(kept, need))


def narrow_cluster(c: "DeviceCluster", policy: DtypePolicy,
                   res: np.ndarray | None = None) -> NarrowCluster:
    """Re-lay a (host numpy) DeviceCluster into the narrow wire form.
    Shared by the full upload and the dirty-row gather, so the two
    paths cannot encode differently.  ``res``: ``c``'s ``_res_cols``
    where the caller has them already (the gather's range proof)."""
    if res is None:
        res = _res_cols(c.alloc, c.requested, c.nonzero)
    res16 = res.astype(policy.res)
    mem32 = np.stack(
        [np.asarray(c.alloc)[:, 1], np.asarray(c.requested)[:, 1],
         np.asarray(c.nonzero)[:, 1]], axis=1).astype(np.int32)
    flags8 = (np.asarray(c.has_taints).astype(np.uint8)
              | (np.asarray(c.mem_pressure).astype(np.uint8) << 1)
              | (np.asarray(c.disk_pressure).astype(np.uint8) << 2))
    return NarrowCluster(
        schedulable=c.schedulable, res16=res16, mem32=mem32,
        ports_used=c.ports_used, vol_any=c.vol_any, vol_rw=c.vol_rw,
        taints_nosched=c.taints_nosched, taints_prefer=c.taints_prefer,
        flags8=flags8, image_kib=np.asarray(c.image_kib)
        .astype(policy.img), topo_dom=np.asarray(c.topo_dom)
        .astype(policy.topo))


def widen_cluster(c: "DeviceCluster | NarrowCluster") -> "DeviceCluster":
    """The exact int32 DeviceCluster back from the narrow wire form —
    idempotent (a wide cluster passes through), traced at the top of
    every jitted entrypoint so the widening fuses into the solve."""
    if isinstance(c, DeviceCluster):
        return c
    r = c.res16.astype(jnp.int32)
    m = c.mem32
    return DeviceCluster(
        schedulable=c.schedulable,
        alloc=jnp.stack([r[:, 0], m[:, 0], r[:, 1], r[:, 2]], axis=1),
        requested=jnp.stack([r[:, 3], m[:, 1], r[:, 4], r[:, 5]],
                            axis=1),
        nonzero=jnp.stack([r[:, 6], m[:, 2]], axis=1),
        ports_used=c.ports_used, vol_any=c.vol_any, vol_rw=c.vol_rw,
        taints_nosched=c.taints_nosched, taints_prefer=c.taints_prefer,
        has_taints=(c.flags8 & 1) > 0,
        mem_pressure=(c.flags8 & 2) > 0,
        disk_pressure=(c.flags8 & 4) > 0,
        image_kib=c.image_kib.astype(jnp.int32),
        topo_dom=c.topo_dom.astype(jnp.int32))


def cluster_nodes(c: "DeviceCluster | NarrowCluster") -> int:
    """Node count of either cluster form (the host-side dispatch sites
    must not widen just to read a shape)."""
    return int(c.schedulable.shape[0])


def _pad_cols(a: np.ndarray, width: int, fill=0) -> np.ndarray:
    if a.shape[1] == width:
        return a
    out = np.full((a.shape[0], width), fill, a.dtype)
    out[:, : a.shape[1]] = a
    return out


def host_batch(b: PodBatch) -> DeviceBatch:
    """The DeviceBatch pytree still holding host numpy arrays — the
    chunked drain slices THIS (free numpy views with no dynamic_slice
    programs; device slicing compiled one program per distinct drain
    length) and device_puts each fixed-shape chunk."""
    parts = [getattr(b, f) for f in DeviceBatch._fields
             if f not in ("aff", "volsvc")]
    aff = DeviceAffinity(*[getattr(b.aff, f)
                           for f in DeviceAffinity._fields])
    volsvc = DeviceVolSvc(*[getattr(b.volsvc, f)
                            for f in DeviceVolSvc._fields])
    return DeviceBatch(*parts, aff=aff, volsvc=volsvc)


# -- the 32-bit carrier ------------------------------------------------------
#
# An upload is ONE host array whatever it holds: every array handed to
# the runtime is a trip through the interpreter's lock, which the launch
# thread shares with the decode, reflector and bind threads, so a dozen
# small arrays cost what a dozen trips cost whatever their bytes.  The
# carrier is a flat int32 buffer of one REGION per storage width — the
# leaves of a region lie end to end in it as their bytes are, the region
# padded to a whole word — and the program that reads the buffer
# bit-casts each region back once and slices the leaves out by static
# offsets.  Dtypes, shapes and values are the leaves' own, so no decision
# can move.

# The regions in carrier order, and the region that stores each leaf
# dtype: uint32 (the tie counter) rides the int32 words bit-cast, bool
# rides as bytes (XLA has no bit-cast to it).
_REGIONS = ("int32", "float32", "int16", "uint8")
_REGION_OF = {"int32": "int32", "uint32": "int32", "float32": "float32",
              "int16": "int16", "uint8": "uint8", "bool": "uint8"}


_I32 = np.dtype(np.int32)


def _words(dtype: str, n: int) -> int:
    """32-bit words that ``n`` elements of ``dtype`` take."""
    return -(-n * np.dtype(dtype).itemsize // 4)


@functools.lru_cache(maxsize=512)
def wire_layout(names: tuple, signature: tuple) -> tuple[tuple, int]:
    """``(wire, words)`` of a carrier for leaves of these names and
    ``(dtype, shape)``: ``wire`` = ``(layout, regions)`` — per leaf
    ``(name, dtype name, shape, offset)`` with the offset in ELEMENTS of
    its region, per region ``(dtype name, word offset, elements)`` — and
    the carrier's length.  A launch's shapes repeat, so the walk (and
    NumPy's slow ``dtype.name``) is paid once each."""
    layout, sizes = [], dict.fromkeys(_REGIONS, 0)
    for name, (dtype, shape) in zip(names, signature):
        dtype = dtype.name
        region = _REGION_OF.get(dtype)
        if region is None:
            raise TypeError(f"leaf {name}: dtype {dtype} has no wire form")
        layout.append((name, dtype, shape, sizes[region]))
        sizes[region] += math.prod(shape)
    regions, words = [], 0
    for region in _REGIONS:
        if sizes[region]:
            regions.append((region, words, sizes[region]))
            words += _words(region, sizes[region])
    return (tuple(layout), tuple(regions)), words


def _pack_words(wire: tuple, leaves: Any) -> np.ndarray:
    """Host leaves of a ``wire_layout`` as its one int32 carrier.  ONE
    ``bytes.join``: a C loop that keeps the interpreter's lock, where a
    NumPy copy per leaf would each offer it to the other threads."""
    layout, regions = wire
    parts: dict[str, list] = {region: [] for region, _off, _n in regions}
    for (_name, dtype, _shape, _off), leaf in zip(layout, leaves):
        parts[_REGION_OF[dtype]].append(
            leaf if leaf.flags.c_contiguous else np.ascontiguousarray(leaf))
    for region, _off, n in regions:
        parts[region].append(bytes(-n * np.dtype(region).itemsize % 4))
    return np.frombuffer(b"".join(itertools.chain.from_iterable(
        parts.values())), np.int32)


def _unpack_words(buf: jnp.ndarray, wire: tuple) -> list:
    """The leaves of a ``wire_layout`` back from its carrier: one static
    slice and one bit-cast per region, one static slice per leaf."""
    layout, regions = wire
    flat = {}
    for region, off, n in regions:
        v = jax.lax.slice(buf, (off,), (off + _words(region, n),))
        if region != "int32":
            v = jax.lax.bitcast_convert_type(v, jnp.dtype(region))
        flat[region] = v.reshape(-1)    # narrower: [words, per word]
    vals = []
    for _name, dtype, shape, off in layout:
        v = jax.lax.slice(flat[_REGION_OF[dtype]], (off,),
                          (off + math.prod(shape),))
        if dtype == "bool":
            v = v != 0
        elif dtype == "uint32":
            v = jax.lax.bitcast_convert_type(v, jnp.uint32)
        vals.append(v.reshape(shape))
    return vals


def _cluster_planes(c: "NarrowCluster") -> tuple:
    """``(dtype, row shape)`` of every resident plane: the part of the
    cluster signature a row's wire form follows."""
    return tuple((a.dtype, tuple(a.shape[1:])) for a in c)


def rows_layout(planes: tuple, k: int) -> tuple[tuple, int]:
    """``wire_layout`` of a ``k``-row scatter buffer over resident planes
    of these ``_cluster_planes``: the row index, then the planes in
    field order.  A function of the cluster signature and the row bucket
    alone, cached by them."""
    return wire_layout(
        ("idx",) + NarrowCluster._fields,
        ((_I32, (k,)),) + tuple((dtype, (k,) + row)
                                for dtype, row in planes))


# -- the batch's wire form ---------------------------------------------------
#
# A launch's pod batch crosses to the device as ONE carrier, as the
# cluster's dirty rows do: ``pack_batch`` lays the 65 leaves and the
# launch's riders out on the host, ``unpack_batch`` / ``unpack_launch``
# slice them back at the top of every jitted entrypoint.

_N_TOP = len(DeviceBatch._fields) - 2
_BATCH_PATHS = (DeviceBatch._fields[:_N_TOP]
                + tuple(f"aff.{f}" for f in DeviceAffinity._fields)
                + tuple(f"volsvc.{f}" for f in DeviceVolSvc._fields))
# What a launch carries besides the DeviceBatch's fields, behind them in
# the buffer: the chunk's live mask, the tie counter (a launch's first
# chunk) and the topology planes.
RIDERS = ("live", "counter", "extra_mask", "score_bias")


@jax.tree_util.register_pytree_node_class
class PackedBatch:
    """The wire form of a DeviceBatch (+ riders): one child, the int32
    carrier, and as static part ``layout``, its ``wire_layout`` — the
    leaves' ``(path, dtype, shape, offset)`` and the regions they lie
    in — derived from the leaves' shapes alone, so it passes through
    ``jax.jit`` as the 65 leaves did and keys the program exactly as
    their shapes did."""

    __slots__ = ("buffer", "layout")

    def __init__(self, buffer: Any, layout: tuple):
        self.buffer = buffer
        self.layout = layout

    def tree_flatten(self) -> tuple[tuple, tuple]:
        return (self.buffer,), self.layout

    @classmethod
    def tree_unflatten(cls, layout: tuple, children: Any) -> "PackedBatch":
        return cls(children[0], layout)


def _batch_leaves(b: DeviceBatch) -> tuple:
    return b[:_N_TOP] + tuple(b.aff) + tuple(b.volsvc)


def batch_layout(b: DeviceBatch, live: Any = None, counter: Any = None,
                 extra_mask: Any = None, score_bias: Any = None
                 ) -> tuple[tuple, list, int]:
    """``(wire, leaves, words)`` of a batch and its riders: the leaves
    in wire order, their ``wire_layout`` and the carrier's length.
    Reads shapes and dtypes only (kt-xray lays out ShapeDtypeStructs
    with it)."""
    riders = [(name, r) for name, r in zip(
        RIDERS, (live, counter, extra_mask, score_bias)) if r is not None]
    leaves = list(_batch_leaves(b)) + [r for _name, r in riders]
    wire, words = wire_layout(
        _BATCH_PATHS + tuple(name for name, _r in riders),
        tuple((leaf.dtype, tuple(leaf.shape)) for leaf in leaves))
    return wire, leaves, words


def pack_batch(b: DeviceBatch, live: np.ndarray | None = None,
               counter: np.uint32 | None = None,
               extra_mask: np.ndarray | None = None,
               score_bias: np.ndarray | None = None) -> PackedBatch:
    """The host-numpy DeviceBatch (and the launch's riders) as a
    PackedBatch of one host carrier."""
    wire, leaves, _words = batch_layout(b, live, counter, extra_mask,
                                        score_bias)
    return PackedBatch(_pack_words(wire, leaves), wire)


def _unpack(pb: PackedBatch) -> tuple[DeviceBatch, dict]:
    """Static slices and reshapes of the carrier back into the exact
    DeviceBatch, and the riders by name."""
    vals = _unpack_words(pb.buffer, pb.layout)
    n_aff = len(DeviceAffinity._fields)
    n_b = len(_BATCH_PATHS)
    db = DeviceBatch(
        *vals[:_N_TOP], aff=DeviceAffinity(*vals[_N_TOP:_N_TOP + n_aff]),
        volsvc=DeviceVolSvc(*vals[_N_TOP + n_aff:n_b]))
    return db, {e[0]: v for e, v in zip(pb.layout[0][n_b:], vals[n_b:])}


def unpack_batch(b: "DeviceBatch | PackedBatch") -> DeviceBatch:
    """The exact DeviceBatch back from the wire form — idempotent (a
    DeviceBatch passes through), traced at the top of every jitted
    entrypoint that takes a batch, like ``widen_cluster``."""
    return b if isinstance(b, DeviceBatch) else _unpack(b)[0]


def unpack_launch(b: "DeviceBatch | PackedBatch",
                  counter: jnp.ndarray | None,
                  score_bias: jnp.ndarray | None,
                  live: jnp.ndarray | None,
                  extra_mask: jnp.ndarray | None) -> tuple:
    """``unpack_batch`` plus the launch's riders, in ``_solve_scan``'s
    argument order: an argument the caller gave outright stands, a None
    is filled from the carrier where the batch carries it."""
    if isinstance(b, DeviceBatch):
        return b, counter, score_bias, live, extra_mask
    db, riders = _unpack(b)
    given = {"counter": counter, "score_bias": score_bias, "live": live,
             "extra_mask": extra_mask}
    return (db,) + tuple(riders.get(k) if v is None else v
                         for k, v in given.items())


def put_batch(hb: DeviceBatch, **riders: Any) -> PackedBatch:
    """Pack a host batch with its riders and hand it to the device: ONE
    device_put of ONE array, counted under cause ``batch``."""
    from kubernetes_tpu.engine import devicestats
    pb = pack_batch(hb, **riders)
    devicestats.record_transfer("batch", pb.buffer.nbytes, arrays=1)
    return jax.device_put(pb)


def device_batch(b: PodBatch, **riders: Any) -> PackedBatch:
    """The PodBatch on the device, in its wire form."""
    return put_batch(host_batch(b), **riders)


def _host_cluster(nt: NodeTensors, agg: NodeAggregates,
                  space: FeatureSpace) -> DeviceCluster:
    """The DeviceCluster pytree as host numpy, aggregate columns padded to
    current vocabulary capacities (pods may have interned new ports or
    volumes).  Row slicing for the incremental mirror and the full upload
    share this one assembly so they cannot diverge."""
    return DeviceCluster(
        schedulable=nt.schedulable,
        alloc=nt.alloc,
        requested=agg.requested,
        nonzero=agg.nonzero,
        ports_used=_pad_cols(agg.ports_used, space.ports.capacity),
        vol_any=_pad_cols(agg.vol_any, space.volumes.capacity),
        vol_rw=_pad_cols(agg.vol_rw, space.volumes.capacity),
        taints_nosched=nt.taints_nosched,
        taints_prefer=nt.taints_prefer,
        has_taints=nt.taints_nosched.any(1) | nt.taints_prefer.any(1),
        mem_pressure=nt.mem_pressure,
        disk_pressure=nt.disk_pressure,
        image_kib=_pad_cols(nt.image_kib, space.images.capacity),
        topo_dom=_pad_cols(nt.topo_val, space.topo_keys.capacity, fill=-1))


def device_cluster(nt: NodeTensors, agg: NodeAggregates,
                   space: FeatureSpace) -> DeviceCluster:
    """Assemble device cluster state, padding aggregate columns to current
    vocabulary capacities (pods may have interned new ports/volumes)."""
    return jax.device_put(_host_cluster(nt, agg, space))


class ResidentCluster:
    """Device-resident mirror of the cache's node tensors.

    The drain loop used to re-assemble and ``device_put`` the full
    ``(nodes x features)`` cluster state on EVERY drain — ~25 MB of
    transfer per batch at 5k nodes, for state that a typical drain
    changes in a handful of rows.  This holder keeps one
    DeviceCluster resident across drains and applies the cache's dirty
    rows (assume/bind aggregate deltas, heartbeat Ready flips) through a
    jitted scatter kernel: per drain, only the changed rows cross the
    wire.

    Invariants (the "device-residency protocol", see ARCHITECTURE.md):

    * the node axis has a CAPACITY (``features.compiler.capacity``:
      whole 128-row tiles with a row to spare; the signature's first
      component), so a node that joins into a free row or leaves its row
      free is a dirty row like any other: no upload of the fleet, no new
      XLA shape.  Free rows are all zeros and not schedulable, so the
      fleet-wide dtype proof and ``FULL_FRACTION`` read the same over
      the capacity as over the live rows;
    * a FULL re-upload happens when every row moved (cache
      ``tensor_epoch`` bump: the rebuild of the node tensors, the node
      axis grown by tiles because a join found no free row) or any
      column capacity grew (vocab interning widened a table — the shape
      signature changed and the resident arrays cannot hold the rows);
    * otherwise the mirror equals ``device_cluster`` of the current host
      arrays after scattering the dirty rows — pinned by
      tests/test_device_resident.py against the full assembly;
    * ``sync`` must run under the cache lock (the engine's ``_compile``
      does), so the gathered rows and the dirty set are one generation;
    * dirty-row counts are padded to a pow2 bucket (duplicate rows — a
      duplicate scatter of identical values is a no-op) so the scatter
      compiles O(log N) shapes, and a drain dirtying more than 1/4 of
      the cluster falls back to the full upload (the gather would move
      most of the bytes anyway);
    * the dirty rows cross as ONE packed int32 buffer (``rows_layout``:
      the row index, then the 11 narrow planes) that ``kt_scatter_rows``
      slices on the device;
    * the narrow dtype policy is proven over the fleet at a full upload
      and kept; a scatter re-proves it from the rows it is about to
      send, and a row the kept planes are too narrow for takes the full
      upload instead, as a signature change does.
    """

    FULL_FRACTION = 4  # dirty rows > N/4 -> full upload wins

    def __init__(self):
        self.dc: NarrowCluster | None = None
        self._sig = None     # its last component: the dtype policy the
        #                      last full upload proved over the fleet
        self._planes = None  # _cluster_planes(dc): the rows' wire layout
        self._epoch = None
        self._scatter = None
        self.stats = {"full_syncs": 0, "row_syncs": 0, "rows_scattered": 0}

    def invalidate(self) -> None:
        self.dc = None

    @staticmethod
    def signature(nt: "NodeTensors", space: "FeatureSpace",
                  policy: Optional[DtypePolicy] = None) -> tuple:
        """The shape signature a resident copy was uploaded at — its
        first component the node axis' CAPACITY, not the node count; any
        component moving — including the narrow dtype policy (a value
        crossing the int16 gate widens the plane) — means the arrays
        cannot be patched in place."""
        return (nt.alloc.shape[0], space.ports.capacity,
                space.volumes.capacity, nt.taints_nosched.shape[1],
                space.images.capacity, space.topo_keys.capacity,
                policy)

    def in_sync(self, nt: "NodeTensors", space: "FeatureSpace",
                epoch: int) -> bool:
        """True when the resident copy mirrors THIS host state's row
        identity (same epoch, same shape signature) — the precondition
        for the invariant checker's row readback to be meaningful (a
        mirror awaiting a full re-upload legitimately differs).  The
        dtype-policy component is excluded: it needs the aggregates to
        recompute, and a pending policy flip re-uploads on the next
        ``sync`` anyway."""
        return self.dc is not None and self._epoch == epoch and \
            self._sig is not None and \
            self._sig[:-1] == self.signature(nt, space)[:-1]

    def readback_rows(self, idx: "np.ndarray | list[int]") -> dict:
        """Device→host readback of the verifier's sampled rows: the four
        resource-truth fields the dirty-row protocol must keep equal to
        the host arrays.  One gather per field, k rows each — cheap at
        verifier cadence."""
        from kubernetes_tpu.engine import devicestats
        i = jnp.asarray(np.asarray(idx, np.int32))
        # Gather the k sampled rows of every plane, then decode through
        # widen_cluster — the ONE authoritative narrow->wide layout
        # (hand-stacking columns here would be a third copy of the
        # res16/mem32 packing that could silently drift from the
        # encode/decode pair).
        rows = widen_cluster(type(self.dc)(*[arr[i] for arr in self.dc]))
        out = {"schedulable": np.asarray(rows.schedulable),
               "alloc": np.asarray(rows.alloc),
               "requested": np.asarray(rows.requested),
               "nonzero": np.asarray(rows.nonzero)}
        devicestats.record_transfer("readback", devicestats.nbytes(out))
        return out

    def _scatter_fn(self):
        if self._scatter is None:
            # NO buffer donation, deliberately: the previous sync's
            # DeviceCluster may still be aliased by an in-flight drain
            # (the streamed generator holds its dc across chunks, and a
            # mid-drain explain_failures pass re-enters _compile/sync
            # with fresh dirty rows) — donating would invalidate buffers
            # a queued _solve_scan still reads.  The cost is one
            # device-side copy of the cluster arrays per scatter,
            # HBM-to-HBM, micro-seconds at 5k nodes — still nothing like
            # the host->device transfer this mirror exists to avoid.
            # Named for the profiler: its ``XLA Modules`` line reads
            # ``jit_kt_scatter_rows`` beside ``jit__solve_scan``.
            def kt_scatter_rows(c: NarrowCluster, buf: jnp.ndarray,
                                k: int) -> NarrowCluster:
                wire, words = rows_layout(_cluster_planes(c), k)
                assert buf.shape == (words,), (buf.shape, words)
                idx, *rows = _unpack_words(buf, wire)
                return type(c)(*[arr.at[idx].set(new)
                                 for arr, new in zip(c, rows)])

            # kt-xray: no-donate(prior DeviceCluster may be aliased by an
            # in-flight drain; see the comment above)
            self._scatter = jax.jit(kt_scatter_rows, static_argnums=2)
        return self._scatter

    @staticmethod
    def scatter_buckets(n: int, max_rows: int | None = None) -> list[int]:
        """The pow2 dirty-row buckets the scatter kernel can compile at
        for an ``n``-row cluster — reachability is bounded by ``sync``'s
        own rule (dirty * FULL_FRACTION >= n takes the full upload), so
        this is the exact shape set ``prewarm_scatter`` traces AND the
        set the kt-xray manifest must cover (one definition, two
        consumers — they cannot drift)."""
        limit = (max(n - 1, 1)) // ResidentCluster.FULL_FRACTION
        if limit < 1:
            return []
        limit = 1 << (limit - 1).bit_length() if limit > 1 else 1
        if max_rows is not None:
            limit = min(limit, max_rows)
        out, k = [], 1
        while k <= limit:
            out.append(k)
            k <<= 1
        return out

    def prewarm_scatter(self, max_rows: int | None = None) -> int:
        """Trace the dirty-row scatter kernel — ``kt_scatter_rows`` over
        the resident planes and ONE packed buffer of ``rows_layout`` —
        at EVERY reachable pow2 row-count bucket, so no drain after an
        assume ever compiles the scatter mid-drain — measured as a fresh
        XLA compile on the clock of the first post-warm-up stream drain
        (the warm-start audit, ISSUE 8).  The reachable set is bounded by
        ``sync``'s own rule (dirty * FULL_FRACTION >= N takes the full
        upload instead), so this is log2(N/4) shapes — ~12 at 5k nodes,
        ~15 at 100k; an explicit ``max_rows`` caps it for tests.
        Requires a resident copy (``sync`` must have run, which any
        ladder prewarm guarantees); the traces scatter row 0's own
        values onto row 0 — a no-op on the data.  Returns the number of
        shapes traced."""
        if self.dc is None:
            return 0
        n = int(self.dc.schedulable.shape[0])
        # sync() only scatters when dirty * FULL_FRACTION < N; larger
        # dirty sets take the full upload, so their shapes are
        # unreachable (ResidentCluster.scatter_buckets is that rule).
        scatter = self._scatter_fn()
        row0 = [np.asarray(arr[:1]) for arr in self.dc]
        traced = 0
        for k in self.scatter_buckets(n, max_rows):
            buf = _pack_words(
                rows_layout(self._planes, k)[0], [np.zeros(k, np.int32)] + [
                    np.repeat(row, k, axis=0) for row in row0])
            scatter(self.dc, buf, k).schedulable.block_until_ready()
            traced += 1
        return traced

    def sync(self, nt: NodeTensors, agg: NodeAggregates,
             space: FeatureSpace, dirty: set[int],
             epoch: int) -> NarrowCluster:
        """The current cluster state on device: scatter ``dirty`` rows
        into the resident arrays, or re-upload everything when the
        resident copy cannot be patched (see class docstring).  Both
        the upload and the scattered rows travel in the NarrowCluster
        wire form; the jitted entrypoints widen on device."""
        from kubernetes_tpu.engine import devicestats
        n = nt.alloc.shape[0]
        buf = None
        if self.in_sync(nt, space, epoch) \
                and len(dirty) * self.FULL_FRACTION < max(n, 1):
            if not dirty:
                return self.dc
            k = 1 << (len(dirty) - 1).bit_length()
            with stage("transfer.rows"):
                buf = self._gather_rows(nt, agg, space, dirty, k)
        if buf is None:
            with stage("transfer.full"):
                policy = narrow_policy(nt, agg, space)
                host = _host_cluster(nt, agg, space)
                self.dc = jax.device_put(narrow_cluster(host, policy))
            self._sig = self.signature(nt, space, policy)
            self._planes = _cluster_planes(self.dc)
            self._epoch = epoch
            self.stats["full_syncs"] += 1
            # Device accounting: the whole-cluster re-snapshot is the
            # EXPENSIVE transfer the residency protocol exists to avoid
            # — full_upload bytes dominating steady-state drains is the
            # regression signature (a silent re-upload where a dirty-row
            # scatter should have run).  (HBM peak sampling deliberately
            # NOT here: on backends without memory_stats the fallback
            # walks every live array — the telemetry scrape cadence
            # covers it off the drain path.)
            devicestats.record_transfer("full_upload",
                                        devicestats.nbytes(self.dc),
                                        arrays=len(self.dc))
            return self.dc
        with stage("transfer.scatter"):
            # The host buffer goes to the program as it is: the dispatch
            # uploads its one array.
            self.dc = self._scatter_fn()(self.dc, buf, k)
        self.stats["row_syncs"] += 1
        self.stats["rows_scattered"] += len(dirty)
        # Only the gathered rows crossed the wire (idx + padded rows).
        devicestats.record_transfer("scatter", buf.nbytes, arrays=1)
        return self.dc

    def _gather_rows(self, nt: NodeTensors, agg: NodeAggregates,
                     space: FeatureSpace, dirty: set[int],
                     k: int) -> np.ndarray | None:
        """The dirty rows on the host as ONE packed buffer of
        ``rows_layout``: the index padded to its bucket ``k`` (with its
        first row: a duplicate scatter of identical values is a no-op)
        and the rows gathered once with it, in the resident planes' wire
        form.  None when a row has outgrown the kept policy: nothing may
        reach a plane too narrow for it, so the caller uploads the fleet
        instead."""
        policy = self._sig[-1]
        idx = np.fromiter(
            itertools.chain(dirty, itertools.repeat(next(iter(dirty)))),
            np.int32, k)
        # Gather the dirty rows directly (fancy indexing copies), padding
        # and deriving only the k gathered rows — assembling the full
        # padded host cluster here would re-pay the O(N x features) host
        # work the mirror exists to avoid.  Same field encoding as
        # _host_cluster by construction; equivalence is pinned by
        # tests/test_device_resident.py.
        tn, tp = nt.taints_nosched[idx], nt.taints_prefer[idx]
        rows = DeviceCluster(
            schedulable=nt.schedulable[idx],
            alloc=nt.alloc[idx],
            requested=agg.requested[idx],
            nonzero=agg.nonzero[idx],
            ports_used=_pad_cols(agg.ports_used[idx],
                                 space.ports.capacity),
            vol_any=_pad_cols(agg.vol_any[idx], space.volumes.capacity),
            vol_rw=_pad_cols(agg.vol_rw[idx], space.volumes.capacity),
            taints_nosched=tn,
            taints_prefer=tp,
            has_taints=tn.any(1) | tp.any(1),
            mem_pressure=nt.mem_pressure[idx],
            disk_pressure=nt.disk_pressure[idx],
            image_kib=_pad_cols(nt.image_kib[idx], space.images.capacity),
            topo_dom=_pad_cols(nt.topo_val[idx],
                               space.topo_keys.capacity, fill=-1))
        res = _res_cols(rows.alloc, rows.requested, rows.nonzero)
        if not policy_holds(policy,
                            _range_policy(res, rows.image_kib, space)):
            return None
        return _pack_words(rows_layout(self._planes, k)[0],
                           (idx,) + narrow_cluster(rows, policy, res))


def _predicate_mask(name: str, b: DeviceBatch, c: DeviceCluster,
                    n_nodes: int, extra: dict) -> jnp.ndarray:
    p = b.request.shape[0]
    if name in ("PodFitsHost", "HostName"):
        return pr.pod_fits_host(b.host_idx, n_nodes)
    if name == "MatchNodeSelector":
        return pr.pod_selector_matches(b.sel_group, b.sel_required)
    if name == "PodToleratesNodeTaints":
        return pr.pod_tolerates_node_taints(b.tol_nosched, b.has_tolerations,
                                            c.taints_nosched, c.has_taints)
    if name == "CheckNodeMemoryPressure":
        return pr.check_node_memory_pressure(b.best_effort, c.mem_pressure)
    if name == "CheckNodeDiskPressure":
        return pr.check_node_disk_pressure(p, c.disk_pressure)
    if name == "NewNodeLabelPredicate":
        return pr.node_label_presence(p, b.volsvc.nl_pred_row)
    if name == "NoVolumeZoneConflict":
        return b.volsvc.vz_mask[b.volsvc.vz_group]
    if name == "ServiceAffinity":
        return b.volsvc.sa_mask[b.volsvc.sa_group]
    if name == "MaxEBSVolumeCount":
        return pr.max_pd_volume_count(b.volsvc.pd_pod_ebs,
                                      b.volsvc.pd_extra_ebs,
                                      b.volsvc.pd_node_ebs,
                                      b.volsvc.pd_node_extra_ebs,
                                      b.volsvc.pd_node_err_ebs,
                                      extra["max_ebs"])
    if name == "MaxGCEPDVolumeCount":
        return pr.max_pd_volume_count(b.volsvc.pd_pod_gce,
                                      b.volsvc.pd_extra_gce,
                                      b.volsvc.pd_node_gce,
                                      b.volsvc.pd_node_extra_gce,
                                      b.volsvc.pd_node_err_gce,
                                      extra["max_gce"])
    if name == "PodFitsResources":
        return pr.pod_fits_resources(b.request, b.zero_request, c.alloc,
                                     c.requested)
    if name in ("PodFitsHostPorts", "PodFitsPorts"):
        return pr.pod_fits_host_ports(b.ports, c.ports_used)
    if name == "NoDiskConflict":
        return pr.no_disk_conflict(b.vol_rw, b.vol_ro, c.vol_any, c.vol_rw)
    if name == "MatchInterPodAffinity":
        a = b.aff
        return interpod.predicate_mask(a.aff_need, a.aff_self, a.anti_need,
                                       a.decl_match, a.match_cnt,
                                       a.match_total, a.decl_reach)
    if name in PASSTHROUGH_PREDICATES:
        return jnp.ones((p, n_nodes), bool)
    raise KeyError(f"unknown predicate {name!r}")


def saa_plane(cnt: jnp.ndarray, num: jnp.ndarray, dom: jnp.ndarray,
              labeled: jnp.ndarray) -> jnp.ndarray:
    """CalculateAntiAffinityPriority score (selector_spreading.go:236-250):
    int(10*(num-count)/num) on ready nodes carrying the label, 10 when the
    service has no pods, 0 on unlabeled nodes.  ``cnt`` [P,D] per-domain
    peer counts of each pod's service group, ``num`` [P,1] peer totals,
    ``dom`` [N] node domain ids, ``labeled`` [N]."""
    per = jnp.take(cnt, dom, axis=1)          # [P, N]
    # prio._trunc, not raw floor: XLA's reciprocal-approximated f32 divide
    # can land an exact quotient (440/110 == 4.0) an ulp low, and the
    # truncation would eat a whole point.
    score = jnp.where(num > 0.0,
                      prio._trunc(10.0 * (num - per) / jnp.maximum(num, 1.0)),
                      10.0)
    return jnp.where(labeled[None, :], score, 0.0)


def _priority_plane(name: str, b: DeviceBatch, c: DeviceCluster,
                    n_nodes: int, extra: dict) -> jnp.ndarray:
    p = b.request.shape[0]
    if name == "LeastRequestedPriority":
        return prio.least_requested(b.nonzero, c.nonzero, c.alloc)
    if name == "MostRequestedPriority":
        return prio.most_requested(b.nonzero, c.nonzero, c.alloc)
    if name == "BalancedResourceAllocation":
        return prio.balanced_resource_allocation(b.nonzero, c.nonzero, c.alloc)
    if name == "NodeAffinityPriority":
        return prio.node_affinity(b.sel_group, b.sel_pref_counts,
                                  c.schedulable)
    if name == "TaintTolerationPriority":
        return prio.taint_toleration(b.tol_prefer, c.taints_prefer,
                                     c.schedulable)
    if name == "ImageLocalityPriority":
        return prio.image_locality(b.images, c.image_kib)
    if name == "NodePreferAvoidPodsPriority":
        return prio.node_prefer_avoid(b.avoid_group, b.avoid_rows)
    if name in ("SelectorSpreadPriority", "ServiceSpreadingPriority"):
        return prio.selector_spread(b.spread_group, b.spread_node_counts,
                                    b.spread_zone_counts, b.spread_has_zones,
                                    b.node_zone_id, c.schedulable)
    if name == "InterPodAffinityPriority":
        a = b.aff
        counts = interpod.priority_counts(a.pref_w, a.match_cnt, a.sym_match,
                                          a.sym_w, a.sym_cnt)
        return interpod.priority_score(counts, c.schedulable, prio._trunc)
    if name == "NodeLabelPriority":
        return prio.node_label(p, b.volsvc.nl_prio_rows[extra.get("aux", 0)])
    if name == "ServiceAntiAffinityPriority":
        vs = b.volsvc
        return saa_plane(vs.saa_cnt[extra.get("aux", 0)][vs.saa_group],
                         vs.saa_num[vs.saa_group][:, None],
                         vs.saa_dom[extra.get("aux", 0)],
                         vs.saa_labeled[extra.get("aux", 0)])
    if name == "EqualPriority":
        return prio.equal_priority(p, n_nodes)
    raise KeyError(f"unknown priority {name!r}")


class Solver:
    """Compiles a Policy into jitted evaluate / sequential-solve callables.

    Solvers are stateless (the policy-derived constants plus XLA
    executables keyed on them), so ``Solver.for_policy`` shares one
    instance per distinct derived signature process-wide: jit caches are
    keyed on the Solver object (static argnum 0), and a fresh Solver per
    daemon/engine instance silently re-traced and re-compiled every
    executable (~15-40 s per rig at the 30k/5k shape)."""

    _registry: dict = {}
    _registry_lock = threading.Lock()

    @classmethod
    def for_policy(cls, policy: Policy) -> "Solver":
        candidate = cls(policy)
        key = (candidate.predicate_names, candidate.priority_specs,
               tuple(sorted(candidate.extra.items())))
        with cls._registry_lock:
            existing = cls._registry.get(key)
            if existing is not None:
                return existing
            cls._registry[key] = candidate
            return candidate

    def __init__(self, policy: Policy):
        self.policy = policy
        # Half-width encoded-score dtype (resolved once with the
        # backend): bf16 on TPU, f16 — wider mantissa, so a larger
        # exact-integer range — elsewhere.
        self._half_dtype = jnp.bfloat16 \
            if jax.default_backend() == "tpu" else jnp.float16
        # Canonical names: argument-carrying entries resolve to their
        # builtin regardless of the user-chosen policy name (plugins.go).
        self.predicate_names = tuple(canonical_predicate_name(p)
                                     for p in expand_predicates(policy))
        # (name, weight, aux) — aux indexes per-instance policy-arg tables
        # (ServiceAntiAffinityPriority / NodeLabelPriority rows).
        specs = []
        saa_i = nl_i = 0
        for s in policy.priorities:
            if s.weight == 0:
                continue
            name = canonical_priority_name(s)
            if name == "ServiceAntiAffinityPriority":
                specs.append((name, s.weight, saa_i))
                saa_i += 1
            elif name == "NodeLabelPriority":
                specs.append((name, s.weight, nl_i))
                nl_i += 1
            else:
                specs.append((name, s.weight, 0))
        self.priority_specs = tuple(specs)
        self._has_affinity_prio = any(
            name == "InterPodAffinityPriority" for name, _w, _aux in specs)
        self.passthrough = tuple(n for n in self.predicate_names
                                 if n in PASSTHROUGH_PREDICATES)
        # MaxPD caps: policy value, else KUBE_MAX_PD_VOLS env, else provider
        # default (defaults.go:42-54).
        env_max = os.environ.get("KUBE_MAX_PD_VOLS", "")
        env_val = int(env_max) if env_max.isdigit() else 0
        self.extra = {"max_ebs": env_val or DEFAULT_MAX_EBS_VOLUMES,
                      "max_gce": env_val or DEFAULT_MAX_GCE_PD_VOLUMES}
        for spec in expand_predicates(policy):
            if spec.name == "MaxEBSVolumeCount" and spec.max_volumes:
                self.extra["max_ebs"] = spec.max_volumes
            elif spec.name == "MaxGCEPDVolumeCount" and spec.max_volumes:
                self.extra["max_gce"] = spec.max_volumes

    # -- one-shot batched evaluation ------------------------------------

    # kt-xray: no-donate(inputs are the resident cluster + a batch the
    # caller re-reads for evaluate in the same decision)
    @functools.partial(jax.jit, static_argnums=(0,))
    def masks(self, b: DeviceBatch, c: DeviceCluster) -> dict[str, jnp.ndarray]:
        """Per-predicate [P,N] masks (for Filter verbs / failure reporting)."""
        b = unpack_batch(b)
        c = widen_cluster(c)
        n = c.alloc.shape[0]
        return {name: _predicate_mask(name, b, c, n, self.extra)
                for name in self.predicate_names}

    # kt-xray: no-donate(c is the shared resident cluster; b is re-used
    # by the failure-detail masks pass)
    @functools.partial(jax.jit, static_argnums=(0, 3))
    def evaluate(self, b: DeviceBatch, c: DeviceCluster,
                 flags: BatchFlags = ALL_ON_FLAGS
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(feasible [P,N] bool, scores [P,N] f32) against current state.

        ``flags`` (content-derived, see batch_flags) skips planes the batch
        provably cannot trigger — an all-pass mask or all-zero plane — which
        matters because per-kernel dispatch overhead, not FLOPs, dominates
        small-batch evaluation."""
        b = unpack_batch(b)
        c = widen_cluster(c)
        n = c.alloc.shape[0]
        skip_preds = set()
        if not flags.any_ports:
            skip_preds |= {"PodFitsHostPorts", "PodFitsPorts"}
        if not flags.any_volumes:
            skip_preds.add("NoDiskConflict")
        if not flags.any_ebs:
            skip_preds.add("MaxEBSVolumeCount")
        if not flags.any_gce:
            skip_preds.add("MaxGCEPDVolumeCount")
        if not flags.any_affinity_pred:
            skip_preds.add("MatchInterPodAffinity")
        # Unready nodes are filtered before scheduling (factory.go:436-462).
        feasible = jnp.broadcast_to(c.schedulable[None, :],
                                    (b.request.shape[0], n))
        for name in self.predicate_names:
            if name not in skip_preds:
                feasible &= _predicate_mask(name, b, c, n, self.extra)
        scores = jnp.zeros((b.request.shape[0], n), jnp.float32)
        for name, weight, aux in self.priority_specs:
            if name == "InterPodAffinityPriority" and \
                    not flags.any_affinity_prio:
                continue  # all counts provably zero -> score plane is zero
            scores += jnp.float32(weight) * \
                _priority_plane(name, b, c, n, {"aux": aux})
        return feasible, scores

    # -- sequential greedy solve ----------------------------------------

    def solve_sequential(self, b: DeviceBatch, c: DeviceCluster,
                         last_node_index: jnp.ndarray | None,
                         flags: BatchFlags | None = None,
                         extra_mask: jnp.ndarray | None = None,
                         score_bias: jnp.ndarray | None = None
                         ) -> tuple[jnp.ndarray, jnp.ndarray, DeviceCluster]:
        """Greedy in-order placement with on-device state updates.

        ``extra_mask``/``score_bias``: optional [P,N] workload-constraint
        planes (topology spread, engine/workloads/topology.py) ANDed into
        feasibility / added to the static score.

        Returns (choices [P] int32 node index or -1, new last_node_index,
        updated cluster aggregates)."""
        if flags is None:
            flags = batch_flags(b)
        choices, counter, final = self._solve_scan(
            b, c, last_node_index, score_bias, flags, None, None,
            extra_mask)
        return choices, counter, self._carry_cluster(c, final)

    def solve_sequential_packed(self, b: DeviceBatch, c: DeviceCluster,
                                last_node_index: jnp.ndarray | None,
                                flags: BatchFlags,
                                extra_mask: jnp.ndarray | None = None,
                                score_bias: jnp.ndarray | None = None,
                                live: jnp.ndarray | None = None
                                ) -> jnp.ndarray:
        """solve_sequential, with every host-bound result packed into ONE
        int32 vector: [choices (P), counter (1), requested (4N), nonzero
        (2N)].  Each device->host fetch is a synchronization point, so
        the daemon fetches exactly one array per drain and unpacks
        host-side."""
        choices, counter, final = self._solve_scan(
            b, c, last_node_index, score_bias, flags, None, live,
            extra_mask)
        requested, nonzero = self._final_aggregates(final)
        return jnp.concatenate([
            choices, counter.astype(jnp.int32)[None],
            requested.ravel(), nonzero.ravel()])

    @staticmethod
    def _final_aggregates(final: dict) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(requested [N,4], nonzero [N,2]) from a scan's final state,
        which carries them as one packed [N,6] matrix."""
        return final["packed"][:, :4], final["packed"][:, 4:6]

    @staticmethod
    def _carry_cluster(c: "DeviceCluster | NarrowCluster",
                       final: dict) -> DeviceCluster:
        """Fold a scan's final dynamic state back into a DeviceCluster."""
        requested, nonzero = Solver._final_aggregates(final)
        return widen_cluster(c)._replace(
            requested=requested, nonzero=nonzero,
            ports_used=final.get("ports_used", c.ports_used),
            vol_any=final.get("vol_any", c.vol_any),
            vol_rw=final.get("vol_rw", c.vol_rw))

    def scores_affinity(self, flags: BatchFlags) -> bool:
        """Whether a scan compiled for ``flags`` carries
        InterPodAffinityPriority as a dynamic priority
        (``_scan_families``' rule for it)."""
        return flags.any_affinity_prio and self._has_affinity_prio

    def _scan_families(self, flags: BatchFlags) -> ScanFamilies:
        """The scan's trace-time specialization for this policy and these
        content flags."""
        # Dynamic predicate -> can this batch move the state it reads?
        gates = {"PodFitsResources": True,
                 "PodFitsHostPorts": flags.any_ports,
                 "PodFitsPorts": flags.any_ports,
                 "NoDiskConflict": flags.any_volumes,
                 "MatchInterPodAffinity": flags.any_affinity_pred,
                 "MaxEBSVolumeCount": flags.any_ebs,
                 "MaxGCEPDVolumeCount": flags.any_gce}
        in_scan_preds = frozenset(name for name in self.predicate_names
                                  if gates.get(name, False))
        static_prios, dynamic_prios = [], []
        for spec in self.priority_specs:
            name = spec[0]
            in_scan = name in DYNAMIC_PRIORITIES
            if name in ("SelectorSpreadPriority", "ServiceSpreadingPriority"):
                in_scan = flags.any_spread
            elif name == "InterPodAffinityPriority":
                in_scan = flags.any_affinity_prio
            elif name == "ServiceAntiAffinityPriority":
                # No batch pod joins any scored service group: counts are
                # provably constant, the batch-start plane is exact.
                in_scan = flags.any_saa
            (dynamic_prios if in_scan else static_prios).append(spec)
        scored = {name for name, _w, _aux in dynamic_prios}
        track_spread = bool(scored & {"SelectorSpreadPriority",
                                      "ServiceSpreadingPriority"})
        return ScanFamilies(
            resources="PodFitsResources" in in_scan_preds,
            ports=bool(in_scan_preds & {"PodFitsHostPorts", "PodFitsPorts"}),
            volumes="NoDiskConflict" in in_scan_preds,
            interpod="MatchInterPodAffinity" in in_scan_preds,
            max_ebs="MaxEBSVolumeCount" in in_scan_preds,
            max_gce="MaxGCEPDVolumeCount" in in_scan_preds,
            in_scan_preds=in_scan_preds,
            static_prios=tuple(static_prios),
            dynamic_prios=tuple(dynamic_prios),
            track_affinity="MatchInterPodAffinity" in in_scan_preds
            or "InterPodAffinityPriority" in scored,
            track_spread=track_spread,
            track_spread_zones=track_spread and flags.any_spread_zones,
            track_saa="ServiceAntiAffinityPriority" in scored)

    # kt-xray: donate(donate_argnums=(6,) — the carry: each chunk's
    # final state is consumed exactly once, by the next chunk's launch;
    # nothing else aliases it (choices ride separate buffers), so the
    # scan updates the carried aggregates in place instead of minting a
    # fresh copy of every state plane per chunk.  c and b stay
    # non-donated: they alias the resident mirror / the sliced batch.)
    @functools.partial(jax.jit, static_argnums=(0, 5), donate_argnums=(6,))
    def _solve_scan(self, b: DeviceBatch, c: DeviceCluster,
                    last_node_index: jnp.ndarray | None,
                    score_bias: jnp.ndarray | None,
                    flags: BatchFlags = ALL_ON_FLAGS,
                    carry: dict | None = None,
                    live: jnp.ndarray | None = None,
                    extra_mask: jnp.ndarray | None = None
                    ) -> tuple[jnp.ndarray, jnp.ndarray, dict]:
        """The sequential scan, with an additive per-(pod,node) score bias
        (zero for parity greedy; price-shaped for the joint solver).

        ``flags`` compiles away dynamic-state families the batch cannot
        touch; ``carry`` continues a previous chunk's final state (chunked
        drain) — flags MUST come from the full batch, not the chunk, so
        every chunk carries the same state shape.  ``extra_mask`` [P,N] is
        an additional hard feasibility plane (workload constraints —
        topology spread's DoNotSchedule terms); None compiles it away.
        A PackedBatch brings ``last_node_index`` / ``live`` / the two
        planes in its carrier; pass None for what rides there.
        Returns (choices [P], counter, final state dict).

        The loop's trip count follows the launch's live rows
        (``run_live_steps``): it stops after the last row ``live`` marks,
        read on the device from the mask the launch already carries —
        a 30-pod launch in the 256-row bucket runs 32 steps — and rows
        it never steps read -1, as a dead row's step would have chosen;
        ``live=None`` steps every row.  The programs keep their shapes:
        one per (bucket, flags, carry) as before.

        The step is built for per-step cost:

        * the hoisted mask/score planes merge into ONE encoded plane
          (``-inf`` = statically infeasible), so each step slices one
          row and folds dynamic feasibility with a single ``where``;
        * ``requested``+``nonzero`` carry as one packed [N,6] matrix
          committed by a single one-row scatter-add;
        * spread/zone counts commit by one-column scatter-adds;
          port/volume/PD planes by one-row updates;
        * the nz-only dynamic priorities (least/most-requested,
          balanced) are template-factored (``_template_col``);
        * mask -> score -> tie-break -> select is
          ``combine.select_host`` — three node-axis reductions."""
        b, last_node_index, score_bias, live, extra_mask = unpack_launch(
            b, last_node_index, score_bias, live, extra_mask)
        c = widen_cluster(c)
        n = c.alloc.shape[0]
        p = b.request.shape[0]
        a = b.aff

        # Hoist placement-invariant work: static predicate masks and static
        # priority planes are the big vocab contractions.  A policy-dynamic
        # predicate whose inputs are absent from this batch (flags) is
        # hoisted too — its mask and state provably never change mid-scan.
        fam = self._scan_families(flags)
        static_mask = jnp.broadcast_to(c.schedulable[None, :], (p, n))
        for name in self.predicate_names:
            if name not in fam.in_scan_preds:
                static_mask &= _predicate_mask(name, b, c, n, self.extra)
        if live is not None:
            # Chunk padding: dead rows are infeasible everywhere, place
            # nothing, and bump no counter (hoisted).  The loop never
            # reaches those past the last live row; this keeps the ones
            # before it (a hole, the round-up to whole iterations) inert.
            static_mask &= live[:, None]
        if extra_mask is not None:
            # Workload-constraint hard plane (batch-start topology spread):
            # hoisted like every other static predicate.
            static_mask &= extra_mask
        # None bias (the greedy path) becomes a zeros plane inside the jit,
        # which XLA elides — callers avoid materializing a [P,N] zeros arg.
        static_score = score_bias if score_bias is not None \
            else jnp.zeros((p, n), jnp.float32)
        for name, weight, aux in fam.static_prios:
            static_score += jnp.float32(weight) * \
                _priority_plane(name, b, c, n, {"aux": aux})

        f32 = jnp.float32
        neg = f32(-jnp.inf)
        zone_ids = b.node_zone_id  # [N]
        fits_pods_alloc = c.alloc[:, RES_PODS]
        alloc3 = c.alloc[:, :3]
        # Template-factored priorities: a carried [T,N] plane is
        # row-gathered per pod and recomputed for ONE column per
        # placement; a batch over the template cap (no templates) scores
        # them in the step instead.
        tmpl_prios = tuple(sp for sp in fam.dynamic_prios
                           if sp[0] in self._TEMPLATE_PRIOS)
        other_prios = tuple(sp for sp in fam.dynamic_prios
                            if sp[0] not in self._TEMPLATE_PRIOS)
        use_templates = bool(tmpl_prios) and b.nz_templates.shape[0] > 0
        if not use_templates:
            other_prios = fam.dynamic_prios
            tmpl_prios = ()

        # The encoded static plane: score where statically feasible,
        # -inf elsewhere — one xs row per step instead of mask + score.
        # Narrow score accumulation: when the greedy score is provably
        # small-integral (no joint price bias; the policy's summed
        # weight x MAX_PRIORITY bound fits the half-width mantissa
        # exactly), the plane stores at half width — halving the
        # dominant hoisted-plane bytes and the per-step row read — and
        # every step widens its one row back to f32 before the reduce
        # (the "bf16 accumulate, f32 final reduce" policy: bf16 on TPU,
        # f16 — wider mantissa — elsewhere; -inf encodes exactly in
        # both).  Values are integers well inside the exact range, so
        # tie sets cannot move (parity-pinned).
        enc = jnp.where(static_mask, static_score, neg)
        weight_bound = sum(abs(w) for _n, w, _a in self.priority_specs) \
            * prio.MAX_PRIORITY
        if score_bias is None:
            exact = 256 if self._half_dtype is jnp.bfloat16 else 2048
            if weight_bound < exact:
                enc = enc.astype(self._half_dtype)

        def step(state, xs):
            counter = state["counter"]
            packed = state["packed"]
            masked = xs["enc"].astype(f32)

            # Dynamic score families (template-factored ones come from
            # the carried plane).
            if use_templates:
                masked = masked + state["D"][xs["tmpl"]]
            for name, weight, aux in other_prios:
                w = f32(weight)
                if name == "LeastRequestedPriority":
                    masked = masked + w * prio.least_requested(
                        xs["nz"][None], packed[:, 4:6], c.alloc)[0]
                elif name == "MostRequestedPriority":
                    masked = masked + w * prio.most_requested(
                        xs["nz"][None], packed[:, 4:6], c.alloc)[0]
                elif name == "BalancedResourceAllocation":
                    masked = masked + w * prio.balanced_resource_allocation(
                        xs["nz"][None], packed[:, 4:6], c.alloc)[0]
                elif name in ("SelectorSpreadPriority",
                              "ServiceSpreadingPriority"):
                    # Reduction-free selector spread: the per-step max
                    # reductions of prio.selector_spread are replaced by
                    # CARRIED per-group maxima (sp_maxn over schedulable
                    # nodes, sp_maxz over zones) — counts only grow, and
                    # only at the placed column, so the maxima update in
                    # O(S) at commit.  Term-for-term the same float
                    # expressions as selector_spreading.go via
                    # prio.selector_spread (parity-pinned).
                    g = xs["sgroup"]
                    counts_g = state["sp_node"][g]          # [N]
                    maxn_g = state["sp_maxn"][g]
                    fsc = jnp.where(
                        maxn_g > 0,
                        10.0 * ((maxn_g - counts_g)
                                / jnp.maximum(maxn_g, 1e-9)), 10.0)
                    if fam.track_spread_zones:
                        zc_g = state["sp_zone"][g]          # [Z]
                        maxz_g = state["sp_maxz"][g]
                        zs_z = 10.0 * ((maxz_g - zc_g)
                                       / jnp.maximum(maxz_g, 1e-9))
                        node_has_zone = zone_ids >= 0
                        zs_n = jnp.where(
                            node_has_zone,
                            zs_z[jnp.clip(zone_ids, 0)],
                            10.0 * (maxz_g
                                    / jnp.maximum(maxz_g, 1e-9)))
                        blended = fsc * (1.0 - 2.0 / 3.0) + \
                            (2.0 / 3.0) * zs_n
                        fsc = jnp.where(
                            b.spread_has_zones[g] & node_has_zone
                            & (maxz_g > 0), blended, fsc)
                    masked = masked + w * prio._trunc(fsc)
                elif name == "InterPodAffinityPriority":
                    counts = interpod.priority_counts(
                        xs["pref_w"][None], state["match_cnt"],
                        xs["sym_match"][None], a.sym_w, state["sym_cnt"])
                    masked = masked + w * interpod.priority_score(
                        counts, c.schedulable, prio._trunc)[0]
                elif name == "ServiceAntiAffinityPriority":
                    masked = masked + w * saa_plane(
                        state["saa_cnt"][aux][xs["saa_g"]][None],
                        state["saa_num"][xs["saa_g"]][None, None],
                        b.volsvc.saa_dom[aux],
                        b.volsvc.saa_labeled[aux])[0]

            # Dynamic predicates (predicates.go:444-485, :721-741,
            # :100-153) on the current aggregates — O(N) per step — folded
            # into the encoded plane by one where.
            dyn_ok = None

            def also(cond):
                return cond if dyn_ok is None else (dyn_ok & cond)

            if fam.resources:
                fits_pods = (packed[:, RES_PODS] + 1) <= fits_pods_alloc
                free = alloc3 - packed[:, :3]
                fits_res = jnp.all(xs["req"][None, :3] <= free, axis=-1)
                dyn_ok = also(fits_pods & (xs["zero"] | fits_res))
            if fam.ports:
                port_conflict = jnp.einsum(
                    "c,nc->n", xs["ports"].astype(f32),
                    state["ports_used"].astype(f32)) > 0
                dyn_ok = also(~port_conflict)
            if fam.volumes:
                vol_conflict = (
                    jnp.einsum("w,nw->n", xs["vrw"].astype(f32),
                               state["vol_any"].astype(f32)) +
                    jnp.einsum("w,nw->n", xs["vro"].astype(f32),
                               state["vol_rw"].astype(f32))) > 0
                dyn_ok = also(~vol_conflict)
            for pd, on in (("ebs", fam.max_ebs), ("gce", fam.max_gce)):
                if not on:
                    continue
                pd_node = state[f"pd_{pd}"]
                pod_row = xs[f"pd_pod_{pd}"].astype(f32)
                overlap = jnp.einsum("w,nw->n", pod_row,
                                     pd_node.astype(f32))
                new = jnp.sum(pod_row) + xs[f"pd_extra_{pd}"].astype(f32)
                node_extra = getattr(b.volsvc, f"pd_node_extra_{pd}")
                node_err = getattr(b.volsvc, f"pd_node_err_{pd}")
                total = jnp.sum(pd_node.astype(f32), axis=1) + \
                    node_extra.astype(f32) + new - overlap
                ok = (total <= f32(self.extra[f"max_{pd}"])) & ~node_err
                dyn_ok = also((new == 0) | ok)
            if fam.track_affinity:
                reach = state["match_cnt"] > 0.0  # [Sm, N]
            if fam.interpod:
                live_need = xs["aff_need"] & ~(
                    xs["aff_self"] & (state["match_total"] == 0.0))
                viol = (jnp.einsum("s,sn->n", live_need.astype(f32),
                                   (~reach).astype(f32)) +
                        jnp.einsum("s,sn->n", xs["anti_need"].astype(f32),
                                   reach.astype(f32)) +
                        jnp.einsum("s,sn->n", xs["decl_match"].astype(f32),
                                   state["decl_reach"].astype(f32))) > 0
                dyn_ok = also(~viol)
            if dyn_ok is not None:
                masked = jnp.where(dyn_ok, masked, neg)

            # selectHost (generic_scheduler.go:124-141): round-robin
            # among max-score feasible nodes; the counter bumps only on
            # success.
            choice, any_feasible = combine.select_host(masked, counter)

            # Commit (the batched AssumePod, cache.go:107) — one-row /
            # one-column scatters instead of full-plane rewrites.
            placed = choice >= 0
            j = jnp.clip(choice, 0)
            pi = placed.astype(jnp.int32)
            pf = placed.astype(f32)
            new_state = dict(state)
            req6 = jnp.concatenate([xs["req"], xs["nz"]])
            new_packed = packed.at[j].add(req6 * pi)
            new_state["packed"] = new_packed
            if use_templates:
                new_state["D"] = state["D"].at[:, j].set(
                    self._template_col(tmpl_prios, b.nz_templates,
                                       new_packed[j, 4:6], c.alloc[j]))
            if fam.ports:
                new_state["ports_used"] = state["ports_used"].at[j].set(
                    state["ports_used"][j] | (xs["ports"] & placed))
            if fam.volumes:
                new_state["vol_any"] = state["vol_any"].at[j].set(
                    state["vol_any"][j] |
                    ((xs["vrw"] | xs["vro"]) & placed))
                new_state["vol_rw"] = state["vol_rw"].at[j].set(
                    state["vol_rw"][j] | (xs["vrw"] & placed))
            if fam.track_spread:
                incr_f = xs["incr"].astype(f32) * pf          # [S]
                new_col = state["sp_node"][:, j] + incr_f
                new_state["sp_node"] = state["sp_node"].at[:, j].set(
                    new_col)
                # The placed node is feasible hence schedulable, so the
                # max-over-schedulable can only move through its column;
                # unplaced steps must NOT fold column 0 (clip target) of
                # a possibly-unschedulable node into the maximum.
                new_state["sp_maxn"] = jnp.where(
                    placed, jnp.maximum(state["sp_maxn"], new_col),
                    state["sp_maxn"])
                if fam.track_spread_zones:
                    zid = zone_ids[j]
                    zc = jnp.clip(zid, 0)
                    zval = incr_f * (zid >= 0).astype(f32)
                    new_zcol = state["sp_zone"][:, zc] + zval
                    new_state["sp_zone"] = state["sp_zone"] \
                        .at[:, zc].set(new_zcol)
                    new_state["sp_maxz"] = jnp.where(
                        placed & (zid >= 0),
                        jnp.maximum(state["sp_maxz"], new_zcol),
                        state["sp_maxz"])
            if fam.max_ebs:
                new_state["pd_ebs"] = state["pd_ebs"].at[j].set(
                    state["pd_ebs"][j] | (xs["pd_pod_ebs"] & placed))
            if fam.max_gce:
                new_state["pd_gce"] = state["pd_gce"].at[j].set(
                    state["pd_gce"][j] | (xs["pd_pod_gce"] & placed))
            if fam.track_saa:
                src = xs["saa_src"].astype(f32) * pf          # [Gy]
                new_state["saa_num"] = state["saa_num"] + src
                dom_j = b.volsvc.saa_dom[:, j]                # [L]
                lab_j = b.volsvc.saa_labeled[:, j] & placed   # [L]
                n_dom = state["saa_cnt"].shape[2]
                domoh = ((jnp.arange(n_dom, dtype=jnp.int32)[None, :]
                          == dom_j[:, None]) & lab_j[:, None]).astype(f32)
                new_state["saa_cnt"] = state["saa_cnt"] + \
                    domoh[:, None, :] * src[None, :, None]
            if fam.track_affinity:
                (new_state["match_cnt"], new_state["match_total"],
                 new_state["decl_reach"], new_state["sym_cnt"]) = \
                    interpod.place_update(
                        a.node_dom, a.match_key, state["match_cnt"],
                        state["match_total"], xs["match_src"],
                        a.decl_key, state["decl_reach"], xs["decl_src"],
                        a.sym_key, state["sym_cnt"], xs["sym_src"],
                        choice, placed)
            new_state["counter"] = counter + \
                jnp.where(any_feasible, jnp.uint32(1), jnp.uint32(0))
            return new_state, choice

        init = {
            "packed": jnp.concatenate([c.requested, c.nonzero], axis=1),
            "counter": last_node_index,
        }
        xs = {
            "req": b.request, "zero": b.zero_request, "nz": b.nonzero,
            "enc": enc,
        }
        if use_templates:
            D0 = jnp.zeros((b.nz_templates.shape[0], n), f32)
            for name, weight, _aux in tmpl_prios:
                w = f32(weight)
                if name == "LeastRequestedPriority":
                    D0 = D0 + w * prio.least_requested(
                        b.nz_templates, c.nonzero, c.alloc)
                elif name == "MostRequestedPriority":
                    D0 = D0 + w * prio.most_requested(
                        b.nz_templates, c.nonzero, c.alloc)
                elif name == "BalancedResourceAllocation":
                    D0 = D0 + w * prio.balanced_resource_allocation(
                        b.nz_templates, c.nonzero, c.alloc)
            init["D"] = D0
            xs["tmpl"] = b.nz_tmpl_idx
        if fam.ports:
            init["ports_used"] = c.ports_used
            xs["ports"] = b.ports
        if fam.volumes:
            init["vol_any"] = c.vol_any
            init["vol_rw"] = c.vol_rw
            xs["vro"] = b.vol_ro
            xs["vrw"] = b.vol_rw
        if fam.track_spread:
            init["sp_node"] = b.spread_node_counts
            init["sp_zone"] = b.spread_zone_counts
            # Carried maxima, seeded exactly like the per-step
            # reductions they replace (selector_spreading.go's
            # countsByNodeName max spans the ready node list; the zone
            # max spans all zones).
            init["sp_maxn"] = jnp.max(
                jnp.where(c.schedulable[None, :],
                          b.spread_node_counts, 0.0), axis=1)
            init["sp_maxz"] = jnp.max(b.spread_zone_counts, axis=1)
            xs["sgroup"] = b.spread_group
            xs["incr"] = b.spread_incr
        if fam.track_affinity:
            init.update(match_cnt=a.match_cnt, match_total=a.match_total,
                        decl_reach=a.decl_reach, sym_cnt=a.sym_cnt)
            xs.update(aff_need=a.aff_need, aff_self=a.aff_self,
                      anti_need=a.anti_need, decl_match=a.decl_match,
                      match_src=a.match_src, decl_src=a.decl_src,
                      pref_w=a.pref_w, sym_match=a.sym_match,
                      sym_src=a.sym_src)
        if fam.track_saa:
            init["saa_cnt"] = b.volsvc.saa_cnt
            init["saa_num"] = b.volsvc.saa_num
            xs["saa_g"] = b.volsvc.saa_group
            xs["saa_src"] = b.volsvc.saa_src
        if fam.max_ebs:
            init["pd_ebs"] = b.volsvc.pd_node_ebs
            xs["pd_pod_ebs"] = b.volsvc.pd_pod_ebs
            xs["pd_extra_ebs"] = b.volsvc.pd_extra_ebs
        if fam.max_gce:
            init["pd_gce"] = b.volsvc.pd_node_gce
            xs["pd_pod_gce"] = b.volsvc.pd_pod_gce
            xs["pd_extra_gce"] = b.volsvc.pd_extra_gce
        if carry is not None:
            init.update({k: v for k, v in carry.items() if k in init})
        final, choices = run_live_steps(step, init, xs, p, live)
        return choices, final["counter"], final

    # Dynamic priorities whose pod-dependence is ONLY the nonzero-request
    # row: their per-step [N] score plane is a pure function of
    # (template, carried aggregates), so the scan can carry one
    # [templates, N] plane and update a single column per placement
    # instead of recomputing the whole chain every step.
    _TEMPLATE_PRIOS = ("LeastRequestedPriority", "MostRequestedPriority",
                       "BalancedResourceAllocation")

    def _template_col(self, tmpl_prios: tuple, templates: jnp.ndarray,
                      nz_j: jnp.ndarray, alloc_j: jnp.ndarray
                      ) -> jnp.ndarray:
        """[T] — the template-factored score column for one node, from
        its (new) nonzero aggregates.  EXACTLY the formulas the step
        applies to a batch without templates, evaluated at a single
        node."""
        col = jnp.zeros(templates.shape[0], jnp.float32)
        for name, weight, _aux in tmpl_prios:
            w = jnp.float32(weight)
            if name == "LeastRequestedPriority":
                col += w * prio.least_requested(
                    templates, nz_j[None], alloc_j[None])[:, 0]
            elif name == "MostRequestedPriority":
                col += w * prio.most_requested(
                    templates, nz_j[None], alloc_j[None])[:, 0]
            elif name == "BalancedResourceAllocation":
                col += w * prio.balanced_resource_allocation(
                    templates, nz_j[None], alloc_j[None])[:, 0]
        return col


    # -- joint batched assignment (the LP-relaxed global solve) ----------

    # kt-xray: no-donate(b/c flow on into the repair scan of the same
    # joint solve)
    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _price_iterate(self, b: DeviceBatch, c: DeviceCluster,
                       n_iters: int,
                       extra_mask: jnp.ndarray | None = None
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Dual-price iteration for the joint assignment objective.

        The batched placement is a generalized assignment problem: maximize
        the summed combined score subject to per-node multi-resource
        capacity.  Its LP relaxation decomposes by pricing each node
        resource (dual variables lam [N, R]): pods bid for their
        utility-argmax node, prices rise on oversubscribed resources
        (projected subgradient on the dual), and the final prices shape a
        regret-ordered greedy repair pass that restores full feasibility
        (including ports/volumes/affinity) in ``solve_joint``.

        Returns (score_bias [P, N] = -price cost, repair-order key [P]).
        """
        b = unpack_batch(b)
        c = widen_cluster(c)
        feasible, scores = self.evaluate(b, c)
        if extra_mask is not None:
            feasible &= extra_mask
        f32 = jnp.float32
        free = jnp.maximum((c.alloc[:, :3] - c.requested[:, :3]).astype(f32),
                           1.0)                          # [N, 3]
        demand = b.request[:, :3].astype(f32)            # [P, 3]
        # Normalize so prices are in score units per fraction-of-node.
        dnorm = demand[:, None, :] / free[None, :, :]    # [P, N, 3]
        neg = f32(-jnp.inf)
        score_span = jnp.maximum(jnp.max(jnp.where(feasible, scores, 0.0)),
                                 1.0)
        lr = score_span  # one full-node oversubscription ~ top score

        def it(lam, _):
            cost = jnp.einsum("pnr,nr->pn", dnorm, lam)
            util = jnp.where(feasible, scores - cost, neg)
            choice = jnp.argmax(util, axis=1)            # [P]
            placed = jnp.any(feasible, axis=1)
            onehot = (jax.nn.one_hot(choice, util.shape[1], dtype=f32)
                      * placed[:, None].astype(f32))     # [P, N]
            load = jnp.einsum("pn,pr->nr", onehot, demand)  # [N, 3]
            over = jnp.maximum(load - free, 0.0) / free
            lam = jnp.maximum(lam + lr * over - 0.02 * lr * (over == 0), 0.0)
            return lam, None

        lam0 = jnp.zeros((c.alloc.shape[0], 3), f32)
        lam, _ = jax.lax.scan(it, lam0, None, length=n_iters)
        cost = jnp.einsum("pnr,nr->pn", dnorm, lam)
        util = jnp.where(feasible, scores - cost, neg)
        top2 = jax.lax.top_k(util, 2)[0] if util.shape[1] > 1 else \
            jnp.pad(util, ((0, 0), (0, 1)), constant_values=neg)
        regret = jnp.where(jnp.isfinite(top2[:, 0]),
                           top2[:, 0] - jnp.where(jnp.isfinite(top2[:, 1]),
                                                  top2[:, 1], top2[:, 0] - 1e3),
                           neg)
        # Repair-order key: smallest dominant-resource fraction first (for a
        # sum-of-scores objective with commensurate per-pod scores this
        # maximizes admitted count), regret-tiebroken within a size bucket.
        # (over the nodes a pod could land on: a free row of the node axis
        # has no room at all and would read as a full node everywhere)
        dfrac = jnp.max(jnp.where(c.schedulable[None, :, None],
                                  demand[:, None, :] / free[None, :, :],
                                  0.0), axis=(1, 2))
        key = -jnp.floor(jnp.minimum(dfrac, 1.0) * 16.0) * \
            (20.0 * score_span) + jnp.where(jnp.isfinite(regret), regret, 0.0)
        return -cost, key

    # kt-xray: no-donate(c is the shared resident cluster; donation
    # would invalidate it for the next drain's scatter)
    @functools.partial(jax.jit, static_argnums=(0, 7, 8))
    def _solve_joint_jit(self, b: DeviceBatch, c: DeviceCluster,
                         last_node_index: jnp.ndarray | None,
                         extra_mask: jnp.ndarray | None,
                         score_bias: jnp.ndarray | None,
                         live: jnp.ndarray | None,
                         n_iters: int, flags: BatchFlags
                         ) -> tuple[jnp.ndarray, jnp.ndarray, dict]:
        """The WHOLE joint pipeline (price iteration -> regret ordering ->
        pod-axis permutation -> repair scan -> inverse permutation) as ONE
        jitted executable.  The pre-r6 host-side glue dispatched ~75
        individual device ops per solve (argsort + one jnp.take per
        DeviceBatch field), each minting its own shape-keyed executable
        OUTSIDE the jit cache — none of which the persistent compilation
        cache could amortize as a unit.  One trace means one XLA program,
        persisted once, deserialized on every later start
        (tests/test_joint_solver.py pins the cold-vs-warm gap)."""
        b, last_node_index, score_bias, live, extra_mask = unpack_launch(
            b, last_node_index, score_bias, live, extra_mask)
        c = widen_cluster(c)
        bias, key = self._price_iterate(b, c, n_iters, extra_mask)
        if score_bias is not None:
            bias = bias + score_bias
        order = jnp.argsort(-key)   # biggest, then highest-regret, first
        pb = permute_pod_axis(b, order)
        pbias = jnp.take(bias, order, axis=0)
        pmask = None if extra_mask is None else \
            jnp.take(extra_mask, order, axis=0)
        plive = None if live is None else jnp.take(live, order)
        choices_p, counter, final = self._solve_scan(
            pb, c, last_node_index, pbias, flags, None, plive, pmask)
        inv = jnp.argsort(order)
        return jnp.take(choices_p, inv), counter, final

    def solve_joint(self, b: DeviceBatch, c: DeviceCluster,
                    last_node_index: jnp.ndarray | None, n_iters: int = 24,
                    flags: BatchFlags | None = None,
                    extra_mask: jnp.ndarray | None = None,
                    score_bias: jnp.ndarray | None = None,
                    live: jnp.ndarray | None = None
                    ) -> tuple[jnp.ndarray, jnp.ndarray, DeviceCluster]:
        """Joint batched assignment: price iteration + regret-ordered greedy
        repair.  Same return contract as solve_sequential; placements honor
        EVERY predicate (the repair pass is the exact sequential scan, just
        price-shaped and reordered) plus the workload-constraint
        ``extra_mask``/``score_bias`` planes.  ``live`` marks real rows
        when the caller padded the batch to a warm bucket.  Quality
        (summed score, placement count) is benchmarked against the greedy
        baseline — BASELINE.json's last config."""
        if flags is None:
            flags = batch_flags(b)
        choices, counter, final = self._solve_joint_jit(
            b, c, last_node_index, extra_mask, score_bias, live,
            n_iters, flags)
        return choices, counter, self._carry_cluster(c, final)


# Pod-axis fields of DeviceBatch (dim 0 = P) for permutation/sharding.
_POD_AXIS_FIELDS = ("request", "zero_request", "nonzero", "best_effort",
                    "host_idx", "ports", "vol_ro", "vol_rw", "tol_nosched",
                    "tol_prefer", "has_tolerations", "images", "sel_group",
                    "spread_group", "spread_incr", "avoid_group",
                    "nz_tmpl_idx")
_AFF_POD_AXIS_FIELDS = ("match_src", "aff_need", "aff_self", "anti_need",
                        "pref_w", "decl_match", "decl_src", "sym_match",
                        "sym_src")
_VS_POD_AXIS_FIELDS = ("pd_pod_ebs", "pd_extra_ebs", "pd_pod_gce",
                       "pd_extra_gce", "vz_group", "sa_group", "saa_group",
                       "saa_src")


def slice_pod_axis(b: DeviceBatch, start: int, stop: int) -> DeviceBatch:
    """A [start:stop) view of every pod-axis tensor (chunked drain)."""
    updates = {f: getattr(b, f)[start:stop] for f in _POD_AXIS_FIELDS}
    aff = b.aff._replace(**{f: getattr(b.aff, f)[start:stop]
                            for f in _AFF_POD_AXIS_FIELDS})
    volsvc = b.volsvc._replace(**{f: getattr(b.volsvc, f)[start:stop]
                                  for f in _VS_POD_AXIS_FIELDS})
    return b._replace(aff=aff, volsvc=volsvc, **updates)


def permute_pod_axis(b: DeviceBatch, order: jnp.ndarray) -> DeviceBatch:
    """Reorder every pod-axis tensor of a DeviceBatch by ``order``."""
    updates = {f: jnp.take(getattr(b, f), order, axis=0)
               for f in _POD_AXIS_FIELDS}
    aff = b.aff._replace(**{f: jnp.take(getattr(b.aff, f), order, axis=0)
                            for f in _AFF_POD_AXIS_FIELDS})
    volsvc = b.volsvc._replace(
        **{f: jnp.take(getattr(b.volsvc, f), order, axis=0)
           for f in _VS_POD_AXIS_FIELDS})
    return b._replace(aff=aff, volsvc=volsvc, **updates)
