"""Feature compiler: Pod/Node objects -> dense device tensors.

This is the tensor-native replacement for the reference's ``schedulercache``
(``plugin/pkg/scheduler/schedulercache/node_info.go``): where ``NodeInfo``
pre-aggregates requested/allocatable resources and per-node pod lists for one
node, we build the whole cluster as stacked arrays so every predicate and
priority evaluates for all (pod, node) pairs at once on the MXU/VPU.

Unit conventions (chosen so exact Go int64 arithmetic fits in int32 on TPU):
  cpu     : millicores            (reference: int64 millicores)
  memory  : MiB — requests ceil'd, allocatable floor'd (reference: bytes).
            Real-world requests are MiB-aligned (incl. the 200*1024*1024-byte
            non-zero default, non_zero.go:47), so quantization is exact in
            practice; the parity harness measures any residual divergence.
  gpu     : count
  pods    : count
  image   : KiB (floor)

Resource vectors are [*, 4] int32 in order (milli_cpu, memory_mib, gpu, pods).
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass, field, replace
from typing import Optional, Sequence

import numpy as np

from kubernetes_tpu.api import types as api
from kubernetes_tpu.features.vocab import LabelVocab, Vocab

RES_CPU, RES_MEM, RES_GPU, RES_PODS = 0, 1, 2, 3

_MIB = 1024 * 1024


def _mib_ceil(b: int) -> int:
    return -((-b) // _MIB)


def _mib_floor(b: int) -> int:
    return b // _MIB


@dataclass
class FeatureSpace:
    """All interning vocabularies; the single source of id assignment."""

    labels: LabelVocab = field(default_factory=LabelVocab)       # node labels
    # Pod labels get their own vocabulary: selector matching against
    # existing pods only ever reads POD labels, and node vocabularies carry
    # per-node uniques (hostname) that would blow the [pods, V] matrix up
    # by orders of magnitude.
    pod_labels: LabelVocab = field(default_factory=LabelVocab)
    taints: Vocab = field(default_factory=Vocab)       # "key=value:effect"
    ports: Vocab = field(default_factory=Vocab)        # "tcp:port" etc
    volumes: Vocab = field(default_factory=Vocab)      # conflict keys
    images: Vocab = field(default_factory=Vocab)       # image name
    namespaces: Vocab = field(default_factory=Vocab)
    topo_keys: Vocab = field(default_factory=Vocab)    # topology label keys
    topo_vals: Vocab = field(default_factory=Vocab)    # "key=value" domains

    def __post_init__(self) -> None:
        # Default failure domains are always interned so topology columns
        # exist from the start (pkg/api/types.go:3053-3063).
        for k in api.DEFAULT_FAILURE_DOMAINS:
            self.topo_keys.id(k)

    # -- volume conflict tokens (predicates.go:100-144) --------------------
    @staticmethod
    def volume_tokens(v: api.Volume) -> list[tuple[str, bool]]:
        """Conflict tokens for a volume as (token, read_only) pairs.

        EBS conflicts regardless of read-only (predicates.go:116-120), so its
        token is always read_only=False.  RBD "shares at least one monitor"
        (haveSame, predicates.go:126-133) is made exact by emitting one token
        per monitor.
        """
        out: list[tuple[str, bool]] = []
        if v.gce_pd_name:
            out.append((f"gce:{v.gce_pd_name}", v.gce_read_only))
        if v.aws_ebs_id:
            out.append((f"ebs:{v.aws_ebs_id}", False))
        if v.rbd_key:
            mons, pool, image = (v.rbd_key.split("#") + ["", ""])[:3]
            for mon in mons.split(","):
                if mon:
                    out.append((f"rbd:{mon}#{pool}#{image}", v.rbd_read_only))
        return out


NODE_TILE = 128


def capacity(n: int) -> int:
    """Rows the node axis is allocated at for a fleet of ``n`` nodes: ``n``
    rounded up to whole 128-row tiles with at least one row free (5,000
    -> 5,120, 1,000 -> 1,024, 128 -> 256).  A function of the count
    alone.  Tiles and not a power of two: 5,000 -> 8,192 would add 64 %
    to every scan of a fleet that never changes."""
    return (n // NODE_TILE + 1) * NODE_TILE


# What the ``nodes`` list of a snapshot holds at a free row: a node no
# pod fits and no score counts (not Ready, no room, no label, no taint).
# Writing it with ``_write_node_row`` IS the encoding of a free row, so a
# feature builder that walks the list needs no case for one.
FREE_NODE = api.Node(name="", allocatable_pods=0, unschedulable=True)


@dataclass
class NodeTensors:
    """Static per-node features [N, ...], N a CAPACITY (``capacity()``
    where the cache builds them): a row is live (``names[i]`` is its
    node) or free (``names[i]`` is None, the row reads as ``FREE_NODE``:
    ``schedulable`` False takes it out of every fit, and every
    normalisation of the priorities spans schedulable rows).  A node
    event inside the capacity writes one row; only a join that finds no
    free row grows every tensor, by whole tiles.  The arrays are written
    in place under the cache lock; ``names`` is replaced at every change
    (``_rename_row``)."""

    names: list[Optional[str]]
    name_to_idx: dict[str, int]
    alloc: np.ndarray          # [N, 4] int32
    labels: np.ndarray         # [N, V] bool — kv + key-presence membership
    taints_nosched: np.ndarray  # [N, T] bool  (effect != PreferNoSchedule)
    taints_prefer: np.ndarray   # [N, T] bool  (effect == PreferNoSchedule)
    mem_pressure: np.ndarray   # [N] bool
    disk_pressure: np.ndarray  # [N] bool
    schedulable: np.ndarray    # [N] bool — getNodeConditionPredicate
    image_kib: np.ndarray      # [N, I] int32
    topo_val: np.ndarray       # [N, K] int32 — domain id per topo key, -1 absent
    free: list[int] = field(default_factory=list)  # free rows, a min-heap

    @property
    def n(self) -> int:
        """Rows (the capacity), live and free."""
        return len(self.names)

    def launch_view(self) -> "NodeTensors":
        """What a launch keeps of the node axis once the cache lock is
        let go, taken under it: row -> node as the scan saw it (the
        ``names`` list of now, which no later event writes) and copies
        of the two planes read after the solve — ``alloc`` by the sanity
        gate, ``schedulable`` by the failure accounts.  A row freed or
        handed to another node in flight reads here as it was."""
        return replace(self, alloc=self.alloc.copy(),
                       schedulable=self.schedulable.copy())


@dataclass
class NodeAggregates:
    """Per-node aggregates over the pods assigned to each node — the tensor
    analogue of NodeInfo.{requestedResource, nonzeroRequest, pods}
    (node_info.go:32-61).  Maintained incrementally by the scheduler cache."""

    requested: np.ndarray      # [N, 4] int32 (cpu, mem_mib, gpu, pod count)
    nonzero: np.ndarray        # [N, 2] int32 (cpu, mem_mib)
    ports_used: np.ndarray     # [N, P] bool
    vol_any: np.ndarray        # [N, W] bool — volume token mounted by any pod
    vol_rw: np.ndarray         # [N, W] bool — mounted by a non-read-only pod...
    vol_rw_count: np.ndarray   # [N, W] int16 rw mount counts (for removal)
    vol_any_count: np.ndarray  # [N, W] int16


@dataclass
class ExistingPodTensors:
    """Existing (assigned, non-terminated) pods as tensors — for selector
    spreading and inter-pod affinity, which must match *other pods'* labels.
    [M, ...] with a capacity that grows geometrically."""

    labels: np.ndarray         # [M, V] bool
    ns_id: np.ndarray          # [M] int32
    node_idx: np.ndarray       # [M] int32 (-1 = slot free)
    alive: np.ndarray          # [M] bool
    deleted: np.ndarray        # [M] bool (DeletionTimestamp set)
    keys: list[Optional[str]]  # slot -> pod key
    key_to_slot: dict[str, int]
    free_slots: list[int]      # O(1) slot allocation (popped LIFO)


def compile_nodes(nodes: Sequence[api.Node], space: FeatureSpace,
                  rows: Optional[int] = None) -> NodeTensors:
    """Build static node tensors, interning all label/taint/image tokens.
    ``nodes`` take the first rows in list order; ``rows`` (default: as
    many as nodes) is the capacity to allocate, the rest free.  Row
    encoding is shared with the incremental churn path
    (update_node_row/take_node_row) via _intern_node/_write_node_row, so
    rebuilt rows and incrementally-updated rows cannot diverge."""
    live = len(nodes)
    n = live if rows is None else rows
    assert n >= live, (n, live)
    # Intern first so capacities are final before allocation.
    for node in nodes:
        _intern_node(node, space)

    V, T, I, K = (space.labels.capacity, space.taints.capacity,
                  space.images.capacity, space.topo_keys.capacity)
    nt = NodeTensors(
        names=[nd.name for nd in nodes] + [None] * (n - live),
        name_to_idx={nd.name: i for i, nd in enumerate(nodes)},
        free=list(range(live, n)),
        alloc=np.zeros((n, 4), np.int32),
        labels=np.zeros((n, V), bool),
        taints_nosched=np.zeros((n, T), bool),
        taints_prefer=np.zeros((n, T), bool),
        mem_pressure=np.zeros(n, bool),
        disk_pressure=np.zeros(n, bool),
        schedulable=np.zeros(n, bool),
        image_kib=np.zeros((n, I), np.int32),
        topo_val=np.full((n, K), -1, np.int32))
    for i, node in enumerate(nodes):
        _write_node_row(nt, i, node, space)
    return nt


def _intern_node(node: api.Node, space: FeatureSpace) -> None:
    for k, v in node.labels.items():
        space.labels.kv_id(k, v)
        space.labels.key_id(k)
    for t in node.taints():
        space.taints.id(f"{t.key}={t.value}:{t.effect}")
    for img in node.images:
        for name in img.names:
            space.images.id(name)
    for key in space.topo_keys.tokens():
        if key in node.labels:
            space.topo_vals.id(f"{key}={node.labels[key]}")


def _grow_node_columns(nt: NodeTensors, space: FeatureSpace) -> None:
    nt.labels = _grow_cols(nt.labels, space.labels.capacity)
    nt.taints_nosched = _grow_cols(nt.taints_nosched, space.taints.capacity)
    nt.taints_prefer = _grow_cols(nt.taints_prefer, space.taints.capacity)
    nt.image_kib = _grow_cols(nt.image_kib, space.images.capacity)
    nt.topo_val = _grow_cols(nt.topo_val, space.topo_keys.capacity, fill=-1)


def _write_node_row(nt: NodeTensors, i: int, node: api.Node,
                    space: FeatureSpace) -> None:
    nt.alloc[i] = (node.allocatable_milli_cpu,
                   _mib_floor(node.allocatable_memory),
                   node.allocatable_gpu, node.allocatable_pods)
    nt.labels[i, :] = False
    for k, v in node.labels.items():
        nt.labels[i, space.labels.kv_id(k, v)] = True
        nt.labels[i, space.labels.key_id(k)] = True
    nt.taints_nosched[i, :] = False
    nt.taints_prefer[i, :] = False
    for t in node.taints():
        tid = space.taints.id(f"{t.key}={t.value}:{t.effect}")
        if t.effect == api.TAINT_EFFECT_PREFER_NO_SCHEDULE:
            nt.taints_prefer[i, tid] = True
        else:
            nt.taints_nosched[i, tid] = True
    nt.mem_pressure[i] = node.condition(api.NODE_MEMORY_PRESSURE) == "True"
    nt.disk_pressure[i] = node.condition(api.NODE_DISK_PRESSURE) == "True"
    nt.schedulable[i] = node.is_ready()
    nt.image_kib[i, :] = 0
    for img in node.images:
        kib = img.size_bytes // 1024
        for name in img.names:
            nt.image_kib[i, space.images.id(name)] = kib
    nt.topo_val[i, :] = -1
    for ki, key in enumerate(space.topo_keys.tokens()):
        if key in node.labels:
            nt.topo_val[i, ki] = space.topo_vals.id(
                f"{key}={node.labels[key]}")


def update_node_row(nt: NodeTensors, idx: int, node: api.Node,
                    space: FeatureSpace) -> None:
    """Incremental node UPDATE: rewrite one row of the static node tensors
    in place (growing vocab columns when the node introduced new tokens) —
    the churn path the node controller exercises with Ready flips
    (nodecontroller.go:70-160) must not recompile 5k rows."""
    _intern_node(node, space)
    _grow_node_columns(nt, space)
    _write_node_row(nt, idx, node, space)


def _rename_row(nt: NodeTensors, i: int, name: Optional[str]) -> None:
    """``names`` is REPLACED, never written in place: a launch keeps the
    list it took under the cache lock, so a row it decided on still
    reads as the node the scan saw there, whoever holds the row now."""
    names = list(nt.names)
    names[i] = name
    nt.names = names


def take_node_row(nt: NodeTensors, node: api.Node,
                  space: FeatureSpace) -> int:
    """Incremental node ADD: the lowest free row becomes ``node``'s,
    written by ``update_node_row``.  The caller grows the tensors first
    where no row is free."""
    i = heapq.heappop(nt.free)
    _rename_row(nt, i, node.name)
    nt.name_to_idx[node.name] = i
    update_node_row(nt, i, node, space)
    return i


def free_node_row(nt: NodeTensors, name: str, space: FeatureSpace) -> int:
    """Incremental node REMOVE: ``name``'s row reads as ``FREE_NODE``
    from here on and is handed out again by ``take_node_row``."""
    i = nt.name_to_idx.pop(name)
    _rename_row(nt, i, None)
    _write_node_row(nt, i, FREE_NODE, space)
    heapq.heappush(nt.free, i)
    return i


def _grow_rows(a: np.ndarray, rows: int, fill=0) -> np.ndarray:
    out = np.full((rows,) + a.shape[1:], fill, a.dtype)
    out[: a.shape[0]] = a
    return out


_NODE_PLANES = ("alloc", "labels", "taints_nosched", "taints_prefer",
                "mem_pressure", "disk_pressure", "schedulable", "image_kib")
_AGG_PLANES = ("requested", "nonzero", "ports_used", "vol_any", "vol_rw",
               "vol_rw_count", "vol_any_count")


def grow_node_rows(nt: NodeTensors, agg: NodeAggregates, rows: int) -> None:
    """The node axis at ``rows`` rows: every [N, ...] array of the node
    tensors and the aggregates copied once, the new rows free."""
    old = nt.n
    assert rows > old, (rows, old)
    for name in _NODE_PLANES:
        setattr(nt, name, _grow_rows(getattr(nt, name), rows))
    nt.topo_val = _grow_rows(nt.topo_val, rows, fill=-1)
    for name in _AGG_PLANES:
        setattr(agg, name, _grow_rows(getattr(agg, name), rows))
    nt.names = nt.names + [None] * (rows - old)
    nt.free.extend(range(old, rows))    # past every row in it: still a heap


def clear_aggregate_row(agg: NodeAggregates, idx: int) -> None:
    """Zero aggregates: the row of a node that left with its pods still
    tracked (their own deletes find no row)."""
    for name in _AGG_PLANES:
        getattr(agg, name)[idx] = 0


def pod_resource_row(pod: api.Pod) -> np.ndarray:
    """[4] int32 (cpu, mem_mib ceil, gpu, 1) — getResourceRequest.

    Cached on the pod: quantity-string parsing dominates at 30k-pod batches
    and pod specs are immutable once submitted (the reference's
    predicateMetadata makes the same assumption, predicates.go:71-98)."""
    row = getattr(pod, "_res_row", None)
    if row is None:
        r = pod.resource_request()
        row = np.array([r.milli_cpu, _mib_ceil(r.memory), r.nvidia_gpu, 1],
                       np.int32)
        pod._res_row = row
    return row


def pod_nonzero_row(pod: api.Pod) -> np.ndarray:
    row = getattr(pod, "_nz_row", None)
    if row is None:
        cpu, mem = pod.non_zero_request()
        row = np.array([cpu, _mib_ceil(mem)], np.int32)
        pod._nz_row = row
    return row


def empty_aggregates(n: int, space: FeatureSpace) -> NodeAggregates:
    P, W = space.ports.capacity, space.volumes.capacity
    return NodeAggregates(
        requested=np.zeros((n, 4), np.int32),
        nonzero=np.zeros((n, 2), np.int32),
        ports_used=np.zeros((n, P), bool),
        vol_any=np.zeros((n, W), bool),
        vol_rw=np.zeros((n, W), bool),
        vol_rw_count=np.zeros((n, W), np.int16),
        vol_any_count=np.zeros((n, W), np.int16))


def _pod_port_ids(pod: api.Pod, space: FeatureSpace) -> list[int]:
    return [space.ports.id(str(p)) for p in pod.used_host_ports()]


def _pod_volume_ids(pod: api.Pod, space: FeatureSpace) -> list[tuple[int, bool]]:
    out = []
    for v in pod.volumes:
        for token, ro in FeatureSpace.volume_tokens(v):
            out.append((space.volumes.id(token), ro))
    return out


def add_pod_to_aggregates(agg: NodeAggregates, node_idx: int, pod: api.Pod,
                          space: FeatureSpace) -> NodeAggregates:
    """NodeInfo.addPod (node_info.go:171-196), tensorized. May grow the port
    and volume columns if the pod interned new tokens."""
    agg = _grow_aggregate_columns(agg, space)
    agg.requested[node_idx] += pod_resource_row(pod)
    agg.nonzero[node_idx] += pod_nonzero_row(pod)
    for pid in _pod_port_ids(pod, space):
        agg = _grow_aggregate_columns(agg, space)
        agg.ports_used[node_idx, pid] = True
    for vid, ro in _pod_volume_ids(pod, space):
        agg = _grow_aggregate_columns(agg, space)
        agg.vol_any_count[node_idx, vid] += 1
        if not ro:
            agg.vol_rw_count[node_idx, vid] += 1
        agg.vol_any[node_idx, vid] = agg.vol_any_count[node_idx, vid] > 0
        agg.vol_rw[node_idx, vid] = agg.vol_rw_count[node_idx, vid] > 0
    return agg


def add_pods_to_aggregates_bulk(agg: NodeAggregates,
                                node_idxs: Sequence[int],
                                pods: Sequence[api.Pod],
                                space: FeatureSpace) -> NodeAggregates:
    """Bulk NodeInfo.addPod for a solved batch: one vectorized update instead
    of per-pod row ops.  Equivalent to repeated add_pod_to_aggregates
    (tested by tests/test_cache_bulk.py)."""
    # Intern first so column growth happens once.
    for pod in pods:
        for port in pod.used_host_ports():
            space.ports.id(str(port))
        for v in pod.volumes:
            for token, _ in FeatureSpace.volume_tokens(v):
                space.volumes.id(token)
    agg = _grow_aggregate_columns(agg, space)
    idxs = np.asarray(node_idxs, np.int64)
    req = np.stack([pod_resource_row(p) for p in pods])
    nz = np.stack([pod_nonzero_row(p) for p in pods])
    np.add.at(agg.requested, idxs, req)
    np.add.at(agg.nonzero, idxs, nz)
    for idx, pod in zip(node_idxs, pods):
        if pod.used_host_ports():
            for pid in _pod_port_ids(pod, space):
                agg.ports_used[idx, pid] = True
        if pod.volumes:
            for vid, ro in _pod_volume_ids(pod, space):
                agg.vol_any_count[idx, vid] += 1
                if not ro:
                    agg.vol_rw_count[idx, vid] += 1
                agg.vol_any[idx, vid] = agg.vol_any_count[idx, vid] > 0
                agg.vol_rw[idx, vid] = agg.vol_rw_count[idx, vid] > 0
    return agg


def remove_pod_from_aggregates(agg: NodeAggregates, node_idx: int, pod: api.Pod,
                               space: FeatureSpace,
                               node_pods: Sequence[api.Pod]) -> NodeAggregates:
    """NodeInfo.removePod (node_info.go:199-227).  ``node_pods`` is the node's
    remaining pod set, needed to recompute the port bitmap exactly (ports are
    a set union, not a counter, in the reference)."""
    agg.requested[node_idx] -= pod_resource_row(pod)
    agg.nonzero[node_idx] -= pod_nonzero_row(pod)
    for vid, ro in _pod_volume_ids(pod, space):
        agg.vol_any_count[node_idx, vid] -= 1
        if not ro:
            agg.vol_rw_count[node_idx, vid] -= 1
        agg.vol_any[node_idx, vid] = agg.vol_any_count[node_idx, vid] > 0
        agg.vol_rw[node_idx, vid] = agg.vol_rw_count[node_idx, vid] > 0
    agg.ports_used[node_idx] = False
    for p in node_pods:
        if p.key != pod.key:
            for pid in _pod_port_ids(p, space):
                agg = _grow_aggregate_columns(agg, space)
                agg.ports_used[node_idx, pid] = True
    return agg


def _grow_cols(a: np.ndarray, width: int, fill=0) -> np.ndarray:
    if a.shape[1] >= width:
        return a
    out = np.full((a.shape[0], width), fill, a.dtype)
    out[:, : a.shape[1]] = a
    return out


def _grow_aggregate_columns(agg: NodeAggregates, space: FeatureSpace) -> NodeAggregates:
    agg.ports_used = _grow_cols(agg.ports_used, space.ports.capacity)
    for f in ("vol_any", "vol_rw", "vol_rw_count", "vol_any_count"):
        setattr(agg, f, _grow_cols(getattr(agg, f), space.volumes.capacity))
    return agg


# ---------------------------------------------------------------------------
# Existing-pod tensors (spreading / inter-pod affinity inputs)
# ---------------------------------------------------------------------------

def empty_existing_pods(space: FeatureSpace, cap: int = 256) -> ExistingPodTensors:
    V = space.pod_labels.capacity
    return ExistingPodTensors(
        labels=np.zeros((cap, V), bool),
        ns_id=np.zeros(cap, np.int32),
        node_idx=np.full(cap, -1, np.int32),
        alive=np.zeros(cap, bool),
        deleted=np.zeros(cap, bool),
        keys=[None] * cap,
        key_to_slot={},
        free_slots=list(range(cap - 1, -1, -1)))


def existing_pods_add(ep: ExistingPodTensors, pod: api.Pod, node_idx: int,
                      space: FeatureSpace) -> ExistingPodTensors:
    for k, v in pod.labels.items():
        space.pod_labels.kv_id(k, v)
        space.pod_labels.key_id(k)
    ep.labels = _grow_cols(ep.labels, space.pod_labels.capacity)
    slot = ep.key_to_slot.get(pod.key)
    if slot is None:
        if not ep.free_slots:
            m = len(ep.keys)
            ep.labels = np.concatenate([ep.labels, np.zeros_like(ep.labels)], 0)
            ep.ns_id = np.concatenate([ep.ns_id, np.zeros(m, np.int32)])
            ep.node_idx = np.concatenate([ep.node_idx, np.full(m, -1, np.int32)])
            ep.alive = np.concatenate([ep.alive, np.zeros(m, bool)])
            ep.deleted = np.concatenate([ep.deleted, np.zeros(m, bool)])
            ep.keys += [None] * m
            ep.free_slots.extend(range(2 * m - 1, m - 1, -1))
        slot = ep.free_slots.pop()
        ep.key_to_slot[pod.key] = slot
        ep.keys[slot] = pod.key
    ep.labels[slot] = False
    for k, v in pod.labels.items():
        ep.labels[slot, space.pod_labels.kv_id(k, v)] = True
        ep.labels[slot, space.pod_labels.key_id(k)] = True
    ep.ns_id[slot] = space.namespaces.id(pod.namespace)
    ep.node_idx[slot] = node_idx
    ep.alive[slot] = True
    ep.deleted[slot] = pod.deletion_timestamp is not None
    return ep


def existing_pods_add_bulk(ep: ExistingPodTensors, pods: Sequence[api.Pod],
                           node_idxs: Sequence[int],
                           space: FeatureSpace) -> ExistingPodTensors:
    """Bulk existing_pods_add: one growth pass + vectorized row writes.
    Label-column ids are memoized per pod template (controller-stamped pods
    share labels)."""
    col_memo: dict = {}

    def label_cols(pod: api.Pod) -> list[int]:
        mk = getattr(pod, "_tpl_key", None) \
            or (pod.namespace, tuple(sorted(pod.labels.items())))
        cl = col_memo.get(mk)
        if cl is None:
            cl = []
            for k, v in pod.labels.items():
                cl.append(space.pod_labels.kv_id(k, v))
                cl.append(space.pod_labels.key_id(k))
            col_memo[mk] = cl
        return cl

    for pod in pods:
        if pod.labels:
            label_cols(pod)  # intern before growth
    ep.labels = _grow_cols(ep.labels, space.pod_labels.capacity)
    need = sum(1 for p in pods if p.key not in ep.key_to_slot)
    while len(ep.free_slots) < need:
        m = len(ep.keys)
        ep.labels = np.concatenate([ep.labels, np.zeros_like(ep.labels)], 0)
        ep.ns_id = np.concatenate([ep.ns_id, np.zeros(m, np.int32)])
        ep.node_idx = np.concatenate([ep.node_idx, np.full(m, -1, np.int32)])
        ep.alive = np.concatenate([ep.alive, np.zeros(m, bool)])
        ep.deleted = np.concatenate([ep.deleted, np.zeros(m, bool)])
        ep.keys += [None] * m
        ep.free_slots.extend(range(2 * m - 1, m - 1, -1))
    slots = np.empty(len(pods), np.int64)
    for i, pod in enumerate(pods):
        slot = ep.key_to_slot.get(pod.key)
        if slot is None:
            slot = ep.free_slots.pop()
            ep.key_to_slot[pod.key] = slot
            ep.keys[slot] = pod.key
        slots[i] = slot
    ep.labels[slots] = False
    rows, cols = [], []
    for i, pod in enumerate(pods):
        if pod.labels:
            cl = label_cols(pod)
            cols.extend(cl)
            rows.extend([slots[i]] * len(cl))
    if rows:
        ep.labels[rows, cols] = True
    ep.ns_id[slots] = [space.namespaces.id(p.namespace) for p in pods]
    ep.node_idx[slots] = np.asarray(node_idxs, np.int64)
    ep.alive[slots] = True
    ep.deleted[slots] = [p.deletion_timestamp is not None for p in pods]
    return ep


def existing_pods_remove(ep: ExistingPodTensors, pod_key: str) -> ExistingPodTensors:
    slot = ep.key_to_slot.pop(pod_key, None)
    if slot is not None:
        ep.alive[slot] = False
        ep.node_idx[slot] = -1
        ep.keys[slot] = None
        ep.free_slots.append(slot)
    return ep
