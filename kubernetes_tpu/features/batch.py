"""Pod batch compilation: a pending queue -> dense [P, ...] tensors plus
deduplicated selector-group tables.

Pods from the same controller share identical node selectors / affinity /
service membership, so per-pod selector evaluation is deduplicated into G
small "groups"; the per-group [G, N] tables are computed once per batch and
gathered per pod on device.  This is the batched analogue of the reference's
per-pod ``predicateMetadata`` precompute (predicates.go:70-98).

Group tables are built host-side in vectorized numpy over the node label
multi-hot matrix; the [P, N] hot path stays on TPU.  For the sequential
device solver, spreading state is carried as (per-node counts [S,N],
per-zone counts [S,Z]) together with an in-batch increment matrix [P,S]
saying which groups' counts grow when pod ``i`` lands.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import numpy as np

from kubernetes_tpu.api import types as api
from kubernetes_tpu.features import compiler as fc
from kubernetes_tpu.features.affinity import (AffinityTensors,
                                              ResidentAffinity,
                                              compile_affinity)
from kubernetes_tpu.features.padcap import (pad_rows_pow2 as _pad_rows_pow2,
                                            pow2 as _pow2)
from kubernetes_tpu.features.volumes import (VolSvcTensors, compile_volsvc,
                                             empty_volsvc)
from kubernetes_tpu.utils.trace import stage


@dataclass
class PodBatch:
    """Dense per-pod features for one scheduling batch."""

    pods: list[api.Pod]
    request: np.ndarray        # [P, 4] int32
    zero_request: np.ndarray   # [P] bool — cpu==mem==gpu==0 (predicates.go:463)
    nonzero: np.ndarray        # [P, 2] int32
    best_effort: np.ndarray    # [P] bool
    host_idx: np.ndarray       # [P] int32: -1 no constraint, -2 unknown node name
    ports: np.ndarray          # [P, PortCap] bool
    vol_ro: np.ndarray         # [P, VolCap] bool — read-only conflict tokens
    vol_rw: np.ndarray         # [P, VolCap] bool — writable conflict tokens
    tol_nosched: np.ndarray    # [P, TaintCap] bool — vocab taints tolerated
    tol_prefer: np.ndarray     # [P, TaintCap] bool — PreferNoSchedule tolerated
    has_tolerations: np.ndarray  # [P] bool — pod declares any toleration
    images: np.ndarray         # [P, ImgCap] int32 — per-container multiplicity
    sel_group: np.ndarray      # [P] int32 into selector group tables
    sel_required: np.ndarray   # [G, N] bool — nodeSelector+required affinity
    sel_pref_counts: np.ndarray  # [G, N] int32 — preferred-term weight sums
    spread_group: np.ndarray   # [P] int32 into spread tables
    spread_node_counts: np.ndarray  # [S, N] f32 — matching pods per node
    spread_zone_counts: np.ndarray  # [S, Z] f32 — matching pods per zone
    spread_has_zones: np.ndarray    # [S] bool — haveZones for the group
    spread_incr: np.ndarray    # [P, S] bool — placing pod i increments group s
    node_zone_id: np.ndarray   # [N] int32 — compact zone id, -1 = no zone
    avoid_group: np.ndarray    # [P] int32 — controller-signature group
    avoid_rows: np.ndarray     # [G, N] bool — NodePreferAvoidPods hit
    nz_tmpl_idx: np.ndarray    # [P] int32 into nz_templates
    nz_templates: np.ndarray   # [T, 2] int32 distinct nonzero rows
    #                            (T=0: above cap, in-scan score path)
    aff: AffinityTensors       # inter-pod (anti-)affinity sig tables
    volsvc: VolSvcTensors      # volume counts/zones + service (anti-)affinity

    @property
    def p(self) -> int:
        return len(self.pods)


def _term_mask(term: api.NodeSelectorTerm, nt: fc.NodeTensors,
               space: fc.FeatureSpace,
               nodes: Optional[Sequence[api.Node]]) -> np.ndarray:
    """[N] bool — one NodeSelectorTerm (AND of exprs), per labels.Requirement
    semantics (pkg/labels/selector.go).  Empty/invalid exprs match nothing
    (predicates.go:520-525, :495)."""
    n = nt.labels.shape[0]
    if not term.match_expressions:
        return np.zeros(n, bool)
    mask = np.ones(n, bool)
    for e in term.match_expressions:
        if e.operator == api.NS_OP_IN:
            ids = [space.labels.kv_get(e.key, v) for v in e.values]
            ids = [i for i in ids if i >= 0]
            sat = nt.labels[:, ids].any(1) if ids else np.zeros(n, bool)
        elif e.operator == api.NS_OP_NOT_IN:
            ids = [space.labels.kv_get(e.key, v) for v in e.values]
            ids = [i for i in ids if i >= 0]
            sat = ~nt.labels[:, ids].any(1) if ids else np.ones(n, bool)
        elif e.operator == api.NS_OP_EXISTS:
            kid = space.labels.key_get(e.key)
            sat = nt.labels[:, kid] if kid >= 0 else np.zeros(n, bool)
        elif e.operator == api.NS_OP_DOES_NOT_EXIST:
            kid = space.labels.key_get(e.key)
            sat = ~nt.labels[:, kid] if kid >= 0 else np.ones(n, bool)
        elif e.operator in (api.NS_OP_GT, api.NS_OP_LT) and nodes is not None:
            # Numeric compare on the raw label value (rare; host loop).
            sat = np.zeros(n, bool)
            if len(e.values) != 1:
                return np.zeros(n, bool)
            try:
                rhs = int(e.values[0])
            except ValueError:
                return np.zeros(n, bool)  # invalid selector matches nothing
            for i, node in enumerate(nodes):
                val = node.labels.get(e.key)
                if val is not None:
                    try:
                        sat[i] = (int(val) > rhs) if e.operator == api.NS_OP_GT \
                            else (int(val) < rhs)
                    except ValueError:
                        pass
        else:
            return np.zeros(n, bool)  # unknown operator: selector parse error
        mask &= sat
    return mask


def _selector_set_mask(sel: dict[str, str], nt: fc.NodeTensors,
                       space: fc.FeatureSpace) -> np.ndarray:
    """[N] bool — labels.SelectorFromSet(map): AND over key=value pairs."""
    n = nt.labels.shape[0]
    mask = np.ones(n, bool)
    for k, v in sel.items():
        kv = space.labels.kv_get(k, v)
        mask &= nt.labels[:, kv] if kv >= 0 else np.zeros(n, bool)
    return mask


def required_node_mask(pod: api.Pod, nt: fc.NodeTensors, space: fc.FeatureSpace,
                       nodes: Optional[Sequence[api.Node]] = None) -> np.ndarray:
    """[N] bool — podMatchesNodeLabels (predicates.go:504-554):
    spec.nodeSelector AND required node affinity."""
    mask = _selector_set_mask(pod.node_selector, nt, space)
    aff = pod.affinity()
    if aff is not None and aff.node_affinity is not None \
            and aff.node_affinity.required is not None:
        terms = aff.node_affinity.required.node_selector_terms
        tmask = np.zeros(nt.labels.shape[0], bool)  # empty terms match nothing
        for t in terms:
            tmask |= _term_mask(t, nt, space, nodes)
        mask &= tmask
    return mask


def preferred_count_row(pod: api.Pod, nt: fc.NodeTensors, space: fc.FeatureSpace,
                        nodes: Optional[Sequence[api.Node]] = None) -> np.ndarray:
    """[N] int32 — sum of preferred-term weights matching each node
    (node_affinity.go:32-65).  Zero-weight terms skipped."""
    n = nt.labels.shape[0]
    counts = np.zeros(n, np.int32)
    aff = pod.affinity()
    if aff is not None and aff.node_affinity is not None:
        for term in aff.node_affinity.preferred:
            if term.weight == 0:
                continue
            counts += term.weight * _term_mask(term.preference, nt, space, nodes)
    return counts


def _label_selector_match_mask(sel: api.LabelSelector, labels_mh: np.ndarray,
                               space: fc.FeatureSpace) -> np.ndarray:
    """[M] bool — LabelSelector vs each existing pod's label multi-hot (pod-label vocab)."""
    m = labels_mh.shape[0]
    mask = np.ones(m, bool)
    for k, v in sel.match_labels:
        kv = space.pod_labels.kv_get(k, v)
        mask &= labels_mh[:, kv] if kv >= 0 else np.zeros(m, bool)
    for e in sel.match_expressions:
        if e.operator == "In":
            ids = [space.pod_labels.kv_get(e.key, v) for v in e.values]
            ids = [i for i in ids if i >= 0]
            mask &= labels_mh[:, ids].any(1) if ids else np.zeros(m, bool)
        elif e.operator == "NotIn":
            ids = [space.pod_labels.kv_get(e.key, v) for v in e.values]
            ids = [i for i in ids if i >= 0]
            if ids:
                mask &= ~labels_mh[:, ids].any(1)
        elif e.operator == "Exists":
            kid = space.pod_labels.key_get(e.key)
            mask &= labels_mh[:, kid] if kid >= 0 else np.zeros(m, bool)
        elif e.operator == "DoesNotExist":
            kid = space.pod_labels.key_get(e.key)
            if kid >= 0:
                mask &= ~labels_mh[:, kid]
        else:
            return np.zeros(m, bool)
    return mask


def _selector_matches_pod_labels(sel, labels: dict[str, str]) -> bool:
    if isinstance(sel, dict):
        return bool(sel) and all(labels.get(k) == v for k, v in sel.items())
    if isinstance(sel, api.LabelSelector):
        return sel.matches(labels)
    return False


def pod_template_key(pod: api.Pod) -> tuple:
    """Equivalence-class key: every field compile_batch/compile_affinity
    reads, except the pod's identity (name/uid).  Controller-stamped pods
    share one key, so per-pod feature rows compile once per template — the
    batched analogue of the reference's per-pod predicateMetadata memo
    (predicates.go:71-98) extended across pods, exploiting that a
    controller's pods are spec-identical.  Cached on the pod (specs are
    immutable once submitted)."""
    k = getattr(pod, "_tpl_key", None)
    if k is not None:
        return k
    ann = pod.annotations
    lab = pod.labels
    nsel = pod.node_selector
    k = (
        pod.namespace, pod.node_name, pod.deletion_timestamp is not None,
        tuple(sorted(lab.items())) if len(lab) > 1 else tuple(lab.items()),
        tuple(sorted(nsel.items())) if len(nsel) > 1 else tuple(nsel.items()),
        (ann.get(api.AFFINITY_ANNOTATION_KEY, ""),
         ann.get(api.TOLERATIONS_ANNOTATION_KEY, "")) if ann else ("", ""),
        tuple((c.image,
               tuple(sorted((k_, str(v)) for k_, v in c.requests.items())),
               tuple(sorted(c.limits)),
               tuple(p.host_port for p in c.ports if p.host_port))
              for c in pod.containers),
        tuple((v.gce_pd_name, v.gce_read_only, v.aws_ebs_id, v.aws_read_only,
               v.rbd_key, v.rbd_read_only, v.iscsi_key, v.iscsi_read_only,
               v.nfs_key, v.nfs_read_only, v.pvc_claim_name)
              for v in pod.volumes) if pod.volumes else (),
    )
    pod._tpl_key = k
    return k


# Lister signature: pod -> list of selector objects (dict for services/RCs,
# LabelSelector for ReplicaSets) matching it.
SpreadSelectors = Callable[[api.Pod], list]
# Lister: pod -> list of controller UIDs as ("ReplicationController"|"ReplicaSet", uid).
ControllerRefs = Callable[[api.Pod], list]


def _node_zone_ids(nt: fc.NodeTensors, space: fc.FeatureSpace) -> np.ndarray:
    """Compact per-batch zone ids from GetZoneKey (region+zone labels)."""
    n = nt.n
    zone_col = space.topo_keys.get(api.ZONE_LABEL)
    region_col = space.topo_keys.get(api.REGION_LABEL)
    zv = nt.topo_val[:, zone_col] if zone_col >= 0 else np.full(n, -1)
    rv = nt.topo_val[:, region_col] if region_col >= 0 else np.full(n, -1)
    has = (zv >= 0) | (rv >= 0)
    packed = (rv.astype(np.int64) + 1) * (len(space.topo_vals) + 2) + zv + 1
    packed = np.where(has, packed, -1)
    ids = np.full(n, -1, np.int32)
    if has.any():
        _, inv = np.unique(packed[has], return_inverse=True)
        ids[has] = inv.astype(np.int32)
    return ids


_DEFAULT_NZ_ROW: Optional[np.ndarray] = None


def _default_nz_row() -> np.ndarray:
    """[2] int32 — the nonzero row of a request-less pod, computed once
    through ``fc.pod_nonzero_row`` (the exact encoder pad/inert pods
    use) so the always-present template row can never diverge from what
    a pad pod actually contributes."""
    global _DEFAULT_NZ_ROW
    if _DEFAULT_NZ_ROW is None:
        _DEFAULT_NZ_ROW = fc.pod_nonzero_row(
            api.Pod(name="__nz-default", namespace="__nz__"))
    return _DEFAULT_NZ_ROW


def compile_batch(pods: Sequence[api.Pod], nt: fc.NodeTensors,
                  space: fc.FeatureSpace,
                  ep: Optional[fc.ExistingPodTensors] = None,
                  nodes: Optional[Sequence[api.Node]] = None,
                  spread_selectors: Optional[SpreadSelectors] = None,
                  controller_refs: Optional[ControllerRefs] = None,
                  affinity_pods: Sequence[tuple[api.Pod, int]] = (),
                  hard_pod_affinity_weight: int = 1,
                  volsvc: Optional[VolSvcTensors] = None,
                  resident_affinity: Optional[ResidentAffinity] = None
                  ) -> PodBatch:
    """Compile a pending-pod batch against the current node tensors.

    ``resident_affinity``: the cache's kept affinity planes; with them
    ``affinity_pods`` is not needed (compile_affinity).

    ``volsvc``: precompiled volume/service tables (compile_volsvc); a
    neutral all-pass table is built when omitted."""
    p = len(pods)
    n = nt.n

    # Group the batch into spec-identical templates; all per-pod rows are
    # compiled once per template and gathered back to [P, ...] at the end.
    tpl_of: dict[tuple, int] = {}
    reps: list[api.Pod] = []
    tpl_idx = np.empty(p, np.int64)
    for i, pod in enumerate(pods):
        k = pod_template_key(pod)
        ti = tpl_of.get(k)
        if ti is None:
            ti = len(reps)
            tpl_of[k] = ti
            reps.append(pod)
        tpl_idx[i] = ti
    t = len(reps)

    # Intern everything first so capacities are final.
    for pod in reps:
        for port in pod.used_host_ports():
            space.ports.id(str(port))
        for v in pod.volumes:
            for token, _ in fc.FeatureSpace.volume_tokens(v):
                space.volumes.id(token)
        for c in pod.containers:
            if c.image:
                space.images.id(c.image)

    request = np.zeros((t, 4), np.int32)
    nonzero = np.zeros((t, 2), np.int32)
    zero_req = np.zeros(t, bool)
    best_effort = np.zeros(t, bool)
    host_idx = np.full(t, -1, np.int32)
    ports = np.zeros((t, space.ports.capacity), bool)
    vol_ro = np.zeros((t, space.volumes.capacity), bool)
    vol_rw = np.zeros((t, space.volumes.capacity), bool)
    tol_ns = np.zeros((t, space.taints.capacity), bool)
    tol_pref = np.zeros((t, space.taints.capacity), bool)
    has_tols = np.zeros(t, bool)
    images = np.zeros((t, space.images.capacity), np.int32)
    avoid_group = np.zeros(t, np.int32)
    avoid_rows_map: dict = {(): 0}
    avoid_rows: list[np.ndarray] = [np.zeros(n, bool)]

    # Parse the taint vocabulary once; every pod's tolerations are matched
    # against it host-side, turning device-side toleration checks into a
    # single untolerated-taints contraction.
    vocab_taints = []
    for tok in space.taints.tokens():
        kv, _, effect = tok.rpartition(":")
        key, _, value = kv.partition("=")
        vocab_taints.append(api.Taint(key=key, value=value, effect=effect))

    # Node avoid-annotation entries, parsed once: node -> set of
    # (kind, uid) controller signatures (GetAvoidPodsFromNodeAnnotations).
    node_avoids: list[set] = []
    if controller_refs is not None and nodes is not None:
        import json as _json
        for node in nodes:
            entries = set()
            raw = node.annotations.get(api.PREFER_AVOID_PODS_ANNOTATION_KEY, "")
            if raw:
                try:
                    d = _json.loads(raw)
                    for e in d.get("preferAvoidPods") or ():
                        pc = (e.get("podSignature") or {}).get("podController") or {}
                        entries.add((pc.get("kind", ""), pc.get("uid", "")))
                except (ValueError, AttributeError):
                    pass
            node_avoids.append(entries)

    sel_sig_to_group: dict = {}
    sel_rows: list[np.ndarray] = []
    pref_rows: list[np.ndarray] = []
    sel_group = np.zeros(t, np.int32)
    # Lister lookups memoized by (namespace, labels): controller-stamped
    # pods share both, and the listers answer from labels alone.
    _sel_memo: dict = {}
    _ref_memo: dict = {}

    node_zone_id = _node_zone_ids(nt, space)
    num_zones = int(node_zone_id.max()) + 1 if (node_zone_id >= 0).any() else 0
    # haveZones iff some READY node carries zone info (the reference's
    # countsByZone only sees the ready node list, selector_spreading.go:121).
    any_zones = bool(((node_zone_id >= 0) & nt.schedulable).any())

    spread_sig_to_group: dict = {}
    spread_groups_meta: list[tuple[str, list]] = []  # (namespace, selectors)
    spread_node_rows: list[np.ndarray] = []
    spread_zone_rows: list[np.ndarray] = []
    spread_has_zone: list[bool] = []
    spread_group = np.zeros(t, np.int32)

    for i, pod in enumerate(reps):
        request[i] = fc.pod_resource_row(pod)
        nonzero[i] = fc.pod_nonzero_row(pod)
        zero_req[i] = not (request[i, 0] or request[i, 1] or request[i, 2])
        best_effort[i] = pod.is_best_effort()
        if pod.node_name:
            host_idx[i] = nt.name_to_idx.get(pod.node_name, -2)
        for port in pod.used_host_ports():
            ports[i, space.ports.id(str(port))] = True
        for v in pod.volumes:
            for token, ro in fc.FeatureSpace.volume_tokens(v):
                (vol_ro if ro else vol_rw)[i, space.volumes.id(token)] = True
        tols = pod.tolerations()
        has_tols[i] = len(tols) > 0
        pref_tols = [t for t in tols if not t.effect
                     or t.effect == api.TAINT_EFFECT_PREFER_NO_SCHEDULE]
        for ti, taint in enumerate(vocab_taints):
            tol_ns[i, ti] = taint.tolerated_by(tols)
            tol_pref[i, ti] = taint.tolerated_by(pref_tols)
        for c in pod.containers:
            if c.image:
                images[i, space.images.id(c.image)] += 1

        # NodePreferAvoidPods: mark nodes whose annotation lists one of the
        # pod's controllers (priorities.go:326-398), deduped by controller
        # signature so the [P, N] plane is a gather of few [N] rows.
        if controller_refs is not None and nodes is not None:
            lkey = (pod.namespace, tuple(sorted(pod.labels.items())))
            refs = _ref_memo.get(lkey)
            if refs is None:
                refs = _ref_memo[lkey] = tuple(controller_refs(pod))
            g = avoid_rows_map.get(refs)
            if g is None:
                row = np.zeros(n, bool)
                for ni, avoids in enumerate(node_avoids):
                    if any(r in avoids for r in refs):
                        row[ni] = True
                g = avoid_rows_map[refs] = len(avoid_rows)
                avoid_rows.append(row)
            avoid_group[i] = g

        # Selector group (nodeSelector + node affinity).
        aff = pod.affinity()
        na = aff.node_affinity if aff else None
        sig = (tuple(sorted(pod.node_selector.items())), na)
        g = sel_sig_to_group.get(sig)
        if g is None:
            g = len(sel_rows)
            sel_sig_to_group[sig] = g
            sel_rows.append(required_node_mask(pod, nt, space, nodes))
            pref_rows.append(preferred_count_row(pod, nt, space, nodes))
        sel_group[i] = g

        # Spread group (services/RCs/RSs selecting this pod), if listers given.
        # Pad rows (the stream drain's inert "__pad__" fill) must not mint
        # a group: their distinct namespace would otherwise change S only
        # on drains that happen to need padding — a new compiled shape for
        # identical real content.
        if spread_selectors is not None and ep is not None \
                and pod.namespace != "__pad__":
            lkey = (pod.namespace, tuple(sorted(pod.labels.items())))
            sels = _sel_memo.get(lkey)
            if sels is None:
                sels = _sel_memo[lkey] = spread_selectors(pod)
            ssig = (pod.namespace, tuple(sorted(repr(s) for s in sels)))
            sg = spread_sig_to_group.get(ssig)
            if sg is None:
                sg = len(spread_node_rows)
                spread_sig_to_group[ssig] = sg
                spread_groups_meta.append((pod.namespace, sels))
                ncounts, zcounts = _spread_counts(
                    pod.namespace, sels, ep, space, n, node_zone_id, num_zones,
                    nt.schedulable)
                spread_node_rows.append(ncounts)
                spread_zone_rows.append(zcounts)
                spread_has_zone.append(any_zones and len(sels) > 0)
            spread_group[i] = sg

    # Content-sized group axes are padded to powers of two (padcap's
    # bucketing discipline): live batches vary these counts freely (every
    # new selector signature, spread group, or avoid signature would
    # otherwise be a fresh compiled shape).  Padding rows are never
    # referenced by any pod index: sel pad rows are all-ones ("no
    # constraint"), the rest zeros.
    G = _pow2(len(sel_rows))
    sel_required = np.ones((G, n), bool)
    if sel_rows:
        sel_required[:len(sel_rows)] = np.stack(sel_rows)
    sel_pref = np.zeros((G, n), np.int32)
    if pref_rows:
        sel_pref[:len(pref_rows)] = np.stack(pref_rows)
    S = _pow2(len(spread_node_rows))
    Z = max(num_zones, 1)
    sp_n = np.zeros((S, n), np.float32)
    if spread_node_rows:
        sp_n[:len(spread_node_rows)] = np.stack(spread_node_rows)
    sp_z = np.zeros((S, Z), np.float32)
    if spread_zone_rows:
        sp_z[:len(spread_zone_rows)] = np.stack(spread_zone_rows)
    sp_hz = np.zeros(S, bool)
    if spread_has_zone:
        sp_hz[:len(spread_has_zone)] = spread_has_zone

    # In-batch increments: once pod i is placed it becomes an "existing pod"
    # for every later pod in the batch (the reference sees it via the assumed-
    # pod cache, cache.go:107).
    spread_incr = np.zeros((t, S), bool)
    if spread_groups_meta:
        for i, pod in enumerate(reps):
            if pod.deletion_timestamp is not None:
                continue
            for s, (ns, sels) in enumerate(spread_groups_meta):
                if ns == pod.namespace and any(
                        _selector_matches_pod_labels(sel, pod.labels)
                        for sel in sels):
                    spread_incr[i, s] = True

    # Stamp the parsed/compiled per-pod caches from each pod's template rep
    # so the assume path (cache.assume_pods -> aggregate updates) never
    # re-parses quantities or affinity JSON for controller-stamped pods.
    for pod, ti in zip(pods, tpl_idx.tolist()):
        rep = reps[ti]
        if rep is not pod:
            pod._res_row = rep._res_row
            pod._nz_row = rep._nz_row
            pod._affinity = rep._affinity
            pod._affinity_parsed = True

    with stage("compile.affinity"):
        aff = compile_affinity(pods, affinity_pods, ep, nodes, n, space,
                               hard_pod_affinity_weight,
                               reps=reps, tpl_idx=tpl_idx,
                               resident=resident_affinity)
    if volsvc is None:
        if nodes is not None:
            volsvc = compile_volsvc(pods, nodes, nt.schedulable)
        else:
            volsvc = empty_volsvc(p, n)

    # Nonzero-request templates for the scan's template-factored
    # score planes (engine/solver.py _solve_scan): the distinct nonzero
    # rows, pow2-row-padded (padcap's "b_nztmpl" axis keeps the bucket
    # monotonic across batches).  Above the cap the table compiles away
    # (shape 0) and the scan keeps its in-step score path.
    from kubernetes_tpu.engine.solver import DYN_TEMPLATE_CAP
    # The default nonzero row (a request-less pod's non_zero_request) is
    # ALWAYS in the table: chunk/gang pad pods carry exactly it, and a
    # live padded batch must not grow the template table past what the
    # prewarm batches (which are never padded) traced — that cap bump
    # minted an unwarmed scan shape on the wire clock.  Derived through
    # the SAME row encoder the pad pods go through (not re-derived
    # constants), so the two can never diverge.
    nz_uniq, nz_inv = np.unique(
        np.concatenate([nonzero, _default_nz_row()[None]]), axis=0,
        return_inverse=True)
    if 0 < len(nz_uniq) <= DYN_TEMPLATE_CAP:
        # Row floor of 8 bounds tiny-batch wobble to one shape.
        rows = max(_pow2(len(nz_uniq)), 8)
        nz_templates = np.zeros((rows, 2), np.int32)
        nz_templates[:len(nz_uniq)] = nz_uniq
        nz_tmpl_idx = nz_inv[:-1].astype(np.int32)[tpl_idx]
    else:
        nz_templates = np.zeros((0, 2), np.int32)
        nz_tmpl_idx = np.zeros(p, np.int32)

    return PodBatch(
        pods=list(pods), request=request[tpl_idx],
        zero_request=zero_req[tpl_idx], nonzero=nonzero[tpl_idx],
        best_effort=best_effort[tpl_idx], host_idx=host_idx[tpl_idx],
        ports=ports[tpl_idx],
        vol_ro=vol_ro[tpl_idx], vol_rw=vol_rw[tpl_idx],
        tol_nosched=tol_ns[tpl_idx], tol_prefer=tol_pref[tpl_idx],
        has_tolerations=has_tols[tpl_idx],
        images=images[tpl_idx], sel_group=sel_group[tpl_idx],
        sel_required=sel_required, sel_pref_counts=sel_pref,
        spread_group=spread_group[tpl_idx],
        spread_node_counts=sp_n, spread_zone_counts=sp_z,
        spread_has_zones=sp_hz, spread_incr=spread_incr[tpl_idx],
        node_zone_id=node_zone_id, avoid_group=avoid_group[tpl_idx],
        avoid_rows=_pad_rows_pow2(np.stack(avoid_rows)),
        nz_tmpl_idx=nz_tmpl_idx, nz_templates=nz_templates,
        aff=aff, volsvc=volsvc)


def _spread_counts(namespace: str, selectors: list,
                   ep: fc.ExistingPodTensors, space: fc.FeatureSpace,
                   n: int, node_zone_id: np.ndarray, num_zones: int,
                   schedulable: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """SelectorSpread count phase (selector_spreading.go:89-135): count
    existing same-namespace, non-deleted pods matching ANY selector, per node
    and per zone.  Only ready nodes are iterated by the reference, so
    non-schedulable nodes' pods never enter the node or zone counts."""
    Z = max(num_zones, 1)
    if not selectors:
        return np.zeros(n, np.float32), np.zeros(Z, np.float32)
    ns = space.namespaces.get(namespace)
    cand = ep.alive & ~ep.deleted & (ep.ns_id == ns) & (ep.node_idx >= 0)
    match = np.zeros(len(cand), bool)
    for sel in selectors:
        if isinstance(sel, dict):
            if not sel:
                continue  # empty map selector selects nothing
            m = np.ones(len(cand), bool)
            for k, v in sel.items():
                kv = space.pod_labels.kv_get(k, v)
                m &= ep.labels[:, kv] if kv >= 0 else False
            match |= m
        elif isinstance(sel, api.LabelSelector):
            match |= _label_selector_match_mask(sel, ep.labels, space)
    match &= cand
    node_counts = np.bincount(ep.node_idx[match], minlength=n).astype(np.float32)[:n]
    node_counts = np.where(schedulable, node_counts, 0.0).astype(np.float32)
    zone_counts = np.zeros(Z, np.float32)
    if num_zones > 0:
        zmask = node_zone_id >= 0
        zone_counts[:num_zones] = np.bincount(
            node_zone_id[zmask], weights=node_counts[zmask],
            minlength=num_zones)[:num_zones]
    return node_counts, zone_counts
