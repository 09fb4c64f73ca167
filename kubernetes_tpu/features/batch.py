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
from kubernetes_tpu.features.padcap import pow2 as _pow2
from kubernetes_tpu.features.plan import FeaturePlan, Fleet, Meta, keep
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


# The inert fill of a padded launch is ONE pod: ``pad_pods`` repeats this
# object at the tail, so a launch neither constructs nor keys its ~200
# pad rows, and ``compile_batch`` walks the live prefix only.  Nothing
# reads a pad's name after the compile.
PAD_POD = api.Pod(name="__pad__", namespace="__pad__")


def pad_pods(pods: Sequence[api.Pod], to: int) -> list[api.Pod]:
    """``pods`` followed by the inert fill up to ``to`` rows."""
    return list(pods) + [PAD_POD] * (to - len(pods))


def _fleet_tables(nt: fc.NodeTensors, space: fc.FeatureSpace) -> Fleet:
    """The node-side tables every template row is compiled against."""
    node_zone_id = _node_zone_ids(nt, space)
    num_zones = int(node_zone_id.max()) + 1 if (node_zone_id >= 0).any() else 0
    # haveZones iff some READY node carries zone info (the reference's
    # countsByZone only sees the ready node list, selector_spreading.go:121).
    any_zones = bool(((node_zone_id >= 0) & nt.schedulable).any())
    # Parse the taint vocabulary once; every pod's tolerations are matched
    # against it host-side, turning device-side toleration checks into a
    # single untolerated-taints contraction.
    vocab_taints = []
    for tok in space.taints.tokens():
        kv, _, effect = tok.rpartition(":")
        key, _, value = kv.partition("=")
        vocab_taints.append(api.Taint(key=key, value=value, effect=effect))
    return Fleet(keep(node_zone_id), num_zones, any_zones, vocab_taints)


def _node_avoids(nodes: Sequence[api.Node]) -> Sequence[set]:
    """Node avoid-annotation entries: node -> set of (kind, uid)
    controller signatures (GetAvoidPodsFromNodeAnnotations); ``()`` when
    no node carries the annotation."""
    import json as _json
    out: list[set] = []
    found = False
    for node in nodes:
        entries: set = set()
        raw = node.annotations.get(api.PREFER_AVOID_PODS_ANNOTATION_KEY, "") \
            if node.annotations else ""
        if raw:
            found = True
            try:
                d = _json.loads(raw)
                for e in d.get("preferAvoidPods") or ():
                    pc = (e.get("podSignature") or {}).get("podController") or {}
                    entries.add((pc.get("kind", ""), pc.get("uid", "")))
            except (ValueError, AttributeError):
                pass
        out.append(entries)
    return out if found else ()


def _compile_template(plan: FeaturePlan, key: tuple, pod: api.Pod,
                      nt: fc.NodeTensors, space: fc.FeatureSpace,
                      nodes: Optional[Sequence[api.Node]],
                      fleet: Fleet) -> int:
    """One template's pod-side rows into a new slot of the plan, and its
    selector signature's node rows when the plan lacks them.  Everything
    that parses (and so can raise) comes before the slot is taken."""
    request = fc.pod_resource_row(pod)
    nonzero = fc.pod_nonzero_row(pod)
    tols = pod.tolerations()
    pref_tols = [t for t in tols if not t.effect
                 or t.effect == api.TAINT_EFFECT_PREFER_NO_SCHEDULE]
    # Selector group (nodeSelector + node affinity).
    aff = pod.affinity()
    na = aff.node_affinity if aff else None
    sig = (tuple(sorted(pod.node_selector.items())), na)
    if sig not in plan.sel:
        plan.sel[sig] = (required_node_mask(pod, nt, space, nodes),
                         preferred_count_row(pod, nt, space, nodes))
    slot = plan.add(key, Meta(
        sel_sig=sig, lkey=(key[0], key[3]), namespace=pod.namespace,
        labels=pod.labels, deleted=pod.deletion_timestamp is not None,
        nz=(int(nonzero[0]), int(nonzero[1])),
        res_row=request, nz_row=nonzero, affinity=aff))
    tab = plan.tables
    tab.request[slot] = request
    tab.nonzero[slot] = nonzero
    tab.zero_req[slot] = not (request[0] or request[1] or request[2])
    tab.best_effort[slot] = pod.is_best_effort()
    if pod.node_name:
        tab.host_idx[slot] = nt.name_to_idx.get(pod.node_name, -2)
    for port in pod.used_host_ports():
        tab.ports[slot, space.ports.id(str(port))] = True
    for v in pod.volumes:
        for token, ro in fc.FeatureSpace.volume_tokens(v):
            (tab.vol_ro if ro else tab.vol_rw)[
                slot, space.volumes.id(token)] = True
    tab.has_tols[slot] = len(tols) > 0
    for ti, taint in enumerate(fleet.vocab_taints):
        tab.tol_ns[slot, ti] = taint.tolerated_by(tols)
        tab.tol_pref[slot, ti] = taint.tolerated_by(pref_tols)
    for c in pod.containers:
        if c.image:
            tab.images[slot, space.images.id(c.image)] += 1
    return slot


def compile_batch(pods: Sequence[api.Pod], nt: fc.NodeTensors,
                  space: fc.FeatureSpace,
                  ep: Optional[fc.ExistingPodTensors] = None,
                  nodes: Optional[Sequence[api.Node]] = None,
                  spread_selectors: Optional[SpreadSelectors] = None,
                  controller_refs: Optional[ControllerRefs] = None,
                  affinity_pods: Sequence[tuple[api.Pod, int]] = (),
                  hard_pod_affinity_weight: int = 1,
                  volsvc: Optional[VolSvcTensors] = None,
                  resident_affinity: Optional[ResidentAffinity] = None,
                  plan: Optional[FeaturePlan] = None
                  ) -> PodBatch:
    """Compile a pending-pod batch against the current node tensors.

    ``resident_affinity``: the cache's kept affinity planes; with them
    ``affinity_pods`` is not needed (compile_affinity).

    ``volsvc``: precompiled volume/service tables (compile_volsvc); a
    neutral all-pass table is built when omitted.

    ``plan``: the tables kept between launches (features/plan.py), valid
    for these node tensors (``FeaturePlan.begin``); what is there is
    looked up, what is not is built and kept.  Without one the call
    builds everything from nothing in a plan of its own: one path, and
    the two give the same batch to the element."""
    if plan is None:
        plan = FeaturePlan()
    p = len(pods)
    n = nt.n

    # Group the batch into spec-identical templates; all per-pod rows are
    # compiled once per template and gathered back to [P, ...] at the end.
    # The inert fill at the tail is one object: counted, not walked.
    n_live = p
    while n_live and pods[n_live - 1] is PAD_POD:
        n_live -= 1
    tpl_of: dict[tuple, int] = {}
    reps: list[api.Pod] = []
    keys: list[tuple] = []
    tpl_list: list[int] = []
    for pod in (pods if n_live == p else pods[:n_live]):
        k = pod_template_key(pod)
        ti = tpl_of.get(k)
        if ti is None:
            ti = tpl_of[k] = len(reps)
            reps.append(pod)
            keys.append(k)
        tpl_list.append(ti)
    if n_live < p:
        k = pod_template_key(PAD_POD)
        ti = tpl_of.get(k)
        if ti is None:
            ti = tpl_of[k] = len(reps)
            reps.append(PAD_POD)
            keys.append(k)
        tpl_idx = np.array(tpl_list + [ti] * (p - n_live), np.int64)
    else:
        tpl_idx = np.array(tpl_list, np.int64)
    t = len(reps)

    # Intern the new templates' tokens first so capacities are final (a
    # kept template's are interned already).
    known = plan.slots
    for k, pod in zip(keys, reps):
        if k in known:
            continue
        for port in pod.used_host_ports():
            space.ports.id(str(port))
        for v in pod.volumes:
            for token, _ in fc.FeatureSpace.volume_tokens(v):
                space.volumes.id(token)
        for c in pod.containers:
            if c.image:
                space.images.id(c.image)
    plan.check_vocab(space)

    fleet = plan.fleet
    if fleet is None:
        fleet = plan.fleet = _fleet_tables(nt, space)
    node_zone_id, num_zones = fleet.node_zone_id, fleet.num_zones

    slots = []
    for k, pod in zip(keys, reps):
        slot = plan.slots.get(k)
        if slot is None:
            slot = _compile_template(plan, k, pod, nt, space, nodes, fleet)
        slots.append(slot)
    meta = plan.meta

    # Stamp the parsed/compiled per-pod caches from each pod's template
    # so the assume path (cache.assume_pods -> aggregate updates) never
    # re-parses quantities or affinity JSON for controller-stamped pods.
    metas = [meta[s] for s in slots]
    for pod, ti in zip(pods, tpl_list):
        m = metas[ti]
        pod._res_row = m.res_row
        pod._nz_row = m.nz_row
        pod._affinity = m.affinity
        pod._affinity_parsed = True

    # -- the batch's groups: numbered in order of first appearance ---------
    want_avoid = controller_refs is not None and nodes is not None
    want_spread = spread_selectors is not None and ep is not None
    avoid_group = [0] * t
    avoid_of: dict = {(): 0}
    avoid_keys: list = [()]
    sel_group = [0] * t
    sel_of: dict = {}
    sel_keys: list = []
    spread_group = [0] * t
    spread_sig_to_group: dict = {}
    spread_groups_meta: list[tuple[str, list]] = []  # (namespace, selectors)
    # Lister lookups memoized by (namespace, labels): controller-stamped
    # pods share both, and the listers answer from labels alone.  The
    # listers are lists mutated in place, so they are asked every launch
    # and what is kept is keyed by their answer.
    _sel_memo: dict = {}
    _ref_memo: dict = {}
    for i, m in enumerate(metas):
        # NodePreferAvoidPods: mark nodes whose annotation lists one of the
        # pod's controllers (priorities.go:326-398), deduped by controller
        # signature so the [P, N] plane is a gather of few [N] rows.
        if want_avoid:
            refs = _ref_memo.get(m.lkey)
            if refs is None:
                refs = _ref_memo[m.lkey] = tuple(controller_refs(reps[i]))
            g = avoid_of.get(refs)
            if g is None:
                g = avoid_of[refs] = len(avoid_keys)
                avoid_keys.append(refs)
            avoid_group[i] = g

        g = sel_of.get(m.sel_sig)
        if g is None:
            g = sel_of[m.sel_sig] = len(sel_keys)
            sel_keys.append(m.sel_sig)
        sel_group[i] = g

        # Spread group (services/RCs/RSs selecting this pod), if listers given.
        # Pad rows (the stream drain's inert "__pad__" fill) must not mint
        # a group: their distinct namespace would otherwise change S only
        # on drains that happen to need padding — a new compiled shape for
        # identical real content.
        if want_spread and m.namespace != "__pad__":
            sels = _sel_memo.get(m.lkey)
            if sels is None:
                sels = _sel_memo[m.lkey] = spread_selectors(reps[i])
            ssig = (m.namespace, tuple(sorted(repr(s) for s in sels)))
            sg = spread_sig_to_group.get(ssig)
            if sg is None:
                sg = spread_sig_to_group[ssig] = len(spread_groups_meta)
                spread_groups_meta.append((m.namespace, sels))
            spread_group[i] = sg

    # Content-sized group axes are padded to powers of two (padcap's
    # bucketing discipline): live batches vary these counts freely (every
    # new selector signature, spread group, or avoid signature would
    # otherwise be a fresh compiled shape).  Padding rows are never
    # referenced by any pod index: sel pad rows are all-ones ("no
    # constraint"), the rest zeros.
    def sel_stack() -> tuple:
        G = _pow2(len(sel_keys))
        required = np.ones((G, n), bool)
        pref = np.zeros((G, n), np.int32)
        for g, sig in enumerate(sel_keys):
            required[g], pref[g] = plan.sel[sig]
        return keep(required), keep(pref)

    def avoid_stack() -> np.ndarray:
        if want_avoid and fleet.node_avoids is None:
            fleet.node_avoids = _node_avoids(nodes)
        rows = np.zeros((_pow2(len(avoid_keys)), n), bool)
        for g, refs in enumerate(avoid_keys):
            if refs and fleet.node_avoids:
                rows[g] = [any(r in avoids for r in refs)
                           for avoids in fleet.node_avoids]
        return keep(rows)

    sel_required, sel_pref = plan.stack("sel", tuple(sel_keys), sel_stack)
    avoid_rows = plan.stack("avoid", tuple(avoid_keys), avoid_stack)

    S = _pow2(len(spread_groups_meta))
    Z = max(num_zones, 1)
    spread_incr = np.zeros((t, S), bool)
    if any(sels for _ns, sels in spread_groups_meta):
        sp_n = np.zeros((S, n), np.float32)
        sp_z = np.zeros((S, Z), np.float32)
        sp_hz = np.zeros(S, bool)
        for s, (ns, sels) in enumerate(spread_groups_meta):
            sp_n[s], sp_z[s] = _spread_counts(
                ns, sels, ep, space, n, node_zone_id, num_zones,
                nt.schedulable)
            sp_hz[s] = fleet.any_zones and len(sels) > 0
        # In-batch increments: once pod i is placed it becomes an "existing
        # pod" for every later pod in the batch (the reference sees it via
        # the assumed-pod cache, cache.go:107).
        for i, m in enumerate(metas):
            if m.deleted:
                continue
            for s, (ns, sels) in enumerate(spread_groups_meta):
                if ns == m.namespace and any(
                        _selector_matches_pod_labels(sel, m.labels)
                        for sel in sels):
                    spread_incr[i, s] = True
    else:
        # No group selects anything: every count is zero whatever the
        # resident pods are.
        sp_n, sp_z, sp_hz = plan.stack(
            "nospread", (S,),
            lambda: (keep(np.zeros((S, n), np.float32)),
                     keep(np.zeros((S, Z), np.float32)),
                     keep(np.zeros(S, bool))))

    with stage("compile.affinity"):
        aff = compile_affinity(pods, affinity_pods, ep, nodes, n, space,
                               hard_pod_affinity_weight,
                               reps=reps, tpl_idx=tpl_idx,
                               resident=resident_affinity)
    if volsvc is None:
        if nodes is not None:
            volsvc = compile_volsvc(pods, nodes, nt.schedulable, plan=plan)
        else:
            volsvc = empty_volsvc(p, n)

    # Nonzero-request templates for the scan's template-factored
    # score planes (engine/solver.py _solve_scan): the distinct nonzero
    # rows in row order, pow2-row-padded (padcap's "b_nztmpl" axis keeps
    # the bucket monotonic across batches).  Above the cap the table
    # compiles away (shape 0) and the scan keeps its in-step score path.
    # The default nonzero row (a request-less pod's non_zero_request) is
    # ALWAYS in the table: chunk/gang pad pods carry exactly it, and a
    # live padded batch must not grow the template table past what the
    # prewarm batches (which are never padded) traced — that cap bump
    # minted an unwarmed scan shape on the wire clock.  Derived through
    # the SAME row encoder the pad pods go through (not re-derived
    # constants), so the two can never diverge.
    default_nz = _default_nz_row()
    nz_rows = {m.nz for m in metas}
    nz_rows.add((int(default_nz[0]), int(default_nz[1])))
    nz_key = tuple(sorted(nz_rows))

    def nz_stack() -> tuple:
        from kubernetes_tpu.engine.solver import DYN_TEMPLATE_CAP
        if len(nz_key) > DYN_TEMPLATE_CAP:
            return keep(np.zeros((0, 2), np.int32)), None
        # Row floor of 8 bounds tiny-batch wobble to one shape.
        table = np.zeros((max(_pow2(len(nz_key)), 8), 2), np.int32)
        table[:len(nz_key)] = nz_key
        return keep(table), {nz: i for i, nz in enumerate(nz_key)}

    nz_templates, nz_of = plan.stack("nz", nz_key, nz_stack)
    if nz_of is not None:
        nz_tmpl_idx = np.array([nz_of[m.nz] for m in metas],
                               np.int32)[tpl_idx]
    else:
        nz_tmpl_idx = np.zeros(p, np.int32)

    tab = plan.tables
    slot_idx = np.array(slots, np.int64)[tpl_idx]
    return PodBatch(
        pods=list(pods), request=tab.request[slot_idx],
        zero_request=tab.zero_req[slot_idx], nonzero=tab.nonzero[slot_idx],
        best_effort=tab.best_effort[slot_idx],
        host_idx=tab.host_idx[slot_idx], ports=tab.ports[slot_idx],
        vol_ro=tab.vol_ro[slot_idx], vol_rw=tab.vol_rw[slot_idx],
        tol_nosched=tab.tol_ns[slot_idx], tol_prefer=tab.tol_pref[slot_idx],
        has_tolerations=tab.has_tols[slot_idx],
        images=tab.images[slot_idx],
        sel_group=np.array(sel_group, np.int32)[tpl_idx],
        sel_required=sel_required, sel_pref_counts=sel_pref,
        spread_group=np.array(spread_group, np.int32)[tpl_idx],
        spread_node_counts=sp_n, spread_zone_counts=sp_z,
        spread_has_zones=sp_hz, spread_incr=spread_incr[tpl_idx],
        node_zone_id=node_zone_id,
        avoid_group=np.array(avoid_group, np.int32)[tpl_idx],
        avoid_rows=avoid_rows,
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
