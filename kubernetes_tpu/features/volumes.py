"""Volume-count, volume-zone, and service-affinity compilation.

Host-side tensor builders for the predicates/priorities that resolve cluster
objects (PVs, PVCs, services) rather than node features:

* ``MaxEBSVolumeCount`` / ``MaxGCEPDVolumeCount``
  (MaxPDVolumeCountChecker, predicates.go:155-316): per-family unique-volume
  id sets become interned bool matrices; the device check is
  ``existing + new - overlap <= max`` with overlap as a [P,W] @ [W,N]
  contraction.
* ``NoVolumeZoneConflict`` (VolumeZoneChecker, predicates.go:318-418):
  bound PVs' zone/region labels against node labels, deduplicated into
  per-group [G, N] masks.
* ``ServiceAffinity`` (predicates.go:623-719) and
  ``ServiceAntiAffinityPriority`` (selector_spreading.go:178-253):
  first-matching-service peer lookups deduplicated into per-group node
  masks / score rows.

Everything here is numpy on small [G, N] / [*, W] shapes; the [P, N] hot
path stays on device.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Protocol, Sequence

import numpy as np

from kubernetes_tpu.api import types as api

# A missing PVC/PV counts as one un-dedupable volume (predicates.go:195-226);
# an unbound PVC is a hard error failing every node (predicates.go:212-214),
# modeled as an impossibly large new-volume count.
INFEASIBLE_EXTRA = 1 << 20

# Content-sized table axes are pow2-bucketed (features.padcap): a live
# daemon mints volume ids / service signatures freely, and every new
# count would otherwise re-specialize the compiled scan (a fresh XLA
# compile on the scheduling clock).  Padded rows are inert — no pod
# indexes them.
from kubernetes_tpu.features.padcap import (pow2 as _pow2,  # noqa: E402
                                            stack_pad as _stack_pad)
from kubernetes_tpu.features.plan import (VOLSVC_CAP,  # noqa: E402
                                          FeaturePlan, keep)


class VolumeListers(Protocol):
    def get_pv(self, name: str) -> Optional[api.PersistentVolume]: ...
    def get_pvc(self, namespace: str,
                name: str) -> Optional[api.PersistentVolumeClaim]: ...
    def first_service(self, pod: api.Pod) -> Optional[api.Service]: ...


class VolSvcTensors(NamedTuple):
    """Device-ready tables (numpy; solver converts)."""

    # MaxPD families: EBS and GCE PD unique-volume membership.
    pd_pod_ebs: np.ndarray    # [P, We] bool
    pd_node_ebs: np.ndarray   # [N, We] bool
    pd_extra_ebs: np.ndarray  # [P] int32 — un-dedupable new volumes
    pd_node_extra_ebs: np.ndarray  # [N] int32 — existing un-dedupable
    pd_node_err_ebs: np.ndarray    # [N] bool — existing unbound PVC
    pd_pod_gce: np.ndarray    # [P, Wg] bool
    pd_node_gce: np.ndarray   # [N, Wg] bool
    pd_extra_gce: np.ndarray  # [P] int32
    pd_node_extra_gce: np.ndarray  # [N] int32
    pd_node_err_gce: np.ndarray    # [N] bool
    # NoVolumeZoneConflict groups.
    vz_group: np.ndarray      # [P] int32
    vz_mask: np.ndarray       # [G, N] bool
    # ServiceAffinity groups.
    sa_group: np.ndarray      # [P] int32
    sa_mask: np.ndarray       # [Gs, N] bool
    # ServiceAntiAffinity (selector_spreading.go:193-253) carried state:
    # the solver's scan carries per-(label, group) per-domain peer counts so
    # every in-batch placement moves the live score — the same visibility
    # the reference's one-at-a-time loop gets through its pod lister.
    saa_group: np.ndarray     # [P] int32 — pod's (ns, first-svc-sel) group
    saa_src: np.ndarray       # [P, Gy] bool — groups a placed pod joins
    saa_dom: np.ndarray       # [L, N] int32 — node's label-value domain id
    saa_labeled: np.ndarray   # [L, N] bool — has label & schedulable
    saa_cnt: np.ndarray       # [L, Gy, D] f32 — batch-start domain counts
    saa_num: np.ndarray       # [Gy] f32 — batch-start peer totals
    # CheckNodeLabelPresence / NodeLabelPriority policy-arg rows
    # (predicates.go:586-621, priorities.go:160-197) — pod-independent.
    nl_pred_row: np.ndarray   # [N] bool
    nl_prio_rows: np.ndarray  # [Lnl, N] bool


def _pd_ids(pod: api.Pod, family: str,
            listers: Optional[VolumeListers]) -> tuple[set[str], int]:
    """filterVolumes (predicates.go:188-241) for one family: unique volume
    ids + count of un-dedupable extras (missing PVC/PV), INFEASIBLE_EXTRA on
    an unbound PVC."""
    ids: set[str] = set()
    extra = 0
    for v in pod.volumes:
        if family == "ebs" and v.aws_ebs_id:
            ids.add(v.aws_ebs_id)
        elif family == "gce" and v.gce_pd_name:
            ids.add(v.gce_pd_name)
        elif v.pvc_claim_name:
            pvc = listers.get_pvc(pod.namespace, v.pvc_claim_name) \
                if listers is not None else None
            if pvc is None:
                extra += 1  # missing PVC: assume it matches (random id)
                continue
            if not pvc.volume_name:
                return ids, INFEASIBLE_EXTRA  # unbound: hard error
            pv = listers.get_pv(pvc.volume_name)
            if pv is None:
                extra += 1  # missing PV: assume it matches
                continue
            if family == "ebs" and pv.aws_ebs_id:
                ids.add(pv.aws_ebs_id)
            elif family == "gce" and pv.gce_pd_name:
                ids.add(pv.gce_pd_name)
    return ids, extra


def _compile_pd_family(pods: Sequence[api.Pod],
                       volume_pods: Sequence[tuple[api.Pod, int]],
                       n_nodes: int, family: str,
                       listers: Optional[VolumeListers]
                       ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                  np.ndarray, np.ndarray]:
    """Returns (pod_ids [P,W], node_ids [N,W], pod_extra [P],
    node_extra [N], node_err [N]).  Existing pods' missing-PVC/PV volumes
    count toward the node total (predicates.go:265-268 runs filterVolumes
    on existing pods too); an existing pod's unbound PVC is a hard error
    failing the node for any volume-carrying candidate."""
    vocab: dict[str, int] = {}
    pod_ids: list[set[str]] = []
    extra = np.zeros(len(pods), np.int32)
    for i, pod in enumerate(pods):
        if not pod.volumes:
            pod_ids.append(set())
            continue
        ids, ex = _pd_ids(pod, family, listers)
        pod_ids.append(ids)
        extra[i] = ex
        for vid in ids:
            vocab.setdefault(vid, len(vocab))
    node_ids: list[tuple[int, set[str]]] = []
    node_extra = np.zeros(n_nodes, np.int32)
    node_err = np.zeros(n_nodes, bool)
    for epod, nidx in volume_pods:
        if nidx < 0 or nidx >= n_nodes:
            continue
        ids, ex = _pd_ids(epod, family, listers)
        if ex >= INFEASIBLE_EXTRA:
            node_err[nidx] = True
        else:
            node_extra[nidx] += ex
        if ids:
            node_ids.append((nidx, ids))
            for vid in ids:
                vocab.setdefault(vid, len(vocab))
    w = _pow2(len(vocab))
    pod_m = np.zeros((len(pods), w), bool)
    node_m = np.zeros((n_nodes, w), bool)
    for i, ids in enumerate(pod_ids):
        for vid in ids:
            pod_m[i, vocab[vid]] = True
    for nidx, ids in node_ids:
        for vid in ids:
            node_m[nidx, vocab[vid]] = True
    return pod_m, node_m, extra, node_extra, node_err


def _vz_constraints(pod: api.Pod, listers: Optional[VolumeListers]
                    ) -> Optional[list[tuple[str, str]]]:
    """Pod's bound-PV zone/region constraints; None = resolution error
    (missing/unbound PVC or missing PV fails nodes with zone labels,
    predicates.go:369-418)."""
    out: list[tuple[str, str]] = []
    for v in pod.volumes:
        if not v.pvc_claim_name:
            continue
        pvc = listers.get_pvc(pod.namespace, v.pvc_claim_name) \
            if listers is not None else None
        if pvc is None or not pvc.volume_name:
            return None
        pv = listers.get_pv(pvc.volume_name)
        if pv is None:
            return None
        for k in (api.ZONE_LABEL, api.REGION_LABEL):
            if k in pv.labels:
                out.append((k, pv.labels[k]))
    return out


def _compile_volume_zone(pods: Sequence[api.Pod],
                         nodes: Sequence[api.Node],
                         listers: Optional[VolumeListers]
                         ) -> tuple[np.ndarray, np.ndarray]:
    n = len(nodes)
    # Nodes without zone/region labels always pass (predicates.go:362-368).
    has_constraint = np.array(
        [api.ZONE_LABEL in nd.labels or api.REGION_LABEL in nd.labels
         for nd in nodes], bool)
    groups: dict = {}
    rows: list[np.ndarray] = []
    group = np.zeros(len(pods), np.int32)
    for i, pod in enumerate(pods):
        if not pod.volumes or not any(v.pvc_claim_name for v in pod.volumes):
            sig = ("pass",)
        else:
            cons = _vz_constraints(pod, listers)
            sig = ("err",) if cons is None else tuple(sorted(set(cons)))
        g = groups.get(sig)
        if g is None:
            g = len(rows)
            groups[sig] = g
            if sig == ("pass",):
                rows.append(np.ones(n, bool))
            elif sig == ("err",):
                rows.append(~has_constraint)
            else:
                ok = np.ones(n, bool)
                for k, v in sig:
                    node_v = np.array([nd.labels.get(k, "") for nd in nodes])
                    ok &= node_v == v
                rows.append(ok | ~has_constraint)
        group[i] = g
    mask = _stack_pad(rows, n, True)
    return group, mask


def _compile_service_affinity(pods: Sequence[api.Pod],
                              nodes: Sequence[api.Node],
                              labels_cfg: tuple[str, ...],
                              listers: Optional[VolumeListers],
                              first_peer) -> tuple[np.ndarray, np.ndarray]:
    """CheckServiceAffinity (predicates.go:649-719): implicit node selector
    on the configured labels, inherited from the first peer pod's node for
    labels the pod's nodeSelector doesn't pin."""
    n = len(nodes)
    groups: dict = {}
    rows: list[np.ndarray] = []
    group = np.zeros(len(pods), np.int32)
    for i, pod in enumerate(pods):
        affinity_labels: dict[str, str] = {}
        missing = False
        for lb in labels_cfg:
            if lb in pod.node_selector:
                affinity_labels[lb] = pod.node_selector[lb]
            else:
                missing = True
        err = False
        if missing and listers is not None and first_peer is not None:
            svc = listers.first_service(pod)
            if svc is not None:
                peer_node_name = first_peer(pod.namespace, svc.selector)
                if peer_node_name is not None:
                    nd = next((x for x in nodes
                               if x.name == peer_node_name), None)
                    if nd is None:
                        err = True  # GetNodeInfo error fails all nodes
                    else:
                        for lb in labels_cfg:
                            if lb not in affinity_labels and lb in nd.labels:
                                affinity_labels[lb] = nd.labels[lb]
        sig = ("err",) if err else tuple(sorted(affinity_labels.items()))
        g = groups.get(sig)
        if g is None:
            g = len(rows)
            groups[sig] = g
            if sig == ("err",):
                rows.append(np.zeros(n, bool))
            else:
                ok = np.ones(n, bool)
                for k, v in sig:
                    node_v = np.array([nd.labels.get(k) or "" for nd in nodes])
                    ok &= node_v == v
                rows.append(ok)
        group[i] = g
    mask = _stack_pad(rows, n, True)
    return group, mask


def _compile_service_anti_affinity(pods: Sequence[api.Pod],
                                   nodes: Sequence[api.Node],
                                   schedulable: np.ndarray,
                                   labels_cfg: tuple[str, ...],
                                   listers: Optional[VolumeListers],
                                   service_peers
                                   ) -> tuple[np.ndarray, np.ndarray,
                                              np.ndarray, np.ndarray,
                                              np.ndarray, np.ndarray]:
    """CalculateAntiAffinityPriority (selector_spreading.go:193-253):
    int(10 * (numServicePods - countsOnLabelValue) / numServicePods) on
    ready nodes carrying the label, 0 elsewhere, 10 when no service pods.

    Emits carried state rather than baked scores: (group [P], src [P,Gy],
    dom [L,N], labeled [L,N], cnt [L,Gy,D], num [Gy]).  The solver scores
    from (cnt, num) and updates both per in-batch placement; `src[i, g]`
    marks every group whose namespace+selector pod i joins when placed
    (a pod counts toward EVERY matching service's spread, not just the
    first service it reads its own score from)."""
    n = len(nodes)
    L = max(len(labels_cfg), 1)
    name_to_idx = {nd.name: j for j, nd in enumerate(nodes)}
    # Per-label node domains: distinct label values interned per label.
    dom = np.zeros((L, n), np.int32)
    labeled = np.zeros((L, n), bool)
    n_doms = 1
    for li, lb in enumerate(labels_cfg):
        values: dict[str, int] = {}
        for j, nd in enumerate(nodes):
            v = nd.labels.get(lb)
            if v is None:
                continue
            labeled[li, j] = bool(schedulable[j])
            d = values.get(v)
            if d is None:
                d = len(values)
                values[v] = d
            dom[li, j] = d
        n_doms = max(n_doms, len(values))
    D = _pow2(n_doms)

    groups: dict = {}
    sigs: list = []          # group -> (ns, selector dict or None)
    peer_lists: list = []    # group -> peer node-name list
    group = np.zeros(len(pods), np.int32)
    for i, pod in enumerate(pods):
        svc = listers.first_service(pod) if listers is not None else None
        sig = (pod.namespace, tuple(sorted(svc.selector.items()))
               if svc is not None else None)
        g = groups.get(sig)
        if g is None:
            g = len(sigs)
            groups[sig] = g
            sigs.append((pod.namespace,
                         dict(svc.selector) if svc is not None else None))
            peer_lists.append(service_peers(pod.namespace, svc.selector)
                              if svc is not None else [])
        group[i] = g
    gcount = _pow2(len(sigs))
    cnt = np.zeros((L, gcount, D), np.float32)
    num = np.zeros(gcount, np.float32)
    for g, peer_nodes in enumerate(peer_lists):
        num[g] = len(peer_nodes)
        for pn in peer_nodes:
            j = name_to_idx.get(pn)
            if j is None:
                continue
            for li in range(L):
                if labeled[li, j]:
                    cnt[li, g, dom[li, j]] += 1.0
    src = np.zeros((len(pods), gcount), bool)
    for i, pod in enumerate(pods):
        for g, (ns, sel) in enumerate(sigs):
            if sel is not None and pod.namespace == ns and \
                    all(pod.labels.get(k) == v for k, v in sel.items()):
                src[i, g] = True
    return group, src, dom, labeled, cnt, num


def empty_volsvc(p: int, n: int) -> VolSvcTensors:
    """Neutral all-pass tables (no volumes, no service policy args)."""
    return VolSvcTensors(
        pd_pod_ebs=np.zeros((p, 1), bool), pd_node_ebs=np.zeros((n, 1), bool),
        pd_extra_ebs=np.zeros(p, np.int32),
        pd_node_extra_ebs=np.zeros(n, np.int32),
        pd_node_err_ebs=np.zeros(n, bool),
        pd_pod_gce=np.zeros((p, 1), bool), pd_node_gce=np.zeros((n, 1), bool),
        pd_extra_gce=np.zeros(p, np.int32),
        pd_node_extra_gce=np.zeros(n, np.int32),
        pd_node_err_gce=np.zeros(n, bool),
        vz_group=np.zeros(p, np.int32), vz_mask=np.ones((1, n), bool),
        sa_group=np.zeros(p, np.int32), sa_mask=np.ones((1, n), bool),
        saa_group=np.zeros(p, np.int32), saa_src=np.zeros((p, 1), bool),
        saa_dom=np.zeros((1, n), np.int32),
        saa_labeled=np.zeros((1, n), bool),
        saa_cnt=np.zeros((1, 1, 1), np.float32),
        saa_num=np.zeros(1, np.float32),
        nl_pred_row=np.ones(n, bool), nl_prio_rows=np.zeros((1, n), bool))


def compile_volsvc(pods: Sequence[api.Pod],
                   nodes: Sequence[api.Node],
                   schedulable: np.ndarray,
                   volume_pods: Sequence[tuple[api.Pod, int]] = (),
                   listers: Optional[VolumeListers] = None,
                   service_affinity_labels: tuple[str, ...] = (),
                   service_anti_affinity_labels: tuple[str, ...] = (),
                   node_label_args: Optional[tuple[tuple[str, ...], bool]] = None,
                   node_label_prio_args: Sequence[tuple[str, bool]] = (),
                   service_peers=None, first_peer=None,
                   plan: Optional[FeaturePlan] = None) -> VolSvcTensors:
    """Build all volume/service tables for a batch.

    ``service_peers(ns, selector)`` -> list of node names hosting matching
    assigned pods; ``first_peer(ns, selector)`` -> first such node name or
    None.  Both come from the scheduler cache.

    ``plan``: the tables kept between launches for these ``nodes``
    (features/plan.py).  Only the NEUTRAL form is kept — no volume in the
    batch or on the fleet, no service label in the policy: then every
    table is a function of the nodes, the pod axis and the node-label
    arguments, and the kept one is handed out.  Any other batch runs the
    code below and tells the plan so.
    """
    n = len(nodes)
    p = len(pods)
    any_vols = any(pod.volumes for pod in pods)
    kept_key = None
    if plan is not None:
        if any_vols or volume_pods or service_affinity_labels \
                or service_anti_affinity_labels:
            plan.miss("not_neutral")
        else:
            kept_key = (p, node_label_args and (tuple(node_label_args[0]),
                                                node_label_args[1]),
                        tuple(node_label_prio_args))
            kept = plan.volsvc.get(kept_key)
            if kept is not None:
                return kept
    if any_vols or volume_pods:
        pe, ne, xe, nxe, nee = _compile_pd_family(
            pods, volume_pods, n, "ebs", listers)
        pg, ng, xg, nxg, neg = _compile_pd_family(
            pods, volume_pods, n, "gce", listers)
    else:
        pe = np.zeros((p, 1), bool)
        ne = np.zeros((n, 1), bool)
        xe = np.zeros(p, np.int32)
        nxe = np.zeros(n, np.int32)
        nee = np.zeros(n, bool)
        pg, ng, xg = pe.copy(), ne.copy(), xe.copy()
        nxg, neg = nxe.copy(), nee.copy()

    if any_vols:
        vz_group, vz_mask = _compile_volume_zone(pods, nodes, listers)
    else:
        vz_group = np.zeros(p, np.int32)
        vz_mask = np.ones((1, n), bool)

    if service_affinity_labels:
        sa_group, sa_mask = _compile_service_affinity(
            pods, nodes, service_affinity_labels, listers, first_peer)
    else:
        sa_group = np.zeros(p, np.int32)
        sa_mask = np.ones((1, n), bool)

    if service_anti_affinity_labels:
        (saa_group, saa_src, saa_dom, saa_labeled, saa_cnt,
         saa_num) = _compile_service_anti_affinity(
            pods, nodes, schedulable, service_anti_affinity_labels, listers,
            service_peers)
    else:
        saa_group = np.zeros(p, np.int32)
        saa_src = np.zeros((p, 1), bool)
        saa_dom = np.zeros((1, n), np.int32)
        saa_labeled = np.zeros((1, n), bool)
        saa_cnt = np.zeros((1, 1, 1), np.float32)
        saa_num = np.zeros(1, np.float32)

    # CheckNodeLabelPresence: with presence=True every listed label must be
    # on the node; with False none may be (predicates.go:599-621).
    nl_pred_row = np.ones(n, bool)
    if node_label_args is not None:
        nl_labels, nl_presence = node_label_args
        for lb in nl_labels:
            has = np.array([lb in nd.labels for nd in nodes], bool)
            nl_pred_row &= has if nl_presence else ~has
    nl_prio_rows = np.zeros((max(len(node_label_prio_args), 1), n), bool)
    for li, (lb, pres) in enumerate(node_label_prio_args):
        has = np.array([lb in nd.labels for nd in nodes], bool)
        nl_prio_rows[li] = has if pres else ~has

    out = VolSvcTensors(
        pd_pod_ebs=pe, pd_node_ebs=ne, pd_extra_ebs=xe,
        pd_node_extra_ebs=nxe, pd_node_err_ebs=nee,
        pd_pod_gce=pg, pd_node_gce=ng, pd_extra_gce=xg,
        pd_node_extra_gce=nxg, pd_node_err_gce=neg,
        vz_group=vz_group, vz_mask=vz_mask,
        sa_group=sa_group, sa_mask=sa_mask,
        saa_group=saa_group, saa_src=saa_src, saa_dom=saa_dom,
        saa_labeled=saa_labeled, saa_cnt=saa_cnt, saa_num=saa_num,
        nl_pred_row=nl_pred_row, nl_prio_rows=nl_prio_rows)
    if kept_key is not None:
        if len(plan.volsvc) >= VOLSVC_CAP:
            plan.volsvc.clear()
        plan.volsvc[kept_key] = out
        for a in out:
            keep(a)
    return out
