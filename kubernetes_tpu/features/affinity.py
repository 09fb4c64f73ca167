"""Inter-pod (anti-)affinity compilation: terms -> sig tables -> tensors.

The reference evaluates inter-pod affinity as nested loops over
(candidate pod x existing pods x terms x nodes) — the quadratic heart of
``predicates.go:825-1068`` and ``interpod_affinity.go:117-260``.  The TPU
recast groups every term by its *signature* — (resolved namespace set,
selector, topology key[, weight]) — and precomputes one [S, N] row table per
signature family, so the whole per-(pod,node) evaluation becomes three
[P,S] @ [S,N] contractions on the MXU (see ops/interpod.py).

Three signature families:

``match`` sigs (M) — "does an existing pod match this (ns, selector)?",
    used by the candidate's OWN terms: required affinity (reach must be
    nonzero), required anti-affinity (reach must be zero), and preferred
    ±weight (reach count scales the score).  Reach of sig s =
    per-node count of matching existing pods' topology domains.

``decl`` sigs (D) — anti-affinity terms DECLARED by existing pods
    (satisfiesExistingPodsAntiAffinity, predicates.go:1000-1035): candidate
    matching the sig may not land in the topology of any declaring pod.

``sym`` sigs (Y) — the priority's symmetric soft part
    (interpod_affinity.go:164-196): terms declared by existing pods
    (required affinity x hardPodAffinityWeight, preferred affinity +w,
    preferred anti-affinity -w) score candidate pods that match them.

Topology: ``node_dom[K, N]`` holds a compact domain id per (key, node), -1
when the node lacks the label; key index -1 in a sig means the term had an
empty topologyKey, which the reference resolves as "any default failure
domain" (topologies.go:66-76).  The first ``n_default`` rows are the default
failure-domain keys.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Iterable, NamedTuple, Optional, Sequence

import numpy as np

from kubernetes_tpu.api import types as api
from kubernetes_tpu.features import compiler as fc
from kubernetes_tpu.features.padcap import pad1 as _pad1, pow2 as _pow2
from kubernetes_tpu.utils import metrics
from kubernetes_tpu.utils.trace import stage

_LAUNCH_SIGNATURES = {
    family: metrics.AFFINITY_LAUNCH_SIGNATURES.labels(family=family)
    for family in ("match", "decl", "sym")}

# Resolved namespace marker: () after resolution means "all namespaces".
_ALL_NS = ()


def _resolve_ns(term: api.PodAffinityTerm, owner: api.Pod) -> tuple[str, ...]:
    """getNamespacesFromPodAffinityTerm (topologies.go:31-38)."""
    if term.namespaces is None:
        return (owner.namespace,)
    return tuple(sorted(set(term.namespaces)))


def _sel_sig(sel: Optional[api.LabelSelector]):
    """Hashable selector identity.  None (nil selector) matches nothing
    (LabelSelectorAsSelector -> Nothing)."""
    if sel is None:
        return None
    return (tuple(sorted(sel.match_labels)),
            tuple(sorted((e.key, e.operator, tuple(sorted(e.values)))
                         for e in sel.match_expressions)))


@dataclass(frozen=True)
class Sig:
    """One deduplicated term signature."""

    namespaces: tuple[str, ...]  # () = all namespaces
    selector: object             # _sel_sig output (None = matches nothing)
    key: str                     # topology key ("" = default domains)
    weight: int = 0              # sym sigs only (signed)


class AffinityTensors(NamedTuple):
    """Device-ready affinity tables for one batch.  All S dims are >= 1
    (padded with inert rows) so shapes are stable when no affinity exists."""

    node_dom: np.ndarray     # [K, N] int32 domain ids, -1 absent
    n_default: np.ndarray    # [] int32 — first rows of node_dom = default keys
    # -- match sigs (candidate's own terms) --
    match_key: np.ndarray    # [Sm] int32 key row, -1 = any-default
    match_cnt: np.ndarray    # [Sm, N] f32 — matching existing pods per domain-reach
    match_total: np.ndarray  # [Sm] f32 — matching existing pods anywhere
    match_src: np.ndarray    # [P, Sm] bool — batch pod matches sig (placement source)
    aff_need: np.ndarray     # [P, Sm] bool — required affinity
    aff_self: np.ndarray     # [P, Sm] bool — self-match escape (predicates.go:1038-1048)
    anti_need: np.ndarray    # [P, Sm] bool — required anti-affinity
    pref_w: np.ndarray       # [P, Sm] f32 — signed preferred weight sum
    # -- decl sigs (existing pods' hard anti-affinity) --
    decl_key: np.ndarray     # [Sd] int32
    decl_reach: np.ndarray   # [Sd, N] bool — forbidden topology of declaring pods
    decl_match: np.ndarray   # [P, Sd] bool — candidate is repelled by sig
    decl_src: np.ndarray     # [P, Sd] bool — batch pod declares sig
    # -- sym sigs (existing pods' scored terms) --
    sym_key: np.ndarray      # [Ss] int32
    sym_w: np.ndarray        # [Ss] f32 signed weight
    sym_cnt: np.ndarray      # [Ss, N] f32 — declaring term instances per domain-reach
    sym_match: np.ndarray    # [P, Ss] bool — candidate matches sig
    sym_src: np.ndarray      # [P, Ss] bool — batch pod declares term with sig
    has_any: bool            # static: skip all kernels when False


def _pod_matches_sig(sig: Sig, ns: str, labels: dict[str, str]) -> bool:
    if sig.namespaces != _ALL_NS and ns not in sig.namespaces:
        return False
    if sig.selector is None:
        return False
    ml, mexpr = sig.selector
    for k, v in ml:
        if labels.get(k) != v:
            return False
    for k, op, vals in mexpr:
        has = k in labels
        if op == "In":
            if not has or labels[k] not in vals:
                return False
        elif op == "NotIn":
            if has and labels[k] in vals:
                return False
        elif op == "Exists":
            if not has:
                return False
        elif op == "DoesNotExist":
            if has:
                return False
        else:
            return False
    return True


def _sig_match_existing(sig: Sig, ep: fc.ExistingPodTensors,
                        space: fc.FeatureSpace) -> np.ndarray:
    """[M] bool — existing pods matching sig (ns + selector), vectorized over
    the existing-pod label multi-hot."""
    m = ep.labels.shape[0]
    cand = ep.alive & (ep.node_idx >= 0)
    if sig.namespaces != _ALL_NS:
        ns_ids = [space.namespaces.get(n) for n in sig.namespaces]
        ns_ids = [i for i in ns_ids if i >= 0]
        if not ns_ids:
            return np.zeros(m, bool)
        cand &= np.isin(ep.ns_id, ns_ids)
    if sig.selector is None:
        return np.zeros(m, bool)
    ml, mexpr = sig.selector
    mask = cand
    for k, v in ml:
        kv = space.pod_labels.kv_get(k, v)
        mask = mask & (ep.labels[:, kv] if kv >= 0 else False)
    for k, op, vals in mexpr:
        kid = space.pod_labels.key_get(k)
        has = ep.labels[:, kid] if kid >= 0 else np.zeros(m, bool)
        ids = [space.pod_labels.kv_get(k, v) for v in vals]
        ids = [i for i in ids if i >= 0]
        inset = ep.labels[:, ids].any(1) if ids else np.zeros(m, bool)
        if op == "In":
            mask = mask & inset
        elif op == "NotIn":
            mask = mask & ~inset
        elif op == "Exists":
            mask = mask & has
        elif op == "DoesNotExist":
            mask = mask & ~has
        else:
            return np.zeros(m, bool)
    return np.asarray(mask, bool)


class _DomainTable:
    """node_dom builder: interned topology keys -> per-node domain ids."""

    def __init__(self, nodes: Sequence[api.Node], n: int):
        self.nodes = nodes
        self.n = n
        self.keys: list[str] = list(api.DEFAULT_FAILURE_DOMAINS)
        self.key_to_row: dict[str, int] = {k: i for i, k in enumerate(self.keys)}
        self.n_default = len(self.keys)

    def row(self, key: str) -> int:
        """Row index for a non-empty topology key ('' handled by caller as -1)."""
        r = self.key_to_row.get(key)
        if r is None:
            r = len(self.keys)
            self.keys.append(key)
            self.key_to_row[key] = r
        return r

    def build(self) -> np.ndarray:
        n = self.n
        dom = np.full((len(self.keys), n), -1, np.int32)
        for ki, key in enumerate(self.keys):
            vals: dict[str, int] = {}
            for i, node in enumerate(self.nodes):
                v = node.labels.get(key)
                if v:  # len(labels[key]) > 0 (topologies.go:58)
                    dom[ki, i] = vals.setdefault(v, len(vals))
        return dom

    def same_topo_row(self, dom: np.ndarray, key_row: int,
                      node_idx: int) -> np.ndarray:
        """[N] bool — nodes sharing topology with node_idx under key_row
        (-1 = any default key), NodesHaveSameTopologyKey semantics."""
        if key_row >= 0:
            d = dom[key_row]
            return (d == d[node_idx]) & (d >= 0)
        out = np.zeros(dom.shape[1], bool)
        for r in range(self.n_default):
            d = dom[r]
            out |= (d == d[node_idx]) & (d >= 0)
        return out


@dataclass
class _SigTable:
    sig_to_idx: dict[Sig, int] = field(default_factory=dict)
    sigs: list[Sig] = field(default_factory=list)

    def idx(self, sig: Sig) -> int:
        i = self.sig_to_idx.get(sig)
        if i is None:
            i = len(self.sigs)
            self.sig_to_idx[sig] = i
            self.sigs.append(sig)
        return i


def _pod_terms(pod: api.Pod):
    """(required_affinity, required_anti, preferred_affinity_weighted,
    preferred_anti_weighted) — getPodAffinityTerms/getPodAntiAffinityTerms
    (predicates.go:881-906) + the priority's preferred lists."""
    aff = pod.affinity()
    req_a: tuple = ()
    req_aa: tuple = ()
    pref_a: tuple = ()
    pref_aa: tuple = ()
    if aff is not None:
        if aff.pod_affinity is not None:
            req_a = aff.pod_affinity.required
            pref_a = aff.pod_affinity.preferred
        if aff.pod_anti_affinity is not None:
            req_aa = aff.pod_anti_affinity.required
            pref_aa = aff.pod_anti_affinity.preferred
    return req_a, req_aa, pref_a, pref_aa


def pod_has_affinity(pod: api.Pod) -> bool:
    """PodsWithAffinity membership (node_info.go): any affinity annotation."""
    return pod.affinity() is not None


def _sig_order(sig: Sig) -> tuple:
    """A total order on signatures (a nil selector sorts first), so that
    the resident pods' decl / sym rows come out in one order whatever
    order the pods were seen in."""
    return (sig.namespaces, sig.selector is not None,
            sig.selector or ((), ()), sig.key, sig.weight)


def _term_sig(term: api.PodAffinityTerm, owner: api.Pod,
              weight: int = 0) -> Sig:
    return Sig(_resolve_ns(term, owner), _sel_sig(term.label_selector),
               term.topology_key, weight)


def _declared_sigs(pod: api.Pod, hard_pod_affinity_weight: int
                   ) -> tuple[list[Sig], list[Sig]]:
    """``(decl, sym)`` signatures a pod DECLARES toward other pods once it
    is placed: its required anti-affinity terms, and one sym instance per
    scored term (required affinity x hardPodAffinityWeight, preferred
    affinity +w, preferred anti-affinity -w)."""
    req_a, req_aa, pref_a, pref_aa = _pod_terms(pod)
    decl = [_term_sig(t, pod) for t in req_aa]
    sym = []
    if hard_pod_affinity_weight > 0:
        sym += [_term_sig(t, pod, hard_pod_affinity_weight) for t in req_a]
    sym += [_term_sig(wt.pod_affinity_term, pod, wt.weight)
            for wt in pref_a if wt.weight != 0]
    sym += [_term_sig(wt.pod_affinity_term, pod, -wt.weight)
            for wt in pref_aa if wt.weight != 0]
    return decl, sym


class _Planes:
    """One signature family's resident planes.  For the signature
    ``rows`` maps to ``row``, ``cnt[row]`` is an int32 count per DOMAIN
    of the signature's topology key (the pods that sit in the domain:
    every node of a domain reads the same, so an update writes one
    element and a launch gathers the row back to the nodes,
    ``ResidentAffinity.node_row``; one more count behind them stays zero
    for the nodes without the label) — or, for the empty key, per NODE
    (the pods whose default failure domains reach the node) — and
    ``total[row]`` the pods counted.  Row numbers are reused; ``total``
    doubles when they run out."""

    def __init__(self):
        self.rows: dict[Sig, int] = {}
        self.cnt: dict[int, np.ndarray] = {}
        self.total = np.zeros(4, np.int64)
        self._free = [3, 2, 1, 0]

    def row(self, sig: Sig, width: int) -> int:
        """The signature's row, a new all-zero one of ``width`` counts
        when it has none."""
        r = self.rows.get(sig)
        if r is None:
            if not self._free:
                have = len(self.total)
                self.total = np.concatenate(
                    [self.total, np.zeros_like(self.total)])
                self._free = list(range(2 * have - 1, have - 1, -1))
            r = self.rows[sig] = self._free.pop()
            self.cnt[r] = np.zeros(width, np.int32)
        return r

    def drop(self, sig: Sig) -> None:
        r = self.rows.pop(sig)
        del self.cnt[r]
        self.total[r] = 0
        self._free.append(r)


class ResidentAffinity:
    """The resident side of the affinity tables, kept between launches.

    What depends on the cluster and its bound (or assumed) pods only —
    ``node_dom`` per topology key, and per signature the [S, N] planes
    behind ``match_cnt`` / ``match_total``, ``decl_reach`` (as counts, so
    that a pod can be taken out again) and ``sym_cnt`` — is maintained
    where the cache maintains its aggregates: ``add_pod`` / ``remove_pod``
    per attached pod, ``invalidate`` when the node rows or their labels
    change (the next launch then builds them again from ``attached()``,
    the cache's ``(pod, node index)`` of every pod on a known node that
    declares a term: right after a reset no match signature is
    registered, so no other pod moves a plane).
    ``compile_affinity(..., resident=self)`` builds a launch's tables
    from these planes and the batch's own incidence rows; it equals the
    from-nothing build to the element.

    ``decl`` and ``sym`` signatures exist while a resident pod declares
    them.  ``match`` signatures are registered by the first batch that
    carries them (one vectorized pass over the resident pods) and kept
    until the planes are built again, so the next batch of the same
    controller finds its row.

    Counted in ``scheduler_affinity_table_rebuilds_total`` (a build from
    nothing, or a new match signature's pass) and
    ``scheduler_affinity_table_row_updates_total`` (one resident pod added
    or taken out).  Not thread-safe: every call on the cache's instance
    is made under the cache lock (a ``twin`` belongs to its caller).
    """

    def __init__(self, attached: Callable[[], Iterable[tuple[api.Pod, int]]],
                 counted: bool = True):
        self._attached = attached
        self._counted = counted       # False: the verifier's throwaway copy
        self.valid = False
        self._reset((), 0, 1)

    def planes(self) -> dict[tuple[str, Sig], tuple[np.ndarray, int]]:
        """``{(family, signature): ([N] counts, total)}``, copied."""
        return {(family, sig): (np.array(self.node_row(p, sig)),
                                int(p.total[r]))
                for family, p in (("match", self.match), ("decl", self.decl),
                                  ("sym", self.sym))
                for sig, r in p.rows.items()}

    def twin(self) -> "ResidentAffinity":
        """An empty, uncounted instance for the same node rows, weight
        and match signatures; ``fill``ed with the attached pods it is
        what the kept planes must equal (the verifier's ground truth)."""
        fresh = ResidentAffinity(self._attached, counted=False)
        fresh._reset(self._nodes, self.n, self.hard_weight)
        fresh.valid = True
        for sig in self.match.rows:
            fresh._row(fresh.match, sig)
        return fresh

    def fill(self, attached: Iterable[tuple[api.Pod, int]]) -> None:
        """Add every ``(pod, node row)``, uncounted as row updates."""
        for pod, nidx in attached:
            self._add(pod, nidx, 1)

    def _reset(self, nodes: Sequence[api.Node], n: int,
               hard_pod_affinity_weight: int) -> None:
        self._nodes = nodes
        self.n = n
        self.hard_weight = hard_pod_affinity_weight
        self._dom: dict[str, np.ndarray] = {}
        self._declared_memo: dict = {}
        self.match, self.decl, self.sym = _Planes(), _Planes(), _Planes()
        self._match_memo: dict = {}

    def invalidate(self) -> None:
        """The node rows or their labels changed: the next launch builds
        from nothing."""
        self.valid = False

    def ensure(self, nodes: Sequence[api.Node], n: int,
               hard_pod_affinity_weight: int) -> None:
        """Valid planes for these node rows and this weight: kept ones,
        or a build from nothing over ``attached()``.  Match signatures
        re-register as batches ask for them."""
        if self.valid and self.n == n and \
                self.hard_weight == hard_pod_affinity_weight:
            return
        self._reset(nodes, n, hard_pod_affinity_weight)
        self.valid = True
        if self._counted:
            metrics.AFFINITY_TABLE_REBUILDS.inc()
        self.fill(self._attached())

    # -- topology ---------------------------------------------------------

    def dom_row(self, key: str) -> np.ndarray:
        """[N] int32 domain ids of one topology key, -1 where the node
        lacks the label; ids in order of first appearance, as
        ``_DomainTable.build`` numbers them."""
        d = self._dom.get(key)
        if d is None:
            d = np.full(self.n, -1, np.int32)
            vals: dict[str, int] = {}
            for i, node in enumerate(self._nodes):
                v = node.labels.get(key)
                if v:
                    d[i] = vals.setdefault(v, len(vals))
            self._dom[key] = d
        return d

    def _row(self, planes: _Planes, sig: Sig) -> int:
        """The signature's row in ``planes``, made when it has none: a
        count per domain of its key and one more that stays zero — what
        a node without the label (domain -1) reads, all there is for a
        key no node carries — or a count per node for the empty key."""
        r = planes.rows.get(sig)
        if r is None:
            if sig.key:
                width = int(self.dom_row(sig.key).max(initial=-1)) + 2
            else:
                width = self.n
            r = planes.row(sig, width)
        return r

    def _bump(self, counts: np.ndarray, key: str, nidx: int,
              sign: int) -> int:
        """A row's ``counts`` ``+= sign`` for a pod on node ``nidx``
        under ``key``: the count of the node's domain, or for the empty
        key every node that shares one of ``nidx``'s default failure
        domains (``_DomainTable.same_topo_row``, added in place).
        Returns the elements written: 1 for a named key (0 where the
        node lacks the label), the nodes reached for the empty one."""
        if not key:
            reach = np.zeros(self.n, bool)
            for k in api.DEFAULT_FAILURE_DOMAINS:
                d = self.dom_row(k)
                reach |= (d == d[nidx]) & (d >= 0)
            counts[reach] += sign
            return int(np.count_nonzero(reach))
        dom = self.dom_row(key)[nidx]
        if dom < 0:
            return 0
        counts[dom] += sign
        return 1

    def node_row(self, planes: _Planes, sig: Sig) -> np.ndarray:
        """[N] int32 counts of a signature's plane: the per-domain counts
        gathered back to the nodes (a node that lacks the key reads the
        row's last count, always zero), the row itself for the empty
        key."""
        counts = planes.cnt[planes.rows[sig]]
        if not sig.key:
            return counts
        return counts[self.dom_row(sig.key)]

    # -- resident pods ------------------------------------------------------

    def _matched(self, pod: api.Pod) -> tuple:
        """Registered match signatures the pod's namespace and labels
        satisfy, memoized by that template.

        How much this keeps is the deployment's, not the code's: the
        memo holds one entry per (namespace, labels) template seen since
        the last match registration (cleared at 4,096) and the planes
        one row per signature some batch carried (match) or some
        resident pod declares (decl / sym); nothing bounds the
        signatures themselves (ROADMAP.md Reach A3).  The first size to
        set such a bound against is upstream's MixedSchedulingBasePod
        (``benchmarks/configs/mixedaffinity-5000n.json``): 5 label
        templates in the memo and 4 match, 1 decl and 3 sym signatures
        (``tests/test_mixed_affinity.py`` holds those numbers)."""
        tkey = (pod.namespace, tuple(sorted(pod.labels.items())))
        got = self._match_memo.get(tkey)
        if got is None:
            if len(self._match_memo) >= 4096:
                self._match_memo.clear()
            got = self._match_memo[tkey] = tuple(
                sig for sig in self.match.rows
                if _pod_matches_sig(sig, pod.namespace, pod.labels))
        return got

    def add_pod(self, pod: api.Pod, nidx: int) -> None:
        """One pod attached to node row ``nidx``."""
        if self.valid:
            self._count(self._add(pod, nidx, 1))

    def remove_pod(self, pod: api.Pod, nidx: int) -> None:
        if self.valid:
            self._count(self._add(pod, nidx, -1))

    @staticmethod
    def _count(cells: int) -> None:
        """One counted update of the planes that wrote ``cells``
        elements (0: the pod touched no plane)."""
        if cells:
            metrics.AFFINITY_TABLE_ROW_UPDATES.inc()
            metrics.AFFINITY_PLANE_CELLS.inc(cells)

    def _declared(self, pod: api.Pod) -> tuple[list[Sig], list[Sig]]:
        """``_declared_sigs``, memoized by the annotation's text and the
        namespace: the pods of a controller carry one text."""
        raw = pod.annotations.get(api.AFFINITY_ANNOTATION_KEY)
        if not raw:
            return _declared_sigs(pod, self.hard_weight)
        got = self._declared_memo.get((raw, pod.namespace))
        if got is None:
            if len(self._declared_memo) >= 4096:
                self._declared_memo.clear()
            got = self._declared_memo[(raw, pod.namespace)] = \
                _declared_sigs(pod, self.hard_weight)
        return got

    def _add(self, pod: api.Pod, nidx: int, sign: int) -> int:
        """Elements of the planes written, 0 = the pod touched none.  On
        a node that lacks a signature's key the pod moves the
        signature's ``total`` alone: that is the one element."""
        if not 0 <= nidx < self.n:
            return 0
        cells = 0
        if self.match.rows:
            for sig in self._matched(pod):
                r = self.match.rows[sig]
                cells += self._bump(self.match.cnt[r], sig.key, nidx,
                                    sign) or 1
                self.match.total[r] += sign
        if pod.affinity() is not None:
            decl, sym = self._declared(pod)
            for planes, sigs in ((self.decl, decl), (self.sym, sym)):
                for sig in sigs:
                    r = self._row(planes, sig)
                    cells += self._bump(planes.cnt[r], sig.key, nidx,
                                        sign) or 1
                    planes.total[r] += sign
                    if planes.total[r] == 0:
                        planes.drop(sig)
        return cells

    # -- what a launch reads ------------------------------------------------

    def declared(self) -> tuple[list[Sig], list[Sig]]:
        """``(decl, sym)`` signatures some resident pod declares, in
        ``_sig_order``."""
        return (sorted(self.decl.rows, key=_sig_order),
                sorted(self.sym.rows, key=_sig_order))

    def match_row(self, sig: Sig, ep: fc.ExistingPodTensors,
                  space: fc.FeatureSpace) -> tuple[np.ndarray, int]:
        """``([N] counts, total)`` of a match signature; a signature not
        seen before is registered by one pass over the resident pods."""
        r = self.match.rows.get(sig)
        if r is None:
            r = self._row(self.match, sig)
            self._match_memo.clear()
            if self._counted:
                metrics.AFFINITY_TABLE_REBUILDS.inc()
            nidxs = ep.node_idx[_sig_match_existing(sig, ep, space)]
            self.match.total[r] = len(nidxs)
            counts = self.match.cnt[r]
            if sig.key:
                doms = self.dom_row(sig.key)[nidxs]
                counts[:] = np.bincount(doms[doms >= 0],
                                        minlength=len(counts))
            else:
                for ni in nidxs.tolist():
                    self._bump(counts, "", ni, 1)
        return self.node_row(self.match, sig), int(self.match.total[r])


def compile_affinity(pods: Sequence[api.Pod],
                     affinity_pods: Sequence[tuple[api.Pod, int]],
                     ep: Optional[fc.ExistingPodTensors],
                     nodes: Optional[Sequence[api.Node]],
                     n_nodes: int,
                     space: fc.FeatureSpace,
                     hard_pod_affinity_weight: int = 1,
                     reps: Optional[Sequence[api.Pod]] = None,
                     tpl_idx: Optional[np.ndarray] = None,
                     resident: Optional[ResidentAffinity] = None
                     ) -> AffinityTensors:
    """Build the batch's affinity tables.

    ``affinity_pods``: (existing pod, node index) for every assigned pod with
    an affinity annotation (the cache's PodsWithAffinity analogue).
    ``ep``: existing-pod label tensors for vectorized own-term matching.
    ``nodes`` may be None (no label access): every topology domain is then
    empty, matching nodes without the label.
    ``reps``/``tpl_idx``: template dedup from compile_batch — per-pod
    incidence rows are built once per spec-identical template and gathered
    back to the full pod axis.
    ``resident``: the cache's kept planes (``ResidentAffinity``).  With it the resident side — the
    signatures resident pods declare, ``node_dom``, ``match_cnt`` /
    ``match_total``, ``decl_reach``, ``sym_cnt`` — is read off the planes
    and ``affinity_pods`` is not looked at; without it that side is built
    here from nothing, pod by pod.  The two give the same tables to the
    element.
    """
    if reps is not None and tpl_idx is not None:
        cand = reps
    else:
        cand = pods
        tpl_idx = None
    p = len(cand)
    n = n_nodes
    dt = _DomainTable(nodes or [], n)
    if resident is not None:
        resident.ensure(nodes or [], n, hard_pod_affinity_weight)

    m_tab, d_tab, y_tab = _SigTable(), _SigTable(), _SigTable()

    # -- candidate pods' own terms -> match sigs ------------------------
    pod_m: list[list[tuple[int, str]]] = []  # per pod: (sig idx, kind)
    pod_pref: list[list[tuple[int, int]]] = []  # per pod: (sig idx, ±weight)
    any_affinity = False
    for pod in cand:
        req_a, req_aa, pref_a, pref_aa = _pod_terms(pod)
        entries = [(m_tab.idx(_term_sig(t, pod)), "aff") for t in req_a]
        entries += [(m_tab.idx(_term_sig(t, pod)), "anti") for t in req_aa]
        prefs = [(m_tab.idx(_term_sig(wt.pod_affinity_term, pod)), wt.weight)
                 for wt in pref_a if wt.weight != 0]
        prefs += [(m_tab.idx(_term_sig(wt.pod_affinity_term, pod)),
                   -wt.weight) for wt in pref_aa if wt.weight != 0]
        if entries or prefs:
            any_affinity = True
        pod_m.append(entries)
        pod_pref.append(prefs)

    # -- existing pods' terms -> decl + sym sigs ------------------------
    # Rows in ``_sig_order``, whatever order the pods come in.
    decl_sources: dict[Sig, list[int]] = {}  # decl sig -> [node_idx]
    sym_sources: dict[Sig, list[int]] = {}   # sym sig -> [node_idx] per instance
    if resident is not None:
        decl_live, sym_live = resident.declared()
    else:
        for epod, nidx in affinity_pods:
            if nidx < 0 or nidx >= n:
                continue
            decl, sym = _declared_sigs(epod, hard_pod_affinity_weight)
            for sig in decl:
                decl_sources.setdefault(sig, []).append(nidx)
            for sig in sym:
                sym_sources.setdefault(sig, []).append(nidx)
        decl_live = sorted(decl_sources, key=_sig_order)
        sym_live = sorted(sym_sources, key=_sig_order)
    for sig in decl_live:
        d_tab.idx(sig)
    if decl_live or sym_live:
        any_affinity = True

    # Batch pods that DECLARE terms (for in-batch sequential visibility):
    # placing pod j extends decl reach / sym counts / match counts.
    # Register their sigs too so the scan state has rows for them.
    declared = [_declared_sigs(pod, hard_pod_affinity_weight)
                for pod in cand]
    pod_decl = [[d_tab.idx(sig) for sig in decl] for decl, _sym in declared]

    # Assign key rows now that all sigs are known.
    def key_row(sig: Sig) -> int:
        return -1 if sig.key == "" else dt.row(sig.key)

    m_rows = [key_row(s) for s in m_tab.sigs]
    d_rows = [key_row(s) for s in d_tab.sigs]
    # The SCORE side of the tables (InterPodAffinityPriority alone reads
    # it) is stage ``compile.affinity.prio``, in two parts: the sym
    # signatures here, their rows and the pods' score incidence below.
    with stage("compile.affinity.prio"):
        for sig in sym_live:
            y_tab.idx(sig)
        pod_sym = [[y_tab.idx(sig) for sig in sym]
                   for _decl, sym in declared]
        y_rows = [key_row(s) for s in y_tab.sigs]
    if resident is not None:
        node_dom = np.stack([resident.dom_row(k) for k in dt.keys])
    else:
        node_dom = dt.build()

    # Sig-axis sizes are pow2-bucketed (padcap's discipline): live batches
    # mint signatures freely, and every new count would otherwise be a
    # fresh compiled scan shape (measured ~5-7 s recompiles per drain at
    # density rates).  Padded rows are all-zero/inert — no pod references
    # them.
    sm, sd, sy = _pow2(len(m_tab.sigs)), _pow2(len(d_tab.sigs)), \
        _pow2(len(y_tab.sigs))

    match_cnt = np.zeros((sm, n), np.float32)
    match_total = np.zeros(sm, np.float32)
    decl_reach = np.zeros((sd, n), bool)
    sym_cnt = np.zeros((sy, n), np.float32)
    if resident is not None:
        # -- the resident side, off the kept planes ---------------------
        if ep is not None:
            for si, sig in enumerate(m_tab.sigs):
                match_cnt[si], match_total[si] = \
                    resident.match_row(sig, ep, space)
        for si, sig in enumerate(decl_live):
            decl_reach[si] = resident.node_row(resident.decl, sig) > 0
    else:
        # -- the resident side, from nothing -----------------------------
        if ep is not None:
            for si, sig in enumerate(m_tab.sigs):
                me = _sig_match_existing(sig, ep, space)
                if not me.any():
                    continue
                nidxs = ep.node_idx[me]
                match_total[si] = float(len(nidxs))
                krow = m_rows[si]
                for ni in nidxs:
                    match_cnt[si] += dt.same_topo_row(node_dom, krow, int(ni))
        for sig, nidxs in decl_sources.items():
            si = d_tab.sig_to_idx[sig]
            for ni in set(nidxs):
                decl_reach[si] |= dt.same_topo_row(node_dom, d_rows[si], ni)

    # -- per-pod incidence matrices --------------------------------------
    aff_need = np.zeros((p, sm), bool)
    aff_self = np.zeros((p, sm), bool)
    anti_need = np.zeros((p, sm), bool)
    pref_w = np.zeros((p, sm), np.float32)
    match_src = np.zeros((p, sm), bool)
    decl_match = np.zeros((p, sd), bool)
    decl_src = np.zeros((p, sd), bool)
    sym_match = np.zeros((p, sy), bool)
    sym_src = np.zeros((p, sy), bool)

    # Candidate-vs-sig matching memoized by (namespace, labels) template:
    # pods stamped from one controller share labels, so each template is
    # matched against each sig family once.
    def matches(pod: api.Pod, sigs: list, cache: dict) -> np.ndarray:
        tkey = (pod.namespace, tuple(sorted(pod.labels.items())))
        row = cache.get(tkey)
        if row is None:
            row = cache[tkey] = np.array(
                [_pod_matches_sig(s, pod.namespace, pod.labels)
                 for s in sigs] or [False], bool)
        return row

    m_cache: dict = {}
    d_cache: dict = {}
    for i, pod in enumerate(cand):
        for si, kind in pod_m[i]:
            if kind == "aff":
                aff_need[i, si] = True
            else:
                anti_need[i, si] = True
        for si in pod_decl[i]:
            decl_src[i, si] = True
        row = matches(pod, m_tab.sigs, m_cache)
        match_src[i, :len(row)] = row[:sm]
        row = matches(pod, d_tab.sigs, d_cache)
        decl_match[i, :len(row)] = row[:sd]
        # Self-match escape hatch (predicates.go:1038-1048).
        for si, kind in pod_m[i]:
            if kind == "aff" and match_src[i, si]:
                aff_self[i, si] = True

    # -- the score side: sym rows, preferred weights, sym incidence ------
    with stage("compile.affinity.prio"):
        if resident is not None:
            for si, sig in enumerate(sym_live):
                sym_cnt[si] = resident.node_row(resident.sym, sig)
        else:
            for sig, nidxs in sym_sources.items():
                si = y_tab.sig_to_idx[sig]
                for ni in nidxs:  # one instance per declaring occurrence
                    sym_cnt[si] += dt.same_topo_row(node_dom, y_rows[si],
                                                    ni)
        y_cache: dict = {}
        for i, pod in enumerate(cand):
            for si, w in pod_pref[i]:
                pref_w[i, si] += w
            for si in pod_sym[i]:
                sym_src[i, si] = True
            row = matches(pod, y_tab.sigs, y_cache)
            sym_match[i, :len(row)] = row[:sy]
        sym_w = _pad1([s.weight for s in y_tab.sigs], sy, 0, np.float32)
    if resident is not None and resident._counted:
        for family, tab in (("match", m_tab), ("decl", d_tab),
                            ("sym", y_tab)):
            _LAUNCH_SIGNATURES[family].inc(len(tab.sigs))

    if tpl_idx is not None:
        # Expand template rows back to the full pod axis.
        aff_need, aff_self, anti_need, pref_w, match_src = (
            a[tpl_idx] for a in (aff_need, aff_self, anti_need, pref_w,
                                 match_src))
        decl_match, decl_src = decl_match[tpl_idx], decl_src[tpl_idx]
        sym_match, sym_src = sym_match[tpl_idx], sym_src[tpl_idx]

    return AffinityTensors(
        node_dom=node_dom,
        n_default=np.int32(dt.n_default),
        match_key=_pad1(m_rows, sm, -1, np.int32),
        match_cnt=match_cnt, match_total=match_total, match_src=match_src,
        aff_need=aff_need, aff_self=aff_self, anti_need=anti_need,
        pref_w=pref_w,
        decl_key=_pad1(d_rows, sd, -1, np.int32),
        decl_reach=decl_reach, decl_match=decl_match, decl_src=decl_src,
        sym_key=_pad1(y_rows, sy, -1, np.int32),
        sym_w=sym_w,
        sym_cnt=sym_cnt, sym_match=sym_match, sym_src=sym_src,
        has_any=any_affinity)
