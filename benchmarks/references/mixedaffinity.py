"""``mixedaffinity``: ``reference.py`` plus MatchInterPodAffinity and
InterPodAffinityPriority for the terms ``shapes/mixedaffinity.py``'s five
templates carry — required and preferred, affinity and anti-affinity, on
the hostname and on a zone key.

Nothing here knows a colour: a pod is its group's ``labels`` and
``terms`` (``shapes/mixedaffinity.py`` ``Term``), a term matches the
groups whose labels hold its ``matchLabels`` (one namespace, so the
namespace always matches), and "the pods a term reaches from a node" is a
count per node of the matching pods in the node's topology domain under
the term's key (the node itself for the hostname; the node's zone for
the zone key).

  fit    ``reference.py``'s fit, and (predicates.go:825-853)
         1. no REQUIRED anti-affinity term of a pod already bound that
            matches the candidate reaches the node (:1000-1035);
         2. every REQUIRED affinity term of the candidate reaches a
            matching pod from the node — unless NO pod anywhere matches
            the term and the candidate matches it itself: the first pod
            of a collection is not blocked for ever (:1038-1048);
         3. no REQUIRED anti-affinity term of the candidate reaches a
            matching pod from the node (:1052-1058).
  score  ``reference.py``'s points plus InterPodAffinityPriority at the
         DefaultProvider's weight 1 (interpod_affinity.go:117-260): per
         node the sum of
           +-weight x the pods the candidate's own PREFERRED terms reach
                      (+ affinity, - anti-affinity),
           +-weight x the bound pods whose PREFERRED term matches the
                      candidate and reaches the node,
           hardPodAffinitySymmetricWeight (default 1) x the bound pods
                      whose REQUIRED AFFINITY term matches the candidate
                      and reaches the node,
         then min-max to 0..10 over the ready nodes (all of them) with
         the minimum and the maximum both starting at 0, truncated to an
         integer as the v1.4 source the program follows.

``GUARANTEES`` = ``references/interpod.py``'s + ``affinity_violations``
(a bind that breaks rule 2; ``antiaffinity_violations`` counts a bind
that breaks rule 1 or 3).  NumPy only; nothing of the program.
"""

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location("interpod", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "interpod.py"))
interpod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(interpod)
base = interpod.base

GUARANTEES = interpod.GUARANTEES + ("affinity_violations",)
HARD_POD_AFFINITY_WEIGHT = 1      # the daemon's default, which the cell keeps
MAX_PRIORITY = base.MAX_PRIORITY
State = interpod.State          # reference.py's plus held[group, node]


def _matches(labels: dict, term) -> bool:
    return all(labels.get(k) == v for k, v in term.match.items())


def _in_domain(state, per_node: np.ndarray, key: str) -> np.ndarray:
    """Per node, the sum of ``per_node`` over the node's topology domain
    under ``key``."""
    if key == "hostname":
        return per_node
    zone = state.nodes.zone
    per_zone = np.bincount(zone, weights=per_node,
                           minlength=int(zone.max()) + 1).astype(np.int64)
    return per_zone[zone]


def _reached(state, term) -> tuple[np.ndarray, int]:
    """``(per node the bound pods matching ``term`` that it reaches from
    there, such pods anywhere)``."""
    pods = state.pods
    per_node = np.zeros(state.nodes.n, np.int64)
    for g in range(pods.n_groups):
        if _matches(pods.labels[g], term):
            per_node = per_node + state.held[g]
    return _in_domain(state, per_node, term.key), int(per_node.sum())


def _declared_reach(state, labels: dict, select) -> list:
    """``[(term, per node the bound pods that declare it and reach the
    node)]`` for every term of a bound pod's group that ``select``s and
    matches ``labels``."""
    pods = state.pods
    return [(term, _in_domain(state, state.held[g], term.key))
            for g in range(pods.n_groups) for term in pods.terms[g]
            if select(term) and _matches(labels, term)]


def _rules(state, pod: int) -> tuple[np.ndarray, np.ndarray]:
    """``(anti-affinity holds, affinity holds)`` per node: rules 1 + 3
    and rule 2 of the docstring."""
    pods = state.pods
    g = int(pods.group[pod])
    anti_ok = np.ones(state.nodes.n, bool)
    aff_ok = np.ones(state.nodes.n, bool)
    for _term, reach in _declared_reach(
            state, pods.labels[g], lambda t: t.required and t.anti):
        anti_ok &= reach == 0
    for term in pods.terms[g]:
        if not term.required:
            continue
        reach, anywhere = _reached(state, term)
        if term.anti:
            anti_ok &= reach == 0
        elif not (anywhere == 0 and _matches(pods.labels[g], term)):
            aff_ok &= reach > 0
    return anti_ok, aff_ok


def fits(state, pod):
    anti_ok, aff_ok = _rules(state, pod)
    return base.fits(state, pod) & anti_ok & aff_ok


def affinity_counts(state, pod) -> np.ndarray:
    """InterPodAffinityPriority's raw count per node."""
    pods = state.pods
    g = int(pods.group[pod])
    counts = np.zeros(state.nodes.n, np.int64)
    for term in pods.terms[g]:
        if not term.required:
            sign = -1 if term.anti else 1
            counts += sign * term.weight * _reached(state, term)[0]
    for term, reach in _declared_reach(
            state, pods.labels[g],
            lambda t: not t.required or not t.anti):
        if term.required:
            counts += HARD_POD_AFFINITY_WEIGHT * reach
        else:
            counts += (-1 if term.anti else 1) * term.weight * reach
    return counts


def affinity_points(counts: np.ndarray) -> np.ndarray:
    """0..10 per node: min-max with both ends anchored at 0."""
    top, low = max(int(counts.max()), 0), min(int(counts.min()), 0)
    if top - low <= 0:
        return np.zeros(len(counts), np.int64)
    return (MAX_PRIORITY * ((counts - low) / float(top - low))) \
        .astype(np.int64)


def scores(state, pod):
    return base.scores(state, pod) \
        + affinity_points(affinity_counts(state, pod))


def best_nodes(state, pod):
    ok = fits(state, pod)
    if not ok.any():
        return np.zeros(0, np.int64)
    sc = np.where(ok, scores(state, pod), -1)
    return np.flatnonzero(sc == sc.max())


def score_gap(state, pod, node):
    ok = fits(state, pod)
    if not ok[node]:
        return float("inf")
    sc = scores(state, pod)
    return float(np.where(ok, sc, -1).max() - sc[node])


def broken(state, pod, node):
    anti_ok, aff_ok = _rules(state, pod)
    return dict(base.broken(state, pod, node),
                antiaffinity_violations=int(not anti_ok[node]),
                affinity_violations=int(not aff_ok[node]))
