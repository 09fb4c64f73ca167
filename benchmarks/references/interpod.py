"""``interpod``: ``reference.py`` plus MatchInterPodAffinity for the one
term ``shapes/interpod.py``'s pods carry — a REQUIRED anti-affinity on
the hostname against the pods of their own label group.

Every pod of a group both carries the term and matches it, so the two
directions of the predicate (the candidate's own term against the pods a
node holds, predicates.go:1038-1068; the terms of the pods a node holds
against the candidate, predicates.go:1000-1035) are one test: a node that
holds a pod of the group does not fit.  The topology is the hostname, so
a domain is a node.  A required term does not enter
InterPodAffinityPriority (only preferred terms and, symmetrically,
required AFFINITY terms do), so the scores are ``reference.py``'s.

``GUARANTEES`` = ``reference.py``'s + ``antiaffinity_violations``: no
node ever holds two pods of one group.  NumPy only; nothing of the
program.
"""

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location("reference", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "reference.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

GUARANTEES = base.GUARANTEES + ("antiaffinity_violations",)
scores = base.scores


class State(base.State):
    """``reference.py``'s state plus ``held[group, node]``: pods of the
    group bound on the node."""

    def __init__(self, nodes, pods):
        super().__init__(nodes, pods)
        self.held = np.zeros((pods.n_groups, nodes.n), np.int64)

    def copy(self):
        out = super().copy()
        out.held = self.held.copy()
        return out

    def add(self, pod, node, sign=1):
        super().add(pod, node, sign)
        self.held[self.pods.group[pod], node] += sign


def fits(state, pod):
    return base.fits(state, pod) & (state.held[state.pods.group[pod]] == 0)


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
    return dict(base.broken(state, pod, node), antiaffinity_violations=int(
        state.held[state.pods.group[pod], node] > 0))
