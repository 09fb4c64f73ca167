"""The plain reference scheduler for the benchmark's configurations.

Upstream's DefaultProvider (``plugin/pkg/scheduler/algorithmprovider/
defaults/defaults.go``) restricted to what ``cluster.py``'s pods and nodes
can exercise: ready, untainted nodes; pods with cpu/memory requests, an
optional node selector on the pool label and an optional *preferred* zone
node-affinity of weight 10; no services, volumes, host ports, taints or
inter-pod affinity.  On such inputs

  fit    = PodFitsResources (pods, cpu, memory) and the node selector
  score  = LeastRequested + BalancedResourceAllocation + NodeAffinity
           (+ SelectorSpread 10 + TaintToleration 10 on every node)

and the reference answer for a pod is the SET of fitting nodes with the
highest score (upstream breaks ties in map order, so parity is
membership).  Integer arithmetic as upstream's (priorities.go:81-149,
271-317; node_affinity.go:32-86).  NumPy only; imports nothing of the
program and takes nothing the program has made.

THE INTERFACE OF A REFERENCE (``references/<word>.py`` for a
configuration that names ``"reference": "<word>"``; this file where it
names none).  Everything is asked BY POD INDEX ``pod`` (pod ``p-<pod>``
of the configuration's shapes, ``cluster.py``'s docstring) and node
index; what a pod or a node IS the reference reads off the shapes'
arrays, its own ones included.  ``run.prefill``, ``judge.py`` and
``refsched.py`` use nothing else:

  ``State(nodes, pods)``          nothing bound yet
  ``state.add(pod, node, sign=1)``  bind ``pod`` on ``node`` (``-1``: take it away)
  ``state.copy()``                an independent copy
  ``fits(state, pod)``            boolean per node
  ``scores(state, pod)``          integer per node (what differs between nodes)
  ``best_nodes(state, pod)``      indices of the answer set, empty = fits nowhere
  ``score_gap(state, pod, node)`` best fitting score minus ``node``'s, ``inf``
                                  where ``node`` does not fit
  ``GUARANTEES``                  names of the exact numbers (limit 0) the
                                  judge counts over EVERY bind of a run and
                                  over the apiserver's list at close
  ``broken(state, pod, node)``    ``{name: 0 | 1}`` for each of them, asked
                                  BEFORE ``state.add(pod, node)``

The arithmetic itself stays in functions of a pod's four attributes
(``fit_mask``, ``score_points``, ``best_of``, ``gap_of``), which the
hand-worked cases of ``tests/test_reference.py`` hold to upstream's
integers; the interface above looks the attributes up and calls them.
"""

from __future__ import annotations

import numpy as np

MAX_PRIORITY = 10
GUARANTEES = ("selector_violations", "over_allocatable")


class State:
    """What is bound where: pods, milli-cpu and bytes in use per node."""

    def __init__(self, nodes, pods=None):
        self.nodes, self.pods = nodes, pods
        self.cnt = np.zeros(nodes.n, np.int64)
        self.cpu = np.zeros(nodes.n, np.int64)
        self.mem = np.zeros(nodes.n, np.int64)

    def copy(self) -> "State":
        out = type(self).__new__(type(self))
        out.nodes, out.pods = self.nodes, self.pods
        out.cnt, out.cpu, out.mem = \
            self.cnt.copy(), self.cpu.copy(), self.mem.copy()
        return out

    def use(self, node: int, cpu: int, mem: int, sign: int = 1) -> None:
        self.cnt[node] += sign
        self.cpu[node] += sign * cpu
        self.mem[node] += sign * mem

    def add(self, pod: int, node: int, sign: int = 1) -> None:
        self.use(node, int(self.pods.cpu[pod]), int(self.pods.mem[pod]), sign)


# -- the interface, by pod index ----------------------------------------------

def _attrs(state: State, pod: int) -> tuple[int, int, int, int]:
    p = state.pods
    return int(p.cpu[pod]), int(p.mem[pod]), int(p.sel[pod]), int(p.aff[pod])


def fits(state: State, pod: int) -> np.ndarray:
    cpu, mem, sel, _aff = _attrs(state, pod)
    return fit_mask(state, cpu, mem, sel)


def scores(state: State, pod: int) -> np.ndarray:
    cpu, mem, _sel, aff = _attrs(state, pod)
    return score_points(state, cpu, mem, aff)


def best_nodes(state: State, pod: int) -> np.ndarray:
    return best_of(state, *_attrs(state, pod))


def score_gap(state: State, pod: int, node: int) -> float:
    return gap_of(state, *_attrs(state, pod), node)


def broken(state: State, pod: int, node: int) -> dict:
    """Which guarantees binding ``pod`` on ``node`` breaks, asked before
    the bind is added: the node would hold more than its allocatable
    pods / cpu / memory; the pod's selector names another pool."""
    cpu, mem, sel, _aff = _attrs(state, pod)
    nd = state.nodes
    over = (state.cnt[node] + 1 > nd.alloc_pods[node]
            or state.cpu[node] + cpu > nd.alloc_cpu[node]
            or state.mem[node] + mem > nd.alloc_mem[node])
    return {"selector_violations": int(sel >= 0 and nd.pool[node] != sel),
            "over_allocatable": int(over)}


# -- the arithmetic, by a pod's attributes ------------------------------------

def fit_mask(state: State, cpu: int, mem: int, sel: int) -> np.ndarray:
    """Boolean per node: the pod fits (predicates.go:444-485 and the
    node selector)."""
    nd = state.nodes
    ok = ((state.cnt + 1 <= nd.alloc_pods)
          & (state.cpu + cpu <= nd.alloc_cpu)
          & (state.mem + mem <= nd.alloc_mem))
    if sel >= 0:
        ok &= nd.pool == sel
    return ok


def score_points(state: State, cpu: int, mem: int, aff: int) -> np.ndarray:
    """Per node, the part of the DefaultProvider score that differs
    between nodes."""
    nd = state.nodes
    want_cpu = state.cpu + cpu
    want_mem = state.mem + mem

    def unused(want, cap):
        s = ((cap - want) * MAX_PRIORITY) // np.maximum(cap, 1)
        return np.where((cap == 0) | (want > cap), 0, s)

    least = (unused(want_cpu, nd.alloc_cpu)
             + unused(want_mem, nd.alloc_mem)) // 2
    cf = np.where(nd.alloc_cpu == 0, 1.0, want_cpu / np.maximum(nd.alloc_cpu, 1))
    mf = np.where(nd.alloc_mem == 0, 1.0, want_mem / np.maximum(nd.alloc_mem, 1))
    balanced = np.where((cf >= 1) | (mf >= 1), 0,
                        (MAX_PRIORITY - np.abs(cf - mf) * MAX_PRIORITY)
                        .astype(np.int64))
    total = least + balanced
    if aff >= 0:
        total = total + np.where(nd.zone == aff, MAX_PRIORITY, 0)
    return total


def best_of(state: State, cpu: int, mem: int, sel: int, aff: int
            ) -> np.ndarray:
    """Indices of the reference's answer set (empty: nothing fits)."""
    ok = fit_mask(state, cpu, mem, sel)
    if not ok.any():
        return np.zeros(0, np.int64)
    sc = np.where(ok, score_points(state, cpu, mem, aff), -1)
    return np.flatnonzero(sc == sc.max())


def gap_of(state: State, cpu: int, mem: int, sel: int, aff: int,
           chosen: int) -> float:
    """How far the chosen node's score lies below the reference's best,
    in score points; ``inf`` when the chosen node does not fit."""
    ok = fit_mask(state, cpu, mem, sel)
    if not ok[chosen]:
        return float("inf")
    sc = score_points(state, cpu, mem, aff)
    return float(np.where(ok, sc, -1).max() - sc[chosen])
