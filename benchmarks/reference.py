"""The plain reference scheduler for the benchmark's configurations.

Upstream's DefaultProvider (``plugin/pkg/scheduler/algorithmprovider/
defaults/defaults.go``) restricted to what the benchmark's pods and nodes
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
"""

from __future__ import annotations

import numpy as np

MAX_PRIORITY = 10


class State:
    """What is bound where: pods, milli-cpu and bytes in use per node."""

    def __init__(self, nodes):
        self.nodes = nodes
        self.cnt = np.zeros(nodes.n, np.int64)
        self.cpu = np.zeros(nodes.n, np.int64)
        self.mem = np.zeros(nodes.n, np.int64)

    def copy(self) -> "State":
        out = State.__new__(State)
        out.nodes = self.nodes
        out.cnt, out.cpu, out.mem = \
            self.cnt.copy(), self.cpu.copy(), self.mem.copy()
        return out

    def add(self, node: int, cpu: int, mem: int, sign: int = 1) -> None:
        self.cnt[node] += sign
        self.cpu[node] += sign * cpu
        self.mem[node] += sign * mem


def fits(state: State, cpu: int, mem: int, sel: int) -> np.ndarray:
    """Boolean per node: the pod fits (predicates.go:444-485 and the
    node selector)."""
    nd = state.nodes
    ok = ((state.cnt + 1 <= nd.alloc_pods)
          & (state.cpu + cpu <= nd.alloc_cpu)
          & (state.mem + mem <= nd.alloc_mem))
    if sel >= 0:
        ok &= nd.pool == sel
    return ok


def scores(state: State, cpu: int, mem: int, aff: int) -> np.ndarray:
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


def best_nodes(state: State, cpu: int, mem: int, sel: int, aff: int
               ) -> np.ndarray:
    """Indices of the reference's answer set (empty: nothing fits)."""
    ok = fits(state, cpu, mem, sel)
    if not ok.any():
        return np.zeros(0, np.int64)
    sc = np.where(ok, scores(state, cpu, mem, aff), -1)
    return np.flatnonzero(sc == sc.max())


def score_gap(state: State, cpu: int, mem: int, sel: int, aff: int,
              chosen: int) -> float:
    """How far the chosen node's score lies below the reference's best,
    in score points; ``inf`` when the chosen node does not fit."""
    ok = fits(state, cpu, mem, sel)
    if not ok[chosen]:
        return float("inf")
    sc = scores(state, cpu, mem, aff)
    return float(np.where(ok, sc, -1).max() - sc[chosen])
