"""The plain reference against hand-worked cases of upstream's integer
arithmetic (its functions of a pod's four attributes; the by-index
interface over them is held to the same cases in
``test_plug_points.py``)."""

import numpy as np

import cluster
import reference


def _fleet():
    return cluster.Nodes({"count": 4, "profile": "uniform", "milli_cpu": 4000,
                          "memory": 8 * 1024 ** 3, "pods": 2}, 0)


def test_least_requested_and_balanced_integers():
    state = reference.State(_fleet())
    state.use(0, 2000, 4 * 1024 ** 3)       # half full both ways
    state.use(1, 3000, 1 * 1024 ** 3)       # lopsided
    sc = reference.score_points(state, 1000, 1024 ** 3, -1)
    # node 0: cpu (4000-3000)*10//4000 = 2, mem (8-5)*10//8 = 3 -> 2;
    #         balanced 10 - |0.75-0.625|*10 = 8.75 -> 8
    assert sc[0] == 2 + 8
    # node 1: cpu 0, mem 7 -> 3; cpu fraction 1.0 -> balanced 0
    assert sc[1] == 3 + 0
    # empty nodes: cpu 7, mem 8 -> 7; balanced 10 - 1.25 -> 8
    assert sc[2] == sc[3] == 7 + 8


def test_fit_counts_pods_cpu_memory_and_selector():
    nodes = _fleet()
    nodes.pool = np.array([0, 1, 0, 1])
    state = reference.State(nodes)
    state.use(0, 100, 100)
    state.use(0, 100, 100)                  # node 0 holds its 2 pods
    state.use(2, 3950, 100)                 # node 2 has 50m left
    assert reference.fit_mask(state, 100, 100, -1).tolist() == \
        [False, True, False, True]
    assert reference.fit_mask(state, 50, 100, 0).tolist() == \
        [False, False, True, False]
    assert reference.best_of(state, 100, 100, 0, -1).size == 0


def test_gap_is_zero_on_a_best_node_and_inf_where_nothing_fits():
    state = reference.State(_fleet())
    state.use(0, 2000, 4 * 1024 ** 3)
    best = reference.best_of(state, 1000, 1024 ** 3, -1, -1)
    assert best.tolist() == [1, 2, 3]
    assert reference.gap_of(state, 1000, 1024 ** 3, -1, -1, 2) == 0
    assert reference.gap_of(state, 1000, 1024 ** 3, -1, -1, 0) == 5
    assert reference.gap_of(state, 5000, 1, -1, -1, 1) == float("inf")


def test_zone_preference_adds_ten():
    nodes = _fleet()
    nodes.zone = np.array([0, 1, 2, 3])
    state = reference.State(nodes)
    assert reference.best_of(state, 100, 100, -1, 2).tolist() == [2]
