"""The three plug points of a configuration: its shapes file, its
reference file and the reference's guarantees.  NumPy only, no JAX, no
subprocess.

* ``reference.py`` through the by-index interface (what ``run.prefill``,
  ``judge.py`` and ``refsched.py`` call) gives, on the hand-worked cases
  of ``test_reference.py``, what its functions of a pod's four attributes
  give;
* a configuration that names a missing ``shapes`` / ``reference`` word
  fails with ``RunFailure`` naming the file; one without the keys gets
  ``cluster.py`` / ``reference.py``;
* the judge counts every name of the reference's ``GUARANTEES`` over every
  bind of the record and over the list at close, whatever the names are.
"""

import types

import numpy as np
import pytest

import cluster
import judge
import reference
import rig
import run
from loadgen import BIND, DELETE

GI = 1024 ** 3
# (cpu, mem, sel, aff) of pods 0..5: the hand-worked cases' pods
PODS = [(1000, GI, -1, -1), (100, 100, -1, -1), (50, 100, 0, -1),
        (100, 100, 0, -1), (5000, 1, -1, -1), (100, 100, -1, 2)]


def _fleet(pool=(-1, -1, -1, -1), zone=(-1, -1, -1, -1)):
    nodes = cluster.Nodes({"count": 4, "profile": "uniform", "milli_cpu": 4000,
                           "memory": 8 * GI, "pods": 2}, 0)
    nodes.pool, nodes.zone = np.array(pool), np.array(zone)
    return nodes


class _Pods:
    def __init__(self):
        self.cpu, self.mem, self.sel, self.aff = np.array(PODS, np.int64).T

    def __len__(self) -> int:
        return len(self.cpu)


def _half_full():
    state = reference.State(_fleet(), _Pods())
    state.use(0, 2000, 4 * GI)
    state.use(1, 3000, 1 * GI)
    return state


def _crowded():
    state = reference.State(_fleet(pool=(0, 1, 0, 1)), _Pods())
    state.use(0, 100, 100)
    state.use(0, 100, 100)
    state.use(2, 3950, 100)
    return state


def _zoned():
    return reference.State(_fleet(zone=(0, 1, 2, 3)), _Pods())


CASES = [(_half_full, 0), (_half_full, 4), (_crowded, 1), (_crowded, 2),
         (_crowded, 3), (_zoned, 5), (_zoned, 1)]


@pytest.mark.parametrize("make, pod", CASES)
def test_by_index_gives_what_the_four_attribute_functions_give(make, pod):
    state = make()
    cpu, mem, sel, aff = PODS[pod]
    assert (reference.fits(state, pod)
            == reference.fit_mask(state, cpu, mem, sel)).all()
    assert (reference.scores(state, pod)
            == reference.score_points(state, cpu, mem, aff)).all()
    assert (reference.best_nodes(state, pod).tolist()
            == reference.best_of(state, cpu, mem, sel, aff).tolist())
    for node in range(4):
        assert reference.score_gap(state, pod, node) \
            == reference.gap_of(state, cpu, mem, sel, aff, node)


def test_by_index_reads_the_hand_worked_numbers():
    state = _half_full()
    assert reference.scores(state, 0).tolist() == [10, 3, 15, 15]
    assert reference.best_nodes(state, 0).tolist() == [2, 3]
    assert reference.score_gap(state, 0, 2) == 0
    assert reference.score_gap(state, 0, 0) == 5
    assert reference.score_gap(state, 4, 1) == float("inf")
    crowded = _crowded()
    assert reference.fits(crowded, 1).tolist() == [False, True, False, True]
    assert reference.fits(crowded, 2).tolist() == [False, False, True, False]
    assert reference.best_nodes(crowded, 3).size == 0
    assert reference.best_nodes(_zoned(), 5).tolist() == [2]


def test_add_by_index_is_use_by_attributes_and_a_copy_is_its_own():
    a, b = _half_full(), _half_full()
    a.add(0, 3)
    b.use(3, 1000, GI)
    for name in ("cnt", "cpu", "mem"):
        assert (getattr(a, name) == getattr(b, name)).all()
    c = a.copy()
    c.add(0, 3, -1)
    assert c.cnt[3] == 0 and a.cnt[3] == 1 and c.pods is a.pods
    assert type(c) is type(a)


@pytest.mark.parametrize("pod, node, want", [
    (1, 1, {"selector_violations": 0, "over_allocatable": 0}),
    (1, 0, {"selector_violations": 0, "over_allocatable": 1}),   # 3rd pod of 2
    (1, 2, {"selector_violations": 0, "over_allocatable": 1}),   # 50m left
    (2, 2, {"selector_violations": 0, "over_allocatable": 0}),
    (2, 1, {"selector_violations": 1, "over_allocatable": 0}),   # pool 1, wants 0
    (3, 0, {"selector_violations": 0, "over_allocatable": 1}),
])
def test_broken_is_asked_before_the_bind_is_added(pod, node, want):
    assert reference.broken(_crowded(), pod, node) == want
    assert tuple(want) == reference.GUARANTEES


@pytest.mark.parametrize("key, directory", [("shapes", "shapes"),
                                            ("reference", "references")])
def test_a_missing_word_fails_naming_the_file(key, directory):
    with pytest.raises(rig.RunFailure, match=f"{directory}/nowhere.py"):
        run.parts_of({key: "nowhere"})


def test_without_the_keys_a_configuration_gets_cluster_and_reference():
    config = run.load_json(rig.REPO + "/benchmarks/configs/"
                           "schedperf-5000n.json")
    assert "shapes" not in config and "reference" not in config
    assert run.parts_of(config) == (cluster, reference)
    shapes, ref = run.parts_of(config)
    pods = shapes.Pods(config["pods"], 3, config["nodes"])
    pods.grow(5)
    state = ref.State(shapes.Nodes(config["nodes"], 3), pods)
    assert len(ref.best_nodes(state, 4)) == 5000


def _anti(nodes_n: int):
    """``reference.py`` with one more guarantee, made in memory: no two
    pods on one node."""
    ref = types.SimpleNamespace(**{k: getattr(reference, k) for k in (
        "State", "fits", "scores", "best_nodes", "score_gap")})
    ref.GUARANTEES = reference.GUARANTEES + ("shared_node",)

    def broken(state, pod, node):
        return dict(reference.broken(state, pod, node),
                    shared_node=int(state.cnt[node] > 0))
    ref.broken = broken
    return ref


ACCOUNT = dict({"mode": "device", "platform": "cpu", "last_fault": None,
                "host_mode_seconds": 0.0, "invariant_violations": 0},
               **{family: 0.0 for family in rig.ACCOUNT_FAMILIES})
CONFIG = {"judge": {"sample": 8, "lag_step": 1, "max_lag_s": 2.0},
          "limits": {"gap_mean": 0.1, "gap_max": 4.0}}


@pytest.mark.parametrize("second_node, deleted_first, in_replay, at_close", [
    (1, False, 0, 0),        # sound
    (0, False, 1, 1),        # two pods on node 0, both still listed
    (0, True, 1, 0),         # the pair met in the record, one is gone at close
])
def test_the_judge_counts_every_guarantee_the_reference_names(
        second_node, deleted_first, in_replay, at_close):
    nodes, pods = _fleet(), _Pods()
    events = [(BIND, 1, 0, 1.0), (BIND, 5, second_node, 1.5)]
    listed = {1: 0, 5: second_node}
    if deleted_first:
        events.append((DELETE, 1, 0, 1.8))
        del listed[1]
    book = types.SimpleNamespace(events=events, n_created=6, errors=[])
    listed.update({0: -1, 2: -1, 3: -1, 4: -1})
    correct, numbers, _info = judge.judge(
        _anti(4), nodes, pods, book, 6, listed, (0.0, 2.0), 7, CONFIG,
        ACCOUNT, "cpu")
    assert numbers["shared_node"] == [in_replay + at_close, 0]
    assert numbers["over_allocatable"] == [0, 0]
    assert numbers["selector_violations"] == [0, 0]
    assert numbers["never_bound"] == [4, 0]
    assert list(numbers).index("shared_node") \
        == list(numbers).index("over_allocatable") + 1
    assert correct is False         # four pods were never bound
    numbers.pop("never_bound")
    assert all(v <= lim for v, lim in numbers.values()) \
        == (in_replay + at_close == 0)
