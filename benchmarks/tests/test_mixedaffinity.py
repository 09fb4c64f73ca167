"""``shapes/mixedaffinity.py`` and ``references/mixedaffinity.py``
(configuration ``mixedaffinity-5000n``): hand-worked cases for the
reference beside ``test_reference.py``'s and ``test_interpod.py``'s, and
tiny runs of the configuration's own files through the whole served path
on the CPU."""

import json
import os

import numpy as np

import run
from conftest import drive

NODES = {"count": 6, "profile": "uniform", "milli_cpu": 4000,
         "memory": 32 * 1024 ** 3, "pods": 110, "n_zones": 3}
PATTERN = ["base", "blue", "green", "red", "yellow", "base", "blue", "green",
           "red", "yellow", "base"]
PODS = {"milli_cpu": 100, "memory": 500 * 1024 ** 2, "pattern": PATTERN}
# a pod index of each template; + 5, + 11 and + 16 are of the same one
BASE, BLUE, GREEN, RED, YELLOW = range(5)
AFFINITY_KEY = "scheduler.alpha.kubernetes.io/affinity"


def _parts(nodes_spec=NODES, pods_spec=PODS, grow=44):
    shapes = run.load_module("shapes", "mixedaffinity")
    ref = run.load_module("references", "mixedaffinity")
    nodes = shapes.Nodes(nodes_spec, 1)
    pods = shapes.Pods(pods_spec, 1, nodes_spec)
    pods.grow(grow)
    return ref, nodes, pods


def _term(color, key="kubernetes.io/hostname"):
    return {"labelSelector": {"matchLabels": {"color": color}},
            "namespaces": ["default"], "topologyKey": key}


def test_the_pods_are_upstreams_five_templates():
    _ref, nodes, pods = _parts()
    assert pods.n_groups == 5
    assert pods.group[:12].tolist() == [0, 1, 2, 3, 4, 0, 1, 2, 3, 4, 0, 0]
    made = [json.loads(pods.json_bytes(i)) for i in range(5)]
    assert [p["metadata"]["labels"] for p in made] == [
        {}, {"color": "blue"}, {"color": "green", "name": "test"},
        {"color": "red"}, {"color": "yellow"}]
    assert made[BASE]["metadata"]["annotations"] == {}
    affinity = [json.loads(p["metadata"]["annotations"][AFFINITY_KEY])
                for p in made[1:]]
    required = "requiredDuringSchedulingIgnoredDuringExecution"
    preferred = "preferredDuringSchedulingIgnoredDuringExecution"
    assert affinity == [
        {"podAffinity": {required: [
            _term("blue", "topology.kubernetes.io/zone")]}},
        {"podAntiAffinity": {required: [_term("green")]}},
        {"podAffinity": {preferred: [
            {"weight": 1, "podAffinityTerm": _term("red")}]}},
        {"podAntiAffinity": {preferred: [
            {"weight": 1, "podAffinityTerm": _term("yellow")}]}}]
    for pod in made:
        assert list(pod) == ["metadata", "status", "spec"]
        assert pod["status"] == {"phase": "Pending"}
        assert pod["spec"]["containers"][0]["resources"]["requests"] == \
            {"cpu": "100m", "memory": "524288000"}
    assert pods.json_bytes(3).endswith(b"}}")
    assert [item["metadata"]["name"] for item in
            json.loads(pods.list_body(2, 5))["items"]] == ["p-2", "p-3", "p-4"]


def test_the_nodes_carry_upstreams_zone_key_and_no_failure_domain_label():
    _ref, nodes, _pods = _parts()
    assert nodes.zone.tolist() == [0, 1, 2, 0, 1, 2]
    labels = [n["metadata"]["labels"] for n in nodes.to_json()]
    assert labels[4] == {"kubernetes.io/hostname": "node-4",
                         "topology.kubernetes.io/zone": "zone2"}
    one = run.load_module("shapes", "mixedaffinity").Nodes(
        dict(NODES, n_zones=1), 1)         # the configuration: one value
    assert {n["metadata"]["labels"]["topology.kubernetes.io/zone"]
            for n in one.to_json()} == {"zone1"}
    assert not one.zone.any()


def test_the_first_blue_pod_fits_anywhere_the_next_only_in_its_zone():
    ref, nodes, pods = _parts()
    state = ref.State(nodes, pods)
    assert ref.fits(state, BLUE).all()                   # the escape
    assert ref.broken(state, BLUE, 4)["affinity_violations"] == 0
    state.add(BLUE, 1)                                   # zone 1: nodes 1, 4
    blue2 = BLUE + 5
    assert ref.fits(state, blue2).tolist() == \
        [False, True, False, False, True, False]
    assert ref.broken(state, blue2, 4)["affinity_violations"] == 0
    assert ref.broken(state, blue2, 0) == {
        "selector_violations": 0, "over_allocatable": 0,
        "antiaffinity_violations": 0, "affinity_violations": 1}
    assert ref.score_gap(state, blue2, 0) == float("inf")
    # no other template looks at the blue pod
    for other in (BASE, GREEN, RED, YELLOW):
        assert ref.fits(state, other).all()
    state.add(BLUE, 1, -1)                               # it retires
    assert ref.fits(state, blue2).all()                  # first again


def test_green_repels_green_on_its_node_and_nobody_else():
    ref, nodes, pods = _parts()
    state = ref.State(nodes, pods)
    state.add(GREEN, 3)
    green2 = GREEN + 5
    assert ref.fits(state, green2).tolist() == \
        [True, True, True, False, True, True]
    assert ref.broken(state, green2, 3)["antiaffinity_violations"] == 1
    assert ref.broken(state, green2, 0)["antiaffinity_violations"] == 0
    for other in (BASE, BLUE, RED, YELLOW):
        assert ref.fits(state, other).all()


def test_a_required_anti_affinity_term_holds_in_both_directions():
    """The templates' green pods carry the term AND match it; apart, the
    two directions are two rules: a pod that only DECLARES the term keeps
    matching pods off its node (predicates.go:1000-1035), and may not
    land where a matching pod is (:1052-1058)."""
    ref, nodes, pods = _parts()
    term = pods.terms[GREEN][0]
    pods.labels = [{}, {"color": "purple"}, {"color": "green"}, {}, {}]
    pods.terms = [(), (term,), (), (), ()]
    declares, matches = BLUE, GREEN          # pod indices of groups 1, 2
    state = ref.State(nodes, pods)
    state.add(declares, 2)
    assert ref.fits(state, matches).tolist() == \
        [True, True, False, True, True, True]
    assert ref.fits(state, declares + 5).all()     # purple is not green
    state.add(matches, 5)
    assert ref.fits(state, declares + 5).tolist() == \
        [True, True, True, True, True, False]
    assert ref.fits(state, matches + 5).tolist() == \
        [True, True, False, True, True, True]


def test_the_normalisation_is_anchored_at_zero_on_both_sides():
    ref, _nodes, _pods = _parts()
    points = ref.affinity_points
    assert points(np.array([0, 0, 0])).tolist() == [0, 0, 0]
    assert points(np.array([4, 2, 0])).tolist() == [10, 5, 0]
    # all positive: the minimum stays 0, so a common count is not lost
    assert points(np.array([7, 7, 7])).tolist() == [10, 10, 10]
    assert points(np.array([6, 4, 3])).tolist() == [10, 6, 5]
    # all negative: the maximum stays 0
    assert points(np.array([-2, -2, -2])).tolist() == [0, 0, 0]
    assert points(np.array([-4, -1, 0])).tolist() == [0, 7, 10]
    # both signs: (c + 2) / 5 * 10, truncated
    assert points(np.array([-2, 0, 1, 3])).tolist() == [0, 4, 6, 10]


def test_red_counts_twice_yellow_counts_twice_the_other_way():
    """A bound red pod scores a red candidate once through the
    candidate's own preferred term and once through its own (the
    symmetric part); yellow the same with the other sign."""
    ref, nodes, pods = _parts()
    state = ref.State(nodes, pods)
    state.add(RED, 2)
    state.add(RED + 5, 2)
    state.add(RED + 11, 4)
    state.add(YELLOW, 0)
    assert ref.affinity_counts(state, RED + 16).tolist() == [0, 0, 4, 0, 2, 0]
    assert ref.affinity_counts(state, YELLOW + 5).tolist() == \
        [-2, 0, 0, 0, 0, 0]
    for other in (BASE, BLUE, GREEN):
        assert not ref.affinity_counts(state, other).any()
    base = ref.base.scores(state, RED + 16)
    assert (ref.scores(state, RED + 16) - base).tolist() == \
        [0, 0, 10, 0, 5, 0]
    base = ref.base.scores(state, YELLOW + 5)
    assert (ref.scores(state, YELLOW + 5) - base).tolist() == \
        [0, 10, 10, 10, 10, 10]
    assert ref.best_nodes(state, RED + 16).tolist() == [2]
    assert 0 not in ref.best_nodes(state, YELLOW + 5)
    assert ref.score_gap(state, RED + 16, 4) == 5.0


def test_a_bound_blue_pod_scores_blue_candidates_by_its_zone():
    """The symmetric required-affinity term at the hard weight (1): every
    node of a blue pod's zone counts it; with ONE zone, as the
    configuration has it, every node reads the same and nothing moves."""
    ref, nodes, pods = _parts()
    state = ref.State(nodes, pods)
    state.add(BLUE, 1)
    state.add(BLUE + 5, 4)
    state.add(BLUE + 11, 2)
    assert ref.affinity_counts(state, BLUE + 16).tolist() == \
        [0, 2, 1, 0, 2, 1]
    assert ref.affinity_points(
        ref.affinity_counts(state, BLUE + 16)).tolist() == [0, 10, 5, 0, 10, 5]
    one_ref, one_nodes, one_pods = _parts(dict(NODES, n_zones=1))
    state = one_ref.State(one_nodes, one_pods)
    state.add(BLUE, 1)
    assert one_ref.affinity_points(
        one_ref.affinity_counts(state, BLUE + 5)).tolist() == [10] * 6


def test_red_pods_gather_on_one_node_until_it_is_full():
    ref, nodes, pods = _parts(dict(NODES, count=3, pods=5, n_zones=1),
                              dict(PODS, pattern=["red"]), grow=12)
    state = ref.State(nodes, pods)
    assert ref.best_nodes(state, 0).tolist() == [0, 1, 2]    # nothing yet
    state.add(0, 1)
    for pod in range(1, 5):
        assert ref.best_nodes(state, pod).tolist() == [1]
        state.add(pod, 1)
    assert ref.fits(state, 5).tolist() == [True, False, True]    # 5 pods
    assert ref.best_nodes(state, 5).tolist() == [0, 2]
    state.add(5, 2)
    assert ref.best_nodes(state, 6).tolist() == [2]
    # on upstream's node the resources give way first: the 10 points of
    # the fullest red node are worth 32 pause pods of LeastRequested +
    # BalancedResourceAllocation, after which an empty node ties with it
    ref, nodes, pods = _parts(dict(NODES, count=3, n_zones=1),
                              dict(PODS, pattern=["red"]), grow=40)
    state = ref.State(nodes, pods)
    for pod in range(32):
        assert ref.best_nodes(state, pod).tolist() == \
            ([0, 1, 2] if pod == 0 else [0])
        state.add(pod, 0)
    assert ref.best_nodes(state, 32).tolist() == [0, 1, 2]


def test_guarantees_are_interpods_and_the_affinity_one():
    ref, _nodes, _pods = _parts()
    assert ref.GUARANTEES == ("selector_violations", "over_allocatable",
                              "antiaffinity_violations",
                              "affinity_violations")


NEW_METRICS = {
    "affinity.scored_pods_share": ("ratio", "higher", "program_counter"),
    "affinity.signatures_per_launch": ("ratio", "lower", "program_counter"),
    "affinity.plane_cells_per_pod": ("ratio", "lower", "program_counter"),
    "affinity.prio_build_ms_per_kpod": ("seconds_per_kpod", "lower",
                                        "program_span"),
}


def _read_metric(name: str, before: str, after: str, pods_bound=1000):
    import rig
    spec = run.load_json(os.path.join(run.HERE, "metrics", name + ".json"))
    ctx = {"daemon": (rig.parse_metrics(before), rig.parse_metrics(after)),
           "apiserver": ({}, {}), "runner": {}, "pods_bound": pods_bound,
           "trace": None, "config": {}}
    return run.load_module("readers", spec["arithmetic"]).read(
        spec["args"], ctx)


def test_new_metrics_are_entries_at_the_end_files_and_existing_readers():
    import rig
    bench = run.load_json(os.path.join(rig.REPO, "BENCHMARK.json"))
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW_METRICS)
    for entry in bench["per_layer"][-4:]:
        arithmetic, better, source = NEW_METRICS[entry["name"]]
        spec = run.load_json(os.path.join(run.HERE, "metrics",
                                          entry["name"] + ".json"))
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], key
        assert (spec["arithmetic"], entry["better"], entry["source"]) == \
            (arithmetic, better, source)
        assert entry["workloads"] == ["interpod5k-arrivals",
                                      "mixedaffinity5k-arrivals"]
        assert entry["layer"] == "feature build"
        assert entry["moves"] == "submit_to_bind_p50_ms"
    # the cell joined the lists interpod5k-arrivals had joined, but the
    # two lists of PR 31 / PR 33 that tests pin with ==
    joined = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if "mixedaffinity5k-arrivals" in m.get("workloads", ())]
    assert len(joined) == 2 + 24 + 3 + 4
    cell = run.Cell(bench, "mixedaffinity5k-arrivals")
    assert len(cell.per_layer()) == 24 + 4 + 3 + 4    # 4 have no list


def test_new_metrics_read_the_counters_and_nothing_from_a_page_without():
    page = """scheduler_affinity_priority_pods_total {scored}
scheduler_affinity_plane_cells_total {cells}
scheduler_affinity_launch_signatures_total{{family="match"}} {match}
scheduler_affinity_launch_signatures_total{{family="decl"}} {decl}
scheduler_affinity_launch_signatures_total{{family="sym"}} {sym}
scheduler_batch_stage_latency_microseconds_count{{stage="solve"}} {solves}
scheduler_batch_stage_latency_microseconds_sum{{stage="compile.affinity.prio"}} {prio_us}
"""
    before = page.format(scored=500, cells=100, match=40, decl=10, sym=30,
                         solves=10, prio_us=1e6)
    after = page.format(scored=1500, cells=3100, match=120, decl=30, sym=90,
                        solves=30, prio_us=1.5e6)
    assert _read_metric("affinity.scored_pods_share", before, after) == 1.0
    assert _read_metric("affinity.plane_cells_per_pod", before, after) == 3.0
    assert _read_metric("affinity.signatures_per_launch", before,
                        after) == 8.0
    assert _read_metric("affinity.prio_build_ms_per_kpod", before,
                        after) == 500.0
    # the parent's program has none of the four: nothing to read, no 0
    old = 'scheduler_batch_stage_latency_microseconds_count' \
        '{stage="solve"} 10\n'
    for name in NEW_METRICS:
        assert _read_metric(name, old, old) is None, name


def _add_tiny_mixed(tree: str) -> None:
    """The configuration's own file at 600 nodes and 330 resident pods
    (3 : 2 : 2 : 2 : 2), a ladder of 512 and 256 and a slow mix; and a
    twin whose pattern STARTS with two green pods, for the one planted
    fault that needs two of them side by side (``colocate``: upstream's
    pattern never has two)."""
    bench_dir = os.path.join(tree, "benchmarks")
    with open(os.path.join(bench_dir, "configs",
                           "mixedaffinity-5000n.json")) as f:
        config = json.load(f)
    config.update(name="tiny-mixed", resident_cap=330,
                  judge={"sample": 200, "lag_step": 20, "max_lag_s": 2.0},
                  limits={"gap_mean": 0.5, "gap_max": 6.0},
                  nodes=dict(config["nodes"], count=600))
    config["daemon"]["env"]["KT_STREAM_CHUNK"] = "512"
    pair = json.loads(json.dumps(config))
    pair["name"] = "tiny-mixed-pair"
    pair["pods"]["pattern"] = ["green", "green", "base", "blue", "red",
                               "yellow", "base", "blue", "red", "yellow",
                               "base"]
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    if any(c["name"] == "tiny-mixed" for c in bench["configs"]):
        return
    with open(os.path.join(bench_dir, "traffic", "tiny-mixed-open.json"),
              "w") as f:
        json.dump({"kind": "poisson_open", "rate_pods_s": 100,
                   "steady_pending_s": 0.5}, f)
    for cfg in (config, pair):
        name = cfg["name"]
        with open(os.path.join(bench_dir, "configs", name + ".json"),
                  "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({
            "name": name, "source": "tests", "reduced": ["count"],
            "file": f"benchmarks/configs/{name}.json", "why": "tests"})
        bench["workloads"].append({
            "name": name + "-open", "config": name,
            "traffic": "tiny-mixed-open", "chips": 1, "why": "tests"})
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if "mixedaffinity5k-arrivals" in metric.get("workloads", ()):
                metric["workloads"].append(name + "-open")
    with open(path, "w") as f:
        json.dump(bench, f)


def _over(result: dict) -> set:
    return {k for k, v in result["compared"].items()
            if v["value"] > v["limit"]}


def test_tiny_run_against_the_reference_scheduler_and_its_faults(tiny_tree):
    """The plain reference in the daemon's place: sound it is
    ``correct``; with the lowest-scoring node picked it is not, by the
    gap; with two green pods on one node it is not, by that number
    alone."""
    _add_tiny_mixed(tiny_tree)
    sound = drive(tiny_tree, "tiny-mixed-open", seed=2147483747, seconds=3.0)
    assert sound["correct"] is True, sound["compared"]
    assert sound["attempted"] > 200 and sound["failed"] == 0
    for name in ("antiaffinity_violations", "affinity_violations",
                 "over_allocatable"):
        assert sound["compared"][name] == {"value": 0, "limit": 0}
    assert sound["compared"]["gap_mean"]["value"] == 0.0

    wrong = drive(tiny_tree, "tiny-mixed-open", seed=44, seconds=3.0,
                  fault="wrong_policy")
    assert wrong["correct"] is False
    assert {"gap_mean", "gap_max"} <= _over(wrong), wrong["compared"]

    pair = drive(tiny_tree, "tiny-mixed-pair-open", seed=43, seconds=3.0,
                 fault="colocate")
    assert pair["correct"] is False
    assert _over(pair) == {"antiaffinity_violations"}, pair["compared"]


def test_tiny_run_of_the_program_is_correct(tiny_tree):
    """The program itself, on the CPU at 600 nodes, through the whole
    served path with the five templates: every number compared inside its
    limit, both affinity guarantees among them, and no program compiled
    after prewarm (the resident pods hold the four coloured templates, so
    the sample pins both affinity flags and the signature axes)."""
    _add_tiny_mixed(tiny_tree)
    for attempt in (1, 2):
        try:
            program = drive(tiny_tree, "tiny-mixed-open", seed=2147483749,
                            seconds=3.0, fault=None)
            break
        except AssertionError as err:
            # the CPU daemon's abort AT EXIT (PERF.md section 7 entry 10e)
            if attempt == 2 or "did not exit 0 on SIGTERM" not in str(err):
                raise
    assert program["correct"] is True, program["compared"]
    for name in ("antiaffinity_violations", "affinity_violations"):
        assert program["compared"][name] == {"value": 0, "limit": 0}
    assert program["attempted"] > 200 and program["failed"] == 0
    with open(os.path.join(
            tiny_tree, "benchmarks", "out",
            "tiny-mixed-open.2147483749.0", "daemon.log")) as f:
        log = f.read()
    assert "pre-warmed stream ladder" in log
    assert "post-prewarm XLA compile" not in log
