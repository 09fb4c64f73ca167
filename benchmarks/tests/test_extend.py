"""A later PR adds a configuration, a traffic mix, a traffic kind, a
per-layer metric and a reader as NEW FILES plus entries in BENCHMARK.json,
editing nothing that is there.  ``conftest.add_tiny_cells`` already adds a
configuration, a mix and a cell that way; the first test adds the rest,
with an end-to-end metric that only the new cell reports.  The second
adds a deployment whose pods, reference and guarantee the benchmark has
never seen — a shapes file, a reference file and a configuration that
names them — and the judge holds a run to the new guarantee."""

import json
import os

from conftest import drive

KIND = '''
"""A kind of its own: the open loop at half the mix's rate."""
import importlib.util, os
_spec = importlib.util.spec_from_file_location(
    "open", os.path.join(os.path.dirname(__file__), "poisson_open.py"))
_open = importlib.util.module_from_spec(_spec); _spec.loader.exec_module(_open)

class Generator(_open.Generator):
    def _start_creators(self):
        self.params = dict(self.params,
                           rate_pods_s=self.params["rate_pods_s"] / 2)
        super()._start_creators()
'''

READER = '''
"""``runner_square``: a runner value, squared."""
def read(args, ctx):
    value = ctx["runner"].get(args["key"])
    return None if value is None else value * value
'''


SHAPES = '''
"""``antigroups``: ``cluster.py``'s uniform fleet; its pause pods in label
groups (``spec["run"]`` consecutive pods a group, ``spec["groups"]``
groups), each with a REQUIRED anti-affinity on the hostname against its
own group, as upstream's ``pod-with-pod-anti-affinity.yaml`` has against
``color: green``."""
import importlib.util, json, os
import numpy as np
_spec = importlib.util.spec_from_file_location("cluster", os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "cluster.py"))
cluster = importlib.util.module_from_spec(_spec); _spec.loader.exec_module(cluster)

Nodes = cluster.Nodes


def _annotation(group):
    term = {"labelSelector": {"matchLabels": {"group": "g%d" % group}},
            "topologyKey": cluster.HOSTNAME_LABEL}
    return json.dumps({cluster.AFFINITY_ANNOTATION_KEY: json.dumps(
        {"podAntiAffinity":
         {"requiredDuringSchedulingIgnoredDuringExecution": [term]}})}).encode()


class Pods(cluster.Pods):
    def __init__(self, spec, seed, nodes_spec=None):
        super().__init__(dict(spec, profile="uniform"), seed, nodes_spec)
        self.n_groups, self.run = int(spec["groups"]), int(spec["run"])
        self.group = np.zeros(0, np.int64)       # per pod, its own array

    def grow(self, n):
        super().grow(n)
        if len(self.group) < len(self.cpu):
            self.group = np.arange(len(self.cpu)) // self.run % self.n_groups

    def json_bytes(self, i):
        body = super().json_bytes(i)
        group = int(self.group[i])
        return body.replace(b'"labels":{}', b'"labels":{"group":"g%d"}' % group) \\
            .replace(b'"annotations":{}', b'"annotations":' + _annotation(group))

    def list_body(self, start, stop):
        return b'{"kind":"List","items":[' + b",".join(
            self.json_bytes(i) for i in range(start, stop)) + b"]}"
'''

REFERENCE = '''
"""``antigroups``: ``reference.py`` plus MatchInterPodAffinity for the one
term the shapes carry: a node that holds a pod of the group does not fit,
and the guarantee ``antiaffinity_violations``: no two pods of a group on
one node."""
import importlib.util, os
import numpy as np
_spec = importlib.util.spec_from_file_location("reference", os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "reference.py"))
base = importlib.util.module_from_spec(_spec); _spec.loader.exec_module(base)

GUARANTEES = base.GUARANTEES + ("antiaffinity_violations",)
scores = base.scores


class State(base.State):
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
'''


def _rewrite_bench(tree: str, change) -> None:
    """BENCHMARK.json of the tree, with ``change(bench)`` applied."""
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    change(bench)
    with open(path, "w") as f:
        json.dump(bench, f)


def _mtimes(bench_dir: str) -> dict:
    before = {}
    for root, _dirs, names in os.walk(bench_dir):
        for name in names:
            path = os.path.join(root, name)
            before[path] = os.path.getmtime(path)
    return before


def test_new_shapes_reference_and_guarantee_are_files_only(tiny_tree):
    """200 uniform nodes, pods in four label groups with a required
    hostname anti-affinity against their own group, the resident
    population placed by the new reference: a sound run is correct with
    the new guarantee among the numbers compared at 0; a run in which two
    pods of a group were put on one node is not, by that number alone."""
    bench_dir = os.path.join(tiny_tree, "benchmarks")
    before = _mtimes(bench_dir)
    for directory, body in (("shapes", SHAPES), ("references", REFERENCE)):
        os.makedirs(os.path.join(bench_dir, directory), exist_ok=True)
        with open(os.path.join(bench_dir, directory, "antigroups.py"),
                  "w") as f:
            f.write(body)
    with open(os.path.join(bench_dir, "configs", "tiny-200n.json")) as f:
        config = json.load(f)
    config.update(
        name="tiny-anti", shapes="antigroups", reference="antigroups",
        resident_cap=400,            # of the 800 places 4 groups have
        nodes={"count": 200, "profile": "uniform", "milli_cpu": 4000,
               "memory": 34359738368, "pods": 110},
        pods={"milli_cpu": 100, "memory": 524288000, "groups": 4, "run": 8})
    config["guarantees"] = config["guarantees"] + [
        "no two pods of one label group on one node"]
    with open(os.path.join(bench_dir, "configs", "tiny-anti.json"), "w") as f:
        json.dump(config, f)

    def entries(bench: dict) -> None:
        bench["configs"].append({
            "name": "tiny-anti", "source": "tests", "reduced": ["count"],
            "file": "benchmarks/configs/tiny-anti.json", "why": "tests"})
        bench["workloads"].append({
            "name": "tiny-anti-open", "config": "tiny-anti",
            "traffic": "tiny-open", "chips": 1, "why": "tests"})
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if "tiny-open" in metric.get("workloads", ()):
                metric["workloads"].append("tiny-anti-open")
    _rewrite_bench(tiny_tree, entries)

    sound = drive(tiny_tree, "tiny-anti-open", seed=41, seconds=3.0)
    assert sound["correct"] is True, sound["compared"]
    assert sound["compared"]["antiaffinity_violations"] == \
        {"value": 0, "limit": 0}
    assert sound["compared"]["over_allocatable"] == {"value": 0, "limit": 0}
    assert sound["attempted"] > 500 and sound["failed"] == 0

    faulty = drive(tiny_tree, "tiny-anti-open", seed=41, seconds=3.0,
                   fault="colocate")
    assert faulty["correct"] is False
    over = {k for k, v in faulty["compared"].items()
            if v["value"] > v["limit"]}
    assert over == {"antiaffinity_violations"}, faulty["compared"]

    # and the program itself, on the CPU at this tiny size, is held to the
    # new guarantee through the served path and keeps it
    for attempt in (1, 2):
        try:
            program = drive(tiny_tree, "tiny-anti-open", seed=42, seconds=3.0,
                            fault=None)
            break
        except AssertionError as err:
            # On the CPU the daemon now and then aborts AT EXIT ("terminate
            # called ... exception not rethrown", code -6; PERF.md section 7
            # entry 10e; never in 100 runs on the chip): not this test's.
            if attempt == 2 or "did not exit 0 on SIGTERM" not in str(err):
                raise
    assert program["correct"] is True, program["compared"]
    assert program["compared"]["antiaffinity_violations"]["value"] == 0
    for existing, mtime in before.items():
        assert os.path.getmtime(existing) == mtime, existing


def test_new_kind_mix_metric_and_reader_are_files_only(tiny_tree):
    bench_dir = os.path.join(tiny_tree, "benchmarks")
    before = _mtimes(bench_dir)
    with open(os.path.join(bench_dir, "generators", "half_open.py"), "w") as f:
        f.write(KIND)
    with open(os.path.join(bench_dir, "readers", "runner_square.py"), "w") as f:
        f.write(READER)
    with open(os.path.join(bench_dir, "traffic", "tiny-half.json"), "w") as f:
        json.dump({"kind": "half_open", "rate_pods_s": 400,
                   "steady_pending_s": 0.5}, f)
    metric = {"name": "ramp_s.squared", "layer": "whole served path", "unit": "s2",
              "better": "lower", "source": "host_clock",
              "moves": "pods_bound_per_s", "workloads": ["tiny-half"],
              "arithmetic": "runner_square", "args": {"key": "ramp_s"}}
    with open(os.path.join(bench_dir, "metrics", "ramp_s.squared.json"),
              "w") as f:
        json.dump(metric, f)

    def entries(bench: dict) -> None:
        bench["workloads"].append({
            "name": "tiny-half", "config": "tiny-200n",
            "traffic": "tiny-half", "chips": 1, "why": "tests"})
        bench["per_layer"].append({k: metric[k] for k in (
            "name", "unit", "better", "source", "layer", "moves",
            "workloads")})
        bench["end_to_end"].append({
            "name": "pods_bound_per_s", "unit": "pods/s", "better": "higher",
            "bound": 0.05, "source": "host_clock", "workloads": ["tiny-half"]})
    _rewrite_bench(tiny_tree, entries)

    res = drive(tiny_tree, "tiny-half", seed=31, seconds=3.0)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"pods_bound_per_s", "setup_s"}
    assert 150 < res["metrics"]["pods_bound_per_s"]["value"] < 250
    for existing, mtime in before.items():
        assert os.path.getmtime(existing) == mtime, existing

    # the new metric is read by its new reader in the cell that lists it
    import subprocess, sys
    proc = subprocess.run([sys.executable, "-c", """
import run, rig
cell = run.Cell(run.load_json(rig.REPO + "/BENCHMARK.json"), "tiny-half")
names = [m["name"] for m, _spec in cell.per_layer()]
m, spec = [x for x in cell.per_layer() if x[0]["name"] == "ramp_s.squared"][0]
print(run.load_module("readers", spec["arithmetic"]).read(
    spec["args"], {"runner": {"ramp_s": 3.0}}), "compiles.in_window" in names)
"""], cwd=tiny_tree, capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=bench_dir))
    assert proc.stdout.split() == ["9.0", "True"], proc.stderr
