"""A later PR adds a configuration, a traffic mix, a traffic kind, a
per-layer metric and a reader as NEW FILES plus entries in BENCHMARK.json,
editing nothing that is there.  ``conftest.add_tiny_cells`` already adds a
configuration, a mix and a cell that way; this adds the rest, with an
end-to-end metric that only the new cell reports."""

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


def test_new_kind_mix_metric_and_reader_are_files_only(tiny_tree):
    bench_dir = os.path.join(tiny_tree, "benchmarks")
    before = {}
    for root, _dirs, names in os.walk(bench_dir):
        for name in names:
            path = os.path.join(root, name)
            before[path] = os.path.getmtime(path)
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
    path = os.path.join(tiny_tree, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny-half", "config": "tiny-200n",
                               "traffic": "tiny-half", "chips": 1,
                               "why": "tests"})
    bench["per_layer"].append({k: metric[k] for k in (
        "name", "unit", "better", "source", "layer", "moves", "workloads")})
    bench["end_to_end"].append({
        "name": "pods_bound_per_s", "unit": "pods/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["tiny-half"]})
    with open(path, "w") as f:
        json.dump(bench, f)

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
