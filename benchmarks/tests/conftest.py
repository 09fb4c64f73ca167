"""Tests of the benchmark's own code, at a tiny size on the CPU.

Run with ``python3 -m pytest benchmarks/tests -q`` from the repository's
root (tier-1 collects ``tests/`` only).  ``tiny_tree`` gives a temporary
copy of the benchmark beside links to the program, with one tiny
configuration, one tiny mix and the cell over them added AS FILES AND
ENTRIES ONLY — the way a later PR adds them.  The tiny fleet is MIXED
(pools, zones, four capacities; pods of 16 sizes, some with a pool
selector or a zone affinity): ``cluster.py`` and ``reference.py`` take
such a configuration as data, and the tests hold them to it although
both cells of BENCHMARK.json run upstream's uniform shapes.
"""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def add_tiny_cells(tree: str) -> None:
    bench_path = os.path.join(tree, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    with open(os.path.join(tree, "benchmarks", "configs",
                           "schedperf-5000n.json")) as f:
        config = json.load(f)
    config.update(
        name="tiny-200n", resident_cap=800,
        judge={"sample": 200, "lag_step": 20, "max_lag_s": 2.0},
        limits={"gap_mean": 1.0, "gap_max": 6.0},
        nodes={"count": 200, "profile": "mixed", "milli_cpu": 4000,
               "memory": 34359738368, "pods": 110, "n_zones": 4,
               "n_pools": 4, "capacity_scales": [0.5, 1.0, 1.0, 2.0]},
        pods={"profile": "mixed", "cpu_choices": [50, 100, 200, 500],
              "memory_mib_choices": [128, 256, 500, 1024],
              "selector_share": 0.1, "zone_affinity_share": 0.05})
    # a ladder the tiny fleet can hold: the ramp drives each of its sizes
    config["daemon"]["env"]["KT_STREAM_CHUNK"] = "256"
    files = {
        "configs/tiny-200n.json": config,
        "traffic/tiny-open.json": {"kind": "poisson_open",
                                   "rate_pods_s": 300,
                                   "steady_pending_s": 0.5},
    }
    for rel, body in files.items():
        with open(os.path.join(tree, "benchmarks", rel), "w") as f:
            json.dump(body, f)
    bench["configs"].append({
        "name": "tiny-200n", "source": "tests", "reduced": ["count"],
        "file": "benchmarks/configs/tiny-200n.json", "why": "tests"})
    bench["workloads"].append(
        {"name": "tiny-open", "config": "tiny-200n", "traffic": "tiny-open",
         "chips": 1, "why": "tests"})
    # the cell joins the open cells' metrics
    for metric in bench["end_to_end"] + bench["per_layer"]:
        cells = metric.get("workloads")
        if cells and "schedperf5k-arrivals" in cells:
            cells.append("tiny-open")
    with open(bench_path, "w") as f:
        json.dump(bench, f)


@pytest.fixture(scope="session")
def tiny_tree(tmp_path_factory):
    tree = str(tmp_path_factory.mktemp("tree"))
    shutil.copytree(BENCH, os.path.join(tree, "benchmarks"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tree)
    for name in ("kubernetes_tpu", "native"):
        os.symlink(os.path.join(REPO, name), os.path.join(tree, name))
    add_tiny_cells(tree)
    return tree


def run_in_tree(tree: str, code: str, timeout: float = 300.0):
    """Run ``code`` in a fresh interpreter whose ``benchmarks`` is the
    tree's copy (``run`` and ``rig`` bind their paths at import)."""
    import subprocess
    return subprocess.run(
        [sys.executable, "-c", code], cwd=tree, capture_output=True,
        text=True, timeout=timeout,
        env=dict(os.environ, PYTHONPATH=os.path.join(tree, "benchmarks"),
                 JAX_PLATFORMS="cpu"))


DRIVE = """
import functools, json, sys
import run, rig, refsched
cell = run.Cell(run.load_json(rig.REPO + "/BENCHMARK.json"), {cell!r})
make = functools.partial(refsched.RefSut, fault={fault!r}, seed={seed}) if {fault!r} else None
flags = {flags!r}
cell.config["daemon"]["flags"] += flags
run.RAMP_TIMEOUT_S = 20.0      # a test does not wait 150 s for a ramp that never settles
run.DRAIN_TIMEOUT_S = 5.0      # nor a minute for answers that never come
res = run.run_cell(cell, {seed}, {seconds}, False, platform="cpu", make_sut=make)
print(json.dumps(res))
"""


def drive(tree: str, cell: str, seed: int, seconds: float,
          fault: str | None = "none", flags=()) -> dict:
    """One whole run of ``run.run_cell`` that skips only the harness's
    look for a chip: against the plain reference scheduler (``fault``
    names what is broken underneath), or with ``fault=None`` against the
    real daemon on the CPU."""
    proc = run_in_tree(tree, DRIVE.format(cell=cell, fault=fault,
                                          flags=list(flags), seed=seed,
                                          seconds=seconds))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
