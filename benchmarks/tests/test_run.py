"""A whole run, with only the harness's look for a chip skipped: the last
line's shape; `correct` false for each fault the cells can have, planted
where the answer is produced; the control; no measurement without a TPU;
a runner that never imports JAX."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO, drive, run_in_tree


def _over(res: dict) -> set:
    return {k for k, v in res["compared"].items() if v["value"] > v["limit"]}


def test_sound_run_is_correct_and_the_line_has_the_contracts_shape(tiny_tree):
    res = drive(tiny_tree, "tiny-open", seed=21, seconds=3.0)
    assert res["correct"] is True and not _over(res)
    # the contract's keys and, last, the numbers compared: nothing else
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert set(res["metrics"]) == {"submit_to_bind_p50_ms",
                                   "submit_to_bind_p95_ms", "setup_s"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for number in res["compared"].values():
        assert set(number) == {"value", "limit"}


@pytest.mark.parametrize("fault, caught_by", [
    ("wrong_policy", {"gap_mean", "gap_max"}),          # the control
    ("state_unchanged", {"over_allocatable"}),
    ("half_batch", {"never_bound", "ramp_not_steady"}),
    ("altered", {"gap_mean"}),
])
def test_each_fault_comes_out_not_correct(tiny_tree, fault, caught_by):
    res = drive(tiny_tree, "tiny-open", seed=22, seconds=3.0, fault=fault)
    assert res["correct"] is False
    assert caught_by <= _over(res), res["compared"]


def test_the_programs_own_wrong_policy_is_the_control(tiny_tree):
    """The daemon itself on the CPU at a tiny size: sound with the
    configuration's policy, not correct with MostRequested in place of
    LeastRequested (``--algorithm-provider ClusterAutoscalerProvider``),
    the control ``tests/control.py`` runs on the chip at the cells' own
    sizes."""
    sound = drive(tiny_tree, "tiny-open", seed=24, seconds=4.0, fault=None)
    assert sound["correct"] is True, sound["compared"]
    control = drive(tiny_tree, "tiny-open", seed=24, seconds=4.0, fault=None,
                    flags=["--algorithm-provider",
                           "ClusterAutoscalerProvider"])
    assert control["correct"] is False
    assert {"gap_mean", "gap_max"} & _over(control), control["compared"]
    assert control["compared"]["gap_mean"]["value"] \
        >= 3 * max(sound["compared"]["gap_mean"]["value"], 0.1)


def test_no_tpu_no_measurement():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "schedperf1k-arrivals", "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "FAILED" in proc.stderr


def test_no_program_no_measurement(tmp_path):
    import shutil
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "schedperf5k-arrivals",
         "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_the_runner_never_imports_jax(tiny_tree):
    proc = run_in_tree(tiny_tree, """
import sys
import run, rig, loadgen, judge, cluster, reference, refsched, work
run.load_module("generators", "poisson_open")
import glob, os
for path in glob.glob(os.path.join(run.HERE, "readers", "*.py")):
    name = os.path.basename(path)[:-3]
    if name != "__init__":
        run.load_module("readers", name)
assert "jax" not in sys.modules and "kubernetes_tpu" not in sys.modules
print("clean")
""")
    assert proc.returncode == 0 and "clean" in proc.stdout, proc.stderr


def test_a_kept_record_is_judged_again_to_the_same_numbers(tiny_tree):
    """``tests/rejudge.py`` on the record a run kept gives the numbers the
    run printed (it is how limits are set from runs already made)."""
    res = drive(tiny_tree, "tiny-open", seed=25, seconds=2.0)
    record = os.path.join(tiny_tree, "benchmarks", "out", "tiny-open.25.0",
                          "record.npz")
    proc = subprocess.run(
        [sys.executable, "benchmarks/tests/rejudge.py", "--config",
         "tiny-200n", "--seed", "25", "--platform", "cpu", record],
        cwd=tiny_tree, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    again = json.loads(proc.stdout.strip().splitlines()[-1])
    assert again["correct"] is res["correct"] is True
    for name, (value, limit) in again["numbers"].items():
        assert res["compared"][name] == {"value": value, "limit": limit}
