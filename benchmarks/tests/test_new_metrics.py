"""The per-layer metrics PR 27 added, each read by an EXISTING reader
from a pair of /metrics pages recorded from a CPU run of the real daemon
at 200 nodes (``testdata/record_metrics.py``): a count that the families
and labels exist as the metric files name them, never a speed."""

import os

import pytest

import rig
import run

NEW = ("cache_lock.ingest_wait_ms_per_kpod",
       "cache_lock.launch_wait_ms_per_kpod",
       "watch.cpu_ms_per_kpod", "handlers.cpu_ms_per_kpod",
       "transfer.batch_ms_per_kpod", "transfer.rows_ms_per_kpod",
       "transfer.scatter_ms_per_kpod", "scatter.device_us_per_pod",
       "scan.wait_ms_per_kpod", "gate.ms_per_kpod",
       "launch.unaccounted_ms_mean", "pod.queue_wait_ms_mean",
       "gc.daemon_pause_ms_per_kpod")
# the readers the benchmark had before this PR
READERS = {"counter_delta", "seconds_per_kpod", "ratio", "runner_value",
           "trace_op_time", "trace_busy", "trace_roofline"}
TESTDATA = os.path.join(run.HERE, "testdata")


def _page(name: str) -> dict:
    with open(os.path.join(TESTDATA,
                           f"daemon_200n.{name}.metrics.txt")) as f:
        return rig.parse_metrics(f.read())


@pytest.fixture(scope="module")
def ctx() -> dict:
    pages = _page("open"), _page("close")
    return {"daemon": pages, "apiserver": ({}, {}), "runner": {},
            "pods_bound": run.pods_scheduled(*pages),
            # the scatter program as the profiler names it (on a TPU: the
            # recorded pages come from a CPU, which has no such line)
            "trace": {"window_s": 2.0, "busy_s": 0.1, "lines": {
                "XLA Modules": {"jit__solve_scan": [16, 0.1],
                                "jit_kt_scatter_rows": [16, 0.002]}}},
            "trace_pods": 2000.0, "pods_per_launch": 125.0,
            "config": {}, "device_kind": "TPU v5 lite"}


def _spec(name: str) -> dict:
    return run.load_json(os.path.join(run.HERE, "metrics", name + ".json"))


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_an_entry_a_file_and_an_existing_reader(name):
    bench = run.load_json(os.path.join(rig.REPO, "BENCHMARK.json"))
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    spec = _spec(name)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == ["schedperf5k-arrivals",
                                  "schedperf1k-arrivals"]
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:29]}
    assert spec["arithmetic"] in READERS
    assert os.path.exists(os.path.join(run.HERE, "readers",
                                       spec["arithmetic"] + ".py"))


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_a_value_from_the_recorded_pages(name, ctx):
    spec = _spec(name)
    value = run.load_module("readers", spec["arithmetic"]).read(
        spec["args"], ctx)
    assert value is not None, f"{name}: nothing to read"
    assert value >= 0.0


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_left_out_where_the_program_lacks_it(name):
    """On a page without the families and stages (the parent commit has
    none but kt-prof's thread counter) the reader returns nothing: it
    does not raise and it does not read 0."""
    old = rig.parse_metrics(
        'scheduler_batch_stage_latency_microseconds_sum{stage="solve"} 5\n'
        'scheduler_batch_stage_latency_microseconds_count{stage="solve"} 1\n'
        'scheduler_pod_scheduling_attempts_total{result="scheduled"} 9\n')
    spec = _spec(name)
    ctx = {"daemon": (old, old), "apiserver": ({}, {}), "runner": {},
           "pods_bound": 9, "trace": {"window_s": 2.0, "busy_s": 0.1,
                                      "lines": {"XLA Modules": {
                                          "jit__solve_scan": [1, 0.1],
                                          "jit_scatter": [1, 0.1]}}},
           "trace_pods": 9.0, "pods_per_launch": 9.0, "config": {},
           "device_kind": "TPU v5 lite"}
    assert run.load_module("readers", spec["arithmetic"]).read(
        spec["args"], ctx) is None


def test_the_account_is_one_subtraction(ctx):
    """``launch.unaccounted_ms_mean`` = launch_total minus the stages
    inside it, per launch: every stage it names is on the recorded page,
    and the parts stay inside the stages that hold them."""
    before, after = ctx["daemon"]

    def grew(stage: str) -> float:
        fam = "scheduler_batch_stage_latency_microseconds_sum"
        return rig.family_sum(after, fam, {"stage": stage}) - (
            rig.family_sum(before, fam, {"stage": stage}) or 0.0)

    spec = _spec("launch.unaccounted_ms_mean")
    named = [t["labels"]["stage"] for t in spec["args"]["num"]]
    assert named[0] == "launch_total"
    assert all(t["scale"] < 0 for t in spec["args"]["num"][1:])
    inside = sum(grew(stage) for stage in named[1:])
    assert 0.0 < inside <= grew("launch_total")
    assert grew("device_wait") <= grew("readback")
    assert sum(grew(f"transfer.{p}") for p in
               ("batch", "rows", "scatter", "full")) <= grew("transfer")
    value = run.load_module("readers", "ratio").read(spec["args"], ctx)
    assert value >= 0.0
