"""Each word of the per-layer vocabulary on a canned /metrics pair."""

import glob
import json
import os

import pytest

import rig
import run

BEFORE = rig.parse_metrics("""
# TYPE scheduler_batch_stage_latency_microseconds histogram
scheduler_batch_stage_latency_microseconds_sum{stage="transfer"} 1000000
scheduler_batch_stage_latency_microseconds_count{stage="transfer"} 10
scheduler_batch_stage_latency_microseconds_sum{stage="solve"} 50
scheduler_batch_stage_latency_microseconds_count{stage="solve"} 10
scheduler_pod_scheduling_attempts_total{result="scheduled"} 1000
scheduler_post_prewarm_compiles_total{path="stream"} 2
scheduler_watch_decode_seconds_total{kind="pods"} 1.5
scheduler_watch_decode_seconds_total{kind="nodes"} 9
scheduler_device_transfer_bytes_total{cause="scatter"} 1000
scheduler_device_transfer_bytes_total{cause="full_upload"} 70000
""")
AFTER = rig.parse_metrics("""
scheduler_batch_stage_latency_microseconds_sum{stage="transfer"} 3000000
scheduler_batch_stage_latency_microseconds_count{stage="transfer"} 30
scheduler_batch_stage_latency_microseconds_sum{stage="solve"} 150
scheduler_batch_stage_latency_microseconds_count{stage="solve"} 30
scheduler_pod_scheduling_attempts_total{result="scheduled"} 21000
scheduler_post_prewarm_compiles_total{path="stream"} 3
scheduler_post_prewarm_compiles_total{path="single"} 1
scheduler_watch_decode_seconds_total{kind="pods"} 3.5
scheduler_watch_decode_seconds_total{kind="nodes"} 9
scheduler_device_transfer_bytes_total{cause="scatter"} 5001000
scheduler_device_transfer_bytes_total{cause="full_upload"} 70000
""")
API = (rig.parse_metrics('apiserver_serialize_seconds_total{verb="GET"} 1\n'),
       rig.parse_metrics('apiserver_serialize_seconds_total{verb="GET"} 1.5\n'
                         'apiserver_serialize_seconds_total{verb="WATCH"} 0.5\n'))
TRACE = {"window_s": 2.0, "busy_s": 0.5,
         "lines": {"XLA Modules": {"jit__solve_scan": [4, 0.4],
                                   "jit_scatter": [4, 0.05]},
                   "XLA Ops": {"while.4": [4, 0.39]}}}
CTX = {"daemon": (BEFORE, AFTER), "apiserver": API,
       "runner": {"client_busy_pct": 12.5, "ramp_s": 9.0},
       "pods_bound": 20000, "trace": TRACE, "trace_pods": 9000,
       "pods_per_launch": 2500.0,
       "config": {"nodes": {"count": 5000}}, "device_kind": "TPU v5 lite"}


def read(name, ctx=CTX):
    spec = run.load_json(os.path.join(run.HERE, "metrics", name + ".json"))
    return run.load_module("readers", spec["arithmetic"]).read(
        spec["args"], ctx)


def test_seconds_per_kpod():
    assert read("scatter.ms_per_kpod") == pytest.approx(2.0 / 20000 * 1e6)
    assert read("watch.decode_ms_per_kpod") == pytest.approx(100.0)
    assert read("apiserver.serialize_ms_per_kpod") == pytest.approx(50.0)


def test_ratio_and_counter_delta():
    assert read("former.pods_per_launch") == pytest.approx(1000.0)
    assert read("compiles.in_window") == 2.0
    # the two upload branches apart: the start-up upload is before the
    # window, and every launch of this window took the scatter branch
    assert read("scatter.bytes_per_pod") == pytest.approx(250.0)
    assert read("full_upload.bytes_per_pod") == 0.0
    # a labelled counter that never counted has no row: 0 compiles on a
    # page that was read (schedperf's uniform pods never compile after
    # prewarm), nothing on an empty page
    quiet = {k: v for k, v in AFTER.items()
             if k != "scheduler_post_prewarm_compiles_total"}
    assert read("compiles.in_window", dict(CTX, daemon=(quiet, quiet))) == 0.0
    assert read("compiles.in_window", dict(CTX, daemon=({}, {}))) is None


def test_runner_value():
    assert read("client.busy_pct") == 12.5
    assert read("tail.submit_to_bind_p99_ms") is None


def test_trace_readers():
    assert read("scan.device_us_per_pod") == pytest.approx(40.0)
    assert read("device.busy_pct") == pytest.approx(25.0)
    least = (5000 * 6 + 3) * 4 * 10000 / 819e9
    assert read("scan_roofline") == pytest.approx(100 * least / 0.4)
    untraced = dict(CTX, trace=None)
    assert read("scan_roofline", untraced) is None
    assert read("device.busy_pct", untraced) is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        read("scan_roofline", dict(CTX, device_kind="TPU v9"))


def test_every_metric_file_matches_benchmark_json():
    bench = run.load_json(os.path.join(os.path.dirname(run.HERE),
                                       "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    files = sorted(glob.glob(os.path.join(run.HERE, "metrics", "*.json")))
    assert {os.path.basename(f)[:-5] for f in files} == set(entries)
    for path in files:
        with open(path) as f:
            spec = json.load(f)
        for key, value in entries[spec["name"]].items():
            if key != "workloads":       # BENCHMARK.json's alone: cells join it
                assert spec[key] == value, (spec["name"], key)
        assert "workloads" not in spec
        assert os.path.exists(os.path.join(
            run.HERE, "readers", spec["arithmetic"] + ".py"))
