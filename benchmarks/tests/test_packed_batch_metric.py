"""The per-layer metric PR 31 added for the pod batch's packed upload
(``engine/solver.py put_batch``): ``transfer.batch_arrays_per_launch``,
a data file over the EXISTING reader ``ratio``, read from a pair of
/metrics pages recorded anew from a CPU run of the real daemon at 200
nodes with the wire form in place (``python3
benchmarks/testdata/record_metrics.py <dir>``, the pair then kept as
``daemon_200n_packed.*``): a count of the arrays a launch hands the
runtime, never a speed."""

import os

import rig
import run

NAME = "transfer.batch_arrays_per_launch"
FAMILY = "scheduler_device_transfer_arrays_total"
TESTDATA = os.path.join(run.HERE, "testdata")
CELLS = ["schedperf5k-arrivals", "schedperf1k-arrivals",
         "interpod5k-arrivals"]


def _pages(stem: str) -> tuple:
    out = []
    for side in ("open", "close"):
        with open(os.path.join(TESTDATA,
                               f"{stem}.{side}.metrics.txt")) as f:
            out.append(rig.parse_metrics(f.read()))
    return tuple(out)


def _read(pages: tuple):
    spec = run.load_json(os.path.join(run.HERE, "metrics", NAME + ".json"))
    ctx = {"daemon": pages, "apiserver": ({}, {}), "runner": {},
           "pods_bound": run.pods_scheduled(*pages), "trace": None,
           "trace_pods": None, "pods_per_launch": None, "config": {},
           "device_kind": "TPU v5 lite"}
    return run.load_module("readers", spec["arithmetic"]).read(
        spec["args"], ctx)


def test_metric_is_an_entry_a_file_and_the_existing_reader():
    bench = run.load_json(os.path.join(rig.REPO, "BENCHMARK.json"))
    assert bench["per_layer"][-1]["name"] == NAME     # appended, at the end
    entry = bench["per_layer"][-1]
    spec = run.load_json(os.path.join(run.HERE, "metrics", NAME + ".json"))
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == CELLS
    assert entry["layer"] == "upload / scatter"
    assert entry["moves"] == "submit_to_bind_p50_ms"
    assert entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert spec["arithmetic"] == "ratio"
    assert [t["family"] for t in spec["args"]["num"]] == [FAMILY]
    assert spec["args"]["num"][0]["labels"] == {"cause": "batch"}
    assert spec["args"]["den"][0]["labels"] == {"stage": "solve"}
    # no "absent": a page without the family has to read nothing, not 0
    assert "absent" not in spec["args"]["num"][0]


def test_three_arrays_a_launch_in_the_recorded_window():
    """Every launch of the recorded window was one chunk: three packed
    buffers each, and nothing else uploaded a batch."""
    pages = _pages("daemon_200n_packed")
    launches = rig.family_sum(
        pages[1], "scheduler_batch_stage_latency_microseconds_count",
        {"stage": "solve"}) - rig.family_sum(
        pages[0], "scheduler_batch_stage_latency_microseconds_count",
        {"stage": "solve"})
    assert launches > 100
    assert _read(pages) == 3.0
    # the dirty rows still cross leaf by leaf (the next upload to pack)
    assert rig.family_sum(pages[1], FAMILY, {"cause": "scatter"}) > \
        rig.family_sum(pages[1], FAMILY, {"cause": "batch"})


def test_metric_is_left_out_where_the_program_lacks_it():
    """The parent commit prints no such family (PR 28's recorded pages
    are such pages): the reader returns nothing; it does not raise and
    it does not read 0."""
    for stem in ("daemon_200n", "daemon_200n_tenuring"):
        pages = _pages(stem)
        assert rig.family_sum(pages[1], FAMILY) is None
        assert _read(pages) is None
