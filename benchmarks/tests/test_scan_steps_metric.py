"""The per-layer metric PR 37 added for the scan's loop, whose bound
follows the launch's live rows (``kubernetes_tpu/engine/solver.py
run_live_steps``): ``scan.steps_run_share`` — a data file over the
EXISTING reader ``ratio``, the steps the loop ran over the rows of the
buckets dispatched (``scheduler_scan_steps_total{kind=run}`` over
``{kind=bucket}``) inside the window, read from a pair of /metrics pages
recorded anew from a CPU run of the real daemon at 200 nodes with the
counter in place (``python3 benchmarks/testdata/record_metrics.py
<dir>``, the pair then kept as ``daemon_200n_steps.*``): a count of
steps, never a speed."""

import os

import pytest

import rig
import run

NAME = "scan.steps_run_share"
FAMILY = "scheduler_scan_steps_total"
TESTDATA = os.path.join(run.HERE, "testdata")
CELLS = ["schedperf5k-arrivals", "schedperf1k-arrivals",
         "interpod5k-arrivals", "mixedaffinity5k-arrivals",
         "mixedchurn5k-arrivals"]
SOLVES = "scheduler_batch_stage_latency_microseconds_count"


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


def _grew(pages: tuple, family: str, labels: dict) -> float:
    return (rig.family_sum(pages[1], family, labels) or 0.0) - \
        (rig.family_sum(pages[0], family, labels) or 0.0)


def test_metric_is_an_entry_a_file_and_the_existing_reader():
    bench = run.load_json(os.path.join(rig.REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    entry = by_name[NAME]
    spec = run.load_json(os.path.join(run.HERE, "metrics", NAME + ".json"))
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    # every cell the issue names is in the list (a later cell may join it)
    assert set(CELLS) <= set(entry["workloads"])
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    # the layer's name as the accepted metrics of that layer spell it
    assert entry["layer"] == by_name["scan.device_us_per_pod"]["layer"] \
        == "device scan (kernel)"
    assert entry["moves"] == "submit_to_bind_p50_ms"
    assert entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert spec["arithmetic"] == "ratio"
    for side, kind in (("num", "run"), ("den", "bucket")):
        (term,) = spec["args"][side]
        assert term["family"] == FAMILY and term["process"] == "daemon"
        assert term["labels"] == {"kind": kind}
        # no "absent": a page without the family has to read nothing,
        # not 0
        assert "absent" not in term


def test_steps_run_over_bucket_rows_in_the_recorded_window():
    """The recorded cell's launches carry a few pods each in the floor
    bucket of 256 rows: one ``bucket`` increment of 256 a dispatch, and
    the loop runs a small share of them."""
    pages = _pages("daemon_200n_steps")
    ran = _grew(pages, FAMILY, {"kind": "run"})
    rows = _grew(pages, FAMILY, {"kind": "bucket"})
    solves = _grew(pages, SOLVES, {"stage": "solve"})
    assert solves > 100
    # every dispatch of the window took the floor bucket
    assert rows == 256 * solves
    # whole iterations of 4, at least one a dispatch that carried a pod
    assert ran % 4 == 0 and 0 < ran < rows
    # the loop covers the pods placed: no live row is left unstepped
    assert ran >= run.pods_scheduled(*pages)
    assert _read(pages) == pytest.approx(ran / rows)
    assert 0.0 < _read(pages) < 0.25


@pytest.mark.parametrize("stem", ["daemon_200n", "daemon_200n_tenuring",
                                  "daemon_200n_packed", "daemon_200n_rows",
                                  "daemon_200n_plan"])
def test_metric_is_left_out_where_the_program_lacks_it(stem):
    """The parent's program (and every one before it) prints no such
    family: the reader returns nothing; it does not raise and it does not
    read 0."""
    pages = _pages(stem)
    assert rig.family_sum(pages[1], FAMILY) is None
    assert _read(pages) is None
