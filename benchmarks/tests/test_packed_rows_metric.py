"""The per-layer metric PR 33 added for the dirty rows' packed upload
(``engine/solver.py ResidentCluster.sync``):
``transfer.scatter_arrays_per_launch``, the twin of PR 31's
``transfer.batch_arrays_per_launch`` — a data file over the EXISTING
reader ``ratio``, read from a pair of /metrics pages recorded anew from a
CPU run of the real daemon at 200 nodes with the rows' wire form in place
(``python3 benchmarks/testdata/record_metrics.py <dir>``, the pair then
kept as ``daemon_200n_rows.*``): a count of the arrays a launch hands the
runtime, never a speed."""

import os

import pytest

import rig
import run

NAME = "transfer.scatter_arrays_per_launch"
FAMILY = "scheduler_device_transfer_arrays_total"
LAUNCHES = "scheduler_batch_stage_latency_microseconds_count"
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


def _grew(pages: tuple, family: str, labels: dict) -> float:
    return (rig.family_sum(pages[1], family, labels) or 0.0) - \
        (rig.family_sum(pages[0], family, labels) or 0.0)


def test_metric_is_an_entry_a_file_and_the_existing_reader():
    bench = run.load_json(os.path.join(rig.REPO, "BENCHMARK.json"))
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    twin = {m["name"]: m for m in bench["per_layer"]}[
        "transfer.batch_arrays_per_launch"]
    # appended behind its twin, which PR 31 put at the end
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) == names.index(twin["name"]) + 1
    spec = run.load_json(os.path.join(run.HERE, "metrics", NAME + ".json"))
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
        if key != "name":
            assert entry[key] == twin[key], key
    assert entry["workloads"] == CELLS
    assert entry["layer"] == "upload / scatter"
    assert entry["moves"] == "submit_to_bind_p50_ms"
    assert spec["arithmetic"] == "ratio"
    assert [t["family"] for t in spec["args"]["num"]] == [FAMILY]
    assert spec["args"]["num"][0]["labels"] == {"cause": "scatter"}
    assert spec["args"]["den"][0]["labels"] == {"stage": "solve"}
    # no "absent": a page without the family has to read nothing, not 0
    assert "absent" not in spec["args"]["num"][0]


def test_one_array_a_scattering_launch_in_the_recorded_window():
    """Every launch of the recorded window that found a row dirty handed
    the runtime ONE array for the rows; the few that found none (a launch
    right behind another, before any bind came back) handed it nothing."""
    pages = _pages("daemon_200n_rows")
    launches = _grew(pages, LAUNCHES, {"stage": "solve"})
    scattered = _grew(pages, FAMILY, {"cause": "scatter"})
    assert launches > 100
    assert 0.9 * launches <= scattered <= launches
    assert _read(pages) == pytest.approx(scattered / launches)
    assert 0.9 <= _read(pages) <= 1.0
    # the rows' bytes still count beside it (scatter.bytes_per_pod)
    assert _grew(pages, "scheduler_device_transfer_bytes_total",
                 {"cause": "scatter"}) > 0


def test_twelve_arrays_a_launch_on_the_parents_pages():
    """PR 31's pages (the parent's program: the index and the 11 narrow
    planes cross leaf by leaf) read 12 a scattering launch through the
    same file: the metric is comparable parent against change."""
    pages = _pages("daemon_200n_packed")
    launches = _grew(pages, LAUNCHES, {"stage": "solve"})
    scattered = _grew(pages, FAMILY, {"cause": "scatter"})
    assert scattered % 12 == 0
    assert _read(pages) == pytest.approx(scattered / launches)
    assert 10.0 < _read(pages) <= 12.0


@pytest.mark.parametrize("stem", ["daemon_200n", "daemon_200n_tenuring"])
def test_metric_is_left_out_where_the_program_lacks_it(stem):
    """A program from before PR 31 prints no such family: the reader
    returns nothing; it does not raise and it does not read 0."""
    pages = _pages(stem)
    assert rig.family_sum(pages[1], FAMILY) is None
    assert _read(pages) is None
