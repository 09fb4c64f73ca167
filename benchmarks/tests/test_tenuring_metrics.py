"""The two per-layer metrics PR 28 added for the daemon's tenuring
(``utils/gcstats.py``), each a data file over the EXISTING reader
``counter_delta``, read from a pair of /metrics pages recorded anew from
a CPU run of the real daemon at 200 nodes with tenuring in place
(``python3 benchmarks/testdata/record_metrics.py <dir>``, the pair then
kept as ``daemon_200n_tenuring.*``): counts that the families exist as
the metric files name them, never a speed."""

import os

import pytest

import rig
import run

NEW = {"gc.tenures_in_window": "scheduler_gc_tenures_total",
       "gc.major_in_window": "scheduler_gc_major_collections_total"}
TESTDATA = os.path.join(run.HERE, "testdata")
CELLS = ["schedperf5k-arrivals", "schedperf1k-arrivals"]


def _page(name: str) -> dict:
    with open(os.path.join(
            TESTDATA, f"daemon_200n_tenuring.{name}.metrics.txt")) as f:
        return rig.parse_metrics(f.read())


def _ctx(pages: tuple) -> dict:
    return {"daemon": pages, "apiserver": ({}, {}), "runner": {},
            "pods_bound": run.pods_scheduled(*pages), "trace": None,
            "trace_pods": None, "pods_per_launch": None, "config": {},
            "device_kind": "TPU v5 lite"}


def _spec(name: str) -> dict:
    return run.load_json(os.path.join(run.HERE, "metrics", name + ".json"))


def _read(name: str, pages: tuple):
    spec = _spec(name)
    return run.load_module("readers", spec["arithmetic"]).read(
        spec["args"], _ctx(pages))


@pytest.mark.parametrize("name", NEW)
def test_metric_is_an_entry_a_file_and_the_existing_reader(name):
    bench = run.load_json(os.path.join(rig.REPO, "BENCHMARK.json"))
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    spec = _spec(name)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == CELLS
    assert entry["layer"] == "daemon runtime"
    assert entry["moves"] == "submit_to_bind_p95_ms"
    assert entry["source"] == "program_counter"
    assert spec["arithmetic"] == "counter_delta"
    assert [t["family"] for t in spec["args"]["terms"]] == [NEW[name]]


def test_tenures_and_majors_of_the_recorded_window():
    """The recorded window held five full collections after the
    baseline: five tenures, and no major collection (the baseline's own
    is counted before the window opens)."""
    pages = _page("open"), _page("close")
    assert rig.family_sum(pages[0], NEW["gc.major_in_window"]) == 1
    assert _read("gc.tenures_in_window", pages) == 5
    assert _read("gc.major_in_window", pages) == 0
    assert _spec("gc.tenures_in_window")["better"] == "higher"
    assert _spec("gc.major_in_window")["better"] == "lower"


def test_an_idle_mechanism_reads_zero_not_nothing():
    """The daemon prints both families from its start, so a window with
    no full collection reads 0 on the change's side."""
    page = _page("open")
    for name in NEW:
        assert _read(name, (page, page)) == 0


@pytest.mark.parametrize("name", NEW)
def test_metric_is_left_out_where_the_program_lacks_it(name):
    """The parent commit prints neither family (PR 27's recorded pages
    are such pages): the reader returns nothing; it does not raise and
    it does not read 0."""
    old = []
    for side in ("open", "close"):
        with open(os.path.join(
                TESTDATA, f"daemon_200n.{side}.metrics.txt")) as f:
            old.append(rig.parse_metrics(f.read()))
    assert rig.family_sum(old[1], NEW[name]) is None
    assert _read(name, tuple(old)) is None
