"""The per-layer metrics that split a launch's wall clock into work and
waiting: ``launch.unnamed_ms_mean`` (the launch's whole less every stage
on its critical path), ``launch.offcpu_ms_mean``,
``transfer.offcpu_ms_mean`` and ``features.offcpu_ms_mean`` (stage wall
less the thread's CPU, ``scheduler_batch_stage_cpu_seconds_total``) —
data files over the EXISTING reader ``ratio``, read from a pair of
/metrics pages recorded anew from a CPU run of the real daemon at 200
nodes with the CPU family and the new stages in place (the run of
``benchmarks/testdata/record_metrics.py``, keeping of the daemon's pages
the consecutive pair that spans the most launches — the window: the
runner now reads /metrics several times while the daemon prewarms, so
the recorder's fixed page indices miss it — kept as
``daemon_200n_cpu.*``): counts and signs, never a speed."""

import math
import os

import pytest

import rig
import run

NAMES = ["launch.unnamed_ms_mean", "launch.offcpu_ms_mean",
         "transfer.offcpu_ms_mean", "features.offcpu_ms_mean"]
# each metric's layer as an accepted metric of that layer spells it
LAYER_OF = {"launch.unnamed_ms_mean": "launch.unaccounted_ms_mean",
            "launch.offcpu_ms_mean": "launch.host_ms_mean",
            "transfer.offcpu_ms_mean": "transfer.scatter_ms_per_kpod",
            "features.offcpu_ms_mean": "features.build_ms_per_kpod"}
NEW_FAMILIES = {"scheduler_batch_stage_cpu_seconds_total"}
TESTDATA = os.path.join(run.HERE, "testdata")
CELLS = ["schedperf5k-arrivals", "schedperf1k-arrivals",
         "interpod5k-arrivals", "mixedaffinity5k-arrivals",
         "mixedchurn5k-arrivals"]
STAGES = "scheduler_batch_stage_latency_microseconds"


def _pages(stem: str) -> tuple:
    out = []
    for side in ("open", "close"):
        with open(os.path.join(TESTDATA,
                               f"{stem}.{side}.metrics.txt")) as f:
            out.append(rig.parse_metrics(f.read()))
    return tuple(out)


def _spec(name: str) -> dict:
    return run.load_json(os.path.join(run.HERE, "metrics", name + ".json"))


def _read(name: str, pages: tuple):
    spec = _spec(name)
    ctx = {"daemon": pages, "apiserver": ({}, {}), "runner": {},
           "pods_bound": run.pods_scheduled(*pages), "trace": None,
           "trace_pods": None, "pods_per_launch": None, "config": {},
           "device_kind": "TPU v5 lite"}
    return run.load_module("readers", spec["arithmetic"]).read(
        spec["args"], ctx)


def _grew(pages: tuple, family: str, labels: dict) -> float:
    return (rig.family_sum(pages[1], family, labels) or 0.0) - \
        (rig.family_sum(pages[0], family, labels) or 0.0)


@pytest.mark.parametrize("name", NAMES)
def test_metric_is_an_entry_a_file_and_the_existing_reader(name):
    bench = run.load_json(os.path.join(rig.REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    entry = by_name[name]
    spec = _spec(name)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    # all five cells are in the list (a later cell may join it)
    assert set(CELLS) <= set(entry["workloads"])
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    assert entry["layer"] == by_name[LAYER_OF[name]]["layer"]
    assert entry["better"] == "lower"
    assert entry["moves"] == "submit_to_bind_p50_ms"
    assert spec["arithmetic"] == "ratio"
    # the four sit at the end of the list, in this order
    assert [m["name"] for m in bench["per_layer"]][-len(NAMES):] == NAMES
    # a term no older program prints, and read without "absent": a page
    # without it reads nothing, not 0
    terms = spec["args"]["num"]
    assert any("absent" not in t and (
        t["family"] in NEW_FAMILIES
        or t["labels"].get("stage") in ("pad", "scan_inputs"))
        for t in terms)


def test_readings_in_the_recorded_window():
    """Every reading is a number; what no stage names is not negative,
    and under the launch's whole."""
    pages = _pages("daemon_200n_cpu")
    launches = _grew(pages, STAGES + "_count", {"stage": "launch_total"})
    assert launches > 50
    for family in NEW_FAMILIES:
        assert rig.family_sum(pages[1], family) is not None, family
    values = {name: _read(name, pages) for name in NAMES}
    for name, value in values.items():
        assert value is not None and math.isfinite(value), (name, value)
    whole_ms = _grew(pages, STAGES + "_sum",
                     {"stage": "launch_total"}) / launches / 1e3
    assert 0.0 <= values["launch.unnamed_ms_mean"] < whole_ms
    # the parts off the CPU are parts of the launch's off-CPU time
    assert values["transfer.offcpu_ms_mean"] <= whole_ms
    assert values["features.offcpu_ms_mean"] <= whole_ms


@pytest.mark.parametrize("stem", ["daemon_200n", "daemon_200n_tenuring",
                                  "daemon_200n_packed", "daemon_200n_rows",
                                  "daemon_200n_plan", "daemon_200n_steps"])
@pytest.mark.parametrize("name", NAMES)
def test_metric_is_left_out_where_the_program_lacks_it(stem, name):
    """The parent's program (and every one before it) prints no CPU
    family and no ``pad`` stage: the reader returns nothing; it does not
    raise and it does not read 0."""
    pages = _pages(stem)
    for family in NEW_FAMILIES:
        assert rig.family_sum(pages[1], family) is None
    assert _read(name, pages) is None
