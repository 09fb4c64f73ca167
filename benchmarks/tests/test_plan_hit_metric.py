"""The per-layer metric PR 35 added for the tables the feature build
keeps between launches (``kubernetes_tpu/features/plan.py``):
``features.plan_hit_share`` — a data file over the EXISTING reader
``ratio``, hits over hits + misses of ``scheduler_feature_plan_total``
inside the window, read from a pair of /metrics pages recorded anew from
a CPU run of the real daemon at 200 nodes with the plan in place
(``python3 benchmarks/testdata/record_metrics.py <dir>``, the pair then
kept as ``daemon_200n_plan.*``): a count of launches, never a speed."""

import os

import pytest

import rig
import run

NAME = "features.plan_hit_share"
FAMILY = "scheduler_feature_plan_total"
TESTDATA = os.path.join(run.HERE, "testdata")
CELLS = ["schedperf5k-arrivals", "schedperf1k-arrivals",
         "interpod5k-arrivals", "mixedaffinity5k-arrivals"]
CAUSES = ("node_epoch", "vocab", "template_new", "not_neutral")


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


def _grew(pages: tuple, labels: dict) -> float:
    return (rig.family_sum(pages[1], FAMILY, labels) or 0.0) - \
        (rig.family_sum(pages[0], FAMILY, labels) or 0.0)


def test_metric_is_an_entry_a_file_and_the_existing_reader():
    bench = run.load_json(os.path.join(rig.REPO, "BENCHMARK.json"))
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    spec = run.load_json(os.path.join(run.HERE, "metrics", NAME + ".json"))
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    # every cell the issue names is in the list (a later cell may join it)
    assert set(CELLS) <= set(entry["workloads"])
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    # the layer's name as the accepted metrics of that layer spell it
    build = {m["name"]: m for m in bench["per_layer"]}[
        "features.build_ms_per_kpod"]
    assert entry["layer"] == build["layer"] == "feature build"
    assert entry["moves"] == "submit_to_bind_p50_ms"
    assert entry["better"] == "higher"
    assert entry["source"] == "program_counter"
    assert spec["arithmetic"] == "ratio"
    assert [t["family"] for t in spec["args"]["num"]] == [FAMILY]
    assert spec["args"]["num"][0]["labels"] == {"result": "hit"}
    assert [t["family"] for t in spec["args"]["den"]] == [FAMILY]
    assert "labels" not in spec["args"]["den"][0]      # hits AND misses
    # no "absent": a page without the family has to read nothing, not 0
    assert "absent" not in spec["args"]["num"][0]
    assert "absent" not in spec["args"]["den"][0]


def test_hits_over_launches_in_the_recorded_window():
    """The recorded cell draws its pods from a grid of ~50 templates on a
    mixed fleet, so its 4 s window still meets new templates: the share
    is hits over hits + misses of every cause, under 1 there (and 1.0
    where one template arrives on a static fleet, as in the four cells)."""
    pages = _pages("daemon_200n_plan")
    hits = _grew(pages, {"result": "hit"})
    misses = {c: _grew(pages, {"result": "miss", "cause": c})
              for c in CAUSES}
    launches = (rig.family_sum(
        pages[1], "scheduler_batch_stage_latency_microseconds_count",
        {"stage": "compile"}) - rig.family_sum(
        pages[0], "scheduler_batch_stage_latency_microseconds_count",
        {"stage": "compile"}))
    assert hits > 100
    # every launch's feature build is counted once, as one or the other
    assert hits + sum(misses.values()) == launches
    assert misses["template_new"] > 0
    assert misses["vocab"] == misses["not_neutral"] == 0
    assert _read(pages) == pytest.approx(hits / launches)
    assert 0.5 < _read(pages) < 1.0


@pytest.mark.parametrize("stem", ["daemon_200n", "daemon_200n_tenuring",
                                  "daemon_200n_packed", "daemon_200n_rows"])
def test_metric_is_left_out_where_the_program_lacks_it(stem):
    """The parent's program (and every one before it) prints no such
    family: the reader returns nothing; it does not raise and it does not
    read 0."""
    pages = _pages(stem)
    assert rig.family_sum(pages[1], FAMILY) is None
    assert _read(pages) is None
