"""The per-layer metric for the thread a streamed launch commits on
(``kubernetes_tpu/scheduler/pipeline.py _solve_stream``):
``commit.inline_share`` — a data file over the EXISTING reader
``ratio``, the launches committed on the drain thread
(``scheduler_commit_launches_total{path=inline}``) over the launches
counted on either path (``inline`` + ``worker``) inside the window, read
from a pair of /metrics pages recorded anew from a CPU run of the real
daemon at 200 nodes with the counter in place (the run of
``benchmarks/testdata/record_metrics.py``, keeping of the daemon's pages
the consecutive pair that spans the most launches, bucket rows and
``# HELP`` lines dropped, as ``daemon_200n_commit.*``): a count of
launches, never a speed."""

import copy
import os

import pytest

import rig
import run

NAME = "commit.inline_share"
FAMILY = "scheduler_commit_launches_total"
TESTDATA = os.path.join(run.HERE, "testdata")
CELLS = ["schedperf5k-arrivals", "schedperf1k-arrivals",
         "interpod5k-arrivals", "mixedaffinity5k-arrivals",
         "mixedchurn5k-arrivals"]
LAUNCHES = "scheduler_batch_stage_latency_microseconds_count"


def _pages(stem: str) -> tuple:
    out = []
    for side in ("open", "close"):
        with open(os.path.join(TESTDATA,
                               f"{stem}.{side}.metrics.txt")) as f:
            out.append(rig.parse_metrics(f.read()))
    return tuple(out)


def _spec() -> dict:
    return run.load_json(os.path.join(run.HERE, "metrics", NAME + ".json"))


def _read(pages: tuple):
    spec = _spec()
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
    spec = _spec()
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    # all five cells are in the list (a later cell may join it)
    assert set(CELLS) <= set(entry["workloads"])
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    # the layer's name as the accepted metrics of that layer spell it
    assert entry["layer"] == by_name["former.pods_per_launch"]["layer"] \
        == "queue + batch former"
    assert entry["moves"] == "submit_to_bind_p50_ms"
    assert entry["better"] == "higher"
    assert entry["source"] == "program_counter"
    assert spec["arithmetic"] == "ratio"
    (num,) = spec["args"]["num"]
    assert [t["labels"] for t in spec["args"]["den"]] == \
        [{"path": "inline"}, {"path": "worker"}]
    # each path's row appears only once it counts: read as 0 on a page
    # that was read
    for term in [num] + spec["args"]["den"]:
        assert term["family"] == FAMILY and term["process"] == "daemon"
        assert term["absent"] == 0
    assert num["labels"] == {"path": "inline"}


def test_every_launch_of_the_recorded_window_commits_inline():
    """The recorded cell's launches carry a few pods each, one chunk of
    the floor bucket: one ``inline`` increment a launch, no ``worker``
    row on either page, and the share reads 1.0, not nothing."""
    pages = _pages("daemon_200n_commit")
    launches = _grew(pages, LAUNCHES, {"stage": "launch_total"})
    assert launches > 100
    assert _grew(pages, FAMILY, {"path": "inline"}) == launches
    for page in pages:
        assert rig.family_sum(page, FAMILY, {"path": "worker"}) is None
    for stage in ("handoff", "wake", "commit.window_wait"):
        assert _grew(pages, LAUNCHES, {"stage": stage}) == 0, stage
    assert _read(pages) == 1.0


@pytest.mark.parametrize("worker", [1, 3, 149])
def test_launches_on_the_worker_lower_the_share(worker):
    """The same pair with ``worker`` launches counted at the close (a
    backlog's drains of several chunks): inline over both."""
    pages = _pages("daemon_200n_commit")
    close = copy.deepcopy(pages[1])
    close[FAMILY] = close[FAMILY] + [({"path": "worker"}, float(worker))]
    inline = _grew(pages, FAMILY, {"path": "inline"})
    assert _read((pages[0], close)) == pytest.approx(
        inline / (inline + worker))


@pytest.mark.parametrize("stem", ["daemon_200n", "daemon_200n_tenuring",
                                  "daemon_200n_packed", "daemon_200n_rows",
                                  "daemon_200n_plan", "daemon_200n_steps",
                                  "daemon_200n_cpu"])
def test_metric_is_left_out_where_the_program_lacks_it(stem):
    """The parent's program (and every one before it) prints no such
    family: both paths read 0, and the share reads nothing — it does not
    raise and it does not read 0."""
    pages = _pages(stem)
    assert rig.family_sum(pages[1], FAMILY) is None
    assert _read(pages) is None
