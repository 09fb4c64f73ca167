"""The trace reduction on the small trace recorded on a TPU v5e
(``testdata/scan_small.xplane.pb``, made by ``testdata/record.py``: three
launches of a 64-step scan inside ``jit(_solve_scan)``, 20 ms of sleep
after each)."""

import json
import os
import subprocess
import sys

import pytest

import reduce_trace

TRACE = os.path.join(os.path.dirname(reduce_trace.__file__), "testdata",
                     "scan_small.xplane.pb")


def test_union_merges_nested_and_overlapping_intervals():
    assert reduce_trace._union([[5, 9], [0, 4], [3, 6], [20, 21], [6, 7]]) \
        == [[0, 9], [20, 21]]


@pytest.fixture(scope="module")
def reduced():
    proc = subprocess.run(
        [sys.executable, reduce_trace.__file__, TRACE], capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_device_busy_is_a_small_positive_share_of_the_span(reduced):
    assert reduced["n_device_planes"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # three launches with 20 ms of sleep after each: mostly idle
    assert reduced["window_s"] >= 0.06
    assert reduced["busy_s"] / reduced["window_s"] < 0.5


def test_the_scan_program_is_found_by_name(reduced):
    modules = reduced["lines"]["XLA Modules"]
    scan = {k: v for k, v in modules.items() if "solve_scan" in k}
    assert scan, modules
    count, seconds = next(iter(scan.values()))
    assert count == 3 and 0 < seconds <= reduced["busy_s"] * 1.01
    assert reduced["device_ops"] and len(reduced["device_ops"]) <= 10
    assert all(sec > 0 for _name, sec in reduced["device_ops"])


def test_idle_gaps_are_attributed_to_what_the_host_was_doing(reduced):
    gaps = reduced["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert any("bench_idle" in name for name, _sec in gaps), gaps
    assert all(" " not in name and "," not in name for name, _s in gaps)
