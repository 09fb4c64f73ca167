"""chip_smoke.py's phases at a tiny shape on the CPU backend.

The smoke itself hard-codes ``"tpu"`` and the full 5,000 x 30,000 shape
and cannot be made to pass here; its phases are functions of
``(platform, n_nodes, n_pods)`` so tier-1 can run the same code — real
apiserver, daemon, chipcheck and extender child processes — with
``"cpu"`` at 64 nodes.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_parent_never_imports_jax():
    """One process holds a chip: the smoke's own process must stay off
    JAX so that each phase's child can acquire it."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; "
         "sys.exit('jax' in sys.modules or 'jaxlib' in sys.modules)"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]


def test_phase_served_binds_every_pod_and_restarts_on_the_cache():
    rec = chip_smoke.phase_served("cpu", 64, 256)
    assert rec["device"]["platform"] == "cpu"
    assert rec["first"]["placed"]["bound"] == 256
    assert rec["second"]["placed"]["bound"] == 256 + 8
    assert rec["second"]["cache_hits"] > 0


def test_phase_answers_match_the_references():
    rec = chip_smoke.phase_answers("cpu", 64, 256)
    assert rec["device"]["platform"] == "cpu"
    checks = rec["checks"]
    assert checks["parity"]["decision_agreement_pct"] == 100.0
    assert checks["stream_vs_host"]["rows_differ"] == 0
    assert checks["half_plane"]["weight_bound"] < 256


def test_phase_extender_feasible_sets_equal_the_oracle():
    rec = chip_smoke.phase_extender("cpu", 64, 4)
    assert rec["device"]["platform"] == "cpu"
    # `rich` nodes filter differently per pod: the sets are not all N.
    assert len(set(rec["feasible_set_sizes"])) > 1


def test_a_phase_on_the_wrong_platform_fails():
    """The child reports its device from inside; a phase asked for one
    platform that ran on another is a failure, whatever it computed."""
    with pytest.raises(chip_smoke.SmokeFailure, match="not 'tpu'"):
        chip_smoke._require_device(
            "X", {"platform": "cpu", "kind": "cpu", "count": 1}, "tpu")


@pytest.mark.skipif(
    bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*")),
    reason="a TPU is attached: the smoke would run in full")
def test_smoke_fails_loudly_without_a_tpu():
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "Unable to initialize backend 'tpu'" in out.stdout
    assert '"ok"' not in out.stdout
    assert "bound " not in out.stdout  # no pod was ever scheduled
