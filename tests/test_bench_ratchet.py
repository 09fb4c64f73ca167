"""The bench ratchet (tools/check_bench.py) guards the perf wins: the
newest committed BENCH_r{N}.json must not regress its predecessor's
density p50 by more than 15 % nor silently drop a stage from the
per-stage breakdown.  The repo's own artifacts must always pass (green
at snapshot); the unit cases pin the regression and stage-loss
detectors against synthetic artifacts."""

from __future__ import annotations

import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

spec = importlib.util.spec_from_file_location(
    "check_bench", os.path.join(REPO, "tools", "check_bench.py"))
cb = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cb)


def _parsed(p50=None, median=None, stages=None, pods=30000,
            device=None):
    d = {"metric": f"scheduler throughput, {pods} pods onto 5000 nodes"}
    if p50 is not None:
        d["elapsed_s_p50"] = p50
    if median is not None:
        d["median"] = median
    if stages is not None:
        d["stages"] = stages
    if device is not None:
        d["device"] = device
    return d


def _device(compiles=0, scatter=150.0, full=0.0, readback=120.0):
    return {"post_prewarm_compiles": compiles,
            "bytes_per_pod": {"scatter": scatter, "full_upload": full,
                              "readback": readback},
            "transfer_bytes": {"scatter": int(scatter * 100),
                               "full_upload": int(full * 100),
                               "readback": int(readback * 100)},
            "scatter_dominates": scatter > full,
            "hbm_peak_bytes": 1 << 20}


def test_repo_artifacts_pass_the_ratchet():
    problems = cb.check()
    assert problems == [], problems


def test_regression_beyond_tolerance_fails():
    arts = [("BENCH_r01.json", _parsed(p50=1.0)),
            ("BENCH_r02.json", _parsed(p50=1.2))]
    problems = cb.check(arts)
    assert len(problems) == 1 and "regressed" in problems[0]


def test_improvement_and_noise_band_pass():
    assert cb.check([("BENCH_r01.json", _parsed(p50=1.0)),
                     ("BENCH_r02.json", _parsed(p50=0.8))]) == []
    # +10% sits inside the 15% noise tolerance.
    assert cb.check([("BENCH_r01.json", _parsed(p50=1.0)),
                     ("BENCH_r02.json", _parsed(p50=1.1))]) == []


def test_p50_derived_from_median_for_old_artifacts():
    # Predecessor predates elapsed_s_p50: 30000 pods / 20000 pods-per-s
    # median = 1.5 s; a 2.0 s successor is a regression.
    arts = [("BENCH_r01.json", _parsed(median=20000.0)),
            ("BENCH_r02.json", _parsed(p50=2.0))]
    problems = cb.check(arts)
    assert len(problems) == 1 and "regressed" in problems[0]


def test_disappearing_stage_fails():
    stages_full = {"solve": {"seconds": 0.4}, "bind": {"seconds": 0.2}}
    stages_lost = {"solve": {"seconds": 0.4}}
    arts = [("BENCH_r01.json", _parsed(p50=1.0, stages=stages_full)),
            ("BENCH_r02.json", _parsed(p50=1.0, stages=stages_lost))]
    problems = cb.check(arts)
    assert len(problems) == 1 and "bind" in problems[0]
    # Losing the whole breakdown is also a failure...
    arts = [("BENCH_r01.json", _parsed(p50=1.0, stages=stages_full)),
            ("BENCH_r02.json", _parsed(p50=1.0))]
    assert any("breakdown" in p for p in cb.check(arts))
    # ...but a predecessor WITHOUT stages ratchets nothing (artifacts
    # predating the stage histogram).
    arts = [("BENCH_r01.json", _parsed(p50=1.0)),
            ("BENCH_r02.json", _parsed(p50=1.0, stages=stages_full))]
    assert cb.check(arts) == []


def test_fewer_than_two_artifacts_is_vacuously_green():
    assert cb.check([]) == []
    assert cb.check([("BENCH_r01.json", _parsed(p50=1.0))]) == []


# -- device-plane ratchet (ISSUE 9) ------------------------------------------

def test_post_prewarm_compile_fails_even_without_predecessor():
    arts = [("BENCH_r09.json", _parsed(p50=1.0,
                                       device=_device(compiles=2)))]
    problems = cb.check(arts)
    assert len(problems) == 1 and "post-prewarm" in problems[0]


def test_zero_compiles_and_steady_bytes_pass():
    arts = [("BENCH_r08.json", _parsed(p50=1.0, device=_device())),
            ("BENCH_r09.json", _parsed(p50=1.0, device=_device()))]
    assert cb.check(arts) == []


def test_transfer_bytes_per_pod_regression_fails():
    # Scatter giving way to full uploads: the per-pod byte total more
    # than doubles -> the device ratchet trips with the per-cause story.
    arts = [("BENCH_r08.json", _parsed(p50=1.0, device=_device())),
            ("BENCH_r09.json", _parsed(
                p50=1.0, device=_device(scatter=10.0, full=900.0)))]
    problems = cb.check(arts)
    assert len(problems) == 1 and "bytes-per-pod regressed" in problems[0]
    assert "full_upload" in problems[0]
    # Inside the noise band, and improvements, pass.
    assert cb.check(
        [("BENCH_r08.json", _parsed(p50=1.0, device=_device())),
         ("BENCH_r09.json", _parsed(p50=1.0, device=_device(
             scatter=160.0)))]) == []
    assert cb.check(
        [("BENCH_r08.json", _parsed(p50=1.0, device=_device())),
         ("BENCH_r09.json", _parsed(p50=1.0, device=_device(
             scatter=80.0, readback=60.0)))]) == []
    # A cause the older artifact never counted (the batch's packed
    # upload, counted since PR 31) is new accounting, not a regression.
    counted = _device()
    counted["bytes_per_pod"]["batch"] = 3000.0
    assert cb.check(
        [("BENCH_r08.json", _parsed(p50=1.0, device=_device())),
         ("BENCH_r09.json", _parsed(p50=1.0, device=counted))]) == []


def test_artifacts_predating_device_columns_ratchet_nothing():
    arts = [("BENCH_r05.json", _parsed(p50=1.0)),
            ("BENCH_r09.json", _parsed(p50=1.0, device=_device()))]
    assert cb.check(arts) == []
    # ...and a newest artifact without the section is not penalized.
    arts = [("BENCH_r05.json", _parsed(p50=1.0, device=_device())),
            ("BENCH_r09.json", _parsed(p50=1.0))]
    assert cb.check(arts) == []


# -- SOAK artifact ratchet (ISSUE 7) ----------------------------------------

def _soak(violations=0, double_binds=0, stranded=0, orphaned=0,
          monotonic=False, parity=100.0, settle=5.0):
    return {"invariant_violations": violations,
            "reconciliation": {"double_binds": double_binds,
                               "stranded_pending": stranded,
                               "orphaned_assumes": orphaned,
                               "bound_to_missing_node": 0},
            "queue_depth": {"monotonic_growth": monotonic,
                            "steady_window_slope_pods_per_s":
                                50.0 if monotonic else 0.0},
            "restart_parity": {"decision_parity_pct": parity,
                               "samples": 50},
            "settle_s": settle}


def test_repo_soak_artifacts_pass_the_ratchet():
    problems = cb.check_soak()
    assert problems == [], problems


def test_soak_invariant_violation_fails():
    problems = cb.check_soak([("SOAK_r07.json", _soak(violations=2))])
    assert len(problems) == 1 and "invariant violation" in problems[0]


def test_soak_reconciliation_failures_fail():
    problems = cb.check_soak([("SOAK_r07.json", _soak(double_binds=1,
                                                      orphaned=3))])
    assert len(problems) == 2
    assert any("double_binds" in p for p in problems)
    assert any("orphaned_assumes" in p for p in problems)


def test_soak_monotonic_queue_growth_fails():
    problems = cb.check_soak([("SOAK_r07.json", _soak(monotonic=True))])
    assert len(problems) == 1 and "monotonically" in problems[0]


def test_soak_restart_parity_below_100_fails():
    problems = cb.check_soak([("SOAK_r07.json", _soak(parity=99.5))])
    assert len(problems) == 1 and "parity" in problems[0]


def test_soak_lock_inversions_fail():
    art = _soak()
    art["locktrace"] = {"lock_inversions": 1, "long_holds": 0}
    problems = cb.check_soak([("SOAK_r13.json", art)])
    assert len(problems) == 1 and "inversion" in problems[0]


def test_soak_long_holds_fail():
    art = _soak()
    art["locktrace"] = {"lock_inversions": 0, "long_holds": 3}
    problems = cb.check_soak([("SOAK_r13.json", art)])
    assert len(problems) == 1 and "long lock hold" in problems[0]


def test_soak_tenancy_poison_contract_rows():
    art = _soak()
    art["tenancy_poison"] = {"offered": 450, "bound": 300,
                             "repromoted": False}
    problems = cb.check_soak([("SOAK_r13.json", art)])
    assert any("bound only 300/450" in p for p in problems)
    assert any("never re-promoted" in p for p in problems)
    art["tenancy_poison"] = {"offered": 450, "bound": 450,
                             "repromoted": True}
    assert cb.check_soak([("SOAK_r13.json", art)]) == []


def test_soak_clean_locktrace_and_prelocktrace_artifacts_pass():
    art = _soak()
    art["locktrace"] = {"lock_inversions": 0, "long_holds": 0}
    assert cb.check_soak([("SOAK_r13.json", art)]) == []
    # Artifacts predating locktrace carry no section: nothing ratchets.
    assert cb.check_soak([("SOAK_r07.json", _soak())]) == []


def test_soak_settle_regression_beyond_tolerance_fails():
    arts = [("SOAK_r07.json", _soak(settle=10.0)),
            ("SOAK_r08.json", _soak(settle=12.0))]
    problems = cb.check_soak(arts)
    assert len(problems) == 1 and "settle regressed" in problems[0]
    # Inside the noise band, and improvements, pass.
    assert cb.check_soak([("SOAK_r07.json", _soak(settle=10.0)),
                          ("SOAK_r08.json", _soak(settle=11.0))]) == []
    assert cb.check_soak([("SOAK_r07.json", _soak(settle=10.0)),
                          ("SOAK_r08.json", _soak(settle=7.0))]) == []


def test_soak_green_artifact_passes_alone():
    assert cb.check_soak([("SOAK_r07.json", _soak())]) == []


# -- device fault-tolerance invariants (ISSUE 10) ----------------------------

def test_soak_sanity_rejected_bind_fails():
    art = _soak()
    art["sanity_gate"] = {"rejects": 3, "rejected_binds": 1}
    problems = cb.check_soak([("SOAK_r10.json", art)])
    assert len(problems) == 1 and "sanity-gate" in problems[0]
    # Gate rejects alone (with zero rejected binds) are healthy chaos.
    art["sanity_gate"] = {"rejects": 3, "rejected_binds": 0}
    assert cb.check_soak([("SOAK_r10.json", art)]) == []


def test_soak_stuck_in_host_mode_fails():
    art = _soak()
    art["engine_mode_final"] = "host"
    problems = cb.check_soak([("SOAK_r10.json", art)])
    assert len(problems) == 1 and "host" in problems[0]
    art["engine_mode_final"] = "device"
    assert cb.check_soak([("SOAK_r10.json", art)]) == []


def test_soak_device_lost_wave_must_repromote():
    art = _soak()
    art["engine_mode_final"] = "device"
    art["device_lost_wave"] = {"tripped_to_host": True,
                               "repromoted": False}
    problems = cb.check_soak([("SOAK_r10.json", art)])
    assert len(problems) == 1 and "re-promoted" in problems[0]
    art["device_lost_wave"]["repromoted"] = True
    assert cb.check_soak([("SOAK_r10.json", art)]) == []


def test_density_run_stuck_in_host_mode_fails():
    dev = _device()
    dev["engine_mode_final"] = "host"
    problems = cb.check_device([("BENCH_r10.json", _parsed(
        p50=1.0, device=dev))])
    assert len(problems) == 1 and "host fallback" in problems[0]


def test_density_sanity_rejected_bind_fails():
    dev = _device()
    dev["engine_mode_final"] = "device"
    dev["sanity_rejected_binds"] = 2
    problems = cb.check_device([("BENCH_r10.json", _parsed(
        p50=1.0, device=dev))])
    assert len(problems) == 1 and "sanity-gate" in problems[0]
    dev["sanity_rejected_binds"] = 0
    assert cb.check_device([("BENCH_r10.json", _parsed(
        p50=1.0, device=dev))]) == []


# -- SERVING artifact ratchet (ISSUE 8) --------------------------------------

def _serving(trickle_p99=150.0, trickle_att=99.8, trickle_floor=99.0,
             burst_p99=900.0, burst_att=99.0, burst_floor=95.0):
    def row(p99, att, floor, slo):
        return {"latency_ms": {"p50": p99 / 2, "p99": p99},
                "slo": {"slo_ms": slo, "attainment_pct": att,
                        "attainment_floor_pct": floor}}
    return {"deadline_ms": 100.0,
            "workloads": {
                "poisson_trickle": row(trickle_p99, trickle_att,
                                       trickle_floor, 1000.0),
                "burst_replay": row(burst_p99, burst_att, burst_floor,
                                    5000.0)}}


def test_repo_serving_artifacts_pass_the_ratchet():
    problems = cb.check_serving()
    assert problems == [], problems


def test_serving_attainment_below_recorded_floor_fails():
    problems = cb.check_serving(
        [("SERVING_r08.json", _serving(trickle_att=97.0))])
    assert len(problems) == 1 and "below its recorded floor" in problems[0]
    # The floor is per-row: a burst-row miss fails too.
    problems = cb.check_serving(
        [("SERVING_r08.json", _serving(burst_att=90.0))])
    assert len(problems) == 1 and "burst_replay" in problems[0]


def test_serving_p99_regression_beyond_tolerance_fails():
    arts = [("SERVING_r08.json", _serving(trickle_p99=100.0)),
            ("SERVING_r09.json", _serving(trickle_p99=130.0))]
    problems = cb.check_serving(arts)
    assert len(problems) == 1 and "p99 regressed" in problems[0]
    # Inside the noise band, and improvements, pass.
    assert cb.check_serving(
        [("SERVING_r08.json", _serving(trickle_p99=100.0)),
         ("SERVING_r09.json", _serving(trickle_p99=110.0))]) == []
    assert cb.check_serving(
        [("SERVING_r08.json", _serving(trickle_p99=100.0)),
         ("SERVING_r09.json", _serving(trickle_p99=60.0))]) == []


def test_serving_green_artifact_passes_alone():
    assert cb.check_serving([("SERVING_r08.json", _serving())]) == []
    assert cb.check_serving([]) == []


# -- backend re-baselining (ISSUE 11 satellite) ------------------------------

def test_backend_change_rebaselines_wall_clock_rows():
    """A p50 measured on a different accelerator backend is a new
    baseline, not a regression: 23 s of CPU scan vs 1.3 s of TPU scan
    says nothing about the code between the artifacts."""
    arts = [("BENCH_r05.json", _parsed(p50=1.3)),
            ("BENCH_r11.json", dict(_parsed(p50=23.0), backend="cpu"))]
    assert cb.check(arts) == []
    # Same backend on both sides: the comparison is live again.
    arts = [("BENCH_r11.json", dict(_parsed(p50=23.0), backend="cpu")),
            ("BENCH_r12.json", dict(_parsed(p50=30.0), backend="cpu"))]
    problems = cb.check(arts)
    assert len(problems) == 1 and "regressed" in problems[0]


def test_backend_change_keeps_invariant_rows():
    """Re-baselining covers WALL-CLOCK rows only: a dropped stage or a
    post-prewarm compile still fails across a backend change."""
    stages = {"solve": {"seconds": 0.4}, "bind": {"seconds": 0.2}}
    arts = [("BENCH_r05.json", _parsed(p50=1.3, stages=stages)),
            ("BENCH_r11.json",
             dict(_parsed(p50=23.0, stages={"solve": {"seconds": 20.0}},
                          device=_device(compiles=2)), backend="cpu"))]
    problems = cb.check(arts)
    assert any("disappeared" in p for p in problems)
    assert any("post-prewarm" in p for p in problems)


def test_soak_settle_rebaselines_across_backend_change():
    arts = [("SOAK_r10.json", _soak(settle=1.7)),
            ("SOAK_r11.json", dict(_soak(settle=4.0), backend="cpu"))]
    assert cb.check_soak(arts) == []


def test_soak_settle_scans_back_past_foreign_backend_artifacts():
    """A mixed-backend history must not retire the wall-clock ratchet:
    the settle row compares against the LAST same-backend artifact,
    not just the immediate predecessor."""
    arts = [("SOAK_r10.json", dict(_soak(settle=1.0), backend="cpu")),
            ("SOAK_r11.json", _soak(settle=1.7)),  # tpu interlude
            ("SOAK_r12.json", dict(_soak(settle=9.0), backend="cpu"))]
    problems = cb.check_soak(arts)
    assert len(problems) == 1 and "settle regressed" in problems[0] \
        and "SOAK_r10" in problems[0]
    ok = [arts[0], arts[1],
          ("SOAK_r12.json", dict(_soak(settle=1.05), backend="cpu"))]
    assert cb.check_soak(ok) == []


# -- active-active HA ratchet (ISSUE 11) -------------------------------------

def _ha(double_binds=0, stranded=0, violations=0, takeover=0.6,
        agg=500.0, baseline=450.0, cpus=8):
    return {"double_binds": double_binds,
            "stranded_pending": stranded,
            "invariant_violations": violations,
            "takeover": {"takeover_settle_s": takeover,
                         "victim": "inc-0",
                         "queue_at_kill": 900},
            "aggregate_steady_pods_per_s": agg,
            "single_scheduler_pods_per_s": baseline,
            "n_incarnations": 3,
            "cpus": cpus,
            "lease_handoffs": 3,
            "cross_shard_conflicts": 12}


def test_repo_ha_artifacts_pass_the_ratchet():
    problems = cb.check_ha()
    assert problems == [], problems


def test_ha_artifacts_predating_the_wave_ratchet_nothing():
    assert cb.check_ha([("SOAK_r10.json", _soak())]) == []
    assert cb.check_ha([]) == []


def test_ha_double_bind_fails():
    problems = cb.check_ha(
        [("SOAK_r11.json", dict(_soak(), ha=_ha(double_binds=1)))])
    assert len(problems) == 1 and "double-bind" in problems[0]


def test_ha_stranded_pod_fails():
    problems = cb.check_ha(
        [("SOAK_r11.json", dict(_soak(), ha=_ha(stranded=4)))])
    assert len(problems) == 1 and "stranded" in problems[0]


def test_ha_slow_takeover_fails():
    problems = cb.check_ha(
        [("SOAK_r11.json", dict(_soak(), ha=_ha(takeover=1.4)))])
    assert len(problems) == 1 and "takeover" in problems[0]
    assert cb.check_ha(
        [("SOAK_r11.json", dict(_soak(), ha=_ha(takeover=0.99)))]) == []


def test_ha_missing_takeover_or_rate_fails():
    ha = _ha()
    del ha["takeover"]
    problems = cb.check_ha([("SOAK_r11.json", dict(_soak(), ha=ha))])
    assert len(problems) == 1 and "takeover_settle_s" in problems[0]
    ha = _ha()
    ha["aggregate_steady_pods_per_s"] = 0
    problems = cb.check_ha([("SOAK_r11.json", dict(_soak(), ha=ha))])
    assert len(problems) == 1 and "aggregate" in problems[0]


def test_ha_aggregate_below_single_scheduler_baseline_fails():
    """The controlled scale-out bar: the aggregate must not fall below
    the wave's OWN phase-0 single-scheduler baseline (same storm, same
    rig, same chaos, one incarnation holding every shard — the only
    variable is the scheduler count)."""
    art = dict(_soak(), ha=_ha(agg=300.0, baseline=352.5))
    problems = cb.check_ha([("SOAK_r11.json", art)])
    assert len(problems) == 1 and "below" in problems[0]
    good = dict(_soak(), ha=_ha(agg=400.0, baseline=352.5))
    assert cb.check_ha([("SOAK_r11.json", good)]) == []
    # A hair's-width miss is measurement noise (both sides are single
    # noisy storm measurements), not a regression: the rate rows carry
    # a tolerance like every other wall-clock ratchet.
    near = dict(_soak(), ha=_ha(agg=340.0, baseline=352.5))
    assert cb.check_ha([("SOAK_r11.json", near)]) == []


def test_ha_missing_single_scheduler_baseline_fails():
    ha = _ha()
    del ha["single_scheduler_pods_per_s"]
    problems = cb.check_ha([("SOAK_r11.json", dict(_soak(), ha=ha))])
    assert len(problems) == 1 and "baseline" in problems[0]


def test_ha_scale_out_bar_disarmed_on_serialized_rig():
    """On a rig that cannot run the incarnations concurrently (cpus <=
    n_incarnations) the aggregate-vs-baseline inequality is physically
    unreachable — N CPU-bound schedulers timeshare one core — so the
    aggregate is pinned by the predecessor ratchet instead."""
    art = dict(_soak(), ha=_ha(agg=180.0, baseline=900.0, cpus=1))
    assert cb.check_ha([("SOAK_r11.json", art)]) == []
    # Same numbers on a parallel rig: the bar arms and fails.
    art = dict(_soak(), ha=_ha(agg=180.0, baseline=900.0, cpus=8))
    problems = cb.check_ha([("SOAK_r11.json", art)])
    assert len(problems) == 1 and "below" in problems[0]


def test_ha_efficiency_ratchets_against_predecessors_ha_wave():
    """Artifact-over-artifact, the bar is the predecessor's scale-out
    EFFICIENCY (aggregate / same-wave solo baseline): both terms of
    each ratio come from one rig minutes apart, so the comparison
    survives the rig itself speeding up or slowing down between
    artifacts — but only within one backend (ratio rows re-baseline on
    a device change like every other cross-artifact row)."""
    prev = dict(_soak(), backend="cpu", ha=_ha(agg=800.0,
                                               baseline=450.0))
    # Efficiency 700/450 = 1.56 vs the predecessor's 800/450 = 1.78:
    # a real scale-out regression, rig speed unchanged.
    arts = [("SOAK_r11.json", prev),
            ("SOAK_r12.json", dict(_soak(), backend="cpu",
                                   ha=_ha(agg=700.0, baseline=450.0)))]
    problems = cb.check_ha(arts)
    assert len(problems) == 1 and "efficiency" in problems[0]
    # Within tolerance of the predecessor's ratio: noise.
    arts = [("SOAK_r11.json", prev),
            ("SOAK_r12.json", dict(_soak(), backend="cpu",
                                   ha=_ha(agg=770.0, baseline=450.0)))]
    assert cb.check_ha(arts) == []
    # Rig drift: the whole box halved, aggregate AND solo both fell —
    # the efficiency held, so nothing regressed in this repo's code.
    arts = [("SOAK_r11.json", prev),
            ("SOAK_r12.json", dict(_soak(), backend="cpu",
                                   ha=_ha(agg=400.0, baseline=225.0)))]
    assert cb.check_ha(arts) == []
    # Different backend: re-baselined, no problem.
    arts = [("SOAK_r11.json", prev),
            ("SOAK_r12.json", dict(_soak(), backend="tpu",
                                   ha=_ha(agg=700.0, baseline=450.0)))]
    assert cb.check_ha(arts) == []
    # One-phase rig drift: the solo baseline inflated 2x (cache
    # warmth a timeshared aggregate cannot follow) while the aggregate
    # held — the ratio fell, but the fleet got no slower: drift, not a
    # regression.
    arts = [("SOAK_r11.json", prev),
            ("SOAK_r12.json", dict(_soak(), backend="cpu",
                                   ha=_ha(agg=810.0, baseline=900.0,
                                          cpus=1)))]
    assert cb.check_ha(arts) == []
    # But an inflated solo does NOT excuse a genuine aggregate
    # collapse: both the ratio and the raw rate fell — regression.
    arts = [("SOAK_r11.json", prev),
            ("SOAK_r12.json", dict(_soak(), backend="cpu",
                                   ha=_ha(agg=400.0, baseline=900.0,
                                          cpus=1)))]
    problems = cb.check_ha(arts)
    assert len(problems) == 1 and "efficiency" in problems[0]


def test_ha_predecessor_without_solo_baseline_falls_back_to_rate():
    """A predecessor stamped before the phase-0 control existed can
    only support the raw-rate comparison."""
    prev_ha = _ha(agg=800.0)
    del prev_ha["single_scheduler_pods_per_s"]
    prev = dict(_soak(), backend="cpu", ha=prev_ha)
    arts = [("SOAK_r11.json", prev),
            ("SOAK_r12.json", dict(_soak(), backend="cpu",
                                   ha=_ha(agg=700.0)))]
    problems = cb.check_ha(arts)
    assert len(problems) == 1 and "HA aggregate" in problems[0]
    arts = [("SOAK_r11.json", prev),
            ("SOAK_r12.json", dict(_soak(), backend="cpu",
                                   ha=_ha(agg=770.0)))]
    assert cb.check_ha(arts) == []


# -- tenancy ratchet (ISSUE 12) ----------------------------------------------

def _tenancy(backend="cpu", ratio=1.4, fair_err=0.03, cross=0,
             attainment=100.0, floor=100.0, compiles=0, repromoted=True,
             victim_mode="device", all_bound=True):
    return {
        "backend": backend,
        "tenants": ["t-a", "t-b", "t-c"],
        "weights": {"t-a": 2.0, "t-b": 1.0, "t-c": 1.0},
        "rows": {"trickle_with_neighbor": {
            "tenant": "t-a",
            "latency_ms": {"p99": 200.0},
            "slo": {"slo_ms": 1000.0, "attainment_pct": attainment,
                    "attainment_floor_pct": floor}}},
        "interference": {"ratio": ratio, "bar": 2.0},
        "fairness": {"max_rel_error": fair_err, "bar": 0.10,
                     "observed_shares": {}, "expected_shares": {}},
        "isolation": {"cross_tenant_faults": cross,
                      "cross_tenant_sanity_rejects": 0,
                      "victim_modes": {"t-a": victim_mode,
                                       "t-b": "device"},
                      "repromoted": repromoted,
                      "all_bound": all_bound},
        "device": {"post_prewarm_compiles": compiles},
    }


def test_tenancy_repo_artifacts_pass():
    assert cb.check_tenancy() == []


def test_tenancy_clean_artifact_passes():
    assert cb.check_tenancy([("TENANCY_r12.json", _tenancy())]) == []


def test_tenancy_slo_floor_breach_fails():
    problems = cb.check_tenancy(
        [("TENANCY_r12.json", _tenancy(attainment=98.0))])
    assert len(problems) == 1 and "attainment" in problems[0]


def test_tenancy_cross_tenant_fault_leak_fails():
    problems = cb.check_tenancy(
        [("TENANCY_r12.json", _tenancy(cross=2))])
    assert len(problems) == 1 and "cross-tenant" in problems[0]


def test_tenancy_interference_over_bar_fails():
    problems = cb.check_tenancy(
        [("TENANCY_r12.json", _tenancy(ratio=2.3))])
    assert len(problems) == 1 and "interference" in problems[0]


def test_tenancy_fairness_over_bar_fails():
    problems = cb.check_tenancy(
        [("TENANCY_r12.json", _tenancy(fair_err=0.15))])
    assert len(problems) == 1 and "fairness" in problems[0]


def test_tenancy_victim_knocked_off_device_fails():
    problems = cb.check_tenancy(
        [("TENANCY_r12.json", _tenancy(victim_mode="host"))])
    assert len(problems) == 1 and "knocked" in problems[0]


def test_tenancy_stuck_host_or_stranded_fails():
    assert any("re-promoted" in p for p in cb.check_tenancy(
        [("TENANCY_r12.json", _tenancy(repromoted=False))]))
    assert any("stranded" in p for p in cb.check_tenancy(
        [("TENANCY_r12.json", _tenancy(all_bound=False))]))


def test_tenancy_post_prewarm_compile_fails():
    problems = cb.check_tenancy(
        [("TENANCY_r12.json", _tenancy(compiles=3))])
    assert len(problems) == 1 and "compile" in problems[0]


def test_tenancy_interference_ratchets_same_backend_scan_back():
    # Regression vs the predecessor fails...
    arts = [("TENANCY_r12.json", _tenancy(ratio=1.2)),
            ("TENANCY_r13.json", _tenancy(ratio=1.5))]
    problems = cb.check_tenancy(arts)
    assert len(problems) == 1 and "regressed" in problems[0]
    # ...within tolerance passes...
    arts = [("TENANCY_r12.json", _tenancy(ratio=1.4)),
            ("TENANCY_r13.json", _tenancy(ratio=1.45))]
    assert cb.check_tenancy(arts) == []
    # ...a foreign-backend predecessor re-baselines, but the scan-back
    # still finds the LAST same-backend artifact past it.
    arts = [("TENANCY_r11.json", _tenancy(ratio=1.0, backend="cpu")),
            ("TENANCY_r12.json", _tenancy(ratio=1.0, backend="tpu")),
            ("TENANCY_r13.json", _tenancy(ratio=1.5, backend="cpu"))]
    problems = cb.check_tenancy(arts)
    assert len(problems) == 1 and "regressed" in problems[0]


def test_tenancy_fairness_error_ratchets():
    arts = [("TENANCY_r12.json", _tenancy(fair_err=0.02)),
            ("TENANCY_r13.json", _tenancy(fair_err=0.06))]
    problems = cb.check_tenancy(arts)
    assert len(problems) == 1 and "fairness error regressed" in problems[0]


# -- soak near-capacity wave (ISSUE 12 satellite) ----------------------------

def test_soak_capacity_wave_overcommit_fails():
    art = dict(_soak(), capacity={"overcommitted_nodes": 2,
                                  "stranded_pending": 0,
                                  "bind_capacity_rejects": 4})
    problems = cb.check_soak([("SOAK_r12.json", art)])
    assert any("overcommitted" in p for p in problems)


def test_soak_capacity_wave_stranded_fails():
    art = dict(_soak(), capacity={"overcommitted_nodes": 0,
                                  "stranded_pending": 3,
                                  "bind_capacity_rejects": 4})
    problems = cb.check_soak([("SOAK_r12.json", art)])
    assert any("stranded" in p for p in problems)


def test_soak_without_capacity_section_ratchets_nothing():
    assert cb.check_soak([("SOAK_r11.json", _soak())]) == []


# -- overload-protection ratchet (ISSUE 16) ----------------------------------

def _kill(lost=0, double=0, stranded=0, mid=True, relists=2):
    return {"acked_creates": 800, "acked_writes_lost": lost,
            "lost_sample": [], "double_binds": double,
            "wal_records_audited": 1600, "stranded_pending": stranded,
            "killed_mid_avalanche": mid, "bound_at_kill": 150 if mid
            else 0, "pending_at_kill": 650 if mid else 0,
            "downtime_s": 1.2, "relists": relists,
            "restart_settle_s": 4.0}


def _overload(shed=5000, expiries=0, system_rejected=0, depth=12,
              limit=16, goodput=120.0, stranded=0, samples=150,
              errors=0, multiple=8.0):
    return {"queue_limit": limit, "calibration_pods_per_s": 300.0,
            "offered_ops": 4200, "offered_multiple": multiple,
            "acked_creates": 900, "shed_429": shed,
            "goodput_pods_per_s": goodput, "lease_expiries": expiries,
            "leases_held_final": 4, "system_rejected": system_rejected,
            "max_queue_depth": depth, "debug_vars_samples": samples,
            "debug_vars_errors": errors, "stranded_pending": stranded}


def test_repo_artifacts_pass_the_overload_ratchet():
    problems = cb.check_overload()
    assert problems == [], problems


def test_overload_sections_absent_ratchet_nothing():
    assert cb.check_overload([("SOAK_r13.json", _soak())]) == []
    assert cb.check_overload([]) == []


def test_kill_wave_acked_write_loss_fails():
    art = dict(_soak(), apiserver_kill=_kill(lost=3))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "acknowledged write" in problems[0]


def test_kill_wave_double_bind_fails():
    art = dict(_soak(), apiserver_kill=_kill(double=1))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "double-bind" in problems[0]


def test_kill_wave_stranded_fails():
    art = dict(_soak(), apiserver_kill=_kill(stranded=7))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "stranded" in problems[0]


def test_kill_wave_must_land_mid_avalanche_and_relist():
    art = dict(_soak(), apiserver_kill=_kill(mid=False))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "mid-avalanche" in problems[0]
    art = dict(_soak(), apiserver_kill=_kill(relists=0))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "relist" in problems[0]


def test_kill_wave_clean_passes():
    art = dict(_soak(), apiserver_kill=_kill())
    assert cb.check_overload([("SOAK_r16.json", art)]) == []


def test_overload_wave_must_actually_shed():
    art = dict(_soak(), overload=_overload(shed=0))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "never tripped" in problems[0]


def test_overload_lease_expiry_or_system_shed_fails():
    art = dict(_soak(), overload=_overload(expiries=2))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "lease" in problems[0]
    art = dict(_soak(), overload=_overload(system_rejected=4))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "system-lane" in problems[0]


def test_overload_unbounded_queue_or_zero_goodput_fails():
    art = dict(_soak(), overload=_overload(depth=40, limit=16))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "bound" in problems[0]
    art = dict(_soak(), overload=_overload(goodput=0.0))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "goodput" in problems[0]


def test_overload_exempt_probe_failures_fail():
    art = dict(_soak(), overload=_overload(errors=3))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "/debug/vars" in problems[0]


def test_overload_below_3x_capacity_fails():
    art = dict(_soak(), overload=_overload(multiple=1.5))
    problems = cb.check_overload([("SOAK_r16.json", art)])
    assert len(problems) == 1 and "3x" in problems[0]


def test_overload_clean_wave_passes():
    art = dict(_soak(), overload=_overload(),
               apiserver_kill=_kill())
    assert cb.check_overload([("SOAK_r16.json", art)]) == []


# -- compile-surface provenance (kt-xray, ISSUE 14 satellite) ----------------

def _xray(h):
    return {"hash": f"sha256:{h}", "programs": 18}


def test_repo_artifacts_pass_the_xray_ratchet():
    assert cb.check_xray() == []


def test_xray_hash_change_with_regeneration_passes():
    arts = [("BENCH_r11.json", dict(_parsed(p50=1.0), xray=_xray("aa"))),
            ("BENCH_r12.json", dict(_parsed(p50=1.0), xray=_xray("bb")))]
    assert cb.check_xray(arts, soak_artifacts=[],
                         manifest=_xray("bb")) == []


def test_xray_hash_change_without_regeneration_fails():
    arts = [("BENCH_r11.json", dict(_parsed(p50=1.0), xray=_xray("aa"))),
            ("BENCH_r12.json", dict(_parsed(p50=1.0), xray=_xray("bb")))]
    problems = cb.check_xray(arts, soak_artifacts=[],
                             manifest=_xray("aa"))
    assert len(problems) == 1 and "without a manifest regeneration" \
        in problems[0]


def test_xray_stable_hash_ignores_committed_manifest_evolution():
    # The manifest legitimately regenerates between benches; only a
    # CHANGE between consecutive stamps demands the committed hash.
    arts = [("BENCH_r11.json", dict(_parsed(p50=1.0), xray=_xray("aa"))),
            ("BENCH_r12.json", dict(_parsed(p50=1.0), xray=_xray("aa")))]
    assert cb.check_xray(arts, soak_artifacts=[],
                         manifest=_xray("zz")) == []


def test_xray_soak_stamp_ratchets_too():
    soaks = [("SOAK_r13.json", dict(_soak(), xray=_xray("aa"))),
             ("SOAK_r14.json", dict(_soak(), xray=_xray("bb")))]
    problems = cb.check_xray([], soak_artifacts=soaks,
                             manifest=_xray("aa"))
    assert len(problems) == 1 and "SOAK" in problems[0]


def test_xray_hash_change_with_no_committed_manifest_fails():
    arts = [("BENCH_r11.json", dict(_parsed(p50=1.0), xray=_xray("aa"))),
            ("BENCH_r12.json", dict(_parsed(p50=1.0), xray=_xray("bb")))]
    problems = cb.check_xray(arts, soak_artifacts=[], manifest=None)
    assert len(problems) == 1 and "not committed" in problems[0]


def test_xray_unstamped_artifacts_ratchet_nothing():
    arts = [("BENCH_r05.json", _parsed(p50=1.0)),
            ("BENCH_r11.json", dict(_parsed(p50=1.0), xray=_xray("aa")))]
    assert cb.check_xray(arts, soak_artifacts=[],
                         manifest=None) == []


# -- wire + scatter ratchets (ISSUE 15) ---------------------------------

def _wire_art(median=4000.0, zero=0, backend="cpu", scatter=None):
    d = _parsed(p50=6.0)
    d["backend"] = backend
    d["wire"] = {"median_pods_per_second": median,
                 "zero_bound_runs": zero}
    if scatter is not None:
        d["device"] = _device(scatter=scatter)
    return d


def test_wire_zero_bound_run_fails():
    problems = cb.check_wire([("BENCH_r15.json", _wire_art(zero=1))])
    assert problems and "zero-bound" in problems[0]


def test_wire_throughput_regression_fails_and_noise_passes():
    arts = [("BENCH_r11.json", _wire_art(median=4000.0)),
            ("BENCH_r15.json", _wire_art(median=3000.0))]
    assert any("wire throughput regressed" in p
               for p in cb.check_wire(arts))
    arts[-1] = ("BENCH_r15.json", _wire_art(median=3900.0))
    assert cb.check_wire(arts) == []


def test_wire_ratchet_scans_back_past_other_backends():
    arts = [("BENCH_r11.json", _wire_art(median=4000.0, backend="cpu")),
            ("BENCH_r12.json", _wire_art(median=9000.0, backend="tpu")),
            ("BENCH_r15.json", _wire_art(median=3000.0, backend="cpu"))]
    assert any("wire throughput regressed" in p
               for p in cb.check_wire(arts))


def test_wire_artifacts_without_wire_section_ratchet_nothing():
    assert cb.check_wire([("BENCH_r01.json", _parsed(p50=6.0))]) == []


def test_scatter_bytes_per_pod_regression_fails():
    arts = [("BENCH_r11.json", _wire_art(scatter=80.0)),
            ("BENCH_r15.json", _wire_art(scatter=120.0))]
    assert any("scatter bytes-per-pod regressed" in p
               for p in cb.check_scatter_bytes(arts))
    arts[-1] = ("BENCH_r15.json", _wire_art(scatter=60.0))
    assert cb.check_scatter_bytes(arts) == []


def test_scatter_ratchet_scans_back_same_backend():
    arts = [("BENCH_r11.json", _wire_art(scatter=80.0, backend="cpu")),
            ("BENCH_r12.json", _wire_art(scatter=10.0, backend="tpu")),
            ("BENCH_r15.json", _wire_art(scatter=120.0, backend="cpu"))]
    assert any("scatter bytes-per-pod regressed" in p
               for p in cb.check_scatter_bytes(arts))


def test_all_runs_zero_bound_still_fails_without_a_median():
    """A fully-broken rig (every wire run zero-bound) emits a wire
    section with only the failure count — the check must fire on it."""
    d = _parsed(p50=6.0)
    d["backend"] = "cpu"
    d["wire"] = {"zero_bound_runs": 3, "runs": []}
    problems = cb.check_wire([("BENCH_r15.json", d)])
    assert problems and "zero-bound" in problems[0]


def test_all_wire_runs_errored_still_fails():
    """A rig whose every wire run errored before sampling (no runs, no
    zero-bounds) must fail too — not silently retire the wire ratchet."""
    d = _parsed(p50=6.0)
    d["backend"] = "cpu"
    d["wire"] = {"zero_bound_runs": 0, "failed_runs": 3, "runs": []}
    problems = cb.check_wire([("BENCH_r15.json", d)])
    assert problems and "every wire run failed" in problems[0]


# -- continuous-defrag ratchet (ISSUE 17) ------------------------------------

def _defrag(gain=0.5, executed=6, pdb=0, stranded=0, intents=0,
            double=0, double_cap=0, inv=0, batch=2, cap=4, mid=True,
            recovered=1):
    return {"n_nodes": 8, "small_pods": 24, "churn_deleted": 8,
            "large_pods": 3, "blocked_larges_bound": 3,
            "defrag_gain": gain, "unblocked_credited": 3,
            "migrations_executed": executed,
            "migrations_completed": executed - 1, "max_batch": batch,
            "migration_cap": cap, "vetoed_budget": 0, "vetoed_pdb": 10,
            "cas_conflicts": 0, "pdb_violations": pdb,
            "stranded": stranded, "lingering_intents": intents,
            "double_binds": double, "double_capacity": double_cap,
            "invariant_violations": inv, "invariant_detail": {},
            "killed_mid_migration": mid,
            "migrations_recovered": recovered,
            "migration_intents_cleared": 0, "duration_s": 5.0}


def test_repo_artifacts_pass_the_defrag_ratchet():
    problems = cb.check_defrag()
    assert problems == [], problems


def test_defrag_section_absent_ratchets_nothing():
    assert cb.check_defrag([("SOAK_r16.json", _soak())]) == []
    assert cb.check_defrag([]) == []


def test_defrag_zero_gain_or_zero_migrations_fails():
    art = dict(_soak(), defrag=_defrag(gain=0.0))
    problems = cb.check_defrag([("SOAK_r17.json", art)])
    assert any("defrag_gain" in p for p in problems)
    art = dict(_soak(), defrag=_defrag(executed=0))
    problems = cb.check_defrag([("SOAK_r17.json", art)])
    assert any("zero migrations" in p for p in problems)


def test_defrag_pdb_violation_fails():
    art = dict(_soak(), defrag=_defrag(pdb=1))
    problems = cb.check_defrag([("SOAK_r17.json", art)])
    assert len(problems) == 1 and "PDB" in problems[0]


def test_defrag_stranded_or_lingering_intent_fails():
    art = dict(_soak(), defrag=_defrag(stranded=2))
    problems = cb.check_defrag([("SOAK_r17.json", art)])
    assert len(problems) == 1 and "stranded" in problems[0]
    art = dict(_soak(), defrag=_defrag(intents=1))
    problems = cb.check_defrag([("SOAK_r17.json", art)])
    assert len(problems) == 1 and "never cleared" in problems[0]


def test_defrag_double_capacity_and_invariants_fail():
    art = dict(_soak(), defrag=_defrag(double_cap=1))
    problems = cb.check_defrag([("SOAK_r17.json", art)])
    assert len(problems) == 1 and "double-capacity" in problems[0]
    art = dict(_soak(), defrag=_defrag(inv=3))
    problems = cb.check_defrag([("SOAK_r17.json", art)])
    assert len(problems) == 1 and "invariant" in problems[0]


def test_defrag_budget_leak_fails():
    art = dict(_soak(), defrag=_defrag(batch=7, cap=4))
    problems = cb.check_defrag([("SOAK_r17.json", art)])
    assert len(problems) == 1 and "per-round cap" in problems[0]


def test_defrag_kill_arc_must_land_and_recover():
    art = dict(_soak(), defrag=_defrag(mid=False))
    problems = cb.check_defrag([("SOAK_r17.json", art)])
    assert len(problems) == 1 and "mid-migration" in problems[0]
    art = dict(_soak(), defrag=_defrag(recovered=0))
    problems = cb.check_defrag([("SOAK_r17.json", art)])
    assert len(problems) == 1 and "requeued" in problems[0]


def test_defrag_clean_passes():
    art = dict(_soak(), defrag=_defrag())
    assert cb.check_defrag([("SOAK_r17.json", art)]) == []


# -- kt-prof profile ratchet (ISSUE 18) --------------------------------------

def _profile(unclassified=0.05, decode_us=40.0, handler_us=25.0,
             serialize_us=60.0, enabled=True, wire=True):
    p = {"wall_s": 12.0, "enabled": enabled, "samples": 220,
         "sampler_self_cpu_s": 0.02}
    if enabled:
        p["cpu_seconds"] = {"solve_host": 8.0, "feature_build": 1.5,
                            "other": 0.5}
        p["cpu_fraction"] = {"solve_host": 0.8, "feature_build": 0.15,
                             "other": 0.05}
        p["unclassified_fraction"] = unclassified
    if wire:
        p["wire"] = {
            "decode": {"seconds": 0.4, "events": 10000,
                       "us_per_event": decode_us},
            "handler": {"seconds": 0.25, "events": 10000,
                        "us_per_event": handler_us},
            "serialize": {"seconds": 0.6, "ops": 10000,
                          "us_per_op": serialize_us}}
    return p


def _prof_art(profile=None, wire_profile=None, backend="cpu"):
    d = _parsed(p50=6.0)
    d["backend"] = backend
    if profile is not None:
        d["profile"] = profile
    if wire_profile is not None:
        d["wire"] = {"median_pods_per_second": 4000.0,
                     "zero_bound_runs": 0, "profile": wire_profile}
    return d


def test_repo_artifacts_pass_the_profile_ratchet():
    problems = cb.check_profile()
    assert problems == [], problems


def test_profile_unclassified_above_bar_fails():
    art = _prof_art(profile=_profile(unclassified=0.35, wire=False))
    problems = cb.check_profile([("BENCH_r16.json", art)])
    assert len(problems) == 1 and "unclassified" in problems[0]
    ok = _prof_art(profile=_profile(unclassified=0.19, wire=False))
    assert cb.check_profile([("BENCH_r16.json", ok)]) == []


def test_profile_stamped_disabled_fails():
    art = _prof_art(profile=_profile(enabled=False, wire=False))
    problems = cb.check_profile([("BENCH_r16.json", art)])
    assert len(problems) == 1 and "KT_PROF=0" in problems[0]


def test_profile_per_event_cost_regression_fails_and_noise_passes():
    arts = [("BENCH_r15.json",
             _prof_art(wire_profile=_profile(decode_us=40.0))),
            ("BENCH_r16.json",
             _prof_art(wire_profile=_profile(decode_us=60.0)))]
    problems = cb.check_profile(arts)
    assert len(problems) == 1 and "decode" in problems[0] \
        and "regressed" in problems[0]
    # Inside the 15% band, and improvements, pass.
    arts[-1] = ("BENCH_r16.json",
                _prof_art(wire_profile=_profile(decode_us=44.0)))
    assert cb.check_profile(arts) == []
    arts[-1] = ("BENCH_r16.json",
                _prof_art(wire_profile=_profile(decode_us=20.0)))
    assert cb.check_profile(arts) == []


def test_profile_serialize_and_handler_costs_ratchet_too():
    arts = [("BENCH_r15.json",
             _prof_art(wire_profile=_profile())),
            ("BENCH_r16.json",
             _prof_art(wire_profile=_profile(serialize_us=90.0,
                                             handler_us=40.0)))]
    problems = cb.check_profile(arts)
    assert any("serialize" in p for p in problems)
    assert any("handler" in p for p in problems)


def test_profile_ratchet_scans_back_past_other_backends():
    arts = [("BENCH_r14.json",
             _prof_art(wire_profile=_profile(decode_us=40.0))),
            ("BENCH_r15.json",
             _prof_art(wire_profile=_profile(decode_us=5.0),
                       backend="tpu")),
            ("BENCH_r16.json",
             _prof_art(wire_profile=_profile(decode_us=60.0)))]
    problems = cb.check_profile(arts)
    assert len(problems) == 1 and "BENCH_r14" in problems[0]


def test_profile_section_disappearing_fails():
    arts = [("BENCH_r15.json",
             _prof_art(profile=_profile(wire=False))),
            ("BENCH_r16.json", _prof_art())]
    problems = cb.check_profile(arts)
    assert len(problems) == 1 and "disappeared" in problems[0]
    # A wire profile only has to persist when the wire phase ran at all.
    arts = [("BENCH_r15.json",
             _prof_art(profile=_profile(wire=False),
                       wire_profile=_profile())),
            ("BENCH_r16.json",
             _prof_art(profile=_profile(wire=False)))]
    assert cb.check_profile(arts) == []


def test_artifacts_predating_the_profile_section_ratchet_nothing():
    arts = [("BENCH_r15.json", _prof_art()),
            ("BENCH_r16.json",
             _prof_art(profile=_profile(wire=False)))]
    assert cb.check_profile(arts) == []
    assert cb.check_profile([]) == []
