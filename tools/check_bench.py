#!/usr/bin/env python
"""Bench ratchet: the newest committed BENCH_r{N}.json must not regress
its predecessor.

The perf PRs each bought a measured win; without a ratchet a later PR can
quietly give it back (the observability rounds caught exactly this shape
of drift in the docs — tools/sync_bench_docs.py — and this is the same
process applied to the NUMBERS).  ``check()`` compares the two
highest-numbered committed artifacts and fails when:

* density p50 (seconds for the headline shape) regressed more than
  ``TOLERANCE`` (15 %), or
* a pipeline stage present in the predecessor's per-stage breakdown
  disappeared from the newest one (a silently-dropped stage means the
  telemetry, or the stage itself, was lost).

Artifacts predating a field (no ``elapsed_s_p50``: derive from the median
throughput; no ``stages``: skip the stage check) are handled so the
ratchet can only tighten going forward.  Wired into tier-1 by
``tests/test_bench_ratchet.py``; runnable standalone:

    python tools/check_bench.py   # exit 1 on regression
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOLERANCE = 0.15  # p50 may grow at most 15% artifact-over-artifact


def _committed_bench_names() -> set[str] | None:
    """The docs ratchet's "green at snapshot" rule, shared — ONE
    implementation of which BENCH artifacts count as committed, so the
    two tier-1 ratchets cannot drift (sync_bench_docs._committed_bench_
    names: git-HEAD tracked names; None when git is unavailable, and the
    caller then falls back to every artifact present)."""
    spec = importlib.util.spec_from_file_location(
        "sync_bench_docs", os.path.join(REPO, "tools",
                                        "sync_bench_docs.py"))
    sync = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sync)
    return sync._committed_bench_names()


def committed_artifacts() -> list[tuple[str, dict]]:
    """[(name, parsed)] for committed BENCH artifacts with a parsed
    payload, ascending by round number."""
    committed = _committed_bench_names()
    found: list[tuple[int, str, dict]] = []
    for name in os.listdir(REPO):
        m = re.fullmatch(r"BENCH_r(\d+)\.json", name)
        if not m:
            continue
        if committed is not None and name not in committed:
            continue
        try:
            with open(os.path.join(REPO, name)) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = data.get("parsed")
        if parsed:
            found.append((int(m.group(1)), name, parsed))
    found.sort()
    return [(name, parsed) for _, name, parsed in found]


def _committed_family_names(prefix: str) -> set[str] | None:
    """``{prefix}_r{N}.json`` artifacts tracked at git HEAD (None when
    git is unavailable) — ONE implementation of the committed-at-HEAD
    rule for every non-BENCH artifact family (WORKLOADS/SOAK/SERVING).
    The BENCH helper stays in sync_bench_docs (shared with the docs
    ratchet), and pattern-filters to BENCH_r*.json — which is why the
    other families need this pass at all."""
    import subprocess
    try:
        out = subprocess.run(
            ["git", "-C", REPO, "ls-tree", "-r", "--name-only", "HEAD"],
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.SubprocessError):
        return None
    if out.returncode != 0:
        return None
    return {n for n in out.stdout.splitlines()
            if re.fullmatch(prefix + r"_r\d+\.json", n)}


def _committed_family_artifacts(prefix: str, validator) -> \
        list[tuple[str, dict]]:
    """[(name, payload)] for committed ``{prefix}_r{N}.json`` artifacts
    whose payload satisfies ``validator``, ascending by round number."""
    committed = _committed_family_names(prefix)
    found: list[tuple[int, str, dict]] = []
    for name in os.listdir(REPO):
        m = re.fullmatch(prefix + r"_r(\d+)\.json", name)
        if not m:
            continue
        if committed is not None and name not in committed:
            continue
        try:
            with open(os.path.join(REPO, name)) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        if validator(data):
            found.append((int(m.group(1)), name, data))
    found.sort()
    return [(name, data) for _, name, data in found]


def last_same_backend(artifacts: list[tuple[str, dict]],
                      new: dict) -> tuple[str, dict] | None:
    """The most recent predecessor measured on the same backend as
    ``new`` (None when no prior artifact matches).  Wall-clock rows
    re-baseline when the accelerator under an artifact changes, but
    they must scan BACK to the last same-backend artifact rather than
    only eyeing the immediate predecessor: a mixed history (cpu ->
    tpu -> cpu) would otherwise re-baseline at every step and never
    wall-clock-compare anything again, silently retiring the ratchet."""
    for name, parsed in reversed(artifacts[:-1]):
        if parsed.get("backend") == new.get("backend"):
            return name, parsed
    return None


def committed_workloads_artifacts() -> list[tuple[str, dict]]:
    """Committed WORKLOADS_r{N}.json artifacts (the workloads
    subsystem's quality/parity/gang rows, emitted by bench.py)."""
    return _committed_family_artifacts(
        "WORKLOADS", lambda d: bool(d.get("joint_quality")))


def quality_row(payload: dict) -> float | None:
    """The joint-vs-greedy placement ratio — the quality number the
    workloads ratchet pins alongside density p50."""
    q = (payload.get("joint_quality") or {}).get("joint_vs_greedy")
    return float(q) if q else None


def check_workloads(artifacts: list[tuple[str, dict]] | None = None,
                    tolerance: float = TOLERANCE) -> list[str]:
    """Problems with the newest WORKLOADS artifact vs its predecessor:
    the joint-vs-greedy quality ratio must not give back more than
    ``tolerance`` of its win, and no partial gang may ever have bound."""
    if artifacts is None:
        artifacts = committed_workloads_artifacts()
    problems: list[str] = []
    if artifacts:
        new_name, new = artifacts[-1]
        partial = (new.get("gang") or {}).get("partial_gangs_bound")
        if partial:
            problems.append(
                f"{new_name}: {partial} partial gang(s) bound — the "
                f"all-or-nothing invariant broke")
    if len(artifacts) < 2:
        return problems
    (prev_name, prev), (new_name, new) = artifacts[-2], artifacts[-1]
    prev_q, new_q = quality_row(prev), quality_row(new)
    if prev_q and new_q and new_q < prev_q * (1.0 - tolerance):
        problems.append(
            f"joint quality regressed: {new_name} x{new_q:.4f} vs "
            f"{prev_name} x{prev_q:.4f} "
            f"(-{(1 - new_q / prev_q) * 100:.0f}%, tolerance "
            f"{tolerance * 100:.0f}%)")
    return problems


def committed_soak_artifacts() -> list[tuple[str, dict]]:
    """Committed SOAK_r{N}.json artifacts (the churn-soak robustness
    rows emitted by perf/soak.py)."""
    return _committed_family_artifacts(
        "SOAK", lambda d: "invariant_violations" in d)


def check_soak(artifacts: list[tuple[str, dict]] | None = None,
               tolerance: float = TOLERANCE) -> list[str]:
    """Problems with the newest SOAK artifact: ANY invariant violation,
    any reconciliation failure (double-bind / stranded pod / orphaned
    assume after the mid-drain kill), monotonically growing
    steady-state queue depth, a restart-parity miss, or (vs the
    predecessor) a settle-time regression beyond ``tolerance``.  The
    soak is the robustness ratchet: these are invariants, so unlike the
    perf rows most checks fail on the newest artifact alone."""
    if artifacts is None:
        artifacts = committed_soak_artifacts()
    problems: list[str] = []
    if not artifacts:
        return problems
    new_name, new = artifacts[-1]
    if new.get("invariant_violations"):
        problems.append(
            f"{new_name}: {new['invariant_violations']} resident-state "
            f"invariant violation(s) — cache/device/apiserver truth "
            f"diverged during the soak")
    rec = new.get("reconciliation") or {}
    for field_name in ("double_binds", "stranded_pending",
                       "orphaned_assumes", "bound_to_missing_node"):
        if rec.get(field_name):
            problems.append(
                f"{new_name}: post-soak reconciliation found "
                f"{rec[field_name]} {field_name} — the mid-drain "
                f"restart broke an acceptance invariant")
    if (new.get("queue_depth") or {}).get("monotonic_growth"):
        problems.append(
            f"{new_name}: steady-state queue depth grew monotonically "
            f"(slope "
            f"{new['queue_depth'].get('steady_window_slope_pods_per_s')}"
            f" pods/s) — bounded-queue degradation failed")
    parity = new.get("restart_parity") or {}
    if parity and parity.get("decision_parity_pct", 100.0) < 100.0:
        problems.append(
            f"{new_name}: post-restart decision parity "
            f"{parity['decision_parity_pct']}% < 100% — recovery "
            f"corrupted the rebuilt scheduling state")
    # Device fault-tolerance invariants (artifacts predating the guard
    # carry none of these keys and ratchet nothing).
    gate = new.get("sanity_gate") or {}
    if gate.get("rejected_binds"):
        problems.append(
            f"{new_name}: {gate['rejected_binds']} pod(s) bound from a "
            f"sanity-gate-rejected solve — the gate's requeue contract "
            f"broke")
    if new.get("engine_mode_final") == "host":
        problems.append(
            f"{new_name}: the soak ended with the engine stuck in host "
            f"fallback mode — the probe loop never re-promoted to the "
            f"device")
    lost_wave = new.get("device_lost_wave") or {}
    if lost_wave and not lost_wave.get("repromoted", True):
        problems.append(
            f"{new_name}: the device-lost wave never re-promoted the "
            f"engine back to device mode")
    # Near-capacity wave (server-side bind capacity validation):
    # overcommit landing in the store, or pods stranded by the 409
    # absorption, both break the zero-overcommit contract.  Artifacts
    # predating the wave carry no section and ratchet nothing.
    capacity = new.get("capacity") or {}
    if capacity.get("overcommitted_nodes"):
        problems.append(
            f"{new_name}: {capacity['overcommitted_nodes']} node(s) "
            f"overcommitted in the near-capacity wave — the server-side "
            f"bind capacity check failed")
    if capacity.get("stranded_pending"):
        problems.append(
            f"{new_name}: {capacity['stranded_pending']} pod(s) "
            f"stranded pending after the near-capacity wave — the "
            f"scheduler never converged past the capacity 409s")
    # Tenancy poison wave (run under KT_LOCKTRACE=1): beyond the lock
    # columns below, the wave's own PR 12 contract holds — everything
    # offered binds and the poisoned tenant re-promotes to device.
    tp = new.get("tenancy_poison") or {}
    if tp and tp.get("bound", 0) < tp.get("offered", 0):
        problems.append(
            f"{new_name}: tenancy poison wave bound only "
            f"{tp.get('bound')}/{tp.get('offered')} pods — the "
            f"per-tenant breaker/packer stopped converging")
    if tp and not tp.get("repromoted", True):
        problems.append(
            f"{new_name}: the tenancy poison wave never re-promoted "
            f"the poisoned tenant back to the device")
    # Concurrency-discipline columns (KT_LOCKTRACE=1 over the churn
    # run, the HA wave, and the tenancy poison wave): a lock-order
    # inversion is a deadlock precondition and a long hold is a latency
    # cliff — both ratchet to ZERO.  Artifacts predating locktrace
    # carry no section and ratchet nothing.
    lt = new.get("locktrace") or {}
    if lt.get("lock_inversions"):
        problems.append(
            f"{new_name}: {lt['lock_inversions']} lock-order "
            f"inversion(s) under KT_LOCKTRACE — a deadlock "
            f"precondition (see locktrace.inversion_detail)")
    if lt.get("long_holds"):
        problems.append(
            f"{new_name}: {lt['long_holds']} long lock hold(s) under "
            f"KT_LOCKTRACE — a traced lock was held past the "
            f"long-hold threshold (see locktrace.long_hold_detail)")
    if len(artifacts) >= 2:
        # Same backend-gate as the BENCH p50 row: wall-clock rows
        # re-baseline when the accelerator under the artifact changed —
        # against the LAST same-backend artifact, not just the
        # immediate predecessor.
        base = last_same_backend(artifacts, new)
        if base is not None:
            prev_name, prev = base
            prev_settle, new_settle = prev.get("settle_s"), \
                new.get("settle_s")
            if prev_settle and new_settle and \
                    float(new_settle) > float(prev_settle) * \
                    (1.0 + tolerance):
                problems.append(
                    f"soak settle regressed: {new_name} {new_settle}s "
                    f"vs {prev_name} {prev_settle}s (tolerance "
                    f"{tolerance * 100:.0f}%)")
    return problems


def check_ha(artifacts: list[tuple[str, dict]] | None = None,
             tolerance: float = 0.10) -> list[str]:
    """The active-active HA ratchet over the newest SOAK artifact's
    ``ha`` section (perf/soak.run_ha_wave): ANY double-bind fails
    outright (the bind CAS + lease partition must make them
    impossible), shard takeover after the mid-drain kill must settle
    in under a second, nothing may strand, and the 3-incarnation
    scale-out efficiency (aggregate over the same wave's solo phase-0
    baseline) must not fall below the committed predecessor's —
    scale-out that slows the fleet down is a regression, not a
    feature, while a rig that got slower under BOTH measurements is
    drift, not a regression.  The rate comparisons
    carry ``tolerance`` (invariant rows never do): both sides are
    single measurements under a chaos storm, and a hair's-width miss
    on a noisy rig is measurement noise, not a regression — the same
    reasoning as check()'s p50 and check_soak's settle margins.
    Artifacts predating the section ratchet nothing."""
    if artifacts is None:
        artifacts = committed_soak_artifacts()
    problems: list[str] = []
    if not artifacts:
        return problems
    new_name, new = artifacts[-1]
    ha = new.get("ha") or {}
    if not ha:
        return problems
    if ha.get("double_binds"):
        problems.append(
            f"{new_name}: {ha['double_binds']} double-bind(s) in the HA "
            f"wave — two incarnations bound one pod; the bind CAS or "
            f"the shard partition broke")
    if ha.get("stranded_pending"):
        problems.append(
            f"{new_name}: {ha['stranded_pending']} pod(s) stranded "
            f"pending after the HA wave — a shard handoff lost them")
    if ha.get("invariant_violations"):
        problems.append(
            f"{new_name}: {ha['invariant_violations']} invariant "
            f"violation(s) during the HA wave")
    takeover = (ha.get("takeover") or {}).get("takeover_settle_s")
    if takeover is None:
        problems.append(
            f"{new_name}: the HA wave recorded no takeover_settle_s — "
            f"the mid-drain kill never ran")
    elif float(takeover) > 1.0:
        problems.append(
            f"{new_name}: shard takeover settled in {takeover}s after "
            f"the kill (bar: < 1 s)")
    agg = ha.get("aggregate_steady_pods_per_s")
    if not agg:
        problems.append(
            f"{new_name}: the HA wave recorded no aggregate "
            f"steady-state rate")
    else:
        # The scale-out bar, controlled: the wave's OWN phase-0
        # single-scheduler baseline — the same storm on the same rig
        # under the same chaos with one incarnation holding every
        # shard, so the only variable is the scheduler count.  Three
        # schedulers slower than one is a regression, not HA — but the
        # inequality is only PHYSICALLY reachable when the rig can run
        # the incarnations concurrently (cpus > n_incarnations); on a
        # serialized rig N CPU-bound schedulers timeshare one core and
        # pay N× the watch fan-out for 1× the compute, so there the
        # aggregate is pinned against the committed predecessor (below)
        # instead of against an unreachable bar.
        own = ha.get("single_scheduler_pods_per_s")
        cpus = ha.get("cpus") or 0
        n_inc = ha.get("n_incarnations") or 0
        if not own:
            problems.append(
                f"{new_name}: the HA wave recorded no single-scheduler "
                f"baseline rate — the phase-0 control never ran")
        elif int(cpus) > int(n_inc) and \
                float(agg) < float(own) * (1.0 - tolerance):
            problems.append(
                f"{new_name}: HA aggregate {agg} pods/s fell more than "
                f"{tolerance:.0%} below the same wave's "
                f"single-scheduler baseline {own} pods/s on a "
                f"{cpus}-cpu rig — scale-out made the fleet slower")
        if len(artifacts) >= 2:
            # Artifact-over-artifact: only ratchet within one backend
            # (check()'s re-baselining rule, with the same scan-back
            # past foreign-backend artifacts), and only against
            # predecessors that ran an HA wave at all.  When both
            # sides carry the phase-0 solo baseline, compare the
            # SCALE-OUT EFFICIENCY ratio (aggregate / same-wave solo)
            # rather than raw wall clock: both terms of each ratio are
            # measured minutes apart on one rig, so the ratio is
            # invariant to the rig being faster or slower than it was
            # when the predecessor was stamped — which is exactly the
            # drift a raw pods/s comparison misreads as a regression.
            # Predecessors without a solo baseline fall back to the
            # raw-rate comparison (the only row they can support).
            comparable = [(n, a) for n, a in artifacts[:-1]
                          if (a.get("ha") or {})
                          .get("aggregate_steady_pods_per_s")
                          and a.get("backend") == new.get("backend")]
            prev_name, prev = comparable[-1] if comparable \
                else (None, {})
            prev_ha = (prev.get("ha") or {}) \
                .get("aggregate_steady_pods_per_s")
            prev_own = (prev.get("ha") or {}) \
                .get("single_scheduler_pods_per_s")
            if prev_ha and prev_own and own:
                ratio = float(agg) / float(own)
                prev_ratio = float(prev_ha) / float(prev_own)
                solo_drift = float(own) / float(prev_own)
                if ratio < prev_ratio * (1.0 - tolerance):
                    if solo_drift > 1.0 + tolerance and \
                            float(agg) >= float(prev_ha) * \
                            (1.0 - tolerance):
                        # The ratio fell, but only because the solo
                        # baseline itself inflated past the tolerance
                        # band (on a serialized rig the solo phase
                        # rides cache warmth the timeshared N-process
                        # aggregate physically cannot follow) while
                        # the aggregate — the rate the fleet actually
                        # serves — held.  That is rig drift in one
                        # phase, not a scale-out regression; the
                        # symmetric case (solo fell with the box, ratio
                        # held) already passes above, and a genuine
                        # aggregate collapse still fails here.
                        pass
                    else:
                        problems.append(
                            f"{new_name}: HA scale-out efficiency "
                            f"{ratio:.2f} (aggregate {agg} / solo "
                            f"{own} pods/s) fell more than "
                            f"{tolerance:.0%} below the committed "
                            f"predecessor's {prev_ratio:.2f} "
                            f"({prev_name}: {prev_ha} / {prev_own})")
            elif prev_ha and \
                    float(agg) < float(prev_ha) * (1.0 - tolerance):
                problems.append(
                    f"{new_name}: HA aggregate {agg} pods/s fell more "
                    f"than {tolerance:.0%} below the committed "
                    f"predecessor's HA aggregate {prev_ha} pods/s "
                    f"({prev_name})")
    return problems


def check_overload(artifacts: list[tuple[str, dict]] | None = None) \
        -> list[str]:
    """The overload-protection ratchet (ISSUE 16) over the newest SOAK
    artifact's ``apiserver_kill`` and ``overload`` sections
    (perf/soak.run_apiserver_kill_wave / run_overload_wave).  All rows
    are invariants — no tolerances:

    ``apiserver_kill``: any acknowledged write lost across the SIGKILL,
    any double-bind in the WAL audit, any stranded pod, a kill that
    never landed mid-avalanche (a quiet restart proves nothing), or a
    recovery with zero reflector relists (the relist path was never
    exercised) all fail.

    ``overload``: a storm that never tripped the flow controller proves
    nothing; the system lane must never shed and no shard lease may
    expire (the protected lease plane); queue depth must stay inside
    the configured bound; goodput must never collapse to zero; the
    exempt /debug/vars must have answered throughout; and every acked
    pod must still have bound.  Artifacts predating the sections
    ratchet nothing."""
    if artifacts is None:
        artifacts = committed_soak_artifacts()
    problems: list[str] = []
    if not artifacts:
        return problems
    new_name, new = artifacts[-1]
    kill = new.get("apiserver_kill") or {}
    if kill:
        if kill.get("acked_writes_lost"):
            problems.append(
                f"{new_name}: {kill['acked_writes_lost']} acknowledged "
                f"write(s) lost across the apiserver SIGKILL — WAL "
                f"durability broke (sample: {kill.get('lost_sample')})")
        if kill.get("double_binds"):
            problems.append(
                f"{new_name}: {kill['double_binds']} double-bind(s) in "
                f"the apiserver-kill WAL audit — a pod's nodeName moved "
                f"between nodes across the crash")
        if kill.get("stranded_pending"):
            problems.append(
                f"{new_name}: {kill['stranded_pending']} pod(s) "
                f"stranded after the apiserver restart — the scheduler "
                f"never reconverged the avalanche")
        if not kill.get("killed_mid_avalanche"):
            problems.append(
                f"{new_name}: the apiserver kill never landed "
                f"mid-avalanche (bound {kill.get('bound_at_kill')}, "
                f"pending {kill.get('pending_at_kill')}) — the wave "
                f"measured a quiet restart, not a crash")
        if not kill.get("relists"):
            problems.append(
                f"{new_name}: zero reflector relists across the "
                f"apiserver restart — the watch-break recovery path "
                f"was never exercised")
    ov = new.get("overload") or {}
    if ov:
        if not ov.get("shed_429"):
            problems.append(
                f"{new_name}: the overload storm never tripped the "
                f"flow controller (0 shed 429s) — the wave measured "
                f"nothing")
        if ov.get("lease_expiries"):
            problems.append(
                f"{new_name}: {ov['lease_expiries']} shard lease(s) "
                f"expired during the overload storm — the protected "
                f"system lane failed to keep renewals inside the "
                f"deadline")
        if ov.get("system_rejected"):
            problems.append(
                f"{new_name}: the flow controller shed "
                f"{ov['system_rejected']} system-lane request(s) — the "
                f"lease plane was not protected")
        if ov.get("max_queue_depth", 0) > ov.get("queue_limit", 0):
            problems.append(
                f"{new_name}: queue depth hit "
                f"{ov['max_queue_depth']} past the configured bound "
                f"{ov.get('queue_limit')} — the APF queues are not "
                f"bounded")
        if not ov.get("goodput_pods_per_s"):
            problems.append(
                f"{new_name}: zero goodput during the overload storm — "
                f"shedding starved the workload lane entirely")
        if ov.get("stranded_pending"):
            problems.append(
                f"{new_name}: {ov['stranded_pending']} pod(s) stranded "
                f"after the overload wave — an admitted create never "
                f"bound")
        if ov.get("debug_vars_samples", 1) == 0 or \
                ov.get("debug_vars_errors"):
            problems.append(
                f"{new_name}: the exempt /debug/vars stopped answering "
                f"during the storm "
                f"({ov.get('debug_vars_samples')} samples, "
                f"{ov.get('debug_vars_errors')} errors) — liveness "
                f"probes would have been shed")
        mult = ov.get("offered_multiple")
        if mult is not None and float(mult) < 3.0:
            problems.append(
                f"{new_name}: the overload storm offered only "
                f"{mult}x what the flow-control envelope admitted "
                f"(bar: >= 3x) — the wave never reached overload")
    return problems


def check_defrag(artifacts: list[tuple[str, dict]] | None = None) \
        -> list[str]:
    """The continuous-defragmentation ratchet (ISSUE 17) over the newest
    SOAK artifact's ``defrag`` section (perf/soak.run_defrag_wave).  All
    rows are invariants — no tolerances:

    The wave fragments the fleet (biased churn), parks gang-sized pods
    that provably fit nowhere, and expects the rebalancer to unblock
    them by migrating small pods — so a zero ``defrag_gain`` (or zero
    migrations) means the defragmenter did nothing and the wave proved
    nothing.  Any PDB-protected eviction, stranded pod, lingering
    migration-intent annotation, double-bind, migration-window double
    capacity, or cache invariant violation fails outright.  A batch
    past the per-round cap means the migration budget leaked.  The
    SIGKILL arc must have landed mid-migration and the restarted
    scheduler's reconcile must have requeued at least one in-flight
    migrant — a quiet restart proves nothing.  Artifacts predating the
    section ratchet nothing."""
    if artifacts is None:
        artifacts = committed_soak_artifacts()
    problems: list[str] = []
    if not artifacts:
        return problems
    new_name, new = artifacts[-1]
    df = new.get("defrag") or {}
    if not df:
        return problems
    if float(df.get("defrag_gain", 0)) <= 0:
        problems.append(
            f"{new_name}: defrag_gain {df.get('defrag_gain')} — the "
            f"rebalancer unblocked nothing; continuous defragmentation "
            f"is not working")
    if not df.get("migrations_executed"):
        problems.append(
            f"{new_name}: zero migrations executed in the defrag wave "
            f"— the rebalancer never moved a pod, the wave measured "
            f"nothing")
    if df.get("pdb_violations"):
        problems.append(
            f"{new_name}: {df['pdb_violations']} PDB-protected pod(s) "
            f"evicted by the defragmenter — the disruption-budget "
            f"interlock failed")
    if df.get("stranded"):
        problems.append(
            f"{new_name}: {df['stranded']} pod(s) stranded after the "
            f"defrag wave — an evicted migrant never rebound")
    if df.get("lingering_intents"):
        problems.append(
            f"{new_name}: {df['lingering_intents']} migration-intent "
            f"annotation(s) never cleared — the two-phase protocol "
            f"leaked phase-1 state")
    if df.get("double_binds"):
        problems.append(
            f"{new_name}: {df['double_binds']} double-bind(s) during "
            f"the defrag wave")
    if df.get("double_capacity"):
        problems.append(
            f"{new_name}: {df['double_capacity']} migration-window "
            f"double-capacity violation(s) — a migrating pod was "
            f"counted on two nodes at once")
    if df.get("invariant_violations"):
        problems.append(
            f"{new_name}: {df['invariant_violations']} cache invariant "
            f"violation(s) during the defrag wave "
            f"({df.get('invariant_detail')})")
    cap = df.get("migration_cap")
    if cap is not None and int(df.get("max_batch", 0)) > int(cap):
        problems.append(
            f"{new_name}: a defrag round executed {df['max_batch']} "
            f"migrations past the per-round cap {cap} — the migration "
            f"budget leaked")
    if not df.get("killed_mid_migration"):
        problems.append(
            f"{new_name}: the scheduler SIGKILL never landed "
            f"mid-migration — the wave measured a quiet restart, not a "
            f"crash-safe migration")
    if int(df.get("migrations_recovered", 0)) < 1:
        problems.append(
            f"{new_name}: the restarted scheduler's reconcile requeued "
            f"{df.get('migrations_recovered', 0)} in-flight migrant(s) "
            f"(bar: >= 1) — the crash-recovery arm was never exercised")
    return problems


def committed_serving_artifacts() -> list[tuple[str, dict]]:
    """Committed SERVING_r{N}.json artifacts (the serving-path latency
    rows emitted by perf/serving.py)."""
    return _committed_family_artifacts(
        "SERVING", lambda d: bool(d.get("workloads")))


def check_serving(artifacts: list[tuple[str, dict]] | None = None,
                  tolerance: float = TOLERANCE) -> list[str]:
    """Problems with the newest SERVING artifact: any workload row whose
    SLO attainment sits below its own recorded floor (an absolute
    invariant — the artifact declares the floor it must meet), or (vs
    the predecessor) a per-row p99 submit->bind regression beyond
    ``tolerance``.  The serving rows are the latency ratchet next to the
    throughput ones: the pipeline unification must never quietly trade
    tail latency back."""
    if artifacts is None:
        artifacts = committed_serving_artifacts()
    problems: list[str] = []
    if not artifacts:
        return problems
    new_name, new = artifacts[-1]
    for row_name, row in (new.get("workloads") or {}).items():
        slo = row.get("slo") or {}
        floor = slo.get("attainment_floor_pct")
        got = slo.get("attainment_pct")
        if floor is not None and got is not None and \
                float(got) < float(floor):
            problems.append(
                f"{new_name}: {row_name} SLO attainment {got}% fell "
                f"below its recorded floor {floor}% "
                f"(slo {slo.get('slo_ms')}ms)")
    if len(artifacts) >= 2:
        prev_name, prev = artifacts[-2]
        for row_name, row in (new.get("workloads") or {}).items():
            prev_row = (prev.get("workloads") or {}).get(row_name) or {}
            prev_p99 = (prev_row.get("latency_ms") or {}).get("p99")
            new_p99 = (row.get("latency_ms") or {}).get("p99")
            if prev_p99 and new_p99 and \
                    float(new_p99) > float(prev_p99) * (1.0 + tolerance):
                problems.append(
                    f"serving p99 regressed: {new_name} {row_name} "
                    f"{new_p99}ms vs {prev_name} {prev_p99}ms "
                    f"(+{(float(new_p99) / float(prev_p99) - 1) * 100:.0f}"
                    f"%, tolerance {tolerance * 100:.0f}%)")
    return problems


def committed_tenancy_artifacts() -> list[tuple[str, dict]]:
    """Committed TENANCY_r{N}.json artifacts (the multi-tenant solver
    service rows emitted by perf/tenancy.py)."""
    return _committed_family_artifacts(
        "TENANCY", lambda d: bool(d.get("tenants")))


def check_tenancy(artifacts: list[tuple[str, dict]] | None = None,
                  tolerance: float = 0.10) -> list[str]:
    """The multi-tenant ratchet over the newest TENANCY artifact.

    Absolute invariants on the newest artifact alone: any per-tenant
    SLO attainment below its recorded floor, a cross-tenant fault leak
    (a fault attributed to a tenant other than the adversary), a victim
    tenant knocked off the device, an adversarial tenant never
    re-promoted, interference or fairness outside the artifact's own
    recorded bars, and any post-prewarm compile all fail tier-1.
    Artifact-over-artifact, the cross-tenant p99 interference ratio and
    the fairness error must not regress beyond ``tolerance`` vs the
    last SAME-BACKEND predecessor (check()'s scan-back rule — a mixed
    cpu/tpu history must not retire the comparison)."""
    if artifacts is None:
        artifacts = committed_tenancy_artifacts()
    problems: list[str] = []
    if not artifacts:
        return problems
    new_name, new = artifacts[-1]
    for row_name, row in (new.get("rows") or {}).items():
        slo = row.get("slo") or {}
        floor = slo.get("attainment_floor_pct")
        got = slo.get("attainment_pct")
        if floor is not None and got is not None and \
                float(got) < float(floor):
            problems.append(
                f"{new_name}: {row_name} SLO attainment {got}% fell "
                f"below its recorded floor {floor}% (tenant "
                f"{row.get('tenant')}, slo {slo.get('slo_ms')}ms)")
    interference = new.get("interference") or {}
    ratio = interference.get("ratio")
    bar = interference.get("bar")
    if ratio is not None and bar is not None and \
            float(ratio) > float(bar):
        problems.append(
            f"{new_name}: cross-tenant p99 interference ratio {ratio} "
            f"exceeded the artifact's bar {bar} — the noisy neighbor "
            f"moved the trickle tenant's tail")
    fairness = new.get("fairness") or {}
    err = fairness.get("max_rel_error")
    fbar = fairness.get("bar")
    if err is not None and fbar is not None and \
            float(err) > float(fbar):
        problems.append(
            f"{new_name}: fairness error {err} exceeded the bar {fbar} "
            f"— observed shares drifted from the configured weights "
            f"(observed {fairness.get('observed_shares')} vs expected "
            f"{fairness.get('expected_shares')})")
    iso = new.get("isolation") or {}
    if iso.get("cross_tenant_faults"):
        problems.append(
            f"{new_name}: {iso['cross_tenant_faults']} cross-tenant "
            f"fault(s) — a fault leaked onto a tenant other than the "
            f"adversary; per-tenant isolation broke")
    if iso.get("cross_tenant_sanity_rejects"):
        problems.append(
            f"{new_name}: {iso['cross_tenant_sanity_rejects']} sanity "
            f"reject(s) on clean tenants' batches during the poison "
            f"phase")
    for victim, mode in (iso.get("victim_modes") or {}).items():
        if mode != "device":
            problems.append(
                f"{new_name}: victim tenant {victim} was knocked to "
                f"{mode} mode by the adversary's poison batches")
    if iso and not iso.get("repromoted", True):
        problems.append(
            f"{new_name}: the adversarial tenant was never re-promoted "
            f"to device after the poison cleared")
    if iso and not iso.get("all_bound", True):
        problems.append(
            f"{new_name}: pods stranded unbound after the isolation "
            f"phase — a tenant's breaker cost another tenant progress")
    dev = new.get("device") or {}
    if dev.get("post_prewarm_compiles"):
        problems.append(
            f"{new_name}: {dev['post_prewarm_compiles']} post-prewarm "
            f"XLA compile(s) during the tenancy run — cross-tenant "
            f"packing minted a shape the prewarm ladder never traced")
    base = last_same_backend(artifacts, new)
    if base is not None:
        prev_name, prev = base
        prev_ratio = (prev.get("interference") or {}).get("ratio")
        if prev_ratio and ratio and \
                float(ratio) > float(prev_ratio) * (1.0 + tolerance):
            problems.append(
                f"interference ratio regressed: {new_name} {ratio} vs "
                f"{prev_name} {prev_ratio} (tolerance "
                f"{tolerance * 100:.0f}%)")
        prev_err = (prev.get("fairness") or {}).get("max_rel_error")
        if prev_err and err and \
                float(err) > float(prev_err) * (1.0 + tolerance):
            problems.append(
                f"fairness error regressed: {new_name} {err} vs "
                f"{prev_name} {prev_err} (tolerance "
                f"{tolerance * 100:.0f}%)")
    return problems


def _shape_pods(parsed: dict) -> int:
    m = re.search(r"([\d,]+) pods onto", parsed.get("metric", ""))
    return int(m.group(1).replace(",", "")) if m else 30000


def density_p50_s(parsed: dict) -> float | None:
    """The artifact's density p50 in seconds: the recorded
    ``elapsed_s_p50``, or (older artifacts) derived from the median
    throughput and the headline pod count."""
    p50 = parsed.get("elapsed_s_p50")
    if p50:
        return float(p50)
    median = parsed.get("median") or parsed.get("value")
    if not median:
        return None
    return _shape_pods(parsed) / float(median)


def check_device(artifacts: list[tuple[str, dict]],
                 tolerance: float = TOLERANCE) -> list[str]:
    """The device-plane ratchet over BENCH artifacts: ANY post-prewarm
    compile in the density run fails outright (every one is a compile
    stall on the serving clock the prewarm ladder should have traced),
    and the steady-state transfer bytes-per-pod (scatter + full_upload
    + readback) must not grow more than ``tolerance`` vs the
    predecessor — the dirty-row scatter quietly giving way to full
    re-uploads is exactly the regression these columns exist to catch.
    Artifacts predating the ``device`` section ratchet nothing."""
    problems: list[str] = []
    if not artifacts:
        return problems
    new_name, new = artifacts[-1]
    dev = new.get("device") or {}
    compiles = dev.get("post_prewarm_compiles")
    if compiles:
        problems.append(
            f"{new_name}: {compiles} post-prewarm XLA compile(s) in the "
            f"density run — a live-path shape the prewarm ladder never "
            f"traced")
    if dev.get("sanity_rejected_binds"):
        problems.append(
            f"{new_name}: {dev['sanity_rejected_binds']} pod(s) bound "
            f"from a sanity-gate-rejected solve in the density run")
    if dev.get("engine_mode_final") == "host":
        problems.append(
            f"{new_name}: the density run ended stuck in host fallback "
            f"mode — the bench measured the NumPy engine, not the "
            f"device")
    if len(artifacts) < 2:
        return problems
    prev_dev = (artifacts[-2][1].get("device") or {})
    prev_name = artifacts[-2][0]
    prev_bpp = prev_dev.get("bytes_per_pod") or {}
    new_bpp = dev.get("bytes_per_pod") or {}
    # Cause for cause: one the older artifact never counted (PR 31's
    # ``batch``: the pod batch's bytes crossed before, uncounted) is new
    # accounting, not new traffic.
    prev_total = sum(v for v in prev_bpp.values() if v)
    new_total = sum(v for c, v in new_bpp.items() if v and c in prev_bpp)
    if prev_total and new_total > prev_total * (1.0 + tolerance):
        problems.append(
            f"device transfer bytes-per-pod regressed: {new_name} "
            f"{new_total:.0f} B/pod vs {prev_name} {prev_total:.0f} "
            f"B/pod (+{(new_total / prev_total - 1) * 100:.0f}%, "
            f"tolerance {tolerance * 100:.0f}%) — per cause "
            f"{new_bpp} vs {prev_bpp}")
    return problems


def check_wire(artifacts: list[tuple[str, dict]] | None = None,
               tolerance: float = TOLERANCE) -> list[str]:
    """The wire-path ratchet (ISSUE 15): the newest artifact's wire
    median pods/s must not regress more than ``tolerance`` against the
    LAST same-backend artifact carrying a wire section (check_ha-style
    scan-back — a backend change re-baselines, a missing wire phase in
    one artifact must not retire the comparison), and any recorded
    zero-bound run fails outright (a zero-bound run is a rig fault the
    harness now raises on; an artifact carrying one measured a broken
    rig)."""
    if artifacts is None:
        artifacts = committed_artifacts()
    problems: list[str] = []
    if not artifacts:
        return problems
    new_name, new = artifacts[-1]
    wire = new.get("wire") or {}
    if not wire:
        return problems
    zero = wire.get("zero_bound_runs")
    if zero:
        problems.append(
            f"{new_name}: {zero} zero-bound wire run(s) — the daemon "
            f"never drained on a measured run; the artifact sampled a "
            f"broken rig")
    if wire.get("failed_runs") and not wire.get("runs"):
        problems.append(
            f"{new_name}: every wire run failed "
            f"({wire['failed_runs']} errored) — the artifact carries "
            f"no wire sample at all")
    wired = [(name, parsed) for name, parsed in artifacts
             if (parsed.get("wire") or {}).get("median_pods_per_second")
             and parsed.get("backend") == new.get("backend")]
    if len(wired) < 2 or wired[-1][0] != new_name:
        return problems
    prev_name, prev = wired[-2]
    new_v = float(wire["median_pods_per_second"])
    prev_v = float(prev["wire"]["median_pods_per_second"])
    if new_v < prev_v * (1.0 - tolerance):
        problems.append(
            f"wire throughput regressed: {new_name} {new_v:,.0f} pods/s "
            f"median vs {prev_name} {prev_v:,.0f} "
            f"(-{(1 - new_v / prev_v) * 100:.0f}%, tolerance "
            f"{tolerance * 100:.0f}%)")
    return problems


# Above this, the kt-prof classifier no longer covers the control
# plane's hot paths and the profile section stops answering "where did
# the CPU go" — the bar check_profile holds the committed artifacts to.
UNCLASSIFIED_BAR = 0.20


def _profile_rows(parsed: dict) -> list[tuple[str, dict]]:
    """The artifact's kt-prof sections as (location, row) pairs: the
    density profile at top level, the wire phase's under ``wire``."""
    rows: list[tuple[str, dict]] = []
    if parsed.get("profile"):
        rows.append(("density", parsed["profile"]))
    if (parsed.get("wire") or {}).get("profile"):
        rows.append(("wire", parsed["wire"]["profile"]))
    return rows


def check_profile(artifacts: list[tuple[str, dict]] | None = None,
                  tolerance: float = TOLERANCE,
                  unclassified_bar: float = UNCLASSIFIED_BAR) -> list[str]:
    """The kt-prof ratchet (ISSUE 18) over the newest BENCH artifact's
    ``profile`` sections (harness.profile_section):

    * a section stamped with the profiler disabled carries no CPU
      attribution and fails outright — the bench must measure with
      kt-prof on, or the component split silently stops existing;
    * an unclassified CPU fraction above ``unclassified_bar`` fails: the
      classifier no longer covers the hot paths, and "other" is exactly
      the bucket a regression hides in;
    * the per-event wire costs (watch-decode and handler-dispatch µs per
      event, serialize µs per op) must not regress more than
      ``tolerance`` vs the LAST same-backend artifact carrying the same
      row (the check_wire scan-back — a backend change re-baselines, a
      skipped phase must not retire the comparison);
    * once a same-backend predecessor carries a profile section, the
      newest artifact must too (a vanished section means the
      attribution plane was dropped from the bench, the exact drift
      this ratchet exists to catch).

    Artifacts predating the section ratchet nothing."""
    if artifacts is None:
        artifacts = committed_artifacts()
    problems: list[str] = []
    if not artifacts:
        return problems
    new_name, new = artifacts[-1]
    new_rows = dict(_profile_rows(new))
    base = last_same_backend(artifacts, new)
    if base is not None:
        prev_name, prev = base
        for loc in dict(_profile_rows(prev)):
            if loc == "wire" and not new.get("wire"):
                continue  # the wire phase itself was skipped this round
            if loc not in new_rows:
                problems.append(
                    f"{new_name}: the {loc} profile section disappeared "
                    f"({prev_name} carried one) — kt-prof attribution "
                    f"was dropped from the bench")
    for loc, row in new_rows.items():
        if row.get("enabled") is False:
            problems.append(
                f"{new_name}: the {loc} profile was stamped with the "
                f"profiler disabled (KT_PROF=0) — the artifact carries "
                f"no CPU attribution")
            continue
        uf = row.get("unclassified_fraction")
        if uf is not None and float(uf) > unclassified_bar:
            problems.append(
                f"{new_name}: {loc} profile unclassified CPU fraction "
                f"{float(uf):.2f} above the {unclassified_bar:.0%} bar "
                f"— the classifier no longer covers the control plane's "
                f"hot paths")
    for loc, row in new_rows.items():
        for comp, per_key in (("decode", "us_per_event"),
                              ("handler", "us_per_event"),
                              ("serialize", "us_per_op")):
            new_v = ((row.get("wire") or {}).get(comp) or {}).get(per_key)
            if not new_v:
                continue
            hit = None
            for name, parsed in reversed(artifacts[:-1]):
                if parsed.get("backend") != new.get("backend"):
                    continue
                prev_row = dict(_profile_rows(parsed)).get(loc) or {}
                pv = ((prev_row.get("wire") or {}).get(comp)
                      or {}).get(per_key)
                if pv:
                    hit = (name, float(pv))
                    break
            if hit is None:
                continue
            prev_name, prev_v = hit
            if float(new_v) > prev_v * (1.0 + tolerance):
                problems.append(
                    f"{loc} {comp} per-event cost regressed: {new_name} "
                    f"{float(new_v):,.1f} {per_key} vs {prev_name} "
                    f"{prev_v:,.1f} "
                    f"(+{(float(new_v) / prev_v - 1) * 100:.0f}%, "
                    f"tolerance {tolerance * 100:.0f}%)")
    return problems


def check_scatter_bytes(artifacts: list[tuple[str, dict]] | None = None,
                        tolerance: float = TOLERANCE) -> list[str]:
    """Scatter bytes-per-pod ratchet (ISSUE 15 dtype narrowing): the
    steady-state scatter bytes-per-pod must not regress vs the last
    same-backend artifact carrying the column (scan-back, not
    immediate-predecessor — check_device's total-bytes check keeps its
    adjacent comparison; this row pins the narrowing win
    specifically)."""
    if artifacts is None:
        artifacts = committed_artifacts()
    problems: list[str] = []
    if not artifacts:
        return problems
    new_name, new = artifacts[-1]

    def scatter_bpp(parsed: dict) -> float | None:
        v = ((parsed.get("device") or {}).get("bytes_per_pod")
             or {}).get("scatter")
        return float(v) if v else None

    rows = [(name, parsed) for name, parsed in artifacts
            if scatter_bpp(parsed) is not None
            and parsed.get("backend") == new.get("backend")]
    if len(rows) < 2 or rows[-1][0] != new_name:
        return problems
    prev_name, prev = rows[-2]
    new_v, prev_v = scatter_bpp(new), scatter_bpp(prev)
    if new_v > prev_v * (1.0 + tolerance):
        problems.append(
            f"scatter bytes-per-pod regressed: {new_name} {new_v:.1f} "
            f"B/pod vs {prev_name} {prev_v:.1f} B/pod "
            f"(+{(new_v / prev_v - 1) * 100:.0f}%, tolerance "
            f"{tolerance * 100:.0f}%) — the narrow wire planes widened "
            f"back")
    return problems


def committed_manifest_summary() -> dict | None:
    """{'hash', 'programs'} of tools/shape_manifest.json — plain JSON
    read (no jax, no tracing; the full drift check is
    tools/check_manifest.py's job)."""
    path = os.path.join(REPO, "tools", "shape_manifest.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        data = json.load(f)
    return {"hash": data.get("hash"),
            "programs": len(data.get("programs") or {})}


_COMMITTED = object()  # check_xray sentinel: read the committed file


def check_xray(artifacts: list[tuple[str, dict]] | None = None,
               soak_artifacts: list[tuple[str, dict]] | None = None,
               manifest: object = _COMMITTED) -> list[str]:
    """Compile-surface provenance ratchet: BENCH/SOAK artifacts carry
    the kt-xray manifest stamp (hash + program count, bench.py
    ``_xray_summary``), and a stamp change between consecutive
    artifacts must come WITH a manifest regeneration — the newest
    artifact's hash must then match the committed
    tools/shape_manifest.json (a bench that measured a compile surface
    the manifest never recorded is an unaccounted perf-trajectory
    jump).  Artifacts predating the stamp ratchet nothing.  Pass
    ``manifest=None`` to mean "no committed manifest" (the default
    sentinel reads tools/shape_manifest.json)."""
    problems: list[str] = []
    committed = committed_manifest_summary() \
        if manifest is _COMMITTED else manifest
    families = (
        ("BENCH", artifacts if artifacts is not None
         else committed_artifacts()),
        ("SOAK", soak_artifacts if soak_artifacts is not None
         else committed_soak_artifacts()),
    )
    for family, arts in families:
        stamped = [(name, parsed["xray"]) for name, parsed in arts
                   if parsed.get("xray")]
        if len(stamped) < 2:
            continue
        (prev_name, prev_x), (new_name, new_x) = stamped[-2], stamped[-1]
        if prev_x.get("hash") == new_x.get("hash"):
            continue
        if committed is None:
            problems.append(
                f"{family} manifest stamp changed ({prev_name} -> "
                f"{new_name}) but tools/shape_manifest.json is not "
                f"committed")
        elif committed.get("hash") != new_x.get("hash"):
            problems.append(
                f"{family} compile-surface hash changed ({prev_name} "
                f"{str(prev_x.get('hash'))[:19]}… -> {new_name} "
                f"{str(new_x.get('hash'))[:19]}…) without a manifest "
                f"regeneration in the same commit (committed manifest "
                f"is {str(committed.get('hash'))[:19]}… — run "
                f"`python -m tools.ktxray --write-manifest`)")
    return problems


def check(artifacts: list[tuple[str, dict]] | None = None,
          tolerance: float = TOLERANCE) -> list[str]:
    """Problems with the newest artifact vs its predecessor (empty =
    ratchet holds).  The device-plane checks (post-prewarm compiles,
    bytes-per-pod) apply even with a single artifact; the rest need a
    predecessor — fewer than two comparable artifacts is vacuously
    green."""
    if artifacts is None:
        artifacts = committed_artifacts()
    problems = check_device(artifacts, tolerance)
    problems += check_wire(artifacts, tolerance)
    problems += check_scatter_bytes(artifacts, tolerance)
    problems += check_profile(artifacts, tolerance)
    if len(artifacts) < 2:
        return problems
    (prev_name, prev), (new_name, new) = artifacts[-2], artifacts[-1]
    new_p50 = density_p50_s(new)
    # Wall-clock rows only compare within one accelerator backend: an
    # artifact measured on a different device (parsed["backend"]:
    # "cpu"/"tpu"/...; absent = BENCH_r05's TPU rig, which predates
    # the field) is a new baseline, not a regression — 23 s of CPU scan
    # against 1.3 s of TPU scan says nothing about the code between
    # them.  The ratchet scans back to the LAST same-backend artifact
    # (a mixed history must not retire the comparison).  The invariant
    # checks (stages, device plane, quality ratios) still apply against
    # the immediate predecessor.
    if prev.get("backend") != new.get("backend"):
        print(f"bench ratchet: backend changed "
              f"({prev_name}={prev.get('backend') or 'tpu'} -> "
              f"{new_name}={new.get('backend') or 'tpu'}); wall-clock "
              f"rows re-baseline")
    base = last_same_backend(artifacts, new)
    if base is not None:
        base_name, base_art = base
        base_p50 = density_p50_s(base_art)
        if base_p50 and new_p50 and \
                new_p50 > base_p50 * (1.0 + tolerance):
            problems.append(
                f"density p50 regressed: {new_name} {new_p50:.3f}s vs "
                f"{base_name} {base_p50:.3f}s "
                f"(+{(new_p50 / base_p50 - 1) * 100:.0f}%, tolerance "
                f"{tolerance * 100:.0f}%)")
    prev_stages = set((prev.get("stages") or {}))
    new_stages = set((new.get("stages") or {}))
    if prev_stages and new_stages:
        lost = prev_stages - new_stages
        if lost:
            problems.append(
                f"stages disappeared from {new_name}'s per-stage "
                f"breakdown: {sorted(lost)} (present in {prev_name})")
    elif prev_stages and not new_stages:
        problems.append(
            f"{new_name} lost the per-stage breakdown entirely "
            f"({prev_name} had {sorted(prev_stages)})")
    # Workloads quality row embedded in the BENCH artifact (bench.py's
    # workloads summary), ratcheted like the standalone artifact.
    prev_q = (prev.get("workloads") or {}).get("joint_vs_greedy")
    new_q = (new.get("workloads") or {}).get("joint_vs_greedy")
    if prev_q and new_q and float(new_q) < float(prev_q) * \
            (1.0 - tolerance):
        problems.append(
            f"joint quality regressed: {new_name} x{float(new_q):.4f} "
            f"vs {prev_name} x{float(prev_q):.4f} (tolerance "
            f"{tolerance * 100:.0f}%)")
    return problems


def main() -> int:
    problems = check_workloads()
    problems += check_soak()
    problems += check_ha()
    problems += check_overload()
    problems += check_defrag()
    problems += check_serving()
    problems += check_tenancy()
    problems += check_xray()
    artifacts = committed_artifacts()
    if len(artifacts) < 2:
        print("bench ratchet: fewer than two committed BENCH artifacts; "
              "nothing to compare")
    else:
        problems += check(artifacts)
    if problems:
        for p in problems:
            print(f"bench ratchet FAIL: {p}", file=sys.stderr)
        return 1
    if len(artifacts) >= 2:
        (prev_name, prev), (new_name, new) = artifacts[-2], artifacts[-1]
        print(f"bench ratchet OK: {new_name} p50 "
              f"{density_p50_s(new):.3f}s vs "
              f"{prev_name} {density_p50_s(prev):.3f}s")
        frac = (new.get("profile") or {}).get("cpu_fraction") or {}
        if frac:
            top = max(frac, key=frac.get)
            print(f"profile ratchet OK: {new_name} top component "
                  f"{top} {frac[top]:.0%}, unclassified "
                  f"{(new['profile']).get('unclassified_fraction')}")
    wl = committed_workloads_artifacts()
    if wl:
        print(f"workloads ratchet OK: {wl[-1][0]} quality "
              f"x{quality_row(wl[-1][1])}")
    sk = committed_soak_artifacts()
    if sk:
        print(f"soak ratchet OK: {sk[-1][0]} settle "
              f"{sk[-1][1].get('settle_s')}s, "
              f"{sk[-1][1].get('invariant_violations')} violations")
        ha = sk[-1][1].get("ha") or {}
        if ha:
            print(f"HA ratchet OK: {sk[-1][0]} takeover "
                  f"{(ha.get('takeover') or {}).get('takeover_settle_s')}"
                  f"s, {ha.get('double_binds')} double-binds, aggregate "
                  f"{ha.get('aggregate_steady_pods_per_s')} pods/s")
        kill = sk[-1][1].get("apiserver_kill") or {}
        if kill:
            print(f"apiserver-kill ratchet OK: {sk[-1][0]} "
                  f"{kill.get('acked_creates')} acked creates, "
                  f"{kill.get('acked_writes_lost')} lost, "
                  f"{kill.get('double_binds')} double-binds, "
                  f"{kill.get('relists')} relists")
        ov = sk[-1][1].get("overload") or {}
        if ov:
            print(f"overload ratchet OK: {sk[-1][0]} "
                  f"{ov.get('offered_multiple')}x capacity offered, "
                  f"{ov.get('shed_429')} shed, goodput "
                  f"{ov.get('goodput_pods_per_s')} pods/s, "
                  f"{ov.get('lease_expiries')} lease expiries")
    tn = committed_tenancy_artifacts()
    if tn:
        new = tn[-1][1]
        print(f"tenancy ratchet OK: {tn[-1][0]} interference "
              f"{(new.get('interference') or {}).get('ratio')}, "
              f"fairness error "
              f"{(new.get('fairness') or {}).get('max_rel_error')}, "
              f"{(new.get('isolation') or {}).get('cross_tenant_faults')}"
              f" cross-tenant faults")
    sv = committed_serving_artifacts()
    if sv:
        trickle = (sv[-1][1].get("workloads") or {}) \
            .get("poisson_trickle") or {}
        print(f"serving ratchet OK: {sv[-1][0]} trickle p99 "
              f"{(trickle.get('latency_ms') or {}).get('p99')}ms, "
              f"attainment "
              f"{(trickle.get('slo') or {}).get('attainment_pct')}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
