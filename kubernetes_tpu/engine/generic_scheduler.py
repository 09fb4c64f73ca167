"""Host orchestration: the batched counterpart of genericScheduler.Schedule
(generic_scheduler.go:78-122).

``GenericScheduler`` owns a Solver (compiled policy), the tensor cache, and
the cluster-object listers (services/RCs/RSs for spreading, per
selector_spreading.go:70-86).  ``schedule()`` places one pod (decision
parity path); ``schedule_batch()`` places a whole pending queue in one
device solve (the TPU win).
"""

from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass, field
from typing import Iterator, Optional

import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.policy import (Policy, default_provider,
                                       node_label_args, node_label_prio_args,
                                       service_affinity_labels,
                                       service_anti_affinity_labels)
from kubernetes_tpu.cache.scheduler_cache import SchedulerCache
from kubernetes_tpu.engine import devicestats
from kubernetes_tpu.engine import guard as guard_mod
from kubernetes_tpu.engine import solver as sv
from kubernetes_tpu.engine.hostsolver import HostSolver
from kubernetes_tpu.engine.extender_client import (ExtenderError,
                                                   ExtenderUnavailable,
                                                   HTTPExtender)
from kubernetes_tpu.utils import metrics
from kubernetes_tpu.features import batch as fb
from kubernetes_tpu.features import compiler as fc
from kubernetes_tpu.features import padcap
from kubernetes_tpu.features.plan import FeaturePlan
from kubernetes_tpu.features.volumes import compile_volsvc
from kubernetes_tpu.utils.logging import get_logger
from kubernetes_tpu.utils.trace import Trace, record_stage, stage

log = get_logger("engine")


def _node_names(nt: fc.NodeTensors, rows) -> list[str | None]:
    """A readback's row indices as node names, None where unschedulable
    (``-1``).  ``nt`` is the launch's own view (``_compile``): a row
    reads as the node the scan saw there, whatever joined or left since
    (a node that left meanwhile is named all the same, as it always was:
    the assume waits for a join under that name).  A free row fits
    nothing, so the scan never yields one; a decision that names one is
    counted as a cache invariant violation and the pod stays pending
    rather than be bound to no node."""
    names = nt.names
    out = [names[c] if c >= 0 else None for c in rows]
    lost = sum(1 for c, name in zip(rows, out) if c >= 0 and name is None)
    if lost:
        metrics.CACHE_INVARIANT_VIOLATIONS.labels(kind="free_row").inc(lost)
        log.error("%d decision(s) named a free row of the node axis; the "
                  "pods stay pending", lost)
    return out


def _node_name(nt: fc.NodeTensors, row: int, pod: api.Pod) -> str:
    """The single-pod decision's row as a node name, by ``_node_names``'
    rules: a free row is counted and fails the pod."""
    (name,) = _node_names(nt, [row])
    if name is None:
        raise FitError(pod, {})
    return name


class FitError(Exception):
    """No node fits (generic_scheduler.go:39-61). failed_predicates maps
    node name -> list of failing predicate names."""

    def __init__(self, pod: api.Pod, failed_predicates: dict[str, list[str]]):
        self.pod = pod
        self.failed_predicates = failed_predicates
        super().__init__(f"pod ({pod.name}) failed to fit in any node")


@dataclass
class Listers:
    """In-memory cluster-object stores standing in for the reference's
    reflector-backed caches (factory.go:387-416)."""

    services: list[api.Service] = field(default_factory=list)
    controllers: list[api.ReplicationController] = field(default_factory=list)
    replica_sets: list[api.ReplicaSet] = field(default_factory=list)
    pvs: list[api.PersistentVolume] = field(default_factory=list)
    pvcs: list[api.PersistentVolumeClaim] = field(default_factory=list)

    def get_pv(self, name: str) -> api.PersistentVolume | None:
        for pv in self.pvs:
            if pv.name == name:
                return pv
        return None

    def get_pvc(self, namespace: str, name: str) -> api.PersistentVolumeClaim | None:
        for pvc in self.pvcs:
            if pvc.namespace == namespace and pvc.name == name:
                return pvc
        return None

    def first_service(self, pod: api.Pod) -> api.Service | None:
        """GetPodServices[0] (the reference's ServiceAffinity/ServiceAnti
        Affinity use only the first matching service,
        predicates.go:676-678)."""
        for s in self.services:
            if s.namespace == pod.namespace and s.selector and \
                    all(pod.labels.get(k) == v for k, v in s.selector.items()):
                return s
        return None

    def spread_selectors(self, pod: api.Pod) -> list:
        """GetPodServices/GetPodControllers/GetPodReplicaSets
        (pkg/client/cache/listers.go): same namespace, empty selectors match
        nothing, unlabeled pods match no RC/RS."""
        out: list = []
        for s in self.services:
            if s.namespace == pod.namespace and s.selector and \
                    all(pod.labels.get(k) == v for k, v in s.selector.items()):
                out.append(s.selector)
        if pod.labels:
            for rc in self.controllers:
                if rc.namespace == pod.namespace and rc.selector and \
                        all(pod.labels.get(k) == v for k, v in rc.selector.items()):
                    out.append(rc.selector)
            for rs in self.replica_sets:
                if rs.namespace == pod.namespace and rs.selector is not None:
                    if (rs.selector.match_labels or rs.selector.match_expressions) \
                            and rs.selector.matches(pod.labels):
                        out.append(rs.selector)
        return out

    def controller_refs(self, pod: api.Pod) -> list:
        """Controller signatures for NodePreferAvoidPods (priorities.go:340-342).
        UIDs are modeled as 'namespace/name'."""
        out = []
        if pod.labels:
            for rc in self.controllers:
                if rc.namespace == pod.namespace and rc.selector and \
                        all(pod.labels.get(k) == v for k, v in rc.selector.items()):
                    out.append(("ReplicationController", f"{rc.namespace}/{rc.name}"))
            for rs in self.replica_sets:
                if rs.namespace == pod.namespace and rs.selector is not None:
                    if (rs.selector.match_labels or rs.selector.match_expressions) \
                            and rs.selector.matches(pod.labels):
                        out.append(("ReplicaSet", f"{rs.namespace}/{rs.name}"))
        return out


class GenericScheduler:
    def __init__(self, policy: Policy | None = None,
                 cache: SchedulerCache | None = None,
                 listers: Listers | None = None):
        self.policy = policy or default_provider()
        self.cache = cache or SchedulerCache()
        self.listers = listers or Listers()
        # Persistent XLA compilation cache, configured before the first
        # trace: warm starts deserialize executables instead of paying
        # the multi-second compile tax again (engine/compile_cache.py).
        from kubernetes_tpu.engine import compile_cache
        compile_cache.configure()
        # Shared per policy signature: a fresh Solver per engine would
        # re-trace and re-compile every executable (see Solver.for_policy).
        self.solver = sv.Solver.for_policy(self.policy)
        # Device-resident cluster mirror: per drain only the cache's
        # dirty rows are scattered into the resident (nodes x features)
        # arrays; a full re-upload happens only on relist or capacity
        # growth (sv.ResidentCluster).
        self.resident = sv.ResidentCluster()
        self.extenders = [HTTPExtender(cfg) for cfg in self.policy.extenders]
        # Guarded device execution (engine/guard.py): every solve site
        # runs inside the guard so accelerator faults classify, count,
        # and recover (OOM -> evict + bisect, repeated/terminal -> the
        # host fallback engine below) instead of stalling the drain.
        self.guard = guard_mod.DeviceGuard(evict_fn=self.resident.invalidate)
        # The NumPy fallback engine behind the same masks/evaluate/solve
        # surface — slower than the device scan, always available.
        self.host_solver = HostSolver(self.solver)
        self.last_node_index = np.uint32(0)
        # Monotonic compile state (features.padcap): table-axis capacities
        # and the OR of all content flags seen, so a long-running daemon
        # converges on ONE compiled scan per (chunk, cluster) shape
        # instead of re-specializing whenever batch content wobbles.
        self._axis_caps: dict[str, int] = {}
        self._flags_seen: sv.BatchFlags | None = None
        # Spread-constraint term tables for the batch _compile last saw
        # (None = no pod carried topologySpreadConstraints).
        self._topo_terms = None
        # What the feature build keeps between launches: the node-side
        # and template-side tables of compile_batch / compile_volsvc,
        # valid for one ``cache.node_epoch`` (features/plan.py).  Read
        # and written in _compile's locked section only.
        self._plan = FeaturePlan()
        # The policy's arguments to compile_volsvc (the policy of an
        # engine never changes).
        self._volsvc_args = dict(
            service_affinity_labels=service_affinity_labels(self.policy),
            service_anti_affinity_labels=service_anti_affinity_labels(
                self.policy),
            node_label_args=node_label_args(self.policy),
            node_label_prio_args=node_label_prio_args(self.policy))

    def _pinned_flags(self, batch) -> sv.BatchFlags:
        """Content flags OR-ed monotonically (padcap's discipline for the
        scan's boolean specialization): once a family has appeared, later
        batches keep paying its (numerically no-op when empty) state
        rather than minting a new compiled scan when it vanishes."""
        flags = sv.batch_flags(batch)
        if self._flags_seen is not None:
            flags = sv.BatchFlags(*(a or b for a, b in
                                    zip(flags, self._flags_seen)))
        self._flags_seen = flags
        return flags

    def _count_scored(self, flags: sv.BatchFlags, pods: int) -> None:
        """``pods`` live pods go into a scan compiled for ``flags``."""
        if self.solver.scores_affinity(flags):
            metrics.AFFINITY_PRIORITY_PODS.inc(pods)

    @staticmethod
    def _count_steps(live: np.ndarray | None, rows: int) -> None:
        """One ``_solve_scan`` dispatch of ``rows`` rows with this live
        mask goes to the device."""
        metrics.SCAN_STEPS.labels(kind="bucket").inc(rows)
        metrics.SCAN_STEPS.labels(kind="run").inc(sv.scan_steps(live, rows))

    # -- compilation helpers --------------------------------------------

    def _features(self, pods: list[api.Pod], nt: fc.NodeTensors,
                  ep: fc.ExistingPodTensors, nodes: list[api.Node],
                  plan: Optional[FeaturePlan],
                  caps: dict[str, int]) -> fb.PodBatch:
        """The host feature build of one launch against a snapshot, under
        the cache lock: the volume / service tables, the pod batch, the
        monotonic caps.  With the engine's ``plan`` what only a node
        event or a new template changes is looked up; ``plan=None``
        builds all of it from nothing — the same batch to the element
        (tests/test_feature_plan.py)."""
        volsvc = compile_volsvc(
            pods, nodes, nt.schedulable,
            volume_pods=self.cache.volume_pods(),
            listers=self.listers,
            service_peers=self.cache.service_peer_nodes,
            first_peer=self.cache.first_peer_node,
            plan=plan, **self._volsvc_args)
        batch = fb.compile_batch(
            pods, nt, self.cache.space, ep=ep, nodes=nodes,
            spread_selectors=self.listers.spread_selectors,
            controller_refs=self.listers.controller_refs,
            resident_affinity=self.cache.affinity_tables(),
            hard_pod_affinity_weight=(
                self.policy.hard_pod_affinity_symmetric_weight),
            volsvc=volsvc, plan=plan)
        return padcap.apply_caps(batch, caps)

    def _compile(self, pods: list[api.Pod], device: bool = True,
                 host_only: bool = False, live: np.ndarray | None = None
                 ) -> tuple[fb.PodBatch, "sv.PackedBatch | sv.DeviceBatch",
                            sv.DeviceCluster, fc.NodeTensors]:
        """The node tensors come back as the launch's own VIEW
        (``NodeTensors.launch_view``, taken under the cache lock): a row
        freed and handed to another node while the solve is in flight
        still reads as the node the scan checked.
        The batch comes back in its wire form on the device
        (``sv.PackedBatch``, with the tie counter and, where the caller
        padded, the ``live`` mask riding its carrier), or with
        ``device=False`` as the host-numpy DeviceBatch the chunked drain
        slices.  ``host_only=True`` is the fallback engine's compile: the
        same snapshot + feature compile, but NO device participation — the
        cluster comes back as host numpy (``_host_cluster``) and the
        dirty-row set is NOT consumed (it belongs to the device mirror,
        which must replay every mutation when the breaker closes)."""
        from kubernetes_tpu.engine.workloads import topology
        # Topology keys named by spread constraints must be interned
        # BEFORE the snapshot so topo_dom columns exist for them (a NEW
        # key marks the node tensors dirty — once per workload type).
        has_spread = topology.batch_has_spread(pods)
        if has_spread:
            for key in topology.spread_topology_keys(pods):
                self.cache.ensure_topo_key(key)
        # The whole compile runs under the cache lock: cache mutators
        # (reflector handlers, async-bind forget_pod) update the aggregate
        # and existing-pod arrays IN PLACE, so every read — snapshot,
        # volume/affinity pod lists, feature compilation, and the device
        # transfer itself — must see one consistent generation.
        t_lock = time.perf_counter()
        with self.cache.lock:
            # What this thread waited behind the handlers for the lock
            # (the wait itself is counted where it is taken, by role:
            # cache/scheduler_cache.py _CacheLock).
            record_stage("lock_wait", start=t_lock)
            with stage("snapshot", pods=len(pods)):
                nt, agg, ep, nodes = self.cache.snapshot()
                view = nt.launch_view()
                # Tag for the device-aggregate handoff: the snapshot the
                # solve starts from (assume_pods validates nothing changed
                # since).
                self._snapshot_generation = self.cache.generation
            with stage("compile", pods=len(pods)):
                plan = self._plan
                plan.begin(self.cache.node_epoch)
                batch = self._features(pods, nt, ep, nodes, plan,
                                       self._axis_caps)
                result, cause = plan.outcome()
                metrics.FEATURE_PLAN.labels(result=result,
                                            cause=cause).inc()
                # Spread-constraint term tables (counts snapshotted under
                # the same lock as everything else this solve reads).
                self._topo_terms = topology.compile_terms(
                    pods, nt, self.cache.space,
                    self.cache.topo_domain_counts_bulk) \
                    if has_spread else None
            if host_only:
                return (batch, sv.host_batch(batch),
                        sv._host_cluster(nt, agg, self.cache.space), view)
            with stage("transfer", device=device):
                # device=False keeps the batch pytree on host (the chunked
                # drain slices it in numpy and transfers fixed-shape
                # chunks).
                if device:
                    with stage("transfer.batch"):
                        db = sv.device_batch(
                            batch, live=live,
                            counter=self.last_node_index)
                else:
                    db = sv.host_batch(batch)
                # Cluster state syncs through the device-resident mirror:
                # dirty rows scatter into the resident arrays; the full
                # snapshot transfer happens only on relist or capacity
                # growth.  Same locked section as the snapshot, so the
                # dirty set and the row contents are one generation.
                dc = self.resident.sync(nt, agg, self.cache.space,
                                        self.cache.take_dirty_rows(),
                                        self.cache.tensor_epoch)
        return batch, db, dc, view

    # -- single-pod path (Schedule, generic_scheduler.go:78) -------------

    def schedule(self, pod: api.Pod) -> str:
        """One decision through the guarded device path; a classified
        device fault (or an open breaker) decides the pod on the host
        fallback engine instead — FitError semantics are identical on
        both engines."""
        if self.guard.enabled and self.guard.mode == "host":
            return self._schedule_host(pod)
        try:
            return self._schedule_device(pod)
        except guard_mod.DeviceFault as fault:
            self.guard.recover(fault, can_bisect=False)
            return self._schedule_host(pod)

    def _schedule_device(self, pod: api.Pod) -> str:
        trace = Trace(f"Scheduling {pod.namespace}/{pod.name}")
        if not self.cache.node_count():
            raise FitError(pod, {})
        with devicestats.live_path("single_pod"), \
                self.guard.watch("single_pod"):
            batch, db, dc, nt = self._compile([pod])
            trace.step("Computing predicates & priorities")
            feasible, scores = self.solver.evaluate(
                db, dc, self._pinned_flags(batch))
            topo_mask_np = None
            if self._topo_terms is not None:
                from kubernetes_tpu.engine.workloads import topology
                tmask, tscore = topology.spread_planes(self._topo_terms,
                                                       dc.topo_dom)
                if tmask is not None:
                    feasible = feasible & tmask
                    topo_mask_np = np.asarray(tmask[0])
                if tscore is not None:
                    scores = scores + tscore
            trace.step("Selecting host")
            feasible_np, _ = self.guard.checked_scores(
                "single_pod", np.asarray(feasible[0]),
                np.asarray(scores[0]))
        if not feasible_np.any():
            # The masks pass is device work too: a fault here must take
            # the same classify -> host-fallback road as the evaluate.
            with self.guard.watch("single_pod", inject=False):
                masks = {k: np.asarray(v[0]) for k, v in
                         self.solver.masks(db, dc).items()}
            if topo_mask_np is not None:
                masks["TopologySpread"] = topo_mask_np
            failed: dict[str, list[str]] = {}
            for i, name in enumerate(nt.names):
                if nt.schedulable[i]:
                    failed[name] = [p for p, m in masks.items() if not m[i]]
            trace.log_if_long()
            raise FitError(pod, failed)
        if self.extenders:
            host = self._schedule_with_extenders(
                pod, nt, feasible_np, np.asarray(scores[0]))
            trace.log_if_long()
            return host
        with self.guard.watch("single_pod", inject=False):
            choice, new_last = sv.combine.select_hosts(
                scores, feasible, self.last_node_index)
            picked = int(choice[0])
        self.last_node_index = np.uint32(new_last)
        trace.log_if_long()
        return _node_name(nt, picked, pod)

    def _schedule_with_extenders(self, pod: api.Pod, nt,
                                 feasible_np: np.ndarray,
                                 scores_np: np.ndarray) -> str:
        """Extender filter after built-in predicates
        (generic_scheduler.go:189-207) and prioritize summed at weight
        (:287-305), then selectHost (:124-141) host-side."""
        # Rows are the launch's (``nt`` is its view); a node that left
        # since is no candidate, one that joined since was not evaluated.
        row_of = {nt.names[i]: int(i) for i in np.flatnonzero(feasible_np)}
        candidates = [n for n in self.cache.nodes() if n.name in row_of]
        if not candidates:
            raise FitError(pod, {})
        failed_ext: dict[str, list[str]] = {}
        degraded = False
        for ext in self.extenders:
            try:
                candidates, failed = ext.filter(pod, candidates)
            except ExtenderUnavailable:
                # Breaker open: the endpoint is known-dead.  Graceful
                # degradation — schedule on built-in predicates alone
                # rather than failing every pod until it recovers.  (A
                # closed-breaker timeout still raises ExtenderError and
                # fails THIS pod, the reference's filter-timeout
                # semantics, api/types.go:128-130.)
                if not degraded:
                    degraded = True
                    metrics.EXTENDER_DEGRADED_DECISIONS.labels(
                        extender=ext.config.url_prefix).inc()
                    # debug, not warning: thousands of pods degrade per
                    # 15 s open window — the breaker transition itself is
                    # logged once (extender_client) and counted above.
                    log.debug("extender %s unavailable (breaker open); "
                              "scheduling %s with built-in predicates "
                              "only", ext.config.url_prefix, pod.key)
                continue
            for name, msg in failed.items():
                failed_ext.setdefault(name, []).append(msg or "extender")
            if not candidates:
                raise FitError(pod, failed_ext)
        combined = {n.name: float(scores_np[row_of[n.name]])
                    for n in candidates}
        for ext in self.extenders:
            for host, score in ext.prioritize(pod, candidates).items():
                if host in combined:
                    combined[host] += score
        best = max(combined.values())
        ties = [n.name for n in candidates if combined[n.name] == best]
        choice = ties[int(self.last_node_index) % len(ties)]
        self.last_node_index = np.uint32(int(self.last_node_index) + 1)
        return choice

    # -- host fallback engine paths (engine/hostsolver.py) ----------------

    def _compile_host(self, pods: list[api.Pod]):
        """The fallback engine's compile: ``_compile`` with
        ``host_only=True`` — ONE implementation of the snapshot/feature
        sequence, so predicate and workload-constraint additions reach
        both engines automatically (incl. ``self._topo_terms``, which
        the host paths consume through ``topology.spread_planes_host``)."""
        return self._compile(pods, host_only=True)

    def _host_topo_planes(self, hc):
        """(extra_mask, score_bias) numpy planes for the host solve —
        the fallback must honor hard DoNotSchedule spread terms too
        (quality may degrade on host, constraints may not)."""
        if self._topo_terms is None:
            return None, None
        from kubernetes_tpu.engine.workloads import topology
        return topology.spread_planes_host(self._topo_terms,
                                           np.asarray(hc.topo_dom))

    def _schedule_host(self, pod: api.Pod) -> str:
        """The single-pod decision on the host fallback engine — same
        FitError / extender / round-robin contract as the device path."""
        trace = Trace(f"Scheduling {pod.namespace}/{pod.name} "
                      f"(host engine)")
        if not self.cache.node_count():
            raise FitError(pod, {})
        metrics.SOLVE_FALLBACKS.labels(mode="host").inc()
        batch, hb, hc, nt = self._compile_host([pod])
        trace.step("Computing predicates & priorities (host)")
        feasible, scores = self.host_solver.evaluate(hb, hc)
        extra_mask, score_bias = self._host_topo_planes(hc)
        topo_mask_np = None
        if extra_mask is not None:
            feasible = feasible & extra_mask
            topo_mask_np = extra_mask[0]
        if score_bias is not None:
            scores = scores + score_bias
        feasible_np, scores_np = feasible[0], scores[0]
        trace.step("Selecting host")
        if not feasible_np.any():
            masks = {k: m[0] for k, m in
                     self.host_solver.masks(hb, hc).items()}
            if topo_mask_np is not None:
                masks["TopologySpread"] = topo_mask_np
            failed: dict[str, list[str]] = {}
            for i, name in enumerate(nt.names):
                if nt.schedulable[i]:
                    failed[name] = [p for p, m in masks.items()
                                    if not m[i]]
            trace.log_if_long()
            raise FitError(pod, failed)
        if self.extenders:
            host = self._schedule_with_extenders(
                pod, nt, feasible_np, scores_np.astype(np.float32))
            trace.log_if_long()
            return host
        # selectHost round-robin (combine.select_hosts, host-side).
        masked = np.where(feasible_np, scores_np, -np.inf)
        ties = feasible_np & (masked == masked.max())
        ix = int(self.last_node_index) % int(ties.sum())
        choice = int(np.nonzero(ties)[0][ix])
        self.last_node_index = np.uint32(int(self.last_node_index) + 1)
        trace.log_if_long()
        return _node_name(nt, choice, pod)

    def schedule_batch_host(self, pods: list[api.Pod]) -> list[str | None]:
        """The host fallback drain: ``schedule_batch``'s contract (node
        names, None where unschedulable) on the NumPy sequential-greedy
        engine.  No padding, no buckets, no device — and its output
        still runs through the sanity gate, so both engines bind under
        the same guarantees."""
        if not pods:
            return []
        if not self.cache.node_count():
            return [None] * len(pods)
        if self.extenders:
            return self._schedule_batch_via_extenders(pods)
        metrics.SOLVE_FALLBACKS.labels(mode="host").inc()
        self._agg_handoff = None
        batch, hb, hc, nt = self._compile_host(pods)
        extra_mask, score_bias = self._host_topo_planes(hc)
        with stage("solve", pods=len(pods), mode="host"):
            choices, counter = self.host_solver.solve_greedy(
                hb, hc, int(self.last_node_index),
                extra_mask=extra_mask, score_bias=score_bias)
        with stage("gate"):
            choices = self.guard.checked_readback(
                "host", choices, len(nt.names),
                alloc=nt.alloc, requests=np.asarray(batch.request),
                keys_fn=lambda: [p.key for p in pods])
        self.last_node_index = np.uint32(counter)
        return _node_names(nt, choices.tolist())

    # -- batched path ----------------------------------------------------

    def schedule_batch(self, pods: list[api.Pod],
                       joint: bool = False,
                       pad_to: int = 0) -> list[str | None]:
        """Place a pending queue in one device solve.  Returns node names,
        None where unschedulable.

        Default mode is sequential-greedy in queue order with full in-batch
        visibility (decision parity with the reference's one-at-a-time
        loop).  ``joint=True`` runs the LP-relaxed global assignment
        (price iteration + regret-ordered repair) — better aggregate
        placement quality, no per-pod order parity.

        ``pad_to``: pad the batch to this length with live-masked inert
        rows so the solve hits a fixed compiled shape (the workload-
        constrained drain's bucket-ladder discipline — gang and joint
        drains can't stream-chunk, so this is how their shapes stay
        pre-warmable)."""
        if not pods:
            return []
        if not self.cache.node_count():
            # Empty cluster: findNodesThatFit over zero nodes fails every
            # pod (no device solve; zero-size tensors don't reduce).
            return [None] * len(pods)
        if self.extenders:
            # Extenders are a per-pod HTTP protocol; run the exact one-pod
            # path with temporary assumes for in-batch visibility, then
            # restore (callers re-assume through the daemon).
            return self._schedule_batch_via_extenders(pods)
        real_p = len(pods)
        live_np = None
        if pad_to > real_p:
            with stage("pad", pods=real_p):
                pods = fb.pad_pods(pods, pad_to)
                live_np = np.zeros(len(pods), bool)
                live_np[:real_p] = True
        # The tie counter and the live mask ride db's carrier: the solve
        # calls below pass None for both.
        with self.guard.watch("oneshot" if not joint else "joint",
                              inject=False):
            batch, db, dc, nt = self._compile(pods, live=live_np)
        with stage("scan_inputs"):
            flags = self._pinned_flags(batch)
            if not joint:
                self._count_scored(flags, real_p)
            extra_mask = score_bias = None
            if self._topo_terms is not None:
                from kubernetes_tpu.engine.workloads import topology
                extra_mask, score_bias = topology.spread_planes(
                    self._topo_terms, dc.topo_dom)
        if log.isEnabledFor(10):
            log.debug("schedule_batch: %d pods (%d templates) x %d nodes, "
                      "joint=%s flags=%s", len(pods),
                      len({getattr(p, "_tpl_key", None) for p in pods}),
                      sv.cluster_nodes(dc), joint, flags)
        self._agg_handoff = None
        if joint:
            with devicestats.live_path("joint"), \
                    self.guard.watch("joint"), \
                    stage("solve", pods=len(pods), mode="joint"):
                choices, new_last, _ = self.solver.solve_joint(
                    db, dc, None, flags=flags,
                    extra_mask=extra_mask, score_bias=score_bias)
                with stage("device_wait"):
                    choices.block_until_ready()
            with stage("readback", pods=len(pods)):
                with self.guard.watch("joint", inject=False):
                    choices_np = np.asarray(choices)
                devicestats.record_transfer("readback", choices_np.nbytes)
                with stage("gate"):
                    choices_np = self.guard.checked_readback(
                        "joint", choices_np, sv.cluster_nodes(dc),
                        live=live_np, alloc=nt.alloc,
                        requests=np.asarray(batch.request),
                        keys_fn=lambda: [pd.key for pd in pods[:real_p]])
                rows = choices_np[:real_p].tolist()
            self.last_node_index = np.uint32(new_last)
        else:
            # One packed device->host fetch for the whole drain (each fetch
            # is a synchronization point): choices + tie counter + final
            # aggregates.
            p, n = len(pods), sv.cluster_nodes(dc)
            with devicestats.live_path("oneshot"), \
                    self.guard.watch("oneshot"), \
                    stage("solve", pods=p, mode="sequential"):
                host_dev = self.solver.solve_sequential_packed(
                    db, dc, None, flags,
                    extra_mask=extra_mask, score_bias=score_bias)
                self._count_steps(live_np, p)
                # Block here so the solve stage measures device compute
                # and readback measures only the D2H copy.
                with stage("device_wait"):
                    host_dev.block_until_ready()
            with stage("readback", pods=p):
                with self.guard.watch("oneshot", inject=False):
                    host = np.asarray(host_dev)
                devicestats.record_transfer("readback", host.nbytes)
            with stage("gate"):
                choices_np = self.guard.checked_readback(
                    "oneshot", host[:p], n, live=live_np, alloc=nt.alloc,
                    requests=np.asarray(batch.request),
                    keys_fn=lambda: [pd.key for pd in pods[:real_p]])
            rows = choices_np[:real_p].tolist()
            self.last_node_index = np.uint32(host[p])
            # Device-aggregate handoff: the scan's final requested/nonzero
            # equal the snapshot plus every in-batch placement, so
            # assume_pods can ingest them instead of re-aggregating — valid
            # only when the batch carries no port/volume state (host-only
            # counters), the cache hasn't moved since the snapshot, and the
            # assumed set is EXACTLY this solve's placements (stamped with
            # their signature so a caller can't pair the aggregates with a
            # different assignment set at an unchanged generation).
            if not (flags.any_ports or flags.any_volumes or flags.any_ebs
                    or flags.any_gce):
                placed_sig = hash(frozenset(
                    (pod.key, rows[i])
                    for i, pod in enumerate(pods[:real_p])
                    if rows[i] >= 0))
                self._agg_handoff = (
                    self._snapshot_generation, placed_sig, nt,
                    host[p + 1:p + 1 + 4 * n].reshape(n, 4),
                    host[p + 1 + 4 * n:].reshape(n, 2))
        return _node_names(nt, rows)

    def plan_report(self) -> dict:
        """What the feature build keeps, for ``/debug/vars``."""
        with self.cache.lock:
            return self._plan.report()

    def take_agg_handoff(self) -> Optional[tuple]:
        """One-shot: the (generation, requested, nonzero) handoff from the
        last schedule_batch, if any (see assume_pods)."""
        h = getattr(self, "_agg_handoff", None)
        self._agg_handoff = None
        return h

    # Cap on pods explained per call: one small compile + two device
    # evaluations cover the whole explained set, but the host-side mask
    # walk is O(pods x nodes x predicates).
    EXPLAIN_CAP = 64

    def explain_failures(self, pods: list[api.Pod]) -> dict:
        """Per-predicate failure counts (and top-scoring nodes) for pods
        that failed to place — the flight recorder's detail pass.  Runs
        against the CURRENT cache snapshot, so a pod that only failed
        because of in-batch contention may show zero failing predicates;
        the counts answer "why does this pod not fit the cluster", the
        reference ``FitError.failed_predicates`` aggregation.

        Cost is one ``_compile`` + ``masks`` + ``evaluate`` over at most
        ``EXPLAIN_CAP`` pods, paid only when a drain actually failed pods
        (a fully-placed drain never calls this).  The batch is padded to
        EXPLAIN_CAP with inert pods so every call hits ONE compiled
        shape — unpadded, each distinct failed-pod count would mint its
        own multi-second XLA compile in the drain path."""
        pods = pods[:self.EXPLAIN_CAP]
        if not pods:
            return {}
        if not self.cache.node_count():
            return {pod.key: {"message": "no nodes in cluster",
                              "failed_predicates": {}}
                    for pod in pods}
        padded = fb.pad_pods(pods, self.EXPLAIN_CAP)
        batch, db, dc, nt = self._compile(padded)
        # The feature build above counts as the launch's own stages; the
        # evaluation and the walk over its masks are stage ``explain``.
        with stage("explain", pods=len(pods)):
            masks = {name: np.asarray(m) for name, m in
                     self.solver.masks(db, dc).items()}
            _, scores = self.solver.evaluate(db, dc, sv.batch_flags(batch))
            # a free row of the node axis is no node: never among the top
            scores = np.where([name is not None for name in nt.names],
                              np.asarray(scores), -np.inf)
            sched = np.asarray(nt.schedulable, dtype=bool)
            n_sched = int(sched.sum())
            out: dict = {}
            for i, pod in enumerate(pods):
                counts = {}
                for name, m in masks.items():
                    failing = int(np.count_nonzero(sched & ~m[i]))
                    if failing:
                        counts[name] = failing
                top_idx = np.argsort(-scores[i])[:5]
                out[pod.key] = {
                    "message": f"pod ({pod.name}) failed to fit in any node"
                    if counts else
                    f"pod ({pod.name}) fit no node in this batch (in-batch "
                    f"contention; predicates pass against the current "
                    f"snapshot)",
                    "nodes_considered": n_sched,
                    "failed_predicates": counts,
                    "top_scores": [{"node": nt.names[int(j)],
                                    "score": float(scores[i][int(j)])}
                                   for j in top_idx]}
        return out

    # Preemption decisions computed per drain: the masks pass pads to
    # this many pods (one compiled shape, the EXPLAIN_CAP discipline) and
    # the per-decision eviction blast radius is bounded separately
    # (workloads.preemption.MAX_VICTIMS).
    PREEMPT_CAP = 16

    def find_preemptions(self, pods: list[api.Pod],
                         protected: frozenset = frozenset()) -> list:
        """Minimal-cost victim sets for unschedulable priority pods — the
        second batched solve (engine/workloads/preemption.py).

        Per pod, in priority order: one vmapped ``victim_solve`` over the
        (nodes x victims) table picks the cheapest feasible eviction
        prefix per node; the host takes the (victim count, victim
        priority sum, node index) argmin.  Decisions within one call see
        each other through host-side overlays (victims already claimed
        are consumed, the preemptor's own request charged), so two pods
        never nominate the same victim.  ``protected`` keys are never
        victims (the daemon shields the current drain's own placements).
        The caller executes the decisions (evict -> assume -> bind,
        scheduler/scheduler.py); it must have ASSUMED the batch's
        placements first so the aggregates this solve reads include
        them."""
        from kubernetes_tpu.engine.workloads import preemption as pre
        pods = [p for p in pods if p.effective_priority > 0]
        pods.sort(key=lambda p: (-p.effective_priority, p.key))
        pods = pods[:self.PREEMPT_CAP]
        if not pods or not self.cache.node_count():
            return []
        padded = fb.pad_pods(pods, self.PREEMPT_CAP)
        batch, db, dc, nt = self._compile(padded)
        # As explain_failures: the feature build counts as the launch's
        # own stages, the search after it is stage ``victims``.
        with stage("victims", pods=len(pods)):
            # Non-resource predicate rows: victims free resources,
            # nothing else — a node that only becomes selector/taint-
            # feasible after eviction is never nominated (conservative).
            masks = {name: np.asarray(m) for name, m in
                     self.solver.masks(db, dc).items()}
            base = np.broadcast_to(np.asarray(nt.schedulable, bool),
                                   (len(padded), nt.alloc.shape[0])).copy()
            for name, m in masks.items():
                if name not in ("PodFitsResources",):
                    base &= m
            if self._topo_terms is not None:
                from kubernetes_tpu.engine.workloads import topology
                tmask, _ = topology.spread_planes(self._topo_terms,
                                                  dc.topo_dom)
                if tmask is not None:
                    base &= np.asarray(tmask)
            with self.cache.lock:
                _, agg, _, _ = self.cache.snapshot()
                vt = self.cache.victim_table(pre.MAX_VICTIMS,
                                             exclude=protected)
                requested = agg.requested.copy()
            alloc = nt.alloc
            vic_req, vic_prio, vic_valid = (vt.req.copy(), vt.prio.copy(),
                                            vt.valid.copy())
            vic_keys = [list(k) for k in vt.keys]
            decisions = []
            with devicestats.live_path("victim"), \
                    self.guard.watch("victim"):
                self._find_preemptions_inner(
                    pods, alloc, requested, base, vic_req, vic_prio,
                    vic_valid, vic_keys, nt, decisions)
        return decisions

    def _find_preemptions_inner(self, pods, alloc, requested, base,
                                vic_req, vic_prio, vic_valid, vic_keys,
                                nt, decisions) -> None:
        from kubernetes_tpu.engine.workloads import preemption as pre
        for i, pod in enumerate(pods):
            pod_req = fc.pod_resource_row(pod)
            k_min, cost, feas = pre.victim_solve(
                jnp.asarray(alloc), jnp.asarray(requested),
                jnp.asarray(base[i]), jnp.asarray(vic_req),
                jnp.asarray(vic_prio), jnp.asarray(vic_valid),
                jnp.asarray(pod_req),
                jnp.asarray(bool(pod_req[0] == pod_req[1]
                                 == pod_req[2] == 0)),
                jnp.asarray(pod.effective_priority, jnp.int32))
            n_idx = pre.pick_node(np.asarray(k_min), np.asarray(cost),
                                  np.asarray(feas))
            if n_idx is None:
                continue
            k = int(np.asarray(k_min)[n_idx])
            victims = vic_keys[n_idx][:k]
            decisions.append(pre.PreemptionDecision(
                pod_key=pod.key, node=nt.names[n_idx], node_idx=n_idx,
                victims=victims,
                prio_cost=int(np.asarray(cost)[n_idx])))
            # Overlay for later pods in this call: free the claimed
            # victims' rows, charge the preemptor, shift the table.
            freed = vic_req[n_idx, :k].sum(axis=0)
            requested[n_idx] = requested[n_idx] - freed + pod_req
            if k:
                vic_req[n_idx] = np.concatenate(
                    [vic_req[n_idx, k:], np.zeros((k, 4), np.int32)])
                vic_prio[n_idx] = np.concatenate(
                    [vic_prio[n_idx, k:], np.zeros(k, np.int32)])
                vic_valid[n_idx] = np.concatenate(
                    [vic_valid[n_idx, k:], np.zeros(k, bool)])
                vic_keys[n_idx] = vic_keys[n_idx][k:]

    def schedule_batch_stream(self, pods: list[api.Pod],
                              chunk_size: int = 2048,
                              defer_readback: bool = False) -> Iterator:
        """Pipelined batched drain: one host compile, then the scan runs in
        equal-shaped chunks with device-carried state (identical choices to
        ``schedule_batch`` — each chunk continues the previous chunk's
        aggregates).  Yields ``(chunk_pods, chunk_placements)`` as each
        chunk's results land, while the device is already scanning the next
        chunk — the double-buffered decide/commit pipeline the reference
        gets from its async-bind goroutine (scheduler.go:122-153), stretched
        over the whole queue.

        With ``defer_readback=True`` each yield is ``(chunk_pods,
        resolve)`` instead, where ``resolve()`` performs the blocking
        device->host readback and returns the placements — the daemon's
        overlapped pipeline calls it on the binder pool so the drain
        thread never blocks on the device and batch N's scan runs while
        batch N-1 commits (scheduler.pipeline.DrainPipeline._solve_stream).

        The last chunk is padded with inert pods (live=False rows are
        infeasible everywhere and bump no tie counter) so every chunk hits
        the same compiled executable."""
        p = len(pods)
        if p == 0:
            return
        if not self.cache.node_count():
            for start in range(0, p, chunk_size):
                chunk = pods[start:start + chunk_size]
                empty = [None] * len(chunk)
                yield (chunk, (lambda c=chunk, e=empty: (c, e))) \
                    if defer_readback else (chunk, empty)
            return
        n_chunks = (p + chunk_size - 1) // chunk_size
        padded = n_chunks * chunk_size
        with stage("pad", pods=p):
            all_pods = fb.pad_pods(pods, padded)
        with self.guard.watch("stream", inject=False):
            batch, hb, dc, nt = self._compile(all_pods, device=False)
        with stage("scan_inputs"):
            flags = self._pinned_flags(batch)
            self._count_scored(flags, p)
            # Spread-constraint planes, host-resident like the batch:
            # each chunk's fixed-shape row slice rides the chunk's packed
            # carrier (pad rows carry no constraints, so their mask rows
            # are all-pass).
            topo_mask_np = topo_score_np = None
            if self._topo_terms is not None:
                from kubernetes_tpu.engine.workloads import topology
                tmask, tscore = topology.spread_planes(self._topo_terms,
                                                       dc.topo_dom)
                topo_mask_np = None if tmask is None else np.asarray(tmask)
                topo_score_np = None if tscore is None \
                    else np.asarray(tscore)
            n = sv.cluster_nodes(dc)
            live_np = np.zeros(padded, bool)
            live_np[:p] = True
        # The first chunk's tie counter rides its packed batch; from the
        # second on it is the device scalar the previous scan returned.
        counter = carry = None
        pending: list[tuple[int, jnp.ndarray]] = []

        def emit(start: int, choices) -> tuple[list, list]:
            with stage("readback", chunk_at=start):
                with self.guard.watch("stream", inject=False):
                    # The wait for this chunk's scan, apart from the
                    # copy: readback - device_wait is the D2H transfer.
                    with stage("device_wait"):
                        choices.block_until_ready()
                    rows = np.asarray(choices)
                devicestats.record_transfer("readback", rows.nbytes)
            stop = min(start + chunk_size, p)
            chunk_pods = pods[start:stop]
            # Post-solve sanity gate: a corrupt chunk readback requeues
            # the chunk (DeviceFault through the commit worker) instead
            # of binding garbage.
            with stage("gate"):
                rows = self.guard.checked_readback(
                    "stream", rows, n,
                    live=live_np[start:start + chunk_size],
                    alloc=nt.alloc,
                    requests=np.asarray(hb.request)[
                        start:start + chunk_size],
                    keys_fn=lambda: [pd.key for pd in chunk_pods])
            return chunk_pods, _node_names(nt, rows[: stop - start].tolist())

        for start in range(0, padded, chunk_size):
            # Host-slice (free numpy views), pack the fixed
            # [chunk_size, ...] leaves with the chunk's live mask (and the
            # counter, and the planes) into one carrier, then ONE
            # device_put of it: slicing ON DEVICE minted a
            # dynamic_slice program per distinct drain length.
            stop = start + chunk_size

            def rows(plane: np.ndarray | None) -> np.ndarray | None:
                return None if plane is None else plane[start:stop]

            with stage("transfer", chunk_at=start), \
                    stage("transfer.batch"):
                db_k = sv.put_batch(
                    sv.slice_pod_axis(hb, start, stop),
                    live=live_np[start:stop],
                    counter=self.last_node_index if carry is None
                    else None,
                    extra_mask=rows(topo_mask_np),
                    score_bias=rows(topo_score_np))
            # The launch is async: device time surfaces in the next
            # chunk's readback, which is what keeps the pipeline
            # overlapped — this stage measures dispatch only.
            with devicestats.live_path("stream"), \
                    self.guard.watch("stream"), \
                    stage("solve", chunk_at=start, mode="stream"):
                choices_k, counter, carry = self.solver._solve_scan(
                    db_k, dc, counter, None, flags, carry)
                self._count_steps(live_np[start:stop], chunk_size)
            pending.append((start, choices_k))
            if len(pending) > 1:
                s_k, c_k = pending.pop(0)
                if defer_readback:
                    yield (pods[s_k:min(s_k + chunk_size, p)],
                           functools.partial(emit, s_k, c_k))
                else:
                    yield emit(s_k, c_k)
        for start, choices_k in pending:
            if defer_readback:
                yield (pods[start:min(start + chunk_size, p)],
                       functools.partial(emit, start, choices_k))
            else:
                yield emit(start, choices_k)
        # A read of the scan's device scalar: on the overlapped drain it
        # waits beside the commit worker's readback, off the launch's
        # critical path (a ``commit.`` stage).
        with stage("commit.counter"):
            self.last_node_index = np.uint32(counter)

    def _schedule_batch_via_extenders(self, pods: list[api.Pod]
                                      ) -> list[str | None]:
        out: list[str | None] = []
        assumed: list[api.Pod] = []
        try:
            for pod in pods:
                try:
                    dest = self.schedule(pod)
                except FitError:
                    out.append(None)
                    continue
                self.cache.assume_pod(pod, dest)
                assumed.append(pod)
                out.append(dest)
        finally:
            for pod in assumed:
                self.cache.forget_pod(pod)
                pod.node_name = ""
        return out
