"""The scheduling daemon loop.

The reference's ``Scheduler`` (plugin/pkg/scheduler/scheduler.go:46-154)
runs ``scheduleOne`` forever: blocking pop -> Schedule -> optimistic
AssumePod -> async Bind; on bind failure ForgetPod + error handler with
per-pod backoff requeue (factory.go:512-556).  This daemon keeps that state
machine and adds the TPU-native batched drain: ``schedule_pending`` pops the
whole queue and solves it as ONE device batch, assuming and binding every
placement — same observable behavior, three orders of magnitude fewer
device round-trips.

The batched drain itself — batch formation (deadline micro-batching,
scheduler/batchformer.py), mode routing, the overlapped solve/commit
worker, and crash handling — lives in ``scheduler.pipeline.DrainPipeline``;
this module keeps the commit-side state machine (assume/bind,
preemption, failure requeue, backoff) the pipeline calls back into, plus
the daemon lifecycle (run loops, prewarm, stop/abandon).
"""

from __future__ import annotations

import heapq
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait
from dataclasses import dataclass, field
from typing import Callable, Optional

from kubernetes_tpu.api import types as api
from kubernetes_tpu.apiserver.memstore import ConflictError
from kubernetes_tpu.engine.extender_client import ExtenderError
from kubernetes_tpu.engine.generic_scheduler import FitError, GenericScheduler
from kubernetes_tpu.scheduler.backoff import PodBackoff
from kubernetes_tpu.scheduler.batchformer import first_seen
from kubernetes_tpu.scheduler.binder import Binder, BindConflict, InMemoryBinder
from kubernetes_tpu.scheduler.flightrecorder import FlightRecorder
from kubernetes_tpu.scheduler.queue import FIFO
from kubernetes_tpu.utils import knobs, threadreg
from kubernetes_tpu.utils import metrics as metrics_mod
from kubernetes_tpu.utils import trace as trace_mod
from kubernetes_tpu.utils.events import EventRecorder
from kubernetes_tpu.utils.logging import get_logger
from kubernetes_tpu.utils.metrics import SchedulerMetrics
from kubernetes_tpu.utils.trace import stage


def _record_bind_failure(err) -> str:
    """409/CAS conflicts and transport faults are different operator
    stories: count them apart (both forget + requeue with backoff).
    Returns the attempts-counter result label for the failure class."""
    if isinstance(err, (BindConflict, ConflictError)):
        metrics_mod.BIND_CONFLICTS.inc()
        return "bind_conflict"
    metrics_mod.BIND_FAILURES.inc()
    return "bind_error"

log = get_logger("daemon")

DEFAULT_SCHEDULER_NAME = api.DEFAULT_SCHEDULER_NAME


def bucket_ladder(floor: int, stream_threshold: int, pad_limit: int,
                  stream_chunk: int = 0) -> list[int]:
    """The fixed set of chunk sizes a daemon's drains can compile at,
    as a pure function of its configuration — shared by the live
    ``Scheduler.effective_ladder`` and the kt-xray compile-surface
    manifest (analysis/xray.py), so the static proof and the runtime
    warmup can never disagree about the ladder.  Two sources: the
    stream chunk, included only when the chunked path is reachable
    (``stream_threshold`` set — at its unset sentinel every large drain
    takes the one-shot path); and the small-drain buckets: the floor
    itself (possibly non-pow2) plus each pow2 strictly above it up to
    the pow2 ceiling of the largest small drain."""
    ladder: set[int] = set()
    if stream_threshold < (1 << 62):
        ladder.add(stream_chunk or min(stream_threshold, 8192))
    small_top = min(stream_threshold, pad_limit)
    if small_top > 1:
        floor = max(floor, 1)
        # pow2 ceiling of the largest small drain (small_top - 1).
        top_bucket = 1 << max(small_top - 2, 0).bit_length()
        ladder.add(floor)
        # Mintable buckets are max(pow2ceil(len), floor): the floor,
        # then pow2 values strictly above it — doubling the floor
        # itself would trace unreachable shapes when it is not a
        # power of two (floor=300 mints {300, 512, ...}, never 600).
        b = 1 << floor.bit_length()  # smallest pow2 > floor
        while b <= top_bucket:
            ladder.add(b)
            b <<= 1
    return sorted(ladder)


def prewarm_plan(ladder: list[int], scatter_rows: list[int],
                 joint: bool = True, preempt: bool = True,
                 topo: bool = True) -> list[str]:
    """The static trace plan: every program key ``prewarm()`` traces
    for a given ladder, WITHOUT touching a device.  kt-xray's X04 rule
    pins the committed shape manifest's warmed-program set against the
    canonical instantiation of this plan, which makes "no live drain
    compiles after prewarm" a parse-time theorem (the PR 9 recompile
    watchdog stays armed as the runtime backstop).  Program keys match
    ``kubernetes_tpu/analysis/xray.py`` program names."""
    progs = [f"scan_first@{b}" for b in ladder]
    progs += [f"scan_carry@{b}" for b in ladder]
    progs += ["single_evaluate@1", "select_hosts@1"]
    progs += [f"scatter@{r}" for r in scatter_rows]
    if preempt:
        progs.append("victim_solve")
    if topo and ladder:
        progs += ["topo_planes", f"oneshot_topo@{min(ladder)}"]
    if joint and ladder:
        progs.append(f"joint@{min(ladder)}")
    return sorted(progs)


@dataclass
class SchedulerConfig:
    """The reference's scheduler.Config (scheduler.go:46-77)."""

    algorithm: GenericScheduler
    binder: Binder = field(default_factory=InMemoryBinder)
    recorder: EventRecorder = field(default_factory=EventRecorder)
    metrics: SchedulerMetrics = field(default_factory=SchedulerMetrics)
    scheduler_name: str = DEFAULT_SCHEDULER_NAME
    # Pod-condition updater analogue (factory.go:589-600); called with
    # (pod, reason, message) when scheduling fails.
    condition_updater: Optional[Callable[[api.Pod, str, str], None]] = None
    # Asked of a pod whose backoff has run out (factory.go:536-549: the
    # reference gets the pod again and requeues it only while its
    # spec.nodeName is empty); None requeues every such pod.
    still_pending: Optional[Callable[[api.Pod], bool]] = None
    async_bind: bool = True
    # Decision flight recorder (/debug/scheduler/decisions); None disables
    # recording entirely (and the failure-detail device pass with it).
    flight_recorder: Optional[FlightRecorder] = \
        field(default_factory=FlightRecorder)


class Scheduler:
    def __init__(self, config: SchedulerConfig):
        self.config = config
        self.queue = FIFO()
        # Failure-requeue backoff, env-tunable (the reference's
        # --pod-backoff knobs): chaos/soak rigs and latency-sensitive
        # fleets compress it; the defaults are the reference's 1s -> 60s.
        self.backoff = PodBackoff(
            default_duration=knobs.get_float("KT_POD_BACKOFF_S"),
            max_duration=knobs.get_float("KT_POD_BACKOFF_MAX_S"))
        # Stream floor, read ONCE at startup: the pre-warm pass and the
        # small-drain bucket computation must agree on the ladder for the
        # daemon's whole lifetime (a later env change would mint shapes
        # the warmup never traced).
        self.stream_min_bucket = knobs.get_int(
            "KT_STREAM_MIN_BUCKET", default=self.STREAM_MIN_BUCKET)
        # Overlapped solve/bind pipeline: while the device scans chunk N,
        # chunk N-1's readback/assume/bind runs on a dedicated commit
        # worker; at most this many chunks are in flight uncommitted
        # (0 = commit synchronously on the drain thread, the pre-pipeline
        # behavior).
        self.pipeline_window = knobs.get_int("KT_PIPELINE_WINDOW")
        # Workload-subsystem prewarm timings (string-keyed; see
        # _prewarm_workloads) — {} until prewarm() runs.
        self.workloads_prewarm_s: dict = {}
        # Per-ladder-bucket persistent-compile-cache hits/misses observed
        # during prewarm (the warm-start audit) — {} until prewarm() runs.
        self.prewarm_cache_stats: dict = {}
        # Live queue depth at expose time (a set-per-mutation gauge would
        # put two lock acquisitions on every enqueue).
        config.metrics.queue_depth.set_fn(lambda: len(self.queue))
        # Bounded-queue degradation surface (live at expose, same reason;
        # the watermark reads through so rigs that retune it after
        # construction stay honest).
        config.metrics.queue_high_watermark.set_fn(
            lambda: self.queue.high_watermark)
        config.metrics.queue_degraded.set_fn(
            lambda: 1.0 if self.queue.degraded() else 0.0)
        # Failure-detail cooldown: an unschedulable pod requeues every
        # backoff period and must not re-pay the explain device pass each
        # round.
        self._explain_ts: dict[str, float] = {}
        # First-seen registry for the e2e decision-latency SLO, keyed by
        # pod key: watch redeliveries (a condition write, any MODIFIED)
        # arrive as FRESH pod objects, so an object-only stamp would
        # reset the SLO clock on exactly the retried tail pods the
        # histogram exists to measure.  Entries clear at bind ack;
        # leftovers (pods deleted while pending) are pruned when the
        # registry outgrows its bound.
        self._first_seen: dict[str, float] = {}
        # Active-active HA hook (scheduler/shards.py): when set, this
        # incarnation enqueues only pods whose namespace shard it holds
        # — the queue feed, the backoff requeue worker, and the
        # cross-shard 409 counter all consult it.  None = own everything
        # (the single-scheduler default).
        self.owns_pod: Optional[Callable[[api.Pod], bool]] = None
        # Multi-tenant solver service (tenancy/service.py), attached by
        # the factory when KT_TENANTS is set (or by a rig): the drain
        # pipeline then packs cross-tenant batches under weighted
        # fairness, routes per-tenant breakers, and the bind path
        # attributes per-tenant SLO metrics.  None = single-owner
        # engine, byte-for-byte the pre-tenancy behavior.
        self.tenancy_service = None
        self._stop = threading.Event()
        # The standing binder: every launch's binds (and the single-pod
        # path's) run on these long-lived workers.  A thread started per
        # launch cost the drain thread a Thread.start() each launch and
        # the bind a fresh keep-alive connection.
        self._bind_pool = ThreadPoolExecutor(
            max_workers=self.BIND_WORKERS, thread_name_prefix="bind-batch")
        self._binds: set = set()
        self._binds_lock = threading.Lock()
        # Single requeue worker over a timer heap (a thread per failed pod
        # would explode on a large unschedulable batch).
        self._requeue_heap: list[tuple[float, int, api.Pod]] = []
        self._requeue_cv = threading.Condition()
        self._requeue_seq = 0
        self._requeue_thread: Optional[threading.Thread] = None
        # THE drain path: every batched drain goes queue -> DrainPipeline
        # (form -> solve -> commit); constructed last so the former can
        # read the daemon's ladder/chunk/cap knobs.
        from kubernetes_tpu.scheduler.pipeline import DrainPipeline
        self.pipeline = DrainPipeline(self)

    @property
    def _commit_pool(self):
        """The overlapped commit worker now lives on the pipeline; kept
        as a read-through so rigs inspecting the daemon keep working."""
        return self.pipeline._commit_pool

    @property
    def accumulate_s(self) -> float:
        """DEPRECATED alias for the batch former's deadline (the old
        arrival-coalescing linger window): reads/writes map onto
        ``pipeline.former.deadline_s`` so pre-serving rig configs keep
        their meaning, but the linger loop itself is gone — the former
        is the only place that decides wait-vs-solve."""
        return self.pipeline.former.deadline_s

    @accumulate_s.setter
    def accumulate_s(self, value: float) -> None:
        self.pipeline.former.deadline_s = max(float(value), 0.0)

    # -- queue feed (the reflector-handler analogue) ---------------------

    def responsible_for(self, pod: api.Pod) -> bool:
        """Multi-scheduler dispatch by annotation (factory.go:428-434)."""
        return pod.scheduler_name == self.config.scheduler_name

    def enqueue(self, pod: api.Pod) -> None:
        if self.owns_pod is not None and not self.owns_pod(pod):
            # Sharded HA: another incarnation holds this namespace's
            # shard lease; its owner schedules it.  Takeover relists
            # (recovery.reconcile_shard) re-deliver anything dropped
            # here if the shard later becomes ours.
            return
        if self.responsible_for(pod) and not pod.node_name:
            # Admission timestamp for the e2e decision-latency SLO
            # (first-seen -> bind ack): the registry keeps the EARLIEST
            # admission per key, so requeues and watch redeliveries
            # (fresh objects) never reset the clock; the object carries
            # a copy for the bind path.
            pod._kt_first_seen = self._first_seen.setdefault(
                pod.key, time.perf_counter())
            if len(self._first_seen) > 65536:
                self._prune_first_seen()
            self.queue.add(pod)

    def _prune_first_seen(self) -> None:
        """Drop registry entries for pods no longer anywhere in flight
        (deleted while pending): keep keys still queued, in backoff, or
        assumed — everything else bound (cleared at ack) or vanished.
        If the registry is STILL over its bound (one tenant flooding
        more live pods than the cap), shed per-namespace-fair — oldest
        first WITHIN the largest namespace groups — so a noisy tenant's
        flood can never evict a quiet tenant's stamps and silently
        reset its SLO clock (the pre-fix pruning was global, exactly
        that failure)."""
        from kubernetes_tpu.scheduler.batchformer import \
            prune_first_seen_fair
        cache = self.config.algorithm.cache
        with self._requeue_cv:
            backoff = {pod.key for _, _, pod in self._requeue_heap}
        # (a copy is walked: the bind threads pop their keys meanwhile,
        # and a dict that changes size under the walk kills this thread
        # — the pending pods' reflector, ISSUE 36's chip run)
        self._first_seen = {
            k: t for k, t in list(self._first_seen.items())
            if k in backoff or k in self.queue or cache.contains(k)}
        if len(self._first_seen) > 65536:
            self._first_seen = prune_first_seen_fair(
                self._first_seen, 65536)

    # -- one-pod path (scheduleOne, scheduler.go:93-154) -----------------

    def schedule_one(self, timeout: Optional[float] = None) -> bool:
        """Pop + schedule + assume + bind one pod; False if queue empty."""
        pod = self.queue.pop(timeout=timeout)
        if pod is None:
            return False
        start = time.perf_counter()
        root = trace_mod.begin_span("schedule_one", pod=pod.key)
        try:
            try:
                dest = self.config.algorithm.schedule(pod)
            except (FitError, ExtenderError) as err:
                if isinstance(err, FitError):
                    # The one-pod preemption path (scheduleOne's
                    # post-priority behavior): an executed victim solve
                    # turns the FitError into a nominated placement.
                    filled = self._preempt_failures([pod], [None], {})
                    if filled[0] is not None:
                        pod.nominated_node = filled[0]
                        self._assume_and_bind(pod, filled[0], start)
                        return True
                # Per-predicate failure counts straight off the FitError
                # (failed_predicates: node -> [names]) for the recorder.
                counts: dict[str, int] = {}
                for preds in getattr(err, "failed_predicates",
                                     {}).values():
                    for name in preds:
                        counts[name] = counts.get(name, 0) + 1
                self._handle_failure(pod, "FailedScheduling", str(err),
                                     failed_predicates=counts or None)
                return True
            algo_us = (time.perf_counter() - start) * 1e6
            self.config.metrics.scheduling_algorithm_latency.observe(algo_us)
            if self.config.flight_recorder is not None:
                self.config.flight_recorder.record_batch(
                    [pod], [dest], trace_id=root.trace_id,
                    duration_s=algo_us / 1e6,
                    tenants=(self.tenancy_service.count_tenants([pod])
                             if self.tenancy_service is not None
                             else None))
            self._assume_and_bind(pod, dest, start)
            return True
        finally:
            root.end()

    # -- batched path (the TPU drain) ------------------------------------

    # Queue sizes past this drain through the chunked device pipeline
    # (assume/bind of chunk k overlaps the device scan of chunk k+1).
    # Off by default (KT_STREAM_CHUNK=0): a drain of _PAD_LIMIT pods or
    # more then compiles a one-shot scan at the exact queue length.  The
    # perf rigs and the soak's daemons set 4096; which default is right
    # on a locally attached chip has not been measured.
    STREAM_THRESHOLD = knobs.get_int("KT_STREAM_CHUNK") or (1 << 62)

    # Drains below this size are routed through the stream path with a
    # power-of-two chunk, whose live-flag padding gives them a fixed
    # compiled shape — a live-arrival workload (queue drained while pods
    # trickle in) then compiles at most log2 distinct batch shapes
    # instead of one per queue length.
    _PAD_LIMIT = 4096

    # Floor on the small-drain bucket: pad rows are numerically inert and,
    # since PR 37, never stepped — the scan's loop stops at its last live
    # row (engine/solver.py run_live_steps), so padding a 3-pod drain to
    # 256 costs the hoisted planes' 256 rows and 4 steps, not 256 (until
    # then a dead row cost a full step: 17-46 us on 5,000 nodes, ~5-9 ms
    # a launch) — while every distinct bucket below the floor costs an
    # XLA compile (seconds).  Measured on the 500-node kubemark rig: the
    # arrival race
    # produces drains of 1..700 pods, and the 1,2,4,...,128 ladder minted
    # ~8 scan compiles (~4-8 s each on a small host) before the fleet
    # settled; with the floor the ladder is {256, 512, 1024, 2048}.
    # The effective value is captured ONCE per daemon in __init__
    # (self.stream_min_bucket): pre-warm traces the bucket ladder this
    # floor defines, and an env change after warmup would otherwise mint
    # unwarmed shapes mid-run.
    STREAM_MIN_BUCKET = 256

    # Workers of the standing binder.  A launch's binds (~6 ms on the
    # served path) rarely overlap the next launch's (~16 ms apart); the
    # spare workers keep a stalled bind (a collector pause, a slow
    # apiserver) from queueing the binds of the launches behind it.
    BIND_WORKERS = 4

    def schedule_pending(self, wait_first: bool = True,
                         timeout: Optional[float] = None) -> int:
        """Drain the queue through the pipeline (form -> solve ->
        commit; scheduler/pipeline.py).  Returns the number of pods
        popped (scheduled or failed).  This is the ONLY batched drain
        entry path — one-shot, streamed, and joint are solve modes the
        pipeline routes internally, not separate control flows."""
        return self.pipeline.drain(wait_first=wait_first, timeout=timeout)

    def _record_batch_decisions(self, pods: list, placements: list,
                                trace_id: str, duration_s: float) -> None:
        """Feed the flight recorder: the placement map always, plus the
        engine's per-predicate failure detail for failed pods not
        explained within the last 30 s (the explain pass costs a small
        device evaluation, paid only when a drain actually failed pods)."""
        recorder = self.config.flight_recorder
        if recorder is None:
            return
        detail = None
        failed = [pod for pod, dest in zip(pods, placements)
                  if dest is None]
        if failed:
            now = time.monotonic()
            fresh = [p for p in failed
                     if now - self._explain_ts.get(p.key, -1e9) > 30.0]
            if fresh:
                try:
                    detail = self.config.algorithm.explain_failures(fresh)
                except Exception:  # noqa: BLE001 — explain is best-effort
                    log.exception("failure-detail pass crashed; recording "
                                  "decisions without predicate counts")
                for p in fresh:
                    self._explain_ts[p.key] = now
                if len(self._explain_ts) > 4096:
                    cutoff = now - 30.0
                    self._explain_ts = {
                        k: t for k, t in self._explain_ts.items()
                        if t > cutoff}
        # (the explain pass above is its own stages: a feature build and
        # ``explain``)
        with stage("decisions", pods=len(pods)):
            recorder.record_batch(
                pods, placements, trace_id=trace_id,
                duration_s=duration_s, failure_detail=detail,
                tenants=(self.tenancy_service.count_tenants(pods)
                         if self.tenancy_service is not None else None))

    def _assume_and_bind_batch(self, pods: list[api.Pod],
                               placements: list, start: float,
                               failure_info: Optional[dict] = None
                               ) -> None:
        """Bulk assume (vectorized), then bind; failures forget + requeue.
        Already-cached pods are skipped, matching the single-pod loop's
        log-and-proceed on assume errors (scheduler.go:116-120).

        ``failure_info`` maps pod key -> (message, result label) for
        failures with a workload-specific story (gang rejections).
        Unschedulable priority pods go through the preemption pass AFTER
        the batch's placements are assumed — the victim solve must see
        this drain's own commitments (else a pod that failed on in-batch
        contention would "preempt" with zero victims onto a node the
        drain just filled, overcommitting it) — and the drain's own
        placements are protected from eviction; an executed decision
        (victims evicted) promotes the pod to placed and it is assumed
        alongside."""
        failure_info = failure_info or {}
        placed = [(pod, dest) for pod, dest in zip(pods, placements)
                  if dest is not None]
        # Sanity-gate backstop (engine/guard.py): a pod whose last solve
        # was gate-rejected and never cleanly re-solved must not bind.
        # Structurally unreachable (the gate raises before placements
        # exist), so the check costs one bool when the rejected set is
        # empty — but a future refactor that swallows DeviceFault would
        # trip the ratcheted scheduler_sanity_rejected_binds_total here
        # instead of binding garbage.
        gd = getattr(self.config.algorithm, "guard", None)
        if gd is not None and gd.enabled and gd.has_rejections():
            placed, refused = gd.filter_rejected(placed)
            for pod, _ in refused:
                self._handle_failure(
                    pod, "SchedulingError",
                    "placement from a sanity-gate-rejected solve refused",
                    result="error")
        cache = self.config.algorithm.cache
        with stage("assume", pods=len(placed)):
            # The commit's wait behind the handlers (and the next
            # launch's compile) for the cache lock, apart from the work.
            t_lock = time.perf_counter()
            with cache.lock:
                trace_mod.record_stage("assume.lock_wait", start=t_lock)
                skipped = set(cache.assume_pods(
                    placed, strict=False,
                    agg_handoff=self.config.algorithm.take_agg_handoff()))
        if skipped:
            placed = [(pod, dest) for pod, dest in placed
                      if pod.key not in skipped]
        filled = self._preempt_failures(
            pods, placements, failure_info,
            protected=frozenset(pod.key for pod, _ in placed))
        newly = [(pod, nd) for pod, nd, od in
                 zip(pods, filled, placements)
                 if od is None and nd is not None]
        if newly:
            with stage("assume", pods=len(newly)):
                skipped2 = set(self.config.algorithm.cache.assume_pods(
                    newly, strict=False))
            placed += [(pod, dest) for pod, dest in newly
                       if pod.key not in skipped2]
            placements = filled
        failed = [pod for pod, dest in zip(pods, placements)
                  if dest is None]
        if failed:
            with stage("failures", pods=len(failed)):
                for pod in failed:
                    msg, result = failure_info.get(
                        pod.key,
                        (f"pod ({pod.name}) failed to fit in any node",
                         "unschedulable"))
                    self._handle_failure(pod, "FailedScheduling", msg,
                                         result=result)
        if self.config.async_bind:
            with stage("bind_spawn"):
                self._submit_bind(self._bind_assumed_batch, placed, start,
                                  trace_mod.current_context())
        else:
            self._bind_assumed_batch(placed, start)

    def _submit_bind(self, fn: Callable, *args) -> None:
        """Hand a bind to the standing binder."""
        try:
            fut = self._bind_pool.submit(fn, *args)
        except RuntimeError:
            # Shut down: stop() and abandon() do not wait for the run
            # loop, so a drain can outlive the binder; it binds here, as
            # a thread of its own would have.
            fn(*args)
            return
        with self._binds_lock:
            self._binds.add(fut)
        fut.add_done_callback(self._bind_done)

    def _bind_done(self, fut) -> None:
        with self._binds_lock:
            self._binds.discard(fut)
        if not fut.cancelled() and fut.exception() is not None:
            # A bind error requeues inside the bind; this is a crash.
            log.error("bind worker crashed", exc_info=fut.exception())

    def _preempt_failures(self, pods: list, placements: list,
                          failure_info: dict,
                          protected: frozenset = frozenset()) -> list:
        """The preemption pass: unschedulable priority pods get a victim
        solve (engine.find_preemptions); executed decisions (victims
        evicted, nominated node recorded) rewrite the placement vector so
        the normal assume/bind path commits them.  ``protected`` keys
        (the caller's just-assumed placements) are never victims.  Gang
        members never preempt individually (a partial gang must not
        evict for a placement the reduction would reject)."""
        from kubernetes_tpu.utils.featuregate import DEFAULT_FEATURE_GATE
        if not DEFAULT_FEATURE_GATE.enabled("Preemption"):
            return placements
        cands = [pod for pod, dest in zip(pods, placements)
                 if dest is None and pod.effective_priority > 0
                 and not pod.gang and pod.key not in failure_info]
        if not cands:
            return placements
        try:
            # (a feature build and ``victims``: stages of their own)
            decisions = self.config.algorithm.find_preemptions(
                cands, protected=protected)
        except Exception:  # noqa: BLE001 — preemption is best-effort
            log.exception("preemption pass crashed; pods requeue with "
                          "backoff instead")
            decisions = []
        with stage("preempt", pods=len(cands)):
            executed = {}
            for dec in decisions:
                if self._execute_preemption(dec):
                    executed[dec.pod_key] = dec
            decided = {d.pod_key for d in decisions}
            for pod in cands:
                if pod.key not in decided:
                    metrics_mod.PREEMPTIONS.labels(
                        result="no_candidate").inc()
        if not executed:
            return placements
        out = []
        for pod, dest in zip(pods, placements):
            dec = executed.get(pod.key) if dest is None else None
            if dec is not None:
                pod.nominated_node = dec.node
                out.append(dec.node)
            else:
                out.append(dest)
        return out

    def _execute_preemption(self, dec) -> bool:
        """Evict a decision's victims (cache + binder) so the preemptor
        can assume and bind — the evict->assume->bind path.  Returns
        False (pod stays unschedulable, requeues with backoff) if any
        eviction fails."""
        cache = self.config.algorithm.cache
        evict = getattr(self.config.binder, "evict", None)
        try:
            for vkey in dec.victims:
                vpod = cache.get_pod(vkey)
                if vpod is not None:
                    cache.remove_pod(vpod)
                else:
                    ns, _, name = vkey.partition("/")
                    vpod = api.Pod(name=name or ns,
                                   namespace=ns if name else "default")
                if evict is not None:
                    evict(vpod)
                self.config.recorder.eventf(
                    vkey, "Normal", "Preempted",
                    f"Preempted by {dec.pod_key} (priority) "
                    f"on node {dec.node}")
        except Exception:  # noqa: BLE001 — a failed eviction aborts
            log.exception("preemption eviction failed for %s on %s",
                          dec.pod_key, dec.node)
            metrics_mod.PREEMPTIONS.labels(result="error").inc()
            return False
        metrics_mod.PREEMPTIONS.labels(result="executed").inc()
        metrics_mod.PREEMPTION_VICTIMS.inc(len(dec.victims))
        if self.config.flight_recorder is not None:
            self.config.flight_recorder.record_preemption(
                dec.pod_key, dec.node, dec.victims)
        log.info("preempted %d pod(s) on %s for %s",
                 len(dec.victims), dec.node, dec.pod_key)
        return True

    # Fixed stream chunk override (else derived from STREAM_THRESHOLD).
    stream_chunk: int = 0

    def stream_chunk_size(self) -> int:
        """Chunk size the streamed drain compiles at (harness warmup must
        pre-trace the same shape)."""
        return self.stream_chunk or min(self.STREAM_THRESHOLD, 8192)

    def degraded_drain_cap(self) -> int:
        """Pods per drain while shedding load: the largest bucket the
        pre-warm traced (a degraded drain must never mint a fresh XLA
        compile — the storm is exactly when compile stalls hurt most),
        falling back to the one-shot pad limit when streaming is off."""
        ladder = self.effective_ladder()
        return max(ladder) if ladder else self._PAD_LIMIT

    def effective_ladder(self) -> list[int]:
        """The fixed set of chunk sizes this daemon's drains can compile
        at — pre-warm traces exactly this set; the drain paths can mint
        no other.  Two sources: the stream chunk, included only when the
        chunked path is reachable (STREAM_THRESHOLD set — at its unset
        sentinel every large drain takes the one-shot schedule_batch
        path instead, whose shape follows the live queue length and
        cannot be pre-traced); and the small-drain buckets, reachable
        for drains below min(STREAM_THRESHOLD, _PAD_LIMIT): the
        startup-captured floor itself (possibly non-pow2 — every drain
        at or below it pads to it) plus each pow2 ABOVE the floor up to
        the pow2 ceiling of the largest such drain (4096 included: a
        2049-4095-pod drain legally mints it even when the stream chunk
        is smaller).  The computation itself is the module-level
        ``bucket_ladder`` so the kt-xray manifest shares it."""
        return bucket_ladder(self.stream_min_bucket, self.STREAM_THRESHOLD,
                             self._PAD_LIMIT, self.stream_chunk)

    def prewarm_plan(self) -> list[str]:
        """The program keys ``prewarm()`` will trace for THIS daemon's
        configuration — static introspection, no device, no compile.
        Mirrors ``prewarm()``'s own no-op conditions (StreamingDrain
        gate off, extenders configured, empty cluster -> []), so the
        report is honest exactly where the watchdog matters.  kt-xray
        compares the canonical-config instantiation against the
        committed shape manifest (rule X04); this instance method is
        the live-daemon view (tests pin it against the manifest for
        the default config)."""
        from kubernetes_tpu.engine.solver import ResidentCluster
        from kubernetes_tpu.utils.featuregate import DEFAULT_FEATURE_GATE
        alg = self.config.algorithm
        if not DEFAULT_FEATURE_GATE.enabled("StreamingDrain") or \
                alg.extenders or not alg.cache.node_count():
            return []
        ladder = self.effective_ladder()
        return prewarm_plan(
            ladder,
            ResidentCluster.scatter_buckets(alg.cache.snapshot()[0].n),
            joint=DEFAULT_FEATURE_GATE.enabled("JointSolver"),
            preempt=DEFAULT_FEATURE_GATE.enabled("Preemption"))

    def prewarm(self, sample_pods: Optional[list] = None) -> dict:
        """Trace the full bucket ladder before the queue opens, so no
        live drain ever pays an XLA compile on the clock.  With the
        persistent compilation cache populated (engine/compile_cache) the
        traces deserialize in well under a second each; cold, the cost is
        paid here once per machine instead of on the first N drains.

        ``sample_pods`` shapes the traced programs (vocab capacities +
        content flags) like the expected workload; without it a minimal
        synthetic pod is used.  Each bucket warms BOTH full-chunk jit
        signatures (first chunk carries no state dict, later chunks do).
        Returns {bucket: seconds}; no-ops when streaming is off, an
        extender is configured, or the cluster is empty."""
        from kubernetes_tpu.engine import devicestats
        from kubernetes_tpu.utils.featuregate import DEFAULT_FEATURE_GATE
        alg = self.config.algorithm
        if not DEFAULT_FEATURE_GATE.enabled("StreamingDrain") or \
                alg.extenders or not alg.cache.node_count():
            return {}
        # Prewarm compiles are never "post-prewarm": disarm for the
        # duration so a fresh rig warming up in an already-armed process
        # (the serving bench builds three in a row) doesn't count its
        # own ladder traces as live-path stalls.  Chaos injection is
        # suppressed the same way: the ladder traces run the live solve
        # sites, but there is no recovery ladder above prewarm — a
        # KT_CHAOS_DEVICE cadence firing here would fail startup
        # instead of exercising recovery (guard.suppressed re-enables
        # on exit even if a trace raises).
        devicestats.disarm()
        import contextlib as _contextlib
        _suppress = alg.guard.suppressed() if alg.guard.enabled \
            else _contextlib.nullcontext()
        with _suppress:
            ladder = self.effective_ladder()
            timings: dict[int, float] = {}
            # Warm-start audit: per-bucket persistent-compile-cache traffic.
            # A bucket whose trace shows misses on a supposedly-warm start is
            # a signature dodging the cache — exactly the 3-4 s "warm" tail
            # ROADMAP item 3 chases.  (The counters ride JAX monitoring
            # events, engine/compile_cache; zero/zero means the executable
            # was already live in process memory.)
            cache_stats: dict = {}

            def audited(key, fn):
                h0 = metrics_mod.COMPILE_CACHE_HITS.value
                m0 = metrics_mod.COMPILE_CACHE_MISSES.value
                t0 = time.perf_counter()
                fn()
                dt = time.perf_counter() - t0
                cache_stats[key] = {
                    "hits": metrics_mod.COMPILE_CACHE_HITS.value - h0,
                    "misses": metrics_mod.COMPILE_CACHE_MISSES.value - m0,
                    "seconds": round(dt, 3)}
                return dt

            # LARGEST bucket first: the monotonic content-axis caps
            # (padcap — spread groups, nz templates, …) grow while the
            # ladder traces, and ascending order would trace the small
            # buckets at a stale cap that the big bucket's richer sample
            # batch then outgrows — minting unwarmed (bucket, final-cap)
            # shapes for every small live drain (measured as two
            # post-prewarm compiles on the wire rig).  Descending order
            # reaches the cap fixed point on the first trace.
            for bucket in sorted(ladder, reverse=True):
                want = 2 * bucket  # both scan signatures (no-carry + carry)
                if sample_pods:
                    pods = list(sample_pods[:want])
                else:
                    pods = []
                pods += [api.Pod(name=f"__warm-{i}", namespace="__warm__")
                         for i in range(want - len(pods))]

                def run_bucket(pods=pods, bucket=bucket):
                    for _ in alg.schedule_batch_stream(pods,
                                                       chunk_size=bucket):
                        pass

                timings[bucket] = audited(bucket, run_bucket)
            # The single-pod decision path (schedule_one / the recovery
            # parity probes): evaluate/masks/select_hosts at P=1 are NOT the
            # scan's signatures, so without this trace the first interactive
            # decision after every start paid ~30 compiles on the clock —
            # a measured 0.3-0.7 s warm-start tail the ladder never covered.
            def run_single():
                try:
                    alg.schedule(api.Pod(name="__warm-one",
                                         namespace="__warm__"))
                except FitError:  # a full fleet still traced the path
                    pass

            audited("single_pod", run_single)
            # The failure-detail pass of a drain that left a pod unplaced
            # (masks + evaluate at EXPLAIN_CAP pods, the sample's flags).
            if self.config.flight_recorder is not None:
                audited("explain", lambda: alg.explain_failures(
                    list(sample_pods[:1]) if sample_pods else
                    [api.Pod(name="__warm-explain", namespace="__warm__")]))
            # The dirty-row scatter kernel compiles per pow2 dirty-row count;
            # untraced, the first drain after any assume paid it mid-drain.
            audited("scatter", lambda: alg.resident.prewarm_scatter())
            # Workload-subsystem signatures warm separately (string-keyed on
            # the daemon, not in the int-keyed bucket dict callers inspect).
            self.workloads_prewarm_s = self._prewarm_workloads(ladder)
            self.prewarm_cache_stats = cache_stats
        # Recompile watchdog: from here on, ANY XLA compile on a live
        # path is a stall the ladder should have traced — counted in
        # scheduler_post_prewarm_compiles_total{path=}, recorded as a
        # post_prewarm_compile span, and failed by the bench ratchet.
        devicestats.arm()
        log.info("pre-warmed stream ladder %s (floor %d, chunk %d): %s "
                 "workloads=%s cache=%s",
                 ladder, self.stream_min_bucket, self.stream_chunk_size(),
                 {b: f"{s:.2f}s" for b, s in timings.items()},
                 {k: f"{s:.2f}s"
                  for k, s in self.workloads_prewarm_s.items()},
                 cache_stats)
        return timings

    def _prewarm_workloads(self, ladder: list[int]) -> dict:
        """Trace the workloads-subsystem solve signatures (ISSUE 6
        satellite): the preemption victim kernel at the cluster's (N, V)
        shape, the topology plane kernel + masked scan at the floor
        bucket, and (when the gate is on) the one-shot joint executable —
        all of which a live drain would otherwise compile on the clock.
        Gang one-shot solves reuse the stream ladder's scan signatures
        (same live-masked _solve_scan), so they need no extra trace."""
        import json as _json

        from kubernetes_tpu.utils.featuregate import DEFAULT_FEATURE_GATE
        alg = self.config.algorithm
        timings: dict = {}
        floor = min(ladder) if ladder else 0
        if DEFAULT_FEATURE_GATE.enabled("Preemption"):
            from kubernetes_tpu.engine.workloads import preemption
            t0 = time.perf_counter()
            preemption.prewarm_shapes(alg.cache.snapshot()[0].n)
            timings["preempt"] = time.perf_counter() - t0
        if floor:
            tsc = _json.dumps([{
                "maxSkew": 1, "topologyKey": api.ZONE_LABEL,
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": {"kt/warm": "1"}}}])
            spods = [api.Pod(
                name=f"__warm-topo-{i}", namespace="__warm__",
                labels={"kt/warm": "1"},
                annotations={api.TOPOLOGY_SPREAD_ANNOTATION_KEY: tsc})
                for i in range(min(floor, 4))]
            t0 = time.perf_counter()
            alg.schedule_batch(spods, pad_to=floor)
            timings["topology"] = time.perf_counter() - t0
            if DEFAULT_FEATURE_GATE.enabled("JointSolver"):
                jpods = [api.Pod(name=f"__warm-joint-{i}",
                                 namespace="__warm__")
                         for i in range(min(floor, 4))]
                t0 = time.perf_counter()
                alg.schedule_batch(jpods, joint=True, pad_to=floor)
                timings["joint"] = time.perf_counter() - t0
        return timings

    # -- run loops --------------------------------------------------------

    def run(self, batched: bool = True) -> threading.Thread:
        """wait.Until(scheduleOne, 0, stop) (scheduler.go:89-91), in a
        daemon thread; batched mode drains the queue per iteration.  A
        crashing iteration is logged and the loop continues — the
        reference's runtime.HandleCrash keeps its daemons alive the same
        way; without this, one bad drain kills scheduling forever."""
        def loop():
            while not self._stop.is_set():
                try:
                    if batched:
                        self.schedule_pending(timeout=0.05)
                    else:
                        self.schedule_one(timeout=0.05)
                except Exception:  # noqa: BLE001 — HandleCrash analogue
                    log.exception("scheduling iteration crashed; "
                                  "continuing")
                    time.sleep(0.5)
        return threadreg.spawn(loop, name="scheduler-loop")

    def stop(self) -> None:
        self._stop.set()
        self.queue.close()
        self.pipeline.shutdown(wait=True)
        self._bind_pool.shutdown(wait=True)
        # Graceful shutdown persists the decision ring (KT_FLIGHT_DIR) so
        # `kubectl explain pod` keeps answering across a scheduler bounce.
        recorder = self.config.flight_recorder
        flight_dir = knobs.get("KT_FLIGHT_DIR")
        if recorder is not None and flight_dir:
            try:
                recorder.save(flight_dir)
            except OSError:
                log.exception("flight-recorder dump to %s failed",
                              flight_dir)

    def abandon(self) -> None:
        """SIGKILL-style stop: no graceful drain, no joins, no flight
        dump — the in-flight pipeline window (solved-but-uncommitted
        chunks, dispatched binds) is simply abandoned, exactly what a
        kill between solve and bind leaves behind.  Safety then rests on
        the apiserver's bind CAS (an abandoned bind that still lands
        cannot be double-applied) and the next incarnation's startup
        reconciliation (scheduler/recovery.py), which requeues anything
        left unbound and adopts anything that did land."""
        self._stop.set()
        self.queue.close()
        self.pipeline.shutdown(cancel=True)
        self._bind_pool.shutdown(wait=False, cancel_futures=True)

    def wait_for_binds(self) -> None:
        """Return once every bind handed to the binder so far landed."""
        with self._binds_lock:
            pending = list(self._binds)
        wait(pending)

    # -- internals --------------------------------------------------------

    def _record_bind_failure(self, err) -> str:
        """The module-level classifier plus the HA plane's cross-shard
        accounting: a CAS conflict observed while running sharded means
        another incarnation (or a chaos rule) bound the pod first —
        near-zero in steady state, bursty during lease handoffs."""
        result = _record_bind_failure(err)
        if result == "bind_conflict" and self.owns_pod is not None:
            metrics_mod.CROSS_SHARD_CONFLICTS.inc()
        return result

    def _forget_quietly(self, pod: api.Pod) -> None:
        """Forget a failed bind's optimistic assume; tolerates the pod
        being gone already — a shard handoff (factory._on_shard_lost
        forgets the lost shard's assumes wholesale) can race the bind
        fan-out, and the loser of that race must requeue-or-drop, not
        die on a ValueError in the bind thread."""
        try:
            self.config.algorithm.cache.forget_pod(pod)
        except ValueError:
            pass

    def _assume_and_bind(self, pod: api.Pod, dest: str, start: float) -> None:
        cache = self.config.algorithm.cache
        # Optimistic assume before the async bind; an assume error is logged
        # and binding proceeds anyway (scheduler.go:116-120).
        assumed = True
        try:
            with stage("assume", pods=1):
                cache.assume_pod(pod, dest)
        except ValueError:
            assumed = False
        ctx = trace_mod.current_context()

        def bind():
            with trace_mod.use_context(ctx):
                self._bind_assumed(pod, dest, start, assumed=assumed)

        if self.config.async_bind:
            self._submit_bind(bind)
        else:
            bind()

    def _bind_assumed(self, pod: api.Pod, dest: str, start: float,
                      assumed: bool = True) -> None:
        bind_start = time.perf_counter()
        try:
            with stage("bind", pods=1):
                self.config.binder.bind(pod, dest)
        except Exception as err:  # noqa: BLE001 — bind errors requeue
            # ForgetPod + error handler (scheduler.go:139-148).  409 and
            # timeout alike: forget the optimistic assume, emit the event,
            # requeue behind per-pod backoff — never silently drop.
            result = self._record_bind_failure(err)
            if assumed:
                self._forget_quietly(pod)
            self._handle_failure(pod, "FailedScheduling",
                                 f"Binding rejected: {err}",
                                 result=result)
            return
        now = time.perf_counter()
        self.config.metrics.binding_latency.observe(
            (now - bind_start) * 1e6)
        self.config.metrics.e2e_scheduling_latency.observe(
            (now - start) * 1e6)
        seen = first_seen(pod)
        if seen is not None:
            metrics_mod.E2E_DECISION_LATENCY.observe(
                (now - seen) * 1e6,
                exemplar=trace_mod.current_trace_id())
        if self.tenancy_service is not None:
            self.tenancy_service.record_bound(
                pod, (now - seen) if seen is not None else None)
        self._first_seen.pop(pod.key, None)
        self.config.metrics.scheduling_attempts.labels(
            result="scheduled").inc()
        self.config.recorder.eventf(
            pod.key, "Normal", "Scheduled",
            f"Successfully assigned {pod.name} to {dest}")

    def _bind_assumed_batch(self, placed: list[tuple[api.Pod, str]],
                            start: float, trace_ctx=None) -> None:
        """Bind a solved batch: per-pod CAS binds (conflicts forget +
        requeue exactly like _bind_assumed), with the per-pod metric
        observations amortized into one bucket pass each.  ``trace_ctx``
        carries the batch's span context into the async bind thread so the
        fan-out (and its HTTP requests) stays on the batch's trace."""
        if trace_ctx is None:  # sync call: stay on the caller's context
            trace_ctx = trace_mod.current_context()
        with trace_mod.use_context(trace_ctx), \
                stage("bind", pods=len(placed)):
            self._bind_assumed_batch_inner(placed, start)

    def _bind_assumed_batch_inner(self, placed: list[tuple[api.Pod, str]],
                                  start: float) -> None:
        recorder = self.config.recorder
        bind_start = time.perf_counter()
        bind_many = getattr(self.config.binder, "bind_many", None)
        bound_pods: list[api.Pod] = []
        if bind_many is not None:
            failed = {pod.key: err for pod, err in bind_many(placed)}
            ok = 0
            items = []
            for pod, dest in placed:
                if pod.key in failed:
                    result = self._record_bind_failure(failed[pod.key])
                    self._forget_quietly(pod)
                    # Surface the real error: a CAS conflict and a
                    # network failure require different operator action.
                    self._handle_failure(
                        pod, "FailedScheduling",
                        f"Binding rejected: {failed[pod.key]}",
                        result=result)
                else:
                    ok += 1
                    bound_pods.append(pod)
                    items.append((pod.key, "Normal", "Scheduled",
                                  f"Successfully assigned {pod.name} to {dest}"))
            recorder.eventf_many(items)
        else:
            ok = 0
            for pod, dest in placed:
                try:
                    self.config.binder.bind(pod, dest)
                except Exception as err:  # noqa: BLE001 — bind errors requeue
                    result = self._record_bind_failure(err)
                    self._forget_quietly(pod)
                    self._handle_failure(pod, "FailedScheduling",
                                         f"Binding rejected: {err}",
                                         result=result)
                    continue
                ok += 1
                bound_pods.append(pod)
                recorder.eventf(
                    pod.key, "Normal", "Scheduled",
                    f"Successfully assigned {pod.name} to {dest}")
        done = time.perf_counter()
        self.config.metrics.binding_latency.observe_many(
            (done - bind_start) * 1e6 / max(len(placed), 1), ok)
        self.config.metrics.e2e_scheduling_latency.observe_many(
            (done - start) * 1e6, ok)
        # The serving SLO number: per-pod first-seen -> bind ack (NOT
        # amortized — every pod carries its own admission stamp, so the
        # histogram captures the real tail the deadline trades against).
        # The batch's trace id rides along as the bucket exemplar: a bad
        # p99 bucket then names the exact trace to pull from the ring.
        tid = trace_mod.current_trace_id()
        svc = self.tenancy_service
        for pod in bound_pods:
            seen = first_seen(pod)
            if seen is not None:
                metrics_mod.E2E_DECISION_LATENCY.observe(
                    (done - seen) * 1e6, exemplar=tid)
            if svc is not None:
                svc.record_bound(
                    pod, (done - seen) if seen is not None else None)
            self._first_seen.pop(pod.key, None)
        if ok:
            self.config.metrics.scheduling_attempts.labels(
                result="scheduled").inc(ok)

    def _handle_failure(self, pod: api.Pod, reason: str, message: str,
                        result: str = "unschedulable",
                        failed_predicates: Optional[dict] = None) -> None:
        """Event + condition update + backoff requeue (factory.go:512-556).
        Every failure class funnels through here, so this is also where
        the attempts counter and the flight recorder see it."""
        log.debug("scheduling failure for %s: %s", pod.key, message)
        self.config.metrics.scheduling_attempts.labels(result=result).inc()
        if self.config.flight_recorder is not None:
            self.config.flight_recorder.record_failure(
                pod.key, reason, message,
                failed_predicates=failed_predicates)
        self.config.recorder.eventf(pod.key, "Warning", reason, message)
        if self.config.condition_updater is not None \
                and result != "bind_conflict":
            # (a 409 says the pod IS assigned: PodScheduled=False on it
            # would be false, and a write to a bound pod besides)
            self.config.condition_updater(pod, "Unschedulable", message)
        backoff_s = self.backoff.get_backoff(pod.key)
        with self._requeue_cv:
            self._requeue_seq += 1
            heapq.heappush(self._requeue_heap,
                           (time.monotonic() + backoff_s,
                            self._requeue_seq, pod))
            if self._requeue_thread is None or \
                    not self._requeue_thread.is_alive():
                self._requeue_thread = threadreg.spawn(
                    self._requeue_worker, name="backoff-requeue")
            self._requeue_cv.notify()

    def _requeue_worker(self) -> None:
        while not self._stop.is_set():
            with self._requeue_cv:
                while not self._requeue_heap and not self._stop.is_set():
                    self._requeue_cv.wait(timeout=0.5)
                if self._stop.is_set():
                    return
                due, _, pod = self._requeue_heap[0]
                delay = due - time.monotonic()
                if delay > 0:
                    self._requeue_cv.wait(timeout=min(delay, 0.5))
                    continue
                heapq.heappop(self._requeue_heap)
            still_pending = self.config.still_pending
            if still_pending is not None and not still_pending(pod):
                # Bound or deleted while it sat in backoff: the condition
                # update of a failed attempt comes back on the unassigned
                # watch and requeues the pod at once, so this entry can
                # outlive the pod's bind; scheduling it again would end
                # in a 409, or a 404 once the pod is gone.
                self._first_seen.pop(pod.key, None)
                continue
            pod.node_name = ""
            if self.owns_pod is not None and not self.owns_pod(pod):
                # The shard moved while this pod sat in backoff: its new
                # owner schedules it (the takeover relist already
                # requeued it there); re-adding here would race two
                # incarnations on one pod as the steady state.
                self._first_seen.pop(pod.key, None)
                continue
            self.queue.add(pod)
