"""The drain pipeline: ONE form -> solve -> commit path for every drain.

Before this module the daemon carried three separately-instrumented
drain control flows (one-shot ``schedule_batch``, the overlapped
streamed drain, and the joint solve) plus an ad-hoc arrival-coalescing
linger, each with its own stage spans and crash handling.
``DrainPipeline`` unifies them: the daemon's ``schedule_pending`` is now
a single call into ``drain()``, and everything between the queue and the
assume/bind commit — batch formation policy (scheduler/batchformer.py),
the degraded-mode cap, mode routing (gang / joint / streamed /
one-shot), the overlapped solve/commit worker, the batch root span and
stage instrumentation, and the crash-requeue handler — lives behind this
one interface.  Batch-formation policy is therefore pluggable (swap the
former) and instrumented once.

The three modes that remain are SOLVE strategies, not control flows:

* ``stream``  — fixed-shape chunks through ``schedule_batch_stream``.
  A drain of one chunk commits on the drain thread; a drain of two or
  more has the commit worker overlap chunk N's device scan against
  chunk N-1's readback/assume/bind (``pipeline_window`` in flight).
* ``oneshot`` — one ``schedule_batch`` solve; gang batches take this
  path padded to a warm bucket (all-or-nothing needs one assignment
  vector), as do extender-constrained and above-pad-limit drains.
* ``joint``   — ``schedule_batch(joint=True)``: prices couple every pod,
  so the whole queue solves at once.

Commit-side semantics (assume-before-bind per pod, flight-recorder
feeds, preemption, failure requeue) stay on the daemon — the pipeline
calls back into it, so the state machine the rest of the repo pins is
byte-for-byte the old one.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from kubernetes_tpu.engine import guard as guard_mod
from kubernetes_tpu.engine.guard import DeviceFault
from kubernetes_tpu.scheduler.batchformer import (BatchFormer, FormedBatch,
                                                  first_seen)
from kubernetes_tpu.utils import metrics as metrics_mod
from kubernetes_tpu.utils import trace as trace_mod
from kubernetes_tpu.utils.logging import get_logger

log = get_logger("pipeline")


def _count_pod_waits(pods: list) -> None:
    """One pass over a formed batch at its hand-off to the solve: how
    long each pod has waited for a launch since it was first seen."""
    now = time.perf_counter()
    total = longest = 0.0
    counted = 0
    for pod in pods:
        seen = first_seen(pod)
        if seen is not None:
            waited = now - seen
            total += waited
            counted += 1
            if waited > longest:
                longest = waited
    if counted:
        metrics_mod.POD_QUEUE_WAIT_SECONDS.inc(total)
        metrics_mod.POD_QUEUE_WAIT_PODS.inc(counted)
        if longest > metrics_mod.POD_QUEUE_WAIT_MAX.value:
            metrics_mod.POD_QUEUE_WAIT_MAX.set(longest)


class DrainPipeline:
    """One drain: form a batch, route it to a solve mode, commit it.

    ``daemon`` is the owning ``scheduler.Scheduler``; the pipeline reads
    its routing knobs (``STREAM_THRESHOLD``, ``stream_chunk``,
    ``pipeline_window``...) live so tests and rigs that retune the
    daemon keep working unchanged."""

    def __init__(self, daemon):
        self.daemon = daemon
        self.former = BatchFormer(
            queue=daemon.queue,
            ladder_fn=daemon.effective_ladder,
            chunk_fn=daemon.stream_chunk_size,
            cap_fn=self._former_cap)
        # The overlapped commit worker (one thread: chunks commit in
        # solve order); created lazily on the first drain of two or
        # more chunks.
        self._commit_pool = None
        # The device guard bisects OOM'd batches down the daemon's
        # pre-warmed bucket ladder — it must read the SAME ladder the
        # prewarm traces, or recovery would mint unwarmed shapes.
        guard = getattr(daemon.config.algorithm, "guard", None)
        if guard is not None:
            guard.ladder_fn = daemon.effective_ladder

    def _former_cap(self) -> int:
        """The degraded drain cap.  With tenancy on the former over-pops
        (4x the solve cap) so the cross-tenant packer sees past the
        FIFO head — a flood tenant's pods dominate the queue front, and
        fair selection needs candidates from the quiet tenants behind
        them; the packer then caps the SOLVE back to one warm bucket
        and defers the rest."""
        cap = self.daemon.degraded_drain_cap()
        if getattr(self.daemon, "tenancy_service", None) is not None:
            return cap * 4
        return cap

    # -- the single drain entry path -------------------------------------

    def drain(self, wait_first: bool = True,
              timeout: Optional[float] = None) -> int:
        """Form one batch and solve+commit it.  Returns the number of
        pods popped (scheduled or failed) — the daemon's
        ``schedule_pending`` contract."""
        daemon = self.daemon
        # The queue_wait span is backdated below (the batch exists only
        # at the wait's end); in a profiler's trace the wait is here.
        with trace_mod.annotation("queue_wait"):
            batch = self.former.form(wait_first=wait_first,
                                     timeout=timeout)
        pods = batch.pods
        if not pods:
            return 0
        svc = getattr(daemon, "tenancy_service", None)
        if svc is not None:
            # Cross-tenant packing: bound every solve at one warm
            # ladder bucket and fill it urgency-first then by weighted
            # share (tenancy/packer.py); the remainder returns to the
            # queue with its SLO stamps intact.  Degradation still
            # wins: the former already shed to a bounded pop above.
            selected, deferred = svc.packer.pack(
                pods, daemon.degraded_drain_cap())
            for pod in deferred:
                daemon.queue.add(pod)
            pods = batch.pods = selected
        # The batch root span is backdated to cover the wait: queue_wait
        # (blocking pop + deadline batch formation) is the pipeline's
        # first stage, even though the batch only existed at its end.
        root = trace_mod.begin_span("schedule_batch", start=batch.t_wait,
                                    pods=len(pods))
        trace_mod.record_stage("queue_wait", start=batch.t_wait,
                               pods=len(pods))
        daemon.config.metrics.batch_size.set(len(pods))
        _count_pod_waits(pods)
        try:
            # One launch is one host interval of a profiler's trace (the
            # root span is backdated, so it cannot be one itself).
            with trace_mod.annotation("launch", pods=len(pods)):
                return self._solve(batch, trace_id=root.trace_id)
        except Exception:  # noqa: BLE001 — HandleCrash analogue
            # The pods were already popped: requeue each through the
            # backoff path (condition + event + delayed retry) so a
            # crashing drain can't silently strand them Pending, and a
            # poison pod retries at most once per 60 s.  A daemon that
            # was stopped/abandoned mid-drain does NOT requeue: the pods
            # belong to the next incarnation (its startup reconciliation
            # relists them), and a dead daemon writing conditions or
            # requeue events would race the replacement's binds.
            if daemon._stop.is_set():
                log.info("drain interrupted by shutdown; %d pods left "
                         "to the next incarnation", len(pods))
                return len(pods)
            log.exception("drain of %d pods crashed; requeueing",
                          len(pods))
            cache = daemon.config.algorithm.cache
            for pod in pods:
                # Skip pods the crash didn't strand: anything tracked in
                # the cache (assumed by a completed chunk, or already
                # confirmed bound by the watch) made it through.
                if not cache.contains(pod.key):
                    daemon._handle_failure(
                        pod, "SchedulingError",
                        "internal error during scheduling",
                        result="error")
            return len(pods)
        finally:
            root.end()
            # The whole the stages are parts of: the root's duration.
            trace_mod.observe_stage(
                "launch_total",
                (time.perf_counter() - batch.t_wait) * 1e6,
                root.trace_id or None)

    # -- mode routing + the device-fault recovery ladder -------------------

    def _solve(self, batch: FormedBatch, trace_id: str = "") -> int:
        """Route the batch to a solve mode under the device guard's
        recovery ladder: a classified ``DeviceFault`` re-dispatches the
        still-uncommitted pods per the guard's decision — unchanged
        (retry), chunked at the next smaller pre-warmed bucket (bisect),
        or on the host fallback engine (breaker open) — for at most
        ``max_rounds`` rounds; exhaustion surfaces to ``drain()``'s
        crash handler, which requeues rather than drops.  Chunks that
        committed before the fault stay committed (the cache knows
        them), so progress is monotone across rounds."""
        daemon = self.daemon
        pods = batch.pods
        if getattr(daemon, "tenancy_service", None) is not None:
            return self._solve_tenants(pods, trace_id)
        guard = getattr(daemon.config.algorithm, "guard", None)
        if guard is None or not guard.enabled:
            return self._dispatch(pods, trace_id)
        total = len(pods)
        remaining = pods
        fault: Optional[DeviceFault] = None
        for _ in range(max(guard.max_rounds, 1)):
            mode = guard.solve_mode()
            try:
                if mode == "host":
                    self._dispatch(remaining, trace_id, host=True)
                else:
                    self._dispatch(remaining, trace_id)
                    guard.note_success(probe=(mode == "probe"))
                return total
            except DeviceFault as f:
                fault = f
                remaining = self._uncommitted(remaining)
                if not remaining:
                    return total
                action = guard.recover(
                    f, can_bisect=self._can_bisect(remaining))
                log.warning("device fault [%s] on %s path: %d pod(s) "
                            "re-dispatched via %s", f.kind, f.path,
                            len(remaining), action)
        raise fault  # ladder exhausted: crash handler requeues

    def _uncommitted(self, pods: list) -> list:
        """The stranded remainder of a faulted dispatch: pods a
        completed chunk already assumed (or the watch confirmed) are in
        the cache, and pods a completed chunk already FAILED are in the
        backoff heap / back on the queue — re-solving those would
        schedule the same pod twice (once here, once when its requeue
        pops)."""
        daemon = self.daemon
        cache = daemon.config.algorithm.cache
        with daemon._requeue_cv:
            handled = {p.key for _, _, p in daemon._requeue_heap}
        return [p for p in pods
                if not cache.contains(p.key)
                and p.key not in handled
                and p.key not in daemon.queue]

    def _solve_tenants(self, pods: list, trace_id: str) -> int:
        """The multi-tenant solve path: per-tenant breaker routing,
        mixed-batch fault ATTRIBUTION by per-tenant split, and
        per-tenant accounting — one tenant's poison batch degrades that
        tenant to the host engine; the service and the other tenants
        stay on device.

        A ``lost`` fault still escalates the GLOBAL guard (a dead chip
        is not one tenant's fault) and OOM still runs the global
        eviction/bisect-cap ladder; the per-tenant breaker owns the
        ATTRIBUTABLE kinds (a tenant's poison readbacks, its repeated
        OOM-sized batches) — it trips at KT_TENANT_BREAKER consecutive
        faults, before the global breaker's threshold can."""
        daemon = self.daemon
        svc = daemon.tenancy_service
        guard = getattr(daemon.config.algorithm, "guard", None)
        guard_on = guard is not None and guard.enabled
        total = len(pods)
        gmode = guard.solve_mode() if guard_on else "device"
        if gmode == "host":
            # Whole-device outage (global breaker open, no probe due):
            # every tenant decides on the host engine this drain.
            self._dispatch(pods, trace_id, host=True)
            return total
        device_pods, host_pods, probing = svc.partition(pods)
        if host_pods:
            self._dispatch(host_pods, trace_id, host=True)
            for t, n in svc.count_tenants(host_pods).items():
                svc.note_host_fallback(t, n)
        if device_pods:
            # One solver at a time on the shared engine: the service's
            # packed submits (remote control planes) and this drain
            # must not race GenericScheduler's solve state.
            with svc.engine_lock:
                self._solve_tenant_groups(
                    device_pods, probing, gmode, trace_id)
        return total

    def _solve_tenant_groups(self, device_pods: list, probing: set,
                             gmode: str, trace_id: str) -> None:
        """The device section of a tenant drain (caller holds the
        service's engine lock): dispatch, attribution splits, and the
        per-tenant breaker routing."""
        from collections import deque

        from kubernetes_tpu.chaos import device as chaos_device
        from kubernetes_tpu.engine import devicestats
        from kubernetes_tpu.engine.guard import ACT_HOST, KIND_LOST, KIND_OOM
        daemon = self.daemon
        svc = daemon.tenancy_service
        guard = getattr(daemon.config.algorithm, "guard", None)
        guard_on = guard is not None and guard.enabled
        # Transfer attribution covers the DEVICE section only — a
        # host-degraded tenant must not be billed for device bytes it
        # never moved.
        transfers0 = sum(devicestats.transfer_snapshot().values())
        groups = deque([device_pods])
        rounds = 0
        budget = (guard.max_rounds if guard_on else 1) + \
            len(svc.tenants) + 2
        while groups:
            group = groups.popleft()
            tenants_g = svc.tenants_of(group)
            try:
                with chaos_device.tenant_context(tenants_g):
                    self._dispatch(group, trace_id)
                if guard_on:
                    guard.note_success(probe=(gmode == "probe"))
                for t in tenants_g:
                    svc.note_success(t, probe=(t in probing))
            except DeviceFault as f:
                rounds += 1
                remaining = self._uncommitted(group)
                if not remaining:
                    continue
                if rounds > budget:
                    raise  # crash handler requeues — never drops
                tenants_r = svc.tenants_of(remaining)
                if len(tenants_r) > 1:
                    # Attribution bisection: split per tenant and
                    # re-solve each alone — the culprit's solo batch
                    # keeps faulting and trips ITS breaker.
                    svc.note_split(f)
                    groups.extend(svc.split_by_tenant(remaining))
                    log.warning("device fault [%s] on a %d-tenant "
                                "batch: split per tenant for "
                                "attribution", f.kind, len(tenants_r))
                    continue
                tenant = tenants_r[0]
                tripped = svc.note_fault(tenant, f.kind,
                                         probe=(tenant in probing))
                to_host = tripped or f.kind == KIND_LOST
                if guard_on and f.kind in (KIND_LOST, KIND_OOM):
                    action = guard.recover(
                        f, can_bisect=self._can_bisect(remaining))
                    to_host = to_host or action == ACT_HOST
                if to_host:
                    self._dispatch(remaining, trace_id, host=True)
                    svc.note_host_fallback(tenant, len(remaining))
                else:
                    groups.append(remaining)
        svc.record_solve(
            device_pods, sum(devicestats.transfer_snapshot().values())
            - transfers0)

    def _can_bisect(self, pods: list) -> bool:
        """OOM bisection re-solves the remainder as stream chunks at a
        smaller warmed bucket — available only where chunking is legal:
        no gang (one assignment vector), no joint (prices couple the
        queue), no extenders, and a non-empty pre-warmed ladder."""
        from kubernetes_tpu.engine.workloads import gang as gang_mod
        from kubernetes_tpu.utils.featuregate import DEFAULT_FEATURE_GATE
        daemon = self.daemon
        if daemon.config.algorithm.extenders:
            return False
        if not DEFAULT_FEATURE_GATE.enabled("StreamingDrain") or \
                DEFAULT_FEATURE_GATE.enabled("JointSolver"):
            return False
        if DEFAULT_FEATURE_GATE.enabled("GangScheduling") and \
                gang_mod.batch_has_gangs(pods):
            return False
        return bool(daemon.effective_ladder())

    def _dispatch(self, pods: list, trace_id: str = "",
                  host: bool = False) -> int:
        from kubernetes_tpu.engine.workloads import gang as gang_mod
        from kubernetes_tpu.utils.featuregate import DEFAULT_FEATURE_GATE
        daemon = self.daemon
        joint = DEFAULT_FEATURE_GATE.enabled("JointSolver")
        # Gangs must be admitted all-or-nothing over ONE assignment
        # vector — a chunked stream could split a gang across chunk
        # boundaries, so gang batches take the one-shot solve (padded to
        # a warm bucket below).
        gangs = DEFAULT_FEATURE_GATE.enabled("GangScheduling") and \
            gang_mod.batch_has_gangs(pods)
        if host:
            # Breaker open: the whole batch decides on the host engine
            # (sequential NumPy — chunking and buckets are meaningless
            # there; gang reduction still applies to its output).
            return self._solve_oneshot(pods, joint=False, gangs=gangs,
                                       trace_id=trace_id, host=True)
        # The joint solve needs the whole queue at once (prices couple
        # every pod); it supersedes the streaming split.
        streaming = DEFAULT_FEATURE_GATE.enabled("StreamingDrain") \
            and not joint and not gangs \
            and not daemon.config.algorithm.extenders
        guard = getattr(daemon.config.algorithm, "guard", None)
        cap = guard.bucket_cap() \
            if guard is not None and guard.enabled else None
        if streaming and cap is not None:
            # Bisected (or HBM-watermark-capped) regime: every
            # streamable drain chunks at the cap — a pre-warmed ladder
            # bucket, never a fresh shape.
            return self._solve_stream(pods, chunk_size=cap,
                                      trace_id=trace_id)
        if streaming and len(pods) >= daemon.STREAM_THRESHOLD:
            return self._solve_stream(pods, trace_id=trace_id)
        if streaming and len(pods) < daemon._PAD_LIMIT:
            # Small drain: one power-of-two stream chunk (live-flag
            # padded), so arrival races don't mint a new compiled shape
            # per queue length; floored so the tail of the ladder doesn't
            # either.
            bucket = max(1 << (len(pods) - 1).bit_length(),
                         daemon.stream_min_bucket)
            return self._solve_stream(pods, chunk_size=bucket,
                                      trace_id=trace_id)
        return self._solve_oneshot(pods, joint=joint, gangs=gangs,
                                   trace_id=trace_id)

    # -- one-shot / joint / gang / host solve ------------------------------

    def _solve_oneshot(self, pods: list, joint: bool, gangs: bool,
                       trace_id: str, host: bool = False) -> int:
        from kubernetes_tpu.engine.workloads import gang as gang_mod
        daemon = self.daemon
        start = time.perf_counter()
        if host:
            placements = daemon.config.algorithm.schedule_batch_host(pods)
        else:
            # Workload-constrained one-shot drains pad to the same
            # bucket ladder the stream path compiles at, so gang/joint
            # solves hit pre-warmed shapes instead of minting one per
            # queue length.
            pad_to = 0
            if (gangs or joint) and len(pods) < daemon._PAD_LIMIT and \
                    not daemon.config.algorithm.extenders:
                pad_to = max(1 << (len(pods) - 1).bit_length(),
                             daemon.stream_min_bucket)
            placements = daemon.config.algorithm.schedule_batch(
                pods, joint=joint, pad_to=pad_to)
        failure_info: dict[str, tuple[str, str]] = {}
        if gangs:
            placements, rejected = gang_mod.reduce_all_or_nothing(
                pods, placements)
            for name, info in rejected.items():
                metrics_mod.GANG_ADMISSIONS.labels(
                    result="rejected").inc()
                msg = gang_mod.gang_failure_message(name, info)
                log.debug("gang rejection: %s", msg)
                for i in info["members"]:
                    failure_info[pods[i].key] = (msg, "gang_rejected")
            admitted = [name for name in gang_mod.gang_groups(pods)
                        if name not in rejected]
            for _ in admitted:
                metrics_mod.GANG_ADMISSIONS.labels(
                    result="admitted").inc()
        algo_us = (time.perf_counter() - start) * 1e6 / len(pods)
        daemon.config.metrics.scheduling_algorithm_latency.observe_many(
            algo_us, len(pods))
        if log.isEnabledFor(10):  # V(2)-style guard (predicates.go:478)
            placed_n = sum(1 for d in placements if d is not None)
            log.debug("drained %d pods: %d placed, %.0f us/pod algorithm",
                      len(pods), placed_n, algo_us)
        daemon._record_batch_decisions(pods, placements, trace_id,
                                       time.perf_counter() - start)
        daemon._assume_and_bind_batch(pods, placements, start,
                                      failure_info=failure_info)
        return len(pods)

    # -- streamed solve: inline commit, or the overlapped commit worker ----

    def _solve_stream(self, pods: list, chunk_size: Optional[int] = None,
                      trace_id: str = "") -> int:
        """The streamed drain.  A drain of one chunk (every launch of a
        steady arrival stream) commits on this thread: the worker below
        overlaps chunk N's scan with chunk N-1's commit, and one chunk
        has nothing to overlap — handing it over would only add a
        hand-off and a wake to the launch.  A drain of two or more
        chunks commits on a single commit worker, with at most
        ``pipeline_window`` chunks in flight uncommitted (0 = inline
        here too).  The one worker keeps chunks committing in solve
        order, and within a chunk assume completes before its bind
        dispatches — the per-pod assume-before-bind ordering of the
        one-shot path.  Either way every popped pod is assumed or failed
        by return, and a commit failure reaches ``drain()``'s crash
        handler."""
        daemon = self.daemon
        start = time.perf_counter()
        window = max(daemon.pipeline_window, 0)
        chunk = chunk_size or daemon.stream_chunk_size()
        inline = window == 0 or len(pods) <= chunk
        metrics_mod.COMMIT_LAUNCHES.labels(
            path="inline" if inline else "worker").inc()
        if inline:
            solve_done = start
            for chunk_pods, placements in \
                    daemon.config.algorithm.schedule_batch_stream(
                        pods, chunk_size=chunk):
                solve_done = time.perf_counter()
                self._commit(chunk_pods, placements, start, solve_done,
                             trace_id)
        else:
            solve_done = self._commit_on_worker(pods, chunk, window,
                                                start, trace_id)
        # Algorithm latency spans until the LAST chunk's results landed
        # (interleaved assume/bind of earlier chunks overlaps the device
        # and is deliberately excluded, matching the one-shot path).
        algo_us = (solve_done - start) * 1e6 / len(pods)
        daemon.config.metrics.scheduling_algorithm_latency.observe_many(
            algo_us, len(pods))
        return len(pods)

    def _commit(self, chunk_pods: list, placements: list, start: float,
                landed: float, trace_id: str) -> None:
        """One chunk's commit: flight-recorder feed, bulk assume, bind
        dispatch."""
        self.daemon._record_batch_decisions(chunk_pods, placements,
                                            trace_id, landed - start)
        self.daemon._assume_and_bind_batch(chunk_pods, placements, start)

    def _commit_on_worker(self, pods: list, chunk: int, window: int,
                          start: float, trace_id: str) -> float:
        """The overlapped drain of several chunks: each chunk's readback
        and commit runs on the commit worker while this thread launches
        the next.  Returns when the last chunk's results landed."""
        if self._commit_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._commit_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="chunk-commit")
        sem = threading.BoundedSemaphore(window)
        ctx = trace_mod.current_context()
        # Stamps the commit worker writes: when each chunk's readback
        # landed (the last bounds algorithm latency) and when its commit
        # ended (the next chunk's hand-off and the drain thread's wake
        # are timed from it).
        stamps = [start, start]
        futures = []
        err = None
        try:
            for _, resolve in \
                    self.daemon.config.algorithm.schedule_batch_stream(
                        pods, chunk_size=chunk, defer_readback=True):
                # Bounded in-flight window: block the drain thread (and
                # with it further device launches) until an outstanding
                # chunk commits — time the worker's commit fills, so a
                # ``commit.`` stage.  The worker times the hand-off from
                # here to its pick-up.
                t_wait = time.perf_counter()
                with trace_mod.annotation("commit.window_wait"):
                    sem.acquire()
                t_handoff = time.perf_counter()
                trace_mod.record_stage("commit.window_wait",
                                       start=t_wait, end=t_handoff)
                with trace_mod.annotation("handoff"):
                    futures.append(self._commit_pool.submit(
                        self._commit_chunk, resolve, start, trace_id,
                        sem, ctx, stamps, t_handoff))
        finally:
            # Join EVERY submitted commit before surfacing anything:
            # drain()'s crash handler requeues pods not yet assumed, and
            # a still-running commit assuming them concurrently would
            # double-track the pod.
            with trace_mod.annotation("commit.join"):
                for fut in futures:
                    try:
                        fut.result()
                    except Exception as exc:  # noqa: BLE001 — requeue
                        err = err or exc
        if err is not None:
            # Surface the first commit failure to drain()'s crash
            # handler, which requeues every pod the completed commits
            # didn't assume.
            raise err
        if futures:
            # From the last commit's end to this thread's return.
            trace_mod.record_stage("wake", start=stamps[1])
        return stamps[0]

    def _commit_chunk(self, resolve, start: float, trace_id: str, sem,
                      trace_ctx, stamps: list, t_handoff: float) -> None:
        """One chunk's commit on the pipeline worker: the blocking
        readback, then the commit."""
        try:
            with trace_mod.use_context(trace_ctx):
                # From the hand-off, or from this worker's end of the
                # previous chunk where that came later: the wait for this
                # thread, never the previous chunk's commit again.
                trace_mod.record_stage("handoff",
                                       start=max(t_handoff, stamps[1]))
                chunk_pods, placements = resolve()
                stamps[0] = time.perf_counter()
                self._commit(chunk_pods, placements, start, stamps[0],
                             trace_id)
        finally:
            sem.release()
            stamps[1] = time.perf_counter()

    # -- lifecycle --------------------------------------------------------

    def shutdown(self, wait: bool = True, cancel: bool = False) -> None:
        if self._commit_pool is not None:
            if cancel:
                self._commit_pool.shutdown(wait=False,
                                           cancel_futures=True)
            else:
                self._commit_pool.shutdown(wait=wait)
