"""Guarded device execution: fault classification, recovery policy, and
the post-solve sanity gate.

Every solve site (one-shot, stream chunk, joint, single-pod, preemption
victim kernel) runs inside this layer so an accelerator fault is a
POLICY DECISION instead of a stalled drain loop:

* **Classification.**  ``classify()`` buckets a device exception into
  four fault kinds — ``oom`` (HBM ``RESOURCE_EXHAUSTED``),
  ``compile`` (XLA compilation failure), ``lost`` (device in an error
  state / runtime gone), or None (not a device fault: re-raised
  untouched so real bugs keep crashing loudly).  Classified faults
  count in ``scheduler_device_faults_total{kind=}`` and re-raise as
  ``DeviceFault`` for the drain pipeline's recovery ladder.

* **Recovery ladder** (``recover()``): OOM evicts the resident cluster
  arrays and bisects the batch onto the NEXT SMALLER pre-warmed bucket
  (never an unwarmed shape — the cap walks ``effective_ladder()``
  downward); repeated faults of any kind, or a single ``lost``, trip a
  circuit breaker into the HOST fallback engine
  (``engine/hostsolver.py``), with periodic probe solves re-promoting
  back to the device once it answers again.  A ladder that exhausts its
  rounds requeues the batch through the pipeline's crash handler —
  never drops pods, never binds garbage.

* **Sanity gate** (``checked_readback``): every assignment vector read
  back from the device is validated before anything binds — integral
  dtype, no NaN/inf, indices in ``[-1, n_nodes)``, live-mask respected
  (padded rows place nothing), and a host spot-check that sampled
  placed pods' requests fit their chosen node's total allocatable.  A
  failed gate classifies as ``corrupt`` and requeues the batch; the
  pod keys of a rejected batch are remembered so the commit path can
  refuse them outright (``scheduler_sanity_rejected_binds_total`` — a
  defense-in-depth counter that must stay 0).

* **HBM watermark** (``KT_HBM_WATERMARK`` bytes): a PROACTIVE cap —
  when the live-HBM gauge crosses it, bucket growth is capped at the
  ladder floor (and the resident arrays evicted once) BEFORE the
  allocator ever throws, counted in
  ``scheduler_hbm_watermark_trips_total``.

Fault injection for all of this is ``chaos/device.py``; the guard is
the ONLY consumer, so un-guarded paths (the explain pass, benches) are
never chaos'd.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Callable, Iterator, Optional

import numpy as np

from kubernetes_tpu.chaos import device as chaos_device
from kubernetes_tpu.utils import knobs, locktrace, metrics
from kubernetes_tpu.utils.logging import get_logger

log = get_logger("guard")

KIND_OOM = "oom"
KIND_COMPILE = "compile"
KIND_LOST = "lost"
KIND_CORRUPT = "corrupt"

# Substring → kind, checked in order: device-lost shapes first because a
# dying runtime often wraps its status in INTERNAL like compile failures.
_PATTERNS = (
    ("RESOURCE_EXHAUSTED", KIND_OOM),
    ("Out of memory", KIND_OOM),
    ("OOM ", KIND_OOM),
    ("DEVICE_LOST", KIND_LOST),
    ("device is in an error state", KIND_LOST),
    ("unrecoverable error state", KIND_LOST),
    ("Unable to initialize backend", KIND_LOST),
    ("FAILED_PRECONDITION", KIND_LOST),
    ("compilation failed", KIND_COMPILE),
    ("XLA compilation", KIND_COMPILE),
    ("during compilation", KIND_COMPILE),
    ("Mosaic", KIND_COMPILE),
)


def _is_device_error(exc: BaseException) -> bool:
    """Only runtime errors raised by the device stack (jaxlib's
    XlaRuntimeError or the chaos simulation) classify; arbitrary
    Python bugs must keep crashing as themselves."""
    if isinstance(exc, chaos_device.SimulatedDeviceError):
        return True
    name = type(exc).__name__
    if name in ("XlaRuntimeError", "JaxRuntimeError"):
        return True
    mod = type(exc).__module__ or ""
    return isinstance(exc, RuntimeError) and (
        "jaxlib" in mod or "jax" in mod)


def classify(exc: BaseException) -> str | None:
    """The fault kind: oom / compile / lost, or None when the
    exception is not a device fault."""
    if isinstance(exc, DeviceFault):
        return exc.kind
    if not _is_device_error(exc):
        return None
    msg = str(exc)
    for token, kind in _PATTERNS:
        if token in msg:
            return kind
    # A device-stack runtime error with an unknown status: treat as
    # lost — the conservative end of the ladder (host keeps scheduling).
    return KIND_LOST


class DeviceFault(Exception):
    """A classified accelerator fault, carrying the recovery ladder's
    inputs: the fault kind and the solve path it struck."""

    def __init__(self, kind: str, path: str, orig: BaseException | None = None):
        self.kind = kind
        self.path = path
        self.orig = orig
        super().__init__(f"device fault [{kind}] on {path} path: {orig}")


# Recovery actions recover() hands the pipeline.  (There is no
# "requeue" action: ladder exhaustion is the PIPELINE's round bound —
# max_rounds spent -> the last fault re-raises into drain()'s crash
# handler, which requeues.)
ACT_RETRY = "retry"      # re-dispatch the remaining pods unchanged
ACT_BISECT = "bisect"    # re-dispatch chunked at the shrunken bucket cap
ACT_HOST = "host"        # breaker open: re-dispatch on the host engine


class DeviceGuard:
    """Per-engine fault-policy state machine (mode, breaker, bucket cap,
    rejected-batch memory).  Thread-safe: the drain thread, the commit
    worker, and the single-pod path all cross it."""

    def __init__(self, evict_fn: Optional[Callable[[], None]] = None,
                 ladder_fn: Optional[Callable[[], list[int]]] = None):
        self.enabled = knobs.get_bool("KT_GUARD")
        # Consecutive same-kind faults before the breaker trips to host.
        self.breaker_threshold = knobs.get_int("KT_GUARD_BREAKER")
        # Seconds between device probe solves while the breaker is open.
        self.probe_period_s = knobs.get_float("KT_GUARD_PROBE_S")
        # Bound on recovery rounds per drain (each round re-solves only
        # the still-uncommitted pods, so progress is monotone anyway).
        self.max_rounds = knobs.get_int("KT_GUARD_ROUNDS")
        # Device-healthy drains before a bisected bucket cap resets.
        self.cap_reset_streak = knobs.get_int("KT_GUARD_CAP_RESET")
        # Proactive HBM ceiling in bytes (0 = off).
        self.hbm_watermark = knobs.get_int("KT_HBM_WATERMARK")
        self.evict_fn = evict_fn
        self.ladder_fn = ladder_fn or (lambda: [])
        self._lock = locktrace.make_lock("engine.DeviceGuard")
        self._mode = "device"
        self._consecutive: dict[str, int] = {}
        self._bucket_cap: int | None = None
        self._success_streak = 0
        self._opened_at = 0.0
        self._host_mode_s = 0.0   # accumulated seconds spent in host mode
        self._last_probe = 0.0
        self._probing = False
        self._wm_active = False
        self._suppress = False
        self._last_fault: dict | None = None
        self._rejected_keys: set[str] = set()
        self.gate_rejects = 0
        if self.enabled:
            metrics.ENGINE_MODE.set(0.0)

    # -- mode / breaker ---------------------------------------------------

    @property
    def mode(self) -> str:
        return self._mode

    def solve_mode(self) -> str:
        """Routing decision for the next drain: ``device``, ``host``,
        or ``probe`` (breaker open but a probe is due — attempt the
        device; failure falls back to host without re-counting)."""
        with self._lock:
            if self._mode == "device":
                return "device"
            now = time.monotonic()
            if now - self._last_probe >= self.probe_period_s:
                self._last_probe = now
                self._probing = True
                return "probe"
            return "host"

    def note_success(self, probe: bool = False) -> None:
        """A device solve completed and passed the gate: close the
        breaker if this was a probe, and walk the bucket cap back up
        after a healthy streak."""
        with self._lock:
            self._consecutive.clear()
            if probe and self._mode == "host":
                self._host_mode_s += time.monotonic() - self._opened_at
                self._mode = "device"
                self._probing = False
                metrics.ENGINE_MODE.set(0.0)
                log.info("device probe succeeded; breaker closed, "
                         "engine re-promoted to device mode")
            self._success_streak += 1
            if self._bucket_cap is not None and \
                    self._success_streak >= self.cap_reset_streak:
                log.info("device healthy for %d drains; lifting bisect "
                         "cap %d", self._success_streak, self._bucket_cap)
                self._bucket_cap = None

    def _trip(self, kind: str) -> None:
        # Called under self._lock.
        if self._mode != "host":
            self._mode = "host"
            self._opened_at = time.monotonic()
            self._last_probe = self._opened_at
            metrics.ENGINE_MODE.set(1.0)
            log.warning("device breaker OPEN after %s fault(s); engine "
                        "falling back to the host solver (probe every "
                        "%.1fs)", kind, self.probe_period_s)

    def recover(self, fault: DeviceFault, can_bisect: bool = True) -> str:
        """The bounded policy ladder: map a classified fault to the
        pipeline's next action.  OOM walks the pre-warmed bucket ladder
        downward (after evicting the resident arrays); repeated faults
        of one kind, or any ``lost``, trip the breaker to host."""
        with self._lock:
            self._success_streak = 0
            n = self._consecutive.get(fault.kind, 0) + 1
            self._consecutive[fault.kind] = n
            if self._probing:
                # A failed probe never re-escalates: stay on host,
                # reset the probe clock.
                self._probing = False
                self._last_probe = time.monotonic()
                self._trip(fault.kind)
                return ACT_HOST
            if fault.kind == KIND_LOST or n >= self.breaker_threshold:
                # SOLVE_FALLBACKS{mode=host} counts at the execution
                # sites (schedule_batch_host / _schedule_host), not here.
                self._trip(fault.kind)
                return ACT_HOST
            if fault.kind == KIND_OOM:
                self._evict_locked()
                if can_bisect and self._shrink_cap_locked():
                    metrics.SOLVE_FALLBACKS.labels(mode="bisect").inc()
                    return ACT_BISECT
                return ACT_RETRY  # at the ladder floor: evicted, retry
            # compile / corrupt under the threshold: plain retry (the
            # every-Nth chaos shapes and transient XLA hiccups clear).
            return ACT_RETRY

    def _evict_locked(self) -> None:
        if self.evict_fn is not None:
            try:
                self.evict_fn()
            except Exception:  # noqa: BLE001 — eviction is best-effort
                log.exception("resident-array eviction failed")

    def _shrink_cap_locked(self) -> bool:
        """Walk the bucket cap one rung down the PRE-WARMED ladder;
        False when already at (or below) the floor.  The cap can only
        ever hold a ladder value — bisection never mints a shape the
        prewarm didn't trace."""
        ladder = sorted(self.ladder_fn() or [])
        if not ladder:
            return False
        current = self._bucket_cap if self._bucket_cap is not None \
            else ladder[-1]
        smaller = [b for b in ladder if b < current]
        if not smaller:
            return False
        self._bucket_cap = smaller[-1]
        log.warning("OOM: resident arrays evicted, batch bisected onto "
                    "the %d-pod pre-warmed bucket", self._bucket_cap)
        return True

    def bucket_cap(self) -> int | None:
        """The ladder bucket device drains are currently capped at:
        the bisect cap, tightened to the ladder FLOOR while the HBM
        watermark is tripped."""
        with self._lock:
            cap = self._bucket_cap
        wm = self._watermark_cap()
        if wm is not None:
            cap = wm if cap is None else min(cap, wm)
        return cap

    def _watermark_cap(self) -> int | None:
        if not self.hbm_watermark:
            return None
        from kubernetes_tpu.engine import devicestats
        live = devicestats.hbm_live_bytes()
        with self._lock:
            if live <= self.hbm_watermark:
                self._wm_active = False
                return None
            if not self._wm_active:
                self._wm_active = True
                metrics.HBM_WATERMARK_TRIPS.inc()
                self._evict_locked()
                log.warning("HBM watermark tripped (%d > %d bytes): "
                            "bucket growth capped at the ladder floor",
                            live, self.hbm_watermark)
        ladder = sorted(self.ladder_fn() or [])
        return ladder[0] if ladder else None

    # -- the solve-site wrapper -------------------------------------------

    @contextlib.contextmanager
    def suppressed(self) -> Iterator[None]:
        """Turn chaos injection off for a scope.  The prewarm ladder
        runs the SAME solve sites as live drains but has no recovery
        ladder above it — a KT_CHAOS_DEVICE cadence firing mid-warmup
        would fail startup instead of exercising recovery, so
        ``Scheduler.prewarm()`` traces under this.  Real device faults
        still propagate (as their original exceptions)."""
        prev = self._suppress
        self._suppress = True
        try:
            yield
        finally:
            self._suppress = prev

    @contextlib.contextmanager
    def watch(self, path: str, inject: bool = True) -> Iterator[None]:
        """Wrap one device interaction: chaos injection on entry (only
        at the solve LAUNCH sites — ``inject=False`` marks
        compile/readback wrappers that classify real faults but don't
        consume the injector's every-Nth cadence), fault classification
        on the way out.  Classified faults count and re-raise as
        ``DeviceFault``; everything else passes through untouched."""
        if not self.enabled or self._suppress:
            yield
            return
        chaos = chaos_device.active()
        if chaos is not None and inject:
            try:
                chaos.maybe_fail(path)
            except chaos_device.SimulatedDeviceError as exc:
                kind = classify(exc) or KIND_LOST
                self._record_fault(kind, path)
                raise DeviceFault(kind, path, exc) from exc
        try:
            yield
        except DeviceFault:
            raise
        except Exception as exc:  # noqa: BLE001 — classify, then decide
            kind = classify(exc)
            if kind is None:
                raise
            self._record_fault(kind, path)
            raise DeviceFault(kind, path, exc) from exc

    def _record_fault(self, kind: str, path: str) -> None:
        metrics.DEVICE_FAULTS.labels(kind=kind).inc()
        with self._lock:
            self._last_fault = {"kind": kind, "path": path,
                                "at": time.time()}
        log.warning("device fault [%s] on %s path", kind, path)

    # -- the post-solve sanity gate ---------------------------------------

    def checked_readback(self, path: str, rows: np.ndarray, n_nodes: int,
                         live: Optional[np.ndarray] = None,
                         alloc: Optional[np.ndarray] = None,
                         requests: Optional[np.ndarray] = None,
                         keys_fn: Optional[Callable[[], list[str]]] = None,
                         spot_k: int = 16) -> np.ndarray:
        """Validate an assignment readback before anything commits.

        ``rows`` is the choices vector (or the packed vector's choices
        slice); ``live`` the real-row mask when the batch was padded;
        ``alloc``/``requests`` the host-side [N,4]/[P,4] arrays for the
        capacity spot-check; ``keys_fn`` lazily names the batch's pod
        keys so a rejected batch is remembered (and a later clean solve
        of the same pods forgets it).  Returns the int32 choices;
        raises ``DeviceFault('corrupt')`` on any violation."""
        if not self.enabled:
            return np.asarray(rows)
        chaos = chaos_device.active()
        if chaos is not None and path != "host" and not self._suppress:
            rows = chaos.maybe_corrupt(path, rows)
        arr = np.asarray(rows)
        problem = None
        if arr.dtype.kind == "f":
            if not np.isfinite(arr).all():
                problem = "NaN/inf in readback"
            elif arr.size and not (arr == np.trunc(arr)).all():
                problem = "non-integral assignment indices"
        if problem is None:
            choices = arr.astype(np.int64, copy=False)
            if choices.size and (int(choices.min(initial=0)) < -1 or
                                 int(choices.max(initial=-1)) >= n_nodes):
                problem = (f"assignment index out of range "
                           f"[-1, {n_nodes})")
            elif live is not None:
                dead = ~np.asarray(live, bool)
                if choices.size and (choices[dead[:len(choices)]]
                                     != -1).any():
                    problem = "padded (dead) row received a placement"
        if problem is None and alloc is not None and requests is not None:
            # Host spot-check on sampled rows: a placed pod's request can
            # never exceed its node's TOTAL allocatable — a necessary
            # condition that is cheap against batch-start host arrays
            # (in-batch occupancy is the scan's job, not the gate's).
            placed = np.nonzero(choices >= 0)[0]
            if placed.size:
                step = max(placed.size // spot_k, 1)
                sample = placed[::step][:spot_k]
                req = np.asarray(requests)[sample, :3]
                cap = np.asarray(alloc)[choices[sample], :3]
                if (req > cap).any():
                    problem = ("sampled placement exceeds the node's "
                               "total allocatable")
        if problem is not None:
            self.gate_rejects += 1
            metrics.GATE_REJECTS.inc()
            if keys_fn is not None:
                try:
                    with self._lock:
                        self._rejected_keys.update(keys_fn())
                except Exception:  # noqa: BLE001 — bookkeeping only
                    pass
            self._record_fault(KIND_CORRUPT, path)
            raise DeviceFault(KIND_CORRUPT, path,
                              RuntimeError(f"sanity gate: {problem}"))
        if keys_fn is not None and self._rejected_keys:
            with self._lock:
                if self._rejected_keys:
                    self._rejected_keys.difference_update(keys_fn())
        return choices.astype(np.int32, copy=False)

    def checked_scores(self, path: str, feasible: object,
                       scores: object) -> tuple:
        """The single-pod gate: evaluation planes must be finite (a NaN
        score would argmax into garbage)."""
        if not self.enabled:
            return feasible, scores
        chaos = chaos_device.active()
        if chaos is not None and path != "host" and not self._suppress:
            scores = chaos.maybe_corrupt(path, scores)
        arr = np.asarray(scores)
        if not np.isfinite(arr).all():
            self.gate_rejects += 1
            metrics.GATE_REJECTS.inc()
            self._record_fault(KIND_CORRUPT, path)
            raise DeviceFault(KIND_CORRUPT, path,
                              RuntimeError("sanity gate: NaN/inf score "
                                           "plane"))
        return np.asarray(feasible), arr

    # -- rejected-batch memory (defense in depth at the bind path) --------

    def has_rejections(self) -> bool:
        return bool(self._rejected_keys)

    def filter_rejected(self, placed: list) -> tuple[list, list]:
        """Split (pod, dest) pairs into (clean, rejected): a pod whose
        last solve failed the gate and was never cleanly re-solved must
        NOT bind.  Structurally unreachable (the gate raises before
        placements exist) — this is the ratcheted backstop, and every
        hit counts in ``scheduler_sanity_rejected_binds_total``."""
        if not self._rejected_keys:
            return placed, []
        with self._lock:
            rejected = [(pod, dest) for pod, dest in placed
                        if pod.key in self._rejected_keys]
        if rejected:
            metrics.GATE_REJECTED_BINDS.inc(len(rejected))
            log.error("refused to bind %d pod(s) from a sanity-gate-"
                      "rejected batch", len(rejected))
            drop = {id(p) for p, _ in rejected}
            placed = [pd for pd in placed if id(pd[0]) not in drop]
        return placed, rejected

    # -- reporting ---------------------------------------------------------

    def host_mode_seconds(self) -> float:
        with self._lock:
            extra = time.monotonic() - self._opened_at \
                if self._mode == "host" else 0.0
            return self._host_mode_s + extra

    def report(self) -> dict:
        """The /debug/vars + soak-artifact payload: the fault-policy
        state plus the device (platform, kind, count) it guards."""
        from kubernetes_tpu.engine import devicestats
        device = devicestats.device_info()
        with self._lock:
            return {
                "platform": device["platform"],
                "deviceKind": device["kind"],
                "deviceCount": device["count"],
                "enabled": self.enabled,
                "mode": self._mode,
                "bucketCap": self._bucket_cap,
                "lastFault": self._last_fault,
                "gateRejects": self.gate_rejects,
                "hbmWatermark": self.hbm_watermark,
                "hostModeSeconds": round(
                    self._host_mode_s +
                    (time.monotonic() - self._opened_at
                     if self._mode == "host" else 0.0), 2),
            }
