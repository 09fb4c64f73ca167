"""Device accounting: HBM occupancy, per-cause transfer bytes, and the
post-prewarm recompile watchdog.

PRs 2-3 made the HOST side observable (spans, stage histograms, a
flight recorder); this module is the DEVICE side — the layer where the
regressions ROADMAP items 1 and 3 name would otherwise be invisible:

* **Transfer accounting.**  ``record_transfer(cause, nbytes)`` feeds
  ``scheduler_device_transfer_bytes_total{cause=}`` (and an ops
  counter).  The drain path records four causes: ``batch`` (the pod
  batch in its wire form, ``solver.put_batch`` — once per chunk),
  ``scatter`` (dirty rows into the resident cluster mirror — the
  steady-state path), ``full_upload`` (whole-cluster re-snapshot —
  legitimate only on relist/capacity growth; dominating steady-state
  drains means the residency protocol silently broke), and ``readback``
  (device→host result fetches).  The uploads also count the host arrays
  they hand to the runtime
  (``scheduler_device_transfer_arrays_total{cause=}``): each is a trip
  through the interpreter's lock on the launch thread, so arrays per
  launch — not bytes — is what an upload costs the served path.
  ``transfer_snapshot()`` returns the per-cause byte
  totals so benches can diff a window and stamp bytes-per-pod columns
  into their artifacts.

* **HBM accounting.**  ``hbm_live_bytes()`` asks the backend
  (``device.memory_stats()``; TPU/GPU report ``bytes_in_use``) and
  falls back to summing ``jax.live_arrays()`` where the backend keeps
  no books (CPU).  ``sample_hbm()`` refreshes a process-lifetime peak;
  the ``scheduler_device_hbm_{live,peak}_bytes`` gauges read through
  live at expose, and the telemetry ring's self-scrape cadence is the
  peak-tracking cadence — deliberately NOT the drain path, where the
  fallback's live-array walk would tax every sync.

* **Recompile watchdog.**  ``arm()`` (called when ``prewarm()``
  finishes) registers a JAX monitoring listener for backend-compile
  events; every compile AFTER arming is a stall the bucket-ladder
  prewarm should have traced, so it increments
  ``scheduler_post_prewarm_compiles_total{path=}`` (the live path the
  drain declared via ``live_path()``) and records a ``slow_trace``-style
  ``post_prewarm_compile`` span carrying the offending signature (the
  innermost non-library frame of the compiling call stack).  The bench
  ratchet (tools/check_bench.py) fails tier-1 on any such compile in
  the density run.  ``watchdog_window()`` scopes arming for benches and
  tests.

Everything here is observability: every hook is wrapped so a failure
can never take the drain path down with it.
"""

from __future__ import annotations

import contextlib
import threading
import traceback
from typing import Callable, Iterator

from kubernetes_tpu.utils import metrics
from kubernetes_tpu.utils.logging import get_logger

log = get_logger("devicestats")

CAUSES = ("batch", "scatter", "full_upload", "readback")

_lock = threading.Lock()
_peak_fallback = 0          # high-water mark of sampled live bytes
_armed = False
_listener_installed = False
_tls = threading.local()    # .path — the live path compiling right now


# -- transfer accounting -----------------------------------------------------

def nbytes(tree: object) -> int:
    """Total array bytes of a pytree-ish value (NamedTuple / list /
    tuple / dict of numpy or jax arrays)."""
    if tree is None:
        return 0
    if hasattr(tree, "nbytes"):
        return int(tree.nbytes)
    if isinstance(tree, dict):
        return sum(nbytes(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):  # NamedTuples included
        return sum(nbytes(v) for v in tree)
    return 0


def record_transfer(cause: str, n: int, arrays: int = 0) -> None:
    """Count ``n`` bytes moved for ``cause`` (batch/scatter/full_upload/
    readback), and for an upload the ``arrays`` it handed the runtime."""
    if n <= 0:
        return
    metrics.DEVICE_TRANSFER_BYTES.labels(cause=cause).inc(int(n))
    metrics.DEVICE_TRANSFERS.labels(cause=cause).inc()
    if arrays:
        metrics.DEVICE_TRANSFER_ARRAYS.labels(cause=cause).inc(arrays)


def transfer_snapshot() -> dict[str, int]:
    """Per-cause byte totals so far — diff two snapshots to account a
    window (the bench's bytes-per-pod columns)."""
    children = metrics.DEVICE_TRANSFER_BYTES.children()
    out = {cause: 0 for cause in CAUSES}
    for key, child in children.items():
        out[key[0]] = int(child.value)
    return out


# -- the device itself -------------------------------------------------------

def device_info() -> dict:
    """The device this process computes on, as JAX reports it.  Unlike
    the accounting below this raises when no backend initializes: a
    process asked to name its device must not invent one."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


# -- HBM accounting ----------------------------------------------------------

def _backend_memory_stats() -> dict | None:
    try:
        import jax
        stats = jax.devices()[0].memory_stats()
        return stats if stats else None
    except Exception:  # noqa: BLE001 — accounting must never raise
        return None


def hbm_live_bytes() -> int:
    """Device bytes currently held by live arrays: the backend's
    ``bytes_in_use`` when it keeps books, else the sum over
    ``jax.live_arrays()``."""
    stats = _backend_memory_stats()
    if stats and "bytes_in_use" in stats:
        return int(stats["bytes_in_use"])
    try:
        import jax
        return int(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:  # noqa: BLE001
        return 0


def hbm_peak_bytes() -> int:
    """Peak device occupancy: the backend's ``peak_bytes_in_use`` when
    reported, else the high-water mark of sampled live bytes."""
    stats = _backend_memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        return int(stats["peak_bytes_in_use"])
    return max(_peak_fallback, 0)


def sample_hbm() -> int:
    """Refresh the fallback peak from the current live bytes (called per
    resident sync and per telemetry scrape); returns the live bytes."""
    global _peak_fallback
    live = hbm_live_bytes()
    if live > _peak_fallback:
        with _lock:
            if live > _peak_fallback:
                _peak_fallback = live
    return live


metrics.DEVICE_HBM_LIVE_BYTES.set_fn(hbm_live_bytes)
metrics.DEVICE_HBM_PEAK_BYTES.set_fn(hbm_peak_bytes)


# -- recompile watchdog ------------------------------------------------------

def _offending_signature() -> str:
    """The innermost caller frame OUTSIDE jax/library code — the call
    site whose shape minted the compile.  Paid only when the watchdog
    actually fires (compiles post-prewarm are the rare bug, not the
    steady state)."""
    try:
        stack = traceback.extract_stack()
        # Innermost frame of OUR code (the drain call site whose shape
        # minted the compile), else the innermost non-jax/non-stdlib one.
        for frame in reversed(stack):
            fn = frame.filename
            if "kubernetes_tpu" in fn and not fn.endswith(
                    "devicestats.py"):
                return (f"{fn.rsplit('/', 1)[-1]}:{frame.lineno} "
                        f"{frame.name}")
    except Exception:  # noqa: BLE001
        pass
    return "unknown"


def _fire(secs: float) -> None:
    path = getattr(_tls, "path", None) or "unknown"
    sig = _offending_signature()
    metrics.POST_PREWARM_COMPILES.labels(path=path).inc()
    try:
        from kubernetes_tpu.utils import trace
        trace.begin_span("post_prewarm_compile", path=path,
                         signature=sig,
                         compile_s=round(secs, 3)).end()
    except Exception:  # noqa: BLE001
        pass
    log.warning("post-prewarm XLA compile on live path %r (%.2fs) at %s "
                "— a shape the prewarm ladder never traced",
                path, secs, sig)


def _on_compile_duration(event: str, secs: float, **kw) -> None:
    # backend_compile_duration wraps compile_or_get_cached, so it fires
    # exactly once per NEW executable — full XLA compiles and
    # persistent-cache deserializes alike (a cache hit is cheaper, but
    # still a live-path program the prewarm ladder missed).  Verified
    # against jax 0.4.37: the hit path fires this event too, so
    # listening for cache_hits as well would double-count.
    if _armed and event.endswith("backend_compile_duration"):
        _fire(secs)


def _install_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        _listener_installed = True
    except Exception:  # noqa: BLE001 — observability only
        log.debug("jax monitoring unavailable; recompile watchdog off")


def arm() -> None:
    """Arm the watchdog: every XLA compile from now on counts as a
    post-prewarm compile.  Called by ``Scheduler.prewarm()`` once the
    ladder is traced."""
    global _armed
    with _lock:
        _install_listener()
        _armed = True


def disarm() -> None:
    global _armed
    with _lock:
        _armed = False


def armed() -> bool:
    return _armed


def post_prewarm_compiles() -> int:
    return int(metrics.POST_PREWARM_COMPILES.value)


@contextlib.contextmanager
def watchdog_window() -> Iterator[Callable[[], int]]:
    """Arm for the duration of a measured window (benches, tests) and
    yield a callable returning the compiles observed inside it."""
    before = post_prewarm_compiles()
    was = _armed
    arm()
    try:
        yield lambda: post_prewarm_compiles() - before
    finally:
        if not was:
            disarm()


@contextlib.contextmanager
def live_path(name: str) -> Iterator[None]:
    """Declare the live path (stream/oneshot/joint/single_pod/...) for
    compiles fired from this thread — the watchdog's ``path`` label."""
    prev = getattr(_tls, "path", None)
    _tls.path = name
    try:
        yield
    finally:
        _tls.path = prev
