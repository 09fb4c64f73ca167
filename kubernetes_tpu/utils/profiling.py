"""Profiling hooks: the TPU analogue of the reference's pprof surface.

Every reference daemon serves /debug/pprof (app/server.go:96-100) and the
perf rig collects cpu/mem/block profiles
(test/component/scheduler/perf/test-performance.sh).  Here the device side
is XLA, so the analogue of ``/debug/pprof/trace?seconds=N`` is ONE
``jax.profiler`` session over a window of the running daemon
(``trace_window``): device operations, PjRt's host events and the
program's own ``kt.*`` stages (utils/trace.py) in one ``.xplane.pb``,
viewable in TensorBoard/XProf.  The host side is the /debug/stacks thread
dump the daemon mux serves (the goroutine-dump analogue).
"""

from __future__ import annotations

import os
import sys
import tempfile
import threading
import time
import traceback

# An operator's window: long enough for a dozen collector pauses, short
# enough that the trace stays loadable.
MAX_TRACE_SECONDS = 30.0

_session = threading.Lock()     # the profiler allows one session a process


class TraceBusy(RuntimeError):
    """Another profiler session is live in this process."""


def trace_window(profile_dir: str, seconds: float) -> dict:
    """Trace ``seconds`` (capped at ``MAX_TRACE_SECONDS``) of the running
    process into ``profile_dir`` (empty: a new temporary directory) and
    return where the trace lies.  The Python tracer stays off — it hooks
    every call of every thread of a host-bound daemon — and TraceMe
    events (level 2) say what the host did in an idle gap.  Stopping a
    session takes the profiler tens of seconds of this process's CPU on
    a TPU host: the caller waits for it, and so does the traffic.
    Raises ``TraceBusy`` while another session is live, whoever started
    it."""
    import jax
    if not _session.acquire(blocking=False):
        raise TraceBusy("a profiler session is already live")
    try:
        seconds = max(0.0, min(float(seconds), MAX_TRACE_SECONDS))
        out = profile_dir or tempfile.mkdtemp(prefix="kt-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        try:
            jax.profiler.start_trace(out, profiler_options=opts)
        except RuntimeError as err:
            # a session opened around this hook (a rig's own)
            raise TraceBusy(str(err)) from err
        t0 = time.perf_counter()
        try:
            time.sleep(seconds)
        finally:
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
        return {"dir": os.path.abspath(out),
                "traced_s": round(t1 - t0, 3),
                "stop_s": round(time.perf_counter() - t1, 3)}
    finally:
        _session.release()


def annotation_class() -> type:
    """``jax.profiler.TraceAnnotation``: what utils/trace.py opens its
    ``kt.*`` host events through, once the process has imported JAX
    (this module is where the utils layer may touch it)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


def thread_stacks() -> str:
    """All live thread stacks as text — /debug/pprof/goroutine?debug=2."""
    frames = sys._current_frames()
    out = []
    for t in threading.enumerate():
        frame = frames.get(t.ident)
        out.append(f"thread {t.name} (daemon={t.daemon}, "
                   f"alive={t.is_alive()}):")
        if frame is not None:
            out.extend("  " + ln for ln in
                       traceback.format_stack(frame))
        out.append("")
    return "\n".join(out)
