#!/usr/bin/env python3
"""The scheduler daemon under the benchmark: the product's own entry
(``kubernetes_tpu.scheduler.__main__.main``) plus ONE control thread.

The control thread reads one-line commands from stdin and answers by
writing a file into the control directory (``--ctl-dir``):

  ``trace-start <dir>``  start one ``jax.profiler`` session -> ``trace-start.ok``
  ``trace-stop``         stop it                            -> ``trace-stop.ok``
  ``stats``              device memory as JAX reports it    -> ``stats.json``
  ``gc-watch``           start timing the collector's pauses -> ``gc-watch.ok``
  ``gc-read``            the pauses since then               -> ``gc.json``

A ``--trace 0`` run uses this same wrapper with the thread idle until the
``stats`` request after the window.  Only the process that holds the chip
can trace it or read its memory, which is why this lives here and not in
the runner.  Everything after ``--`` goes to the product's ``main``.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time


def _answer(ctl_dir: str, name: str, body: str = "ok") -> None:
    tmp = os.path.join(ctl_dir, name + ".tmp")
    with open(tmp, "w") as f:
        f.write(body)
    os.replace(tmp, os.path.join(ctl_dir, name))


def _device_stats() -> dict:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    d0 = jax.local_devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": jax.local_device_count(),
            "memory_peak_bytes": max(peaks)}


class _GcWatch:
    """Wall time the cyclic collector held the interpreter (every Python
    thread of the daemon stands still for it), from ``gc.callbacks``.
    Installed only when the runner asks (the traced run): the callback
    runs twice per collection, young generations included."""

    def __init__(self):
        self.started = None
        self.total_s = self.longest_s = 0.0
        self.collections = 0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.started = time.perf_counter()
        elif self.started is not None:
            took = time.perf_counter() - self.started
            self.started = None
            self.total_s += took
            self.collections += 1
            self.longest_s = max(self.longest_s, took)

    def snapshot(self) -> dict:
        return {"gc_pause_s": self.total_s, "gc_pause_max_s": self.longest_s,
                "gc_collections": self.collections}


def _control(ctl_dir: str) -> None:
    watch = None
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        try:
            if words[0] == "trace-start":
                import jax
                opts = jax.profiler.ProfileOptions()
                # The Python tracer hooks every call of every thread of a
                # host-bound daemon; TraceMe spans (level 2) are enough
                # to say what the host was doing in an idle gap.
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(words[1], profiler_options=opts)
                _answer(ctl_dir, "trace-start.ok")
            elif words[0] == "trace-stop":
                import jax
                jax.profiler.stop_trace()
                _answer(ctl_dir, "trace-stop.ok")
            elif words[0] == "stats":
                _answer(ctl_dir, "stats.json", json.dumps(_device_stats()))
            elif words[0] == "gc-watch":
                watch = _GcWatch()
                gc.callbacks.append(watch)
                _answer(ctl_dir, "gc-watch.ok")
            elif words[0] == "gc-read":
                _answer(ctl_dir, "gc.json", json.dumps(
                    watch.snapshot() if watch else {}))
        except Exception as err:  # noqa: BLE001 — reported to the runner,
            # which fails the run; the daemon itself keeps serving.
            _answer(ctl_dir, words[0] + ".err", repr(err))


def main(argv: list[str]) -> int:
    if len(argv) < 3 or argv[0] != "--ctl-dir" or argv[2] != "--":
        print("usage: daemon.py --ctl-dir DIR -- <scheduler flags>",
              file=sys.stderr)
        return 2
    ctl_dir = argv[1]
    os.makedirs(ctl_dir, exist_ok=True)
    threading.Thread(target=_control, args=(ctl_dir,), daemon=True,
                     name="bench-control").start()
    from kubernetes_tpu.scheduler.__main__ import main as scheduler_main
    return scheduler_main(argv[3:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
