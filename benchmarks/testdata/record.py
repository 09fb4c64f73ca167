#!/usr/bin/env python3
"""How ``scan_small.xplane.pb`` was recorded (on a TPU v5e, PR 26):

  python3 benchmarks/testdata/record.py <out dir>

A 64-step ``lax.scan`` over a [512] plane inside ``jit(_solve_scan)`` —
the shape of the program's scan at a size whose trace is a few tens of
KB — run three times with an idle gap between, under the same profiler
options the benchmark's daemon wrapper uses.  ``tests/test_reduce_trace.py``
holds the reduction to what this trace is known to contain.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def _solve_scan(used, reqs):
    def step(used, req):
        score = jnp.where(used + req <= 1000, 1000 - used, -1)
        best = jnp.argmax(score)
        return used.at[best].add(req), best
    return jax.lax.scan(step, used, reqs)


def main(out_dir: str) -> None:
    f = jax.jit(_solve_scan)
    used = jnp.zeros((512,), jnp.int32)
    reqs = jnp.ones((64,), jnp.int32)
    jax.block_until_ready(f(used, reqs))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench_launch"):
            jax.block_until_ready(f(used, reqs))
        with jax.profiler.TraceAnnotation("bench_idle"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(found[0], os.path.join(out_dir, "scan_small.xplane.pb"))
    print(found[0], os.path.getsize(found[0]), jax.devices())


if __name__ == "__main__":
    main(sys.argv[1])
