"""Joint batched assignment quality tests (BASELINE.json's last config):
the LP-relaxed global solve must dominate the greedy baseline on aggregate
quality while honoring every predicate."""

from __future__ import annotations

import numpy as np

from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
from kubernetes_tpu.perf import synth

from helpers import compile_cache_at, make_node, make_pod


def _placed_load(sched, pods, placements):
    """(placed count, per-node cpu load dict) for a solved batch."""
    load: dict[str, int] = {}
    placed = 0
    for pod, dest in zip(pods, placements):
        if dest is None:
            continue
        placed += 1
        load[dest] = load.get(dest, 0) + pod.resource_request().milli_cpu
    return placed, load


def test_joint_honors_capacity():
    s = GenericScheduler()
    for i in range(4):
        s.cache.add_node(make_node(f"n{i}", milli_cpu=1000))
    pods = [make_pod(f"jp{i}", cpu="300m") for i in range(16)]
    got = s.schedule_batch(pods, joint=True)
    placed, load = _placed_load(s, pods, got)
    assert placed == 12  # 3 per node x 4 nodes
    assert all(v <= 1000 for v in load.values())


def test_joint_places_at_least_as_many_when_contended():
    # Mixed big/small pods on tight nodes: greedy order can strand
    # capacity; the joint solve must not place fewer.
    def build():
        s = GenericScheduler()
        for i in range(6):
            s.cache.add_node(make_node(f"n{i}", milli_cpu=1000,
                                       memory=4 * 1024 ** 3))
        rng = np.random.RandomState(3)
        pods = []
        for i in range(40):
            cpu = int(rng.choice([100, 400, 700]))
            pods.append(make_pod(f"mix{i}", cpu=f"{cpu}m", memory="128Mi"))
        return s, pods

    s1, pods1 = build()
    greedy = s1.schedule_batch(pods1)
    s2, pods2 = build()
    joint = s2.schedule_batch(pods2, joint=True)
    g_placed, g_load = _placed_load(s1, pods1, greedy)
    j_placed, j_load = _placed_load(s2, pods2, joint)
    assert all(v <= 1000 for v in j_load.values())
    assert j_placed >= g_placed


def test_joint_respects_predicates():
    # Node selector + taints must hold in the joint mode as well.
    s = GenericScheduler()
    s.cache.add_node(make_node("gpu", labels={"accel": "tpu"}))
    s.cache.add_node(make_node(
        "fenced", taints=[{"key": "k", "value": "v",
                           "effect": "NoSchedule"}]))
    s.cache.add_node(make_node("plain"))
    pods = [make_pod("sel", node_selector={"accel": "tpu"}),
            make_pod("free1"), make_pod("free2")]
    got = s.schedule_batch(pods, joint=True)
    assert got[0] == "gpu"
    assert "fenced" not in got


def test_joint_on_synthetic_rig():
    sched, pods = synth.make_rig(30, 200, profile="mixed")
    got = sched.schedule_batch(pods, joint=True)
    assert sum(1 for g in got if g is not None) >= 195  # ample capacity


def test_joint_warm_start_reuses_persistent_compile_cache(tmp_path):
    """The ~77 s joint wall-clock was compile tax: the pipeline's
    host-side glue (argsort + ~75 per-field jnp.take permutes) lived
    OUTSIDE any jit, so nothing the persistent compilation cache stored
    covered the solve as a unit.  Now the whole pipeline is ONE jitted
    executable (Solver._solve_joint_jit): cold populates the persistent
    cache, and a warm re-trace (fresh executables after
    jax.clear_caches, what a daemon restart pays) deserializes instead
    of recompiling — pinned via the compile_cache_{hits,misses}_total
    counters and the cold-vs-warm wall-clock gap."""
    import time

    import jax

    from kubernetes_tpu.engine import compile_cache
    from kubernetes_tpu.utils.metrics import (COMPILE_CACHE_HITS,
                                              COMPILE_CACHE_MISSES)

    with compile_cache_at(str(tmp_path)):
        assert compile_cache.configure() == str(tmp_path)

        def build():
            s = GenericScheduler()
            for i in range(5):
                s.cache.add_node(make_node(f"cw{i}", milli_cpu=1000))
            return s, [make_pod(f"cw-p{i}", cpu="300m")
                       for i in range(12)]

        misses_before = COMPILE_CACHE_MISSES.value
        s1, pods1 = build()
        t0 = time.perf_counter()
        cold_got = s1.schedule_batch(pods1, joint=True)
        cold_s = time.perf_counter() - t0
        assert COMPILE_CACHE_MISSES.value > misses_before  # populated
        hits_before = COMPILE_CACHE_HITS.value
        jax.clear_caches()  # drop in-memory executables: restart analogue
        s2, pods2 = build()
        t0 = time.perf_counter()
        warm_got = s2.schedule_batch(pods2, joint=True)
        warm_s = time.perf_counter() - t0
        assert warm_got == cold_got
        assert COMPILE_CACHE_HITS.value > hits_before, \
            "warm joint solve recompiled instead of hitting the " \
            "persistent cache"
        assert warm_s < cold_s, (warm_s, cold_s)


def test_prewarm_covers_the_single_pod_path_and_scatter(tmp_path):
    """ISSUE 8 warm-start audit: after ``prewarm()`` NO post-warm-up
    decision path may mint a fresh XLA compile on the clock.  Measured
    before the fix, the single-pod path (evaluate/masks/select_hosts at
    P=1 — the first ``schedule_one`` and every recovery parity probe)
    paid ~30 compiles (~0.7 s cold), and the dirty-row scatter kernel
    compiled mid-drain on the first post-assume drain; both signatures
    dodged the ladder prewarm entirely.  Cold-vs-warm pin: a restart
    analogue (``jax.clear_caches``) re-traces everything prewarm traced
    out of the persistent cache — hits only, zero misses."""
    import jax

    from kubernetes_tpu.engine import compile_cache
    from kubernetes_tpu.perf import synth
    from kubernetes_tpu.scheduler.binder import InMemoryBinder
    from kubernetes_tpu.scheduler.scheduler import (Scheduler,
                                                    SchedulerConfig)
    from kubernetes_tpu.utils.metrics import (COMPILE_CACHE_HITS,
                                              COMPILE_CACHE_MISSES)

    with compile_cache_at(str(tmp_path)):
        assert compile_cache.configure() == str(tmp_path)

        def build() -> Scheduler:
            sched, _ = synth.make_rig(16, 0)
            d = Scheduler(SchedulerConfig(algorithm=sched,
                                          binder=InMemoryBinder(),
                                          async_bind=False))
            d.STREAM_THRESHOLD = 16
            d.stream_chunk = 16
            d.stream_min_bucket = 8
            return d

        # Drop executables earlier tests left in process memory: the
        # cold pass must actually compile (and persist) into THIS cache
        # dir for the warm half of the pin to mean anything.
        jax.clear_caches()
        daemon = build()
        timings = daemon.prewarm()
        assert timings  # the ladder traced
        # The audit's per-signature cache stats cover the ladder AND the
        # single-pod + scatter signatures the ladder used to miss.
        stats = daemon.prewarm_cache_stats
        assert "single_pod" in stats and "scatter" in stats
        assert "explain" in stats      # the failure-detail pass (PR 30)
        assert all(b in stats for b in timings)
        # Post-prewarm, the previously-dodging paths compile NOTHING on
        # the clock: a schedule_one and a dirtying drain are all cache
        # hits already live in memory.
        misses0 = COMPILE_CACHE_MISSES.value
        daemon.enqueue(synth.make_pods(1, name_prefix="sp")[0])
        assert daemon.schedule_one(timeout=0.1)
        for p in synth.make_pods(12, name_prefix="dirty"):
            daemon.enqueue(p)
        daemon.schedule_pending(wait_first=False)  # scatters dirty rows
        daemon.wait_for_binds()
        # a pod no node holds: the drain's failure-detail pass
        big = synth.make_pods(1, name_prefix="big")[0]
        big.containers[0].requests["cpu"] = "4096"
        daemon.enqueue(big)
        daemon.schedule_pending(wait_first=False)
        daemon.wait_for_binds()
        assert daemon.config.recorder.events(big.key)[-1].reason \
            == "FailedScheduling"
        assert COMPILE_CACHE_MISSES.value == misses0, \
            "a post-prewarm decision path still compiles on the clock"
        # Cold vs warm: a fresh-executable re-trace (restart analogue)
        # deserializes every prewarmed signature from the persistent
        # cache instead of recompiling.
        jax.clear_caches()
        hits0, misses0 = COMPILE_CACHE_HITS.value, \
            COMPILE_CACHE_MISSES.value
        daemon2 = build()
        daemon2.prewarm()
        assert COMPILE_CACHE_HITS.value > hits0
        assert COMPILE_CACHE_MISSES.value == misses0, \
            "warm prewarm recompiled instead of hitting the persistent " \
            "cache"
