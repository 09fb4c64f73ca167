"""The standalone scheduler daemon binary.

``python -m kubernetes_tpu.scheduler --api-server http://... `` is the
analogue of plugin/cmd/kube-scheduler (app/server.go:71-183): flag surface
(options/options.go:55-77), policy-file load (server.go:165-183), an HTTP
mux serving /healthz /metrics /configz (server.go:93-109), and an optional
leader-election-wrapped run on an Endpoints annotation lease
(server.go:142-159).  Without --api-server it runs against a fresh
in-process MemStore + HTTP apiserver (--serve-apiserver), the all-in-one
dev mode.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from kubernetes_tpu.api import types as api
from kubernetes_tpu.utils import gcstats, knobs, threadreg
from kubernetes_tpu.api.policy import (cluster_autoscaler_provider,
                                       default_provider, policy_from_json)
from kubernetes_tpu.scheduler.factory import ConfigFactory
from kubernetes_tpu.utils.leaderelection import (APIResourceLock,
                                                 LeaderElector)
from kubernetes_tpu.utils.logging import configure, get_logger

log = get_logger("scheduler")

DEFAULT_PORT = 10251  # options/options.go:49 SchedulerDefaultPort


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="kube-scheduler (kubernetes_tpu)",
        description="TPU-batched scheduler daemon; watches an apiserver and "
                    "binds pods (plugin/cmd/kube-scheduler analogue)")
    p.add_argument("--api-server", default="",
                   help="apiserver base URL; empty runs an in-process "
                        "MemStore control plane")
    p.add_argument("--serve-apiserver", type=int, default=0, metavar="PORT",
                   help="with no --api-server: also expose the in-process "
                        "store over HTTP on this port (0 = off)")
    p.add_argument("--port", type=int, default=DEFAULT_PORT,
                   help="healthz/metrics/configz port (0 = ephemeral)")
    p.add_argument("--algorithm-provider", default="DefaultProvider",
                   choices=["DefaultProvider", "ClusterAutoscalerProvider"])
    p.add_argument("--policy-config-file", default="",
                   help="scheduler policy JSON (overrides the provider)")
    p.add_argument("--scheduler-name", default=api.DEFAULT_SCHEDULER_NAME)
    p.add_argument("--kube-api-qps", type=float, default=50.0)
    p.add_argument("--kube-api-burst", type=int, default=100)
    p.add_argument("--kube-api-token", default="",
                   help="bearer token for an authenticated apiserver")
    from kubernetes_tpu.client.http import TLSConfig
    TLSConfig.add_flags(p)
    p.add_argument("--hard-pod-affinity-symmetric-weight", type=int,
                   default=None)
    p.add_argument("--leader-elect", action="store_true", default=False)
    p.add_argument("--leader-elect-lease-duration", type=float, default=15.0)
    p.add_argument("--leader-elect-renew-deadline", type=float, default=10.0)
    p.add_argument("--leader-elect-retry-period", type=float, default=2.0)
    p.add_argument("--v", type=int, default=None,
                   help="log verbosity (glog-style; also KT_LOG_V)")
    p.add_argument("--profile-dir", default=knobs.get("KT_PROFILE_DIR"),
                   help="where GET /debug/pprof/trace?seconds=N writes "
                        "its windowed jax.profiler traces (also "
                        "KT_PROFILE_DIR; empty = a new temporary "
                        "directory per trace; view with XProf)")
    p.add_argument("--config", default="",
                   help="KubeSchedulerConfiguration JSON file "
                        "(componentconfig/types.go:426-457); explicit "
                        "flags override file values")
    p.add_argument("--feature-gates", default="",
                   help="comma-separated Name=true|false pairs "
                        "(BatchBindings, StreamingDrain, JointSolver)")
    return p


def apply_component_config(p: argparse.ArgumentParser, argv):
    """--config provides flag DEFAULTS, explicit flags override (the
    reference's scheme-defaults-then-flags order).  Returns parsed opts
    with the validated config folded in."""
    pre, _ = p.parse_known_args(argv)
    if pre.config:
        from kubernetes_tpu.api.componentconfig import (
            KubeSchedulerConfiguration)
        with open(pre.config) as f:
            cfg = KubeSchedulerConfiguration.from_json(f.read())
        errors = cfg.validate()
        if errors:
            raise SystemExit("invalid --config: " + "; ".join(errors))
        p.set_defaults(
            port=cfg.port,
            algorithm_provider=cfg.algorithm_provider,
            policy_config_file=cfg.policy_config_file,
            scheduler_name=cfg.scheduler_name,
            kube_api_qps=cfg.kube_api_qps,
            kube_api_burst=cfg.kube_api_burst,
            hard_pod_affinity_symmetric_weight=(
                cfg.hard_pod_affinity_symmetric_weight),
            feature_gates=cfg.feature_gates,
            enable_profiling=cfg.enable_profiling,
            leader_elect=cfg.leader_election.leader_elect,
            leader_elect_lease_duration=cfg.leader_election.lease_duration,
            leader_elect_renew_deadline=cfg.leader_election.renew_deadline,
            leader_elect_retry_period=cfg.leader_election.retry_period)
    opts = p.parse_args(argv)
    if not hasattr(opts, "enable_profiling"):
        opts.enable_profiling = True  # reference scheduler default
    return opts


def load_policy(opts):
    """createConfig (server.go:165-183): policy file beats provider; file
    policies are validated (CreateFromConfig -> validation.ValidatePolicy)."""
    if opts.policy_config_file:
        from kubernetes_tpu.api.validation import validate_policy
        with open(opts.policy_config_file) as f:
            policy = policy_from_json(f.read())
        validate_policy(policy)
    elif opts.algorithm_provider == "ClusterAutoscalerProvider":
        policy = cluster_autoscaler_provider()
    else:
        policy = default_provider()
    if opts.hard_pod_affinity_symmetric_weight is not None:
        policy.hard_pod_affinity_symmetric_weight = \
            opts.hard_pod_affinity_symmetric_weight
    return policy


def _decisions_route(daemon, query: str) -> tuple[int, bytes, str]:
    """/debug/scheduler/decisions: the flight recorder's batch ring;
    ``?pod=ns/name`` explains one pod's latest decision (chosen node, or
    per-predicate failure counts and top-scoring candidates);
    ``?tenant=name`` filters batch summaries to one tenant's rows (the
    multi-tenant service's per-tenant decision history)."""
    from urllib.parse import parse_qs
    recorder = daemon.config.flight_recorder
    if recorder is None:
        return 404, b"flight recorder disabled", "text/plain"
    q = parse_qs(query)
    pod = q.get("pod", [""])[0]
    if pod:
        decision = recorder.explain(pod)
        if decision is None:
            return (404,
                    json.dumps({"pod": pod,
                                "error": "no recorded decision"}).encode(),
                    "application/json")
        return 200, json.dumps(decision).encode(), "application/json"
    try:
        limit = int(q.get("limit", ["0"])[0] or "0")
    except ValueError:
        return (400, b'{"error": "limit must be an integer"}',
                "application/json")
    tenant = q.get("tenant", [""])[0]
    return (200, json.dumps(recorder.snapshot(
        limit=limit, tenant=tenant)).encode(), "application/json")


def _trace_route(profile_dir: str, query: str) -> tuple[int, bytes, str]:
    """/debug/pprof/trace?seconds=N (net/http/pprof's trace endpoint):
    one windowed ``jax.profiler`` session of the live daemon — device
    operations, PjRt's host events and the ``kt.*`` stages in one file.
    Answers when the profiler's stop has finished, which on a TPU host
    takes 20-33 s of this process's CPU (PERF.md section 6), so traffic
    stalls behind it; 409 while another session is live."""
    from urllib.parse import parse_qs
    from kubernetes_tpu.utils import profiling
    try:
        seconds = float(parse_qs(query).get("seconds", ["1"])[0])
    except ValueError:
        return (400, b'{"error": "seconds must be a number"}',
                "application/json")
    try:
        written = profiling.trace_window(profile_dir, seconds)
    except profiling.TraceBusy as err:
        return (409, json.dumps({"error": str(err)}).encode(),
                "application/json")
    return 200, json.dumps(written).encode(), "application/json"


def _status_mux(factory: ConfigFactory, configz: dict, port: int,
                profile_dir: str = "") -> ThreadingHTTPServer:
    """The daemon's own HTTP surface (server.go:93-109)."""
    from kubernetes_tpu.utils import telemetry
    # The collector's pauses are the daemon's own to count, from its
    # first launch on (utils/gcstats.py); main() also makes it tenure
    # the long-lived heap once start-up is over.
    gcstats.install()
    # Self-scrape ring: the daemon-scoped metric set (queue depth, batch
    # size, attempts) rides the ring next to the default registry so the
    # dashboard's queue/stage/SLO sparklines have their sources.
    telemetry.ensure_started(
        factory.daemon.config.metrics.all_metrics())
    # kt-prof rides the same lifecycle: sampling starts with the mux so
    # the profile covers the daemon's whole life (KT_PROF=0 = no-op).
    from kubernetes_tpu.utils import profiler
    profiler.ensure_started()

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def _send(self, code: int, body: bytes,
                  ctype: str = "text/plain") -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            path, _, query = self.path.partition("?")
            if path == "/healthz":
                self._send(200, b"ok")
            elif path == "/metrics":
                if "format=openmetrics" in query:
                    from kubernetes_tpu.utils.debugmux import \
                        OPENMETRICS_CTYPE
                    self._send(
                        200,
                        factory.daemon.config.metrics
                        .expose_openmetrics().encode(),
                        OPENMETRICS_CTYPE)
                else:
                    self._send(
                        200,
                        factory.daemon.config.metrics.expose().encode())
            elif path == "/configz":
                self._send(200, json.dumps(configz).encode(),
                           "application/json")
            elif path.startswith("/debug/pprof"):
                # The goroutine-dump analogue (app/server.go:96-100): all
                # live thread stacks.  EnableProfiling=false removes the
                # handlers, as the reference's mux does (server.go:96).
                if not configz.get("enableProfiling", True):
                    self._send(404, b"profiling disabled")
                    return
                if path == "/debug/pprof/trace":
                    self._send(*_trace_route(profile_dir, query))
                    return
                from kubernetes_tpu.utils.profiling import thread_stacks
                self._send(200, thread_stacks().encode())
            elif path == "/debug/profile":
                # kt-prof continuous CPU profile (speedscope JSON, or
                # collapsed stacks via ?format=collapsed).  KT_PROF=0 is
                # a client-visible state: 404, never 500.
                from kubernetes_tpu.utils import profiler
                resolved = profiler.render(query)
                if resolved is None:
                    self._send(404, b"profiling disabled (KT_PROF=0)")
                else:
                    body, ctype = resolved
                    self._send(200, body, ctype)
            elif path == "/debug/traces":
                # The span ring as Chrome trace-event JSON: load in
                # Perfetto and the queue_wait -> snapshot -> compile ->
                # transfer -> solve -> readback -> assume -> bind pipeline
                # is visible per batch.
                from kubernetes_tpu.utils import trace
                self._send(200, trace.to_chrome_trace().encode(),
                           "application/json")
            elif path == "/debug/scheduler/decisions":
                self._send(*_decisions_route(factory.daemon, query))
            elif path == "/debug/timeseries":
                from kubernetes_tpu.utils import telemetry
                self._send(200, telemetry.timeseries_json().encode(),
                           "application/json")
            elif path == "/debug/dashboard":
                from kubernetes_tpu.utils import telemetry
                self._send(200, telemetry.dashboard_html().encode(),
                           "text/html; charset=utf-8")
            elif path == "/debug/vars":
                from kubernetes_tpu.utils.metrics import (
                    CACHE_INVARIANT_VIOLATIONS, POST_PREWARM_COMPILES)
                cache = factory.algorithm.cache
                node_capacity, node_rows_free = cache.node_rows()
                queue = factory.daemon.queue
                self._send(200, json.dumps({
                    "queueDepth": len(queue),
                    "queueHighWatermark": queue.high_watermark,
                    "queuePeakDepth": queue.peak_depth,
                    # The degradation ladder's operator surface: 1 while
                    # the daemon sheds load (largest-bucket drains, gang
                    # holds bypassed).
                    "degraded": queue.degraded(),
                    # The serving surface: the formation deadline in
                    # force, the former's adaptive target bucket, and
                    # the warm-start audit's per-signature cache stats.
                    "batchDeadlineMs": round(
                        factory.daemon.pipeline.former.deadline_s * 1e3,
                        1),
                    "batchFormerTarget":
                        factory.daemon.pipeline.former.target,
                    "prewarmCacheStats":
                        factory.daemon.prewarm_cache_stats,
                    # The SLO plane: live burn rates + budget left
                    # (scheduler/slo.py) and the device-side watchdog.
                    "slo": factory.slo.report(),
                    # The device-fault plane: engine mode (device/host),
                    # last classified fault, bisect cap, gate rejects
                    # (engine/guard.py).
                    "engine": factory.algorithm.guard.report(),
                    "postPrewarmCompiles": POST_PREWARM_COMPILES.value,
                    "invariantViolations":
                        CACHE_INVARIANT_VIOLATIONS.value,
                    "lastRecovery": getattr(factory, "last_recovery",
                                            None),
                    # Active-active HA (scheduler/shards.py): this
                    # incarnation's id, the shards it holds, and the
                    # recent shard-takeover reconciles; null when
                    # running single-scheduler (KT_HA_SHARDS=0).
                    "ha": (factory.shards.report()
                           if getattr(factory, "shards", None)
                           is not None else None),
                    # The multi-tenant solver service (tenancy/): per-
                    # tenant mode/weights/trips/fault attribution; null
                    # when KT_TENANTS is unset.
                    "tenancy": (factory.tenancy.report()
                                if getattr(factory, "tenancy", None)
                                is not None else None),
                    "shardRecoveries": getattr(
                        factory, "shard_recoveries", [])[-8:],
                    # Client-side backpressure against a shedding
                    # apiserver (utils/flowcontrol.py): the AIMD bind
                    # window + retry-budget saturation; null when the
                    # store is in-process (no wire, nothing to shed).
                    "overload": (factory.store.flow_report()
                                 if hasattr(factory.store, "flow_report")
                                 else None),
                    "cachedPods": cache.pod_count(),
                    # counts the cache keeps: nothing here builds the
                    # node tensors (a starting daemon's list is partial)
                    "cachedNodes": cache.node_count(),
                    "nodeCapacity": node_capacity,
                    "nodeRowsFree": node_rows_free,
                    "cacheStats": cache.stats,
                    "generation": cache.generation,
                    # The three counters of the cache (ARCHITECTURE.md)
                    # and what the feature build kept under the node
                    # epoch: launches that reused it, misses by cause.
                    "tensorEpoch": cache.tensor_epoch,
                    "nodeEpoch": cache.node_epoch,
                    # (null behind a shared solver service, whose
                    # engine holds the plan)
                    "featurePlan": (
                        factory.algorithm.plan_report()
                        if hasattr(factory.algorithm, "plan_report")
                        else None),
                }).encode(), "application/json")
            elif path == "/tenancy":
                if getattr(factory, "tenancy", None) is None:
                    self._send(404, b"tenancy disabled")
                else:
                    self._send(200,
                               json.dumps(factory.tenancy.report())
                               .encode(), "application/json")
            else:
                self._send(404, b"not found")

        def do_POST(self):
            # The solver-service boundary over the daemon's existing
            # HTTP surface: with KT_TENANTS set, other control planes
            # POST /solve {tenant, pods:[...]} and get placements from
            # THIS daemon's device (tenancy/service.solve_route).
            path = self.path.partition("?")[0]
            if path != "/solve":
                self._send(404, b"not found")
                return
            if getattr(factory, "tenancy", None) is None:
                self._send(404, b"tenancy disabled")
                return
            try:
                clen = int(self.headers.get("Content-Length", "0") or 0)
            except ValueError:
                clen = 0
            body = self.rfile.read(clen) if clen else b""
            from kubernetes_tpu.tenancy.service import solve_route
            self._send(*solve_route(factory.tenancy, body))

    server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    threadreg.spawn(server.serve_forever, name="scheduler-status-http")
    return server


def main(argv=None) -> int:
    opts = apply_component_config(build_parser(), argv)
    configure(v=opts.v)
    from kubernetes_tpu.utils import featuregate
    try:
        gates = featuregate.FeatureGate.parse(opts.feature_gates)
    except ValueError as err:
        raise SystemExit(f"--feature-gates: {err}")
    featuregate.set_default(gates)
    # Initialize the backend NOW and name it: with JAX_PLATFORMS pinned,
    # a missing or busy chip fails start-up here instead of surfacing as
    # a classified `lost` fault (and a silent host-engine run) on the
    # first drain.
    from kubernetes_tpu.engine import devicestats
    device = devicestats.device_info()
    log.info("engine device: platform=%s kind=%s count=%d",
             device["platform"], device["kind"], device["count"])
    policy = load_policy(opts)
    configz = {
        "apiServer": opts.api_server or "(in-process)",
        "algorithmProvider": opts.algorithm_provider,
        "policyConfigFile": opts.policy_config_file,
        "schedulerName": opts.scheduler_name,
        "kubeAPIQPS": opts.kube_api_qps,
        "kubeAPIBurst": opts.kube_api_burst,
        "leaderElect": opts.leader_elect,
        "featureGates": gates.as_dict(),
        "enableProfiling": getattr(opts, "enable_profiling", True),
        "predicates": [s.name for s in policy.predicates],
        "priorities": [[s.name, s.weight] for s in policy.priorities],
    }

    if opts.api_server:
        from kubernetes_tpu.client.http import APIClient, TLSConfig
        source = APIClient(opts.api_server, qps=opts.kube_api_qps,
                           burst=opts.kube_api_burst,
                           token=opts.kube_api_token,
                           tls=TLSConfig.from_opts(opts))
    else:
        from kubernetes_tpu.apiserver.memstore import MemStore
        source = MemStore()
        if opts.serve_apiserver:
            from kubernetes_tpu.apiserver.server import serve
            serve(source, port=opts.serve_apiserver)
            log.info("in-process apiserver on :%d", opts.serve_apiserver)

    # source is a ready APIClient (credentials + TLS) or a MemStore;
    # qps/burst still feed the factory's event-sink rate bucket.
    factory = ConfigFactory(source, policy=policy,
                            scheduler_name=opts.scheduler_name,
                            qps=opts.kube_api_qps,
                            burst=opts.kube_api_burst)
    mux = _status_mux(factory, configz, opts.port,
                      profile_dir=opts.profile_dir)
    log.info("status http on :%d (healthz, metrics, configz)",
             mux.server_address[1])

    stop = threading.Event()

    def shutdown(*_):
        stop.set()

    def tenure_heap() -> None:
        # Start-up is over: jax, the compiled programs, the nodes and
        # the listed resident pods leave the cyclic collector's pass,
        # and so do the survivors of every full collection after it.
        log.info("heap tenured: %d objects",
                 gcstats.install().baseline())

    signal.signal(signal.SIGTERM, shutdown)
    signal.signal(signal.SIGINT, shutdown)

    if opts.leader_elect:
        identity = f"{socket.gethostname()}-{os.getpid()}"
        lock = APIResourceLock(factory.store) if opts.api_server else None
        if lock is None:
            log.warning("--leader-elect without --api-server: using an "
                        "in-process lock (single candidate)")
            from kubernetes_tpu.utils.leaderelection import InMemoryLock
            lock = InMemoryLock()
        elector = LeaderElector(
            lock=lock, identity=identity,
            lease_duration=opts.leader_elect_lease_duration,
            renew_deadline=opts.leader_elect_renew_deadline,
            retry_period=opts.leader_elect_retry_period,
            on_started_leading=lambda: (log.info("leading as %s", identity),
                                        factory.run(started=tenure_heap)),
            on_stopped_leading=lambda: (log.warning("lost lease; exiting"),
                                        stop.set()))
        elector.run()
        log.info("leader election: candidate %s", identity)
    else:
        factory.run(started=tenure_heap)
        log.info("scheduler loop running (no leader election)")

    stop.wait()
    factory.stop()
    mux.shutdown()
    gcstats.uninstall()
    return 0


if __name__ == "__main__":
    sys.exit(main())
