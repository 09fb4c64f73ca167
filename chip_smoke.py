#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the served scheduling path once, through the entry points a user
would call, at the shape every record of this repo uses — 5,000 nodes x
30,000 pending pods, ``mixed`` profile, default policy (BASELINE.json's
north star; ``perf/synth.py``, seeded) — and checks the answers by the
repo's own means:

A. **served**: client -> apiserver -> watch -> queue -> feature build ->
   scatter -> device scan -> readback -> assume -> bind, with the daemon
   ``python -m kubernetes_tpu.scheduler`` as the process that holds the
   chip.  Judged from the client's side (every pod bound, no node over
   its allocatable) and from the daemon's (engine mode ``device``, no
   classified device fault, no solve fallback, no rejected bind).  A
   second daemon start must take the chip over from the first and find
   the compile cache the first one filled.
B. **answers**: ``python -m kubernetes_tpu.perf.chipcheck`` — oracle
   parity on a ``rich`` cluster, the streamed scan row-for-row against
   the NumPy host solver, the half-width score plane, the select.
C. **extender**: ``python -m kubernetes_tpu.server.extender`` answering
   ``filter`` + ``prioritize`` calls that carry the full node list; the
   feasible sets equal ``oracle.find_nodes_that_fit``.

This process never imports JAX: one process holds a chip, so each phase
starts ONE child pinned to the platform (``JAX_PLATFORMS``), which must
report that platform from inside, and stops it before the next phase
starts.  Any failed check, dead child or timeout ends the run non-zero
with no result line; nothing here can make it pass on a CPU — tier-1
calls the phase functions with ``"cpu"`` at a tiny shape instead.

Prints, as the last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

from kubernetes_tpu import oracle
from kubernetes_tpu.api import types as api
from kubernetes_tpu.apiserver import native
from kubernetes_tpu.client.http import APIClient
from kubernetes_tpu.perf import synth

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

PLATFORM = "tpu"
N_NODES = 5000
N_PODS = 30000
N_EXTENDER_PODS = 8
# The whole run must end inside the chip check's 1200 s.
TOTAL_DEADLINE_S = 1140

# What perf/soak.py gives its daemons; KT_STREAM_CHUNK's shipped default
# (0) is unmeasured on a local chip (scheduler/scheduler.py).
DAEMON_ENV = {"KT_PREWARM": "1", "KT_STREAM_CHUNK": "4096"}


class SmokeFailure(Exception):
    """A check failed, a child died or a deadline passed."""


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


# -- children ---------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _tail(path: str, n: int = 3000) -> str:
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        f.seek(max(f.tell() - n, 0))
        return f.read().decode(errors="replace")


class Child:
    """One started process with its output in a log file."""

    def __init__(self, name: str, cmd: list[str], platform: str | None,
                 extra_env: dict | None = None):
        os.makedirs(LOG_DIR, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        env = dict(os.environ, PYTHONPATH=REPO, **(extra_env or {}))
        if platform is not None:
            # Pinned: a missing or busy chip is an error at backend
            # init, never a quiet fall-through to another platform.
            env["JAX_PLATFORMS"] = platform
        with open(self.log_path, "wb") as out:
            self.proc = subprocess.Popen(
                cmd, env=env, cwd=REPO, stdout=out,
                stderr=subprocess.STDOUT)

    def require_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(f"{self.name} exited with code {rc}:\n"
                               f"{_tail(self.log_path)}")

    def stop(self, graceful_s: float = 60.0) -> int | None:
        """SIGTERM and wait; SIGKILL past ``graceful_s``.  Returns the
        exit code, None when it had to be killed."""
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=graceful_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                return None
        return self.proc.returncode


def _get(url: str, timeout: float = 10.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def _wait_until(what: str, cond, child: Child, timeout_s: float,
                period_s: float = 0.5):
    """Poll ``cond`` (OSError = not up yet) until truthy; the child
    dying or the deadline passing is a failure."""
    deadline = time.monotonic() + timeout_s
    while True:
        child.require_alive()
        try:
            got = cond()
        except OSError:
            got = None
        if got:
            return got
        if time.monotonic() > deadline:
            raise SmokeFailure(f"{child.name}: {what} not reached in "
                               f"{timeout_s:.0f} s:\n"
                               f"{_tail(child.log_path)}")
        time.sleep(period_s)


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _require_device(phase: str, device: dict, platform: str) -> dict:
    log(f"phase {phase} ran on platform: {device.get('platform')} "
        f"device_kind: {device.get('kind')} "
        f"device count: {device.get('count')}")
    _require(device.get("platform") == platform,
             f"phase {phase} ran on {device.get('platform')!r}, "
             f"not {platform!r}")
    return device


# -- phase A: the served path -------------------------------------------------

def _start_apiserver() -> tuple[Child, str]:
    port = _free_port()
    if native.toolchain_available():
        cmd = [native.native_binary(), "--port", str(port)]
        log("apiserver: native-c++ (built from native/apiserver.cpp)")
    else:
        cmd = [sys.executable, "-m", "kubernetes_tpu.apiserver",
               "--port", str(port)]
        log("apiserver: python (this machine has no make/g++ to build "
            "the native one)")
    child = Child("apiserver", cmd, platform=None)
    url = f"http://127.0.0.1:{port}"
    _wait_until("healthz", lambda: _get(url + "/healthz"), child, 30)
    return child, url


def _create_lists(client: APIClient, kind: str, objs: list[dict]) -> None:
    """Lists of 1,000 from four creator connections (the
    perf/harness.py density_wire creator pattern)."""
    bodies = [objs[i:i + 1000] for i in range(0, len(objs), 1000)]
    with ThreadPoolExecutor(4) as pool:
        for body, results in zip(bodies, pool.map(
                lambda b: client.create_list(kind, b), bodies)):
            bad = [r for r in results if r.get("code") != 201]
            _require(len(results) == len(body) and not bad,
                     f"creating {kind}: {len(bad)} of {len(body)} "
                     f"rejected; first: {bad[:1]}")


class Daemon:
    """``python -m kubernetes_tpu.scheduler`` holding the chip."""

    def __init__(self, name: str, api_url: str, platform: str):
        self.port = _free_port()
        self.child = Child(
            name,
            [sys.executable, "-m", "kubernetes_tpu.scheduler",
             "--api-server", api_url, "--port", str(self.port),
             "--kube-api-qps", "5000", "--kube-api-burst", "5000"],
            platform, DAEMON_ENV)
        self.url = f"http://127.0.0.1:{self.port}"

    def vars(self) -> dict:
        return json.loads(_get(self.url + "/debug/vars"))

    def wait_prewarmed(self, timeout_s: float) -> dict:
        """healthz comes up BEFORE prewarm finishes; pods created
        mid-prewarm would compile on the clock."""
        return _wait_until(
            "prewarm", lambda: self.vars()["prewarmCacheStats"],
            self.child, timeout_s, period_s=1.0)

    def metric_sums(self, families: tuple[str, ...]) -> dict:
        """Each counter family summed over its label sets; a family the
        daemon does not export at all is a failure, not a zero."""
        text = _get(self.url + "/metrics").decode()
        sums = {}
        for family in families:
            _require(f"# TYPE {family} " in text,
                     f"{self.child.name} /metrics has no {family}")
            sums[family] = sum(
                float(line.rsplit(None, 1)[-1])
                for line in text.splitlines()
                if line.startswith((family + "{", family + " ")))
        return sums

    def require_clean(self, platform: str) -> dict:
        """The daemon's own account: it solved on the device, and
        nothing was classified, fallen back from or refused."""
        v = self.vars()
        engine = v["engine"]
        device = _require_device(
            f"A ({self.child.name})",
            {"platform": engine["platform"], "kind": engine["deviceKind"],
             "count": engine["deviceCount"]}, platform)
        counters = self.metric_sums((
            "scheduler_device_faults_total",
            "scheduler_solve_fallback_total",
            "scheduler_sanity_rejected_binds_total"))
        log(f"{self.child.name}: engine mode {engine['mode']}, lastFault "
            f"{engine['lastFault']}, hostModeSeconds "
            f"{engine['hostModeSeconds']}, invariantViolations "
            f"{v['invariantViolations']}, postPrewarmCompiles "
            f"{v['postPrewarmCompiles']}, {counters}")
        _require(engine["mode"] == "device" and engine["lastFault"] is None
                 and engine["hostModeSeconds"] == 0
                 and v["invariantViolations"] == 0
                 and not any(counters.values()),
                 f"{self.child.name} did not run clean on the device: "
                 f"engine {engine}, invariantViolations "
                 f"{v['invariantViolations']}, {counters}")
        return device

    def stop(self) -> None:
        rc = self.child.stop()
        _require(rc == 0, f"{self.child.name} did not exit 0 on SIGTERM "
                          f"(code {rc}):\n{_tail(self.child.log_path)}")


def _wait_all_bound(client: APIClient, daemon: Daemon, total: int,
                    timeout_s: float) -> None:
    """Until the apiserver lists no pod without a node."""
    deadline = time.monotonic() + timeout_s
    left, since = None, time.monotonic()
    while True:
        daemon.child.require_alive()
        now_left = len(
            client.list("pods", field_selector="spec.nodeName=")[0])
        if now_left != left:
            left, since = now_left, time.monotonic()
            log(f"  bound {total - left}/{total}")
        if left == 0:
            return
        if time.monotonic() - since > 120 or time.monotonic() > deadline:
            raise SmokeFailure(
                f"binding stalled at {total - left}/{total} (no progress "
                f"for 120 s, or {timeout_s:.0f} s in all):\n"
                f"{_tail(daemon.child.log_path)}")
        time.sleep(2.0)


def _judge_placements(client: APIClient, n_nodes: int, n_pods: int) -> dict:
    """From the apiserver's own lists: every pod carries a node that
    exists, and no node holds more than its allocatable."""
    nodes = {n.name: n for n in
             map(api.node_from_json, client.list("nodes")[0])}
    pods = client.list("pods")[0]
    _require(len(nodes) == n_nodes and len(pods) == n_pods,
             f"apiserver lists {len(nodes)} nodes / {len(pods)} pods, "
             f"expected {n_nodes} / {n_pods}")
    used: dict[str, list[int]] = {}
    for d in pods:
        pod = api.pod_from_json(d)
        _require(bool(pod.node_name), f"pod {pod.key} is not bound")
        _require(pod.node_name in nodes,
                 f"pod {pod.key} bound to unknown node {pod.node_name}")
        req = pod.resource_request()
        u = used.setdefault(pod.node_name, [0, 0, 0])
        u[0] += 1
        u[1] += req.milli_cpu
        u[2] += req.memory
    for name, (count, cpu, mem) in used.items():
        node = nodes[name]
        _require(count <= node.allocatable_pods
                 and cpu <= node.allocatable_milli_cpu
                 and mem <= node.allocatable_memory,
                 f"node {name} over its allocatable: {count} pods / "
                 f"{cpu}m / {mem} B")
    return {"bound": len(pods), "nodes_used": len(used),
            "max_pods_on_a_node": max(u[0] for u in used.values())}


def _serve_wave(name: str, api_url: str, platform: str, client: APIClient,
                make_pods, n_nodes: int, total: int) -> dict:
    """One daemon life: start (it takes the chip), prewarm, create the
    pods ``make_pods()`` returns, wait until ``total`` are bound, judge
    from both sides, SIGTERM (it releases the chip)."""
    t0 = time.monotonic()
    daemon = Daemon(name, api_url, platform)
    try:
        pod_jsons = make_pods()   # made while the daemon prewarms
        stats = daemon.wait_prewarmed(420)
        hits = sum(s["hits"] for s in stats.values())
        misses = sum(s["misses"] for s in stats.values())
        log(f"{name} prewarmed in {time.monotonic() - t0:.0f} s (set-up "
            f"wall): compile cache hits {hits}, misses {misses}")
        t0 = time.monotonic()
        _create_lists(client, "pods", pod_jsons)
        _wait_all_bound(client, daemon, total, 420)
        log(f"{name}: {len(pod_jsons)} pods created and bound in "
            f"{time.monotonic() - t0:.0f} s (smoke wall time, polled "
            f"every 2 s)")
        placed = _judge_placements(client, n_nodes, total)
        device = daemon.require_clean(platform)
        daemon.stop()
        return {"device": device, "placed": placed,
                "cache_hits": hits, "cache_misses": misses}
    finally:
        daemon.child.stop(graceful_s=10)


def phase_served(platform: str, n_nodes: int, n_pods: int) -> dict:
    n_more = max(n_pods // 30, 1)
    apiserver, api_url = _start_apiserver()
    try:
        client = APIClient(api_url, qps=0, timeout=120.0)
        # Nodes FIRST: prewarm no-ops on an empty cluster.
        _create_lists(client, "nodes", [
            api.node_to_json(n) for n in
            synth.make_nodes(n_nodes, profile="mixed", n_zones=4)])
        first = _serve_wave(
            "scheduler-1", api_url, platform, client,
            lambda: [api.pod_to_json(p) for p in
                     synth.make_pods(n_pods, profile="mixed")],
            n_nodes, n_pods)
        # The second start: the first process released the chip, this
        # one acquires it, and its prewarm finds the first one's cache.
        second = _serve_wave(
            "scheduler-2", api_url, platform, client,
            lambda: [api.pod_to_json(p) for p in synth.make_pods(
                n_more, seed=2, profile="mixed", name_prefix="again")],
            n_nodes, n_pods + n_more)
        _require(second["cache_hits"] > 0,
                 f"second daemon start hit the compile cache 0 times "
                 f"(misses {second['cache_misses']})")
        _require(second["device"] == first["device"],
                 "the two daemon starts report different devices")
        return {"device": first["device"], "first": first,
                "second": second}
    finally:
        apiserver.stop(graceful_s=10)


# -- phase B: answers equal the references ------------------------------------

def phase_answers(platform: str, n_nodes: int, n_pods: int) -> dict:
    parity_pods = max(n_pods // 3, 1)   # PARITY.json's 5,000 x 10,000
    child = Child("chipcheck", [
        sys.executable, "-m", "kubernetes_tpu.perf.chipcheck",
        "--nodes", str(n_nodes), "--pods", str(n_pods),
        "--parity-pods", str(parity_pods),
        "--samples", str(min(200, parity_pods))], platform)
    try:
        try:
            rc = child.proc.wait(timeout=720)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"chipcheck still running after 720 s:\n"
                               f"{_tail(child.log_path)}") from None
        lines = _tail(child.log_path, 20000).strip().splitlines()
        _require(rc == 0, f"chipcheck exited with code {rc}:\n"
                          + "\n".join(lines[-30:]))
        out = json.loads(lines[-1])
        device = _require_device("B", out["device"], platform)
        par, svh, half = out["parity"], out["stream_vs_host"], \
            out["half_plane"]
        log(f"parity {par['n_nodes']} x {par['n_pods']} rich: "
            f"{par['decision_agreement_pct']} % of "
            f"{par['sampled_decisions']} sampled decisions, "
            f"{par['infeasible_choices']} infeasible choices")
        log(f"stream vs host solver {svh['n_nodes']} x {svh['n_pods']}: "
            f"{svh['rows_differ']} rows differ ({svh['placed']} placed)")
        log(f"half-width plane ({half['half_dtype']}, weight bound "
            f"{half['weight_bound']}) {half['n_nodes']} x "
            f"{half['n_pods']}: {half['rows_differ']} rows differ")
        log(f"select vs NumPy selectHost: {out['select']['wrong']} of "
            f"{out['select']['cases']} wrong")
        _require(out["ok"] is True, f"chipcheck failed: {out}")
        return {"device": device, "checks": out}
    finally:
        child.stop(graceful_s=10)


# -- phase C: the extender hook -----------------------------------------------

def _post(url: str, body: bytes) -> object:
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        return json.loads(r.read())


def phase_extender(platform: str, n_nodes: int, n_pods: int) -> dict:
    port = _free_port()
    child = Child("extender", [
        sys.executable, "-m", "kubernetes_tpu.server.extender",
        "--port", str(port), "--host", "127.0.0.1"], platform)
    url = f"http://127.0.0.1:{port}"
    try:
        _wait_until("healthz", lambda: _get(url + "/healthz"), child, 120)
        device = _require_device(
            "C", json.loads(_get(url + "/configz"))["device"], platform)
        # `rich`: tainted, NotReady and memory-pressured nodes; pods with
        # selectors, tolerations and best-effort QoS — so the feasible
        # sets differ from call to call.
        nodes = synth.make_nodes(n_nodes, profile="rich", n_zones=4)
        node_items = [api.node_to_json(n) for n in nodes]
        cluster = oracle.ClusterState(nodes=nodes)
        seen: set = set()
        pods = []
        for pod in synth.make_pods(64 * n_pods, seed=5, profile="rich"):
            key = (tuple(sorted(pod.node_selector.items())),
                   tuple(sorted(pod.annotations.items())))
            if key not in seen:
                seen.add(key)
                pods.append(pod)
            if len(pods) == n_pods:
                break
        _require(len(pods) == n_pods, "too few distinct pod templates")
        sizes = []
        for pod in pods:
            body = json.dumps({"pod": api.pod_to_json(pod),
                               "nodes": {"items": node_items}}).encode()
            got = _post(url + "/scheduler/filter", body)
            _require(not got.get("error"),
                     f"filter({pod.name}) answered error: "
                     f"{got.get('error')}")
            kept = {it["metadata"]["name"] for it in got["nodes"]["items"]}
            want = {n.name for n in
                    oracle.find_nodes_that_fit(pod, cluster)[0]}
            _require(kept == want,
                     f"filter({pod.name}): {len(kept)} feasible vs the "
                     f"oracle's {len(want)}; only extender: "
                     f"{sorted(kept - want)[:5]}, only oracle: "
                     f"{sorted(want - kept)[:5]}")
            scores = _post(url + "/scheduler/prioritize", body)
            _require(len(scores) == n_nodes
                     and {s["host"] for s in scores} ==
                     {n.name for n in nodes}
                     and all(0 <= s["score"] <= 10 for s in scores)
                     and max(s["score"] for s in scores) > 0,
                     f"prioritize({pod.name}): not one 0-10 score per "
                     f"node with a non-zero maximum")
            sizes.append(len(kept))
            child.require_alive()
        log(f"extender: {len(pods)} filter + prioritize calls over "
            f"{n_nodes} nodes; feasible-set sizes {sizes} equal the "
            f"oracle's")
        return {"device": device, "feasible_set_sizes": sizes}
    finally:
        child.stop(graceful_s=10)


# -- main ---------------------------------------------------------------------

def _on_signal(signum, _frame):
    raise SmokeFailure(
        f"exceeded {TOTAL_DEADLINE_S} s" if signum == signal.SIGALRM
        else f"signal {signum}")


def main() -> int:
    signal.signal(signal.SIGALRM, _on_signal)
    signal.signal(signal.SIGTERM, _on_signal)
    signal.alarm(TOTAL_DEADLINE_S)
    t0 = time.monotonic()
    devices = []
    try:
        for name, phase, n in (("A served", phase_served, N_PODS),
                               ("B answers", phase_answers, N_PODS),
                               ("C extender", phase_extender,
                                N_EXTENDER_PODS)):
            t_phase = time.monotonic()
            log(f"phase {name}: start")
            rec = phase(PLATFORM, N_NODES, n)
            devices.append(rec["device"])
            log(f"phase {name}: ok in {time.monotonic() - t_phase:.0f} s "
                f"(smoke wall time)")
    except (SmokeFailure, native.NativeBuildError) as err:
        log(f"FAILED: {err}")
        return 1
    finally:
        signal.alarm(0)
    if any(d != devices[0] for d in devices):
        log(f"FAILED: phases disagree on the device: {devices}")
        return 1
    log(f"all phases ok in {time.monotonic() - t0:.0f} s (smoke wall time)")
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
