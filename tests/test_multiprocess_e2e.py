"""Full control plane as SEPARATE PROCESSES joined only by HTTP with
bearer tokens — the reference's integration tier (test/integration/,
test/kubemark/start-kubemark.sh): apiserver (authn/z on), scheduler,
controller-manager (leader-elected), three hollow kubelets, and the
hollow proxy, each a real binary speaking the real socket surface.

Replays the node-death story over the wire: RC -> schedule -> kubelets
run -> kill a kubelet PROCESS -> node Ready=Unknown -> eviction ->
reschedule onto survivors -> service endpoints follow.
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from kubernetes_tpu.client.http import APIClient, APIError

TOKENS = "admin-token,admin,1\nsched-token,scheduler,2\n" \
         "cm-token,controller-manager,3\nkubelet-token,kubelet,4\n" \
         "proxy-token,proxy,5\nviewer-token,viewer,6,readonly\n"
ABAC = "\n".join([
    '{"user": "admin"}',
    '{"user": "scheduler"}',
    '{"user": "controller-manager"}',
    '{"user": "kubelet"}',
    '{"user": "proxy"}',
    '{"group": "readonly", "readonly": true}',
]) + "\n"


def _spawn(module: str, args: list[str]) -> subprocess.Popen:
    # The children inherit JAX_PLATFORMS=cpu from conftest.py.
    return subprocess.Popen(
        [sys.executable, "-m", module, *args],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        env=dict(os.environ))


def _wait(cond, timeout=60.0, period=0.25, msg=""):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            v = cond()
        except Exception:  # noqa: BLE001 — components still starting
            v = None
        if v:
            return v
        time.sleep(period)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture()
def cluster(tmp_path):
    tok_file = tmp_path / "tokens.csv"
    tok_file.write_text(TOKENS)
    abac_file = tmp_path / "abac.jsonl"
    abac_file.write_text(ABAC)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = f"http://127.0.0.1:{port}"

    procs: dict[str, subprocess.Popen] = {}
    procs["apiserver"] = _spawn("kubernetes_tpu.apiserver", [
        "--port", str(port),
        "--token-auth-file", str(tok_file),
        "--authorization-policy-file", str(abac_file)])
    admin = APIClient(base, qps=0, token="admin-token")
    _wait(lambda: admin.list("nodes") is not None, timeout=30,
          msg="authenticated apiserver up")

    procs["scheduler"] = _spawn("kubernetes_tpu.scheduler", [
        "--api-server", base, "--kube-api-token", "sched-token",
        "--kube-api-qps", "1000", "--kube-api-burst", "1000",
        "--port", "0"])
    procs["controller-manager"] = _spawn(
        "kubernetes_tpu.controller", [
            "--api-server", base, "--kube-api-token", "cm-token",
            "--port", "0", "--leader-elect",
            "--leader-elect-lease-duration", "2.0",
            "--leader-elect-renew-deadline", "1.5",
            "--leader-elect-retry-period", "0.3",
            "--node-monitor-grace-period", "2.0",
            "--pod-eviction-timeout", "1.0"])
    for i in range(3):
        procs[f"kubelet-{i}"] = _spawn("kubernetes_tpu.kubelet", [
            "--api-server", base, "--node-name", f"mp-{i}",
            "--cpu", "8000", "--kube-api-token", "kubelet-token",
            "--heartbeat-period", "0.4"])
    procs["proxy"] = _spawn("kubernetes_tpu.proxy", [
        "--api-server", base, "--kube-api-token", "proxy-token"])

    yield base, admin, procs
    for p in procs.values():
        if p.poll() is None:
            p.terminate()
    for p in procs.values():
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


def _rc(name: str, replicas: int) -> dict:
    return {"metadata": {"name": name, "namespace": "default"},
            "spec": {"replicas": replicas,
                     "selector": {"run": name},
                     "template": {
                         "metadata": {"labels": {"run": name}},
                         "spec": {"containers": [{
                             "name": "c",
                             "resources": {"requests": {"cpu": "100m"}}}]}}}}


def test_multiprocess_node_death_reschedule(cluster):
    base, admin, procs = cluster

    # All three kubelet processes self-register over the wire.
    def nodes_ready():
        items, _ = admin.list("nodes")
        ready = [n for n in items if any(
            c.get("type") == "Ready" and c.get("status") == "True"
            for c in (n.get("status") or {}).get("conditions") or ())]
        return len(ready) == 3
    _wait(nodes_ready, msg="3 kubelet processes registered+Ready")

    admin.create("replicationcontrollers", _rc("mp-ha", 4))
    admin.create("services", {
        "metadata": {"name": "mp-svc", "namespace": "default"},
        "spec": {"selector": {"run": "mp-ha"}}})

    def pods():
        items, _ = admin.list("pods")
        return [o for o in items
                if ((o.get("metadata") or {}).get("labels") or {})
                .get("run") == "mp-ha"
                and not (o.get("metadata") or {}).get("deletionTimestamp")]

    def all_running():
        ps = pods()
        return len(ps) == 4 and all(
            (p.get("status") or {}).get("phase") == "Running"
            and (p.get("spec") or {}).get("nodeName") for p in ps)
    _wait(all_running, msg="4 replicas Running across processes")

    def endpoints_full():
        ep = admin.get("endpoints", "default/mp-svc")
        return ep and ep.get("subsets") and \
            len(ep["subsets"][0]["addresses"]) == 4
    _wait(endpoints_full, msg="endpoints published by controller-manager")

    # Kill one kubelet PROCESS (SIGKILL: no graceful dergistration).
    used = {(p.get("spec") or {}).get("nodeName") for p in pods()}
    victim_node = sorted(used)[0]
    victim_proc = procs[f"kubelet-{victim_node.split('-')[1]}"]
    victim_proc.send_signal(signal.SIGKILL)

    def node_unknown():
        n = admin.get("nodes", victim_node)
        conds = {c.get("type"): c.get("status")
                 for c in (n.get("status") or {}).get("conditions") or ()}
        return conds.get("Ready") == "Unknown"
    _wait(node_unknown, timeout=30,
          msg=f"{victim_node} marked Unknown by controller-manager process")

    def rescheduled():
        ps = pods()
        return len(ps) == 4 and all(
            (p.get("spec") or {}).get("nodeName") != victim_node
            and (p.get("status") or {}).get("phase") == "Running"
            for p in ps)
    _wait(rescheduled, timeout=60,
          msg="replicas evicted + rescheduled onto surviving kubelets")

    def endpoints_recovered():
        ep = admin.get("endpoints", "default/mp-svc")
        return ep and ep.get("subsets") and \
            len(ep["subsets"][0]["addresses"]) == 4
    _wait(endpoints_recovered, msg="endpoints follow the reschedule")


def test_multiprocess_authnz(cluster):
    base, admin, procs = cluster
    # No token: 401.
    anon = APIClient(base, qps=0)
    with pytest.raises(APIError) as e:
        anon.list("pods")
    assert e.value.status == 401
    # Bad token: 401.
    bad = APIClient(base, qps=0, token="wrong")
    with pytest.raises(APIError) as e:
        bad.list("pods")
    assert e.value.status == 401
    # Readonly group: GET ok, write 403.
    viewer = APIClient(base, qps=0, token="viewer-token")
    viewer.list("pods")
    with pytest.raises(APIError) as e:
        viewer.create("pods", {"metadata": {"name": "nope"},
                               "spec": {"containers": [{"name": "c"}]}})
    assert e.value.status == 403
