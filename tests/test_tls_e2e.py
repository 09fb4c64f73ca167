"""The control plane with the insecure port DISABLED (VERDICT r4
missing #3 / next #5): apiserver serves only HTTPS with a client CA;
scheduler, controller-manager, hollow kubelet and kubectl all join via
the TLS client config (CA bundle + client certificate), their x509
CN/O identities driving RBAC.
"""

from __future__ import annotations

import json
import os
import socket
import ssl
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from kubernetes_tpu.client.http import APIClient, TLSConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BOOT = (
    "import os\n"
    "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
    "import jax\n"
    "jax.config.update('jax_platforms', 'cpu')\n"
    "from {module} import main\n"
    "import sys\n"
    "sys.exit(main({args!r}))\n"
)


# One limit PER STEP, sized for the slowest thing a step waits on when
# this process and its four daemons share one loaded core (the driver
# runs six workers): a daemon's start imports JAX and initialises the
# backend, the first decision compiles its programs.  A step that is
# merely slow passes; a daemon that died, or a step that is stuck, fails
# at once under the step's name with the daemons' logs.  Every daemon
# takes an ephemeral status port: on its default one it dies at start
# whenever another worker's test runs the same daemon.
STEP_S = 120.0


def _spawn(module: str, args: list[str], log_path) -> subprocess.Popen:
    with open(log_path, "wb") as log:
        return subprocess.Popen(
            [sys.executable, "-c", _BOOT.format(module=module, args=args)],
            stdout=subprocess.DEVNULL, stderr=log, env=dict(os.environ))


def _wait(cond, msg, procs, logs, timeout=STEP_S, period=0.25):
    t0 = time.monotonic()
    dead: list[str] = []
    while not dead and time.monotonic() - t0 < timeout:
        try:
            v = cond()
        except Exception:  # noqa: BLE001 — components still starting
            v = None
        if v:
            return v
        dead = [name for name, p in procs.items() if p.poll() is not None]
        time.sleep(period)
    why = f"{', '.join(dead)} exited" if dead \
        else f"timed out after {timeout:.0f}s"
    tails = "".join(
        f"\n--- {path.name} ---\n"
        + path.read_text(errors="replace")[-2000:]
        for path in sorted(logs.glob("*.log")))
    raise AssertionError(f"{why} waiting for {msg}{tails}")


@pytest.fixture(scope="module")
def pki(tmp_path_factory):
    d = tmp_path_factory.mktemp("tls-e2e-pki")

    def sh(*args):
        subprocess.run(args, cwd=d, check=True,
                       stdout=subprocess.DEVNULL,
                       stderr=subprocess.DEVNULL)

    sh("openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
       "-keyout", "ca.key", "-out", "ca.crt", "-days", "1",
       "-subj", "/CN=e2e-ca")
    certs = (("server", "/CN=127.0.0.1"),
             ("admin", "/O=system:masters/CN=cluster-admin"),
             ("scheduler", "/CN=system:kube-scheduler"),
             ("cm", "/CN=system:kube-controller-manager"),
             ("kubelet", "/CN=kubelet-wn0"))
    for name, subj in certs:
        sh("openssl", "req", "-newkey", "rsa:2048", "-nodes",
           "-keyout", f"{name}.key", "-out", f"{name}.csr",
           "-subj", subj)
        ext = d / f"{name}.ext"
        ext.write_text("subjectAltName=IP:127.0.0.1\n"
                       if name == "server"
                       else "basicConstraints=CA:FALSE\n")
        sh("openssl", "x509", "-req", "-in", f"{name}.csr",
           "-CA", "ca.crt", "-CAkey", "ca.key", "-CAcreateserial",
           "-out", f"{name}.crt", "-days", "1", "-extfile", str(ext))
    return d


def _client(pki, base, who, qps=100.0) -> APIClient:
    return APIClient(base, qps=qps, burst=int(qps * 2), tls=TLSConfig(
        ca_file=str(pki / "ca.crt"),
        cert_file=str(pki / f"{who}.crt"),
        key_file=str(pki / f"{who}.key")))


def _tls_args(pki, who) -> list[str]:
    return ["--certificate-authority", str(pki / "ca.crt"),
            "--client-certificate", str(pki / f"{who}.crt"),
            "--client-key", str(pki / f"{who}.key")]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_full_control_plane_tls_only(pki, tmp_path):
    port, status_port = _free_port(), _free_port()
    base = f"https://127.0.0.1:{port}"
    procs = {"apiserver": _spawn("kubernetes_tpu.apiserver.__main__", [
        "--port", str(port),
        "--tls-cert-file", str(pki / "server.crt"),
        "--tls-private-key-file", str(pki / "server.key"),
        "--client-ca-file", str(pki / "ca.crt"),
        "--authorization-mode", "RBAC"], tmp_path / "apiserver.log")}
    admin = _client(pki, base, "admin")
    try:
        _wait(lambda: admin.list("pods")[1] >= 0, "secure apiserver",
              procs, tmp_path)

        # There is no insecure surface AT ALL: a plaintext request to
        # the same port dies in the handshake.
        with pytest.raises(Exception):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                                   timeout=5)
        # An https client the CA doesn't vouch for (no client cert) is
        # anonymous -> RBAC 403s it.
        anon = ssl.create_default_context(cafile=str(pki / "ca.crt"))
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{base}/api/v1/pods", timeout=5,
                                   context=anon)
        assert e.value.code == 403

        # x509 CN/O drive RBAC: admin (O=system:masters) bootstraps the
        # component grants for the CN identities the daemons dial with.
        admin.create("clusterroles", {
            "metadata": {"name": "component"},
            "rules": [{"verbs": ["*"], "resources": ["*"]}]})
        admin.create("clusterrolebindings", {
            "metadata": {"name": "components"},
            "subjects": [
                {"kind": "User", "name": "system:kube-scheduler"},
                {"kind": "User",
                 "name": "system:kube-controller-manager"},
                {"kind": "User", "name": "kubelet-wn0"}],
            "roleRef": {"kind": "ClusterRole", "name": "component"}})

        procs["scheduler"] = _spawn(
            "kubernetes_tpu.scheduler.__main__",
            ["--api-server", base, "--port", str(status_port)]
            + _tls_args(pki, "scheduler"), tmp_path / "scheduler.log")
        procs["cm"] = _spawn(
            "kubernetes_tpu.controller.__main__",
            ["--api-server", base, "--port", "0"] + _tls_args(pki, "cm"),
            tmp_path / "cm.log")
        procs["kubelet"] = _spawn(
            "kubernetes_tpu.kubelet.__main__",
            ["--api-server", base, "--node-name", "wn0",
             "--heartbeat-period", "2"] + _tls_args(pki, "kubelet"),
            tmp_path / "kubelet.log")

        _wait(lambda: urllib.request.urlopen(
            f"http://127.0.0.1:{status_port}/healthz",
            timeout=5).status == 200,
            "the scheduler daemon's start (its status port)", procs,
            tmp_path)
        _wait(lambda: any(n["metadata"]["name"] == "wn0"
                          for n in admin.list("nodes")[0]),
              "kubelet registered over TLS", procs, tmp_path)

        # kubectl over TLS creates the workload; the whole loop
        # (controller -> scheduler -> kubelet) runs on the secure port.
        manifest = pki / "rc.json"
        manifest.write_text(json.dumps({
            "kind": "ReplicationController",
            "metadata": {"name": "web", "namespace": "default"},
            "spec": {"replicas": 2, "selector": {"app": "web"},
                     "template": {
                         "metadata": {"labels": {"app": "web"}},
                         "spec": {"containers": [{
                             "name": "c", "resources": {
                                 "requests": {"cpu": "100m"}}}]}}}}))
        out = subprocess.run(
            [sys.executable, "-m", "kubernetes_tpu.kubectl",
             "--server", base, "--token", ""]
            + _tls_args(pki, "admin")
            + ["create", "-f", str(manifest)],
            capture_output=True, text=True,
            env=dict(os.environ, PYTHONPATH=REPO), cwd=REPO)
        assert "created" in out.stdout, out.stdout + out.stderr

        def web_pods(ready) -> bool:
            pods = [p for p in admin.list("pods")[0]
                    if (p["metadata"].get("labels") or {})
                    .get("app") == "web"]
            return len(pods) == 2 and all(ready(p) for p in pods)

        _wait(lambda: web_pods(lambda p: True),
              "the controller-manager to create the RC's pods", procs,
              tmp_path)
        _wait(lambda: web_pods(
            lambda p: (p.get("spec") or {}).get("nodeName") == "wn0"),
            "the scheduler's first decisions (they compile)", procs,
            tmp_path)
        _wait(lambda: web_pods(
            lambda p: (p.get("status") or {}).get("phase") == "Running"),
            "the kubelet to run the pods, all over TLS", procs, tmp_path)

        # kubectl get over TLS reads it back.
        out = subprocess.run(
            [sys.executable, "-m", "kubernetes_tpu.kubectl",
             "--server", base] + _tls_args(pki, "admin")
            + ["get", "pods"],
            capture_output=True, text=True,
            env=dict(os.environ, PYTHONPATH=REPO), cwd=REPO)
        assert "web-" in out.stdout
    finally:
        for p in procs.values():
            p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                p.kill()
