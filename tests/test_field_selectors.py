"""Server-side field selectors — conformance across BOTH apiservers.

The reference scheduler's informers are fielded: the queue side
lists/watches ``spec.nodeName=`` only, so assigned-pod churn never
crosses its wire (plugin/pkg/scheduler/factory/factory.go:466-469),
and kubelets watch ``spec.nodeName=<node>``.  VERDICT r4 missing #4.

Every behavior here is pinned identically against the Python server
(apiserver/server.py) and the native rig (native/apiserver.cpp) via the
parametrized ``base`` fixture — a selector behavior drifting between the
two servers fails this module.
"""

from __future__ import annotations

import json
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

from kubernetes_tpu.api import fieldsel


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_healthy(base: str, proc) -> None:
    deadline = time.time() + 15
    while True:
        try:
            urllib.request.urlopen(base + "/healthz", timeout=2).read()
            return
        except OSError:
            if time.time() > deadline:
                proc.kill()
                raise
            time.sleep(0.05)


@pytest.fixture(params=["python", "native"])
def base(request):
    port = _free_port()
    if request.param == "python":
        cmd = [sys.executable, "-m", "kubernetes_tpu.apiserver",
               "--port", str(port)]
    else:
        from kubernetes_tpu.apiserver.native import (native_binary,
                                                     toolchain_available)
        if not toolchain_available():
            pytest.skip("no C++ toolchain")
        binary = native_binary()
        cmd = [binary, "--port", str(port)]
    proc = subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    url = f"http://127.0.0.1:{port}"
    _wait_healthy(url, proc)
    yield url
    proc.terminate()
    proc.wait(timeout=10)


def _req(base, method, path, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(base + path, data=data, method=method,
                                 headers={"Content-Type":
                                          "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read() or b"{}")


def _pod(name, node=""):
    spec = {"containers": [{"name": "c"}]}
    if node:
        spec["nodeName"] = node
    return {"metadata": {"name": name, "namespace": "default"},
            "spec": spec}


def _names(items):
    return sorted(i["metadata"]["name"] for i in items)


def _list(base, kind, sel):
    q = "?fieldSelector=" + urllib.parse.quote(sel) if sel else ""
    code, body = _req(base, "GET", f"/api/v1/{kind}{q}")
    assert code == 200, body
    return body["items"]


class TestGroupPaths:
    """/apis/{group}/{version}/... serves the same kinds as the legacy
    core path on BOTH servers (the reference's clients address
    extensions/v1beta1 replicasets, batch/v1 jobs, autoscaling/v1
    HPAs)."""

    def test_group_paths_alias_core(self, base):
        code, created = _req(
            base, "POST", "/apis/extensions/v1beta1/replicasets",
            {"metadata": {"name": "rs1"},
             "spec": {"replicas": 1,
                      "selector": {"matchLabels": {"a": "b"}}}})
        assert code == 201, created
        assert created["metadata"]["namespace"] == "default"
        code, got = _req(
            base, "GET",
            "/apis/extensions/v1beta1/namespaces/default/"
            "replicasets/rs1")
        assert code == 200
        # The same object is visible through the core path (one store).
        code, got = _req(
            base, "GET", "/api/v1/namespaces/default/replicasets/rs1")
        assert code == 200
        code, body = _req(base, "POST", "/apis/batch/v1/jobs",
                          {"metadata": {"name": "j1"},
                           "spec": {"completions": 1,
                                    "template": {"spec": {
                                        "containers": [{"name": "c"}]}}}})
        assert code == 201
        code, lst = _req(base, "GET", "/apis/batch/v1/jobs")
        assert code == 200 and _names(lst["items"]) == ["j1"]
        code, _ = _req(base, "DELETE",
                       "/apis/batch/v1/namespaces/default/jobs/j1")
        assert code == 200


class TestListSelectors:
    def test_node_name_set_membership(self, base):
        _req(base, "POST", "/api/v1/pods", _pod("u1"))
        _req(base, "POST", "/api/v1/pods", _pod("u2"))
        _req(base, "POST", "/api/v1/pods", _pod("a1", node="n1"))
        _req(base, "POST", "/api/v1/pods", _pod("a2", node="n2"))
        assert _names(_list(base, "pods", "spec.nodeName=")) == ["u1", "u2"]
        assert _names(_list(base, "pods", "spec.nodeName!=")) == \
            ["a1", "a2"]
        assert _names(_list(base, "pods", "spec.nodeName=n1")) == ["a1"]
        assert _names(_list(base, "pods", "spec.nodeName!=n1")) == \
            ["a2", "u1", "u2"]
        assert len(_list(base, "pods", "")) == 4

    def test_double_equals_and_combined(self, base):
        _req(base, "POST", "/api/v1/pods", _pod("x", node="n1"))
        _req(base, "POST", "/api/v1/pods", _pod("y", node="n1"))
        assert _names(_list(
            base, "pods",
            "spec.nodeName==n1,metadata.name!=y")) == ["x"]

    def test_metadata_fields_and_missing_field(self, base):
        _req(base, "POST", "/api/v1/pods", _pod("m1"))
        assert _names(_list(base, "pods", "metadata.name=m1")) == ["m1"]
        # A field no pod has compares as "".
        assert _names(_list(base, "pods", "status.phase=")) == ["m1"]
        assert _list(base, "pods", "status.phase=Running") == []

    def test_invalid_selector_400(self, base):
        code, _ = _req(base, "GET",
                       "/api/v1/pods?fieldSelector=no-operator")
        assert code == 400


class TestWatchSelectors:
    """Set-transition semantics: the fielded watch surfaces membership
    changes, not raw store events (cacher.go watchCache)."""

    def _watch(self, base, sel, rv):
        url = (f"{base}/api/v1/pods?watch=1&resourceVersion={rv}"
               f"&fieldSelector={urllib.parse.quote(sel)}")
        return urllib.request.urlopen(url, timeout=10)

    @staticmethod
    def _next(stream):
        while True:
            line = stream.readline()
            assert line, "watch stream EOF"
            line = line.strip()
            if line:
                return json.loads(line)

    def test_bind_leaves_unassigned_set_as_deleted(self, base):
        code, body = _req(base, "GET", "/api/v1/pods")
        rv = body["metadata"]["resourceVersion"]
        unassigned = self._watch(base, "spec.nodeName=", rv)
        assigned = self._watch(base, "spec.nodeName!=", rv)
        _req(base, "POST", "/api/v1/pods", _pod("p"))
        ev = self._next(unassigned)
        assert ev["type"] == "ADDED"
        assert ev["object"]["metadata"]["name"] == "p"
        # Bind: MODIFIED in the store; DELETED to the unassigned watch,
        # ADDED to the assigned watch.
        code, _ = _req(base, "POST", "/api/v1/namespaces/default/bindings",
                       {"metadata": {"name": "p", "namespace": "default"},
                        "target": {"kind": "Node", "name": "n9"}})
        assert code == 201
        ev = self._next(unassigned)
        assert ev["type"] == "DELETED"
        assert ev["object"]["spec"]["nodeName"] == "n9"
        ev = self._next(assigned)
        assert ev["type"] == "ADDED"
        assert ev["object"]["metadata"]["name"] == "p"
        # A pod created never-matching is never seen by the unassigned
        # watch; the next event there is the next unassigned create.
        _req(base, "POST", "/api/v1/pods", _pod("pre", node="n3"))
        _req(base, "POST", "/api/v1/pods", _pod("q"))
        ev = self._next(unassigned)
        assert ev["object"]["metadata"]["name"] == "q"
        ev = self._next(assigned)
        assert ev["object"]["metadata"]["name"] == "pre"
        unassigned.close()
        assigned.close()

    def test_replay_is_classified_too(self, base):
        """Events already buffered replay with the same transition
        rewriting a live watcher would have seen."""
        _req(base, "POST", "/api/v1/pods", _pod("r"))
        _req(base, "POST", "/api/v1/namespaces/default/bindings",
             {"metadata": {"name": "r", "namespace": "default"},
              "target": {"kind": "Node", "name": "n1"}})
        stream = self._watch(base, "spec.nodeName=", 0)
        ev1 = self._next(stream)
        ev2 = self._next(stream)
        assert (ev1["type"], ev2["type"]) == ("ADDED", "DELETED")
        stream.close()
        stream = self._watch(base, "spec.nodeName!=", 0)
        ev = self._next(stream)
        assert ev["type"] == "ADDED"
        assert ev["object"]["spec"]["nodeName"] == "n1"
        stream.close()

    def test_delete_of_nonmember_is_dropped(self, base):
        _req(base, "POST", "/api/v1/pods", _pod("gone", node="n1"))
        code, body = _req(base, "GET", "/api/v1/pods")
        rv = body["metadata"]["resourceVersion"]
        unassigned = self._watch(base, "spec.nodeName=", rv)
        _req(base, "DELETE", "/api/v1/namespaces/default/pods/gone")
        _req(base, "POST", "/api/v1/pods", _pod("seen"))
        ev = self._next(unassigned)
        # The assigned pod's deletion never surfaces here.
        assert ev["type"] == "ADDED"
        assert ev["object"]["metadata"]["name"] == "seen"
        unassigned.close()


class TestInProcess:
    """The same contract against the in-process MemStore (what the
    controllers and integration rigs use)."""

    def test_memstore_fielded_watch(self):
        from kubernetes_tpu.apiserver.memstore import MemStore
        store = MemStore()
        store.create("pods", _pod("a"))
        w = store.watch(["pods"], 0,
                        selector=fieldsel.matcher("spec.nodeName="))
        ev = w.next(timeout=1)
        assert ev.type == "ADDED" and ev.key == "default/a"
        store.bind("default", "a", "n1")
        ev = w.next(timeout=1)
        assert ev.type == "DELETED"
        assert ev.object["spec"]["nodeName"] == "n1"
        store.create("pods", _pod("b", node="n2"))
        store.delete("pods", "default/b")
        store.create("pods", _pod("c"))
        ev = w.next(timeout=1)
        assert ev.type == "ADDED" and ev.key == "default/c"
        w.stop()

    def test_reflector_fielded(self):
        from kubernetes_tpu.apiserver.memstore import MemStore
        from kubernetes_tpu.client.reflector import Reflector
        store = MemStore()
        store.create("pods", _pod("pend"))
        store.create("pods", _pod("bound", node="n1"))
        seen: list[tuple[str, str]] = []
        r = Reflector(store, "pods",
                      lambda t, o: seen.append(
                          (t, o["metadata"]["name"])),
                      field_selector="spec.nodeName=")
        r.run()
        assert r.wait_for_sync()
        deadline = time.time() + 5
        store.bind("default", "pend", "n2")
        while time.time() < deadline and \
                ("DELETED", "pend") not in seen:
            time.sleep(0.05)
        r.stop()
        assert ("ADDED", "pend") in seen
        assert ("ADDED", "bound") not in seen  # filtered at list
        assert ("DELETED", "pend") in seen     # left the set on bind


class TestParser:
    def test_parse(self):
        reqs = fieldsel.parse("spec.nodeName=,metadata.name!=x")
        assert [(r.path, r.op, r.value) for r in reqs] == [
            (("spec", "nodeName"), "=", ""),
            (("metadata", "name"), "!=", "x")]
        assert fieldsel.matcher("") is None
        with pytest.raises(ValueError):
            fieldsel.parse("garbage")
        with pytest.raises(ValueError):
            fieldsel.parse("=value")

    def test_match_scalars(self):
        m = fieldsel.matcher("status.phase=Running")
        assert m({"status": {"phase": "Running"}})
        assert not m({"status": {"phase": "Failed"}})
        assert not m({})
        m = fieldsel.matcher("spec.replicas=3")
        assert m({"spec": {"replicas": 3}})  # numbers stringify
