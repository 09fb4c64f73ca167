"""``shapes/interpod.py`` and ``references/interpod.py`` (configuration
``interpod-5000n``): hand-worked cases for the reference beside
``test_reference.py``'s, and a tiny run of the configuration's own files
through the whole served path on the CPU."""

import json
import os

import numpy as np

import run
from conftest import drive

NODES = {"count": 3, "profile": "uniform", "milli_cpu": 4000,
         "memory": 32 * 1024 ** 3, "pods": 110}
PODS = {"milli_cpu": 100, "memory": 500 * 1024 ** 2}


def _parts(pods_spec=PODS):
    shapes = run.load_module("shapes", "interpod")
    ref = run.load_module("references", "interpod")
    nodes = shapes.Nodes(NODES, 1)
    pods = shapes.Pods(pods_spec, 1, NODES)
    pods.grow(8)
    return ref, nodes, pods


def test_the_pod_is_upstreams_template():
    _ref, nodes, pods = _parts()
    pod = json.loads(pods.json_bytes(5))
    assert pod["metadata"]["labels"] == {"color": "green", "name": "test"}
    affinity = json.loads(pod["metadata"]["annotations"][
        "scheduler.alpha.kubernetes.io/affinity"])
    assert affinity == {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "labelSelector": {"matchLabels": {"color": "green"}},
            "namespaces": ["default"],
            "topologyKey": "kubernetes.io/hostname"}]}}
    requests = pod["spec"]["containers"][0]["resources"]["requests"]
    assert requests == {"cpu": "100m", "memory": "524288000"}
    assert pods.json_bytes(5).endswith(b"}}")
    assert list(pod) == ["metadata", "status", "spec"]
    assert pod["status"] == {"phase": "Pending"}
    assert [item["metadata"]["name"] for item in
            json.loads(pods.list_body(2, 5))["items"]] == ["p-2", "p-3", "p-4"]
    assert pods.n_groups == 1 and not pods.group.any()
    assert all(n["metadata"]["labels"]["kubernetes.io/hostname"]
               == n["metadata"]["name"] for n in nodes.to_json())


def test_the_observer_sees_the_bind_of_a_pod_that_waited(tmp_path):
    """A green pod that found every node taken gets ``PodScheduled =
    False`` from the daemon (get, add the condition, put) before it is
    bound.  ``status`` stands ahead of ``spec`` from the create on, so
    ``nodeName`` is still the last key of the bind's watch line and
    ``loadgen._EVENT`` finds it; a pod created without ``status`` shows
    what the order is for."""
    import http.client
    import socket
    import time

    import loadgen
    import rig
    _ref, nodes, pods = _parts()
    api = rig.ApiServer(str(tmp_path))
    sock = None
    try:
        api.post_list("nodes", json.dumps(
            {"kind": "List", "items": nodes.to_json()}).encode(), nodes.n)
        sock = socket.create_connection(("127.0.0.1", api.port))
        sock.sendall(b"GET /api/v1/pods?watch=1&fieldSelector="
                     b"spec.nodeName!%3D HTTP/1.1\r\nHost: bench\r\n\r\n")
        bare = pods.json_bytes(2).replace(
            b'"status":{"phase":"Pending"},', b"", 1)
        api.post_list("pods", b'{"kind":"List","items":['
                      + pods.json_bytes(1) + b"," + bare + b"]}", 2)
        conn = http.client.HTTPConnection("127.0.0.1", api.port, timeout=30)
        for name in ("p-1", "p-2"):
            path = f"/api/v1/namespaces/default/pods/{name}"
            conn.request("GET", path)
            pod = json.loads(conn.getresponse().read())
            pod.setdefault("status", {}).setdefault("conditions", []).append(
                {"type": "PodScheduled", "status": "False",
                 "reason": "Unschedulable", "message": "0/3 nodes"})
            conn.request("PUT", path, json.dumps(pod),
                         {"Content-Type": "application/json"})
            assert conn.getresponse().read() and True
            conn.request("POST", "/api/v1/namespaces/default/bindings",
                         json.dumps({"metadata": {"name": name},
                                     "target": {"kind": "Node",
                                                "name": "node-1"}}),
                         {"Content-Type": "application/json"})
            response = conn.getresponse()
            response.read()
            assert response.status == 201
        conn.close()
        sock.settimeout(5.0)
        data = b""
        deadline = time.monotonic() + 5.0
        while data.count(b'"nodeName":"node-1"') < 2 \
                and time.monotonic() < deadline:
            data += sock.recv(1 << 16)
    finally:
        if sock is not None:
            sock.close()
        api.stop()
    assert data.count(b'"nodeName":"node-1"') == 2
    assert b"PodScheduled" in data
    seen = [m.groups() for m in loadgen._EVENT.finditer(data)]
    assert seen == [(b"ADDED", b"1", b"1")]


def test_a_node_that_holds_a_green_pod_does_not_fit_until_it_retires():
    ref, nodes, pods = _parts()
    state = ref.State(nodes, pods)
    assert ref.fits(state, 0).tolist() == [True, True, True]
    assert ref.best_nodes(state, 0).tolist() == [0, 1, 2]
    state.add(0, 1)
    assert ref.fits(state, 1).tolist() == [True, False, True]
    assert ref.best_nodes(state, 1).tolist() == [0, 2]
    assert ref.score_gap(state, 1, 1) == float("inf")
    assert ref.score_gap(state, 1, 2) == 0.0
    kept = state.copy()
    state.add(0, 1, -1)                       # the retirement opens node 1
    assert ref.fits(state, 1).tolist() == [True, True, True]
    assert ref.fits(kept, 1).tolist() == \
        [True, False, True]                   # a copy is independent
    for pod, node in ((1, 0), (2, 1), (3, 2)):
        state.add(pod, node)
    assert not ref.fits(state, 4).any()       # every node taken
    assert len(ref.best_nodes(state, 4)) == 0


def test_broken_counts_a_second_green_pod_and_nothing_else():
    ref, nodes, pods = _parts()
    assert ref.GUARANTEES == ("selector_violations", "over_allocatable",
                              "antiaffinity_violations")
    state = ref.State(nodes, pods)
    assert ref.broken(state, 0, 2) == {
        "selector_violations": 0, "over_allocatable": 0,
        "antiaffinity_violations": 0}
    state.add(0, 2)
    assert ref.broken(state, 1, 2) == {
        "selector_violations": 0, "over_allocatable": 0,
        "antiaffinity_violations": 1}
    assert ref.broken(state, 1, 0)["antiaffinity_violations"] == 0


def test_groups_repel_their_own_colour_only():
    ref, nodes, pods = _parts(dict(PODS, colors=["green", "blue"], run=2))
    assert pods.group[:6].tolist() == [0, 0, 1, 1, 0, 0]
    assert json.loads(pods.json_bytes(2))["metadata"]["labels"]["color"] \
        == "blue"
    state = ref.State(nodes, pods)
    state.add(0, 0)                           # green on node 0
    assert ref.fits(state, 1).tolist() == [False, True, True]
    assert ref.fits(state, 2).tolist() == [True, True, True]
    # the scores are reference.py's, in whole points: one pause pod on
    # a 4-cpu node moves none, so the blue pod's answer set is every node
    assert ref.best_nodes(state, 2).tolist() == [0, 1, 2]
    assert ref.best_nodes(state, 1).tolist() == [1, 2]


def _add_tiny_interpod(tree: str) -> None:
    """The configuration's own file at 600 nodes, with a ladder whose
    first burst (512) is more than the 440 open nodes hold, as the
    deployment's 4,096 is more than its 3,000: 72 green pods wait for a
    retirement, get a status condition and are bound late, and the
    observer has to see those binds for the ramp to end; beside a mix
    slow enough that the open nodes outnumber the pods in flight."""
    bench_dir = os.path.join(tree, "benchmarks")
    with open(os.path.join(bench_dir, "configs", "interpod-5000n.json")) as f:
        config = json.load(f)
    config.update(name="tiny-interpod", resident_cap=160,
                  judge={"sample": 200, "lag_step": 20, "max_lag_s": 2.0},
                  nodes=dict(config["nodes"], count=600))
    config["daemon"]["env"]["KT_STREAM_CHUNK"] = "512"
    with open(os.path.join(bench_dir, "configs", "tiny-interpod.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "traffic", "tiny-interpod-open.json"),
              "w") as f:
        json.dump({"kind": "poisson_open", "rate_pods_s": 100,
                   "steady_pending_s": 0.5}, f)
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    if any(c["name"] == "tiny-interpod" for c in bench["configs"]):
        return
    bench["configs"].append({
        "name": "tiny-interpod", "source": "tests", "reduced": ["count"],
        "file": "benchmarks/configs/tiny-interpod.json", "why": "tests"})
    bench["workloads"].append({
        "name": "tiny-interpod-open", "config": "tiny-interpod",
        "traffic": "tiny-interpod-open", "chips": 1, "why": "tests"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "schedperf5k-arrivals" in metric.get("workloads", ()):
            metric["workloads"].append("tiny-interpod-open")
    with open(path, "w") as f:
        json.dump(bench, f)


def test_tiny_run_of_the_configurations_files_is_correct(tiny_tree):
    """The program itself, on the CPU at 600 nodes, through the whole
    served path with the green pods of ``shapes/interpod.py``: every
    number compared is inside its limit, the new guarantee among them,
    and no program compiles after prewarm; with two green pods planted
    on one node the run is not correct, by that number alone."""
    _add_tiny_interpod(tiny_tree)
    for attempt in (1, 2):
        try:
            program = drive(tiny_tree, "tiny-interpod-open", seed=2147483747,
                            seconds=3.0, fault=None)
            break
        except AssertionError as err:
            # the CPU daemon's abort AT EXIT (PERF.md section 7 entry 10e)
            if attempt == 2 or "did not exit 0 on SIGTERM" not in str(err):
                raise
    assert program["correct"] is True, program["compared"]
    assert program["compared"]["antiaffinity_violations"] == \
        {"value": 0, "limit": 0}
    assert program["attempted"] > 200 and program["failed"] == 0
    # the daemon found green pods resident at start, so its prewarm traced
    # the programs with the affinity flag: none compiled on the live path
    with open(os.path.join(
            tiny_tree, "benchmarks", "out",
            "tiny-interpod-open.2147483747.0", "daemon.log")) as f:
        log = f.read()
    assert "pre-warmed stream ladder" in log
    assert "post-prewarm XLA compile" not in log

    faulty = drive(tiny_tree, "tiny-interpod-open", seed=43, seconds=3.0,
                   fault="colocate")
    assert faulty["correct"] is False
    over = {k for k, v in faulty["compared"].items()
            if v["value"] > v["limit"]}
    assert over == {"antiaffinity_violations"}, faulty["compared"]
