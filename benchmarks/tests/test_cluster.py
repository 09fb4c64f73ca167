"""The seeded populations: same seed -> same fleet and pods; another seed
-> the same multiset in another order; the wire JSON says what the arrays
say."""

import json

import numpy as np

import cluster

MIXED_NODES = {"count": 400, "profile": "mixed", "milli_cpu": 4000,
               "memory": 32 * 1024 ** 3, "pods": 110, "n_zones": 4,
               "n_pools": 4, "capacity_scales": [0.5, 1.0, 1.0, 2.0]}
MIXED_PODS = {"profile": "mixed", "cpu_choices": [50, 100, 200, 500],
              "memory_mib_choices": [128, 256, 500, 1024],
              "selector_share": 0.10, "zone_affinity_share": 0.05}


def test_same_seed_same_fleet_other_seed_same_multiset():
    a, b = cluster.Nodes(MIXED_NODES, 7), cluster.Nodes(MIXED_NODES, 7)
    c = cluster.Nodes(MIXED_NODES, 2 ** 31 + 12345)
    for name in ("alloc_cpu", "alloc_mem", "pool", "zone"):
        assert (getattr(a, name) == getattr(b, name)).all()
        assert sorted(getattr(a, name)) == sorted(getattr(c, name))
    assert (a.alloc_cpu != c.alloc_cpu).any()
    assert a.alloc_cpu.sum() == 400 * 4000 * 1.125


def test_pods_blocks_are_permutations_with_exact_shares():
    a, b = cluster.Pods(MIXED_PODS, 7), cluster.Pods(MIXED_PODS, 7)
    c = cluster.Pods(MIXED_PODS, 8)
    for p in (a, b, c):
        p.grow(6400)
    assert (a.cpu == b.cpu).all() and (a.sel == b.sel).all()
    assert (a.cpu[:6400] != c.cpu[:6400]).any()
    assert sorted(a.mem[:6400]) == sorted(c.mem[:6400])
    assert (a.sel[:6400] >= 0).mean() == 0.10
    assert (a.aff[:6400] >= 0).mean() == 0.05
    assert not ((a.sel >= 0) & (a.aff >= 0)).any()


def test_wire_json_matches_the_arrays():
    pods = cluster.Pods(MIXED_PODS, 3)
    body = json.loads(pods.list_body(0, 200))
    assert body["kind"] == "List" and len(body["items"]) == 200
    for i, item in enumerate(body["items"]):
        assert item["metadata"]["name"] == f"p-{i}"
        req = item["spec"]["containers"][0]["resources"]["requests"]
        assert req["cpu"] == f"{pods.cpu[i]}m"
        assert int(req["memory"]) == pods.mem[i]
        sel = item["spec"].get("nodeSelector")
        assert (sel == {cluster.POOL_LABEL: f"pool-{pods.sel[i]}"}) \
            if pods.sel[i] >= 0 else sel is None
        note = item["metadata"]["annotations"].get(
            cluster.AFFINITY_ANNOTATION_KEY)
        if pods.aff[i] >= 0:
            assert f"zone-{pods.aff[i]}" in note and json.loads(note)
        else:
            assert note is None
    nodes = cluster.Nodes(MIXED_NODES, 3)
    items = nodes.to_json()
    assert items[5]["status"]["allocatable"]["cpu"] == f"{nodes.alloc_cpu[5]}m"
    assert items[5]["metadata"]["labels"][cluster.POOL_LABEL] == \
        f"pool-{nodes.pool[5]}"


def test_uniform_profile_is_upstreams_pause_pod():
    pods = cluster.Pods({"profile": "uniform", "milli_cpu": 100,
                         "memory": 500 * 1024 ** 2}, 1)
    pods.grow(10)
    assert set(pods.cpu) == {100} and set(pods.sel) == {-1}
    nodes = cluster.Nodes({"count": 10, "profile": "uniform",
                           "milli_cpu": 4000, "memory": 1 << 35,
                           "pods": 110}, 1)
    assert set(nodes.pool) == {-1} and "kt/pool" not in \
        nodes.to_json()[0]["metadata"]["labels"]
    assert np.all(nodes.alloc_pods == 110)
