"""Seeded cluster and pod populations, as arrays and as wire JSON.

Copied in shape from ``kubernetes_tpu/perf/synth.py`` (``make_nodes`` /
``make_pods``, profiles ``uniform`` and ``mixed``) so that no later change
to the program can move the traffic.  Two differences, both on purpose:

* every seed gets the SAME multiset of node capacities, pools, zones and
  of pod sizes and constraints, in another order (synth draws each one
  independently, so a seed would also move the fleet's total capacity);
* objects are produced as attribute arrays (what the reference scheduler
  replays) and as pre-formatted JSON bytes (what the generator sends),
  never as the program's ``api`` objects: this module imports nothing of
  the program.

Both cells of BENCHMARK.json run profile ``uniform`` with upstream
scheduler_perf's shapes (one request template).  Profile ``mixed`` takes
a heterogeneous fleet and request mix wholly as parameters: it is there
so that a later cell can bring a PUBLISHED mix as a data file (the
numbers ``perf/synth.py`` uses for it are synth's inventions and stand in
no configuration); the benchmark's own tests run it at a tiny size.

THE INTERFACE OF A SHAPES MODULE (``shapes/<word>.py`` for a
configuration that names ``"shapes": "<word>"``; this file where it names
none).  ``run.py``, ``loadgen.py``, the traffic kinds, ``judge.py`` and
``refsched.py`` use nothing else; every object is made from the
configuration's ``nodes`` / ``pods`` specs and the seed alone, so that the
runner, the judge and ``refsched.py`` (another process) each make the
same ones:

  ``Nodes(spec, seed)``            ``n``; ``to_json()`` -> the v1 node
                                   objects, node ``i`` named ``node-<i>``
  ``Pods(spec, seed, nodes_spec)`` an endless population: ``len()``,
                                   ``grow(n)`` (thread-safe, at least ``n``
                                   pods), ``json_bytes(i)`` (pod ``p-<i>``
                                   as the v1 JSON a create sends, ending
                                   in ``}}`` of ``spec`` so that
                                   ``run.prefill`` can add a ``nodeName``),
                                   ``list_body(start, stop)`` (a v1
                                   ``List`` of those)

plus whatever per-node and per-pod arrays the configuration's REFERENCE
reads (here ``alloc_cpu``, ``alloc_mem``, ``alloc_pods``, ``pool``,
``zone`` per node and ``cpu``, ``mem``, ``sel``, ``aff`` per pod, every
pod array ``len()`` long).  A shapes file adds arrays of its own (a label
group, a topology domain); nothing but its own reference reads them.

Node profile parameters (``configs/<name>.json`` ``nodes``):
  count, profile ("uniform" | "mixed"), milli_cpu, memory, pods, n_zones,
  n_pools, capacity_scales (one entry per equal share of the fleet).
Pod profile parameters (``pods``):
  profile ("uniform" | "mixed"), milli_cpu / memory (uniform), or
  cpu_choices / memory_mib_choices / selector_share / zone_affinity_share
  (mixed).
"""

from __future__ import annotations

import json
import threading

import numpy as np

HOSTNAME_LABEL = "kubernetes.io/hostname"
ZONE_LABEL = "failure-domain.beta.kubernetes.io/zone"
REGION_LABEL = "failure-domain.beta.kubernetes.io/region"
POOL_LABEL = "kt/pool"
AFFINITY_ANNOTATION_KEY = "scheduler.alpha.kubernetes.io/affinity"
NAMESPACE = "default"

# One canonical block of mixed pods: every (cpu, memory) pair equally
# often, and inside each pair exact shares of selector / affinity pods.
_BLOCK_PER_PAIR = 400


def _spread(values, n: int) -> np.ndarray:
    """``n`` entries holding each of ``values`` equally often (the
    remainder goes to the first ones)."""
    reps = -(-n // len(values))
    return np.tile(np.asarray(values), reps)[:n]


class Nodes:
    """The fleet: ``alloc_cpu`` (milli), ``alloc_mem`` (bytes),
    ``alloc_pods``, ``pool`` and ``zone`` (-1 = no label) per node."""

    def __init__(self, spec: dict, seed: int):
        n = int(spec["count"])
        rng = np.random.RandomState(seed % (2 ** 32))
        self.n = n
        self.alloc_pods = np.full(n, int(spec["pods"]), np.int64)
        if spec["profile"] == "uniform":
            self.alloc_cpu = np.full(n, int(spec["milli_cpu"]), np.int64)
            self.alloc_mem = np.full(n, int(spec["memory"]), np.int64)
            self.pool = np.full(n, -1, np.int64)
            self.zone = np.full(n, -1, np.int64)
        elif spec["profile"] == "mixed":
            scale = rng.permutation(_spread(spec["capacity_scales"], n))
            self.alloc_cpu = (int(spec["milli_cpu"]) * scale).astype(np.int64)
            self.alloc_mem = (int(spec["memory"]) * scale).astype(np.int64)
            self.pool = rng.permutation(
                _spread(range(int(spec["n_pools"])), n)).astype(np.int64)
            self.zone = rng.permutation(
                _spread(range(int(spec["n_zones"])), n)).astype(np.int64)
        else:
            raise ValueError(f"unknown node profile {spec['profile']!r}")

    def to_json(self) -> list[dict]:
        out = []
        for i in range(self.n):
            labels = {HOSTNAME_LABEL: f"node-{i}"}
            if self.zone[i] >= 0:
                z = int(self.zone[i])
                labels[ZONE_LABEL] = f"zone-{z}"
                labels[REGION_LABEL] = f"region-{z % 3}"
            if self.pool[i] >= 0:
                labels[POOL_LABEL] = f"pool-{int(self.pool[i])}"
            out.append({
                "metadata": {"name": f"node-{i}", "labels": labels,
                             "annotations": {}},
                "spec": {"unschedulable": False},
                "status": {
                    "allocatable": {
                        "cpu": f"{int(self.alloc_cpu[i])}m",
                        "memory": str(int(self.alloc_mem[i])),
                        "pods": str(int(self.alloc_pods[i]))},
                    "conditions": [{"type": "Ready", "status": "True"}]},
            })
        return out


def _affinity_annotation(zone: int) -> str:
    return json.dumps({"nodeAffinity": {
        "preferredDuringSchedulingIgnoredDuringExecution": [{
            "weight": 10,
            "preference": {"matchExpressions": [{
                "key": ZONE_LABEL, "operator": "In",
                "values": [f"zone-{zone}"]}]}}]}})


class Pods:
    """An endless, seeded population of pending pods.  Pod ``i`` is
    named ``p-<i>``; ``cpu[i]`` (milli), ``mem[i]`` (bytes), ``sel[i]``
    (required pool, -1 none) and ``aff[i]`` (preferred zone, -1 none)
    exist for every ``i < len``; ``grow`` extends them."""

    def __init__(self, spec: dict, seed: int, nodes_spec: dict | None = None):
        self.spec = spec
        self.rng = np.random.RandomState((seed + 1) % (2 ** 32))
        self.n_pools = int((nodes_spec or {}).get("n_pools", 4))
        self.n_zones = int((nodes_spec or {}).get("n_zones", 4))
        self.cpu = np.zeros(0, np.int64)
        self.mem = np.zeros(0, np.int64)
        self.sel = np.zeros(0, np.int64)
        self.aff = np.zeros(0, np.int64)
        self._block = self._canonical_block()
        self._grow_lock = threading.Lock()

    def _canonical_block(self) -> np.ndarray:
        s = self.spec
        if s["profile"] == "uniform":
            return np.array([[int(s["milli_cpu"]), int(s["memory"]),
                              -1, -1]], np.int64)
        if s["profile"] != "mixed":
            raise ValueError(f"unknown pod profile {s['profile']!r}")
        n_sel = int(round(_BLOCK_PER_PAIR * float(s["selector_share"])))
        n_aff = int(round(_BLOCK_PER_PAIR * float(s["zone_affinity_share"])))
        rows = []
        for cpu in s["cpu_choices"]:
            for mib in s["memory_mib_choices"]:
                for k in range(_BLOCK_PER_PAIR):
                    sel = k % self.n_pools if k < n_sel else -1
                    aff = ((k - n_sel) % self.n_zones
                           if n_sel <= k < n_sel + n_aff else -1)
                    rows.append((int(cpu), int(mib) * 1024 ** 2, sel, aff))
        return np.array(rows, np.int64)

    def __len__(self) -> int:
        return len(self.cpu)

    def grow(self, n: int) -> None:
        """Make attributes for at least ``n`` pods (whole blocks, each a
        seeded permutation of the canonical block)."""
        with self._grow_lock:
            parts = []
            have = len(self.cpu)
            while have < n:
                parts.append(
                    self._block[self.rng.permutation(len(self._block))])
                have += len(self._block)
            if parts:
                new = np.concatenate(parts)
                self.cpu = np.concatenate([self.cpu, new[:, 0]])
                self.mem = np.concatenate([self.mem, new[:, 1]])
                self.sel = np.concatenate([self.sel, new[:, 2]])
                self.aff = np.concatenate([self.aff, new[:, 3]])

    def json_bytes(self, i: int) -> bytes:
        """Pod ``i`` as the v1 JSON the program's ``pod_to_json`` writes
        for synth's pause pod."""
        self.grow(i + 1)
        return _pod_json(i, int(self.cpu[i]), int(self.mem[i]),
                         int(self.sel[i]), int(self.aff[i])).encode()

    def list_body(self, start: int, stop: int) -> bytes:
        """A v1 ``List`` body creating pods ``start..stop-1``."""
        self.grow(stop)
        rows = zip(range(start, stop), self.cpu[start:stop].tolist(),
                   self.mem[start:stop].tolist(),
                   self.sel[start:stop].tolist(),
                   self.aff[start:stop].tolist())
        return ('{"kind":"List","items":['
                + ",".join(_pod_json(*row) for row in rows) + "]}").encode()


_POD = ('{"metadata":{"name":"p-%d","namespace":"' + NAMESPACE + '","uid":"",'
        '"labels":{},"annotations":%s},"spec":{"containers":[{"name":'
        '"pause","image":"kubernetes/pause:go","resources":{"requests":'
        '{"cpu":"%dm","memory":"%d"}},"ports":[{"hostPort":0,'
        '"containerPort":80,"protocol":"TCP"}]}]%s}}')


def _pod_json(i: int, cpu: int, mem: int, sel: int, aff: int) -> str:
    if sel < 0 and aff < 0:
        return _POD % (i, "{}", cpu, mem, "")
    tail = (',"nodeSelector":{"%s":"pool-%d"}' % (POOL_LABEL, sel)
            if sel >= 0 else "")
    annotations = (json.dumps({AFFINITY_ANNOTATION_KEY:
                               _affinity_annotation(aff)})
                   if aff >= 0 else "{}")
    return _POD % (i, annotations, cpu, mem, tail)
