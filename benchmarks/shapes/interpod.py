"""``interpod``: upstream's ``SchedulingPodAntiAffinity`` shapes.

``cluster.py``'s uniform fleet (every node of ``node-default.yaml`` with
its own ``kubernetes.io/hostname`` label, upstream's
``uniqueNodeLabelStrategy``) and the pause pod of ``pod-default.yaml`` as
``pod-with-pod-anti-affinity.yaml`` has it: labels ``color: <colour>``,
``name: test`` and ONE required ``podAntiAffinity`` term, ``matchLabels
{color: <colour>}`` on the hostname, written as the
``scheduler.alpha.kubernetes.io/affinity`` annotation (the v1.3 / 1.4 API
this program speaks) and naming the one namespace the harness creates in.

Pod parameters (``configs/<name>.json`` ``pods``): ``milli_cpu``,
``memory``, ``colors`` (the label groups; upstream's template has one,
``["green"]``) and ``run`` (consecutive pods a group).  ``group[i]`` is
pod ``i``'s index into ``colors`` and ``n_groups`` their number: what
``references/interpod.py`` reads beside ``cluster.py``'s arrays.  One
group here; the parameters stay so that a deployment with several can
reuse the file.

A pod is created with ``"status": {"phase": "Pending"}`` (what an
apiserver gives a new pod), written AHEAD of ``spec``.  Pods of this
deployment can wait for a node; the daemon then writes ``PodScheduled =
False`` into ``status.conditions`` (upstream's podConditionUpdater), and
the apiserver keeps the key order it was given.  With ``status`` first,
``spec.nodeName`` stays the last key of the bind's watch line, which is
where ``loadgen.py``'s observer looks for it; with no ``status`` at
create the update appends it after ``spec`` and that pod's bind is
never seen.
"""

import importlib.util
import json
import os

import numpy as np

_spec = importlib.util.spec_from_file_location("cluster", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "cluster.py"))
cluster = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cluster)

Nodes = cluster.Nodes


def _labels(color: str) -> bytes:
    return json.dumps({"color": color, "name": "test"},
                      separators=(",", ":")).encode()


def _annotations(color: str) -> bytes:
    term = {"labelSelector": {"matchLabels": {"color": color}},
            "namespaces": [cluster.NAMESPACE],
            "topologyKey": cluster.HOSTNAME_LABEL}
    compact = {"separators": (",", ":")}
    return json.dumps({cluster.AFFINITY_ANNOTATION_KEY: json.dumps(
        {"podAntiAffinity":
         {"requiredDuringSchedulingIgnoredDuringExecution": [term]}},
        **compact)}, **compact).encode()


_STATUS = b'},"status":{"phase":"Pending"},"spec":{'


class Pods(cluster.Pods):
    def __init__(self, spec, seed, nodes_spec=None):
        super().__init__(dict(spec, profile="uniform"), seed, nodes_spec)
        colors = list(spec.get("colors", ["green"]))
        self.n_groups, self.run = len(colors), int(spec.get("run", 1))
        self.group = np.zeros(0, np.int64)       # per pod, its own array
        self._meta = [(b'"labels":' + _labels(c),
                       b'"annotations":' + _annotations(c)) for c in colors]

    def grow(self, n):
        super().grow(n)
        if len(self.group) < len(self.cpu):
            self.group = np.arange(len(self.cpu)) // self.run % self.n_groups

    def json_bytes(self, i):
        body = super().json_bytes(i)
        labels, annotations = self._meta[int(self.group[i])]
        return body.replace(b'"labels":{}', labels, 1) \
            .replace(b'"annotations":{}', annotations, 1) \
            .replace(b'},"spec":{', _STATUS, 1)

    def list_body(self, start, stop):
        return b'{"kind":"List","items":[' + b",".join(
            self.json_bytes(i) for i in range(start, stop)) + b"]}"
