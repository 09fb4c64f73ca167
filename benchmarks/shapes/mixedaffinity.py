"""``mixedaffinity``: upstream's ``MixedSchedulingBasePod`` shapes.

``shapes/interpod.py``'s fleet and pause pod (loaded by path, as that
file loads ``cluster.py``) with the case's node label and its five pod
templates side by side:

  nodes   every node of ``node-default.yaml`` with its own
          ``kubernetes.io/hostname`` and, from the case's
          ``labelNodePrepareStrategy``, ``topology.kubernetes.io/zone``:
          ONE value (``zone1``) on all of them upstream; ``n_zones`` of
          the ``nodes`` spec spreads the fleet over more (node ``i`` in
          zone ``i % n_zones``), which only the benchmark's tests use, so
          that the blue term can fail
  base    ``pod-default.yaml``: no labels, no term
  blue    ``pod-with-pod-affinity.yaml``: ``color: blue``, ONE REQUIRED
          ``podAffinity`` term, ``{color: blue}`` on the zone key
  green   ``pod-with-pod-anti-affinity.yaml``: ``color: green``, ``name:
          test``, ONE REQUIRED ``podAntiAffinity`` term, ``{color:
          green}`` on the hostname (``interpod-5000n``'s term)
  red     ``pod-with-preferred-pod-affinity.yaml``: ``color: red``, ONE
          PREFERRED ``podAffinity`` term, weight 1, ``{color: red}`` on
          the hostname
  yellow  ``pod-with-preferred-pod-anti-affinity.yaml``: ``color:
          yellow``, ONE PREFERRED ``podAntiAffinity`` term, weight 1,
          ``{color: yellow}`` on the hostname

Each term is written as the ``scheduler.alpha.kubernetes.io/affinity``
annotation and names the one namespace the harness creates in; each pod
is created with ``status`` ahead of ``spec`` (``shapes/interpod.py`` says
why).

Pod parameters (``configs/<name>.json`` ``pods``): ``milli_cpu``,
``memory`` and ``pattern``, the template names pod ``i`` cycles through
(``pattern[i % len(pattern)]``; upstream's run ends at 3 : 2 : 2 : 2 : 2).
What ``references/mixedaffinity.py`` reads beside ``cluster.py``'s
arrays: ``nodes.zone`` (the zone's index per node), ``pods.group``
(per pod, its index into ``TEMPLATES``), ``pods.n_groups``,
``pods.labels[g]`` and ``pods.terms[g]`` (per group, its ``Term``s).
"""

import copy
import importlib.util
import json
import os
from typing import NamedTuple

import numpy as np

_spec = importlib.util.spec_from_file_location("interpod", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "interpod.py"))
interpod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(interpod)
cluster = interpod.cluster

ZONE_KEY = "topology.kubernetes.io/zone"


class Term(NamedTuple):
    """One (anti-)affinity term of a template."""

    anti: bool          # podAntiAffinity, else podAffinity
    required: bool      # requiredDuringScheduling..., else preferred
    weight: int         # of a preferred term; 0 on a required one
    match: dict         # labelSelector.matchLabels
    key: str            # "hostname" | "zone": the topologyKey


# name -> (labels, terms); the order is the group index
TEMPLATES = {
    "base": ({}, ()),
    "blue": ({"color": "blue"},
             (Term(False, True, 0, {"color": "blue"}, "zone"),)),
    "green": ({"color": "green", "name": "test"},
              (Term(True, True, 0, {"color": "green"}, "hostname"),)),
    "red": ({"color": "red"},
            (Term(False, False, 1, {"color": "red"}, "hostname"),)),
    "yellow": ({"color": "yellow"},
               (Term(True, False, 1, {"color": "yellow"}, "hostname"),)),
}
_KEYS = {"hostname": cluster.HOSTNAME_LABEL, "zone": ZONE_KEY}
_COMPACT = {"separators": (",", ":")}


class Nodes(cluster.Nodes):
    """``cluster.py``'s uniform fleet; ``zone[i]`` is node ``i``'s value
    of ``topology.kubernetes.io/zone`` (``zone<zone[i] + 1>``)."""

    def __init__(self, spec, seed):
        super().__init__(dict(spec, profile="uniform"), seed)
        self.zone = np.arange(self.n, dtype=np.int64) \
            % int(spec.get("n_zones", 1))

    def to_json(self):
        # cluster.py writes the v1.4 failure-domain labels for a zone;
        # this deployment's nodes carry upstream's key and nothing else
        plain = copy.copy(self)
        plain.zone = np.full(self.n, -1, np.int64)
        out = cluster.Nodes.to_json(plain)
        for obj, zone in zip(out, self.zone.tolist()):
            obj["metadata"]["labels"][ZONE_KEY] = "zone%d" % (zone + 1)
        return out


def _term_json(term: Term) -> dict:
    body = {"labelSelector": {"matchLabels": term.match},
            "namespaces": [cluster.NAMESPACE],
            "topologyKey": _KEYS[term.key]}
    if term.required:
        return body
    return {"weight": term.weight, "podAffinityTerm": body}


def _annotations(terms) -> bytes:
    if not terms:
        return b"{}"
    affinity: dict = {}
    for term in terms:
        field = "podAntiAffinity" if term.anti else "podAffinity"
        when = ("required" if term.required else "preferred") \
            + "DuringSchedulingIgnoredDuringExecution"
        affinity.setdefault(field, {}).setdefault(when, []) \
            .append(_term_json(term))
    return json.dumps({cluster.AFFINITY_ANNOTATION_KEY: json.dumps(
        affinity, **_COMPACT)}, **_COMPACT).encode()


class Pods(interpod.Pods):
    def __init__(self, spec, seed, nodes_spec=None):
        super().__init__(dict(spec, colors=[], run=1), seed, nodes_spec)
        names = list(TEMPLATES)
        self.n_groups = len(names)
        self.labels = [TEMPLATES[name][0] for name in names]
        self.terms = [TEMPLATES[name][1] for name in names]
        self.pattern = np.array([names.index(name)
                                 for name in spec["pattern"]], np.int64)
        self._meta = [
            (b'"labels":' + json.dumps(labels, **_COMPACT).encode(),
             b'"annotations":' + _annotations(terms))
            for labels, terms in zip(self.labels, self.terms)]

    def grow(self, n):
        cluster.Pods.grow(self, n)
        if len(self.group) < len(self.cpu):
            self.group = self.pattern[
                np.arange(len(self.cpu)) % len(self.pattern)]
