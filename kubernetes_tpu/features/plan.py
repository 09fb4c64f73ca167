"""What a launch's feature build keeps between launches.

``compile_batch`` / ``compile_volsvc`` build two kinds of tables: those
that follow the RESIDENT PODS (spread counts, the affinity planes' rows,
volume pods, service peers) and those that are a function of the NODES,
the vocabularies' capacities and one pod TEMPLATE alone.  The first kind
is computed per launch; the second is the same bytes launch after launch
while pods come and go, and a ``FeaturePlan`` holds it: the builders in
``features/batch.py`` and ``features/volumes.py`` look a table up here and
build it — with the code a plan-less call runs — only when it is not
there.  A call without a plan gets an empty one of its own, so there is
one path and a miss IS the build from nothing.

What is kept, and what it was built from:

* per fleet (``fleet``): ``node_zone_id`` / ``num_zones`` / ``any_zones``,
  the parsed taint vocabulary, the nodes' avoid-annotation entries (or the
  fact that no node carries one);
* per template key (``slot`` -> a row of ``tables`` and of ``meta``): the
  pod-side rows ``request`` .. ``images``, the selector signature, the
  (namespace, labels) the listers are asked with, the nonzero row, the
  parsed caches stamped onto the template's pods;
* per selector signature (``sel``): the ``sel_required`` / ``sel_pref``
  rows;
* assembled group tables (``stacks``), by the tuple of their rows' keys:
  selector signatures, the controller refs the lister ANSWERED (the avoid
  rows), the distinct nonzero rows, the all-zero spread planes;
* ``compile_volsvc``'s tables in their neutral form (``volsvc``), by the
  pod axis and the policy's node-label arguments.

Validity is what the build can observe, never a knob: ``begin`` takes the
cache's ``node_epoch`` (a node added, removed or changed drops
everything), ``check_vocab`` the capacities of the vocabularies whose
width a pod row has (a grown one drops the rows), and a template is known
by its key.  The listers are plain lists mutated in place, so nothing is
keyed on them: they are asked per launch and what is kept is keyed by
their ANSWER.  ``outcome`` says whether a launch reused the plan, and
the cause when it did not (``CAUSES``, the first that applies).

Kept arrays that reach a ``PodBatch`` whole are marked read-only
(``keep``): a writer that tried to reuse one in place raises instead of
moving the next launch's features.  Not thread-safe: the engine's plan is
used under the cache lock.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import numpy as np

from kubernetes_tpu.features import compiler as fc

# Why a launch could not reuse the plan, most general first; a launch
# with several reports the first.
CAUSES = ("node_epoch", "vocab", "template_new", "not_neutral")

# Templates (and, with them, signatures and stacks) kept before the rows
# are dropped and start again: a stream of never-repeating templates
# (a per-pod label) must not grow the tables without bound.
TEMPLATE_CAP = 4096
# Pod-axis sizes of the neutral volume / service tables kept.
VOLSVC_CAP = 64
# Assembled group tables kept (a [G, N] stack per distinct tuple of keys).
STACKS_CAP = 256


def keep(a: np.ndarray) -> np.ndarray:
    """Mark an array kept: shared between launches, never written."""
    a.flags.writeable = False
    return a


class Fleet:
    """The node-side tables of one ``node_epoch``."""

    __slots__ = ("node_zone_id", "num_zones", "any_zones", "vocab_taints",
                 "node_avoids")

    def __init__(self, node_zone_id: np.ndarray, num_zones: int,
                 any_zones: bool, vocab_taints: list):
        self.node_zone_id = node_zone_id
        self.num_zones = num_zones
        self.any_zones = any_zones
        self.vocab_taints = vocab_taints
        # list[set] per node, () when no node carries the annotation,
        # None until a launch with controller listers asks.
        self.node_avoids: Any = None


class Tables:
    """The pod-side rows of every kept template, one row per slot; the
    capacity doubles.  A launch gathers its ``[P, ...]`` leaves from
    these, so no leaf aliases them."""

    FIELDS = (("request", 4, np.int32), ("nonzero", 2, np.int32),
              ("zero_req", 0, bool), ("best_effort", 0, bool),
              ("host_idx", 0, np.int32), ("ports", "ports", bool),
              ("vol_ro", "volumes", bool), ("vol_rw", "volumes", bool),
              ("tol_ns", "taints", bool), ("tol_pref", "taints", bool),
              ("has_tols", 0, bool), ("images", "images", np.int32))

    def __init__(self, space: fc.FeatureSpace, rows: int = 8):
        self.rows = rows
        for name, width, dtype in self.FIELDS:
            if isinstance(width, str):
                width = getattr(space, width).capacity
            shape = (rows, width) if width else (rows,)
            setattr(self, name, np.full(shape, self._neutral(name), dtype))

    @staticmethod
    def _neutral(name: str) -> int:
        return -1 if name == "host_idx" else 0      # -1: no node named

    def grow(self) -> None:
        for name, _width, _dtype in self.FIELDS:
            a = getattr(self, name)
            setattr(self, name, np.concatenate(
                [a, np.full_like(a, self._neutral(name))]))
        self.rows *= 2


class Meta(NamedTuple):
    """What a template's slot holds beside its rows."""

    sel_sig: tuple          # (nodeSelector items, node affinity)
    lkey: tuple             # (namespace, labels): what the listers answer from
    namespace: str
    labels: dict[str, str]
    deleted: bool
    nz: tuple[int, int]     # the nonzero row, as nz_templates' key
    res_row: np.ndarray     # the parsed caches stamped onto the
    nz_row: np.ndarray      # template's pods (fc.pod_resource_row,
    affinity: Any           # pod_nonzero_row, Pod.affinity)


class FeaturePlan:
    def __init__(self) -> None:
        self.epoch: Optional[int] = None
        self.hits = 0
        self.misses: dict[str, int] = dict.fromkeys(CAUSES, 0)
        self._cause = len(CAUSES)
        self._drop_all()

    # -- validity -----------------------------------------------------------

    def _drop_rows(self) -> None:
        self.caps: Optional[tuple] = None
        self.slots: dict[tuple, int] = {}
        self.meta: list[Meta] = []
        self.tables: Optional[Tables] = None
        self.sel: dict = {}
        self.stacks: dict = {}

    def _drop_all(self) -> None:
        self.fleet: Optional[Fleet] = None
        self.volsvc: dict = {}
        self._drop_rows()

    def miss(self, cause: str) -> None:
        self._cause = min(self._cause, CAUSES.index(cause))

    def begin(self, node_epoch: int) -> None:
        """A launch starts, against the cache's ``node_epoch``."""
        self._cause = len(CAUSES)
        if node_epoch != self.epoch:
            self.epoch = node_epoch
            self._drop_all()
            self.miss("node_epoch")
        elif len(self.meta) > TEMPLATE_CAP:
            self._drop_rows()
            self.miss("template_new")

    def check_vocab(self, space: fc.FeatureSpace) -> None:
        """After the launch's new templates are interned: the widths the
        kept rows were built with still hold, or the rows go."""
        caps = (space.ports.capacity, space.volumes.capacity,
                space.taints.capacity, space.images.capacity)
        if caps != self.caps:
            if self.meta:
                self._drop_rows()
                self.miss("vocab")
            self.caps = caps
            self.tables = Tables(space)

    def outcome(self) -> tuple[str, str]:
        """``("hit", "")`` or ``("miss", cause)`` of the launch since
        ``begin``, counted."""
        if self._cause == len(CAUSES):
            self.hits += 1
            return "hit", ""
        cause = CAUSES[self._cause]
        self.misses[cause] += 1
        return "miss", cause

    def report(self) -> dict:
        """The ``/debug/vars`` payload."""
        return {"nodeEpoch": self.epoch, "hits": self.hits,
                "misses": dict(self.misses),
                "templates": len(self.meta), "selectorRows": len(self.sel),
                "volsvcKept": len(self.volsvc)}

    # -- templates ------------------------------------------------------------

    def add(self, key: tuple, meta: Meta) -> int:
        """Template ``key`` takes the next slot: an all-neutral row of
        ``tables`` for the caller to write."""
        slot = len(self.meta)
        if slot == self.tables.rows:
            self.tables.grow()
        self.slots[key] = slot
        self.meta.append(meta)
        self.miss("template_new")
        return slot

    def stack(self, kind: str, keys: tuple, build: Callable[[], Any]) -> Any:
        """An assembled group table, kept by the tuple of its rows' keys."""
        got = self.stacks.get((kind, keys))
        if got is None:
            if len(self.stacks) >= STACKS_CAP:
                self.stacks.clear()
            got = self.stacks[(kind, keys)] = build()
        return got
