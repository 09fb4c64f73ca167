"""Traffic kind ``poisson_open_churn``: ``poisson_open`` with upstream's
background churn beside it.

The arrivals, the warm-up bursts, ``steady()`` and the latencies are
``generators/poisson_open.py``'s, untouched: this kind loads that file
(as ``run.load_module`` does) and subclasses its ``Generator``.  It adds
ONE thread (``bench-churn``) with a keep-alive connection of its own
that does what scheduler_perf's ``churn`` op does in mode ``recreate``:
from the first Poisson arrival (after the warm-up bursts) until the
traffic stops — through the ramp, the window and the traced span — it
ticks every ``interval_ms``; on even ticks it creates ``number`` objects
of each kind in ``objects`` from the configuration's ``churn_templates``
(``{k}`` in a template counts the objects of that kind made so far), on
odd ticks it deletes them.  The first tick falls at a seed-derived
offset inside one interval; nothing else of the churn depends on the
seed.

When the traffic stops the thread deletes whatever it still holds and
has every answer before it returns (``stop_creating`` joins it), so the
apiserver's list at close holds the traffic's pods alone.  Any answer
that is not 201 (create) / 200 (delete) goes to ``book.errors`` (the
judge's ``client_errors``, limit 0).  Each tick is timed from when it was
due.  The churn's pod is scheduled and bound by the system like any pod;
its name does not match the observer's ``p-<i>``, so it is in no count of
the book and in no latency.  A delete of it WAITS until the apiserver
shows it bound (polled on the churn's connection; a tick that waited is
late by that much): a pod deleted under a bind in flight makes the bind
fail, which upstream logs and goes on from, and which the judge here
counts (``bind_failures``, limit 0) — the last create tick may fall
milliseconds before the traffic stops.

What the kind needs of the program, checked when this file is loaded
(``require_node_capacity``): a node axis with a CAPACITY, which the
daemon states as ``nodeCapacity`` on its ``/debug/vars`` page.  A
program without one reshapes its node tensors at every node event: it
compiles every launch size again at 5,001 rows on the live path, rebuilds
5,000 rows and re-attaches 30,000 pods under the cache lock every other
second, and falls seconds behind the arrivals — it cannot hold the
configuration's guarantees (my chip runs, PR 36, and the driver's: the
judge read its view of the deletes staler than ``max_lag_s`` in 5 of 5
runs, ``gap_mean`` 0.16-0.33 against 0.1; from the arrivals' start its
ramp had no bound and one run died of a race the backlog exposed).  On
such a tree the run ends at once with exit code 1 and no result line,
before anything is started: that program cannot run this deployment.

Parameters (``traffic/<name>.json``): ``poisson_open``'s, and ``churn``:
``{"mode": "recreate", "number", "interval_ms", "objects"}``.
``report()`` adds, over the ticks that were due inside the window (the
ramp before it and the traced span after it are not counted):
``churn_ticks``, ``churn_node_creates``, ``churn_node_deletes``,
``churn_late_ms_max``.
"""

from __future__ import annotations

import http.client
import importlib.util
import json
import os
import threading
import time

import rig

_spec = importlib.util.spec_from_file_location(
    "bench_generators_poisson_open",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "poisson_open.py"))
_parent = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_parent)

BIND_TIMEOUT_S = 60.0     # run.DRAIN_TIMEOUT_S: later is never


def require_node_capacity(repo: str) -> None:
    """Fail the run cleanly where the program under ``repo`` has no
    node-axis capacity (the module's docstring says why): its daemon's
    ``/debug/vars`` page, ``kubernetes_tpu/scheduler/__main__.py`` — the
    file ``run.main`` finds the program by — does not state
    ``nodeCapacity``."""
    path = os.path.join(repo, "kubernetes_tpu", "scheduler", "__main__.py")
    with open(path) as f:
        if "nodeCapacity" not in f.read():
            raise rig.RunFailure(
                "traffic kind poisson_open_churn: this program has no "
                "node-axis capacity (no nodeCapacity on /debug/vars), so a "
                "node event reshapes its node tensors and it cannot hold "
                "the deployment's guarantees; it cannot run this cell")


require_node_capacity(rig.REPO)

# where each kind of object is created, and where one of them is deleted
_PATHS = {"node": ("/api/v1/nodes", "/api/v1/nodes/{name}"),
          "pod": ("/api/v1/pods",
                  "/api/v1/namespaces/{namespace}/pods/{name}"),
          "service": ("/api/v1/services",
                      "/api/v1/namespaces/{namespace}/services/{name}")}


def _fill(template, k: int):
    """The template with ``{k}`` replaced in every string."""
    if isinstance(template, dict):
        return {key: _fill(v, k) for key, v in template.items()}
    if isinstance(template, list):
        return [_fill(v, k) for v in template]
    if isinstance(template, str):
        return template.replace("{k}", str(k))
    return template


class Generator(_parent.Generator):
    def _start_creators(self) -> None:
        churn = self.params["churn"]
        if churn["mode"] != "recreate":
            raise ValueError(f"churn mode {churn['mode']!r}: only "
                             f"'recreate' is written")
        self.churn_interval_s = float(churn["interval_ms"]) / 1e3
        self.churn_number = int(churn["number"])
        self.churn_objects = list(churn["objects"])
        self.churn_templates = self.config["churn_templates"]
        # the first tick's offset inside one interval: all the seed moves
        self.churn_offset_s = (self.seed * 7919 % 1000) / 1000.0 \
            * self.churn_interval_s
        self.churn_held: list = []      # (kind, delete path) of what lives
        self.churn_stats = {"churn_ticks": 0.0, "churn_node_creates": 0.0,
                            "churn_node_deletes": 0.0,
                            "churn_late_ms_max": 0.0}
        super()._start_creators()
        t = threading.Thread(target=self._churn, daemon=True,
                             name="bench-churn")
        t.start()
        self.threads.append(t)

    def _ask(self, conn, method: str, path: str, body: dict | None,
             want: int) -> None:
        """One request on the churn's own connection."""
        payload = None if body is None else json.dumps(body).encode()
        conn.request(method, path, payload,
                     {"Content-Type": "application/json"} if payload else {})
        r = conn.getresponse()
        raw = r.read()
        if r.status != want:
            self.book.errors.append(
                f"churn: {method} {path} answered {r.status}, not {want}: "
                f"{raw[:120]!r}")

    def _churn_create(self, conn, k: int) -> int:
        """Create tick ``k``: ``number`` objects of each kind.  Returns
        the nodes made."""
        for j in range(self.churn_number):
            for kind in self.churn_objects:
                obj = _fill(self.churn_templates[kind],
                            k * self.churn_number + j)
                create, delete = _PATHS[kind]
                self._ask(conn, "POST", create, obj, 201)
                self.churn_held.append(
                    (kind, delete.format(**obj["metadata"])))
        return self.churn_number * self.churn_objects.count("node")

    def _wait_bound(self, conn, path: str) -> None:
        """Until the apiserver shows the pod at ``path`` bound."""
        deadline = time.monotonic() + BIND_TIMEOUT_S
        while True:
            conn.request("GET", path)
            r = conn.getresponse()
            raw = r.read()
            if r.status == 200 and (json.loads(raw).get("spec") or {}).get(
                    "nodeName"):
                return
            if r.status != 200 or time.monotonic() > deadline:
                self.book.errors.append(
                    f"churn: {path} not bound after {BIND_TIMEOUT_S:.0f} s "
                    f"(status {r.status})")
                return
            time.sleep(0.005)

    def _churn_delete(self, conn) -> int:
        """Delete what the churn holds, the newest first, a pod once it
        is bound.  Returns the nodes deleted."""
        nodes = 0
        while self.churn_held:
            kind, path = self.churn_held[-1]
            if kind == "pod":
                self._wait_bound(conn, path)
            self._ask(conn, "DELETE", path, None, 200)
            self.churn_held.pop()
            nodes += kind == "node"
        return nodes

    def _churn(self) -> None:
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=30)
        stats = self.churn_stats
        try:
            while self.creating and not self.warmed:
                time.sleep(0.01)
            due = time.monotonic() + self.churn_offset_s
            tick = 0
            while self.creating:
                now = time.monotonic()
                if now < due:
                    time.sleep(min(due - now, 0.02))
                    continue
                made = gone = 0
                if tick % 2 == 0:
                    made = self._churn_create(conn, tick // 2)
                else:
                    gone = self._churn_delete(conn)
                late_ms = (time.monotonic() - due) * 1e3
                t_close = self.t_close      # set after t_open: read first
                # neither the ramp nor the traced span is counted
                if t_close is not None and self.t_open <= due < t_close:
                    stats["churn_ticks"] += 1
                    stats["churn_node_creates"] += made
                    stats["churn_node_deletes"] += gone
                    stats["churn_late_ms_max"] = max(
                        stats["churn_late_ms_max"], late_ms)
                tick += 1
                due += self.churn_interval_s
        except (OSError, http.client.HTTPException) as err:
            self.book.errors.append(f"churn: {err!r}")
        # nothing of the churn is left in the apiserver's list at close
        try:
            self._churn_delete(conn)
        except (OSError, http.client.HTTPException) as err:
            self.book.errors.append(f"churn clean-up: {err!r}")
        conn.close()
        self.book.cpu_s["bench-churn"] = time.thread_time()

    def report(self) -> dict:
        out = super().report()
        if out:
            out.update(self.churn_stats)
        return out
