#!/usr/bin/env python3
"""The plain reference, put in the program's place: a one-thread
scheduler process over ``reference.py`` that lists the nodes, watches
pods, answers each pending pod with the reference's first best node and
binds it.  No JAX, nothing of the program.

It exists for two things.  The benchmark's own tests drive a whole run
of ``run.py`` against it at a tiny size (``RefSut`` below takes the
daemon's place), sound and with each fault the cell can have planted
where the answer is produced:

  ``--fault wrong_policy``     picks the fitting node with the LOWEST
                               score (breaks "each decision is one the
                               reference could have made"; the control)
  ``--fault state_unchanged``  never accounts its own placements (a step
                               that returns its state unchanged)
  ``--fault half_batch``       answers every second pod only
  ``--fault altered``          every 5th answer is replaced, after it was
                               decided, by the first node in index order
                               that fits (on a fleet of identical nodes
                               the neighbour of a best node is as good a
                               choice, so "moved to the next node" would
                               be no fault at all)

And it documents what "the reference" decides, executable end to end.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import cluster  # noqa: E402
import reference  # noqa: E402
import rig  # noqa: E402

FAULTS = ("none", "wrong_policy", "state_unchanged", "half_batch", "altered")


def _quantity(text: str) -> int:
    return int(text[:-1]) if text.endswith("m") else int(text)


class _Fleet:
    """``cluster.Nodes``-shaped arrays from the apiserver's node list."""

    def __init__(self, items: list):
        self.n = len(items)
        order = sorted(items, key=lambda it: int(
            it["metadata"]["name"].split("-")[1]))
        alloc = [it["status"]["allocatable"] for it in order]
        labels = [it["metadata"].get("labels", {}) for it in order]
        self.alloc_cpu = np.array([_quantity(a["cpu"]) for a in alloc])
        self.alloc_mem = np.array([_quantity(a["memory"]) for a in alloc])
        self.alloc_pods = np.array([int(a["pods"]) for a in alloc])
        self.pool = np.array([int(lab.get(cluster.POOL_LABEL, "x--1")
                                  .split("-", 1)[1]) for lab in labels])
        self.zone = np.array([int(lab.get(cluster.ZONE_LABEL, "x--1")
                                  .split("-", 1)[1]) for lab in labels])


def _pod_args(obj: dict) -> tuple:
    req = obj["spec"]["containers"][0]["resources"]["requests"]
    sel = obj["spec"].get("nodeSelector", {}).get(cluster.POOL_LABEL)
    aff = -1
    note = obj["metadata"].get("annotations", {}).get(
        cluster.AFFINITY_ANNOTATION_KEY)
    if note:
        term = json.loads(note)["nodeAffinity"][
            "preferredDuringSchedulingIgnoredDuringExecution"][0]
        aff = int(term["preference"]["matchExpressions"][0]["values"][0]
                  .split("-")[1])
    return (_quantity(req["cpu"]), _quantity(req["memory"]),
            int(sel.split("-")[1]) if sel else -1, aff)


def serve(api_url: str, fault: str) -> None:
    host, port = api_url.rsplit("/", 1)[-1].split(":")
    with urllib.request.urlopen(api_url + "/api/v1/nodes") as r:
        fleet = _Fleet(json.loads(r.read())["items"])
    state = reference.State(fleet)
    where: dict = {}                  # pod name -> (node, cpu, mem)
    pending: list = []
    lock = threading.Lock()

    # The apiserver replays only its last 1,024 events to a new watcher:
    # list what is pending first, then watch from the list's version.
    with urllib.request.urlopen(api_url + "/api/v1/pods") as r:
        listed = json.loads(r.read())
    for obj in listed["items"]:
        name, args = obj["metadata"]["name"], _pod_args(obj)
        bound_to = obj["spec"].get("nodeName")
        if not bound_to:
            pending.append((name, args))
        else:                       # what runs there already
            node = int(bound_to.split("-")[1])
            where[name] = (node, args[0], args[1])
            state.add(node, args[0], args[1])
    sock = socket.create_connection((host, int(port)))
    sock.sendall(b"GET /api/v1/pods?watch=1&resourceVersion=%d HTTP/1.1"
                 b"\r\nHost: ref\r\n\r\n"
                 % int(listed["metadata"]["resourceVersion"]))
    print("ready", flush=True)

    def watch() -> None:
        buf = b""
        while True:
            data = sock.recv(1 << 20)
            if not data:
                return
            buf += data
            cut = buf.rfind(b"\n")
            lines, buf = buf[:cut + 1], buf[cut + 1:]
            for line in lines.split(b"\n"):
                if not line.startswith(b'{"type"'):
                    continue
                ev = json.loads(line)
                obj = ev["object"]
                name = obj["metadata"]["name"]
                with lock:
                    if ev["type"] == "ADDED" and \
                            not obj["spec"].get("nodeName"):
                        pending.append((name, _pod_args(obj)))
                    elif ev["type"] == "DELETED" and name in where:
                        node, cpu, mem = where.pop(name)
                        if fault != "state_unchanged":
                            state.add(node, cpu, mem, -1)

    threading.Thread(target=watch, daemon=True).start()
    bind = socket.create_connection((host, int(port)))
    seen = 0
    while True:
        with lock:
            batch, pending[:] = pending[:256], pending[256:]
        if not batch:
            time.sleep(0.002)
            continue
        out = []
        for name, (cpu, mem, sel, aff) in batch:
            seen += 1
            if fault == "half_batch" and seen % 2:
                continue
            with lock:
                if fault == "wrong_policy":
                    ok = reference.fits(state, cpu, mem, sel)
                    sc = np.where(ok, reference.scores(state, cpu, mem, aff),
                                  1 << 30)
                    best = np.flatnonzero(sc == sc.min()) if ok.any() else []
                else:
                    best = reference.best_nodes(state, cpu, mem, sel, aff)
                if not len(best):
                    pending.append((name, (cpu, mem, sel, aff)))
                    continue
                node = int(best[0])
                if fault == "altered" and seen % 5 == 0:
                    node = int(np.flatnonzero(
                        reference.fits(state, cpu, mem, sel))[0])
                if fault != "state_unchanged":
                    state.add(node, cpu, mem)
                where[name] = (node, cpu, mem)
            body = json.dumps({"metadata": {"name": name,
                                            "namespace": cluster.NAMESPACE},
                               "target": {"kind": "Node",
                                          "name": f"node-{node}"}}).encode()
            out.append(b"POST /api/v1/namespaces/default/bindings HTTP/1.1"
                       b"\r\nHost: ref\r\nContent-Type: application/json\r\n"
                       b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
        if not out:
            continue
        bind.sendall(b"".join(out))
        got = b""
        while got.count(b"HTTP/1.1 ") < len(out) or not got.endswith(b"}"):
            got += bind.recv(1 << 16)


class RefSut:
    """Takes ``rig.Daemon``'s place in ``run.run_cell``: the same calls,
    a clean account on whatever platform the run asked for (there is no
    engine to fall back from), no counters and no device."""

    def __init__(self, api_url: str, config: dict, platform: str,
                 out_dir: str, fault: str = "none"):
        self.platform = platform
        self.child = rig.Child(
            "refsched", [sys.executable, os.path.abspath(__file__),
                         "--api-server", api_url, "--fault", fault],
            out_dir, env=dict(os.environ))

    def wait_prewarmed(self, timeout_s: float) -> float:
        t0 = time.monotonic()
        rig.wait_until("ready", lambda: "ready" in rig.tail(
            self.child.log_path), self.child, 30, period_s=0.05)
        return time.monotonic() - t0

    def account(self) -> dict:
        out = {"mode": "device", "platform": self.platform,
               "kind": "reference", "count": 1, "last_fault": None,
               "host_mode_seconds": 0.0, "invariant_violations": 0,
               "queue_depth": 0}
        out.update({family: 0.0 for family in rig.ACCOUNT_FAMILIES})
        return out

    def vars(self) -> dict:
        return {"postPrewarmCompiles": 0}

    def metrics(self) -> dict:
        return {}

    def ask(self, command: str, answer: str, timeout_s: float = 0) -> str:
        return json.dumps({"platform": self.platform, "kind": "reference",
                           "count": 1, "memory_peak_bytes": 0})

    def stop(self) -> int:
        self.child.stop(graceful_s=5)
        return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--api-server", required=True)
    p.add_argument("--fault", choices=FAULTS, default="none")
    opts = p.parse_args()
    serve(opts.api_server, opts.fault)
    return 0


if __name__ == "__main__":
    sys.exit(main())
