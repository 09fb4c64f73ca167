#!/usr/bin/env python3
"""The plain reference, put in the program's place: a one-thread
scheduler process over the configuration's reference (``reference.py`` or
the ``references/<word>.py`` it names) that watches pods, answers each
pending pod with the reference's first best node and binds it.  No JAX,
nothing of the program.  What a pod or a node IS it takes from the
configuration's shapes by the index in ``p-<i>`` / ``node-<i>`` (made
from the configuration and ``--seed``, as the runner and the judge make
them), not from a parser of its own: a new shape needs no edit here.

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
  ``--fault colocate``         ONE answer, the second of the run, goes to
                               the node the first went to (breaks a
                               guarantee BETWEEN pods, such as a required
                               anti-affinity, where a configuration's
                               reference states one; two pause pods of
                               today's cells may share a node, so there
                               it is no fault, and it falls in the ramp,
                               so only a number counted over every bind
                               can see it)

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

import rig  # noqa: E402
import run  # noqa: E402

FAULTS = ("none", "wrong_policy", "state_unchanged", "half_batch", "altered",
          "colocate")
NAMESPACE = "default"


def _index(name: str) -> int:
    return int(name.split("-")[1])


def serve(api_url: str, fault: str, config: dict, seed: int) -> None:
    host, port = api_url.rsplit("/", 1)[-1].split(":")
    shapes, reference = run.parts_of(config)
    fleet = shapes.Nodes(config["nodes"], seed)
    pods = shapes.Pods(config["pods"], seed, config["nodes"])
    state = reference.State(fleet, pods)
    where: dict = {}                  # pod index -> node
    pending: list = []                # pod indices, oldest first
    lock = threading.Lock()

    def known(name: str) -> int:
        pod = _index(name)
        pods.grow(pod + 1)
        return pod

    # The apiserver replays only its last 1,024 events to a new watcher:
    # list what is pending first, then watch from the list's version.
    with urllib.request.urlopen(api_url + "/api/v1/pods") as r:
        listed = json.loads(r.read())
    for obj in listed["items"]:
        pod = known(obj["metadata"]["name"])
        bound_to = obj["spec"].get("nodeName")
        if not bound_to:
            pending.append(pod)
        else:                       # what runs there already
            where[pod] = _index(bound_to)
            state.add(pod, where[pod])
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
                pod = known(obj["metadata"]["name"])
                with lock:
                    if ev["type"] == "ADDED" and \
                            not obj["spec"].get("nodeName"):
                        pending.append(pod)
                    elif ev["type"] == "DELETED" and pod in where:
                        node = where.pop(pod)
                        if fault != "state_unchanged":
                            state.add(pod, node, -1)

    threading.Thread(target=watch, daemon=True).start()
    bind = socket.create_connection((host, int(port)))
    seen = answered = 0
    first_node = None
    while True:
        with lock:
            batch, pending[:] = pending[:256], pending[256:]
        if not batch:
            time.sleep(0.002)
            continue
        out = []
        for pod in batch:
            seen += 1
            if fault == "half_batch" and seen % 2:
                continue
            with lock:
                if fault == "wrong_policy":
                    ok = reference.fits(state, pod)
                    sc = np.where(ok, reference.scores(state, pod), 1 << 30)
                    best = np.flatnonzero(sc == sc.min()) if ok.any() else []
                else:
                    best = reference.best_nodes(state, pod)
                if not len(best):
                    pending.append(pod)
                    continue
                node = int(best[0])
                if fault == "altered" and seen % 5 == 0:
                    node = int(np.flatnonzero(reference.fits(state, pod))[0])
                if answered == 0:
                    first_node = node
                elif answered == 1 and fault == "colocate":
                    node = first_node
                answered += 1
                if fault != "state_unchanged":
                    state.add(pod, node)
                where[pod] = node
            body = json.dumps({"metadata": {"name": f"p-{pod}",
                                            "namespace": NAMESPACE},
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
                 out_dir: str, fault: str = "none", seed: int = 0):
        """``seed`` is the run's: the shapes are made from it."""
        self.platform = platform
        config_path = os.path.join(out_dir, "refsched.config.json")
        with open(config_path, "w") as f:
            json.dump(config, f)
        self.child = rig.Child(
            "refsched", [sys.executable, os.path.abspath(__file__),
                         "--api-server", api_url, "--fault", fault,
                         "--config", config_path, "--seed", str(seed)],
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
    p.add_argument("--config", required=True,
                   help="the configuration as the run has it, a JSON file")
    p.add_argument("--seed", type=int, required=True)
    opts = p.parse_args()
    serve(opts.api_server, opts.fault, run.load_json(opts.config), opts.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
