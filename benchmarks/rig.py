"""The processes of one run and how the runner talks to them.

Three children, none sharing an interpreter with the load generator:
the native apiserver (built from ``native/apiserver.cpp`` by the
program's own Makefile; a failed build is an error), the scheduler daemon
through ``benchmarks/daemon.py`` (the only process that touches JAX), and
after the run a short child that reduces the profiler trace.  Pattern
copied from ``chip_smoke.py`` phase A; this module never imports JAX or
the program.
"""

from __future__ import annotations

import http.client
import json
import os
import re
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class RunFailure(Exception):
    """The run cannot produce a result line (no chip, dead child, ...)."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(f.tell() - n, 0))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


class Child:
    """One started process with its output in a log file."""

    def __init__(self, name: str, cmd: list[str], out_dir: str,
                 env: dict | None = None, stdin=None):
        self.name = name
        self.log_path = os.path.join(out_dir, f"{name}.log")
        with open(self.log_path, "wb") as out:
            self.proc = subprocess.Popen(
                cmd, env=env, cwd=REPO, stdout=out,
                stderr=subprocess.STDOUT, stdin=stdin)

    def require_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise RunFailure(f"{self.name} exited with code {rc}:\n"
                             f"{tail(self.log_path)}")

    def stop(self, graceful_s: float = 30.0) -> int | None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=graceful_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                return None
        return self.proc.returncode


def http_get(port: int, path: str, timeout: float = 10.0) -> bytes:
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request("GET", path)
        r = c.getresponse()
        body = r.read()
        if r.status != 200:
            raise RunFailure(f"GET :{port}{path} -> {r.status} {body[:200]!r}")
        return body
    finally:
        c.close()


def wait_until(what: str, cond, child: Child, timeout_s: float,
               period_s: float = 0.25):
    deadline = time.monotonic() + timeout_s
    while True:
        child.require_alive()
        try:
            got = cond()
        except (OSError, http.client.HTTPException, RunFailure):
            got = None
        if got:
            return got
        if time.monotonic() > deadline:
            raise RunFailure(f"{child.name}: {what} not reached in "
                             f"{timeout_s:.0f} s:\n{tail(child.log_path)}")
        time.sleep(period_s)


# -- Prometheus text ----------------------------------------------------------

_ROW = re.compile(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{([^}]*)\})?\s+(\S+)$')
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"')


def parse_metrics(text: str) -> dict:
    """``{family: [(labels_dict, value), ...]}`` of a /metrics page."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _ROW.match(line)
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        labels = dict(_LABEL.findall(m.group(2) or ""))
        out.setdefault(m.group(1), []).append((labels, value))
    return out


def family_sum(parsed: dict, family: str, labels: dict | None = None
               ) -> float | None:
    """Sum of a family's rows whose labels include ``labels``; None when
    the page has no such row at all."""
    rows = [v for lab, v in parsed.get(family, ())
            if all(lab.get(k) == want for k, want in (labels or {}).items())]
    return sum(rows) if rows else None


# -- apiserver ----------------------------------------------------------------

def build_apiserver() -> str:
    """``make -C native`` (the program's own build); returns the binary."""
    native = os.path.join(REPO, "native")
    if not os.path.exists(os.path.join(native, "apiserver.cpp")):
        raise RunFailure("the program is not here: native/apiserver.cpp "
                         "is missing")
    try:
        proc = subprocess.run(
            ["make", "-C", native, f"PYTHON={sys.executable}"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"))
    except (OSError, subprocess.TimeoutExpired) as err:
        raise RunFailure(f"building the native apiserver: {err}") from None
    binary = os.path.join(native, "kube-apiserver-native")
    if proc.returncode != 0 or not os.path.exists(binary):
        raise RunFailure(f"make -C native failed (rc {proc.returncode}):\n"
                         f"{proc.stdout[-1500:]}{proc.stderr[-1500:]}")
    return binary


class ApiServer:
    def __init__(self, out_dir: str):
        self.port = free_port()
        self.child = Child("apiserver",
                           [build_apiserver(), "--port", str(self.port)],
                           out_dir, env=dict(os.environ))
        wait_until("healthz", lambda: http_get(self.port, "/healthz"),
                   self.child, 30, period_s=0.05)

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def metrics(self) -> dict:
        return parse_metrics(http_get(self.port, "/metrics").decode())

    def post_list(self, kind: str, body: bytes, n_items: int) -> None:
        c = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
        try:
            c.request("POST", f"/api/v1/{kind}", body,
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            res = json.loads(r.read() or b"{}")
        finally:
            c.close()
        if r.status != 200 or res.get("created") != n_items:
            raise RunFailure(f"creating {kind}: status {r.status}, created "
                             f"{res.get('created')} of {n_items}")

    def resource_version(self) -> int:
        """The store's current version (a list that selects nothing)."""
        body = json.loads(http_get(
            self.port, "/api/v1/pods?fieldSelector=metadata.name%3D-none-"))
        return int(body["metadata"]["resourceVersion"])

    def list_pods(self) -> tuple[dict, int]:
        """``({pod index: node index or -1}, resourceVersion)`` from the
        apiserver's own list; a foreign name maps to index -2."""
        body = json.loads(http_get(self.port, "/api/v1/pods", timeout=120))
        out = {}
        for item in body["items"]:
            out[_index(item["metadata"]["name"], "p-")] = _index(
                item["spec"].get("nodeName") or "", "node-", empty=-1)
        return out, int(body["metadata"]["resourceVersion"])

    def stop(self) -> None:
        self.child.stop(graceful_s=10)


def _index(name: str, prefix: str, empty: int = -2) -> int:
    if not name:
        return empty
    if name.startswith(prefix) and name[len(prefix):].isdigit():
        return int(name[len(prefix):])
    return -2


# -- daemon -------------------------------------------------------------------

ACCOUNT_FAMILIES = ("scheduler_device_faults_total",
                    "scheduler_solve_fallback_total",
                    "scheduler_sanity_rejected_binds_total",
                    "scheduler_bind_failures_total")


class Daemon:
    """The scheduler through ``benchmarks/daemon.py``, pinned to
    ``platform``: a missing or busy chip is a start-up error there."""

    def __init__(self, api_url: str, config: dict, platform: str,
                 out_dir: str):
        self.port = free_port()
        self.ctl_dir = os.path.join(out_dir, "ctl")
        os.makedirs(self.ctl_dir)     # the run's out_dir is made anew
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS=platform,
                   **{k: str(v) for k, v in config["daemon"]["env"].items()})
        # Fixed path inside the checkout (the path is part of the cache
        # key); a directory the caller already chose is kept.
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(REPO, ".jax_cache"))
        self.lists_resident = int(config.get("resident_cap", 0)) > 0
        self.lists_s = None       # daemon start -> its first lists are in
        self.started = time.monotonic()
        self.child = Child(
            "daemon",
            [sys.executable, os.path.join(HERE, "daemon.py"),
             "--ctl-dir", self.ctl_dir, "--",
             "--api-server", api_url, "--port", str(self.port)]
            + [str(f) for f in config["daemon"]["flags"]],
            out_dir, env=env, stdin=subprocess.PIPE)

    def vars(self) -> dict:
        return json.loads(http_get(self.port, "/debug/vars"))

    def metrics(self) -> dict:
        return parse_metrics(http_get(self.port, "/metrics").decode())

    def lists_delivered(self) -> bool:
        """Whether the daemon's reflectors have handed their first lists
        (the nodes, and the resident pods where the configuration has
        any) to the handlers, read off ``/metrics`` alone: each counts
        its list's events once, when the whole list is in."""
        m = self.metrics()
        return all(
            (family_sum(m, "scheduler_handler_events_total",
                        {"handler": kind}) or 0) > 0
            for kind in ("nodes",) + (("pods",) if self.lists_resident
                                      else ()))

    def wait_prewarmed(self, timeout_s: float) -> float:
        """healthz comes up BEFORE prewarm finishes; pods created
        mid-prewarm would compile on the clock.  Returns seconds from
        daemon start.

        ``/debug/vars`` is NOT asked while the daemon lists the cluster:
        its ``cachedNodes`` goes through ``cache.nodes()``, which builds
        the cache's node tensors from whatever part of the list is in,
        and every node after that is appended to them row by row — the
        list of 5,000 nodes and 30,000 pods then takes 10-22 s for 2-4,
        runs past the reflectors' 10 s sync waits, and prewarm traces a
        partial cluster whose programs no cache holds (PERF.md section
        6, PR 29: the yardstick's own poll was what made ``setup_s`` of
        the 5,000-node cell swing between 37 and 140 s)."""
        wait_until("the first lists (scheduler_handler_events_total)",
                   self.lists_delivered, self.child, min(timeout_s, 300),
                   period_s=0.5)
        self.lists_s = time.monotonic() - self.started
        wait_until("prewarm", lambda: self.vars()["prewarmCacheStats"],
                   self.child, timeout_s, period_s=0.5)
        return time.monotonic() - self.started

    def ask(self, command: str, answer: str, timeout_s: float = 120.0) -> str:
        """Send one control line, wait for its answer file, return its
        text."""
        path = os.path.join(self.ctl_dir, answer)
        err = os.path.join(self.ctl_dir, command.split()[0] + ".err")
        self.child.proc.stdin.write((command + "\n").encode())
        self.child.proc.stdin.flush()

        def done():
            if os.path.exists(err):
                with open(err) as f:
                    raise RunFailure(f"daemon control {command!r}: {f.read()}")
            return os.path.exists(path)
        deadline = time.monotonic() + timeout_s
        while not done():
            self.child.require_alive()
            if time.monotonic() > deadline:
                raise RunFailure(f"daemon control {command!r}: no answer in "
                                 f"{timeout_s:.0f} s")
            time.sleep(0.02)
        with open(path) as f:
            return f.read()

    def account(self) -> dict:
        """The daemon's own account of how it solved: engine mode,
        platform, and every counter that means 'not the device path'."""
        v = self.vars()
        m = self.metrics()
        engine = v["engine"]
        out = {"mode": engine["mode"], "platform": engine["platform"],
               "kind": engine["deviceKind"], "count": engine["deviceCount"],
               "last_fault": engine["lastFault"],
               "host_mode_seconds": engine["hostModeSeconds"],
               "invariant_violations": v["invariantViolations"],
               "queue_depth": v["queueDepth"]}
        for family in ACCOUNT_FAMILIES:
            out[family] = family_sum(m, family) or 0.0
        return out

    def stop(self) -> int | None:
        return self.child.stop(graceful_s=60)
