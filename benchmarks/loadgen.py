"""The client side of a run: bind observer, pod retirer, and the base
class every traffic kind (``generators/<kind>.py``) extends.

All of it runs as a few threads of the runner's process — never in the
daemon's — and reports its own CPU time (``client.busy_pct``) so that a
starved generator is not read as a slow scheduler.

* ``Observer`` holds ONE watch on pods with the field selector
  ``spec.nodeName!=``: a bind arrives as ADDED, a retired pod as DELETED.
  Lines are scanned for name and ``nodeName``, not JSON-decoded, and
  stamped with the monotonic clock of the ``recv`` that delivered them.
* ``Retirer`` keeps the resident bound population at ``resident_cap``: it
  deletes the OLDEST bound pods over one pipelined keep-alive connection.
  Pods finish in every real cluster; this is the product's normal path
  (assigned-pod watch -> cache.remove_pod -> dirty row -> scatter).
* ``Traffic`` wires both to a creator that the kind provides.
"""

from __future__ import annotations

import collections
import http.client
import json
import re
import socket
import threading
import time

# One watch line: {"type":"ADDED","object":{"metadata":{"name":"p-7",...},
# "spec":{...,"nodeName":"node-3"}}}\n — nodeName is the last key the
# apiserver writes.  Group 3 is empty for a node that is not ``node-<i>``.
_EVENT = re.compile(
    rb'\{"type":"([A-Z]+)","object":\{"metadata":\{"name":"p-(\d+)"[^\n]*?'
    rb'"nodeName":"(?:node-(\d+)|[^"]*)"\}\}\}\n')
BIND, DELETE = 0, 1
_STATUS = re.compile(rb"HTTP/1\.1 (\d\d\d)")


def statuses(carry: bytes, data: bytes) -> tuple[list, bytes]:
    """The status codes of the pipelined answers in ``data`` and the carry
    for the next read: a status line split over two reads is found by the
    second (the carry is shorter than the pattern, so none is counted
    twice)."""
    buf = carry + data
    return _STATUS.findall(buf), buf[-11:]


class Book:
    """What the client has seen and asked for.  Written by the observer
    (events, resident), the retirer (retire_*) and the creator
    (created); counters are plain ints under the GIL, read racily only
    for pacing."""

    def __init__(self):
        self.events: list = []          # (BIND|DELETE, pod, node, t) in watch order
        self.bind_t: dict = {}          # pod -> monotonic time its bind was seen
        self.resident = collections.deque()   # bound, retirement not yet asked
        self.n_bound = 0
        self.n_deleted_seen = 0
        self.n_created = 0              # acknowledged creates
        self.n_retire_asked = 0
        self.n_retire_acked = 0
        self.errors: list = []          # operations of the client that failed
        self.cpu_s: dict = {}           # thread name -> CPU seconds, at its end

    @property
    def n_pending(self) -> int:
        return self.n_created - self.n_bound

    @property
    def n_resident(self) -> int:
        return self.n_bound - self.n_retire_asked


class Observer(threading.Thread):
    def __init__(self, port: int, book: Book, from_rv: int):
        super().__init__(name="bench-observer", daemon=True)
        self.book = book
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.sock.sendall(
            b"GET /api/v1/pods?watch=1&resourceVersion=%d"
            b"&fieldSelector=spec.nodeName!%%3D HTTP/1.1\r\nHost: bench\r\n\r\n"
            % from_rv)
        self.stopping = False

    def run(self) -> None:
        book = self.book
        buf = b""
        try:
            while True:
                data = self.sock.recv(1 << 20)
                if not data:
                    break
                t = time.monotonic()
                buf += data
                cut = buf.rfind(b"\n")
                if cut < 0:
                    continue
                events, bind_t, resident = \
                    book.events, book.bind_t, book.resident
                n_bind = n_del = 0
                for m in _EVENT.finditer(buf, 0, cut + 1):
                    kind, pod, node = m.groups()
                    pod = int(pod)
                    node = int(node) if node else -2
                    if kind == b"DELETED":
                        events.append((DELETE, pod, node, t))
                        n_del += 1
                    else:
                        events.append((BIND, pod, node, t))
                        bind_t.setdefault(pod, t)
                        resident.append(pod)
                        n_bind += 1
                buf = buf[cut + 1:]
                book.n_bound += n_bind
                book.n_deleted_seen += n_del
        except OSError as err:
            if not self.stopping:
                book.errors.append(f"observer: {err!r}")
        if not self.stopping:
            book.errors.append("observer: the watch stream ended")
        book.cpu_s[self.name] = time.thread_time()

    def stop(self) -> None:
        self.stopping = True
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()


class Retirer(threading.Thread):
    BATCH = 512
    MIN_BATCH = 64        # or whatever is over the cap after MAX_WAIT_S:
    MAX_WAIT_S = 0.05     # a system call per pod would cost more than the pod

    def __init__(self, port: int, book: Book, cap: int):
        super().__init__(name="bench-retirer", daemon=True)
        self.book, self.cap = book, cap
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.stopping = False

    def run(self) -> None:
        book = self.book
        try:
            waited = 0.0
            while not self.stopping:
                over = len(book.resident) - self.cap
                if over <= 0 or (over < self.MIN_BATCH
                                 and waited < self.MAX_WAIT_S):
                    time.sleep(0.005)
                    waited += 0.005
                    continue
                waited = 0.0
                batch = [book.resident.popleft()
                         for _ in range(min(over, self.BATCH))]
                book.n_retire_asked += len(batch)
                self.sock.sendall(b"".join(
                    b"DELETE /api/v1/namespaces/default/pods/p-%d HTTP/1.1"
                    b"\r\nHost: bench\r\n\r\n" % pod for pod in batch))
                answered = ok = 0
                carry = b""
                while answered < len(batch):
                    data = self.sock.recv(1 << 16)
                    if not data:
                        raise OSError("connection closed")
                    found, carry = statuses(carry, data)
                    answered += len(found)
                    ok += found.count(b"200")
                book.n_retire_acked += ok
                if ok != len(batch):
                    book.errors.append(
                        f"retirer: {len(batch) - ok} of {len(batch)} deletes "
                        f"refused")
        except OSError as err:
            if not self.stopping:
                book.errors.append(f"retirer: {err!r}")
        book.cpu_s[self.name] = time.thread_time()

    def stop(self) -> None:
        self.stopping = True
        self.join(timeout=10)
        self.sock.close()


def post_list(conn: http.client.HTTPConnection, body: bytes, n_items: int,
              book: Book) -> None:
    """One ``List`` create on a keep-alive connection; every item must be
    acknowledged with 201."""
    conn.request("POST", "/api/v1/pods", body,
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    raw = r.read()
    created = json.loads(raw or b"{}").get("created") if r.status == 200 \
        else None
    if created != n_items:
        book.errors.append(f"creator: status {r.status}, created {created} "
                           f"of {n_items}: {raw[:200]!r}")
    book.n_created += created or 0


class Traffic:
    """Base of a traffic kind.  The runner calls, in order: ``start``
    (begin the ramp), ``steady`` (poll), ``open_window``, ``stop_creating``,
    ``drained`` and ``settled`` (poll), ``stop``, then ``n_offered``,
    ``attempted_failed`` and ``report``.

    A kind overrides ``_start_creators``, ``n_offered``,
    ``attempted_failed``, ``report``, and ``steady`` where the cap alone
    is not its steady state."""

    def __init__(self, port: int, pods, config: dict, params: dict,
                 seed: int, seconds: float, from_rv: int, resident=(),
                 launch_buckets=()):
        """``launch_buckets``: the launch sizes the system under test
        says it compiled for (empty where it reports none).
        ``resident[i]``: the node on which pod ``i`` was created bound
        before the daemon started (``run.prefill``); the kind's own
        creates start at ``first_pod = len(resident)``.  The watch starts
        at ``from_rv``, after them (the apiserver replays only its last
        1,024 events), so they enter the record here."""
        self.port, self.pods = port, pods
        self.first_pod = len(resident)
        self.config, self.params = config, params
        self.seed, self.seconds = seed, seconds
        self.launch_buckets = [int(b) for b in launch_buckets]
        self.cap = int(config["resident_cap"])
        self.book = book = Book()
        t = time.monotonic()
        book.events = [(BIND, pod, node, t)
                       for pod, node in enumerate(resident)]
        book.bind_t = {pod: t for pod in range(len(resident))}
        book.resident.extend(range(len(resident)))
        book.n_created = book.n_bound = len(resident)
        self.observer = Observer(port, self.book, from_rv)
        self.retirer = Retirer(port, self.book, self.cap)
        self.creating = True
        self.t_start = self.t_open = self.t_close = None
        self.threads: list = []

    def start(self) -> None:
        self.t_start = time.monotonic()
        self.observer.start()
        self.retirer.start()
        self._start_creators()

    def _start_creators(self) -> None:
        raise NotImplementedError

    def steady(self) -> bool:
        """The resident population has reached the cap and retirements
        are flowing."""
        return self.book.n_retire_acked > 0 \
            and self.book.n_resident >= self.cap

    def settled(self) -> bool:
        """Every retirement the apiserver acknowledged has come back on
        the watch (so the record is whole)."""
        return self.book.n_deleted_seen >= self.book.n_retire_acked

    def open_window(self, t_open: float, t_close: float) -> None:
        self.t_open, self.t_close = t_open, t_close

    def stop_creating(self) -> None:
        self.creating = False
        for t in self.threads:
            t.join(timeout=30)

    def drained(self) -> bool:
        """Every acknowledged create is bound."""
        return self.book.n_pending <= 0

    def stop(self) -> None:
        self.retirer.stop()
        self.observer.stop()
        self.observer.join(timeout=10)

    # -- what the window held, for the metrics -------------------------------

    def binds_in(self, t0: float, t1: float) -> int:
        return sum(1 for kind, _p, _n, t in self.book.events
                   if kind == BIND and t0 <= t < t1)

    def n_offered(self) -> int:
        """Pods written to the apiserver over the whole run, the resident
        ones included (names ``p-0`` .. ``p-<n-1>``)."""
        raise NotImplementedError

    def attempted_failed(self) -> tuple[int, int]:
        """(pods offered in the window, how many of them were never
        bound)."""
        raise NotImplementedError

    def report(self) -> dict:
        """Runner values of this kind, by metric-facing key."""
        return {}
