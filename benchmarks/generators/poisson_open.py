"""Traffic kind ``poisson-open`` (open loop).

Independent single-pod creates on a Poisson schedule at a fixed
``rate_pods_s``, each POSTed when due on one pipelined keep-alive
connection, each timed FROM WHEN IT WAS DUE (a stall therefore charges
every pod it delays).  Every seed gets the same multiset of gaps — one
canonical block of exponential gaps, scaled to the rate exactly — in
another order.  The retirer holds the bound population at the
configuration's ``resident_cap``.

The ramp first WARMS THE DAEMON'S SHAPES with live pods: one ``List``
create per bucket of the launch ladder THE DAEMON ITSELF REPORTS (the
numeric keys of ``prewarmCacheStats`` in its ``/debug/vars``, handed in
as ``launch_buckets``), largest first, then a single pod, each sent once
the one before is bound.  The product's prewarm uses a minimal sample
pod, and the first live launch of each bucket otherwise compiles inside
the window (PERF.md, "compiles on the clock").  No size of the ladder is
written into a traffic file: a later change of the ladder changes the
bursts with it, and a system that reports no ladder gets none.  Then the
Poisson arrivals start.

Steady state: the cap reached, retirements flowing, and no more than
``steady_pending_s`` seconds' worth of arrivals unbound (the backlog the
ramp's compiles built has drained).

Parameters (``traffic/<name>.json``): ``rate_pods_s``,
``steady_pending_s``.  ``TICK_S``: creates due within one tick are
written together (a system call costs tens of microseconds on the chip
machine's sandboxed kernel); each is still timed from when it was due,
and ``generator.late_ms_p99`` says what the tick cost.
"""

from __future__ import annotations

import bisect
import socket
import threading
import time

import numpy as np

import loadgen

_BLOCK = 8192
TICK_S = 0.002
PAUSE_S = 0.05


class Generator(loadgen.Traffic):
    def _start_creators(self) -> None:
        self.rate = float(self.params["rate_pods_s"])
        canon = np.random.RandomState(0).exponential(1.0, _BLOCK)
        self.canon = canon * (_BLOCK / self.rate / canon.sum())
        self.rng = np.random.RandomState((self.seed + 2) % (2 ** 32))
        self.due: list = []           # monotonic due time of create i (pod first_pod + i)
        self.sent: list = []          # monotonic time pod i was written
        self.t_stopped = None         # when the creator stopped writing
        # the creator's own longest stalls inside the window, so that a
        # run whose GENERATOR ran late says where: in a write the
        # apiserver did not take, in making the next block, or in a loop
        # pass that lost the CPU; and how many passes took over
        # PAUSE_S (a block build takes ~25 ms; the chip machines pause
        # everything for ~110 ms now and then, and a window's p95 follows
        # how often: PERF.md, PR 29)
        self.stall_s = {"send": 0.0, "extend": 0.0, "pass": 0.0}
        self.pauses = 0
        self.requests: list = []
        self.warm = sorted(self.launch_buckets, reverse=True)
        if self.warm:
            self.warm.append(1)       # the single-pod decision path
        self.first_pod += sum(self.warm)     # the arrivals follow the bursts
        self.warmed = not self.warm
        t = threading.Thread(target=self._create, daemon=True,
                             name="bench-creator")
        t.start()
        self.threads.append(t)

    def _extend(self, origin: float | None = None) -> None:
        """One more block of the schedule and its request bytes."""
        last = self.due[-1] if self.due else origin
        gaps = self.canon[self.rng.permutation(_BLOCK)]
        start = len(self.due)
        self.due.extend((last + np.cumsum(gaps)).tolist())
        self.pods.grow(self.first_pod + len(self.due))
        for i in range(start, len(self.due)):
            body = self.pods.json_bytes(self.first_pod + i)
            self.requests.append(
                b"POST /api/v1/pods HTTP/1.1\r\nHost: bench\r\nContent-Type: "
                b"application/json\r\nContent-Length: %d\r\n\r\n%s"
                % (len(body), body))

    def _acks(self, data: bytes) -> None:
        """Count the status lines in what the server answered."""
        found, self._carry = loadgen.statuses(self._carry, data)
        for status in found:
            if status == b"201":
                self.book.n_created += 1
            else:
                self.book.errors.append(
                    f"creator: a create answered {status.decode()}")

    def _warm(self) -> None:
        """The bursts, one after the other."""
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
        start = self.first_pod - sum(self.warm)
        try:
            for n in self.warm:
                loadgen.post_list(conn, self.pods.list_body(start, start + n),
                                  n, self.book)
                start += n
                while self.book.n_pending > 0 and self.creating:
                    time.sleep(0.01)
        finally:
            conn.close()

    def steady(self) -> bool:
        return self.warmed and super().steady() and self.book.n_pending \
            <= self.rate * float(self.params.get("steady_pending_s", 0.5))

    def _create(self) -> None:
        self._carry = b""
        i = 0
        sock = None
        try:
            self._warm()
            self._extend(time.monotonic() + 0.05)
            self.warmed = True
            sock = socket.create_connection(("127.0.0.1", self.port))
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            tick = 0
            while self.creating:
                t_pass = time.monotonic()
                if len(self.due) - i < _BLOCK // 2:
                    self._extend()
                t_made = time.monotonic()
                j = bisect.bisect_right(self.due, t_made, i)
                t_sent = t_made
                if j > i:
                    sock.sendall(b"".join(self.requests[i:j]))
                    t_sent = time.monotonic()
                    self.sent.extend([t_sent] * (j - i))
                    self.requests[i:j] = [None] * (j - i)
                    i = j
                tick += 1
                if tick % 16 == 0:
                    try:
                        data = sock.recv(1 << 18, socket.MSG_DONTWAIT)
                        if not data:
                            raise OSError("connection closed")
                        self._acks(data)
                    except BlockingIOError:
                        pass
                time.sleep(TICK_S)
                t_close = self.t_close      # set after t_open, so read first
                if t_close is not None and self.t_open <= t_pass < t_close:
                    stall = self.stall_s
                    stall["extend"] = max(stall["extend"], t_made - t_pass)
                    stall["send"] = max(stall["send"], t_sent - t_made)
                    took = time.monotonic() - t_pass
                    stall["pass"] = max(stall["pass"], took)
                    self.pauses += took > PAUSE_S
            self.t_stopped = time.monotonic()
            sock.settimeout(5.0)
            while self.book.n_created < self.first_pod + i \
                    and not self.book.errors:
                data = sock.recv(1 << 16)
                if not data:
                    break
                self._acks(data)
        except (OSError, ValueError) as err:
            self.book.errors.append(f"creator: {err!r}")
        finally:
            if sock is not None:
                sock.close()
            self.book.cpu_s["bench-creator"] = time.thread_time()

    def _due_in_window(self) -> range:
        """Every create that was DUE in the window, written or not: one
        that the generator never got to write is offered and unbound."""
        lo = bisect.bisect_left(self.due, self.t_open)
        hi = bisect.bisect_left(self.due, self.t_close)
        return range(lo, hi)

    def n_offered(self) -> int:
        return self.first_pod + len(self.sent)

    def attempted_failed(self) -> tuple[int, int]:
        pods = self._due_in_window()
        return len(pods), sum(1 for p in pods
                              if self.first_pod + p not in self.book.bind_t)

    def report(self) -> dict:
        pods = self._due_in_window()
        if not len(pods):
            return {}
        # A pod with no bind (or never written) has waited until now and
        # is still waiting: its time counts the wait, so it sits above
        # every pod that was bound (and `correct` is false: never_bound).
        now = time.monotonic()
        end = self.t_stopped or now
        lat = np.array([(self.book.bind_t.get(self.first_pod + p, now)
                         - self.due[p]) * 1e3 for p in pods])
        late = np.array([((self.sent[p] if p < len(self.sent) else end)
                          - self.due[p]) * 1e3 for p in pods])
        return {"submit_to_bind_p50_ms": float(np.percentile(lat, 50)),
                "submit_to_bind_p95_ms": float(np.percentile(lat, 95)),
                "submit_to_bind_p99_ms": float(np.percentile(lat, 99)),
                "submit_to_bind_max_ms": float(lat.max()),
                "late_ms_p99": float(np.percentile(late, 99)),
                "late_ms_max": float(late.max()),
                "creator_send_max_ms": self.stall_s["send"] * 1e3,
                "creator_extend_max_ms": self.stall_s["extend"] * 1e3,
                "creator_pass_max_ms": self.stall_s["pass"] * 1e3,
                "creator_pauses": float(self.pauses),
                "latency_samples": len(pods)}
