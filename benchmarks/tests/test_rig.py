"""``rig.Daemon.wait_prewarmed`` leaves ``/debug/vars`` alone while the
daemon lists the cluster: that page's ``cachedNodes`` builds the cache's
node tensors from a partial list and sends the rest of the list down the
row-by-row path (PERF.md section 6, PR 29)."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import rig

LISTED = ('scheduler_handler_events_total{{handler="nodes"}} {nodes}\n'
          'scheduler_handler_events_total{{handler="pods"}} {pods}\n')


class _Alive:
    name = "daemon"
    log_path = "/nonexistent"

    def require_alive(self):
        pass


def _fake_daemon(pages: list, resident: bool):
    """A ``rig.Daemon`` over a fake status server that answers
    ``/metrics`` with ``pages`` in turn (the last one for ever) and logs
    every path asked."""
    asked = []

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            asked.append(self.path)
            if self.path == "/metrics":
                n = sum(p == "/metrics" for p in asked)
                body = pages[min(n, len(pages)) - 1].encode()
            else:
                body = json.dumps({"prewarmCacheStats": {"256": {}}}).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    daemon = rig.Daemon.__new__(rig.Daemon)
    daemon.port = server.server_address[1]
    daemon.child = _Alive()
    daemon.started = time.monotonic()
    daemon.lists_resident = resident
    return daemon, asked, server


@pytest.mark.parametrize("resident, pages, metrics_asks", [
    # nodes in, resident pods still coming: the pods' count is waited for
    (True, ["", LISTED.format(nodes=200, pods=0),
            LISTED.format(nodes=200, pods=800)], 3),
    # a configuration with no resident pods waits for the nodes alone
    (False, ["", LISTED.format(nodes=200, pods=0)], 2),
])
def test_vars_is_not_asked_before_the_first_lists_are_in(
        resident, pages, metrics_asks):
    daemon, asked, server = _fake_daemon(pages, resident)
    try:
        daemon.wait_prewarmed(30)
    finally:
        server.shutdown()
    first_vars = asked.index("/debug/vars")
    assert asked[:first_vars] == ["/metrics"] * metrics_asks
    assert "/metrics" not in asked[first_vars:]


def test_a_daemon_that_never_counts_its_lists_fails_the_run(monkeypatch):
    daemon, asked, server = _fake_daemon([""], True)
    monkeypatch.setattr(rig.time, "sleep", lambda s: None)
    clock = iter(range(0, 100000, 50))
    monkeypatch.setattr(rig.time, "monotonic", lambda: next(clock))
    try:
        with pytest.raises(rig.RunFailure, match="handler_events_total"):
            daemon.wait_prewarmed(1100)
    finally:
        server.shutdown()
    assert "/debug/vars" not in asked
