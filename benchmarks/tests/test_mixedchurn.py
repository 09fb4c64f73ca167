"""``generators/poisson_open_churn.py``, ``traffic/poisson-open-churn.json``
and ``configs/mixedchurn-5000n.json`` (the cell ``mixedchurn5k-arrivals``):
the churn's tick schedule and clean-up against a stub apiserver, what the
configuration shares with ``schedperf-5000n``, the three ``fleet.*``
metrics, and one tiny run of the cell's own files through the whole
served path on the CPU."""

import http.server
import json
import os
import threading
import time

import pytest

import loadgen
import run
from conftest import run_in_tree

CELL = "mixedchurn5k-arrivals"
NEW_METRICS = {"fleet.node_events_in_window": "counter_delta",
               "fleet.rebuilds_in_window": "counter_delta",
               "fleet.node_event_ms_mean": "ratio"}


def _config():
    return run.load_json(os.path.join(run.HERE, "configs",
                                      "mixedchurn-5000n.json"))


# -- the kind against a stub apiserver ---------------------------------------

class _Stub(http.server.ThreadingHTTPServer):
    """Records every request; answers 201 / 200 as the apiserver does,
    but 500 for a create whose body names ``refuse``."""

    def __init__(self, refuse: str = ""):
        self.seen: list = []          # (method, path, name or None)
        self.refuse = refuse
        super().__init__(("127.0.0.1", 0), _Handler)
        threading.Thread(target=self.serve_forever, daemon=True).start()


class _Handler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def _answer(self, code: int) -> None:
        body = b'{"status":"x"}'
        self.send_response(code)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):
        obj = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        name = obj["metadata"]["name"]
        self.server.seen.append(("POST", self.path, name))
        refused = self.server.refuse and name.startswith(self.server.refuse)
        self._answer(500 if refused else 201)

    def do_GET(self):             # the churn asks whether its pod is bound
        self.server.seen.append(("GET", self.path, None))
        body = b'{"spec":{"nodeName":"node-1"}}'
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_DELETE(self):
        self.server.seen.append(("DELETE", self.path, None))
        self._answer(200)

    def log_message(self, *args):
        pass


def _generator(port: int, seed: int = 3, interval_ms: int = 20):
    """The kind's churn thread alone, as ``_start_creators`` sets it up
    (the parent's creator is not started: no pod is sent)."""
    kind = run.load_module("generators", "poisson_open_churn")
    g = kind.Generator.__new__(kind.Generator)
    g.port, g.seed, g.config = port, seed, _config()
    g.params = dict(run.load_json(os.path.join(
        run.HERE, "traffic", "poisson-open-churn.json")))
    g.params["churn"] = dict(g.params["churn"], interval_ms=interval_ms)
    g.book = loadgen.Book()
    g.creating, g.warmed = True, True
    g.t_start = g.t_open = g.t_close = None
    g.threads, g.launch_buckets, g.first_pod = [], [], 0
    g.pods = None
    start = threading.Thread.start
    threading.Thread.start = lambda self: None      # the set-up alone
    try:
        g._start_creators()
    finally:
        threading.Thread.start = start
    return g


def _run_churn(g, ticks_wanted: int, stub: _Stub) -> None:
    g.open_window(time.monotonic(), time.monotonic() + 3600)
    t = threading.Thread(target=g._churn, daemon=True)
    t.start()
    deadline = time.monotonic() + 20
    while g.churn_stats["churn_ticks"] < ticks_wanted \
            and time.monotonic() < deadline:
        time.sleep(0.005)
    g.creating = False
    t.join(timeout=20)
    assert not t.is_alive()
    stub.shutdown()


def test_creates_and_deletes_alternate_and_nothing_is_left_at_the_end():
    stub = _Stub()
    g = _generator(stub.server_address[1])
    _run_churn(g, 5, stub)          # an odd count: the last tick created
    assert g.book.errors == []
    # a pod is deleted once the apiserver shows it bound: one GET of it
    # ahead of its DELETE
    for i, (method, path, _n) in enumerate(stub.seen):
        if method == "DELETE" and "/pods/" in path:
            assert stub.seen[i - 1] == ("GET", path, None)
    stub.seen = [s for s in stub.seen if s[0] != "GET"]
    posts = [s for s in stub.seen if s[0] == "POST"]
    deletes = [s for s in stub.seen if s[0] == "DELETE"]
    # every tick is three requests of one method, create ticks first
    assert len(stub.seen) % 3 == 0
    for i in range(0, len(stub.seen), 3):
        methods = {m for m, _p, _n in stub.seen[i:i + 3]}
        assert methods == ({"POST"} if (i // 3) % 2 == 0 else {"DELETE"})
    assert [p for _m, p, _n in posts[:3]] == [
        "/api/v1/nodes", "/api/v1/pods", "/api/v1/services"]
    assert [n for _m, _p, n in posts[:6]] == [
        "node-churn-0", "pod-churn-0", "service-churn-0",
        "node-churn-1", "pod-churn-1", "service-churn-1"]
    # what was made is deleted, the newest first, at its own path
    assert [p for _m, p, _n in deletes[:3]] == [
        "/api/v1/namespaces/default/services/service-churn-0",
        "/api/v1/namespaces/default/pods/pod-churn-0",
        "/api/v1/nodes/node-churn-0"]
    assert len(posts) == len(deletes) and not g.churn_held
    made = {(p.rsplit("/", 1)[-1], n) for _m, p, n in posts}
    gone = {(p.split("/")[-2], p.rsplit("/", 1)[-1]) for _m, p, _n in deletes}
    assert made == gone
    rep = g.churn_stats
    assert rep["churn_ticks"] >= 5
    assert rep["churn_node_creates"] - rep["churn_node_deletes"] in (0, 1)
    assert 0 <= rep["churn_late_ms_max"] < 5000


def test_the_first_tick_is_seeded_and_nothing_else_is():
    a, b, c = (_generator(1, seed=s) for s in (5, 5, 2147483659))
    assert a.churn_offset_s == b.churn_offset_s != c.churn_offset_s
    for g in (a, c):
        assert 0 <= g.churn_offset_s < g.churn_interval_s == 0.02
        assert (g.churn_number, g.churn_objects) == (
            1, ["node", "pod", "service"])


def test_a_refused_create_lands_among_the_clients_errors():
    stub = _Stub(refuse="node-churn")
    g = _generator(stub.server_address[1])
    _run_churn(g, 2, stub)
    assert g.book.errors and all("answered 500, not 201" in e
                                 for e in g.book.errors)
    assert "POST /api/v1/nodes" in g.book.errors[0]


def test_a_program_without_a_node_capacity_ends_the_run_cleanly(tmp_path):
    """The kind runs only a program whose daemon states ``nodeCapacity``
    on ``/debug/vars``; on any other tree the run ends with exit code 1
    before anything is started (the driver then measures the cell on the
    change alone)."""
    import rig
    kind = run.load_module("generators", "poisson_open_churn")
    kind.require_node_capacity(rig.REPO)            # this tree has one
    main = tmp_path / "kubernetes_tpu" / "scheduler" / "__main__.py"
    main.parent.mkdir(parents=True)
    main.write_text('page = {"cachedNodes": 0, "nodeEpoch": 0}\n')
    with pytest.raises(rig.RunFailure, match="no node-axis capacity"):
        kind.require_node_capacity(str(tmp_path))
    # ... and it is what loading the kind does: run.main answers 1
    repo = rig.REPO
    rig.REPO = str(tmp_path)
    try:
        with pytest.raises(rig.RunFailure):
            run.load_module("generators", "poisson_open_churn")
        (tmp_path / "BENCHMARK.json").write_text(
            open(os.path.join(repo, "BENCHMARK.json")).read())
        (tmp_path / "benchmarks").symlink_to(run.HERE)
        t = time.monotonic()
        assert run.main(["--workload", CELL, "--seed", "1",
                         "--seconds", "1"]) == 1
        assert time.monotonic() - t < 5
    finally:
        rig.REPO = repo


def test_the_churn_starts_with_the_arrivals_and_counts_the_window_alone():
    """Upstream's span: ticks from the end of the warm-up bursts, through
    the ramp, the window and what follows it (the traced span), and
    counts the ticks that were due inside the window alone."""
    stub = _Stub()
    # (an interval a create tick fits in: a POST to THIS stub takes ~40 ms;
    # the native apiserver answers a whole tick in under 12 ms on the chip)
    g = _generator(stub.server_address[1], interval_ms=200)
    g.warmed = False
    t = threading.Thread(target=g._churn, daemon=True)
    t.start()
    time.sleep(0.1)                  # the bursts are not through: no tick
    assert stub.seen == []
    g.warmed = True                  # the first Poisson arrival
    deadline = time.monotonic() + 20
    while len([s for s in stub.seen if s[0] == "DELETE"]) < 3 \
            and time.monotonic() < deadline:
        time.sleep(0.005)
    # a create tick and a delete tick in the ramp, none of them counted
    assert len([s for s in stub.seen if s[0] == "DELETE"]) == 3
    assert g.churn_stats["churn_ticks"] == 0
    t_open = time.monotonic()
    g.open_window(t_open, t_open + 1.0)      # five intervals long
    while time.monotonic() < t_open + 1.3:
        time.sleep(0.01)
    g.creating = False
    t.join(timeout=20)
    stub.shutdown()
    assert g.book.errors == [] and not g.churn_held
    assert 4 <= g.churn_stats["churn_ticks"] <= 5


# -- the configuration, the mix, the entries ---------------------------------

def test_the_configuration_is_schedperf_5000n_with_the_churn_templates():
    config = _config()
    base = run.load_json(os.path.join(run.HERE, "configs",
                                      "schedperf-5000n.json"))
    for key in ("nodes", "pods", "resident_cap", "daemon", "judge",
                "limits"):
        assert config[key] == base[key], key
    assert "shapes" not in config and "reference" not in config
    assert config["reduced"] == []
    assert config["guarantees"][:len(base["guarantees"])] == \
        base["guarantees"]
    assert len(config["guarantees"]) == len(base["guarantees"]) + 2
    for word in ("SchedulingWithMixedChurn", "5000Nodes", "churn/"):
        assert word in config["source"]
    for name in ("churn/node-default.yaml", "churn/pod-default.yaml",
                 "churn/service-default.yaml"):
        assert any(name in a for a in config["assumed"]), name
    tpl = config["churn_templates"]
    assert set(tpl) == {"node", "pod", "service"}
    assert tpl["node"]["status"]["allocatable"] == {"pods": "0"}
    assert tpl["node"]["status"]["conditions"] == [
        {"type": "Ready", "status": "True"}]
    assert "resources" not in tpl["pod"]["spec"]["containers"][0]
    assert tpl["pod"]["metadata"]["labels"] == {}
    assert tpl["service"]["spec"]["selector"] == {"app": "foo"}
    assert tpl["service"]["spec"]["ports"][0]["port"] == 8080
    # no name of the churn can be taken for one of the traffic's
    for obj in tpl.values():
        assert loadgen._EVENT.search(
            b'{"type":"ADDED","object":' + json.dumps(
                obj, separators=(",", ":")).encode()
            + b',"nodeName":"node-1"}}}\n') is None
    import rig
    assert rig._index("node-churn-7", "node-") == -2


def test_the_mix_is_the_other_cells_arrivals_with_upstreams_churn():
    mix = run.load_json(os.path.join(run.HERE, "traffic",
                                     "poisson-open-churn.json"))
    base = run.load_json(os.path.join(run.HERE, "traffic",
                                      "poisson-open.json"))
    assert mix["kind"] == "poisson_open_churn"
    for key in ("rate_pods_s", "steady_pending_s"):
        assert mix[key] == base[key]
    assert mix["churn"] == {"mode": "recreate", "number": 1,
                            "interval_ms": 1000,
                            "objects": ["node", "pod", "service"]}


def test_new_metrics_are_entries_files_and_existing_readers_by_name():
    import rig
    bench = run.load_json(os.path.join(rig.REPO, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, arithmetic in NEW_METRICS.items():
        entry = entries[name]
        spec = run.load_json(os.path.join(run.HERE, "metrics",
                                          name + ".json"))
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], key
        assert spec["arithmetic"] == arithmetic
        assert os.path.exists(os.path.join(run.HERE, "readers",
                                           arithmetic + ".py"))
        assert CELL in entry["workloads"]
        assert entry["moves"] == "submit_to_bind_p95_ms"
        assert entry["source"] == "program_counter"
    # the cell reports what schedperf5k-arrivals reports, but (until a
    # benchmark PR repairs them) the lists that tests pin with ==
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mixedchurn-5000n", "poisson-open-churn", 1)
    pinned = [m["name"] for m in bench["per_layer"]
              if "schedperf5k-arrivals" in m.get("workloads", ())
              and CELL not in m["workloads"]]
    assert len(pinned) <= 15 + 2
    joined = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert len(joined) >= 2 + 25 + 3
    for name in ("features.plan_hit_share", "full_upload.bytes_per_pod",
                 "scan_roofline", "scan.device_us_per_pod",
                 "device.busy_pct"):
        assert name in joined
    assert len(run.Cell(bench, CELL).per_layer()) >= 25 + 3 + 4


def _read_metric(name: str, before: str, after: str):
    import rig
    spec = run.load_json(os.path.join(run.HERE, "metrics", name + ".json"))
    ctx = {"daemon": (rig.parse_metrics(before), rig.parse_metrics(after)),
           "apiserver": ({}, {}), "runner": {}, "pods_bound": 1000,
           "trace": None, "config": {}}
    return run.load_module("readers", spec["arithmetic"]).read(
        spec["args"], ctx)


def test_new_metrics_read_the_counters_and_nothing_from_a_page_without():
    page = """scheduler_cache_node_events_total{{event="added",path="row"}} {added}
scheduler_cache_node_events_total{{event="removed",path="row"}} {removed}
scheduler_cache_node_events_total{{event="removed",path="rebuild"}} {hard}
scheduler_cache_node_event_seconds_total{{event="added"}} {added_s}
scheduler_cache_node_event_seconds_total{{event="removed"}} {removed_s}
scheduler_cache_rebuilds_total {rebuilds}
scheduler_cache_rebuild_seconds_total {rebuild_s}
"""
    before = page.format(added=5000, removed=3, hard=0, added_s=2.0,
                         removed_s=0.001, rebuilds=1, rebuild_s=1.5)
    after = page.format(added=5010, removed=12, hard=1, added_s=2.004,
                        removed_s=0.003, rebuilds=2, rebuild_s=1.514)
    assert _read_metric("fleet.node_events_in_window", before, after) == 20
    assert _read_metric("fleet.rebuilds_in_window", before, after) == 1
    assert abs(_read_metric("fleet.node_event_ms_mean", before, after)
               - 1.0) < 1e-9          # (4 + 2 + 14) ms over 20 events
    # the parent's program has none of the families: nothing, not 0
    old = "scheduler_post_prewarm_compiles_total 0\n"
    for name in NEW_METRICS:
        assert _read_metric(name, old, old) is None, name


# -- one tiny run of the cell's own files ------------------------------------

def _add_tiny_churn(tree: str) -> None:
    """The configuration's own file at 200 nodes and 600 resident pods,
    a ladder of 256 and a slow mix with a tick every 250 ms."""
    bench_dir = os.path.join(tree, "benchmarks")
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    if any(c["name"] == "tiny-churn" for c in bench["configs"]):
        return
    config = _config()
    config.update(name="tiny-churn", resident_cap=600,
                  judge={"sample": 200, "lag_step": 20, "max_lag_s": 2.0},
                  limits={"gap_mean": 0.5, "gap_max": 6.0},
                  nodes=dict(config["nodes"], count=200))
    config["daemon"]["env"]["KT_STREAM_CHUNK"] = "256"
    mix = run.load_json(os.path.join(run.HERE, "traffic",
                                     "poisson-open-churn.json"))
    mix.update(rate_pods_s=200, steady_pending_s=0.5,
               churn=dict(mix["churn"], interval_ms=250))
    for rel, body in (("configs/tiny-churn.json", config),
                      ("traffic/tiny-churn-open.json", mix)):
        with open(os.path.join(bench_dir, rel), "w") as f:
            json.dump(body, f)
    bench["configs"].append({
        "name": "tiny-churn", "source": "tests", "reduced": ["count"],
        "file": "benchmarks/configs/tiny-churn.json", "why": "tests"})
    bench["workloads"].append({
        "name": "tiny-churn-open", "config": "tiny-churn",
        "traffic": "tiny-churn-open", "chips": 1, "why": "tests"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-churn-open")
    with open(path, "w") as f:
        json.dump(bench, f)


# conftest.DRIVE against the real daemon on the CPU, keeping the
# /metrics pages the runner read at window open and close (the last
# three reads of a run are: open, close, the account at close)
DRIVE = """
import json
import run, rig
pages = []
read = rig.Daemon.metrics
def keeping(self):
    page = read(self)
    pages.append(page)
    return page
rig.Daemon.metrics = keeping
cell = run.Cell(run.load_json(rig.REPO + "/BENCHMARK.json"), "tiny-churn-open")
run.RAMP_TIMEOUT_S = 60.0
run.DRAIN_TIMEOUT_S = 10.0
res = run.run_cell(cell, {seed}, {seconds}, False, platform="cpu")
ctx = {{"daemon": (pages[-3], pages[-2]), "apiserver": ({{}}, {{}}),
       "runner": {{}}, "pods_bound": 1, "trace": None, "config": cell.config}}
for m, spec in cell.per_layer():
    if m["name"].startswith("fleet.") or m["name"] in (
            "features.plan_hit_share", "compiles.in_window"):
        reader = run.load_module("readers", spec["arithmetic"])
        res["metrics"][m["name"]] = reader.read(spec["args"], ctx)
res["info"] = run.load_json(run.out_dir_of(cell.name, {seed}, False)
                            + "/info.json")
print(json.dumps(res))
"""


def test_tiny_run_of_the_cell_on_the_cpu_is_correct_and_counts_the_churn(
        tiny_tree):
    _add_tiny_churn(tiny_tree)
    proc = run_in_tree(tiny_tree, DRIVE.format(seed=2147483653, seconds=4.0))
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["compared"]
    compared = res["compared"]
    for name in ("unknown_node_binds", "list_mismatch", "client_errors",
                 "never_bound", "invariant_violations"):
        assert compared[name]["value"] == 0, name
    seen = res["info"]["seen"]
    assert seen["churn_ticks"] >= 14          # 4 s of a tick every 250 ms
    assert seen["churn_node_creates"] >= 7
    assert abs(seen["churn_node_creates"] - seen["churn_node_deletes"]) <= 1
    m = res["metrics"]
    # the program counted the node events the churn sent, and none of
    # them rebuilt the node tensors or compiled
    assert m["fleet.node_events_in_window"] >= 12
    assert m["fleet.rebuilds_in_window"] == 0
    assert 0 < m["fleet.node_event_ms_mean"] < 50
    assert m["compiles.in_window"] == 0
    assert 0 < m["features.plan_hit_share"] < 1
