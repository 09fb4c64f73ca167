"""The traffic kind's schedule and the resident-cap bookkeeping, from a
seed, against the real native apiserver and the plain reference
scheduler."""

import numpy as np

from conftest import drive, run_in_tree

SCHEDULE = """
import json, time
import cluster, run
kind = run.load_module("generators", "poisson_open")
def schedule(seed):
    g = kind.Generator.__new__(kind.Generator)
    g.params = {"rate_pods_s": 500.0}; g.seed = seed
    g.pods = cluster.Pods({"profile": "uniform", "milli_cpu": 100,
                           "memory": 1}, seed)
    g.threads = []; g.first_pod = 0; g.launch_buckets = [512, 256]
    import threading
    orig = threading.Thread.start
    threading.Thread.start = lambda self: None     # schedule only
    try:
        g._start_creators()
    finally:
        threading.Thread.start = orig
    g._extend(100.05)
    schedule.warm = g.warm
    return g.due, g.requests
a, ra = schedule(5); b, rb = schedule(5); c, _ = schedule(6)
import numpy as np
print(json.dumps({"same": a == b and ra == rb, "differ": a != c,
    "n": len(a), "span": a[-1] - 100.05,
    "gaps_equal": bool(np.allclose(np.sort(np.diff([100.05] + a)), np.sort(np.diff([100.05] + c)))),
    "first_request": ra[0].decode()[:60],
    "warm": schedule.warm}))
"""


def test_poisson_schedule_is_seeded_and_every_seed_has_the_same_gaps(tiny_tree):
    proc = run_in_tree(tiny_tree, SCHEDULE)
    assert proc.returncode == 0, proc.stderr[-2000:]
    import json
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["same"] and got["differ"] and got["gaps_equal"]
    assert got["n"] == 8192
    assert got["span"] == np.float64(8192 / 500.0).item() or \
        abs(got["span"] - 8192 / 500.0) < 1e-6
    assert got["first_request"].startswith("POST /api/v1/pods HTTP/1.1")
    # the ramp's bursts are the launch sizes the system reported, then one
    assert got["warm"] == [512, 256, 1]


def test_open_loop_times_every_pod_from_when_it_was_due(tiny_tree):
    res = drive(tiny_tree, "tiny-open", seed=4, seconds=3.0)
    assert res["correct"] is True, res["compared"]
    assert 800 <= res["attempted"] <= 1000          # 300/s for 3 s
    m = res["metrics"]
    assert 0 < m["submit_to_bind_p50_ms"]["value"] \
        <= m["submit_to_bind_p95_ms"]["value"] < 5000
    # the resident-cap bookkeeping: nothing over its allocatable, every
    # pod of the run accounted for
    for name in ("over_allocatable", "list_mismatch", "lost_pods",
                 "never_bound"):
        assert res["compared"][name]["value"] == 0


DUE = """
import json
import numpy as np
import run, loadgen
kind = run.load_module("generators", "poisson_open")
g = kind.Generator.__new__(kind.Generator)
g.first_pod = 0
g.due = [10.0 + 0.1 * i for i in range(100)]       # 10.0 .. 19.9
g.sent = [d + 0.001 for d in g.due[:60]]           # the last 40 never written
g.t_stopped = 21.0
g.stall_s = {"send": 0.25, "extend": 0.03, "pass": 0.26}
g.pauses = 2
g.t_open, g.t_close = 12.0, 18.0                   # pods 20 .. 79 are due
g.book = loadgen.Book()
g.book.bind_t = {p: g.due[p] + 0.05 for p in range(60)}
print(json.dumps({"attempted_failed": g.attempted_failed(),
                  "report": g.report()}))
"""


def test_a_due_pod_that_was_never_written_counts_as_offered_and_unbound(
        tiny_tree):
    import json
    proc = run_in_tree(tiny_tree, DUE)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["attempted_failed"] == [60, 20]
    rep = got["report"]
    assert rep["latency_samples"] == 60
    assert abs(rep["submit_to_bind_p50_ms"] - 50.0) < 1e-6   # 40 of 60 bound
    assert rep["submit_to_bind_p95_ms"] > 1000     # the unbound wait still
    assert rep["late_ms_p99"] > 1000               # and the unwritten are late
    assert rep["late_ms_max"] >= rep["late_ms_p99"]
    # where the generator itself stalled, for the run's info.json
    assert (rep["creator_send_max_ms"], rep["creator_extend_max_ms"],
            rep["creator_pass_max_ms"]) == (250.0, 30.0, 260.0)
    assert rep["creator_pauses"] == 2.0
