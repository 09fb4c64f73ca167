"""kt-prof (ISSUE 18): the in-process sampling profiler's classifier,
render surfaces, and — because an always-on profiler that isn't cheap is
a regression, not a feature — its overhead budgets:

* KT_PROF=0 path: 100k no-op calls under a second (one branch each);
* the sampler's own CPU under 2 % of a busy window (self-measured via
  ``time.thread_time``, the same number exported as the
  ``kt-prof-sampler`` thread row);
* the per-frame wire accounting under 5 % of the pinned HTTPWatcher
  decode budget (test_http_wire pins 10k events < 1 s; the accounting
  adds two clock reads + two cached-child incs per CHUNK);
* the density smoke still runs with zero post-prewarm compiles with the
  sampler live, and stamps a profile section.
"""

from __future__ import annotations

import json
import threading
import time

from kubernetes_tpu.utils import profiler


# -- classifier --------------------------------------------------------------

def test_classify_frame_path_rules():
    cf = profiler.classify_frame
    assert cf("/r/kubernetes_tpu/engine/solver.py", "solve") == "solve_host"
    assert cf("/r/kubernetes_tpu/ops/scatter.py", "go") == "solve_host"
    assert cf("/r/kubernetes_tpu/features/nodeinfo.py", "build") == \
        "feature_build"
    assert cf("/r/kubernetes_tpu/client/reflector.py", "loop") == \
        "handler_dispatch"
    assert cf("/r/kubernetes_tpu/apiserver/memstore.py", "list") == \
        "apiserver"
    assert cf("/r/kubernetes_tpu/scheduler/binder.py", "bind") == \
        "commit_bind"
    assert cf("/r/kubernetes_tpu/cache/scheduler_cache.py", "add") == \
        "commit_bind"
    assert cf("/usr/lib/python3.11/json/encoder.py", "iterencode") == \
        "serialize"
    assert cf("/usr/lib/python3.11/json/decoder.py", "raw_decode") == \
        "watch_decode"


def test_classify_frame_function_gated_rules():
    cf = profiler.classify_frame
    # client/http.py hosts the watch pump AND the binder POST path: only
    # _pump classifies; everything else walks outward to its caller.
    assert cf("/r/kubernetes_tpu/client/http.py", "_pump") == "watch_decode"
    assert cf("/r/kubernetes_tpu/client/http.py", "request") is None
    # C-accelerated json.dumps leaves no Python frame: the _send_*
    # CALLER is where serialize time lands.
    assert cf("/r/kubernetes_tpu/apiserver/server.py", "_send_json") == \
        "serialize"
    assert cf("/usr/lib/python3.11/json/__init__.py", "dumps") == \
        "serialize"
    # loads stays unmatched so decode attributes to its caller.
    assert cf("/usr/lib/python3.11/json/__init__.py", "loads") is None
    assert cf("/home/x/app.py", "main") is None
    # The drain pipeline splits by function: solve pump vs commit chunk.
    pl = "/r/kubernetes_tpu/scheduler/pipeline.py"
    assert cf(pl, "_solve_stream") == "solve_host"
    assert cf(pl, "_commit_chunk") == "commit_bind"
    assert cf(pl, "drain") is None
    # scheduler.py's batch assume/bind path classifies; the drain loop
    # around it stays unmatched (walks outward / lands in other).
    sc = "/r/kubernetes_tpu/scheduler/scheduler.py"
    assert cf(sc, "_bind_assumed_batch_inner") == "commit_bind"
    assert cf(sc, "_assume_and_bind_batch") == "commit_bind"
    assert cf(sc, "run") is None
    # Commit-time side channels: events + the decision flight recorder.
    assert cf("/r/kubernetes_tpu/scheduler/events.py", "eventf_many") == \
        "commit_bind"
    assert cf("/r/kubernetes_tpu/scheduler/flightrecorder.py",
              "record_batch") == "commit_bind"


def test_classify_stack_walks_outward_and_defaults_to_other():
    """classify_stack walks innermost -> outward and takes the first
    classified frame; a stack with none at any depth is other."""
    import sys

    def leaf():
        return profiler.classify_stack(
            sys._current_frames()[threading.get_ident()])

    assert leaf() == "other"   # test file frames: no rule matches
    assert profiler.classify_stack(None) == "other"


def test_thread_label_suffix_collapses_and_caps():
    p = profiler.Profiler()
    p._note_thread_locked("bind-worker-17", 0.5)
    p._note_thread_locked("bind-worker-3", 0.25)
    assert p._thread_cpu == {"bind-worker": 0.75}
    for i in range(profiler._MAX_THREAD_LABELS + 10):
        p._note_thread_locked(f"role{i}x", 0.01)
    assert len(p._thread_cpu) <= profiler._MAX_THREAD_LABELS + 1
    assert "other" in p._thread_cpu


def test_stack_ring_bounds_and_truncation_bucket():
    p = profiler.Profiler()
    p.ring = 16
    for i in range(40):
        p._note_stack_locked(f"a.py:f{i}", 0.001)
    assert len(p._stacks) <= 16
    assert p._stacks_truncated > 0
    assert "(ring-truncated)" in p.collapsed()


# -- sampling + render surfaces ----------------------------------------------

def _burn(stop: threading.Event) -> None:
    x = 0
    while not stop.is_set():
        x += 1


def test_sampler_attributes_busy_thread_cpu_and_renders():
    stop = threading.Event()
    t = threading.Thread(target=_burn, args=(stop,), name="burner-7",
                         daemon=True)
    t.start()
    p = profiler.Profiler()
    try:
        p.sample_once()
        deadline = time.time() + 5.0
        while time.time() < deadline:
            time.sleep(0.05)
            p.sample_once()
            if sum(p.snapshot()["cpu_seconds"].values()) > 0.05:
                break
    finally:
        stop.set()
        t.join()
    snap = p.snapshot()
    assert snap["samples"] >= 2
    # The burner's CPU landed, under the suffix-stripped label.
    assert snap["threads"].get("burner", 0) > 0
    assert sum(snap["cpu_seconds"].values()) > 0
    # A busy loop in this test file classifies to other — and the
    # unclassified fraction says so.
    assert snap["unclassified_fraction"] > 0
    # Collapsed: "stack weight_us" lines, weights integer microseconds.
    lines = [ln for ln in p.collapsed().strip().splitlines() if ln]
    assert lines and all(ln.rsplit(" ", 1)[1].isdigit() for ln in lines)
    # Speedscope: schema + sampled profile with aligned samples/weights.
    doc = p.speedscope()
    assert doc["$schema"].startswith("https://www.speedscope.app/")
    prof = doc["profiles"][0]
    assert prof["type"] == "sampled"
    assert len(prof["samples"]) == len(prof["weights"])
    assert all(isinstance(s, list) and s for s in prof["samples"])
    nframes = len(doc["shared"]["frames"])
    assert all(i < nframes for s in prof["samples"] for i in s)
    # The document round-trips as JSON (what /debug/profile serves).
    json.loads(json.dumps(doc))


def test_render_formats_and_disabled_path(monkeypatch):
    body, ctype = profiler.render()
    assert ctype == "application/json"
    doc = json.loads(body)
    assert doc["profiles"][0]["unit"] == "seconds"
    # Raw query-string form (debugmux) and parse_qs form (apiserver).
    body, ctype = profiler.render("format=collapsed")
    assert ctype == "text/plain"
    body2, ctype2 = profiler.render({"format": ["collapsed"]})
    assert ctype2 == "text/plain"
    # Disabled: render answers None and muxes map that to 404.
    monkeypatch.setattr(profiler, "_ENABLED", False)
    assert profiler.render() is None
    assert profiler.ensure_started() is None


# -- overhead budgets --------------------------------------------------------

def test_disabled_path_is_one_branch(monkeypatch):
    """KT_PROF=0: 100k calls to the two public entrypoints hot sites use
    must cost well under a second TOTAL — the off path is a flag read."""
    monkeypatch.setattr(profiler, "_ENABLED", False)
    t0 = time.perf_counter()
    for _ in range(100_000):
        profiler.enabled()
        profiler.ensure_started()
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.0, f"off-path 200k calls took {elapsed:.3f}s"


def test_sampler_self_cost_under_2_percent_of_busy_window(monkeypatch):
    """The GWP claim, held by the loop's own pacing beside a busy
    thread: ticks made to cost 5 ms of the sampler thread's CPU (at a
    fixed 19 Hz, ~10 % of a core) still leave the sampler under 2 % of
    the wall clock it ran for.  Costs are CPU seconds of the sampler's
    thread and every sleep is at least the one the loop asked for, so
    the bound holds whatever else the machine runs; the tick that the
    stop cuts off from its sleep is the only one left out."""
    p = profiler.Profiler()
    tick_cpu = 0.005
    costs: list[float] = []
    sample = p.sample_once

    def costly_tick() -> None:
        c0 = time.thread_time()
        sample()
        while time.thread_time() - c0 < tick_cpu:
            pass
        costs.append(time.thread_time() - c0)

    monkeypatch.setattr(p, "sample_once", costly_tick)
    stop = threading.Event()
    t = threading.Thread(target=_burn, args=(stop,), name="busy",
                         daemon=True)
    t.start()
    t0 = time.monotonic()
    try:
        p.start()
        while len(costs) < 4 and time.monotonic() - t0 < 120.0:
            time.sleep(0.05)
    finally:
        p.stop()
        stop.set()
        t.join()
    elapsed = time.monotonic() - t0
    assert len(costs) >= 4, f"{len(costs)} ticks in {elapsed:.1f}s"
    self_cpu = p.snapshot()["sampler_self_cpu_s"]
    assert self_cpu >= sum(costs) - 1e-6  # snapshot rounds to 1 us
    paced = self_cpu - costs[-1]
    assert paced <= profiler._SELF_BUDGET * elapsed, \
        f"sampler burned {paced:.4f}s of a {elapsed:.2f}s window " \
        f"({paced / elapsed:.1%}, budget 2%)"


def test_sampler_paces_itself_to_budget():
    """KT_PROF_HZ is a ceiling: a tick that cost C seconds of sampler
    CPU must be followed by a sleep of at least C / 2% — thread-heavy
    phases (a kubemark fleet is ~1,000 threads; a tick there costs
    ~17 ms) would otherwise pay ~30% of a 1-core rig to the profiler."""
    p = profiler.Profiler()
    assert p._next_delay(0.0) == 1.0 / p.hz
    # a 17 ms tick -> at least 0.85 s of sleep (2% duty cycle)
    assert p._next_delay(0.017) >= 0.017 / profiler._SELF_BUDGET
    assert p._next_delay(999.0) == profiler._MAX_INTERVAL


def test_proc_reads_capped_by_thread_count(monkeypatch):
    """Above _PROC_THREAD_CAP live threads the per-thread /proc stat
    reads (the O(threads) tick cost) shut off and the tick degrades to
    the process-wide fallback split — 500 hollow kubelets must not pay
    1,000 stat reads per tick."""
    p = profiler.Profiler()
    calls = []
    monkeypatch.setattr(p._proc, "cpu_seconds",
                        lambda nid: calls.append(nid) or 0.0)
    monkeypatch.setattr(profiler, "_PROC_THREAD_CAP", 0)
    p.sample_once()
    assert calls == []
    assert p.snapshot()["samples"] == 1
    # Under the cap the per-thread path is back in force.
    monkeypatch.setattr(profiler, "_PROC_THREAD_CAP", 10_000)
    if p._proc.available:
        p.sample_once()
        assert calls


def test_wire_accounting_under_5_percent_of_decode_budget():
    """test_http_wire pins the watch pump at 10k events < 1 s.  The
    kt-prof accounting adds, per CHUNK, two perf_counter_ns reads and
    two cached-child incs — 10k iterations of that (one chunk per event,
    a strict upper bound on the real per-chunk flushing) must cost
    < 5 % of the pinned budget."""
    from kubernetes_tpu.utils.metrics import (WATCH_DECODE_EVENTS,
                                              WATCH_DECODE_SECONDS)
    m_s = WATCH_DECODE_SECONDS.labels(kind="overhead-test")
    m_n = WATCH_DECODE_EVENTS.labels(kind="overhead-test")
    perf_ns = time.perf_counter_ns
    # This thread's CPU, not the wall: the budget is what the accounting
    # costs, and a worker descheduled mid-loop has spent nothing on it.
    t0 = time.thread_time()
    for _ in range(10_000):
        t_chunk = perf_ns()
        m_s.inc((perf_ns() - t_chunk) / 1e9)
        m_n.inc(1)
    elapsed = time.thread_time() - t0
    assert elapsed < 0.05, \
        f"10k accounting flushes took {elapsed:.4f}s (budget 50ms = 5% " \
        f"of the pinned 1s decode budget)"


def test_density_smoke_profiles_without_recompiles():
    """The sampler live during a density run: still zero post-prewarm
    compiles (the profiler adds no device work), and the run stamps an
    enabled profile section with a component split."""
    from kubernetes_tpu.perf.harness import density
    r = density(20, 100, quiet=True)
    assert r.device["post_prewarm_compiles"] == 0
    assert r.profile is not None
    assert r.profile["enabled"] is True
    assert r.profile["samples"] >= 1
    assert set(r.profile.get("cpu_fraction", {})) <= \
        set(profiler.COMPONENTS)
