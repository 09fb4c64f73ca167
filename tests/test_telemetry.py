"""The device-telemetry & SLO plane (ISSUE 9): the time-series ring's
bounded memory and cadence, the SLO burn-rate window math (fake clock),
the recompile watchdog (mint an unwarmed shape -> counter + span), the
per-cause transfer accounting on the resident-cluster sync, and the
profiling hook's zero-overhead no-op path."""

from __future__ import annotations

import json
import time

import numpy as np

from kubernetes_tpu.api import types as api
from kubernetes_tpu.engine import devicestats
from kubernetes_tpu.utils import metrics as m
from kubernetes_tpu.utils import telemetry
from kubernetes_tpu.utils.metrics import exponential_buckets
from kubernetes_tpu.scheduler.slo import SLOMonitor

from tests.helpers import make_node, make_pod


# -- time-series ring --------------------------------------------------------

class TestTimeSeriesRing:
    def test_bounded_memory(self):
        ring = telemetry.TimeSeriesRing(
            capacity=10, period_s=0,
            collect=lambda: {"x": 1.0, "y": 2.0})
        for i in range(100):
            ring.scrape(now=float(i))
        payload = ring.payload()
        assert payload["samples"] == 10
        # Oldest samples fell off the ring; the newest survive.
        assert payload["series"]["x"][0][0] == 90.0
        assert payload["series"]["x"][-1][0] == 99.0
        assert len(ring._samples) == 10

    def test_cadence(self):
        ticks = []
        ring = telemetry.TimeSeriesRing(
            capacity=100, period_s=0.02,
            collect=lambda: ticks.append(1) or {"n": float(len(ticks))})
        ring.run()
        try:
            deadline = time.time() + 5.0
            while ring.scrapes < 3 and time.time() < deadline:
                time.sleep(0.01)
            assert ring.scrapes >= 3, "self-scrape cadence never fired"
        finally:
            ring.stop()

    def test_default_collector_flattens_registry_and_extras(self):
        ring = telemetry.TimeSeriesRing(capacity=4, period_s=0)
        extra = m.SchedulerMetrics()
        extra.queue_depth.set(7)
        ring.add_metrics(extra.all_metrics())
        ring.add_metrics(extra.all_metrics())  # identity-deduped
        assert len(ring._extra) == len(extra.all_metrics())
        sample = ring.scrape()["values"]
        assert sample["scheduler_pending_queue_depth"] == 7.0
        # Registry counters and histogram _count/_sum flatten too.
        assert "apiclient_retry_budget_exhausted_total" in sample
        assert "scheduler_e2e_decision_latency_microseconds_count" \
            in sample
        # Labeled children are their own series.
        m.DEVICE_TRANSFER_BYTES.labels(cause="scatter").inc(0)
        sample = ring.scrape()["values"]
        assert 'scheduler_device_transfer_bytes_total{cause="scatter"}' \
            in sample

    def test_payload_is_series_major_json(self):
        ring = telemetry.TimeSeriesRing(capacity=4, period_s=0,
                                        collect=lambda: {"x": 3.5})
        ring.scrape(now=1.0)
        ring.scrape(now=2.0)
        payload = json.loads(json.dumps(ring.payload()))
        assert payload["series"]["x"] == [[1.0, 3.5], [2.0, 3.5]]

    def test_empty_ring_serves_one_on_demand_sample(self):
        ring = telemetry.TimeSeriesRing(capacity=4, period_s=0,
                                        collect=lambda: {"x": 1.0})
        assert ring.payload()["samples"] == 1

    def test_dashboard_is_self_contained_html(self):
        html = telemetry.DASHBOARD_HTML
        assert "/debug/timeseries" in html
        assert "<script>" in html and "fetch(" in html
        # Zero-dependency: no external scripts, styles, or fonts.
        assert "http://" not in html and "https://" not in html
        for series in ("scheduler_slo_", "scheduler_device_hbm_",
                       "stage_latency"):
            assert series in html
        # kt-prof group: the CPU-attribution panel and its series.
        assert "Control-plane CPU" in html
        for series in ("cpu_fraction", "apiserver_serialize",
                       "watch_decode"):
            assert series in html


# -- SLO burn-rate window math ----------------------------------------------

def _slo(hist, clock_box):
    return SLOMonitor(histogram=hist, slo_ms=10.0, objective_pct=99.0,
                      clock=lambda: clock_box[0])


def _hist(name):
    # Buckets 1ms/10ms/100ms in us: the 10ms SLO lands exactly on a
    # bound, so good == observations <= 10ms with no bucket rounding.
    return m.Histogram(name, "t", [1e3, 1e4, 1e5])


class TestSLOBurnRate:
    def test_no_traffic_is_zero_burn(self):
        clock = [0.0]
        mon = _slo(_hist("slo_t0_us"), clock)
        burns = mon.tick()
        assert burns == {"5m": 0.0, "1h": 0.0}
        assert float(m.SLO_BUDGET_REMAINING.value) == 1.0

    def test_all_good_is_zero_burn(self):
        clock = [0.0]
        h = _hist("slo_t1_us")
        mon = _slo(h, clock)
        mon.tick()
        for _ in range(100):
            h.observe(5e3)            # 5ms, inside the 10ms SLO
        clock[0] = 60.0
        assert mon.tick() == {"5m": 0.0, "1h": 0.0}

    def test_burn_is_error_rate_over_budget(self):
        clock = [0.0]
        h = _hist("slo_t2_us")
        mon = _slo(h, clock)
        mon.tick()
        for _ in range(98):
            h.observe(5e3)
        for _ in range(2):
            h.observe(5e4)            # 50ms: over the SLO
        clock[0] = 60.0
        burns = mon.tick()
        # error rate 2% over a 1% budget = burn 2.0, in every window
        # that spans all the traffic.
        assert abs(burns["5m"] - 2.0) < 1e-9
        assert abs(burns["1h"] - 2.0) < 1e-9
        assert abs(float(m.SLO_BUDGET_REMAINING.value) - 0.0) < 1e-9

    def test_short_window_recovers_while_long_still_burns(self):
        clock = [0.0]
        h = _hist("slo_t3_us")
        mon = _slo(h, clock)
        mon.tick()
        for _ in range(50):
            h.observe(5e4)            # a bad burst at t=0..60
        clock[0] = 60.0
        mon.tick()
        # 10 minutes later: plenty of good traffic since the burst.
        for _ in range(5000):
            h.observe(5e3)
        clock[0] = 660.0
        burns = mon.tick()
        # The 5m window starts at t=360 > the burst: only good traffic.
        assert burns["5m"] == 0.0
        # The 1h window still sees the burst: 50 bad / 5050 total.
        expected = (50 / 5050) / 0.01
        assert abs(burns["1h"] - expected) < 1e-6

    def test_sample_ring_is_bounded_by_longest_window(self):
        clock = [0.0]
        h = _hist("slo_t4_us")
        mon = _slo(h, clock)
        for i in range(200):
            clock[0] = i * 60.0       # 200 minutes of ticks
            mon.tick()
        # Only ~1h of samples (+1 edge sample) may survive.
        assert len(mon._samples) <= 3600 / 60 + 2

    def test_report_shape(self):
        clock = [0.0]
        h = _hist("slo_t5_us")
        mon = _slo(h, clock)
        mon.tick()
        rep = mon.report()
        assert rep["sloMs"] == 10.0 and rep["objectivePct"] == 99.0
        assert set(rep["burnRate"]) == {"5m", "1h"}


# -- recompile watchdog ------------------------------------------------------

class TestRecompileWatchdog:
    def test_unwarmed_shape_fires_counter_and_span(self):
        """Mint a program the prewarm never traced while armed: the
        path-labeled counter bumps and a post_prewarm_compile span with
        the offending signature lands in the ring."""
        import jax
        import jax.numpy as jnp

        from kubernetes_tpu.utils import trace
        # A content-unique program (random constant baked into the HLO)
        # so neither the in-process jit cache nor the persistent
        # compilation cache can have seen it.
        c = float(np.random.default_rng().random()) + 1.5
        fresh = jax.jit(lambda x: x * c + x.sum())
        before_children = dict(
            m.POST_PREWARM_COMPILES.children()).get(("stream_test",))
        before = before_children.value if before_children else 0
        with devicestats.watchdog_window() as compiles:
            with devicestats.live_path("stream_test"):
                fresh(jnp.ones((17,))).block_until_ready()
            assert compiles() >= 1
        after = m.POST_PREWARM_COMPILES.labels(
            path="stream_test").value
        assert after - before >= 1
        spans = [s for s in trace.snapshot()
                 if s["name"] == "post_prewarm_compile"
                 and (s.get("attrs") or {}).get("path") == "stream_test"]
        assert spans, "watchdog fired no span"
        assert spans[-1]["attrs"]["signature"], "span lost the signature"

    def test_warm_shape_stays_silent(self):
        import jax
        import jax.numpy as jnp
        f = jax.jit(lambda x: x + 1)
        f(jnp.ones((9,))).block_until_ready()     # trace BEFORE arming
        with devicestats.watchdog_window() as compiles:
            f(jnp.ones((9,))).block_until_ready()
            assert compiles() == 0

    def test_disarmed_is_silent(self):
        import jax
        import jax.numpy as jnp
        devicestats.disarm()
        before = devicestats.post_prewarm_compiles()
        c = float(np.random.default_rng().random()) + 2.5
        jax.jit(lambda x: x * c)(jnp.ones((11,))).block_until_ready()
        assert devicestats.post_prewarm_compiles() == before


# -- per-cause transfer accounting -------------------------------------------

def _rig(n_nodes=64):
    from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
    eng = GenericScheduler()
    for i in range(n_nodes):
        eng.cache.add_node(make_node(f"ds-{i}", milli_cpu=4000))
    return eng


class TestTransferAccounting:
    def test_full_upload_then_scatter(self):
        eng = _rig()
        pods = [make_pod(f"dp-{i}", cpu="100m") for i in range(4)]
        before = devicestats.transfer_snapshot()
        placements = eng.schedule_batch(pods)
        mid = devicestats.transfer_snapshot()
        # First sync has no resident copy: a full upload, plus the
        # result readback.
        assert mid["full_upload"] > before["full_upload"]
        assert mid["readback"] > before["readback"]
        assert mid["scatter"] == before["scatter"]
        # Assume the placements (dirtying a handful of rows of 64) and
        # drain again: the delta moves as a scatter, NOT a full upload.
        eng.cache.assume_pods(
            [(p, d) for p, d in zip(pods, placements) if d],
            strict=False)
        more = [make_pod(f"dq-{i}", cpu="100m") for i in range(4)]
        eng.schedule_batch(more)
        after = devicestats.transfer_snapshot()
        assert after["scatter"] > mid["scatter"]
        assert after["full_upload"] == mid["full_upload"]
        # Steady-state bytes: the scatter moved a few rows, the upload
        # the whole cluster — per-event, scatter must be far smaller.
        scatter_bytes = after["scatter"] - mid["scatter"]
        full_bytes = mid["full_upload"] - before["full_upload"]
        assert 0 < scatter_bytes < full_bytes

    def test_hbm_gauges_live(self):
        import jax.numpy as jnp
        keep = jnp.ones((256, 256))   # hold a live device array
        live = devicestats.sample_hbm()
        assert live >= keep.nbytes
        assert float(m.DEVICE_HBM_LIVE_BYTES.value) >= keep.nbytes
        assert float(m.DEVICE_HBM_PEAK_BYTES.value) >= live
        del keep


# -- profiling hook (the windowed trace and its --profile-dir) ---------------

class TestProfilingHook:
    def test_noop_path_is_zero_overhead(self):
        """With KT_TRACE=0 a host event is the one shared no-op object:
        nothing is built per stage."""
        from kubernetes_tpu.utils import trace
        was = trace.enabled()
        trace.set_enabled(False)
        try:
            noop = trace.annotation("solve")
            t0 = time.perf_counter()
            for _ in range(100_000):
                with trace.annotation("solve") as a:
                    assert a is None
            elapsed = time.perf_counter() - t0
            assert trace.annotation("launch", pods=3) is noop
        finally:
            trace.set_enabled(was)
        assert elapsed < 1.0, f"no-op annotation cost {elapsed:.2f}s"

    def test_daemon_flag_names_the_trace_directory(self, tmp_path,
                                                   monkeypatch):
        """--profile-dir (default KT_PROFILE_DIR) is where the daemon's
        /debug/pprof/trace writes; a second session is refused whoever
        holds the first."""
        from kubernetes_tpu.scheduler import __main__ as daemon
        from kubernetes_tpu.utils import profiling
        opts = daemon.build_parser().parse_args(
            ["--profile-dir", str(tmp_path)])
        assert opts.profile_dir == str(tmp_path)
        monkeypatch.setenv("KT_PROFILE_DIR", str(tmp_path / "env"))
        assert daemon.build_parser().parse_args([]).profile_dir == \
            str(tmp_path / "env")
        assert profiling._session.acquire(blocking=False)
        try:
            status, body, _ctype = daemon._trace_route(
                opts.profile_dir, "seconds=0")
        finally:
            profiling._session.release()
        assert status == 409, body
        assert not list(tmp_path.rglob("*.xplane.pb"))
