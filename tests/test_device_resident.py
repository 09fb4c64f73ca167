"""Device-resident cluster state, the persistent compile cache contract,
the pre-warm bucket ladder, and the overlapped solve/bind pipeline
(ISSUE 5 tentpole).

The invariants pinned here are the "device-residency protocol" from
ARCHITECTURE.md: the resident mirror equals a fresh full snapshot after
every sync; per-drain updates are row scatters, not full transfers; full
re-uploads happen exactly on relist / node-set change / column-capacity
growth; and a daemon's bucket ladder is fixed at startup."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.engine import solver as sv
from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
from kubernetes_tpu.scheduler.binder import InMemoryBinder
from kubernetes_tpu.scheduler.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.utils import metrics

from tests.helpers import make_node, make_pod


def _rig(n_nodes: int = 40, **daemon_kw):
    algo = GenericScheduler()
    for i in range(n_nodes):
        algo.cache.add_node(make_node(f"rn{i}", milli_cpu=4000))
    daemon = Scheduler(SchedulerConfig(algorithm=algo,
                                       binder=InMemoryBinder(),
                                       async_bind=False))
    for k, v in daemon_kw.items():
        setattr(daemon, k, v)
    return daemon


def _assert_resident_matches_fresh(algo: GenericScheduler) -> None:
    """After a sync, the mirror must be bit-identical to a freshly
    assembled full snapshot of the current host arrays (the narrow wire
    form widens losslessly — comparing through widen_cluster IS the
    dtype-policy soundness invariant)."""
    with algo.cache.lock:
        nt, agg, ep, nodes = algo.cache.snapshot()
        res = algo.resident.sync(nt, agg, algo.cache.space,
                                 algo.cache.take_dirty_rows(),
                                 algo.cache.tensor_epoch)
        fresh = sv.device_cluster(nt, agg, algo.cache.space)
    res = sv.widen_cluster(res)
    for field, a, b in zip(sv.DeviceCluster._fields, fresh, res):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"resident.{field} diverged from the full snapshot"


class TestResidentCluster:
    def test_second_drain_scatters_rows_instead_of_full_transfer(self):
        daemon = _rig()
        algo = daemon.config.algorithm
        for i in range(8):
            daemon.enqueue(make_pod(f"ra{i}", cpu="100m"))
        daemon.schedule_pending(wait_first=False)
        daemon.wait_for_binds()
        assert algo.resident.stats == {"full_syncs": 1, "row_syncs": 0,
                                       "rows_scattered": 0}
        for i in range(8):
            daemon.enqueue(make_pod(f"rb{i}", cpu="100m"))
        daemon.schedule_pending(wait_first=False)
        daemon.wait_for_binds()
        # The 8 assumed pods dirtied at most 8 of 40 rows: a scatter, not
        # a re-snapshot.
        assert algo.resident.stats["full_syncs"] == 1
        assert algo.resident.stats["row_syncs"] == 1
        assert 1 <= algo.resident.stats["rows_scattered"] <= 8
        _assert_resident_matches_fresh(algo)

    def test_heartbeat_flip_is_visible_through_the_mirror(self):
        """A node Ready->NotReady update must reach the device through
        the row scatter: the next drain places nothing there."""
        daemon = _rig(n_nodes=30)
        algo = daemon.config.algorithm
        daemon.enqueue(make_pod("warmup", cpu="100m"))
        daemon.schedule_pending(wait_first=False)
        algo.cache.update_node(make_node("rn0", milli_cpu=4000,
                                         conditions=[("Ready", "False")]))
        placements = algo.schedule_batch(
            [make_pod(f"hb{i}", cpu="100m") for i in range(6)])
        assert all(p is not None and p != "rn0" for p in placements)
        assert algo.resident.stats["full_syncs"] == 1
        _assert_resident_matches_fresh(algo)

    def test_assume_and_forget_keep_mirror_consistent(self):
        daemon = _rig(n_nodes=24)
        algo = daemon.config.algorithm
        pods = [make_pod(f"af{i}", cpu="500m") for i in range(6)]
        for p in pods:
            daemon.enqueue(p)
        daemon.schedule_pending(wait_first=False)
        daemon.wait_for_binds()
        algo.cache.forget_pod(pods[0]) if algo.cache.is_assumed(
            pods[0].key) else None
        _assert_resident_matches_fresh(algo)

    def test_node_join_inside_the_capacity_is_a_scattered_row(self):
        """(was: a node append forces a full re-snapshot.)  The node
        axis has a capacity: the joiner takes a free row, and that row
        crosses in the scatter like any dirty row."""
        daemon = _rig(n_nodes=10)
        algo = daemon.config.algorithm
        algo.schedule_batch([make_pod("pre", cpu="100m")])
        before = dict(algo.resident.stats)
        epoch, sig = algo.cache.tensor_epoch, algo.resident._sig
        algo.cache.add_node(make_node("joiner", milli_cpu=4000))
        algo.schedule_batch([make_pod("post", cpu="100m")])
        assert algo.resident.stats["full_syncs"] == before["full_syncs"]
        assert algo.resident.stats["row_syncs"] == before["row_syncs"] + 1
        assert (algo.cache.tensor_epoch, algo.resident._sig) == (epoch, sig)
        _assert_resident_matches_fresh(algo)
        # the joiner is a node like any other: a pod only it can hold
        [dest] = algo.schedule_batch([make_pod("only", cpu="100m",
                                               node_name="joiner")])
        assert dest == "joiner"

    def test_node_removal_is_a_scattered_row_and_a_relist_a_full_upload(
            self):
        """(was: a removal rebuilds and forces a full re-snapshot.)  A
        removal frees the node's row in place: one dirty row.  The
        rebuild from the tracked objects (a relist, the verifier's
        self-heal) is what still re-uploads the fleet."""
        daemon = _rig(n_nodes=10)
        algo = daemon.config.algorithm
        algo.schedule_batch([make_pod("pre2", cpu="100m")])
        before = dict(algo.resident.stats)
        epoch = algo.cache.tensor_epoch
        algo.cache.remove_node("rn3")
        placed = algo.schedule_batch(
            [make_pod(f"post2-{i}", cpu="100m") for i in range(12)])
        assert None not in placed and "rn3" not in placed
        assert algo.resident.stats["full_syncs"] == before["full_syncs"]
        assert algo.resident.stats["row_syncs"] == before["row_syncs"] + 1
        assert algo.cache.tensor_epoch == epoch
        _assert_resident_matches_fresh(algo)
        algo.cache.force_resnapshot()
        algo.schedule_batch([make_pod("post3", cpu="100m")])
        assert algo.resident.stats["full_syncs"] == before["full_syncs"] + 1
        assert algo.cache.tensor_epoch == epoch + 1
        _assert_resident_matches_fresh(algo)

    def test_column_capacity_growth_forces_full_resnapshot(self):
        """Interning enough new port tokens to cross a vocab capacity
        bucket widens the cluster's ports columns — the resident arrays
        cannot hold the rows and must re-upload."""
        daemon = _rig(n_nodes=16)
        algo = daemon.config.algorithm
        algo.schedule_batch([make_pod("cap0", cpu="100m")])
        before = algo.resident.stats["full_syncs"]
        cap0 = algo.cache.space.ports.capacity
        i = 0
        while algo.cache.space.ports.capacity == cap0:
            algo.cache.space.ports.id(str(20000 + i))
            i += 1
        algo.schedule_batch([make_pod("cap1", cpu="100m")])
        assert algo.resident.stats["full_syncs"] == before + 1
        _assert_resident_matches_fresh(algo)

    def test_node_delete_readd_same_name_different_capacity(self):
        """ISSUE 7 satellite: delete a node and re-add it under the SAME
        name with DIFFERENT capacity between drains.  The removal frees
        the row and the join takes it again (ISSUE 36: both are dirty
        rows, no ``tensor_epoch`` bump, no re-upload) — a stale mirror
        would keep scheduling against the old capacity."""
        daemon = _rig(n_nodes=3)
        algo = daemon.config.algorithm
        # Fill the tiny fleet so only fresh capacity can take more.
        for i, node in enumerate(("rn0", "rn1", "rn2")):
            algo.cache.update_node(make_node(node, milli_cpu=1000))
        fillers = [make_pod(f"fill{i}", cpu="900m") for i in range(3)]
        for pod, dest in zip(fillers, algo.schedule_batch(fillers)):
            assert dest is not None
            algo.cache.assume_pod(pod, dest)
        epoch_before = algo.cache.tensor_epoch
        fulls_before = algo.resident.stats["full_syncs"]
        # The churn: rn1 dies and rejoins with 8x the capacity.  Its
        # pods stay tracked until their own deletes arrive (reference
        # semantics) — remove them explicitly like the node drain does.
        for pod in fillers:
            if pod.node_name == "rn1":
                algo.cache.remove_pod(pod)
        algo.cache.remove_node("rn1")
        algo.cache.add_node(make_node("rn1", milli_cpu=8000))
        # A big pod fits ONLY the re-added node's new capacity: a stale
        # resident row (old 1000m) would fail it everywhere.
        [dest] = algo.schedule_batch([make_pod("big", cpu="4")])
        assert dest == "rn1"
        assert algo.cache.tensor_epoch == epoch_before
        assert algo.resident.stats["full_syncs"] == fulls_before
        _assert_resident_matches_fresh(algo)
        # And the reverse edge: re-add with SHRUNK capacity — the mirror
        # must not keep placing against the old larger row.
        algo.cache.remove_node("rn2")
        algo.cache.add_node(make_node("rn2", milli_cpu=100))
        placements = algo.schedule_batch(
            [make_pod(f"post{i}", cpu="600m") for i in range(2)])
        assert all(p != "rn2" for p in placements)
        _assert_resident_matches_fresh(algo)

    def test_majority_dirty_falls_back_to_full_upload(self):
        """Dirtying over a quarter of the node axis re-uploads instead
        of scattering (the gather would move most of the bytes anyway)."""
        daemon = _rig(n_nodes=40)
        algo = daemon.config.algorithm
        algo.schedule_batch([make_pod("sd0", cpu="100m")])
        before = algo.resident.stats["full_syncs"]
        assert algo.cache.snapshot()[0].n == 128
        for i in range(33):                     # 33 x 4 > 128 rows
            algo.cache.update_node(make_node(f"rn{i}", milli_cpu=8000))
        algo.schedule_batch([make_pod("sd1", cpu="100m")])
        assert algo.resident.stats["full_syncs"] == before + 1


# -- the dirty rows' wire form (ISSUE 33) ------------------------------------

PACKED_NODES = 72     # scatter_buckets(72) == [1, 2, 4, 8, 16, 32]


def _packed_rig(res: str) -> GenericScheduler:
    """A fleet whose every column family holds content — taints, images,
    zone / hostname topology, and through resident pods host ports and
    volumes — synced once.  ``res`` = "int32": one node past
    ``_I16_GATE`` keeps the resource plane wide from the first upload."""
    algo = GenericScheduler()
    for i in range(PACKED_NODES):
        algo.cache.add_node(make_node(
            f"pk{i}", milli_cpu=64000 if res == "int32" and i == 5 else 4000,
            labels={api.HOSTNAME_LABEL: f"pk{i}",
                    api.ZONE_LABEL: f"z{i % 3}"},
            taints=[{"key": "dedicated", "value": f"t{i % 2}",
                     "effect": "PreferNoSchedule" if i % 4 else
                     "NoSchedule"}] if i % 3 == 0 else None,
            images=[([f"img{i % 5}:v1"], (2 + i % 8) * 1024 * 1024)]))
    for i in range(0, PACKED_NODES, 7):
        _land(algo, f"seed{i}", i)
    _assert_resident_matches_fresh(algo)
    assert algo.resident.dc.res16.dtype == np.dtype(res)
    return algo


def _land(algo: GenericScheduler, name: str, row: int) -> None:
    """A bound pod with a host port and an RBD volume lands on ``row``."""
    algo.cache.add_pod(make_pod(
        name, cpu="100m", memory="64Mi", node_name=f"pk{row}",
        host_ports=[31000 + row % 3],
        volumes=[api.Volume(name="d", rbd_key=f"mon#pool#img{row % 4}",
                            rbd_read_only=bool(row % 2))]))


def _scatter_arrays() -> float:
    child = metrics.DEVICE_TRANSFER_ARRAYS.children().get(("scatter",))
    return float(child.value) if child is not None else 0.0


class TestPackedRows:
    def test_buckets_of_the_rig(self):
        assert sv.ResidentCluster.scatter_buckets(PACKED_NODES) == \
            [1, 2, 4, 8, 16, 32]

    @pytest.mark.parametrize("res", ["int16", "int32"])
    @pytest.mark.parametrize("dirty", [1, 2, 3, 4, 7, 8, 11, 16, 17])
    def test_packed_scatter_equals_the_full_assembly(self, dirty, res):
        """Every bucket of ``scatter_buckets`` that ``sync`` can reach
        (17 rows of 72 pad to 32; 18 would take the full upload), full
        and padded with duplicate rows, under both ``res`` policies."""
        algo = _packed_rig(res)
        rows = [(3 * j + 1) % PACKED_NODES for j in range(dirty)]
        assert len(set(rows)) == dirty
        for j, row in enumerate(rows):
            if j % 2:
                _land(algo, f"d{j}", row)
            else:       # a node event: cordon, a new taint, a new zone
                algo.cache.update_node(make_node(
                    f"pk{row}", milli_cpu=3000 + j,
                    labels={api.HOSTNAME_LABEL: f"pk{row}",
                            api.ZONE_LABEL: "z0"},
                    taints=[{"key": "dedicated", "value": "t0",
                             "effect": "NoSchedule"}],
                    images=[(["img0:v1"], 3 * 1024 * 1024)],
                    unschedulable=bool(j % 4)))
        before = dict(algo.resident.stats)
        arrays = _scatter_arrays()
        _assert_resident_matches_fresh(algo)
        assert algo.resident.stats["full_syncs"] == before["full_syncs"]
        assert algo.resident.stats["row_syncs"] == before["row_syncs"] + 1
        assert algo.resident.stats["rows_scattered"] == \
            before["rows_scattered"] + dirty
        # the mechanism engaged: ONE array crossed for the rows
        assert _scatter_arrays() - arrays == 1
        dc = algo.resident.dc
        assert dc.res16.dtype == np.dtype(res)
        for plane in (dc.ports_used, dc.vol_any, dc.taints_nosched,
                      dc.taints_prefer, dc.image_kib):
            assert np.asarray(plane).any()
        assert (np.asarray(dc.topo_dom) >= 0).any()

    def test_layout_follows_signature_and_bucket_alone(self):
        algo = _packed_rig("int16")
        planes = sv._cluster_planes(algo.resident.dc)
        (layout, regions), words = sv.rows_layout(planes, 8)
        assert [e[0] for e in layout] == \
            ["idx"] + list(sv.NarrowCluster._fields)
        assert layout[0][1:] == ("int32", (8,), 0)
        # one region per storage width, each on a whole word behind the
        # one before it; a region's leaves lie end to end in it
        assert [r[0] for r in regions] == ["int32", "int16", "uint8"]
        at = 0
        for dtype, off, n in regions:
            assert off == at
            at += sv._words(dtype, n)
            mine = [e for e in layout if sv._REGION_OF[e[1]] == dtype]
            assert [e[3] for e in mine] == list(np.cumsum(
                [0] + [int(np.prod(e[2])) for e in mine[:-1]]))
            assert n == sum(int(np.prod(e[2])) for e in mine)
        assert at == words
        assert sv.rows_layout(planes, 8)[0] is sv.rows_layout(planes, 8)[0]
        wide = sv._cluster_planes(_packed_rig("int32").resident.dc)
        assert sv.rows_layout(wide, 8)[1] > words

    @pytest.mark.parametrize("how", ["alloc", "requested", "image"])
    def test_a_row_over_the_gate_takes_the_full_upload_first(self, how):
        """Between two launches a row crosses ``_I16_GATE``: the kept
        proof no longer holds for it, so the fleet is uploaded under the
        wider policy BEFORE any scatter — nothing reaches a plane too
        narrow for it."""
        algo = _packed_rig("int16")
        assert algo.resident.dc.image_kib.dtype == np.int16
        if how == "alloc":
            algo.cache.update_node(make_node(
                "pk9", milli_cpu=sv._I16_GATE + 1,
                labels={api.HOSTNAME_LABEL: "pk9", api.ZONE_LABEL: "z0"}))
        elif how == "requested":
            algo.cache.add_pod(make_pod("hog", cpu="33", node_name="pk9"))
        else:
            algo.cache.update_node(make_node(
                "pk9", labels={api.HOSTNAME_LABEL: "pk9",
                               api.ZONE_LABEL: "z0"},
                images=[(["img0:v1"], (sv._I16_GATE + 5) * 1024)]))
        before = dict(algo.resident.stats)
        arrays = _scatter_arrays()
        _assert_resident_matches_fresh(algo)
        assert algo.resident.stats["full_syncs"] == before["full_syncs"] + 1
        assert algo.resident.stats["row_syncs"] == before["row_syncs"]
        assert _scatter_arrays() == arrays
        wide = algo.resident.dc.image_kib if how == "image" else \
            algo.resident.dc.res16
        assert wide.dtype == np.int32
        # back under the gate: the row is scattered, and the plane stays
        # wide until the next full upload (bytes, never correctness)
        if how == "requested":
            algo.cache.remove_pod(make_pod("hog", cpu="33",
                                           node_name="pk9"))
        else:
            algo.cache.update_node(make_node(
                "pk9", labels={api.HOSTNAME_LABEL: "pk9",
                               api.ZONE_LABEL: "z0"},
                images=[(["img0:v1"], 3 * 1024 * 1024)]))
        _assert_resident_matches_fresh(algo)
        assert algo.resident.stats["full_syncs"] == before["full_syncs"] + 1
        assert algo.resident.stats["row_syncs"] == before["row_syncs"] + 1
        dc = algo.resident.dc
        assert (dc.image_kib if how == "image" else dc.res16).dtype == \
            np.int32

    def test_a_negative_row_widens_too(self):
        """The gate has two sides: a negative aggregate (an overcommit
        ingested backwards) is outside int16's proven range as well."""
        need = sv._range_policy(np.array([[-1, 0, 0, 0, 0, 0, 0]]),
                                np.zeros((1, 1), np.int32),
                                GenericScheduler().cache.space)
        assert need.res == "int32"
        kept = sv.DtypePolicy("int16", "int16", "int16")
        assert not sv.policy_holds(kept, need)
        assert sv.policy_holds(need, kept)       # wider than asked: fine
        assert sv.policy_holds(kept, kept)

    def test_prewarm_traces_the_packed_program_at_every_bucket(self):
        algo = _packed_rig("int16")
        scatter = algo.resident._scatter_fn()
        assert algo.resident.prewarm_scatter() == 6
        size = scatter._cache_size()
        assert size >= 6
        before = _scatter_arrays()
        for row in (2, 3, 5):
            _land(algo, f"late{row}", row)
        _assert_resident_matches_fresh(algo)      # bucket 4: warm
        assert scatter._cache_size() == size
        assert _scatter_arrays() - before == 1


class TestPrewarmLadder:
    def test_stream_floor_read_once_at_startup(self, monkeypatch):
        """The ISSUE 5 bugfix: KT_STREAM_MIN_BUCKET changing after the
        daemon started must not move the ladder (it would mint shapes
        the pre-warm never traced)."""
        monkeypatch.setenv("KT_STREAM_MIN_BUCKET", "128")
        daemon = _rig(n_nodes=4, stream_chunk=1024)
        daemon.STREAM_THRESHOLD = 1024
        assert daemon.stream_min_bucket == 128
        assert daemon.effective_ladder() == [128, 256, 512, 1024]
        monkeypatch.setenv("KT_STREAM_MIN_BUCKET", "32")
        # Captured at startup: the running daemon's ladder is unchanged.
        assert daemon.stream_min_bucket == 128
        assert daemon.effective_ladder() == [128, 256, 512, 1024]
        # With the small-drain path open past the chunk (huge threshold),
        # the ladder covers every mintable pow2 bucket up to 4096 — a
        # 2049..4095-pod drain legally mints 4096 (the review catch).
        daemon.STREAM_THRESHOLD = 1 << 62
        assert daemon.effective_ladder() == \
            [128, 256, 512, 1024, 2048, 4096]
        # Threshold 1 routes EVERY drain through the stream chunk: the
        # small-drain buckets are unreachable and the ladder is minimal.
        daemon.STREAM_THRESHOLD = 1
        assert daemon.effective_ladder() == [1024]

    def test_ladder_covers_exactly_the_mintable_buckets(self):
        """A non-pow2 floor mints {floor} then pow2 values above it —
        never floor doublings; and the stream chunk only joins the
        ladder when the chunked path is reachable (STREAM_THRESHOLD
        set)."""
        daemon = _rig(n_nodes=4, stream_chunk=8192)
        daemon.stream_min_bucket = 300
        daemon.STREAM_THRESHOLD = 1 << 62  # unset sentinel: one-shot big
        assert daemon.effective_ladder() == [300, 512, 1024, 2048, 4096]
        daemon.STREAM_THRESHOLD = 8192
        assert daemon.effective_ladder() == \
            [300, 512, 1024, 2048, 4096, 8192]

    def test_prewarm_traces_every_ladder_bucket_and_drains_reuse_it(self):
        daemon = _rig(n_nodes=6, stream_chunk=64)
        daemon.stream_min_bucket = 16
        daemon.STREAM_THRESHOLD = 64
        assert daemon.effective_ladder() == [16, 32, 64]
        timings = daemon.prewarm()
        assert sorted(timings) == [16, 32, 64]
        assert all(s > 0 for s in timings.values())
        # A post-warm drain through the small-drain stream path still
        # schedules correctly (prewarm left no cache state behind).
        assert daemon.config.algorithm.cache.pod_count() == 0
        daemon.STREAM_THRESHOLD = 1
        for i in range(10):
            daemon.enqueue(make_pod(f"pw{i}", cpu="100m"))
        assert daemon.schedule_pending(wait_first=False) == 10
        daemon.wait_for_binds()
        assert daemon.config.binder.count() == 10

    def test_prewarm_noops_without_nodes(self):
        algo = GenericScheduler()
        daemon = Scheduler(SchedulerConfig(algorithm=algo,
                                           async_bind=False))
        assert daemon.prewarm() == {}


class TestOverlappedPipeline:
    def test_pipelined_stream_drain_binds_everything(self):
        daemon = _rig(n_nodes=12, stream_chunk=8)
        daemon.STREAM_THRESHOLD = 1
        daemon.stream_min_bucket = 8
        daemon.pipeline_window = 2
        pods = [make_pod(f"pl{i}", cpu="50m") for i in range(30)]
        for p in pods:
            daemon.enqueue(p)
        assert daemon.schedule_pending(wait_first=False) == 30
        daemon.wait_for_binds()
        assert daemon.config.binder.count() == 30
        # The commit pool carried the readback/assume/bind stages.
        assert daemon._commit_pool is not None
        daemon.stop()

    def test_window_zero_is_the_synchronous_path(self):
        daemon = _rig(n_nodes=12, stream_chunk=8)
        daemon.STREAM_THRESHOLD = 1
        daemon.stream_min_bucket = 8
        daemon.pipeline_window = 0
        for i in range(20):
            daemon.enqueue(make_pod(f"sy{i}", cpu="50m"))
        assert daemon.schedule_pending(wait_first=False) == 20
        daemon.wait_for_binds()
        assert daemon.config.binder.count() == 20
        assert daemon._commit_pool is None

    def test_commit_order_and_assume_before_bind(self):
        """Chunks commit in solve order on the single worker, and within
        a chunk every pod is assumed before its bind runs."""
        events: list[tuple[str, str]] = []
        lock = threading.Lock()
        daemon = _rig(n_nodes=12, stream_chunk=4)
        daemon.STREAM_THRESHOLD = 1
        daemon.stream_min_bucket = 4
        daemon.pipeline_window = 2
        algo = daemon.config.algorithm
        real_assume = algo.cache.assume_pods

        def spy_assume(assignments, **kw):
            with lock:
                events.extend(("assume", pod.key)
                              for pod, _ in assignments)
            return real_assume(assignments, **kw)

        algo.cache.assume_pods = spy_assume
        real_bind = daemon.config.binder.bind_many

        def spy_bind(placed):
            with lock:
                events.extend(("bind", pod.key) for pod, _ in placed)
            return real_bind(placed)

        daemon.config.binder.bind_many = spy_bind
        for i in range(12):
            daemon.enqueue(make_pod(f"ord{i:02d}", cpu="50m"))
        assert daemon.schedule_pending(wait_first=False) == 12
        daemon.wait_for_binds()
        assumed_at = {k: i for i, (kind, k) in enumerate(events)
                      if kind == "assume"}
        for i, (kind, key) in enumerate(events):
            if kind == "bind":
                assert assumed_at[key] < i, \
                    f"{key} bound before it was assumed"
        # Assume order across chunks follows solve (queue) order.
        assumed_keys = [k for kind, k in events if kind == "assume"]
        assert assumed_keys == sorted(assumed_keys)
        daemon.stop()

    def test_commit_crash_requeues_unassumed_pods(self):
        """A crashing commit surfaces to schedule_pending's handler:
        pods the crashed chunk never assumed are requeued, pods from
        completed chunks are not double-tracked."""
        daemon = _rig(n_nodes=12, stream_chunk=4)
        daemon.STREAM_THRESHOLD = 1
        daemon.stream_min_bucket = 4
        daemon.pipeline_window = 1
        from kubernetes_tpu.scheduler.backoff import PodBackoff
        daemon.backoff = PodBackoff(default_duration=0.01,
                                    max_duration=0.1)
        algo = daemon.config.algorithm
        real_assume = algo.cache.assume_pods
        calls = [0]

        def failing_assume(assignments, **kw):
            calls[0] += 1
            if calls[0] == 2:
                raise RuntimeError("injected commit crash")
            return real_assume(assignments, **kw)

        algo.cache.assume_pods = failing_assume
        for i in range(12):
            daemon.enqueue(make_pod(f"cr{i}", cpu="50m"))
        assert daemon.schedule_pending(wait_first=False) == 12
        daemon.wait_for_binds()
        algo.cache.assume_pods = real_assume
        # Chunk 2's four pods were requeued through backoff; wait for
        # the requeue worker, then drain again.
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and \
                daemon.config.binder.count() < 12:
            daemon.schedule_pending(wait_first=False, timeout=0.05)
            daemon.wait_for_binds()
            time.sleep(0.05)
        assert daemon.config.binder.count() == 12
        daemon.stop()


class TestDeferredReadbackFaults:
    """ISSUE 10 satellite: a device fault raised inside the deferred
    readback (``resolve()`` under ``defer_readback=True``, i.e. on the
    commit worker) must requeue the chunk's pods — never drop them, and
    never wedge the KT_PIPELINE_WINDOW semaphore."""

    def _fault_second_resolve(self, algo):
        """Wrap schedule_batch_stream so chunk 2's resolve() raises a
        classified device fault at readback time."""
        from kubernetes_tpu.engine.guard import DeviceFault
        real_stream = algo.schedule_batch_stream
        chunk_no = [0]

        def faulting_stream(pods, chunk_size=2048, defer_readback=False):
            for chunk_pods, resolve in real_stream(
                    pods, chunk_size=chunk_size, defer_readback=True):
                chunk_no[0] += 1
                if chunk_no[0] == 2:
                    def resolve(_resolve=resolve):
                        raise DeviceFault(
                            "oom", "stream",
                            RuntimeError("RESOURCE_EXHAUSTED: injected "
                                         "at readback"))
                # a drain of one chunk reads back inline
                yield (chunk_pods, resolve) if defer_readback \
                    else resolve()

        algo.schedule_batch_stream = faulting_stream

    def test_guard_off_fault_in_resolve_requeues_chunk(self, monkeypatch):
        """Legacy path (KT_GUARD=0): the fault surfaces through the
        commit future to drain()'s crash handler, which requeues exactly
        the chunk's pods through backoff; the semaphore is released and
        the next drain binds them."""
        monkeypatch.setenv("KT_GUARD", "0")
        daemon = _rig(n_nodes=12, stream_chunk=4)
        daemon.STREAM_THRESHOLD = 1
        daemon.stream_min_bucket = 4
        daemon.pipeline_window = 1
        from kubernetes_tpu.scheduler.backoff import PodBackoff
        daemon.backoff = PodBackoff(default_duration=0.01,
                                    max_duration=0.05)
        algo = daemon.config.algorithm
        assert not algo.guard.enabled
        self._fault_second_resolve(algo)
        for i in range(12):
            daemon.enqueue(make_pod(f"rb{i}", cpu="50m"))
        assert daemon.schedule_pending(wait_first=False) == 12
        daemon.wait_for_binds()
        # Chunk 2 (4 pods) was requeued, not dropped or double-bound.
        assert daemon.config.binder.count() == 8
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and \
                daemon.config.binder.count() < 12:
            daemon.schedule_pending(wait_first=False, timeout=0.05)
            daemon.wait_for_binds()
            time.sleep(0.02)
        assert daemon.config.binder.count() == 12
        # The window semaphore is not wedged: a further windowed drain
        # completes.
        for i in range(8):
            daemon.enqueue(make_pod(f"rb2-{i}", cpu="50m"))
        assert daemon.schedule_pending(wait_first=False) == 8
        daemon.wait_for_binds()
        assert daemon.config.binder.count() == 20
        daemon.stop()

    def test_guard_on_fault_in_resolve_recovers_in_one_drain(self):
        """With the guard enabled, the same fault is caught by the
        pipeline's recovery ladder inside ONE schedule_pending call:
        committed chunks stay committed, the stranded remainder
        re-dispatches, and every pod binds without waiting out a
        backoff."""
        daemon = _rig(n_nodes=12, stream_chunk=4)
        daemon.STREAM_THRESHOLD = 1
        daemon.stream_min_bucket = 4
        daemon.pipeline_window = 1
        algo = daemon.config.algorithm
        assert algo.guard.enabled
        self._fault_second_resolve(algo)
        for i in range(12):
            daemon.enqueue(make_pod(f"rg{i}", cpu="50m"))
        assert daemon.schedule_pending(wait_first=False) == 12
        daemon.wait_for_binds()
        assert daemon.config.binder.count() == 12
        daemon.stop()


class TestCompileCache:
    """Where the persistent compile cache lives is decided from outside
    (engine/compile_cache.py).  JAX reads JAX_COMPILATION_CACHE_DIR at
    import, so each case is a fresh interpreter."""

    _PROBE = (
        "import jax\n"
        "updates = []\n"
        "real = jax.config.update\n"
        "def spy(name, value):\n"
        "    updates.append(name)\n"
        "    real(name, value)\n"
        "jax.config.update = spy\n"
        "from kubernetes_tpu.engine import compile_cache as cc\n"
        "d = cc.configure()\n"
        "assert cc.configure() == d == cc.cache_dir()\n"
        "assert d == jax.config.jax_compilation_cache_dir\n"
        "assert jax.config.jax_persistent_cache_min_compile_time_secs == 0\n"
        "import json\n"
        "print(json.dumps({'dir': d, 'set_dir': "
        "'jax_compilation_cache_dir' in updates}))\n")

    def _probe(self, cwd, cache_env=None) -> dict:
        import json
        import os
        import subprocess
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PYTHONPATH=repo)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if cache_env is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = cache_env
        out = subprocess.run([sys.executable, "-c", self._PROBE],
                             cwd=str(cwd), env=env, capture_output=True,
                             text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    def test_env_places_the_cache_and_code_sets_no_directory(self,
                                                             tmp_path):
        got = self._probe(tmp_path, cache_env=str(tmp_path / "xla"))
        assert got == {"dir": str(tmp_path / "xla"), "set_dir": False}

    def test_unset_env_means_the_checkout_from_any_cwd(self, tmp_path):
        import os
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = {"dir": os.path.join(repo, ".jax_cache"), "set_dir": True}
        assert self._probe(tmp_path) == want
        assert self._probe(repo) == want
