#!/usr/bin/env python3
"""One launch after node events, on the chip, against the plain reference.

  python3 benchmarks/tests/node_events.py --config mixedchurn-5000n --seed 7

The pattern of ``bucket.py``: the configuration's fleet and its resident
pods (placed by the reference, as ``run.prefill`` places them) go into a
``GenericScheduler`` in THIS process; a first launch of ``--bucket`` pods
builds the node tensors, uploads the fleet and compiles (its answers are
dropped), the dirty-row scatter is traced at every bucket, and the
recompile watchdog is armed as ``Scheduler.prewarm`` arms it.  Then the
node events: ``--remove`` EMPTY nodes leave, as many join under new
names (they take the freed rows), and ``--more`` join beside them (they
take free rows past the fleet's).  One launch of ``--bucket`` pods
follows, and the reference — given the same live nodes: a node that left
has no room in its arrays, a node that joined is one more of the same
shape — replays the answers in order: every placement one of its
``best_nodes`` on the state the pods before it left.  At least one pod
has to land on a re-used row and one on a row past the fleet's, and
between the events and the answers nothing may compile, rebuild or
upload the fleet.  Prints one JSON line and exits 0 when all of that
holds; fails without a TPU.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import run  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--bucket", type=int, default=256)
    p.add_argument("--remove", type=int, default=64)
    p.add_argument("--more", type=int, default=100)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--platform", default="tpu")
    opts = p.parse_args()
    config = run.load_json(os.path.join(
        os.path.dirname(HERE), "configs", opts.config + ".json"))
    shapes, ref = run.parts_of(config)
    n0 = int(config["nodes"]["count"])
    joined = opts.remove + opts.more
    # the reference's fleet: the configuration's, and the nodes that join
    # (the same shape, named on from node-<n0>)
    nodes = shapes.Nodes(dict(config["nodes"], count=n0 + joined), opts.seed)
    pods = shapes.Pods(config["pods"], opts.seed, config["nodes"])
    n_resident = int(config["resident_cap"])
    pods.grow(n_resident + 2 * opts.bucket)

    import jax
    import numpy as np
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.engine import devicestats
    from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
    device = jax.devices()[0]
    if device.platform != opts.platform:
        print(f"needs {opts.platform}, found {device.platform}",
              file=sys.stderr)
        return 1

    def make(i: int) -> "api.Pod":
        return api.pod_from_json(json.loads(pods.json_bytes(i)))

    objs = nodes.to_json()
    engine = GenericScheduler()
    for obj in objs[:n0]:
        engine.cache.add_node(api.node_from_json(obj))
    # the nodes still to join have no room yet, for the reference too
    alloc = (nodes.alloc_cpu, nodes.alloc_mem, nodes.alloc_pods)
    later = [a[n0:].copy() for a in alloc]
    for a in alloc:
        a[n0:] = 0
    state = ref.State(nodes, pods)
    for i in range(n_resident):
        best = ref.best_nodes(state, i)
        node = int(best[i % len(best)])
        state.add(i, node)
        pod = make(i)
        pod.node_name = f"node-{node}"
        engine.cache.add_pod(pod)

    cache, resident = engine.cache, engine.resident
    t0 = time.perf_counter()
    warm = [make(n_resident + opts.bucket + k) for k in range(opts.bucket)]
    list(engine.schedule_batch_stream(warm, chunk_size=opts.bucket))
    resident.prewarm_scatter()
    first_call_s = time.perf_counter() - t0
    devicestats.arm()
    before = {"compiles": devicestats.post_prewarm_compiles(),
              "tensor_epoch": cache.tensor_epoch,
              "rebuilds": cache.stats["rebuilds"],
              "full_syncs": resident.stats["full_syncs"],
              "row_syncs": resident.stats["row_syncs"],
              "signature": resident._sig}

    # -- the node events ------------------------------------------------------
    rng = np.random.RandomState(opts.seed % (2 ** 32))
    empty = [i for i in range(n0) if not cache.node_pods(f"node-{i}")]
    if len(empty) < opts.remove:
        # a full fleet: retire the pods of the nodes that leave, as a
        # drain does, in the cache and in the reference's state
        empty = rng.permutation(n0)[:opts.remove].tolist()
        for i in empty:
            for pod in cache.node_pods(f"node-{i}"):
                cache.remove_pod(pod)
                state.add(int(pod.name[len("p-"):]), i, -1)
    gone = sorted(rng.permutation(empty)[:opts.remove].tolist())
    t0 = time.perf_counter()
    for i in gone:
        cache.remove_node(f"node-{i}")
    for obj in objs[n0:]:
        cache.add_node(api.node_from_json(obj))
    events_s = time.perf_counter() - t0
    for a, kept in zip(alloc, later):
        a[gone] = 0
        a[n0:] = kept
    nt = cache.snapshot()[0]
    row_of = {name: nt.name_to_idx[name]
              for name in (f"node-{i}" for i in range(n0, n0 + joined))}
    reused = {name for name, row in row_of.items() if row < n0}
    past = {name for name, row in row_of.items() if row >= n0}

    # -- the launch -----------------------------------------------------------
    batch = [make(n_resident + k) for k in range(opts.bucket)]
    t0 = time.perf_counter()
    (_chunk_pods, placements), = list(engine.schedule_batch_stream(
        batch, chunk_size=opts.bucket))
    launch_s = time.perf_counter() - t0
    placed = unplaced = wrong = on_reused = on_past = on_gone = 0
    for k, chosen in enumerate(placements[:opts.bucket]):
        i = n_resident + k
        best = ref.best_nodes(state, i)
        if chosen is None:
            unplaced += 1
            wrong += len(best) > 0
            continue
        node = int(chosen[len("node-"):])
        placed += 1
        on_reused += chosen in reused
        on_past += chosen in past
        on_gone += node in gone
        if node not in best or any(ref.broken(state, i, node).values()):
            wrong += 1
            continue
        state.add(i, node)
    after = {"compiles": devicestats.post_prewarm_compiles(),
             "tensor_epoch": cache.tensor_epoch,
             "rebuilds": cache.stats["rebuilds"],
             "full_syncs": resident.stats["full_syncs"],
             "row_syncs": resident.stats["row_syncs"],
             "signature": resident._sig}
    moved = {k: [before[k], after[k]] for k in before
             if before[k] != after[k]}
    out = {"config": opts.config, "bucket": opts.bucket, "seed": opts.seed,
           "nodes": n0, "capacity": nt.n, "resident": n_resident,
           "removed": len(gone), "joined": joined,
           "joined_on_reused_rows": len(reused),
           "joined_on_rows_past_the_fleet": len(past),
           "placed": placed, "unplaced": unplaced,
           "not_the_references": wrong, "on_reused_rows": on_reused,
           "on_rows_past_the_fleet": on_past, "on_removed_nodes": on_gone,
           "moved": moved, "first_call_s": first_call_s,
           "node_events_s": events_s, "launch_s": launch_s,
           "engine_mode": engine.guard.mode,
           "device": {"platform": device.platform,
                      "kind": device.device_kind}}
    print(json.dumps(out, default=str))
    ok = (wrong == 0 and on_gone == 0 and on_reused > 0 and on_past > 0
          and len(reused) == len(gone) and set(moved) == {"row_syncs"}
          and engine.guard.mode == "device")
    return int(not ok)


if __name__ == "__main__":
    sys.exit(main())
