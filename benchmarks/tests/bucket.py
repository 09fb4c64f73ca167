#!/usr/bin/env python3
"""One launch of a configuration's pods at a bucket the harness's ramp
cannot drive, on the chip, against the configuration's plain reference.

  python3 benchmarks/tests/bucket.py --config interpod-5000n --bucket 4096 --seed 7

The harness's ramp cannot drive a burst that leaves pods unschedulable
(``loadgen.py``'s observer does not see such a pod's bind), and 4,096
pending green pods beside 2,000 resident ones are more than
``interpod-5000n``'s 5,000 nodes hold: until that is repaired no run
through ``run.py`` shows whether the scan with the dynamic affinity
predicate lowers for the chip, and is exact, at the
program's default bucket.  This does: the configuration's fleet and its
resident pods (placed by the reference, as ``run.prefill`` places them)
go into a ``GenericScheduler`` in THIS process, one
``schedule_batch_stream`` chunk of ``--bucket`` pods runs on the device,
and the reference replays the answers in order: every placement has to
be one of its ``best_nodes`` on the state the pods before it left, and a
pod without a placement has to fit nowhere.  Prints one JSON line and
exits 0 when all of that holds; fails without a TPU.  The one program of
``benchmarks/`` besides ``daemon.py`` that imports the program under
test.
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
    p.add_argument("--bucket", type=int, default=4096)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--platform", default="tpu")
    opts = p.parse_args()
    config = run.load_json(os.path.join(
        os.path.dirname(HERE), "configs", opts.config + ".json"))
    shapes, ref = run.parts_of(config)
    nodes = shapes.Nodes(config["nodes"], opts.seed)
    pods = shapes.Pods(config["pods"], opts.seed, config["nodes"])
    n_resident = int(config["resident_cap"])
    pods.grow(n_resident + opts.bucket)

    import jax
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
    device = jax.devices()[0]
    if device.platform != opts.platform:
        print(f"needs {opts.platform}, found {device.platform}",
              file=sys.stderr)
        return 1

    def make(i: int) -> "api.Pod":
        return api.pod_from_json(json.loads(pods.json_bytes(i)))

    engine = GenericScheduler()
    for obj in nodes.to_json():
        engine.cache.add_node(api.node_from_json(obj))
    state = ref.State(nodes, pods)
    for i in range(n_resident):
        best = ref.best_nodes(state, i)
        node = int(best[i % len(best)])
        state.add(i, node)
        pod = make(i)
        pod.node_name = f"node-{node}"
        engine.cache.add_pod(pod)

    batch = [make(n_resident + k) for k in range(opts.bucket)]
    timings = []
    for attempt in range(2):        # the first compiles, the second is timed
        t0 = time.perf_counter()
        chunks = list(engine.schedule_batch_stream(
            batch, chunk_size=opts.bucket))
        timings.append(time.perf_counter() - t0)
    (chunk_pods, placements), = chunks
    placed = unplaced = wrong = 0
    for k, chosen in enumerate(placements[:opts.bucket]):
        i = n_resident + k
        best = ref.best_nodes(state, i)
        if chosen is None:
            unplaced += 1
            wrong += len(best) > 0
            continue
        node = int(chosen[len("node-"):])
        placed += 1
        if node not in best:
            wrong += 1
            continue
        broken = ref.broken(state, i, node)
        wrong += any(broken.values())
        state.add(i, node)
    out = {"config": opts.config, "bucket": opts.bucket, "seed": opts.seed,
           "nodes": nodes.n, "resident": n_resident, "placed": placed,
           "unplaced": unplaced, "not_the_references": wrong,
           "first_call_s": timings[0], "second_call_s": timings[1],
           "engine_mode": engine.guard.mode,
           "device": {"platform": device.platform,
                      "kind": device.device_kind}}
    print(json.dumps(out))
    return int(wrong > 0 or engine.guard.mode != "device")


if __name__ == "__main__":
    sys.exit(main())
