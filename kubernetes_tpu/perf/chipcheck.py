"""Answers on THIS process's device against the repo's references.

One process, one device: every check below runs the engine on whatever
backend JAX initializes here and compares its answers with a reference
that never touches it.  ``chip_smoke.py`` starts this module as the one
child that holds the chip for its phase and requires the ``device`` it
reports to be the TPU; tier-1 runs it on the CPU backend at a tiny size.

* ``parity``: ``perf.parity.run_parity`` — the batched drain of a
  ``rich`` cluster (inter-pod affinity, volumes, taints, ports) replayed
  through ``oracle.py``; 100 % of the sampled decisions must agree and no
  choice may be infeasible.
* ``stream_vs_host``: the streamed scan's choices for the
  ``mixed`` backlog must equal ``HostSolver.solve_greedy`` row for row.
* ``half_plane``: the same under a policy whose summed weight bound fits
  the half-width mantissa (the default provider minus
  NodePreferAvoidPods' weight 10,000), the only policies that store the
  encoded static plane at half width (``Solver._solve_scan``).
* ``select``: ``combine.select_host`` against a NumPy selectHost at ragged
  node counts with tie counters past 2^31.

Run: ``python -m kubernetes_tpu.perf.chipcheck --nodes 5000 --pods 30000``
(prints one JSON line; exit 1 when any check failed).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from kubernetes_tpu.api.policy import Policy, default_provider
from kubernetes_tpu.perf import parity, synth

STREAM_CHUNK = 4096  # the perf rigs' and the soak daemons' KT_STREAM_CHUNK


def _small_weight_policy() -> Policy:
    pol = default_provider()
    pol.priorities = [s for s in pol.priorities
                      if s.name != "NodePreferAvoidPodsPriority"]
    return pol


def stream_vs_host(n_nodes: int, n_pods: int,
                   policy_fn=default_provider) -> dict:
    """Row-for-row: the device's streamed scan vs the NumPy greedy
    engine, each on its own identical ``synth.make_rig`` cluster."""
    from kubernetes_tpu.ops.priorities import MAX_PRIORITY
    dev_eng, pods = synth.make_rig(n_nodes, n_pods, n_services=0,
                                   policy=policy_fn())
    t0 = time.perf_counter()
    dev: list = []
    for _chunk, placements in dev_eng.schedule_batch_stream(
            pods, chunk_size=min(STREAM_CHUNK, n_pods)):
        dev.extend(placements)
    dev_s = time.perf_counter() - t0
    host_eng, _ = synth.make_rig(n_nodes, 0, n_services=0,
                                 policy=policy_fn())
    t0 = time.perf_counter()
    host = host_eng.schedule_batch_host(list(pods))
    host_s = time.perf_counter() - t0
    differ = [i for i, (d, h) in enumerate(zip(dev, host)) if d != h]
    solver = dev_eng.solver
    return {
        "n_nodes": n_nodes, "n_pods": n_pods,
        "placed": sum(1 for d in dev if d is not None),
        "rows_differ": len(differ),
        "first_differ": [{"row": i, "device": dev[i], "host": host[i]}
                         for i in differ[:5]],
        "weight_bound": sum(abs(w) for _n, w, _a in solver.priority_specs)
        * MAX_PRIORITY,
        "half_dtype": np.dtype(solver._half_dtype).name,
        "engine_mode": dev_eng.guard.mode,
        # Set-up wall (compile + run), not a speed figure.
        "device_wall_s": round(dev_s, 1), "host_wall_s": round(host_s, 1),
        "ok": len(dev) == len(host) == n_pods and not differ
        and dev_eng.guard.mode == "device",
    }


def _select_reference(masked: np.ndarray, counter: int) -> int:
    """selectHost (generic_scheduler.go:124-141) in NumPy."""
    feasible = np.isfinite(masked)
    if not feasible.any():
        return -1
    ties = np.flatnonzero(feasible & (masked == masked[feasible].max()))
    return int(ties[counter % len(ties)])


def select_check(n_nodes: int) -> dict:
    import jax
    import jax.numpy as jnp

    from kubernetes_tpu.ops import combine
    select = jax.jit(combine.select_host)
    rng = np.random.RandomState(21)
    cases = wrong = 0
    for n in (n_nodes, n_nodes - 1, 128, 100):
        for trial in range(6):
            scores = rng.randint(0, 3, n).astype(np.float32)
            keep = rng.rand(n) > (1.0 if trial == 5 else 0.4)
            masked = np.where(keep, scores, -np.inf).astype(np.float32)
            counter = (2 ** 31 + 7 * trial + int(rng.randint(0, 1000))) \
                if trial % 2 else int(rng.randint(0, 1000))
            choice, any_feasible = select(jnp.asarray(masked),
                                          jnp.uint32(counter))
            want = _select_reference(masked, counter)
            cases += 1
            wrong += int(int(choice) != want
                         or bool(any_feasible) != (want >= 0))
    return {"cases": cases, "wrong": wrong, "ok": wrong == 0}


def run(n_nodes: int, n_pods: int, parity_pods: int,
        n_samples: int) -> dict:
    from kubernetes_tpu.engine import devicestats
    out: dict = {"device": devicestats.device_info()}
    print(f"chipcheck on {out['device']}: {n_nodes} nodes x {n_pods} pods",
          file=sys.stderr)
    out["select"] = select_check(n_nodes)
    rec = parity.run_parity(n_nodes, parity_pods, profile="rich",
                            n_samples=n_samples)
    rec["ok"] = rec["decision_agreement_pct"] == 100.0 and \
        rec["infeasible_choices"] == 0 and rec["sampled_decisions"] > 0
    out["parity"] = rec
    out["stream_vs_host"] = stream_vs_host(n_nodes, n_pods)
    half = stream_vs_host(n_nodes, min(n_pods, STREAM_CHUNK),
                          policy_fn=_small_weight_policy)
    # The case exists to take the half-width branch: a bound that no
    # longer fits it is a failed check, not a pass on the f32 plane.
    half["ok"] = half["ok"] and half["weight_bound"] < 256
    out["half_plane"] = half
    out["ok"] = all(out[k]["ok"] for k in
                    ("select", "parity", "stream_vs_host", "half_plane"))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nodes", type=int, required=True)
    ap.add_argument("--pods", type=int, required=True)
    ap.add_argument("--parity-pods", type=int, required=True)
    ap.add_argument("--samples", type=int, default=200)
    opts = ap.parse_args()
    out = run(opts.nodes, opts.pods, opts.parity_pods, opts.samples)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
