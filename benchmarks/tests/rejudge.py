#!/usr/bin/env python3
"""Judge again the record a run kept (``benchmarks/out/<run>/record.npz``),
with the configuration's ``judge`` parameters or others, without the chip:

  python3 benchmarks/tests/rejudge.py --config schedperf-5000n --seed 7 \
      [--set max_lag_s=1.0 --set sample=4096] benchmarks/out/<run>/record.npz

Prints every number compared.  It is how the limits and ``max_lag_s`` of
a configuration were set from the runs that had been made (PERF.md), and
how a later PR looks at a run that came out not correct.
"""

import argparse
import json
import os
import sys
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import judge  # noqa: E402
import run  # noqa: E402


def rejudge(config: dict, seed: int, path: str, platform: str = "tpu"):
    rec = np.load(path)
    shapes, ref = run.parts_of(config)
    nodes = shapes.Nodes(config["nodes"], seed)
    pods = shapes.Pods(config["pods"], seed, config["nodes"])
    pods.grow(int(rec["n_offered"]) + 1)
    book = types.SimpleNamespace(
        events=list(zip(rec["kind"].tolist(), rec["pod"].tolist(),
                        rec["node"].tolist(), rec["t"].tolist())),
        n_created=int(rec["n_created"]),
        errors=["client error"] * int(rec["n_errors"]))
    final_list = {int(p): int(n) for p, n in rec["listed"]}
    return judge.judge(ref, nodes, pods, book, int(rec["n_offered"]), final_list,
                       tuple(rec["window"]), seed, config,
                       json.loads(str(rec["account"])), platform)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--platform", default="tpu")
    p.add_argument("--set", action="append", default=[],
                   metavar="KEY=VALUE")
    p.add_argument("record")
    opts = p.parse_args()
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "configs",
            opts.config + ".json")) as f:
        config = json.load(f)
    for item in opts.set:
        key, value = item.split("=")
        config["judge"][key] = float(value)
    config["judge"]["sample"] = int(config["judge"]["sample"])
    correct, numbers, info = rejudge(config, opts.seed, opts.record,
                                     opts.platform)
    print(json.dumps({"correct": correct, "numbers": numbers, "info": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
