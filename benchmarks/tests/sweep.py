#!/usr/bin/env python3
"""The sweep that fixed ``rate_pods_s`` of ``traffic/poisson-open.json``:
one normal run of a cell with the mix's rate replaced, on the chip.

  python3 benchmarks/tests/sweep.py --workload schedperf5k-arrivals --rate 1600 --seed 3 --seconds 12

Prints the latencies, how late the generator ran, what was still pending
at close and the binds per second beside the offered rate.  The highest
rate at which pending does not grow with the window and the generator
stays on time is the knee; the mix runs at about four fifths of it
(PERF.md has the readings).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import rig  # noqa: E402
import run  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rate", type=float, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=12.0)
    opts = p.parse_args()
    cell = run.Cell(run.load_json(os.path.join(rig.REPO, "BENCHMARK.json")),
                    opts.workload)
    cell.traffic = dict(cell.traffic, rate_pods_s=opts.rate)
    try:
        res = run.run_cell(cell, opts.seed, opts.seconds, False)
    except rig.RunFailure as err:
        run.log(f"FAILED: {err}")
        return 2
    info = run.load_json(os.path.join(
        run.out_dir_of(cell.name, opts.seed, False), "info.json"))
    print(json.dumps({"rate": opts.rate, "correct": res["correct"],
                      "info": info,
                      "failed": res["failed"], "attempted": res["attempted"],
                      "over": {k: v for k, v in res["compared"].items()
                               if v["value"] > v["limit"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
