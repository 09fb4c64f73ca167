#!/usr/bin/env python3
"""The controls of "How `correct` is decided", run on the chip at a cell's
own size and load.  Each is a normal run of ``run.py`` -- the same
traffic, window, judge and limits -- with one thing broken underneath,
and has to come out NOT correct; the benchmark's own runs never run one.

  python3 benchmarks/tests/control.py --workload <cell> --seed <n> --seconds <s> [--fault <name>]

Without ``--fault``: the program itself with the one path of its own
that breaks a guarantee the configuration states,
``--algorithm-provider ClusterAutoscalerProvider`` (MostRequested in
place of LeastRequested), so that its decisions are no longer ones the
DefaultProvider reference could have made.

With ``--fault <name>``: the plain reference scheduler in the daemon's
place (``refsched.py``; it needs no chip but runs where the cell runs, at
the cell's size) with that fault planted where the answer is produced:
``none`` (sound: the judge's lower reading from a second system),
``wrong_policy``, ``state_unchanged``, ``half_batch``, ``altered``,
``colocate`` (``refsched.py`` says what each breaks; it drives the cell's
configuration through its own shapes and reference files, so a fault can
be planted against a new configuration's guarantee).

Prints the numbers compared, each beside its limit, and exits 0 when the
control failed at least one of them (1 when it passed for correct; with
``--fault none`` the other way round).  ``tests/test_run.py`` keeps the
same controls at a tiny size on the CPU.
"""

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import refsched  # noqa: E402
import rig  # noqa: E402
import run  # noqa: E402

CONTROL_FLAGS = ["--algorithm-provider", "ClusterAutoscalerProvider"]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--fault", choices=refsched.FAULTS)
    opts = p.parse_args()
    cell = run.Cell(run.load_json(os.path.join(rig.REPO, "BENCHMARK.json")),
                    opts.workload)
    make_sut = None
    if opts.fault:
        make_sut = functools.partial(refsched.RefSut, fault=opts.fault,
                                     seed=opts.seed)
    else:
        cell.config["daemon"]["flags"] = \
            cell.config["daemon"]["flags"] + CONTROL_FLAGS
    try:
        res = run.run_cell(cell, opts.seed, opts.seconds, False,
                           make_sut=make_sut)
    except rig.RunFailure as err:
        run.log(f"FAILED: {err}")
        return 2
    info = run.load_json(os.path.join(
        run.out_dir_of(cell.name, opts.seed, False), "info.json"))
    print(json.dumps({"control": opts.fault or "ClusterAutoscalerProvider",
                      "correct": res["correct"], "seed": opts.seed,
                      "attempted": res["attempted"], "failed": res["failed"],
                      "seen": info["seen"], "compared": res["compared"]}))
    return int(res["correct"] != (opts.fault == "none"))


if __name__ == "__main__":
    sys.exit(main())
