#!/usr/bin/env python3
"""Where the spread of a window's percentiles comes from: one normal run
of a cell, on the chip, that also KEEPS every pod's latency, so that a
long window can be read again as shorter ones.

  python3 benchmarks/tests/study.py --workload schedperf5k-arrivals --seed 7 \
      --seconds 60 --keep chiprun_out/study

If the sub-windows of one run differ as much as runs do, the noise is
inside a run and a longer window buys it down; if they agree and runs
differ, it is between runs and length buys nothing (PERF.md, PR 29).
Kept under ``--keep``: ``<cell>.<seed>.lat.npz`` (per pod due in the
window: seconds after the window opened, submit -> bind in ms, how late
it was written in ms), ``.vars.json`` (the daemon's ``/debug/vars`` and
its collector's counters at the close), ``.record.npz`` and
``.info.json`` of the run.  Prints one line: the run's own numbers and
the percentiles of each ``--split`` seconds of the window.

  python3 benchmarks/tests/study.py --read chiprun_out/study --lengths 20,30,40,60

reads the kept files again: for each cell and window length, each run's
first window of that length (the spread BETWEEN runs, as a check reads
it) beside the spread of the consecutive windows INSIDE each run.

  python3 benchmarks/tests/study.py --sets a.jsonl b.jsonl

reads two sets of ``run.py`` result lines (one cell, the same seeds in
both) as a check does: per end-to-end metric each set's median, its
spread (inter-quartile distance over the median, by
``statistics.quantiles``) and the same without the set's run farthest
from its median, beside what BENCHMARK.json's bound allows (half of it
for the mean of the two trimmed spreads, an eighth of it as the least the
widest spread may be), and the second median against the first.
"""

import argparse
import glob
import json
import os
import shutil
import statistics
import sys
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import rig  # noqa: E402
import run  # noqa: E402

KEPT: dict = {}


def spread(values) -> float:
    """Inter-quartile distance as a share of the median, as the driver
    reads a set of runs."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values) -> float:
    """The same without the run farthest from the median."""
    mid = statistics.median(values)
    keep = sorted(values, key=lambda v: abs(v - mid))[:-1]
    return spread(keep)


class StudyDaemon(rig.Daemon):
    def account(self) -> dict:
        m = self.metrics()
        KEPT["vars"] = self.vars()
        KEPT["gc"] = {f: rows for f, rows in m.items()
                      if f.startswith("scheduler_gc_")}
        return super().account()


def keeping(kind):
    class Generator(kind.Generator):
        def report(self) -> dict:
            out = super().report()
            pods = self._due_in_window()
            now = self.t_stopped or self.t_close
            KEPT["lat"] = dict(
                due_s=np.array([self.due[p] - self.t_open for p in pods]),
                lat_ms=np.array([(self.book.bind_t.get(self.first_pod + p, now)
                                  - self.due[p]) * 1e3 for p in pods]),
                late_ms=np.array([((self.sent[p] if p < len(self.sent)
                                    else now) - self.due[p]) * 1e3
                                  for p in pods]))
            return out
    return Generator


def one_run(opts) -> int:
    cell = run.Cell(run.load_json(os.path.join(rig.REPO, "BENCHMARK.json")),
                    opts.workload)
    cell.kind = types.SimpleNamespace(Generator=keeping(cell.kind))
    try:
        res = run.run_cell(cell, opts.seed, opts.seconds, False,
                           platform=opts.platform, make_sut=StudyDaemon)
    except rig.RunFailure as err:
        run.log(f"FAILED: {err}")
        return 2
    out_dir = run.out_dir_of(cell.name, opts.seed, False)
    os.makedirs(opts.keep, exist_ok=True)
    stem = os.path.join(opts.keep, f"{cell.name}.{opts.seed}")
    np.savez_compressed(stem + ".lat.npz", **KEPT["lat"])
    with open(stem + ".vars.json", "w") as f:
        json.dump({"vars": KEPT["vars"], "gc": KEPT["gc"]}, f)
    for name in ("record.npz", "info.json"):
        shutil.copy(os.path.join(out_dir, name), f"{stem}.{name}")
    info = run.load_json(os.path.join(out_dir, "info.json"))
    due, lat = KEPT["lat"]["due_s"], KEPT["lat"]["lat_ms"]
    parts = []
    for lo in np.arange(0.0, opts.seconds, opts.split):
        part = lat[(due >= lo) & (due < lo + opts.split)]
        parts.append([float(np.percentile(part, 50)),
                      float(np.percentile(part, 95))])
    print(json.dumps({
        "cell": cell.name, "seed": opts.seed, "seconds": opts.seconds,
        "correct": res["correct"], "failed": res["failed"],
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "ready_s": info["seen"]["daemon_ready_s"],
        "prefill_s": info["seen"]["prefill_s"],
        "late_ms_p99": info["seen"]["late_ms_p99"],
        "split_s": opts.split, "p50_p95_by_part": parts,
        "over": {k: v for k, v in res["compared"].items()
                 if v["value"] > v["limit"]}}), flush=True)
    return 0


def read(opts) -> int:
    lengths = [float(x) for x in opts.lengths.split(",")]
    cells: dict = {}
    for path in sorted(glob.glob(os.path.join(opts.read, "*.lat.npz"))):
        cell, seed = os.path.basename(path).split(".")[:2]
        cells.setdefault(cell, []).append((int(seed), np.load(path)))
    for cell, runs in cells.items():
        for pct in (50, 95):
            for length in lengths:
                first, inside = [], []
                for _seed, rec in runs:
                    due, lat = rec["due_s"], rec["lat_ms"]
                    vals = [float(np.percentile(
                        lat[(due >= lo) & (due < lo + length)], pct))
                        for lo in np.arange(0.0, due.max() - length + 1.0,
                                            length)]
                    first.append(vals[0])
                    if len(vals) > 1:
                        inside.append((max(vals) - min(vals))
                                      / statistics.median(vals))
                line = {"cell": cell, "pct": pct, "length_s": length,
                        "runs": len(first),
                        "first_windows": [round(v, 2) for v in first],
                        "median": round(statistics.median(first), 2)}
                if len(first) >= 4:
                    line["between_runs_trimmed"] = round(trimmed(first), 4)
                if len(first) >= 2:
                    line["between_runs_iqr"] = round(spread(first), 4)
                    line["between_runs_range"] = round(
                        (max(first) - min(first)) / statistics.median(first),
                        4)
                if inside:
                    line["inside_run_range_median"] = round(
                        statistics.median(inside), 4)
                print(json.dumps(line))
    return 0


def read_sets(paths: list) -> int:
    bench = run.load_json(os.path.join(rig.REPO, "BENCHMARK.json"))
    sets = []
    for path in paths:
        with open(path) as f:
            sets.append([json.loads(line) for line in f if line.strip()])
    for metric in bench["end_to_end"]:
        name, bound = metric["name"], metric["bound"]
        cols = [[r["metrics"][name]["value"] for r in rows
                 if name in r["metrics"]] for rows in sets]
        if not all(len(c) >= 3 for c in cols):
            continue
        meds = [statistics.median(c) for c in cols]
        line = {"metric": name, "bound": bound, "runs": [len(c) for c in cols],
                "medians": [round(m, 3) for m in meds],
                "spread": [round(spread(c), 4) for c in cols],
                "trimmed": [round(trimmed(c), 4) for c in cols],
                "all_runs_spread": round(spread(sum(cols, [])), 4),
                "correct": [sum(bool(r["correct"]) for r in rows)
                            for rows in sets]}
        line["trimmed_mean_over_bound"] = round(
            statistics.mean(line["trimmed"]) / bound, 3)   # at most 0.5
        line["bound_over_widest"] = round(
            bound / max(line["spread"] + [line["all_runs_spread"]]), 2)  # at most 8
        if len(meds) == 2:
            line["second_over_first"] = round(meds[1] / meds[0] - 1, 4)
        print(json.dumps(line))
    return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload")
    p.add_argument("--seed", type=int)
    p.add_argument("--seconds", type=float, default=60.0)
    p.add_argument("--split", type=float, default=20.0)
    p.add_argument("--keep", default=os.path.join(rig.REPO, "chiprun_out",
                                                  "study"))
    p.add_argument("--platform", default="tpu")
    p.add_argument("--read")
    p.add_argument("--lengths", default="20,30,40,60")
    p.add_argument("--sets", nargs="+")
    opts = p.parse_args()
    if opts.sets:
        return read_sets(opts.sets)
    if opts.read:
        return read(opts)
    return one_run(opts)


if __name__ == "__main__":
    sys.exit(main())
