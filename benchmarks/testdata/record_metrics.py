#!/usr/bin/env python3
"""How ``daemon_200n.open.metrics.txt`` / ``.close.metrics.txt`` were
recorded (PR 27, on this sandbox's CPU — counts that the families exist,
never a speed):

  python3 benchmarks/testdata/record_metrics.py <out dir>

One whole run of the benchmark's tiny test cell (``tests/conftest.py``:
200 mixed nodes, 800 resident pods, 300 pods/s open loop, a 4 s window)
against the real daemon pinned to the CPU, keeping the daemon's /metrics
page as the runner read it at window open and at window close, histogram
bucket rows dropped.  ``tests/test_new_metrics.py`` reads every per-layer
metric that PR 27 added from this pair.
"""

import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

CHILD = """
import json, sys
import run, rig
pages = []
read = rig.http_get
def keep(port, path, timeout=10.0):
    body = read(port, path, timeout)
    if path == "/metrics":
        pages.append((port, body.decode()))
    return body
rig.http_get = keep
cell = run.Cell(run.load_json(rig.REPO + "/BENCHMARK.json"), "tiny-open")
res = run.run_cell(cell, 27, 4.0, False, platform="cpu")
assert res["correct"], res["compared"]
# the daemon's pages in the order run_cell reads them: its account after
# prewarm, WINDOW OPEN, WINDOW CLOSE, its account at the close
ports = [p for p, _ in pages]
daemon = max(set(ports), key=ports.count)
mine = [text for p, text in pages if p == daemon]
for name, text in (("open", mine[1]), ("close", mine[2])):
    with open(sys.argv[1] + f"/daemon_200n.{name}.metrics.txt", "w") as f:
        f.write("\\n".join(line for line in text.splitlines()
                          if "_bucket{" not in line) + "\\n")
"""


def main(out_dir: str) -> None:
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    import conftest
    tree = tempfile.mkdtemp(prefix="record-metrics-")
    try:
        shutil.copytree(BENCH, os.path.join(tree, "benchmarks"),
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
        shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tree)
        for name in ("kubernetes_tpu", "native"):
            os.symlink(os.path.join(REPO, name), os.path.join(tree, name))
        conftest.add_tiny_cells(tree)
        subprocess.run(
            [sys.executable, "-c", CHILD, os.path.abspath(out_dir)],
            cwd=tree, check=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONPATH=os.path.join(tree, "benchmarks")))
    finally:
        shutil.rmtree(tree, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
