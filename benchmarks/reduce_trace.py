#!/usr/bin/env python3
"""From a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy share, device time per operation and per
program, and the longest idle gaps with what the host was doing.

Run as a child of the runner (``python3 benchmarks/reduce_trace.py
<profile dir>``, ``JAX_PLATFORMS=cpu``: it parses, it never touches a
chip) after the daemon has stopped; prints one JSON object.

* device planes: ``/device:TPU:<n>`` (any ``/device:`` plane but the
  host's).  Their ``XLA Ops`` line holds one event per executed HLO
  operation — nested: a ``while`` spans the operations of its body — and
  ``XLA Modules`` one per executed program (``jit_<name>(<id>)``).
* ``busy_s``: the UNION of the operation intervals of a plane, averaged
  over the device planes; ``window_s``: first to last event of the whole
  trace, host planes included.
* ``lines``: per line name, ``{event name: [count, seconds]}`` summed
  over the device planes (names as the profiler gives them, ids in
  parentheses stripped).  Operation seconds are as recorded, so a
  ``while`` counts its body again.
* ``idle_gaps``: the longest intervals in which no device operation ran,
  each labelled with the host event (TraceMe) that overlaps it most, or
  ``unattributed``.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
_ID = re.compile(r"\(\d+\)$")
_HLO = re.compile(r"^%?([A-Za-z0-9_.-]+) = ")
_SAFE = re.compile(r"[^A-Za-z0-9_.-]+")
TOP = 10
NAMES_PER_LINE = 300


def _union(intervals: list) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def short_name(name: str) -> str:
    """``%while.4 = (s32[]...) while(...)`` -> ``while.4``;
    ``jit__solve_scan(123)`` -> ``jit__solve_scan``."""
    m = _HLO.match(name)
    return m.group(1) if m else _ID.sub("", name)


def find_xplane(profile_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_planes, host_events = [], []
    t_first, t_last = None, None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") \
            and not plane.name.startswith("/device:CPU")
        lines = {}
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            if not events:
                continue
            lo = min(e[1] for e in events)
            hi = max(e[2] for e in events)
            t_first = lo if t_first is None else min(t_first, lo)
            t_last = hi if t_last is None else max(t_last, hi)
            if is_device:
                lines.setdefault(line.name, []).extend(events)
            elif plane.name.startswith("/host:"):
                host_events.extend(events)
        if is_device and lines:
            device_planes.append((plane.name, lines))
    if t_first is None:
        return {"window_s": 0.0, "busy_s": 0.0, "n_device_planes": 0,
                "lines": {}, "device_ops": [], "idle_gaps": []}
    window_ns = t_last - t_first

    busy_ns = []
    totals: dict = {}
    gaps = []
    for _name, lines in device_planes:
        ops = lines.get(OPS_LINE)
        if ops is None:      # a plane without an ops line: all its events
            ops = [e for evs in lines.values() for e in evs]
        merged = _union([[s, e] for _n, s, e in ops])
        busy_ns.append(sum(e - s for s, e in merged))
        edges = [t_first] + [t for iv in merged for t in iv] + [t_last]
        gaps.extend((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        for line_name, events in lines.items():
            bucket = totals.setdefault(line_name, {})
            for name, start, end in events:
                rec = bucket.setdefault(short_name(name), [0, 0.0])
                rec[0] += 1
                rec[1] += (end - start) / 1e9

    lines_out = {}
    for line_name, bucket in totals.items():
        keep = sorted(bucket.items(), key=lambda kv: -kv[1][1])
        lines_out[line_name] = dict(keep[:NAMES_PER_LINE])
    ops_total = totals.get(OPS_LINE, {})
    device_ops = [[_SAFE.sub("_", name), rec[1]] for name, rec in sorted(
        ops_total.items(), key=lambda kv: -kv[1][1])[:TOP]]

    idle = []
    for length, start, end in sorted(gaps, reverse=True)[:TOP]:
        # the SHORTEST host event covering at least half of the gap says
        # most about it; failing that, the one overlapping it most
        label, most, tight = "unattributed", 0, None
        for name, h_start, h_end in host_events:
            overlap = min(end, h_end) - max(start, h_start)
            if overlap <= 0:
                continue
            if 2 * overlap >= length and \
                    (tight is None or h_end - h_start < tight):
                label, tight = name, h_end - h_start
            elif tight is None and overlap > most:
                label, most = name, overlap
        if start == t_first or end == t_last:
            label = "trace_edge_" + label
        idle.append([_SAFE.sub("_", label)[:64], length / 1e9])

    n = len(device_planes)
    return {"window_s": window_ns / 1e9,
            "busy_s": (sum(busy_ns) / n / 1e9) if n else 0.0,
            "n_device_planes": n,
            "lines": lines_out, "device_ops": device_ops, "idle_gaps": idle}


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print("usage: reduce_trace.py <profile dir | file.xplane.pb>",
              file=sys.stderr)
        return 2
    path = argv[0] if argv[0].endswith(".pb") else find_xplane(argv[0])
    if not path or not os.path.exists(path):
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    print(json.dumps(reduce(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
