"""The least work one placement needs, from the configuration alone, and
the table of peaks.

The system runs no model, so there is no FLOP count and no ``mfu``: the
scan's bound is memory.  One decision has to see, for EVERY node, what
the node can hold and what it holds after the previous decision — the
fit predicate (pods, cpu, memory) and both default resource priorities
(LeastRequested, BalancedResourceAllocation) are functions of exactly
those numbers:

  allocatable: cpu, memory, pod count      3 values
  requested:   cpu, memory, pod count      3 values

at 4 bytes each (milli-cpu and pod counts fit 32 bits; memory fits in
MiB), so 24 bytes read per node per decision.  It then writes the chosen
node's three requested values back (12 bytes).  Labels, selectors and
affinities are per-template masks that a launch can hold in registers or
VMEM across decisions; they are not counted.  Nothing here looks at the
program's arrays: a program that moves more bytes than this reads a lower
share, one that moves fewer (keeping the planes in VMEM across the scan)
can pass 100 % only by no longer touching HBM per decision — which would
be the finding, and the function would then need VMEM's bandwidth.
"""

from __future__ import annotations

import json
import os

_BYTES_PER_VALUE = 4
_VALUES_READ_PER_NODE = 6
_VALUES_WRITTEN = 3


def least_bytes_per_placement(config: dict) -> int:
    n = int(config["nodes"]["count"])
    return (n * _VALUES_READ_PER_NODE + _VALUES_WRITTEN) * _BYTES_PER_VALUE


def peak(device_kind: str) -> dict:
    """The peaks of one chip; a device that is not in the table is an
    error, not a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json")
    return table[device_kind]
