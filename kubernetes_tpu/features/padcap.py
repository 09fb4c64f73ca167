"""Monotonic compile capacities for content-sized table axes.

The solver's scan is specialized on the shapes of its signature/group
tables (inter-pod affinity sigs, PD volume widths, volume-zone and
service-affinity groups, selector/spread/avoid groups).  Those counts vary
freely with live batch content, and every new count is a fresh XLA
compile — measured as multi-second stalls on the scheduling clock at
density rates.  The vocabulary spaces (features.vocab) already solve this
for string features by growing capacity monotonically in buckets; this
module applies the same discipline to the table axes: each axis is padded
up to the largest pow2 size this scheduler instance has ever seen, so a
long-running daemon converges on one compiled program per (chunk, cluster)
shape.

Padded rows/columns are inert by construction: no pod index references
them, mask rows pad with "no constraint" (True), count/score rows with
zero, key rows with -1.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# axis name -> list of (container, field, axis, fill).  Container "" = the
# PodBatch itself, "aff"/"volsvc" its nested tables.  Every field listed
# for one axis name shares that axis size by construction.
AXES: dict[str, list[tuple[str, str, int, object]]] = {
    "aff_sm": [("aff", "match_key", 0, -1), ("aff", "match_cnt", 0, 0.0),
               ("aff", "match_total", 0, 0.0), ("aff", "match_src", 1, False),
               ("aff", "aff_need", 1, False), ("aff", "aff_self", 1, False),
               ("aff", "anti_need", 1, False), ("aff", "pref_w", 1, 0.0)],
    "aff_sd": [("aff", "decl_key", 0, -1), ("aff", "decl_reach", 0, False),
               ("aff", "decl_match", 1, False), ("aff", "decl_src", 1, False)],
    "aff_sy": [("aff", "sym_key", 0, -1), ("aff", "sym_w", 0, 0.0),
               ("aff", "sym_cnt", 0, 0.0), ("aff", "sym_match", 1, False),
               ("aff", "sym_src", 1, False)],
    "vs_we": [("volsvc", "pd_pod_ebs", 1, False),
              ("volsvc", "pd_node_ebs", 1, False)],
    "vs_wg": [("volsvc", "pd_pod_gce", 1, False),
              ("volsvc", "pd_node_gce", 1, False)],
    "vs_vz": [("volsvc", "vz_mask", 0, True)],
    "vs_sa": [("volsvc", "sa_mask", 0, True)],
    "vs_saa_g": [("volsvc", "saa_src", 1, False),
                 ("volsvc", "saa_cnt", 1, 0.0),
                 ("volsvc", "saa_num", 0, 0.0)],
    "vs_saa_d": [("volsvc", "saa_cnt", 2, 0.0)],
    "b_sel": [("", "sel_required", 0, True),
              ("", "sel_pref_counts", 0, 0)],
    "b_spread": [("", "spread_node_counts", 0, 0.0),
                 ("", "spread_zone_counts", 0, 0.0),
                 ("", "spread_has_zones", 0, False),
                 ("", "spread_incr", 1, False)],
    "b_avoid": [("", "avoid_rows", 0, False)],
    "b_nztmpl": [("", "nz_templates", 0, 0)],
}

# Axes where an EMPTY table is a semantic sentinel (feature disabled for
# this batch — the scan's over-cap fallback), not a size-0 count:
# padding it up would fabricate live rows.
SKIP_EMPTY_AXES = frozenset({"b_nztmpl"})


def pow2(x: int) -> int:
    """Next power of two ≥ max(x, 1) — the bucket size for every
    content-sized axis (bounds distinct compiled shapes at log2)."""
    return 1 << (max(x, 1) - 1).bit_length()


def pad_rows_pow2(a: np.ndarray, fill=0) -> np.ndarray:
    """Pad dim 0 to its pow2 bucket with `fill` rows."""
    return _pad_axis(a, 0, pow2(a.shape[0]), fill)


def stack_pad(rows: list, n: int, fill, dtype=bool) -> np.ndarray:
    """Stack [*, n] rows padded to a pow2 row count with `fill` rows."""
    g = pow2(len(rows))
    out = np.full((g, n), fill, dtype)
    if rows:
        out[:len(rows)] = np.stack(rows)
    return out


def pad1(vals, size: int, fill, dtype) -> np.ndarray:
    """A 1-D array of `size` filled with `fill` beyond len(vals)."""
    out = np.full(size, fill, dtype)
    vals = np.asarray(vals, dtype)[:size]
    out[:len(vals)] = vals
    return out


def _pad_axis(a: np.ndarray, axis: int, size: int, fill) -> np.ndarray:
    if a.shape[axis] >= size:
        return a
    shape = list(a.shape)
    shape[axis] = size
    out = np.full(shape, fill, a.dtype)
    sl = tuple(slice(0, s) for s in a.shape)
    out[sl] = a
    return out


def apply_caps(batch, caps: dict[str, int]):
    """Pad `batch`'s content-sized axes up to the monotonic caps, growing
    the caps to cover this batch.  Returns a (possibly replaced) batch;
    untouched arrays are shared, not copied."""
    batch_updates: dict = {}
    aff_updates: dict = {}
    vs_updates: dict = {}
    for axis_name, fields in AXES.items():
        container0, field0, axis0, _ = fields[0]
        src0 = batch if container0 == "" else getattr(batch, container0)
        current = getattr(src0, field0).shape[axis0]
        if current == 0 and axis_name in SKIP_EMPTY_AXES:
            continue
        cap = max(caps.get(axis_name, 1), current)
        caps[axis_name] = cap
        if cap == current:
            continue
        for container, field, axis, fill in fields:
            src = batch if container == "" else getattr(batch, container)
            updates = (batch_updates if container == "" else
                       aff_updates if container == "aff" else vs_updates)
            # A field listed under two axes (saa_cnt: group AND domain)
            # must pad its already-padded copy, not the original.
            arr = updates.get(field, getattr(src, field))
            updates[field] = _pad_axis(arr, axis, cap, fill)
    if aff_updates:
        batch_updates["aff"] = batch.aff._replace(**aff_updates)
    if vs_updates:
        batch_updates["volsvc"] = batch.volsvc._replace(**vs_updates)
    if batch_updates:
        batch = dataclasses.replace(batch, **batch_updates)
    return batch
