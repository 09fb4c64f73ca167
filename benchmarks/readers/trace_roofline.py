"""``trace_roofline``: the least time the chip could take for the traced
span's placements (``work.least_bytes_per_placement`` over the device's
peak memory bandwidth from ``peaks.json``) as a share of the device time
the matched program took, in per cent.  Memory-bound: the scan does a
handful of integer operations per byte it reads."""

import work
from readers.trace_op_time import launch_pods, matched


def read(args: dict, ctx: dict):
    got = matched(args, ctx)
    pods = launch_pods(args, ctx)
    if not got or not got[1] or not pods:
        return None
    seconds = got[1]
    peak = work.peak(ctx["device_kind"])["hbm_bytes_per_s"]
    least_s = work.least_bytes_per_placement(ctx["config"]) * pods / peak
    return 100.0 * least_s / seconds
