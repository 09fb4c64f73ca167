"""``trace_op_time``: device seconds of the events on trace line ``line``
whose name matches ``match`` (a regular expression), divided by ``per``
(``"launch_pod"``: the matched events' count times the pods the daemon
placed per launch inside the traced span, so a launch cut by the span's
edge does not move it; ``"pod"``: pods placed in the traced span; ``"span"``:
the traced span's length; absent: 1), times ``scale``."""

import re


def matched(args: dict, ctx: dict):
    """``(events, seconds)`` of the matching names, or None."""
    trace = ctx.get("trace")
    if not trace:
        return None
    names = trace["lines"].get(args["line"])
    if not names:
        return None
    pattern = re.compile(args["match"])
    hits = [rec for name, rec in names.items() if pattern.search(name)]
    if not hits:
        return None
    return sum(n for n, _s in hits), sum(s for _n, s in hits)


def launch_pods(args: dict, ctx: dict):
    """Pods the matched launches placed: their count times the traced
    span's pods per launch."""
    got = matched(args, ctx)
    if got is None or not ctx.get("pods_per_launch"):
        return None
    return got[0] * ctx["pods_per_launch"]


def read(args: dict, ctx: dict):
    got = matched(args, ctx)
    if got is None:
        return None
    seconds = got[1]
    per = args.get("per")
    if per == "launch_pod":
        den = launch_pods(args, ctx)
    elif per == "pod":
        den = ctx.get("trace_pods")
    elif per == "span":
        den = ctx["trace"]["window_s"]
    else:
        den = 1.0
    if not den:
        return None
    return seconds / den * args.get("scale", 1.0)
