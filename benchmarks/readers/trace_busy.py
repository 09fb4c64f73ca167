"""``trace_busy``: union of the device-operation intervals over the traced
span, in per cent."""


def read(args: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * trace["busy_s"] / trace["window_s"]
