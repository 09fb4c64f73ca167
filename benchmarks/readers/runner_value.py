"""``runner_value``: a number the runner or the traffic kind measured
itself (``key``), times ``scale``."""


def read(args: dict, ctx: dict):
    value = ctx["runner"].get(args["key"])
    return None if value is None else value * args.get("scale", 1.0)
