"""``ratio``: sum of ``num`` terms over sum of ``den`` terms, times
``scale``; ``den`` may be the string ``"pods_bound"``."""

from readers import terms_sum


def read(args: dict, ctx: dict):
    num = terms_sum(args["num"], ctx)
    den = ctx["pods_bound"] if args["den"] == "pods_bound" \
        else terms_sum(args["den"], ctx)
    if num is None or not den:
        return None
    return num / den * args.get("scale", 1.0)
