"""``seconds_per_kpod``: summed seconds of the ``terms`` inside the window
per 1,000 pods bound in it, in milliseconds (each term's ``scale`` turns
its family's unit into seconds)."""

from readers import terms_sum


def read(args: dict, ctx: dict):
    seconds = terms_sum(args["terms"], ctx)
    if seconds is None or not ctx["pods_bound"]:
        return None
    return seconds / ctx["pods_bound"] * 1e6
