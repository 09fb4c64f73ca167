"""``counter_delta``: how much the ``terms`` grew inside the window."""

from readers import terms_sum


def read(args: dict, ctx: dict):
    return terms_sum(args["terms"], ctx)
