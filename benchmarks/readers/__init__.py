"""Per-layer arithmetic: one module per word of the vocabulary.

``metrics/<name>.json`` names its ``arithmetic``; the runner imports
``readers/<arithmetic>.py`` and calls ``read(args, ctx)``.  A reader that
finds nothing to read returns None and the metric is left out of the
line; it never returns 0 for a share of a roofline or of a peak.

``ctx``: ``daemon`` / ``apiserver`` = (before, after) parsed /metrics
pages taken at window open and close; ``runner`` = the runner's own
values; ``pods_bound`` = binds the observer saw inside the window;
``trace`` = ``reduce_trace.reduce`` of the traced span (None untraced);
``trace_pods`` = pods the daemon placed inside the traced span and
``pods_per_launch`` = pods per device launch there (its own counters,
read at the span's two ends: the trace readers divide traced time by
traced work); ``config``; ``device_kind``.
"""

from rig import family_sum


def term_delta(term: dict, ctx: dict) -> float | None:
    """After minus before of one term: ``{"runner": key}`` or
    ``{"process", "family", "labels", "scale", "absent"}``.  A labelled
    counter has no row until it first counts: ``"absent": 0`` reads such a
    family as 0 on a page that was read (never on an empty page)."""
    if "runner" in term:
        value = ctx["runner"].get(term["runner"])
        return None if value is None else value * term.get("scale", 1.0)
    before, after = ctx[term["process"]]
    b = family_sum(before, term["family"], term.get("labels"))
    a = family_sum(after, term["family"], term.get("labels"))
    if a is None:
        if not after or "absent" not in term:
            return None
        a = term["absent"]
    return (a - (b or 0.0)) * term.get("scale", 1.0)


def terms_sum(terms: list, ctx: dict) -> float | None:
    parts = [term_delta(t, ctx) for t in terms]
    if not parts or any(p is None for p in parts):
        return None
    return sum(parts)
