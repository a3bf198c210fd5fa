"""Reader ``histogram_mean``: window sum of one or more host-clock
histograms divided by their sample count, or by a counter's window delta
where ``per_counter`` is given (e.g. seconds of staging per tick).
Arguments: ``stats`` (list of histogram names), ``scale``, ``per_counter``.
"""


def read(ctx: dict, stats: list, scale: float = 1.0,
         per_counter: str | None = None):
    hs = [ctx["histograms"].get(s) for s in stats]
    if any(h is None for h in hs):
        return None
    total = sum(h["sum"] for h in hs)
    n = ctx["counters"].get(per_counter, 0) if per_counter \
        else sum(h["count"] for h in hs)
    return total / n * scale if n else None
