"""Reader ``histogram_mean_or_zero``: ``histogram_mean`` over the samples'
own count, for a histogram that may take no sample in a window — the mean
wait of the messages that waited, 0 where none did. A histogram the
program does not have (an older program, or a cell in which it was never
observed since the silo started) still reads nothing.
Arguments: ``stats`` (list of histogram names), ``scale``.
"""


def read(ctx: dict, stats: list, scale: float = 1.0):
    hs = [ctx["histograms"].get(s) for s in stats]
    if any(h is None for h in hs):
        return None
    n = sum(h["count"] for h in hs)
    return sum(h["sum"] for h in hs) / n * scale if n else 0.0
