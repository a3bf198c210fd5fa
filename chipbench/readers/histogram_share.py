"""Reader ``histogram_share``: window sum of one host-clock histogram over
the seconds of the window (e.g. seconds inside write-behind flushes per
second of wall). Arguments: ``stat``, ``scale`` (100 for per cent). A
span held across awaits sums wall time, so the share is of the wall
clock, not of one thread's CPU."""


def read(ctx: dict, stat: str, scale: float = 1.0):
    h = ctx["histograms"].get(stat)
    if h is None or not ctx["seconds"]:
        return None
    return h["sum"] / ctx["seconds"] * scale
