"""Reader ``histogram_prefix_share``: window sums of every host-clock
histogram whose name starts with one of ``prefixes`` (e.g. the loop
account's seconds in no stage, ``loop.other.``), over the seconds of the
window, times ``scale`` (100 for per cent). Where no histogram matches (a
program without the loop account) there is nothing to read."""


def read(ctx: dict, prefixes: list, scale: float = 1.0):
    hs = [h for name, h in ctx["histograms"].items()
          if name.startswith(tuple(prefixes))]
    if not hs or not ctx["seconds"]:
        return None
    return sum(h["sum"] for h in hs) / ctx["seconds"] * scale
