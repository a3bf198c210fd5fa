"""Reader ``histogram_count``: samples the window added to every histogram
whose name starts with one of ``prefixes`` (e.g. the compiles the program
booked to a family of stages, ``compile.flush.``), as a count, or per unit
of a counter's window delta where ``per_counter`` is given (times
``scale``). A family with no histogram yet counts 0, so ``present`` names a
histogram that proves the program books to such families at all: without
it (a program from before the stage spans) there is nothing to read."""


def read(ctx: dict, prefixes: list, present: str | None = None,
         per_counter: str | None = None, scale: float = 1.0):
    hs = ctx["histograms"]
    if present is not None and present not in hs:
        return None
    n = sum(h["count"] for name, h in hs.items()
            if name.startswith(tuple(prefixes)))
    if per_counter is None:
        return n * scale
    den = ctx["counters"].get(per_counter)
    return n / den * scale if den else None
