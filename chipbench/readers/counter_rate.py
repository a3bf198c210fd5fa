"""Reader ``counter_rate``: window delta of ``counter`` per second of the
window. Arguments: ``counter``, ``scale``."""


def read(ctx: dict, counter: str, scale: float = 1.0):
    if counter not in ctx["counters"]:
        return None
    return ctx["counters"][counter] / ctx["seconds"] * scale
