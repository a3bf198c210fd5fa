"""Reader ``counter_ratio``: window delta of counter ``num`` over that of
counter ``den``. Arguments: ``num``, ``den``, ``scale``."""


def read(ctx: dict, num: str, den: str, scale: float = 1.0):
    c = ctx["counters"]
    if num not in c or not c.get(den):
        return None
    return c[num] / c[den] * scale
