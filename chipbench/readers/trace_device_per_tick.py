"""Reader ``trace_device_per_tick``: seconds of device operations in the
traced slice (profiler trace, summed op durations) over the engine ticks
counted in the same slice. Arguments: ``scale`` (1e6 for us/tick)."""


def read(ctx: dict, scale: float = 1.0):
    tr, sl = ctx.get("trace"), ctx.get("slice")
    if not tr or not sl or not sl["counters"].get("rt.ticks"):
        return None
    return tr["op_seconds"] / sl["counters"]["rt.ticks"] * scale
