"""Reader ``trace_op_per_count``: seconds, in the traced slice, of the
device operations whose name contains ``op`` (``all-to-all``: the
exchange's collective, found by its HLO name), a device plane in the mean,
over the slice's delta of counter ``per_counter``. Read from the run's own
trace (``exchange_trace``), because ``ctx["trace"]`` names the ten largest
operations only. None without a trace, without such an operation or where
the counter did not move (a program without it). Arguments: ``op``,
``per_counter``, ``scale``."""

import exchange_trace


def read(ctx: dict, op: str, per_counter: str, scale: float = 1.0):
    tr, sl = ctx.get("trace"), ctx.get("slice")
    n = sl["counters"].get(per_counter) if sl else None
    if not tr or not n:
        return None
    per_plane = exchange_trace.device_ops(cpu_fallback=not ctx.get("peaks"))
    secs = [sum(e[2] for e in ev if op in e[0]) / 1e9 for ev in per_plane]
    if not per_plane or not any(secs):
        return None
    return sum(secs) / len(per_plane) / n * scale
