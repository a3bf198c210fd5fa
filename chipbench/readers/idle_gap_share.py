"""Reader ``idle_gap_share``: seconds of device idle gap that the trace
reduction put under ``label`` (``chipbench/trace_reduce.py``: the innermost
host event spanning the gap, else ``unattributed``) over the idle seconds
of the traced slice, in per cent. The reduction keeps the ten largest
labels: one that is not among them is below the tenth and reads 0."""


def read(ctx: dict, label: str):
    tr = ctx.get("trace")
    if not tr:
        return None
    idle = tr["window_s"] - tr["busy_s"]
    if idle <= 0:
        return None
    return 100.0 * dict(tr["idle_gaps"]).get(label, 0.0) / idle
