"""Reader ``hbm_roofline``: the bytes the ticks of the traced slice needed
(messages ticked x bytes per message from the shapes,
``chipbench/kernel_bytes.py``) over the device time of the slice's
operations, as a share of the device's peak HBM bandwidth
(``chipbench/peaks.py``). Bound by bandwidth: the tick does no arithmetic
to speak of. Not measured on the CPU platform."""


def read(ctx: dict):
    tr, sl, peaks = ctx.get("trace"), ctx.get("slice"), ctx.get("peaks")
    if not tr or not sl or not peaks or tr["op_seconds"] <= 0:
        return None
    msgs = sl["counters"].get("ingest.messages")
    if not msgs:
        return None
    achieved = msgs * ctx["bytes_per_message"]["total"] / tr["op_seconds"]
    return 100.0 * achieved / peaks["hbm_bytes_per_s"]
