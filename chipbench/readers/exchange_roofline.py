"""Reader ``exchange_roofline``: the bytes the slice's deliveries had to
move (``exchange.delivered`` in the slice x bytes a delivery,
``chipbench/exchange_bytes.py``, from the shapes of ``grain``'s ``method``
in app ``app``) over the device time of the exchange's programs in the
slice, as a share of the device's peak HBM bandwidth
(``chipbench/peaks.py``). The exchange's programs are the device
operations that started inside a host span called ``span``
(``otpu:exchange``: the stage holds the tick fence and waits for every
round it launches, so nothing else runs on the device inside it), a device
plane in the mean. None without a trace, a span or a delivery; not
measured on the CPU platform. Arguments: ``span``, ``app``, ``grain``,
``method``."""

import numpy as np

import exchange_bytes
import exchange_trace
from loadgen import load_by_name


def read(ctx: dict, span: str, app: str, grain: str, method: str):
    tr, sl, peaks = ctx.get("trace"), ctx.get("slice"), ctx.get("peaks")
    if not tr or not sl or not peaks:
        return None
    delivered = sl["counters"].get("exchange.delivered")
    spans = sorted(exchange_trace.host_intervals(span))
    per_plane = exchange_trace.device_ops(cpu_fallback=False)
    if not delivered or not spans or not per_plane:
        return None
    s0 = np.array([s for s, _e in spans])
    s1 = np.array([e for _s, e in spans])
    secs = 0.0
    for ev in per_plane:
        start = np.array([e[1] for e in ev])
        dur = np.array([e[2] for e in ev])
        i = np.searchsorted(s0, start, side="right") - 1
        inside = (i >= 0) & (start < s1[np.maximum(i, 0)])
        secs += float(dur[inside].sum()) / 1e9
    secs /= len(per_plane)
    if secs <= 0:
        return None
    per = exchange_bytes.delivery_bytes(
        load_by_name("apps", app).GRAINS[grain], method)["total"]
    return 100.0 * delivered * per / secs / peaks["hbm_bytes_per_s"]
