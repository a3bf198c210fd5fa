"""Shared device-time attribution for the device-tier benchmarks.

A single blocking dispatch cannot separate the host's per-dispatch cost
from device execution. Measure BLOCKING calls at two fusion levels S_A
and S_B = 2*S_A and fit ``T(S) = overhead + S * device_time``: the slope
is device execution per fused unit, the intercept is the per-dispatch
host cost. The intercept on this round's chip machine is not measured
yet; until it is, keep S_A >= 8 so the slope has a lever arm well above
timer noise. (A profiler trace gives both directly and replaces this fit
once the benchmark PR lands — ROADMAP S0.)

Peaks live in ONE table, :data:`DEVICE_PEAKS`, keyed by jax's
``device_kind``, with their source. A device that is not in the table is
an error, never a default; the CPU platform has no device peaks at all,
so a run there reports its roofline share as not measured.
"""

from __future__ import annotations

import time

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
DEVICE_PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 819 GB/s HBM per chip",
    },
}


def device_peaks(device=None) -> dict | None:
    """Peaks of the device this process computes on (default: jax's
    first device). None on the CPU platform — nothing timed there is a
    device metric. KeyError for an accelerator the table lacks."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device.device_kind!r}: "
            f"add it to benchmarks/attribution.py DEVICE_PEAKS with its "
            f"source") from None


def two_point_fit(run_blocking, s_a: int, s_b: int, reps: int = 3
                  ) -> dict:
    """``run_blocking(s)`` executes ONE blocking dispatch fusing ``s``
    units and returns its wall seconds. Returns the fitted per-unit
    device seconds and per-dispatch overhead (medians over ``reps``)."""
    def med(s: int) -> float:
        ts = sorted(run_blocking(s) for _ in range(reps))
        return ts[len(ts) // 2]

    med(s_a)  # warm both shapes before timing
    med(s_b)
    t_a, t_b = med(s_a), med(s_b)
    per_unit = (t_b - t_a) / (s_b - s_a)
    overhead = t_a - s_a * per_unit
    return {
        "fit_s_a": s_a, "fit_s_b": s_b,
        "t_a_ms": round(t_a * 1e3, 3), "t_b_ms": round(t_b * 1e3, 3),
        "device_unit_ms": round(per_unit * 1e3, 4),
        "dispatch_overhead_ms": round(overhead * 1e3, 3),
        "device_unit_s": per_unit,
    }


def roofline_fields(fit: dict, bytes_per_unit: float | None = None,
                    flops_per_unit: float | None = None) -> dict:
    """Achieved fraction of the relevant peak from the fitted device time
    per unit. ``bytes_per_unit``/``flops_per_unit`` are the workload's
    model traffic/compute per fused unit."""
    out: dict = {}
    per = fit["device_unit_s"]
    peaks = device_peaks()
    if peaks is None:
        out["roofline_note"] = ("not measured: this run was on the cpu "
                                "platform, which has no device peaks")
        return out
    if per <= 0:
        out["roofline_note"] = ("fit slope <= 0: device time below timer "
                                "noise at this fusion level")
        return out
    if bytes_per_unit is not None:
        bps = bytes_per_unit / per
        out["model_bytes_per_unit"] = int(bytes_per_unit)
        out["achieved_gb_per_s"] = round(bps / 1e9, 1)
        out["pct_of_peak_bw"] = round(100 * bps / peaks["hbm_bytes_per_s"], 1)
    if flops_per_unit is not None:
        fps = flops_per_unit / per
        out["model_flops_per_unit"] = int(flops_per_unit)
        out["achieved_tflops"] = round(fps / 1e12, 2)
        out["pct_of_mxu_peak"] = round(
            100 * fps / peaks["bf16_flops_per_s"], 1)
    return out


def timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def staged_cache(make):
    """Lazily-cached staged payload buffers for the blocking fit:
    ``get(k)`` builds via ``make(k)`` once per k. A bare
    ``dict.setdefault(k, make(k))`` would EAGER-evaluate make on every
    call — host RNG + a device upload overlapping the timed launch —
    which silently biased early fits; this helper is the one correct
    implementation."""
    bufs: dict = {}

    def get(k: int):
        if k not in bufs:
            bufs[k] = make(k)
        return bufs[k]

    return get
