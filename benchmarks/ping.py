"""Ping benchmark — grain-call throughput.

Mirrors /root/reference/test/Benchmarks/Ping/PingBenchmark.cs:35-46: N
EchoGrains, C concurrent in-flight pings, timed loop, prints calls/sec.
Two tiers are measured:

* **host tier** — arbitrary-Python grains through the full silo path
  (client → dispatcher → catalog → activation turn), the analog of the
  reference's measurement;
* **vector tier** — the same no-op echo as a VectorGrain through the
  batched dispatch engine (per-key futures coalesced into per-tick
  kernels), the batched-dispatch acceptance config of BASELINE.md
  ("10k EchoGrains, batched no-op invoke").
"""

import argparse
import asyncio
import json
import time

import numpy as np

if __package__ in (None, ""):
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from orleans_tpu.runtime import ClusterClient, Grain, SiloBuilder


class EchoGrain(Grain):
    """EchoGrain (test/Benchmarks/Grains/PingGrain-style no-op)."""

    async def ping(self, x: int) -> int:
        return x


async def bench_host_tier(n_grains: int, concurrency: int,
                          seconds: float,
                          trace_sample: float | None = None,
                          hot_lane: bool = True,
                          tail: bool = False,
                          metrics: bool = False,
                          profiling: bool = False,
                          slo: bool = False,
                          ledger: bool = False) -> dict:
    """``trace_sample``: None runs untraced (no collector installed);
    a float enables distributed tracing at that head-sampling rate — the
    overhead-tracking variant wired into run_all and the perf floor.
    ``hot_lane=False`` forces every call onto the full messaging path
    (the A/B lever for the hot-lane margin floor). ``tail=True`` turns on
    tail-based retention (record at the head rate, keep/drop at trace
    completion — the worst-case tail-record tax, since fast-clean pings
    buffer, quiesce, and then drop every single trace). ``metrics=True``
    enables the live metrics pipeline — ingest stage instrumentation on
    every message plus the queue/backpressure sampler loop (fast period
    so it actually ticks during the run) — the A/B lever for the
    metrics-overhead floor. ``ledger=True`` enables the cost-attribution
    ledger alone (no metrics registry sampling) — the A/B lever for the
    ledger-overhead floor: every turn pays the charge_turn upsert +
    sketch add."""
    import gc

    # settled-heap start for every A/B pair built on this harness (the
    # bench_profiling_overhead discipline, hoisted): in a long-lived CI
    # process (~700 tests of heap by floor time) a gen-2 collection
    # landing inside ONE side's timed window skews the pair's ratio by
    # 15-30% — far more than any tax the floors guard. collect + FREEZE:
    # the bench allocates hard enough that a gen-2 collection can TRIGGER
    # inside the timed window regardless of phase, and which side draws
    # it shifts with every suite-size change — freezing parks the
    # pre-existing heap in the permanent generation so in-measure
    # collections scan only this bench's young objects.
    gc.collect()
    gc.freeze()
    try:
        return await _bench_host_tier_frozen(
            n_grains, concurrency, seconds, trace_sample, hot_lane,
            tail, metrics, profiling, slo, ledger)
    finally:
        gc.unfreeze()


async def _bench_host_tier_frozen(n_grains, concurrency, seconds,
                                  trace_sample, hot_lane, tail, metrics,
                                  profiling, slo, ledger=False) -> dict:
    b = (SiloBuilder().with_name("ping-silo").add_grains(EchoGrain)
         .with_config(hot_lane_enabled=hot_lane))
    if trace_sample is not None:
        b = b.with_config(trace_enabled=True, trace_sample_rate=trace_sample,
                          trace_tail_enabled=tail)
    if metrics:
        b = b.with_config(metrics_enabled=True, metrics_sample_period=0.2)
    if slo:
        # SLO engine at a fast evaluation cadence on top of metrics (the
        # monitor reads interval diffs of the metrics histograms — the
        # A/B lever for the slo-overhead floor is metrics+slo vs metrics)
        b = b.with_config(metrics_enabled=True, metrics_sample_period=0.2,
                          slo_enabled=True, slo_period=0.1,
                          slo_fast_window=0.5, slo_slow_window=2.0)
    if profiling:
        b = b.with_config(profiling_enabled=True, profiling_window=0.25)
    if ledger:
        b = b.with_config(ledger_enabled=True, ledger_top_k=32)
    silo = b.build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    client.hot_lane_enabled = hot_lane
    if trace_sample is not None:
        client.enable_tracing(trace_sample, tail=tail)
    grains = [client.get_grain(EchoGrain, k) for k in range(n_grains)]

    # warmup: activate every grain
    await asyncio.gather(*(g.ping(0) for g in grains))
    hits0, falls0 = client.hot_hits, client.hot_fallbacks

    calls = 0
    lat: list[float] = []
    stop_at = time.perf_counter() + seconds

    async def worker(wid: int) -> int:
        nonlocal calls
        i = wid
        n = 0
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            await grains[i % n_grains].ping(i)
            lat.append(time.perf_counter() - t0)
            i += concurrency
            n += 1
        return n

    t0 = time.perf_counter()
    counts = await asyncio.gather(*(worker(w) for w in range(concurrency)))
    elapsed = time.perf_counter() - t0
    calls = sum(counts)
    hits = client.hot_hits - hits0
    falls = client.hot_fallbacks - falls0
    await client.close_async()
    await silo.stop()
    return {
        "metric": ("ping_host_profiled_calls_per_sec" if profiling
                   else "ping_host_slo_calls_per_sec" if slo
                   else "ping_host_ledgered_calls_per_sec" if ledger
                   else "ping_host_metered_calls_per_sec" if metrics
                   else "ping_host_calls_per_sec" if trace_sample is None
                   else "ping_host_tail_traced_calls_per_sec" if tail
                   else "ping_host_traced_calls_per_sec"),
        "value": round(calls / elapsed, 1),
        "unit": "calls/sec",
        "vs_baseline": None,
        "extra": {
            "n_grains": n_grains,
            "concurrency": concurrency,
            "calls": calls,
            "trace_sample": trace_sample,
            "hot_lane": hot_lane,
            "hotlane_hit_ratio": round(hits / (hits + falls), 4)
            if hits + falls else None,
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
        },
    }


async def bench_hotlane(n_grains: int = 256, concurrency: int = 100,
                        seconds: float = 2.0,
                        sampled_rate: float | None = 0.01) -> dict:
    """Hot-lane A/B: the same ping workload with the hot lane on vs forced
    onto the full messaging path, reporting the speedup and the hit ratio.
    Asserts the lane actually engaged (a silent 0% hit ratio would report
    a meaningless speedup of ~1.0 and hide a regression).

    Third A/B point (``sampled_rate``): hot lane with a tracing collector
    installed at a realistic sample rate ≪1. The lane rolls the
    head-sample die itself, so the hit ratio must stay ≈ 1 - rate —
    before the sampled-trace lane it collapsed to 0 whenever a collector
    existed, paying full messaging cost for the 99% unsampled majority."""
    hot = await bench_host_tier(n_grains, concurrency, seconds,
                                hot_lane=True)
    cold = await bench_host_tier(n_grains, concurrency, seconds,
                                 hot_lane=False)
    ratio = hot["extra"]["hotlane_hit_ratio"]
    assert ratio is not None and ratio > 0.95, \
        f"hot lane engaged on only {ratio} of warm local calls"
    extra = {
        "messaging_calls_per_sec": cold["value"],
        "speedup": round(hot["value"] / cold["value"], 2),
        "hotlane_hit_ratio": ratio,
        "n_grains": n_grains,
        "concurrency": concurrency,
        "p50_ms": hot["extra"]["p50_ms"],
        "p99_ms": hot["extra"]["p99_ms"],
    }
    if sampled_rate is not None:
        sampled = await bench_host_tier(n_grains, concurrency, seconds,
                                        trace_sample=sampled_rate,
                                        hot_lane=True)
        sratio = sampled["extra"]["hotlane_hit_ratio"]
        assert sratio is not None and sratio > 1 - sampled_rate - 0.05, \
            f"hot lane engaged on only {sratio} of calls at " \
            f"sample_rate={sampled_rate} — the lane is falling back on " \
            f"the unsampled majority"
        extra.update(
            sampled_trace_rate=sampled_rate,
            sampled_calls_per_sec=sampled["value"],
            sampled_hit_ratio=sratio)
    return {
        "metric": "ping_hotlane_calls_per_sec",
        "value": hot["value"],
        "unit": "calls/sec",
        "vs_baseline": None,
        "extra": extra,
    }


async def bench_trace_tail(n_grains: int = 128, concurrency: int = 50,
                           seconds: float = 1.5) -> dict:
    """trace_tail_overhead: tail-record mode (head rate 1.0, every trace
    buffered then dropped as fast-clean) vs untraced ping, as a ratio —
    interpreter-independent like the hot-lane margin. The floor companion
    (tests/test_perf_floors.py) keeps this within 1.5x of the
    trace_overhead budget.

    Both sides run with the hot lane off: full-rate record forces the
    messaging path anyway (a sampled call must carry trace headers), so a
    hot-lane baseline would measure the lane's margin — already floored
    separately — instead of the span-recording + tail-stage tax this
    ratio exists to guard."""
    base = await bench_host_tier(n_grains, concurrency, seconds,
                                 hot_lane=False)
    tail = await bench_host_tier(n_grains, concurrency, seconds,
                                 trace_sample=1.0, tail=True,
                                 hot_lane=False)
    return {
        "metric": "trace_tail_overhead",
        "value": round(tail["value"] / base["value"], 3),
        "unit": "ratio (tail-record / untraced)",
        "vs_baseline": None,
        "extra": {
            "untraced_calls_per_sec": base["value"],
            "tail_traced_calls_per_sec": tail["value"],
            "n_grains": n_grains, "concurrency": concurrency,
        },
    }


async def bench_metrics_overhead(n_grains: int = 128, concurrency: int = 50,
                                 seconds: float = 1.5) -> dict:
    """metrics_overhead: the live metrics pipeline (ingest stage
    histograms on every message + the sampler loop) vs a bare silo, as a
    ratio — interpreter-independent like the tail/hot-lane ratios. The
    floor companion (tests/test_perf_floors.py::test_floor_metrics_overhead)
    keeps this >= 0.85.

    Both sides run with the hot lane off: hot-lane calls collapse the
    whole messaging frame — including every instrumented site — so a
    hot-lane baseline would measure the lane's margin instead of the
    per-message stamp/observe tax this ratio exists to guard."""
    base = await bench_host_tier(n_grains, concurrency, seconds,
                                 hot_lane=False)
    metered = await bench_host_tier(n_grains, concurrency, seconds,
                                    hot_lane=False, metrics=True)
    return {
        "metric": "metrics_overhead",
        "value": round(metered["value"] / base["value"], 3),
        "unit": "ratio (metered / bare)",
        "vs_baseline": None,
        "extra": {
            "bare_calls_per_sec": base["value"],
            "metered_calls_per_sec": metered["value"],
            "n_grains": n_grains, "concurrency": concurrency,
        },
    }


async def bench_ledger_overhead(n_grains: int = 128, concurrency: int = 50,
                                seconds: float = 1.5) -> dict:
    """ledger_overhead: the cost-attribution ledger (per-turn
    charge_turn — one dict upsert + two bounded sketch adds — with the
    metrics registry OFF, its production shape) vs a bare silo, as a
    ratio. Floor companion:
    tests/test_perf_floors.py::test_floor_ledger_overhead (>= 0.85).

    Both sides run with the hot lane off, like the metrics floor: the
    dispatcher epilogue the charge rides must actually execute."""
    base = await bench_host_tier(n_grains, concurrency, seconds,
                                 hot_lane=False)
    ledgered = await bench_host_tier(n_grains, concurrency, seconds,
                                     hot_lane=False, ledger=True)
    return {
        "metric": "ledger_overhead",
        "value": round(ledgered["value"] / base["value"], 3),
        "unit": "ratio (ledgered / bare)",
        "vs_baseline": None,
        "extra": {
            "bare_calls_per_sec": base["value"],
            "ledgered_calls_per_sec": ledgered["value"],
            "n_grains": n_grains, "concurrency": concurrency,
        },
    }


async def bench_slo_overhead(n_grains: int = 128, concurrency: int = 50,
                             seconds: float = 1.5) -> dict:
    """slo_overhead: the SLO monitor (10Hz multi-window burn-rate
    evaluation over interval-diffed registry snapshots) on top of the
    metrics pipeline vs the metrics pipeline alone, as a ratio. The
    monitor adds ZERO hot-path instrumentation — both sides pay the
    identical per-message metrics stamps — so this ratio isolates the
    evaluation loop's own loop-share tax. Floor companion:
    tests/test_perf_floors.py::test_floor_slo_overhead (>= 0.85).

    Both sides run with the hot lane off, like the metrics floor: the
    instrumented sites the monitor's diffs ride must actually execute."""
    base = await bench_host_tier(n_grains, concurrency, seconds,
                                 hot_lane=False, metrics=True)
    slo = await bench_host_tier(n_grains, concurrency, seconds,
                                hot_lane=False, slo=True)
    return {
        "metric": "slo_overhead",
        "value": round(slo["value"] / base["value"], 3),
        "unit": "ratio (metrics+slo / metrics)",
        "vs_baseline": None,
        "extra": {
            "metered_calls_per_sec": base["value"],
            "slo_calls_per_sec": slo["value"],
            "n_grains": n_grains, "concurrency": concurrency,
        },
    }


async def bench_profiling_overhead(n_grains: int = 128,
                                   concurrency: int = 50,
                                   seconds: float = 1.5) -> dict:
    """profiling_overhead: the host-loop occupancy profiler (per-callback
    interposition + category accounting + the flight-recorder ring) vs a
    bare silo, as a ratio — interpreter-independent like the tail/metrics
    ratios. The floor companion
    (tests/test_perf_floors.py::test_floor_profiling_overhead) keeps this
    >= 0.85; the profiling-OFF path installs nothing at all (asserted in
    tests/test_loop_profiler.py), so the off side of this A/B IS the
    unprofiled baseline.

    Both sides run with the hot lane off: hot-lane calls collapse the
    messaging frame and skip most loop callbacks, so a hot-lane baseline
    would measure the lane's margin instead of the per-callback
    interposition tax this ratio exists to guard. A gc.collect before
    each side keeps gen2 pauses from prior silo builds in one process
    from landing asymmetrically on one side."""
    import gc
    gc.collect()
    base = await bench_host_tier(n_grains, concurrency, seconds,
                                 hot_lane=False)
    gc.collect()
    profiled = await bench_host_tier(n_grains, concurrency, seconds,
                                     hot_lane=False, profiling=True)
    return {
        "metric": "profiling_overhead",
        "value": round(profiled["value"] / base["value"], 3),
        "unit": "ratio (profiled / bare)",
        "vs_baseline": None,
        "extra": {
            "bare_calls_per_sec": base["value"],
            "profiled_calls_per_sec": profiled["value"],
            "n_grains": n_grains, "concurrency": concurrency,
        },
    }


async def bench_vector_tier(n_grains: int, rounds: int) -> dict:
    import jax.numpy as jnp

    from orleans_tpu.dispatch import VectorGrain, VectorRuntime, actor_method
    from orleans_tpu.parallel import make_mesh

    class EchoVectorGrain(VectorGrain):
        STATE = {"pings": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"pings": jnp.int32(0)}

        @actor_method(args={"x": (jnp.int32, ())})
        def ping(state, args):
            return {"pings": state["pings"] + 1}, args["x"]

    rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=n_grains)
    rt.table(EchoVectorGrain).ensure_dense(n_grains)
    keys = np.arange(n_grains)
    x = np.arange(n_grains, dtype=np.int32)
    plan = rt.make_dense_plan(EchoVectorGrain, keys)

    out = rt.call_batch(EchoVectorGrain, "ping", keys, {"x": x}, plan=plan)
    np.testing.assert_array_equal(out, x)  # warmup + correctness

    # K scanned rounds per launch + pipelined launches: the per-launch
    # dispatch overhead (not measured on this round's chip) amortizes over K
    # ticks, and bounded in-flight depth keeps round-trips off the
    # critical path (the reference harness's concurrent-in-flight style)
    import jax

    K = 8
    x_rounds = np.broadcast_to(x, (K, n_grains))
    supers = max(1, rounds // K)
    r = rt.call_batch_rounds(EchoVectorGrain, "ping", keys,
                             {"x": x_rounds}, plan=plan,
                             device_results=True)
    jax.block_until_ready(r)  # compile the scan kernel off the clock
    t0 = time.perf_counter()
    inflight = []
    for _ in range(supers):
        r = rt.call_batch_rounds(EchoVectorGrain, "ping", keys,
                                 {"x": x_rounds}, plan=plan,
                                 device_results=True)
        inflight.append(r)
        if len(inflight) >= 4:
            jax.block_until_ready(inflight.pop(0))
    jax.block_until_ready(inflight[-1])
    elapsed = time.perf_counter() - t0
    rounds = supers * K
    calls = rounds * n_grains
    return {
        "metric": "ping_vector_calls_per_sec",
        "value": round(calls / elapsed, 1),
        "unit": "calls/sec",
        "vs_baseline": None,
        "extra": {"n_grains": n_grains, "rounds": rounds,
                  "tick_ms": round(elapsed / rounds * 1e3, 3)},
    }


async def attribution(seconds: float = 3.0, concurrency: int = 100
                      ) -> dict:
    """Host-tier time-split attribution (VERDICT_r4 #5): where the gap
    between this pipeline (~45k calls/sec) and the r3 bare-asyncio
    skeleton (129-175k, commit 06a72b8) actually goes.

    Method: REAL-throughput A/B neutralization — re-measure with one
    component at a time replaced by a no-op — rather than cProfile
    (whose ~4x instrumentation tax distorts sub-30µs turns). Each
    marginal is small and the sum is nowhere near the gap: the cost is
    the ~40 Python frames of full messaging semantics per call
    (addressing, gating, turn ownership, response routing, callback
    registry), each individually a few hundred ns. The in-proc fabric
    does NO serialization (messages pass by reference; hotwire is the
    socket path), so unlike the reference's SocketManager investment
    there is no buffer-management lever here — the remaining 2.5-3x
    needs a native (C) dispatch pipeline, not asyncio tuning."""
    from orleans_tpu.core import message as msg_mod
    from orleans_tpu.observability import stats as stats_mod
    from orleans_tpu.runtime import context as ctx
    from orleans_tpu.runtime import dispatcher as dmod

    async def measure():
        r = await bench_host_tier(1000, concurrency, seconds)
        return r["value"]

    out = {"baseline_calls_per_sec": await measure(), "marginals": {}}

    saved = (stats_mod.StatsRegistry.increment,
             stats_mod.StatsRegistry.observe,
             ctx.RequestContext.import_, ctx.RequestContext.clear,
             dmod.copy_result, msg_mod.Message.is_expired)
    try:
        stats_mod.StatsRegistry.increment = lambda self, n, d=1: None
        stats_mod.StatsRegistry.observe = lambda self, n, v: None
        out["marginals"]["stats"] = await measure()
        ctx.RequestContext.import_ = staticmethod(lambda d: None)
        ctx.RequestContext.clear = staticmethod(lambda: None)
        out["marginals"]["plus_request_context"] = await measure()
        dmod.copy_result = lambda x: x
        out["marginals"]["plus_copy_result"] = await measure()
        msg_mod.Message.is_expired = property(lambda self: False)
        out["marginals"]["plus_expiry_checks"] = await measure()
    finally:
        (stats_mod.StatsRegistry.increment,
         stats_mod.StatsRegistry.observe,
         ctx.RequestContext.import_, ctx.RequestContext.clear,
         dmod.copy_result, msg_mod.Message.is_expired) = saved

    base = out["baseline_calls_per_sec"]
    alln = out["marginals"]["plus_expiry_checks"]
    out["all_neutralized_gain_pct"] = round(100 * (alln - base) / base, 1)
    out["bare_asyncio_ceiling"] = "129k-175k calls/sec (r3, commit 06a72b8)"
    out["conclusion"] = (
        "stats+context+copy+expiry together are ~4%: the remaining gap "
        "to the bare-asyncio ceiling is the Python frame cost of full "
        "messaging semantics (~40 frames/call), with no serialization "
        "on the in-proc path; closing it needs a native dispatch "
        "pipeline, not asyncio tuning. Catalog-first addressing "
        "(dispatcher.send_message) already trimmed the per-call "
        "locator work (+5-15% depending on machine noise).")
    return {"metric": "ping_host_attribution", "value": base,
            "unit": "calls/sec", "vs_baseline": None, "extra": out}


async def run(n_grains: int = 10_000, concurrency: int = 100,
              seconds: float = 5.0, rounds: int = 50,
              host_grains: int | None = None) -> list[dict]:
    results = [
        await bench_host_tier(host_grains or min(n_grains, 1000),
                              concurrency, seconds),
        await bench_hotlane(host_grains or min(n_grains, 256),
                            concurrency, min(seconds, 2.0)),
        await bench_vector_tier(n_grains, rounds),
    ]
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--grains", type=int, default=10_000)
    ap.add_argument("--concurrency", type=int, default=100)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--attribution", action="store_true",
                    help="host-tier time-split attribution instead of "
                         "the throughput benchmarks")
    ap.add_argument("--hotlane", action="store_true",
                    help="hot-lane A/B only: collapsed inline dispatch vs "
                         "the full messaging path, with hit ratio")
    a = ap.parse_args()
    if a.attribution:
        print(json.dumps(asyncio.run(attribution(a.seconds, a.concurrency))))
        return
    if a.hotlane:
        print(json.dumps(asyncio.run(bench_hotlane(
            min(a.grains, 256), a.concurrency, a.seconds))))
        return
    for r in asyncio.run(run(a.grains, a.concurrency, a.seconds, a.rounds)):
        print(json.dumps(r))


if __name__ == "__main__":
    main()
