"""Run every benchmark harness with moderate sizes; one JSON line each.

(The metric of record for the driver stays `python bench.py` at the repo
root — this is the wider surface, mirroring test/Benchmarks/Program.cs's
menu of Ping/MapReduce/Serialization/Transactions harnesses.)
"""

import asyncio
import json

if __package__ in (None, ""):
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import (
    chirper_fanout,
    gauntlet,
    gpstracker_stream,
    ingest_attribution,
    ledger_attribution,
    loop_attribution,
    multiproc_attribution,
    mxu_handler,
    mapreduce,
    ping,
    ping_socket,
    rebalance,
    serialization,
    streams_durable,
    streams_vector,
    transactions,
)


def main() -> None:
    import jax

    from orleans_tpu import native
    from orleans_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())},
                      "wire_codec": native.wire_codec()}))
    for r in asyncio.run(ping.run(n_grains=10_000, concurrency=100,
                                  seconds=3.0, rounds=30)):
        print(json.dumps(r))
    # traced-ping variant: full-rate sampling — the worst-case tracing
    # overhead, tracked in BENCH output against the untraced figure above
    print(json.dumps(asyncio.run(ping.bench_host_tier(
        n_grains=1000, concurrency=100, seconds=3.0, trace_sample=1.0))))
    # tail-record mode overhead as a ratio vs untraced (every fast-clean
    # ping buffers, quiesces, and drops — the tail stage's worst case)
    print(json.dumps(asyncio.run(ping.bench_trace_tail(
        n_grains=128, concurrency=50, seconds=1.5))))
    # hot-lane A/B: collapsed inline dispatch vs the full messaging path,
    # with the hit ratio asserted in the harness (PR 3) + the
    # sampled-trace point at rate 0.01 (the lane rolls the die itself)
    print(json.dumps(asyncio.run(ping.bench_hotlane(
        n_grains=256, concurrency=100, seconds=2.0))))
    # metrics pipeline overhead as a ratio vs a bare silo (stage
    # instrumentation on every message + the sampler loop; CI floor 0.85)
    print(json.dumps(asyncio.run(ping.bench_metrics_overhead(
        n_grains=128, concurrency=50, seconds=1.5))))
    # ingest attribution: socket -> decode/enqueue/queue-wait ->
    # staging/transfer/tick stage breakdown (shares sum to 1.0 of the
    # measured ingest wall — the substrate the ingest-wall work lands
    # on)
    print(json.dumps(asyncio.run(ingest_attribution.run(
        seconds=2.0, concurrency=8))))
    # batched-vs-per-frame ingest hand-off A/B (one decode_frames +
    # deliver_batch vs N decode_message + deliver for identical bytes;
    # CI floor 1.5x in test_floor_batched_ingest, measured 3-5x)
    print(json.dumps(asyncio.run(ingest_attribution.run_ab(
        n_msgs=512, seconds=1.5))))
    # loop attribution: per-category occupancy of the silo's event loop
    # at closed-loop saturation (c=32 mixed host+vector over TCP) — the
    # measured split behind "residual queue-wait is loop contention":
    # turns vs the device tick's loop side (tick_schedule) vs pump vs
    # observability vs idle, shares summing to ~1.0 of loop wall time
    print(json.dumps(asyncio.run(loop_attribution.run(
        seconds=2.0, concurrency=32))))
    # multi-loop silo A/B (ISSUE 11): 1 vs 2 ingress pump loops on
    # identical mixed TCP traffic over 2 gateway connections — the
    # main-loop pump share sheds onto the shard threads (structural
    # signal, measured ~0.55-0.72x); the msgs/sec ratio is only
    # meaningful on a genuinely multi-core runner (>=1.7x target,
    # gated in test_floor_multiloop by a parallelism probe)
    print(json.dumps(asyncio.run(loop_attribution.run_multiloop_ab(
        seconds=2.0, concurrency=32))))
    # sharded egress A/B (ISSUE 15): egress_shards 0 vs 2 on identical
    # mixed TCP traffic over 2-ingress-loop silos — the main loop's
    # "egress" occupancy share (response encode + sender/route writes)
    # sheds onto the shard loops (structural signal, acceptance <=0.5x;
    # measured ~0.0-0.1x); msgs/sec ratio probe-gated like multiloop
    print(json.dumps(asyncio.run(loop_attribution.run_egress_shards_ab(
        seconds=2.0, concurrency=32))))
    # multi-process silos A/B (ISSUE 18): worker_procs 1 vs 2 on
    # identical mixed TCP traffic to the SO_REUSEPORT gateway — the
    # main process's pump+egress share collapses to ~0 (structural,
    # measured ~0.01-0.06x) and clients spread over both workers;
    # msgs/sec ratio probe-gated like multiloop (separate GILs only pay
    # off on genuinely parallel cores — parallel_capacity is stamped
    # into the payload)
    print(json.dumps(asyncio.run(loop_attribution.run_multiproc_ab(
        seconds=2.0, concurrency=32))))
    # multi-process observability A/B (ISSUE 20): bare vs full stack
    # (profiling + metrics + tracing + ledger + management) on identical
    # worker_procs=2 traffic — the overhead ratio (CI floor 0.85 in
    # test_floor_multiproc_observability), plus the cluster critical
    # path (merged shares_sum ~1.0), per-worker ledger attribution, and
    # the traced probe's cross-process waterfall coverage (>= 0.95)
    print(json.dumps(asyncio.run(
        multiproc_attribution.run_observability_ab(
            seconds=2.0, concurrency=32))))
    # deliberate client-side batching vs per-message senders, vector-only
    # (isolates the sender-side win from the mixed harness's host/vec
    # mix shift; measured ~1.5-1.8x, CI floor 1.2x)
    print(json.dumps(asyncio.run(ingest_attribution.run_call_batch_ab(
        seconds=1.5))))
    # profiler overhead as a ratio vs a bare silo (per-callback
    # interposition + category accounting; CI floor 0.85)
    print(json.dumps(asyncio.run(ping.bench_profiling_overhead(
        n_grains=128, concurrency=50, seconds=1.5))))
    # SLO monitor overhead as a ratio vs metrics-only (multi-window
    # burn-rate evaluation rides snapshot diffs; CI floor 0.85)
    print(json.dumps(asyncio.run(ping.bench_slo_overhead(
        n_grains=128, concurrency=50, seconds=1.5))))
    # cost-ledger overhead as a ratio vs a bare silo (ISSUE 17:
    # per-turn charge + sketch update on every message; CI floor 0.85)
    print(json.dumps(asyncio.run(ping.bench_ledger_overhead(
        n_grains=128, concurrency=50, seconds=1.5))))
    # cost-attribution accuracy (ISSUE 17): Zipf-skewed 2-silo drive
    # scored against client-side ground truth — does the merged cluster
    # ledger name the hot key / hot tenant, and what fraction of the
    # host bill do the bounded top-k burners explain?
    print(json.dumps(asyncio.run(ledger_attribution.run(
        seconds=2.0, concurrency=32))))
    # traffic-shape gauntlet (ISSUE 12): flash crowd / hot-key Zipf /
    # diurnal ramp / churn storm over real TCP, each emitting SLO
    # VERDICTS (objective met/breached, burn rates, budget burned,
    # time-to-detect) instead of raw msgs/sec — plus the QoS invariant
    # (probe RTT bounded, zero false suspicions while app traffic sheds)
    for r in asyncio.run(gauntlet.run(short=True)):
        print(json.dumps(r))
    print(json.dumps(asyncio.run(mapreduce.run())))
    # MapReduce-over-actors A/B (ISSUE 13): bulk collectives
    # (broadcast_actors + reduce_actors) vs one RPC per (block, word) /
    # (chirp, follower) edge on identical traffic — CI floor 3x at
    # fan-out >= 64 in test_floor_map_actors, measured ~10-13x in-proc
    # (symmetric warmup: steady-state dispatch, compile excluded)
    print(json.dumps(asyncio.run(mapreduce.run_ab())))
    print(json.dumps(asyncio.run(chirper_fanout.run_ab())))
    # Device-stream A/B (ISSUE 16): per-subscriber delivery RPCs vs the
    # DeviceStreamProvider's compiled edge-list fan-out on identical
    # edge traffic — CI floor 3x at fan-out >= 64 in
    # test_floor_device_streams, measured ~8-10x in-proc
    print(json.dumps(asyncio.run(chirper_fanout.run_ab_device())))
    for r in serialization.run():
        print(json.dumps(r))
    print(json.dumps(asyncio.run(transactions.run(seconds=3.0))))
    print(json.dumps(asyncio.run(transactions.run(seconds=3.0,
                                                  concurrency=32))))
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        for r in asyncio.run(ping_socket.run(concurrency=64, seconds=3.0,
                                             n_grains=200, tmpdir=td)):
            print(json.dumps(r))
    print(json.dumps(chirper_fanout.run(seconds=5.0)))
    print(json.dumps(mxu_handler.run(n_actors=512, fuse=2, seconds=1.0,
                                     reps=1)))
    for r in asyncio.run(gpstracker_stream.run(seconds=2.0)):
        print(json.dumps(r))
    print(json.dumps(asyncio.run(streams_vector.run(n_keys=50_000))))
    for r in asyncio.run(streams_durable.run(seconds=3.0)):
        print(json.dumps(r))
    print(json.dumps(asyncio.run(rebalance.run(n_grains=32, concurrency=16,
                                               seconds=1.0))))


if __name__ == "__main__":
    main()
