"""Benchmark harnesses run end-to-end with tiny sizes (the reference ships
its harnesses inside the test tree too — test/Benchmarks builds against
TestCluster). Correctness assertions inside each harness (echo values,
word-count table, balance conservation) are the point; speed is not."""

from benchmarks import chirper_fanout, gpstracker_stream, mapreduce, ping, \
    serialization, streams_durable, transactions


def _check(r: dict) -> None:
    assert set(r) >= {"metric", "value", "unit", "vs_baseline"}
    assert r["value"] > 0


async def test_ping_harness():
    for r in await ping.run(n_grains=64, concurrency=8, seconds=0.3,
                            rounds=3, host_grains=16):
        _check(r)


async def test_ingest_attribution_harness():
    """ISSUE 6 acceptance (updated for the ISSUE 7 batched pipeline):
    the ingest-attribution point reports a per-stage breakdown whose
    shares sum to ≈1.0 of the measured ingest wall time, covering both
    the host stages (decode — one timed observation per decode_frames
    pass on the batched path — enqueue/queue_wait per message) and the
    device stages (staging/transfer/tick, counted per vector batch)."""
    from benchmarks import ingest_attribution

    r = await ingest_attribution.run(seconds=0.5, concurrency=8,
                                     n_grains=16, n_keys=16)
    _check(r)
    shares = r["extra"]["stage_shares"]
    assert set(shares) == {"decode", "queue_wait", "staging", "transfer",
                           "tick"}
    assert abs(sum(shares.values()) - 1.0) < 0.01
    counts = r["extra"]["stage_counts"]
    # batched ingress: decode is timed once per decode_frames pass (the
    # whole socket read is one C call — stage SUMS stay truthful, which
    # is what the share math divides), while every message still records
    # one queue_wait sample (host turn or vector item) on the owning silo
    assert 1 <= counts["decode"] <= counts["queue_wait"]
    assert counts["queue_wait"] >= r["extra"]["calls"]
    assert counts["tick"] >= 1 and counts["staging"] == counts["tick"]
    assert r["extra"]["frames_decoded"] >= r["extra"]["calls"]


async def test_ingest_ab_harness():
    """ISSUE 7: the batched-vs-per-frame hand-off A/B runs end to end and
    reports both sides' throughput (the ratio floor lives in
    test_perf_floors — this only proves the harness)."""
    from benchmarks import ingest_attribution

    r = await ingest_attribution.run_ab(n_msgs=64, seconds=0.3)
    _check(r)
    assert r["extra"]["per_frame_msgs_per_sec"] > 0
    assert r["extra"]["batched_msgs_per_sec"] > 0


async def test_multiloop_ab_harness():
    """ISSUE 11: the 1-vs-2 ingress-loop A/B runs end to end and
    reports both sides plus the main-loop pump-share ratio and the
    per-ingress-loop profiles (the ratio floor lives in
    test_perf_floors — this only proves the harness)."""
    from benchmarks import loop_attribution

    r = await loop_attribution.run_multiloop_ab(seconds=0.5, concurrency=8)
    _check(r)
    assert r["extra"]["single"]["calls_per_sec"] > 0
    assert r["extra"]["multi"]["calls_per_sec"] > 0
    assert "main_loop_pump_share_ratio" in r["extra"]
    profs = r["extra"]["multi"]["ingress_loop_profiles"]
    assert profs and any(p["frames"] > 0 for p in profs)


async def test_multiproc_ab_harness():
    """ISSUE 18: the worker_procs 1-vs-2 A/B runs end to end — real
    forked SO_REUSEPORT workers, shm staging rings — and reports both
    sides plus the structural signals the floor asserts on: the main
    process's pump+egress share ratio and the per-worker client-route
    spread (the ratio floor lives in test_perf_floors — this proves the
    harness on any box, including single-core ones where the floor's
    core gate skips)."""
    from benchmarks import loop_attribution

    r = await loop_attribution.run_multiproc_ab(seconds=0.5, concurrency=8)
    _check(r)
    x = r["extra"]
    assert x["single"]["calls_per_sec"] > 0
    assert x["multi"]["calls_per_sec"] > 0
    assert "main_process_ingest_share_ratio" in x
    workers = x["multi"]["workers"]
    assert workers["worker_procs"] == 2
    assert all(w["alive"] for w in workers["workers"])
    # every decoded-and-staged vector record was drained by the engine
    # before teardown read the counters (single-writer, torn-free)
    assert all(w["req_pushed"] == w["req_drained"]
               for w in workers["workers"])
    # kernel accept balancing: with 4 connections the spread USUALLY
    # covers both workers, but 0.5s of roulette can land one-sided —
    # the hard spread assertion lives in the floor's best-of-two
    assert sum(x["worker_client_routes"]) == 4


async def test_metrics_overhead_harness():
    from benchmarks.ping import bench_metrics_overhead

    r = await bench_metrics_overhead(n_grains=16, concurrency=8,
                                     seconds=0.3)
    _check(r)
    assert r["extra"]["metered_calls_per_sec"] > 0


async def test_mapreduce_harness():
    r = await mapreduce.run(n_mappers=4, n_reducers=2, words_per_block=200,
                            repeats=1)
    _check(r)


def test_serialization_harness():
    for r in serialization.run(n=200):
        _check(r)


async def test_transactions_harness():
    r = await transactions.run(n_accounts=8, concurrency=3, seconds=0.3)
    _check(r)
    assert r["extra"]["committed"] > 0


async def test_streams_durable_harness(tmp_path):
    for r in await streams_durable.run(seconds=0.3, batch=16,
                                       db_path=str(tmp_path / "q.db")):
        _check(r)


async def test_gpstracker_harness():
    for r in await gpstracker_stream.run(n_devices=4, batch=8, seconds=0.3,
                                         vec_devices=256, vec_rounds=2,
                                         vec_iters=2):
        _check(r)


def test_chirper_fanout_harness():
    # 8-shard CPU mesh: exercises expand → all_to_all → ranked ring append
    # (fused: a scan of ticks per launch, the round-4 RPC-amortization)
    r = chirper_fanout.run(n_accounts=1024, followers_per=4,
                           chirps_per_tick=64, timeline_len=8,
                           seconds=0.3, n_devices=8, fuse=2, reps=1)
    _check(r)
    assert r["extra"]["devices"] == 8
    assert r["extra"]["ticks_per_launch"] == 2
    assert r["extra"]["pipeline_depth"] == 1  # multi-shard: sequential


def test_mxu_handler_harness():
    from benchmarks import mxu_handler

    r = mxu_handler.run(n_actors=128, fuse=2, seconds=0.3, reps=1)
    _check(r)
    assert r["extra"]["flops_per_actor_round"] > 1e6
    assert r["extra"]["verified_rounds"] >= 2


async def test_rebalance_harness():
    from benchmarks import rebalance
    r = await rebalance.run(n_grains=16, concurrency=4, seconds=0.2,
                            budget=8)
    assert r["activations_moved"] > 0
    assert max(r["counts_after"]) < r["skew_before"]
    assert r["throughput_balanced"] > 0
