"""Loop-attribution benchmark — what actually occupies the silo's event
loop at closed-loop saturation.

PR 7 left the residual: at c=32 the queue-wait share stays ~0.95, and the
ROADMAP attributes it to "event-loop contention between host turns and
the ~1.8 ms device tick" — an inference, not a measurement. This harness
turns it into a measured split: the same saturated mixed host+vector
harness as ``ingest_attribution`` (GatewayClient over real TCP, c=32),
with the host-loop occupancy profiler on (``profiling_enabled``), then
reads the per-category loop shares back out:

    turns                    host grain turns
    tick_schedule            the device tick's loop side: the claim, the
                             hand-off to the tick worker and the
                             completion (staging, transfer, dispatch and
                             sync run on the worker, off the loop)
    pump                     socket reads + wire decode + batched routing
    client                   client-side gateway machinery (pumps,
                             senders, reconnector) — first-class since
                             PR 9 so harness cost leaves "other"
    storage/observability    provider IO / our own telemetry machinery
    other / idle             unattributed callbacks / select() wait

Shares are contiguous per-callback wall-time segments plus inter-callback
idle, so they sum to ~1.0 of measured loop wall time by construction —
``shares_sum`` is emitted as the self-check. ``--profiling-off`` runs the
same harness bare (the overhead A/B the CI floor reads via
``ping.bench_profiling_overhead``)."""

import argparse
import asyncio
import json
import time

if __package__ in (None, ""):
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from orleans_tpu.runtime import SiloBuilder
from orleans_tpu.runtime.socket_fabric import GatewayClient, SocketFabric

# same saturated mixed workload as the ingest harness this is modeled on
# (one definition: the two benches must measure identical traffic, or
# cross-bench share comparisons in the ROADMAP stop meaning anything)
from benchmarks.ingest_attribution import (EchoGrain, _make_vector_grain,
                                           batched_vec_sender,
                                           connect_clients)


class LocalEchoGrain(EchoGrain):
    """EchoGrain pinned to the accepting silo (ISSUE 18): under
    ``worker_procs>1`` a client connection lands in ONE worker process,
    and prefer_local placement keeps that client's host activations in
    the worker that accepted it — host turns then run without a
    cross-process relay hop, which is the multi-process lever's whole
    throughput story. Used on BOTH sides of the multiproc A/B so the
    ``worker_procs`` config is the only delta."""
    __orleans_placement__ = "prefer_local"


async def run(seconds: float = 2.0, concurrency: int = 32,
              n_grains: int = 64, n_keys: int = 64,
              call_batch: bool = False,
              call_batch_size: int = 16, ingress_loops: int = 1,
              egress_shards: int = 0, n_clients: int = 1,
              worker_procs: int = 1,
              prefer_local_hosts: bool = False) -> dict:
    """One silo over real TCP, profiling on, mixed host + device traffic
    at closed-loop saturation; returns the loop-occupancy breakdown.
    ``call_batch=True`` switches the vector senders to deliberate
    client-side wire batches;
    ``ingress_loops>=2`` runs the multi-loop silo (sharded ingress pump
    threads — ISSUE 11) and ``n_clients`` controls how many gateway
    connections feed it (each pins to one ingress loop, so the
    multi-loop A/B drives >= 2 connections on BOTH sides);
    ``egress_shards>=1`` moves outbound senders + shard-owned response
    encode/writev onto shard loops (ISSUE 15) — the main loop's
    "egress" occupancy share is that lever's structural signal;
    ``worker_procs>=2`` forks SO_REUSEPORT worker processes fed through
    shared-memory staging rings (ISSUE 18) — clients connect to the
    advertised gateway endpoint and the MAIN process's pump+egress
    shares are that lever's structural signal (``prefer_local_hosts``
    keeps host activations in the accepting worker on both A/B sides)."""
    import numpy as np

    from orleans_tpu.dispatch import add_vector_grains
    from orleans_tpu.parallel import make_mesh

    EchoVec = _make_vector_grain()
    Host = LocalEchoGrain if prefer_local_hosts else EchoGrain
    fabric = SocketFabric()
    b = (SiloBuilder().with_name("loop-silo").with_fabric(fabric)
         .add_grains(Host)
         .with_config(profiling_enabled=True, profiling_window=0.25,
                      ingress_loops=ingress_loops,
                      egress_shards=egress_shards,
                      worker_procs=worker_procs))
    add_vector_grains(b, EchoVec, mesh=make_mesh(1),
                      dense={EchoVec: n_keys})
    silo = b.build()
    await silo.start()
    # silo bracketed from HERE: a connect failure must still stop it
    # (threads/sockets otherwise leak into every later measurement)
    clients = []
    try:
        # gateway_endpoint IS silo_address.endpoint when worker_procs=1
        # (the property falls back), so single-process runs are
        # unchanged and the multiproc A/B differs only in the lever
        clients = await connect_clients(silo.gateway_endpoint,
                                        n_clients)
        client = clients[0]
        host_refs = [clients[k % len(clients)].get_grain(Host, k)
                     for k in range(n_grains)]
        vec_refs = [clients[k % len(clients)].get_grain(EchoVec, k)
                    for k in range(n_keys)]
        # warmup: activate host grains, compile the vector kernels
        await asyncio.gather(*(g.ping(0) for g in host_refs))
        await asyncio.gather(*(v.ping(x=np.int32(0)) for v in vec_refs[:8]))

        # profiler totals are cumulative since install: snapshot them
        # AFTER warmup so the reported shares cover only the measured
        # saturation interval — warmup activation + one-time JIT kernel
        # compilation are loop-blocking tick work that would otherwise
        # skew the very split this harness exists to measure
        lp = silo.loop_prof
        base_sec = dict(lp.profile(windows=0, snapshots=False)["seconds"])

        stop_at = time.perf_counter() + seconds
        calls = 0

        async def host_worker(wid: int) -> None:
            nonlocal calls
            i = wid
            while time.perf_counter() < stop_at:
                await host_refs[i % n_grains].ping(i)
                i += 1
                calls += 1

        async def vec_worker(wid: int) -> None:
            nonlocal calls
            i = wid
            while time.perf_counter() < stop_at:
                await vec_refs[i % n_keys].ping(x=np.int32(i & 0x7FFF))
                i += 1
                calls += 1

        # deliberate client-side batching (call_batch): one group per
        # await fills a wire batch at the sender and lands silo-side as
        # one routing hop — the sender loop is SHARED with the ingest
        # harness (identical traffic is the cross-bench contract)
        cb_count = [0]
        vw = (batched_vec_sender(client, EchoVec, n_keys, call_batch_size,
                                 stop_at, cb_count)
              if call_batch else vec_worker)

        t0 = time.perf_counter()
        half = max(1, concurrency // 2)
        await asyncio.gather(
            *(host_worker(w) for w in range(half)),
            *(vw(w) for w in range(half)))
        elapsed = time.perf_counter() - t0
        calls += cb_count[0]

        # read the profile BEFORE stop (stop uninstalls the profiler)
        # and diff against the post-warmup snapshot: interval-only split
        prof = silo.loop_prof.profile(windows=4)
        sec = {k: round(v - base_sec.get(k, 0.0), 6)
               for k, v in prof["seconds"].items()
               if v - base_sec.get(k, 0.0) > 1e-9}
        wall = sum(sec.values())
        shares = {k: round(v / wall, 4) for k, v in sec.items()} \
            if wall else {}
        top = (prof["windows"][-1]["top"][:4]
               if prof["windows"] else [])
        ingress = None
        pool = silo.ingress_pool
        if pool is not None:
            # per-ingress-loop attribution (the per-loop profiler
            # install): each shard's pump share + hand-off counters
            ingress = [{"loop": p["ingress_loop"],
                        "frames": p["frames"],
                        "ring_batches": p["ring_batches"],
                        "qos_direct": p["qos_direct"],
                        "pump_share": p["shares"].get("pump", 0.0),
                        "busy_share": round(
                            1.0 - p["shares"].get("idle", 0.0), 4)}
                       for p in await pool.loop_profiles(windows=0)]
        workers = (silo.workers.describe()
                   if silo.workers is not None else None)
    finally:
        for c in clients:
            await c.close_async()
        await silo.stop()
    busy = round(1.0 - shares.get("idle", 0.0), 4)
    tick_total = round(sum(v for k, v in shares.items()
                           if k.startswith("tick_")), 4)
    return {
        "metric": "loop_occupancy_busy_share",
        "value": busy,
        "unit": "share of loop wall time",
        "vs_baseline": None,
        "extra": {
            "seconds": seconds, "concurrency": concurrency,
            "call_batch": call_batch,
            "ingress_loops": ingress_loops,
            "egress_shards": egress_shards, "n_clients": n_clients,
            "worker_procs": worker_procs,
            "workers": workers,
            "ingress_loop_profiles": ingress,
            "calls": calls,
            "calls_per_sec": round(calls / elapsed, 1),
            "shares": shares,
            "shares_sum": round(sum(shares.values()), 4),
            "seconds_by_category": sec,
            "device_tick_share": tick_total,
            "turns_share": shares.get("turns", 0.0),
            "pump_share": shares.get("pump", 0.0),
            "egress_share": shares.get("egress", 0.0),
            "egress_seconds": sec.get("egress", 0.0),
            "client_share": shares.get("client", 0.0),
            "observability_share": shares.get("observability", 0.0),
            "top_callbacks_last_window": top,
        },
    }


async def run_multiloop_ab(seconds: float = 2.0, concurrency: int = 32,
                           loops: int = 2, n_clients: int = 2) -> dict:
    """Multi-loop silo A/B (the ISSUE 11 acceptance point): identical
    mixed TCP traffic over ``n_clients`` gateway connections against a
    1-ingress-loop silo vs an N-ingress-loop silo — ONLY the
    ``ingress_loops`` lever differs. Emits the silo msgs/sec ratio plus
    the main-loop pump-share drop (the structural signal: the socket
    read + wire decode leave the main loop for the shard threads) and
    the per-ingress-loop profiles.

    Ratio-based on purpose: absolute rates on a shared-core container
    are noise; and on a GIL interpreter the ratio is bounded by how much
    of the pump is syscalls/select (GIL-released) vs header/body decode
    (GIL-held) — a multi-core runner with free cores is where the
    >= 1.7x target is meaningful."""
    one = await run(seconds, concurrency, ingress_loops=1,
                    n_clients=n_clients)
    multi = await run(seconds, concurrency, ingress_loops=loops,
                      n_clients=n_clients)

    def rate(r):
        return r["extra"]["calls_per_sec"]

    ratio = rate(multi) / rate(one) if rate(one) else 0.0
    pump_one = one["extra"]["pump_share"]
    pump_multi = multi["extra"]["pump_share"]
    return {
        "metric": "multiloop_speedup",
        "value": round(ratio, 3),
        "unit": f"x (ingress_loops={loops} vs 1, same traffic)",
        "vs_baseline": None,
        "extra": {
            "seconds": seconds, "concurrency": concurrency,
            "loops": loops, "n_clients": n_clients,
            "single": {"calls_per_sec": rate(one),
                       "pump_share": pump_one,
                       "shares": one["extra"]["shares"]},
            "multi": {"calls_per_sec": rate(multi),
                      "pump_share": pump_multi,
                      "shares": multi["extra"]["shares"],
                      "ingress_loop_profiles":
                          multi["extra"]["ingress_loop_profiles"]},
            # the structural signal: the main loop sheds its pump share
            # onto the shard threads regardless of end-to-end noise
            "main_loop_pump_share_ratio": round(
                pump_multi / pump_one, 3) if pump_one else 0.0,
        },
    }


async def run_egress_shards_ab(seconds: float = 2.0,
                               concurrency: int = 32, shards: int = 2,
                               n_clients: int = 2) -> dict:
    """Sharded-egress A/B (the ISSUE 15 acceptance point): identical
    mixed TCP traffic against two multi-loop silos differing ONLY in
    ``egress_shards`` — 0 keeps every response encode + sender write on
    the main loop, N hands shard-owned routes' flush groups across SPSC
    egress rings so encode + writev run on the shard loops. The
    structural signal is the main loop's "egress" occupancy share
    (per-batch encode + transport write, labeled via the profiler's
    egress category): acceptance is the sharded side's share falling to
    <= 0.5x of the unsharded baseline. Both sides run
    ``ingress_loops=shards`` so shard-owned client routes exist and the
    ONLY delta is the egress lever; the end-to-end msgs/sec ratio is
    reported but — as with the multi-loop A/B — only meaningful on a
    genuinely multi-core runner (test_floor_sharded_egress gates it on
    the same parallelism probe)."""
    base = await run(seconds, concurrency, ingress_loops=shards,
                     n_clients=n_clients, egress_shards=0)
    sharded = await run(seconds, concurrency, ingress_loops=shards,
                        n_clients=n_clients, egress_shards=shards)

    def rate(r):
        return r["extra"]["calls_per_sec"]

    def eg(r):
        return r["extra"]["egress_share"]

    ratio = rate(sharded) / rate(base) if rate(base) else 0.0
    return {
        "metric": "sharded_egress_speedup",
        "value": round(ratio, 3),
        "unit": f"x (egress_shards={shards} vs 0, same traffic)",
        "vs_baseline": None,
        "extra": {
            "seconds": seconds, "concurrency": concurrency,
            "shards": shards, "n_clients": n_clients,
            "unsharded": {"calls_per_sec": rate(base),
                          "egress_share": eg(base),
                          "egress_seconds":
                              base["extra"]["egress_seconds"],
                          "shares": base["extra"]["shares"]},
            "sharded": {"calls_per_sec": rate(sharded),
                        "egress_share": eg(sharded),
                        "egress_seconds":
                            sharded["extra"]["egress_seconds"],
                        "shares": sharded["extra"]["shares"]},
            # the structural signal: main-loop egress (encode + write)
            # share sheds onto the shard loops regardless of end-to-end
            # noise (the ISSUE 15 acceptance read)
            "main_loop_egress_share_ratio": round(
                eg(sharded) / eg(base), 3) if eg(base) else 0.0,
        },
    }


async def run_multiproc_ab(seconds: float = 2.0, concurrency: int = 32,
                           procs: int = 2, n_clients: int = 4) -> dict:
    """Multi-process silo A/B (the ISSUE 18 acceptance point): identical
    mixed TCP traffic over ``n_clients`` gateway connections against a
    single-process silo vs a ``worker_procs=procs`` silo — ONLY the
    ``worker_procs`` lever differs (both sides use prefer_local host
    grains and connect to ``silo.gateway_endpoint``). Two structural
    signals ride beside the msgs/sec ratio:

      * the MAIN process's pump+egress occupancy share → ~0: clients
        connect to the SO_REUSEPORT gateway, so the kernel hands every
        accept to a worker process and the owner's loop never touches
        client socket reads, wire decode, or response encode — only the
        device engine (fed through the shm staging rings) remains;
      * the accept-balance spread: per-worker live client-route counts
        from the relay table prove the connections actually landed in
        >= 2 distinct worker processes.

    The end-to-end ratio is separate-GIL real parallelism, so — like
    the multiloop A/B — it is only meaningful on a genuinely multi-core
    runner; ``parallel_capacity`` is stamped into the payload so the
    recorded ratio travels with the capacity of the box that measured
    it (test_floor_multiproc gates on the same probe)."""
    from benchmarks.parallel_probe import parallel_capacity

    one = await run(seconds, concurrency, n_clients=n_clients,
                    worker_procs=1, prefer_local_hosts=True)
    multi = await run(seconds, concurrency, n_clients=n_clients,
                      worker_procs=procs, prefer_local_hosts=True)

    def rate(r):
        return r["extra"]["calls_per_sec"]

    def ingest_share(r):
        # everything client-facing the workers should absorb: socket
        # reads + wire decode (pump) and response encode + writes
        # (egress) on the MAIN process's loop
        x = r["extra"]
        return round(x["pump_share"] + x["egress_share"], 4)

    ratio = rate(multi) / rate(one) if rate(one) else 0.0
    spread = [w["client_routes"]
              for w in (multi["extra"]["workers"] or {}).get("workers", [])]
    return {
        "metric": "multiproc_speedup",
        "value": round(ratio, 3),
        "unit": f"x (worker_procs={procs} vs 1, same traffic)",
        "vs_baseline": None,
        "extra": {
            "seconds": seconds, "concurrency": concurrency,
            "procs": procs, "n_clients": n_clients,
            "parallel_capacity": round(parallel_capacity(), 3),
            "single": {"calls_per_sec": rate(one),
                       "pump_share": one["extra"]["pump_share"],
                       "egress_share": one["extra"]["egress_share"],
                       "shares": one["extra"]["shares"]},
            "multi": {"calls_per_sec": rate(multi),
                      "pump_share": multi["extra"]["pump_share"],
                      "egress_share": multi["extra"]["egress_share"],
                      "shares": multi["extra"]["shares"],
                      "workers": multi["extra"]["workers"]},
            # the structural signals (the ISSUE 18 acceptance reads):
            # owner sheds client-facing work entirely, and the kernel
            # actually balanced accepts across >= 2 workers
            "main_process_ingest_share": ingest_share(multi),
            "main_process_ingest_share_single": ingest_share(one),
            "main_process_ingest_share_ratio": round(
                ingest_share(multi) / ingest_share(one), 3)
            if ingest_share(one) else 0.0,
            "worker_client_routes": spread,
            "workers_with_clients": sum(1 for n in spread if n > 0),
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--concurrency", type=int, default=32)
    ap.add_argument("--call-batch", action="store_true",
                    help="vector senders use client-side call_batch")
    ap.add_argument("--ingress-loops", type=int, default=1,
                    help="multi-loop silo: N ingress pump threads")
    ap.add_argument("--egress-shards", type=int, default=0,
                    help="sharded egress: N egress shard loops")
    ap.add_argument("--clients", type=int, default=1,
                    help="gateway connections feeding the silo")
    ap.add_argument("--multiloop-ab", action="store_true",
                    help="run the 1-vs-2 ingress-loop A/B (ISSUE 11)")
    ap.add_argument("--egress-shards-ab", action="store_true",
                    help="run the egress_shards 0-vs-N A/B (ISSUE 15)")
    ap.add_argument("--worker-procs", type=int, default=1,
                    help="multi-process silo: N SO_REUSEPORT workers")
    ap.add_argument("--multiproc-ab", action="store_true",
                    help="run the worker_procs 1-vs-N A/B (ISSUE 18)")
    a = ap.parse_args()
    if a.multiproc_ab:
        print(json.dumps(asyncio.run(run_multiproc_ab(
            a.seconds, a.concurrency,
            procs=a.worker_procs if a.worker_procs > 1 else 2,
            n_clients=a.clients if a.clients > 1 else 4))))
    elif a.egress_shards_ab:
        print(json.dumps(asyncio.run(run_egress_shards_ab(
            a.seconds, a.concurrency,
            shards=a.egress_shards if a.egress_shards > 1 else 2,
            n_clients=a.clients if a.clients > 1 else 2))))
    elif a.multiloop_ab:
        print(json.dumps(asyncio.run(run_multiloop_ab(
            a.seconds, a.concurrency,
            loops=a.ingress_loops if a.ingress_loops > 1 else 2,
            n_clients=a.clients if a.clients > 1 else 2))))
    else:
        print(json.dumps(asyncio.run(run(
            a.seconds, a.concurrency,
            call_batch=a.call_batch, ingress_loops=a.ingress_loops,
            egress_shards=a.egress_shards, n_clients=a.clients,
            worker_procs=a.worker_procs,
            prefer_local_hosts=a.worker_procs > 1))))


if __name__ == "__main__":
    main()
