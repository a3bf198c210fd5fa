"""Chirper fan-out benchmark — follower-graph multicast over the ICI mesh.

BASELINE.md config: "Samples/Chirper — follower-graph fan-out as ICI
all-to-all multicast" (reference Samples/Chirper: ChirperAccount grains
push each chirp to all follower accounts' timelines). Vectorized: accounts
live in a sharded timeline table; one tick takes a batch of chirps,
expands each to its followers (dense [B, F] follower lists), routes the
(follower, chirp) messages across shards with the tick exchange
(all_to_all — parallel.transport), then appends delivered chirps into
per-follower timeline ring buffers using the sort-based rank kernel
(ops.route.rank_dense_keys — large key space) for within-follower append
positions.

Measures delivered follower-timeline writes/sec (the fan-out analog of
grain msgs/sec).
"""

import argparse
import json
import time

import numpy as np

if __package__ in (None, ""):
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from orleans_tpu.ops.route import rank_dense_keys
from orleans_tpu.parallel import make_mesh
from orleans_tpu.parallel.mesh import SILO_AXIS
from orleans_tpu.parallel.transport import build_exchange


def build_tick(mesh, n_accounts: int, timeline_len: int,
               exchange_capacity: int):
    """Compile the chirp-fan-out tick.

    Tables (sharded over the silo axis): timelines [n, A/n, T] int32,
    tl_pos [n, A/n] int32 (ring cursors), followers [n, A/n, F] int32,
    fcount [n, A/n] int32. Chirp batch: chirpers/chirp_ids/chirp_valid
    [n, B] (local account index per shard).
    """
    n = mesh.devices.size
    per_shard = n_accounts // n
    assert n_accounts % n == 0
    exchange = build_exchange(mesh, capacity=exchange_capacity)
    spec = P(SILO_AXIS)

    def expand_local(followers, fcount, chirpers, chirp_ids, chirp_valid):
        foll, fc = followers[0], fcount[0]
        accounts, cids, cvalid = chirpers[0], chirp_ids[0], chirp_valid[0]
        B = accounts.shape[0]
        targets = foll[accounts]                              # [B, F]
        lane = jax.lax.broadcasted_iota(jnp.int32, targets.shape, 1)
        t_valid = (lane < fc[accounts][:, None]) & cvalid[:, None]
        flat_t = targets.reshape(-1)
        flat_v = t_valid.reshape(-1)
        flat_c = jnp.broadcast_to(cids[:, None], targets.shape).reshape(-1)
        dest = flat_t // per_shard
        return flat_t[None], flat_v[None], flat_c[None], dest[None]

    def deliver_local(recv_target, recv_chirp, recv_valid, timelines,
                      tl_pos):
        tls, pos = timelines[0], tl_pos[0]
        tgt, cid, ok = recv_target[0], recv_chirp[0], recv_valid[0]
        local_f = jnp.minimum(tgt % per_shard, per_shard - 1)
        f_or_sink = jnp.where(ok, local_f, per_shard)
        # within-follower append order: conflict-free ring append
        rank = rank_dense_keys(f_or_sink)
        write_pos = (pos[local_f] + rank) % timeline_len
        flat = jnp.where(ok, local_f * timeline_len + write_pos,
                         per_shard * timeline_len)
        buf = jnp.concatenate(
            [tls.reshape(-1), jnp.zeros((1,), tls.dtype)])
        new_tls = buf.at[flat].set(
            jnp.where(ok, cid, 0))[:-1].reshape(per_shard, timeline_len)
        counts = jnp.zeros((per_shard + 1,), jnp.int32).at[f_or_sink].add(
            jnp.where(ok, 1, 0))[:per_shard]
        new_pos = (pos + counts) % timeline_len
        delivered = jnp.sum(jnp.where(ok, 1, 0))
        return new_tls[None], new_pos[None], delivered[None]

    if n > 1:
        expand = jax.shard_map(expand_local, mesh=mesh,
                               in_specs=(spec,) * 5, out_specs=(spec,) * 4,
                               check_vma=False)
        deliver = jax.shard_map(deliver_local, mesh=mesh,
                                in_specs=(spec,) * 5,
                                out_specs=(spec,) * 3, check_vma=False)
    else:
        expand, deliver = expand_local, deliver_local

    def tick(timelines, tl_pos, followers, fcount, chirpers, chirp_ids,
             chirp_valid):
        flat_t, flat_v, flat_c, dest = expand(
            followers, fcount, chirpers, chirp_ids, chirp_valid)
        recv, recv_valid, drops = exchange(
            dest, flat_v, {"target": flat_t, "chirp": flat_c})
        new_tls, new_pos, delivered = deliver(
            recv["target"], recv["chirp"], recv_valid, timelines, tl_pos)
        return new_tls, new_pos, delivered, drops

    def fused(timelines, tl_pos, followers, fcount, staged_ch, staged_ci,
              staged_cv):
        """S ticks per dispatch via lax.scan (the fusion lever: the
        host's per-launch cost — not measured on this round's chip — is
        paid once per LAUNCH, so fusing S ticks amortizes it S-fold).
        Accumulators stay per-shard shaped — no
        standalone cross-shard reduction inside the scan."""
        def body(carry, xs):
            tls, pos, dlv, drp = carry
            ch, ci, cv = xs
            ntls, npos, d, dr = tick(tls, pos, followers, fcount,
                                     ch, ci, cv)
            dr = jnp.sum(jnp.reshape(dr, (dr.shape[0], -1)).astype(
                jnp.int32), axis=1)
            return (ntls, npos, dlv + d, drp + dr), None

        n_sh = timelines.shape[0]
        zero = jnp.zeros((n_sh,), jnp.int32)
        (ntls, npos, dlv, drp), _ = jax.lax.scan(
            body, (timelines, tl_pos, zero, zero),
            (staged_ch, staged_ci, staged_cv))
        return ntls, npos, dlv, drp

    return jax.jit(fused, donate_argnums=(0, 1))


def run(n_accounts: int = 65536, followers_per: int = 16,
        chirps_per_tick: int = 16384, timeline_len: int = 32,
        seconds: float = 8.0, n_devices: int | None = None,
        fuse: int | None = None, pipeline_depth: int = 4,
        reps: int = 3) -> dict:
    import os

    from benchmarks.attribution import (roofline_fields, staged_cache,
                                        two_point_fit)

    fuse = fuse if fuse is not None else int(
        os.environ.get("CHIRPER_FUSE", "32"))
    mesh = make_mesh(n_devices) if n_devices else make_mesh()
    n = mesh.devices.size
    per_shard = n_accounts // n
    rng = np.random.default_rng(7)

    followers = rng.integers(0, n_accounts,
                             (n, per_shard, followers_per)).astype(np.int32)
    fcount = np.full((n, per_shard), followers_per, np.int32)
    timelines = jnp.zeros((n, per_shard, timeline_len), jnp.int32)
    tl_pos = jnp.zeros((n, per_shard), jnp.int32)

    # worst-case lanes one shard can send to one destination: all its
    # expanded messages (uniform graphs stay far below this)
    per_tick = chirps_per_tick // n
    fused = build_tick(mesh, n_accounts, timeline_len,
                       exchange_capacity=per_tick * followers_per)

    d_foll = jnp.asarray(followers)
    d_fc = jnp.asarray(fcount)

    def staged(s: int) -> tuple:
        ch = rng.integers(0, per_shard, (s, n, per_tick)).astype(np.int32)
        ci = rng.integers(1, 1 << 30, (s, n, per_tick)).astype(np.int32)
        cv = np.ones((s, n, per_tick), bool)
        return jnp.asarray(ch), jnp.asarray(ci), jnp.asarray(cv)

    # overlapping collective launches deadlock the CPU backend's
    # rendezvous pool (VectorRuntime.validate_pipeline_depth documents
    # it); the same constraint applies to this hand-built exchange tick
    depth = 1 if n > 1 else pipeline_depth
    d_ch, d_ci, d_cv = staged(fuse)

    # correctness: one verified launch — every expanded message is
    # delivered or accounted as a capacity drop
    timelines, tl_pos, delivered, drops = fused(
        timelines, tl_pos, d_foll, d_fc, d_ch, d_ci, d_cv)
    jax.block_until_ready(tl_pos)
    total_msgs = fuse * n * per_tick * followers_per
    assert int(np.asarray(delivered).sum()) + \
        int(np.asarray(drops).sum()) == total_msgs

    # ---- throughput: pipelined fused launches -------------------------
    launches = 0
    inflight = []
    completions = []  # (wall time, delivered count) per finished launch
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        timelines, tl_pos, delivered, drops = fused(
            timelines, tl_pos, d_foll, d_fc, d_ch, d_ci, d_cv)
        inflight.append(delivered)
        launches += 1
        if len(inflight) >= depth:
            d = int(np.asarray(inflight.pop(0)).sum())
            completions.append((time.perf_counter(), d))
    for dd in inflight:
        d = int(np.asarray(dd).sum())  # blocks; stamp AFTER the sync
        completions.append((time.perf_counter(), d))
    comp = np.asarray([t for t, _ in completions])
    if len(comp) > 1:
        # the measured window spans the intervals BETWEEN completions,
        # so the first completion's deliveries fall outside it
        elapsed = comp[-1] - comp[0]
        total_delivered = sum(d for _, d in completions[1:])
    else:
        elapsed = time.perf_counter() - t0
        total_delivered = sum(d for _, d in completions)

    # ---- attribution + roofline --------------------------------------
    # blocking fit over tick counts separates device execution from the
    # per-dispatch host cost (benchmarks/attribution.py)
    state = {"tls": timelines, "pos": tl_pos}
    get_staged = staged_cache(staged)

    def run_blocking(s: int) -> float:
        b = get_staged(s)
        t0 = time.perf_counter()
        ntls, npos, _, _ = fused(state["tls"], state["pos"], d_foll, d_fc,
                                 *b)
        jax.block_until_ready(npos)
        state["tls"], state["pos"] = ntls, npos
        return time.perf_counter() - t0

    s_a = max(8, fuse // 2)
    fit = two_point_fit(run_blocking, s_a, 2 * s_a, reps=reps)
    m_per_tick = n * per_tick * followers_per
    # HBM traffic model per tick (int32 lanes): follower-list gather
    # (B*F), exchange send+recv of 3 payload arrays (2*3*M), timeline
    # scatter (M) + message source reads (3*B). The rank sort's compare
    # traffic is NOT modeled — this workload is partly sort-compute, so
    # pct_of_peak_bw is a LOWER bound on device utilization
    bytes_per_tick = 4 * (m_per_tick * (1 + 6 + 1) + 4 * n * per_tick)
    roof = roofline_fields(fit, bytes_per_unit=bytes_per_tick)

    extra = {
        "n_accounts": n_accounts,
        "followers_per": followers_per,
        "chirps_per_tick": n * per_tick,
        "ticks_per_launch": fuse,
        "pipeline_depth": depth,
        "launches": launches,
        "chirps_per_sec": round(
            (len(comp) - 1) * fuse * n * per_tick / elapsed, 1)
        if len(comp) > 1 else None,
        "devices": n,
        "roofline_note": "bytes model excludes rank-sort traffic: "
                         "pct_of_peak_bw is a lower bound",
        **fit, **roof,
    }
    extra.pop("device_unit_s", None)
    return {
        "metric": "chirper_timeline_deliveries_per_sec",
        "value": round(total_delivered / elapsed, 1),
        "unit": "deliveries/sec",
        "vs_baseline": None,
        "extra": extra,
    }


# ---------------------------------------------------------------------------
# Primitive-vs-message-per-edge A/B (ISSUE 13): celebrity-post follower
# multicast through the HOST tier — one RPC per (chirp, follower) edge vs
# one broadcast_actors collective carrying the whole edge list.
# ---------------------------------------------------------------------------

async def run_ab(n_followers: int = 64, n_chirpers: int = 8,
                 n_accounts: int = 512, repeats: int = 2) -> dict:
    """Follower fan-out on IDENTICAL edge traffic: per-edge
    ``TimelineVec.recv`` RPCs (message-per-edge, the pre-primitive
    shape) vs ONE ``broadcast_actors`` call per drive. Fan-out per chirp
    is ``n_followers`` (the >=64 acceptance regime); emits the
    wall-clock ratio + messages-eliminated; best-of-``repeats`` per side
    with per-side ``gc.collect()`` (the ping-floor A/B discipline)."""
    import asyncio
    import gc

    import jax.numpy as jnp
    from orleans_tpu.dispatch import (VectorGrain, actor_method,
                                      add_vector_grains)
    from orleans_tpu.runtime import ClusterClient, SiloBuilder

    class TimelineVec(VectorGrain):
        STATE = {"received": (jnp.int32, ()), "last": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"received": jnp.int32(0), "last": jnp.int32(0)}

        @actor_method(args={"chirp": (jnp.int32, ())})
        def recv(state, args):
            new = {"received": state["received"] + 1,
                   "last": args["chirp"]}
            return new, new["received"]

        @actor_method(read_only=True)
        def count(state, args):
            return state, state["received"]

    rng = np.random.default_rng(17)
    # each chirper multicasts one chirp to its n_followers followers
    followers = rng.integers(0, n_accounts, (n_chirpers, n_followers))
    targets = followers.reshape(-1).astype(np.int64)
    chirps = np.repeat(
        rng.integers(1, 1 << 30, n_chirpers), n_followers).astype(np.int32)
    n_edges = int(targets.size)

    async def side(bulk: bool) -> tuple[float, int]:
        b = SiloBuilder().with_name("chirp-ab")
        add_vector_grains(b, TimelineVec, mesh=make_mesh(1),
                          capacity_per_shard=n_accounts,
                          dense={TimelineVec: n_accounts})
        silo = b.build()
        await silo.start()
        client = await ClusterClient(silo.fabric).connect()
        async def drive() -> int:
            if bulk:
                return await client.broadcast_actors(
                    TimelineVec, "recv", targets, {"chirp": chirps})
            delivered = 0
            for off in range(0, n_edges, 256):
                got = await asyncio.gather(*(
                    client.get_grain(TimelineVec, int(t)).recv(
                        chirp=np.int32(c))
                    for t, c in zip(targets[off:off + 256],
                                    chirps[off:off + 256])))
                delivered += len(got)
            return delivered

        try:
            # SYMMETRIC warmup: one full identical drive per side, out
            # of the timed window — both sides' first-shape jit compiles
            # / first-bucket tick-kernel builds are amortized equally,
            # so the ratio measures steady-state dispatch, not compile
            await drive()
            gc.collect()
            msgs0 = silo.stats.get("messaging.received.application")
            t0 = time.perf_counter()
            delivered = await drive()
            wall = time.perf_counter() - t0
            msgs = silo.stats.get("messaging.received.application") - msgs0
            assert delivered == n_edges, (delivered, n_edges)
            total = int(await client.reduce_actors(TimelineVec, "count"))
            assert total == n_edges * 2, (total, n_edges * 2)
            return wall, msgs
        finally:
            await client.close_async()
            await silo.stop()

    best_edge = best_bulk = float("inf")
    msgs_edge = msgs_bulk = 0
    for _ in range(repeats):
        w, m = await side(bulk=False)
        if w < best_edge:
            best_edge, msgs_edge = w, m
        w, m = await side(bulk=True)
        if w < best_bulk:
            best_bulk, msgs_bulk = w, m
    ratio = best_edge / best_bulk
    return {
        "metric": "chirper_bulk_vs_per_edge_ratio",
        "value": round(ratio, 2),
        "unit": "x",
        "vs_baseline": None,
        "extra": {
            "n_edges": n_edges,
            "fan_out": n_followers,
            "n_chirpers": n_chirpers,
            "per_edge_wall_s": round(best_edge, 4),
            "bulk_wall_s": round(best_bulk, 4),
            "per_edge_deliveries_per_sec": round(n_edges / best_edge, 1),
            "bulk_deliveries_per_sec": round(n_edges / best_bulk, 1),
            "per_edge_app_msgs": msgs_edge,
            "bulk_app_msgs": msgs_bulk,
            "messages_eliminated": msgs_edge - msgs_bulk,
        },
    }


# ---------------------------------------------------------------------------
# Device-stream-vs-per-subscriber A/B (ISSUE 16): celebrity post fan-out
# through a STREAM namespace — one RPC per (event, subscriber) vs the
# DeviceStreamProvider's compiled edge-list delivery. Identical edge
# traffic both sides; measures publish -> all-delivered wall clock.
# ---------------------------------------------------------------------------

async def run_ab_device(n_subscribers: int = 64, n_events: int = 16,
                        batch: int = 4, repeats: int = 2) -> dict:
    """Stream fan-out on IDENTICAL edge traffic: per-subscriber
    ``TimelineVec.recv`` RPCs per published event (the per-consumer
    delivery shape of the host-tier providers) vs DeviceStreamProvider
    publishes whose delivery compiles onto ``stream_fanout`` edge
    exchanges. ``n_events`` events publish in groups of ``batch`` items
    (each cached batch is one stacked dispatch); fan-out per event is
    ``n_subscribers`` (the >=64 acceptance regime). Best-of-``repeats``
    per side with per-side ``gc.collect()`` + ``gc.freeze()`` over the
    timed window (the ping-floor A/B discipline)."""
    import asyncio
    import gc

    import jax.numpy as jnp
    from orleans_tpu.dispatch import (VectorGrain, actor_method,
                                      add_vector_grains)
    from orleans_tpu.runtime import ClusterClient, SiloBuilder
    from orleans_tpu.streams import StreamId, add_device_streams

    class TimelineVec(VectorGrain):
        STATE = {"received": (jnp.int32, ()), "last": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"received": jnp.int32(0), "last": jnp.int32(0)}

        @actor_method(args={"chirp": (jnp.int32, ())})
        def recv(state, args):
            new = {"received": state["received"] + 1,
                   "last": args["chirp"]}
            return new, new["received"]

        @actor_method(read_only=True)
        def count(state, args):
            return state, state["received"]

    rng = np.random.default_rng(23)
    chirps = rng.integers(1, 1 << 30, n_events).astype(np.int32)
    n_edges = n_events * n_subscribers

    async def side(device: bool) -> tuple[float, int]:
        b = SiloBuilder().with_name("chirp-ds")
        add_vector_grains(b, TimelineVec, mesh=make_mesh(1),
                          capacity_per_shard=max(64, n_subscribers),
                          dense={TimelineVec: n_subscribers})
        add_device_streams(b, "device")
        silo = b.build()
        await silo.start()
        client = await ClusterClient(silo.fabric).connect()
        provider = silo.stream_providers["device"]
        if device:
            await provider.subscribe_keys("celebrity", TimelineVec,
                                          np.arange(n_subscribers),
                                          method="recv")
        stream = StreamId("device", "celebrity", "post")
        keys = np.arange(n_subscribers)

        async def drive() -> None:
            if device:
                base = silo.stats.get("streams.device.delivered")
                for off in range(0, n_events, batch):
                    await provider.produce(stream, [
                        {"chirp": c} for c in chirps[off:off + batch]])
                target = base + n_edges
                while silo.stats.get("streams.device.delivered") < target:
                    await asyncio.sleep(0)
                return
            for c in chirps:
                for off in range(0, n_subscribers, 256):
                    await asyncio.gather(*(
                        client.get_grain(TimelineVec, int(k)).recv(
                            chirp=np.int32(c))
                        for k in keys[off:off + 256]))

        try:
            # SYMMETRIC warmup (see run_ab): one identical drive per
            # side amortizes jit compiles / row activation equally
            await drive()
            gc.collect()
            gc.freeze()
            try:
                t0 = time.perf_counter()
                await drive()
                wall = time.perf_counter() - t0
            finally:
                gc.unfreeze()
            total = int(await client.reduce_actors(TimelineVec, "count"))
            assert total == n_edges * 2, (total, n_edges * 2)
            grp = (provider.stream_delivery_group() if device else 0)
            return wall, int(grp)
        finally:
            await client.close_async()
            await silo.stop()

    best_edge = best_dev = float("inf")
    group = 0
    for _ in range(repeats):
        w, _ = await side(device=False)
        best_edge = min(best_edge, w)
        w, g = await side(device=True)
        if w < best_dev:
            best_dev, group = w, g
    ratio = best_edge / best_dev
    return {
        "metric": "chirper_device_stream_vs_per_subscriber_ratio",
        "value": round(ratio, 2),
        "unit": "x",
        "vs_baseline": None,
        "extra": {
            "n_edges": n_edges,
            "fan_out": n_subscribers,
            "n_events": n_events,
            "items_per_publish": batch,
            "per_subscriber_wall_s": round(best_edge, 4),
            "device_wall_s": round(best_dev, 4),
            "per_subscriber_deliveries_per_sec":
                round(n_edges / best_edge, 1),
            "device_deliveries_per_sec": round(n_edges / best_dev, 1),
            "last_delivery_group": group,
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--accounts", type=int, default=65536)
    ap.add_argument("--followers", type=int, default=16)
    ap.add_argument("--chirps", type=int, default=16384)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--ab", action="store_true",
                    help="run the host-tier bulk-vs-per-edge A/B")
    ap.add_argument("--ab-device", action="store_true",
                    help="run the device-stream-vs-per-subscriber A/B")
    a = ap.parse_args()
    if a.ab:
        import asyncio
        print(json.dumps(asyncio.run(run_ab())))
        return
    if a.ab_device:
        import asyncio
        print(json.dumps(asyncio.run(run_ab_device())))
        return
    print(json.dumps(run(a.accounts, a.followers, a.chirps,
                         seconds=a.seconds)))


if __name__ == "__main__":
    main()
