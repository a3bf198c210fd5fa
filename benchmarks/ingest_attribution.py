"""Ingest attribution benchmark — where an ingested message's time goes.

The ROADMAP's #1 wall: the device tier absorbs ~3.9B rounds/sec while
host-side ingest caps at ~12-18M msgs/sec bound, and until this PR
nothing could say *where* a message spends its time between socket and
device tick. This harness drives the full ingest path — GatewayClient →
TCP → wire decode (hotwire) → fabric enqueue → dispatcher → host turn
AND device-tier tick — with `metrics_enabled`, then reads the stage
histograms (observability.stats.INGEST_STATS) back out of the silo's
registry:

    decode / enqueue / queue_wait        host-side, per socket frame
    staging / transfer / tick            device-side, per vector batch

Stage *shares* are each stage's summed seconds over the total of all
stage sums — contiguous segments against the envelope's single
``received_at`` stamp, so they sum to 1.0 of the measured ingest wall
time by construction; ``stage_seconds_per_wall_second`` reports the
summed per-message stage time per wall second (>1 under concurrency —
N queued messages accrue wait simultaneously, which is the saturation
signal). This is the hard attribution PR 7's zero-copy batched-ingress
work lands against.
"""

import argparse
import asyncio
import json
import time

if __package__ in (None, ""):
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from orleans_tpu.observability.stats import (EGRESS_STAGES, EGRESS_STATS,
                                             INGEST_STAGES, INGEST_STATS)
from orleans_tpu.runtime import Grain, SiloBuilder
from orleans_tpu.runtime.socket_fabric import GatewayClient, SocketFabric


class EchoGrain(Grain):
    async def ping(self, x: int) -> int:
        return x


def call_batch_group(i: int, n_keys: int, batch: int) -> list:
    """One deliberate ``call_batch`` group for the attribution/A-B
    harnesses — the ONE key-striding + payload scheme every batched
    sender loop shares (ingest/loop attribution and the sender A/B must
    drive identical traffic or their cross-bench comparisons stop
    meaning anything)."""
    import numpy as np
    return [((i + j) % n_keys, {"x": np.int32((i + j) & 0x7FFF)})
            for j in range(batch)]


def batched_vec_sender(client, vec_cls, n_keys: int, batch: int,
                       stop_at: float, counter: list):
    """The ONE deliberate batched vector-sender loop every harness
    drives (ingest/loop attribution and the sender A/B share it so
    their traffic stays byte-identical): one ``call_batch`` group per
    await, gather the round, stride on. ``counter`` is a one-element
    list accumulating sent calls (the harnesses fold it into their own
    totals)."""
    async def worker(wid: int) -> None:
        i = wid * 1000
        while time.perf_counter() < stop_at:
            await asyncio.gather(*client.call_batch(
                vec_cls, "ping", call_batch_group(i, n_keys, batch)))
            i += batch
            counter[0] += batch
    return worker


def _make_vector_grain():
    import jax.numpy as jnp

    from orleans_tpu.dispatch import VectorGrain, actor_method

    class EchoVec(VectorGrain):
        STATE = {"pings": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"pings": jnp.int32(0)}

        @actor_method(args={"x": (jnp.int32, ())})
        def ping(state, args):
            return {"pings": state["pings"] + 1}, args["x"]

    return EchoVec


async def connect_clients(ep: str, n: int) -> list:
    """N gateway connections to one silo endpoint (multi-loop harness
    wiring: each connection pins to one ingress shard, so A/B points
    drive >= 2 on both sides). ONE definition shared with
    loop_attribution — the two harnesses must not drift."""
    return [await GatewayClient([ep]).connect() for _ in range(max(1, n))]


async def run(seconds: float = 2.0, concurrency: int = 32,
              n_grains: int = 64, n_keys: int = 64,
              call_batch: bool = False,
              call_batch_size: int = 16,
              ingress_loops: int = 1,
              egress_shards: int = 0, n_clients: int = 1) -> dict:
    """One silo over real TCP, metrics on, mixed host + device traffic;
    returns the stage breakdown in the BENCH extra.
    ``call_batch=True`` switches the vector workers from per-message
    awaited pings to deliberate ``client.call_batch`` groups of
    ``call_batch_size`` — the sender-side half of the pump share.
    ``ingress_loops>=2`` runs the multi-loop silo (ISSUE 11) with
    ``n_clients`` gateway connections feeding its shards — the
    queue-wait share under multi-loop is this harness's acceptance
    read. ``egress_shards>=1`` (ISSUE 15) moves outbound senders and
    shard-owned response encode onto shard loops — the egress stage
    seconds then include shard-stamped/loop-replayed observations."""
    import numpy as np

    from orleans_tpu.dispatch import add_vector_grains
    from orleans_tpu.parallel import make_mesh

    EchoVec = _make_vector_grain()
    fabric = SocketFabric()
    b = (SiloBuilder().with_name("ingest-silo").with_fabric(fabric)
         .add_grains(EchoGrain)
         .with_config(metrics_enabled=True, metrics_sample_period=0.25,
                      ingress_loops=ingress_loops,
                      egress_shards=egress_shards))
    add_vector_grains(b, EchoVec, mesh=make_mesh(1),
                      dense={EchoVec: n_keys})
    silo = b.build()
    await silo.start()
    clients = await connect_clients(silo.silo_address.endpoint, n_clients)
    client = clients[0]
    try:
        host_refs = [clients[k % len(clients)].get_grain(EchoGrain, k)
                     for k in range(n_grains)]
        vec_refs = [clients[k % len(clients)].get_grain(EchoVec, k)
                    for k in range(n_keys)]
        # warmup: activate host grains, compile the vector kernel
        await asyncio.gather(*(g.ping(0) for g in host_refs))
        await asyncio.gather(*(v.ping(x=np.int32(0)) for v in vec_refs[:8]))

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

        # deliberate client-side batching: one call_batch per round fills
        # a wire batch at the sender instead of relying on the greedy
        # drain, and lands silo-side as ONE routing hop (loop shared
        # with loop_attribution and the sender A/B — identical traffic
        # is the cross-bench contract)
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

        snap = silo.stats.snapshot()
        hists = snap["histograms"]
        stage_seconds = {}
        stage_counts = {}
        for stage in INGEST_STAGES:
            h = hists.get(INGEST_STATS[stage], {})
            stage_seconds[stage] = float(h.get("sum", 0.0))
            stage_counts[stage] = int(h.get("count", 0))
        total = sum(stage_seconds.values())
        shares = {k: (round(v / total, 4) if total else 0.0)
                  for k, v in stage_seconds.items()}
        frames = snap["counters"].get(INGEST_STATS["frames"], 0)
        batch_h = hists.get(INGEST_STATS["frame_batch"], {})
        # response-path decomposition (EGRESS_STATS, the egress twin):
        # summed stage seconds + the share of total instrumented wall the
        # response leg takes
        egress_seconds = {}
        for stage in EGRESS_STAGES:
            h = hists.get(EGRESS_STATS[stage], {})
            egress_seconds[stage] = float(h.get("sum", 0.0))
        egress_total = sum(egress_seconds.values())
        group_h = hists.get(EGRESS_STATS["group"], {})
        responses = snap["counters"].get(EGRESS_STATS["responses"], 0)
    finally:
        for c in clients:
            await c.close_async()
        await silo.stop()
    return {
        "metric": "ingest_attribution_msgs_per_sec",
        "value": round(calls / elapsed, 1),
        "unit": "msgs/sec",
        "vs_baseline": None,
        "extra": {
            "seconds": seconds, "concurrency": concurrency,
            "call_batch": call_batch,
            "ingress_loops": ingress_loops,
            "egress_shards": egress_shards, "n_clients": n_clients,
            "calls": calls,
            "stage_seconds": {k: round(v, 4)
                              for k, v in stage_seconds.items()},
            "stage_counts": stage_counts,
            "stage_shares": shares,
            "shares_sum": round(sum(shares.values()), 4),
            # summed per-message stage seconds over the bench wall: >1
            # under concurrency (N in-flight messages each accrue queue
            # wait simultaneously) — the saturation signal itself
            "stage_seconds_per_wall_second":
                round(total / elapsed, 4) if elapsed else 0.0,
            "frames_decoded": frames,
            "mean_frames_per_read": round(
                batch_h.get("mean", 0.0), 2) if batch_h else None,
            "egress_seconds": {k: round(v, 4)
                               for k, v in egress_seconds.items()},
            "egress_responses": responses,
            "mean_flush_group": round(
                group_h.get("mean", 0.0), 2) if group_h else None,
            # response-path share of ALL instrumented stage seconds
            # (ingest + egress): how much of the measured wall the
            # return leg costs under this configuration
            "response_path_share": round(
                egress_total / (total + egress_total), 4)
                if (total + egress_total) else 0.0,
        },
    }


async def _drain(silo) -> None:
    """Let one injection round fully retire: vector ticks flush (incl.
    off-loop worker in-flight batches), host turn tasks complete."""
    rt = silo.vector
    while True:
        if rt is not None and (rt.pending or rt._inflight):
            await rt.flush()
        if not any(not t.done() for t in silo.dispatcher._turn_tasks):
            return
        await asyncio.sleep(0)


async def run_ab(n_msgs: int = 512, seconds: float = 1.5,
                 host_every: int = 8) -> dict:
    """Batched ingest hand-off against a plain per-message reference,
    measured at the boundary the queue-wait attribution blamed.

    One silo, mixed messaging+vector traffic: a wire batch of ``n_msgs``
    ONE_WAY requests (1-in-``host_every`` host-tier pings, the rest
    device-tier vector pings — the regime the ingest wall is about) is
    pre-encoded once, then injected repeatedly for ``seconds`` through
    each hand-off:

      per_frame   the reference: Python length-prefix walk, one
                  decode_message + one MessageCenter.deliver per frame
                  (addressing + rt.call per message)
      batched     ONE decode_frames pass (a single unpack_batch C call)
                  + ONE deliver_batch (vector calls grouped into
                  call_group engine enqueues)

    Both sides decode the same bytes and retire the same work (ticks +
    turns drain between rounds), so the ratio isolates the hand-off —
    interpreter-independent, like the hot-lane margin floor."""
    import numpy as np

    from orleans_tpu.core.ids import GrainId, GrainType
    from orleans_tpu.core.message import Direction, make_request
    from orleans_tpu.dispatch import add_vector_grains
    from orleans_tpu.parallel import make_mesh
    from orleans_tpu.runtime.cluster import InProcFabric
    from orleans_tpu.runtime.wire import (decode_frames, decode_message,
                                          encode_message)

    EchoVec = _make_vector_grain()
    b = (SiloBuilder().with_name("ingest-ab")
         .with_fabric(InProcFabric())
         .add_grains(EchoGrain))
    add_vector_grains(b, EchoVec, mesh=make_mesh(1), dense={EchoVec: n_msgs})
    silo = b.build()
    await silo.start()
    try:
        # warmup: activate the host grains, compile the vector kernels
        # (both bucket sizes the rounds will hit)
        hostg = GrainType.of("EchoGrain")
        vecg = GrainType.of("EchoVec")
        frames = []
        n_host = 0
        for i in range(n_msgs):
            if i % host_every == 0:
                msg = make_request(
                    target_grain=GrainId.for_grain(hostg, i),
                    interface_name="EchoGrain", method_name="ping",
                    body=((i,), {}), direction=Direction.ONE_WAY)
                n_host += 1
            else:
                # plain-int payloads ride the native value codec (an
                # np.int32 body would pickle-escape per message, and that
                # decode cost — identical on both sides — only dilutes
                # the hand-off ratio being measured)
                msg = make_request(
                    target_grain=GrainId.for_grain(vecg, i),
                    interface_name="EchoVec", method_name="ping",
                    body=((), {"x": i & 0x7FFF}),
                    direction=Direction.ONE_WAY)
            frames.append(encode_message(msg))
        batch = bytearray(b"".join(frames))
        mc = silo.message_center

        def inject_per_frame() -> int:
            import struct
            pos, end = 0, len(batch)
            n = 0
            while end - pos >= 8:
                hlen, blen = struct.unpack_from("<II", batch, pos)
                h0 = pos + 8
                headers = bytes(batch[h0:h0 + hlen])
                body = bytes(batch[h0 + hlen:h0 + hlen + blen])
                pos = h0 + hlen + blen
                mc.deliver(decode_message(headers, body))
                n += 1
            return n

        def inject_batched() -> int:
            _, msgs, _ = decode_frames(batch)
            mc.deliver_batch(msgs)
            return len(msgs)

        async def measure(inject) -> float:
            # warmup round compiles kernels / fills caches
            inject()
            await _drain(silo)
            total = 0
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                total += inject()
                await _drain(silo)
            return total / (time.perf_counter() - t0)

        per_frame = await measure(inject_per_frame)
        batched = await measure(inject_batched)
    finally:
        await silo.stop()
    ratio = batched / per_frame if per_frame else 0.0
    return {
        "metric": "batched_ingest_speedup",
        "value": round(ratio, 2),
        "unit": "x",
        "vs_baseline": None,
        "extra": {
            "per_frame_msgs_per_sec": round(per_frame, 1),
            "batched_msgs_per_sec": round(batched, 1),
            "n_msgs": n_msgs, "host_frac": round(n_host / n_msgs, 3),
            "seconds": seconds,
        },
    }


async def run_call_batch_ab(seconds: float = 1.5, workers: int = 16,
                            n_keys: int = 64, batch: int = 16) -> dict:
    """Deliberate client-side batching vs per-message sends, vector-only
    (the sender-side half of the pump story, isolated from the mixed
    harness's host/vec mix shift): the same worker count drives the same
    device-tier keys over real TCP, once awaiting one ``ref.ping`` per
    round trip, once filling a ``call_batch`` group per round trip.

    The measured win is predominantly CLIENT-side — per-call
    send_request/GrainRef machinery collapses to one pass per group and
    the wire batch is filled deliberately rather than by greedy-drain
    luck — while per-message pump cost stays ~flat (the receive side has
    been batch-routed since the PR-7 ingress pipeline). Ratio-based, so
    interpreter/container speed cancels."""
    import gc

    import numpy as np

    from orleans_tpu.dispatch import add_vector_grains
    from orleans_tpu.parallel import make_mesh

    # GC discipline (collect + FREEZE): in a full-suite
    # run a gen-2 collection can trigger inside ONE side's timed window
    # and which side draws it shifts with every suite-size change —
    # park the pre-existing heap so in-measure collections scan only
    # this bench's young objects. The try/finally brackets the freeze
    # IMMEDIATELY: a failed silo start/connect must not leave the
    # process heap permanently frozen for every later floor
    gc.collect()
    gc.freeze()
    try:
        EchoVec = _make_vector_grain()
        fabric = SocketFabric()
        b = (SiloBuilder().with_name("cb-ab").with_fabric(fabric)
             .add_grains(EchoGrain))
        add_vector_grains(b, EchoVec, mesh=make_mesh(1),
                          dense={EchoVec: n_keys})
        silo = b.build()
        await silo.start()
        # the silo's own try/finally starts HERE: a connect() failure
        # must still stop it, or its threads/sockets pollute every
        # later floor in the process
        client = None
        try:
            client = await GatewayClient(
                [silo.silo_address.endpoint]).connect()
            refs = [client.get_grain(EchoVec, k) for k in range(n_keys)]
            await asyncio.gather(*(v.ping(x=np.int32(0)) for v in refs[:8]))

            async def measure(use_batch: bool) -> float:
                stop_at = time.perf_counter() + seconds
                calls = 0
                cb_count = [0]

                async def w_pm(wid: int) -> None:
                    nonlocal calls
                    i = wid
                    while time.perf_counter() < stop_at:
                        await refs[i % n_keys].ping(x=np.int32(i & 0x7FFF))
                        i += 1
                        calls += 1

                # the shared sender loop (batched_vec_sender): the A/B's
                # batched side drives the same traffic the attribution
                # harnesses measure
                w_cb = batched_vec_sender(client, EchoVec, n_keys, batch,
                                          stop_at, cb_count)

                t0 = time.perf_counter()
                await asyncio.gather(*((w_cb if use_batch else w_pm)(w)
                                       for w in range(workers)))
                return (calls + cb_count[0]) / (time.perf_counter() - t0)

            per_msg = await measure(False)
            batched = await measure(True)
        finally:
            if client is not None:
                await client.close_async()
            await silo.stop()
    finally:
        gc.unfreeze()
    ratio = batched / per_msg if per_msg else 0.0
    return {
        "metric": "call_batch_speedup",
        "value": round(ratio, 2),
        "unit": "x (vector-only, call_batch vs per-message senders)",
        "vs_baseline": None,
        "extra": {
            "per_message_msgs_per_sec": round(per_msg, 1),
            "call_batch_msgs_per_sec": round(batched, 1),
            "workers": workers, "batch": batch, "seconds": seconds,
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--concurrency", type=int, default=32)
    ap.add_argument("--ab", action="store_true",
                    help="run the batched-vs-per-frame hand-off A/B")
    ap.add_argument("--call-batch-ab", action="store_true",
                    help="run the call_batch-vs-per-message sender A/B")
    ap.add_argument("--call-batch", action="store_true",
                    help="vector senders use deliberate client-side "
                         "call_batch groups instead of per-message pings")
    ap.add_argument("--ingress-loops", type=int, default=1,
                    help="multi-loop silo: N ingress pump threads")
    ap.add_argument("--egress-shards", type=int, default=0,
                    help="sharded egress: N egress shard loops")
    a = ap.parse_args()
    if a.ab:
        print(json.dumps(asyncio.run(run_ab(seconds=a.seconds))))
    elif a.call_batch_ab:
        print(json.dumps(asyncio.run(run_call_batch_ab(seconds=a.seconds))))
    else:
        print(json.dumps(asyncio.run(run(
            a.seconds, a.concurrency,
            call_batch=a.call_batch,
            ingress_loops=a.ingress_loops,
            egress_shards=a.egress_shards))))


if __name__ == "__main__":
    main()
