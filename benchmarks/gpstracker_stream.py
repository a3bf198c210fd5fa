"""GPSTracker streaming benchmark — batched position pushes down streams.

BASELINE.md config: "Samples/GPSTracker — DeviceGrain geo-stream, streaming
batched push" (reference Samples/GPSTracker: device grains push position
updates onto a stream consumed by a web notifier). Two tiers:

* **host streams** — N DeviceGrains publish position batches onto a
  persistent (queue-backed) stream provider; a PushNotifier consumer per
  stream counts deliveries. Measures end-to-end events/sec through the
  full pulling-agent machinery (adapter → pulling agent → pubsub →
  consumer delivery — PersistentStreamPullingAgent.cs:141,350-368).
* **device tier** — the same workload vectorized: positions streamed
  through a DeviceGrain vector table with K rounds per upload
  (``call_batch_rounds`` — the pump re-expressed as a scanned kernel) and
  a region fan-in via the MXU segment sum. Measures events/sec/chip.
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
from orleans_tpu.streams import (MemoryQueueAdapter, add_persistent_streams,
                                 batch_consumer)

NS = "position"


class DeviceGrain(Grain):
    """DeviceGrain (Samples/GPSTracker/GPSTracker.GrainImplementation/
    DeviceGrain.cs): receives position fixes, publishes to its stream."""

    async def process_batch(self, fixes: list) -> int:
        stream = self.get_stream_provider("queue").get_stream(
            NS, self.primary_key)
        await stream.on_next_batch(fixes)
        return len(fixes)


class PushNotifierGrain(Grain):
    """PushNotifierGrain analog: consumes a device's stream; counts
    deliveries (the web-push boundary)."""

    def __init__(self):
        self.seen = 0

    async def join(self, device_key: int) -> None:
        stream = self.get_stream_provider("queue").get_stream(NS, device_key)
        await stream.subscribe(self.on_fixes)

    @batch_consumer
    async def on_fixes(self, fixes: list, first_token: int) -> None:
        # IAsyncBatchObserver-style web-push boundary: one notification
        # flush per delivered batch (the reference's notifier batches the
        # same way)
        self.seen += len(fixes)

    async def count(self) -> int:
        return self.seen


async def bench_host_streams(n_devices: int, batch: int,
                             seconds: float) -> dict:
    adapter = MemoryQueueAdapter(n_queues=8)
    b = (SiloBuilder().with_name("gps")
         .add_grains(DeviceGrain, PushNotifierGrain)
         .with_config(response_timeout=10.0))
    add_persistent_streams(b, "queue", adapter, pull_period=0.01)
    silo = b.build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()

    devices = [client.get_grain(DeviceGrain, k) for k in range(n_devices)]
    notifiers = [client.get_grain(PushNotifierGrain, k)
                 for k in range(n_devices)]
    await asyncio.gather(*(n.join(k) for k, n in enumerate(notifiers)))

    fixes = [{"lat": 37.7 + i * 1e-4, "lon": -122.4} for i in range(batch)]
    published = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        await asyncio.gather(*(d.process_batch(fixes) for d in devices))
        published += n_devices * batch
    # drain: all published fixes delivered through the pulling agents
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        got = sum(await asyncio.gather(*(n.count() for n in notifiers)))
        if got >= published:
            break
        await asyncio.sleep(0.05)
    elapsed = time.perf_counter() - t0
    assert got == published, (got, published)
    await client.close_async()
    await silo.stop()
    return {
        "metric": "gpstracker_stream_events_per_sec",
        "value": round(got / elapsed, 1),
        "unit": "events/sec",
        "vs_baseline": None,
        "extra": {"devices": n_devices, "batch": batch,
                  "events": got},
    }


def bench_device_tier(n_devices: int, rounds: int, iters: int,
                      reps: int = 3) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.attribution import (roofline_fields, staged_cache,
                                        two_point_fit)
    from orleans_tpu.dispatch import VectorGrain, VectorRuntime, actor_method
    from orleans_tpu.ops import segment_sum_onehot
    from orleans_tpu.parallel import make_mesh

    N_REGIONS = 256

    class DeviceVectorGrain(VectorGrain):
        STATE = {"pos": (jnp.float32, (2,)), "fixes": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"pos": jnp.zeros(2, jnp.float32), "fixes": jnp.int32(0)}

        @actor_method(args={"pos": (jnp.float16, (2,))})
        def fix(state, args):
            new = {"pos": args["pos"].astype(jnp.float32),
                   "fixes": state["fixes"] + 1}
            # region id for the notifier fan-in (velocity/geo bucketing)
            region = (jnp.abs(new["pos"][0] * 10).astype(jnp.int32)
                      % N_REGIONS)
            return new, region

    rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=n_devices)
    rt.table(DeviceVectorGrain).ensure_dense(n_devices)
    keys = np.arange(n_devices)
    plan = rt.make_dense_plan(DeviceVectorGrain, keys)
    rng = np.random.default_rng(0)

    def staged(k: int):
        # device-resident: a host payload would re-transfer per launch,
        # swamping both throughput and the fit
        import jax.numpy as jnp
        return jnp.asarray(rng.random((k, n_devices, 2),
                                      np.float32).astype(np.float16))

    pos_rounds = staged(rounds)

    @jax.jit
    def notify(regions):  # [K, n, B] — per-region delivery counts
        # per-round MXU segment sums (each region count <= B < 2^24 stays
        # exact in f32), then an int32 reduction over rounds — one flat
        # f32 accumulation would round once a region passes 2^24 events
        flat = regions.reshape(regions.shape[0], -1)

        def one(r):
            return segment_sum_onehot(jnp.ones_like(r, jnp.float32), r,
                                      N_REGIONS)

        return jnp.sum(jax.vmap(one)(flat).astype(jnp.int32), axis=0)

    def super_round(buf):
        out = rt.call_batch_rounds(DeviceVectorGrain, "fix", keys,
                                   {"pos": buf}, plan=plan,
                                   device_results=True)
        return notify(out)

    counts = super_round(pos_rounds)
    jax.block_until_ready(counts)
    assert int(jnp.sum(counts)) == rounds * plan.B  # all fixes bucketed
    t0 = time.perf_counter()
    for _ in range(iters):
        counts = super_round(pos_rounds)
    jax.block_until_ready(counts)
    elapsed = time.perf_counter() - t0
    events = iters * rounds * n_devices

    # ---- attribution + roofline (benchmarks/attribution.py) ----------
    get_staged = staged_cache(staged)

    def run_blocking(k: int) -> float:
        buf = get_staged(k)
        t0 = time.perf_counter()
        jax.block_until_ready(super_round(buf))
        return time.perf_counter() - t0

    # S_A = 64 floor: one fix round is sub-0.2 ms of device time, so a
    # shorter lever arm leaves the slope below timer noise (the same
    # S_A>=8 rule bench.py applies to heartbeats, scaled to this kernel)
    s_a = max(64, rounds)
    fit = two_point_fit(run_blocking, s_a, 2 * s_a, reps=reps)
    # per event: pos read+write (2*8 B f32) + fixes r/w (2*4) + staged
    # fix read (2*2) + region emit (4) + notify re-read (4); the one-hot
    # fan-in matmul's [B, 256] intermediate is fused, not re-materialized
    bytes_per_round = n_devices * (16 + 8 + 4 + 4 + 4)
    roof = roofline_fields(fit, bytes_per_unit=bytes_per_round)
    fit.pop("device_unit_s", None)

    return {
        "metric": "gpstracker_device_events_per_sec",
        "value": round(events / elapsed, 1),
        "unit": "events/sec/chip",
        "vs_baseline": None,
        "extra": {"devices": n_devices, "rounds_per_upload": rounds,
                  "iters": iters, "regions": N_REGIONS,
                  "bytes_per_event_model": 36, **fit, **roof},
    }


async def run(n_devices: int = 64, batch: int = 64, seconds: float = 3.0,
              vec_devices: int = 100_000, vec_rounds: int = 64,
              vec_iters: int = 10) -> list[dict]:
    host = await bench_host_streams(n_devices, batch, seconds)
    dev = bench_device_tier(vec_devices, vec_rounds, vec_iters)
    return [host, dev]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=64)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--vec-devices", type=int, default=100_000)
    a = ap.parse_args()
    for r in asyncio.run(run(a.devices, a.batch, a.seconds,
                             vec_devices=a.vec_devices)):
        print(json.dumps(r))


if __name__ == "__main__":
    main()
