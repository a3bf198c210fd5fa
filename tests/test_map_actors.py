"""MapReduce-over-actors bulk collectives (ISSUE 13): map_actors /
reduce_actors / broadcast_actors / join_when on the vector runtime, the
dispatcher's one-envelope-per-silo bulk surface, reduction determinism
against the host-side fold, and fence safety under grow/migration/
checkpoint racing bulk ticks."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.dispatch import (
    VectorGrain,
    VectorRuntime,
    actor_method,
    add_vector_grains,
    reshard_dense,
)
from orleans_tpu.parallel import make_mesh
from orleans_tpu.runtime import ClusterClient, InProcFabric, SiloBuilder


class Cell(VectorGrain):
    STATE = {"total": (jnp.int32, ()), "hits": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"total": jnp.int32(0), "hits": jnp.int32(0)}

    @actor_method(args={"c": (jnp.int32, ())})
    def add(state, args):
        new = {"total": state["total"] + args["c"],
               "hits": state["hits"] + 1}
        return new, new["total"]

    @actor_method(read_only=True)
    def read(state, args):
        return state, state["total"]

    @actor_method(read_only=True)
    def ready(state, args):
        return state, (state["hits"] >= 2).astype(jnp.int32)


class FloatCell(VectorGrain):
    STATE = {"v": (jnp.float32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"v": jnp.float32(0)}

    @actor_method(args={"x": (jnp.float32, ())})
    def add(state, args):
        return {"v": state["v"] + args["x"]}, state["v"] + args["x"]

    @actor_method(read_only=True)
    def read(state, args):
        return state, state["v"]


def _rt(n_shards=4, dense=None, capacity=64) -> VectorRuntime:
    rt = VectorRuntime(mesh=make_mesh(n_shards),
                       capacity_per_shard=capacity)
    rt.register(Cell)
    if dense:
        rt.table(Cell).ensure_dense(dense)
    return rt


# ---------------------------------------------------------------------------
# Engine semantics
# ---------------------------------------------------------------------------

async def test_map_actors_all_live_dense_and_hashed():
    rt = _rt(dense=32)
    # live set: 6 dense actors + 3 hashed actors
    for k in range(6):
        rt.call(Cell, k, "add", c=np.int32(1))
    hashed = [10**13 + i * 7919 for i in range(3)]
    for k in hashed:
        rt.call(Cell, k, "add", c=np.int32(1))
    await rt.flush()
    n = await rt.map_actors(Cell, "add", {"c": np.int32(5)})
    assert n == 9
    tbl = rt.table(Cell)
    for k in list(range(6)) + hashed:
        assert int(tbl.read_row(k)["total"]) == 6
    # untouched dense keys stayed un-activated (map targets LIVE actors)
    assert int(tbl.dense_active.sum()) == 6


async def test_map_actors_subset_activates_dense_keys():
    rt = _rt(dense=32)
    n = await rt.map_actors(Cell, "add", {"c": np.int32(7)},
                            keys=np.arange(10, 20))
    assert n == 10
    tbl = rt.table(Cell)
    assert int(tbl.read_row(15)["total"]) == 7
    assert int(tbl.read_row(15)["hits"]) == 1
    assert not tbl.dense_active[:10].any()
    # duplicate keys in the subset collapse to one message per actor
    n2 = await rt.map_actors(Cell, "add", {"c": np.int32(1)},
                             keys=np.array([10, 10, 11, 11, 11]))
    assert n2 == 2
    # non-resident hashed keys are skipped, resident ones apply
    rt.call(Cell, 10**14, "add", c=np.int32(1))
    await rt.flush()
    n3 = await rt.map_actors(Cell, "add", {"c": np.int32(1)},
                             keys=np.array([10**14, 10**14 + 1]))
    assert n3 == 1


async def test_map_actors_defers_conflicting_per_key_turns():
    rt = _rt(dense=16)
    futs = [rt.call(Cell, k, "add", c=np.int32(1)) for k in range(8)]
    # the per-key turns are still pending: the bulk apply must defer
    # those keys (turn semantics), then apply them in a later round
    n = await rt.map_actors(Cell, "add", {"c": np.int32(10)})
    assert n == 8
    await rt.flush()
    for f in futs:
        await f
    s = await rt.reduce_actors(Cell, "read", combine="sum")
    assert int(s) == 8 * 11  # both the per-key add AND the bulk add ran


async def test_map_actors_worker_parity():
    rt = _rt(dense=16)
    try:
        futs = [rt.call(Cell, k, "add", c=np.int32(2)) for k in range(16)]
        n = await rt.map_actors(Cell, "add", {"c": np.int32(3)})
        assert n == 16
        await rt.flush()
        for f in futs:
            await f
        s = await rt.reduce_actors(Cell, "read", combine="sum")
        assert int(s) == 16 * 5
    finally:
        rt.shutdown_worker()


# ---------------------------------------------------------------------------
# Reduction determinism: device reduce == host fold
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
async def test_reduce_int_exactly_matches_host_fold(n_shards):
    """Property (ISSUE 13 satellite): int reduction is EXACTLY the
    host-side fold regardless of shard count or key order."""
    rng = np.random.default_rng(n_shards)
    keys = rng.permutation(48)
    vals = rng.integers(-1000, 1000, 48).astype(np.int32)
    rt = VectorRuntime(mesh=make_mesh(n_shards), capacity_per_shard=64)
    rt.register(Cell)
    rt.table(Cell).ensure_dense(48)
    rt.call_batch(Cell, "add", keys, {"c": vals})
    got = await rt.reduce_actors(Cell, "read", combine="sum")
    assert int(got) == int(vals.sum())
    assert int(await rt.reduce_actors(Cell, "read", combine="max")) == \
        int(vals.max())
    assert int(await rt.reduce_actors(Cell, "read", combine="min")) == \
        int(vals.min())


async def test_reduce_int_survives_reshard_roundtrip():
    """The fold is invariant under elastic resharding: 4 → 8 → 3 shards
    reduce to the identical integer every time."""
    rng = np.random.default_rng(7)
    vals = rng.integers(0, 10000, 64).astype(np.int32)
    rt = VectorRuntime(mesh=make_mesh(4), capacity_per_shard=16)
    rt.register(Cell)
    rt.table(Cell).ensure_dense(64)
    rt.call_batch(Cell, "add", np.arange(64), {"c": vals})
    expect = int(vals.sum())
    assert int(await rt.reduce_actors(Cell, "read")) == expect
    for n_to in (8, 3):
        rt2 = VectorRuntime(mesh=make_mesh(n_to), capacity_per_shard=32)
        rt2.tables[Cell] = reshard_dense(rt.table(Cell), rt2)
        assert int(await rt2.reduce_actors(Cell, "read")) == expect
        rt = rt2


async def test_reduce_float_within_tolerance_and_mean():
    rng = np.random.default_rng(3)
    vals = rng.normal(size=40).astype(np.float32)
    for n_shards in (1, 4):
        rt = VectorRuntime(mesh=make_mesh(n_shards),
                           capacity_per_shard=64)
        rt.register(FloatCell)
        rt.table(FloatCell).ensure_dense(40)
        rt.call_batch(FloatCell, "add", np.arange(40), {"x": vals})
        got = await rt.reduce_actors(FloatCell, "read", combine="sum")
        assert np.isclose(float(got), float(vals.sum()), rtol=1e-5)
        mean = await rt.reduce_actors(FloatCell, "read", combine="mean")
        assert np.isclose(float(mean), float(vals.mean()), rtol=1e-5)


async def test_reduce_empty_population_returns_none():
    rt = _rt(dense=8)
    assert await rt.reduce_actors(Cell, "read") is None
    assert await rt.reduce_actors(Cell, "read", combine="mean") is None
    with pytest.raises(ValueError):
        await rt.reduce_actors(Cell, "read", combine="median")


# ---------------------------------------------------------------------------
# Broadcast
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 4])
async def test_broadcast_delivers_every_edge(n_shards):
    rt = VectorRuntime(mesh=make_mesh(n_shards), capacity_per_shard=64)
    rt.register(Cell)
    rt.table(Cell).ensure_dense(64)
    rng = np.random.default_rng(5)
    targets = rng.integers(0, 64, 200)
    payload = rng.integers(1, 9, 200).astype(np.int32)
    d = await rt.broadcast_actors(Cell, "add", targets, {"c": payload})
    assert d == 200
    tbl = rt.table(Cell)
    for k in np.unique(targets):
        m = targets == k
        assert int(tbl.read_row(int(k))["total"]) == int(payload[m].sum())
        assert int(tbl.read_row(int(k))["hits"]) == int(m.sum())


async def test_broadcast_scalar_payload_and_range_check():
    rt = _rt(dense=16)
    d = await rt.broadcast_actors(Cell, "add", np.array([1, 1, 1, 2]),
                                  {"c": np.int32(3)})
    assert d == 4
    assert int(rt.table(Cell).read_row(1)["total"]) == 9
    with pytest.raises(ValueError):
        await rt.broadcast_actors(Cell, "add", np.array([999]),
                                  {"c": np.int32(1)})


async def test_broadcast_marks_write_behind_dirty_keys():
    """Regression: broadcast-applied writes must reach the write-behind
    flusher — the target keys live on the host, so the device-resident
    exchange exemption does not apply; without the marks a restart
    silently reverts every broadcast-delivered update."""
    rt = _rt(dense=16)
    rt.enable_dirty_tracking()
    targets = np.array([2, 3, 3, 5])
    await rt.broadcast_actors(Cell, "add", targets, {"c": np.int32(1)})
    dirty = rt.drain_dirty(Cell)
    assert set(dirty.tolist()) >= {2, 3, 5}
    # read-only bulk ops mark nothing
    await rt.reduce_actors(Cell, "read")
    assert rt.drain_dirty(Cell).size == 0
    # map_actors marks too (the sibling path, for contrast)
    await rt.map_actors(Cell, "add", {"c": np.int32(1)})
    assert set(rt.drain_dirty(Cell).tolist()) == {2, 3, 5}


async def test_broadcast_defers_conflicting_targets():
    rt = _rt(dense=16)
    futs = [rt.call(Cell, k, "add", c=np.int32(1)) for k in (3, 4)]
    d = await rt.broadcast_actors(Cell, "add", np.array([3, 4, 5]),
                                  {"c": np.int32(10)})
    assert d == 3
    await rt.flush()
    for f in futs:
        await f
    assert int(rt.table(Cell).read_row(3)["total"]) == 11
    assert int(rt.table(Cell).read_row(5)["total"]) == 10


# ---------------------------------------------------------------------------
# join_when
# ---------------------------------------------------------------------------

async def test_join_when_fires_at_k():
    rt = _rt(dense=16)
    keys = np.arange(6)

    async def feed():
        for _ in range(2):
            await asyncio.sleep(0.01)
            await rt.map_actors(Cell, "add", {"c": np.int32(1)},
                                keys=keys[:4])

    t = asyncio.ensure_future(feed())
    got = await rt.join_when(Cell, keys, k=4, method="ready",
                             timeout=5.0)
    await t
    assert got >= 4


async def test_join_when_times_out():
    rt = _rt(dense=8)
    await rt.map_actors(Cell, "add", {"c": np.int32(1)},
                        keys=np.arange(3))
    with pytest.raises(asyncio.TimeoutError):
        await rt.join_when(Cell, np.arange(3), method="ready",
                           timeout=0.05, poll=0.01)


# ---------------------------------------------------------------------------
# Fence safety: grow / migration / checkpoint racing bulk ops
# ---------------------------------------------------------------------------

async def test_bulk_ops_survive_table_grow_racing(request):
    """Continuous bulk ticks (off-loop worker live) while hashed
    allocations force grow(): every write lands, none truncated."""
    rt = VectorRuntime(mesh=make_mesh(2), capacity_per_shard=8)
    rt.register(Cell)
    request.addfinalizer(rt.shutdown_worker)
    base = 10**13
    alive = []

    async def allocate():
        for i in range(64):  # far past 2 shards x 8 slots: several grows
            k = base + i * 7919
            alive.append(k)
            rt.call(Cell, k, "add", c=np.int32(1))
            if i % 8 == 7:
                await asyncio.sleep(0)

    alloc = asyncio.ensure_future(allocate())
    maps = 0
    while not alloc.done():
        maps += await rt.map_actors(Cell, "add", {"c": np.int32(1)})
        await asyncio.sleep(0)
    await alloc
    await rt.flush()
    final = await rt.map_actors(Cell, "add", {"c": np.int32(1)})
    assert final == 64
    s = await rt.reduce_actors(Cell, "read", combine="sum")
    host = sum(int(rt.table(Cell).read_row(k)["total"]) for k in alive)
    assert int(s) == host
    total_hits = sum(int(rt.table(Cell).read_row(k)["hits"])
                     for k in alive)
    assert total_hits == 64 + maps + final  # per-key + every bulk round


async def test_bulk_ops_safe_across_migration_rounds():
    """move_rows between bulk rounds: locations re-resolve per round, so
    a migrated key's next bulk tick lands in the NEW row."""
    rt = _rt(n_shards=4, capacity=16)
    keys = [10**12 + i * 104729 for i in range(12)]
    for k in keys:
        rt.call(Cell, k, "add", c=np.int32(2))
    await rt.flush()
    tbl = rt.table(Cell)
    # migrate a third of the keys to different shards
    moved = keys[::3]
    dests = [(tbl.key_to_slot[k][0] + 1) % 4 for k in moved]
    assert tbl.move_rows(moved, dests) == len(moved)
    n = await rt.map_actors(Cell, "add", {"c": np.int32(5)})
    assert n == 12
    for k in keys:
        assert int(tbl.read_row(k)["total"]) == 7
    s = await rt.reduce_actors(Cell, "read", combine="sum")
    assert int(s) == 12 * 7


async def test_bulk_in_flight_keys_are_fenced(request):
    """While an off-loop per-key batch is in flight, a concurrent bulk
    apply defers those keys (pending_key_hashes covers the worker)."""
    rt = _rt(dense=8)
    request.addfinalizer(rt.shutdown_worker)
    futs = [rt.call(Cell, k, "add", c=np.int32(1)) for k in range(8)]
    # hand the batch to the worker, then immediately bulk-apply
    n = await rt.map_actors(Cell, "add", {"c": np.int32(10)})
    assert n == 8
    await rt.flush()
    for f in futs:
        await f
    s = await rt.reduce_actors(Cell, "read", combine="sum")
    assert int(s) == 8 * 11


async def test_bulk_snapshot_restore_roundtrip_under_traffic():
    """Checkpoint capture racing bulk ticks: the fence serializes the
    snapshot against in-flight kernels, and restore round-trips."""
    rt = _rt(dense=16)
    await rt.map_actors(Cell, "add", {"c": np.int32(3)},
                        keys=np.arange(16))
    tbl = rt.table(Cell)

    async def storm():
        for _ in range(4):
            await rt.map_actors(Cell, "add", {"c": np.int32(1)})
            await asyncio.sleep(0)

    t = asyncio.ensure_future(storm())
    snap = tbl.snapshot()  # fenced: never materializes a donated array
    await t
    before = await rt.reduce_actors(Cell, "read", combine="sum")
    tbl.restore(snap)
    after = await rt.reduce_actors(Cell, "read", combine="sum")
    assert int(after) <= int(before)
    assert int(after) % 16 == 0  # a consistent whole-population state


# ---------------------------------------------------------------------------
# Client surface: one envelope per silo, not one per actor/edge
# ---------------------------------------------------------------------------

def _cell_silo_builder(name, fabric=None, n_dense=64):
    b = SiloBuilder().with_name(name)
    if fabric is not None:
        b = b.with_fabric(fabric)
    add_vector_grains(b, Cell, mesh=make_mesh(2), capacity_per_shard=64,
                      dense={Cell: n_dense})
    return b


async def test_client_bulk_ops_single_silo_o1_envelopes():
    silo = _cell_silo_builder("bulk-1").build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        for k in range(8):
            await client.get_grain(Cell, k).add(c=np.int32(1))
        base = silo.stats.get("messaging.received.application")
        assert await client.map_actors(Cell, "add",
                                       {"c": np.int32(4)}) == 8
        assert int(await client.reduce_actors(Cell, "read")) == 8 * 5
        targets = np.repeat(np.arange(16), 8)  # fan-out 128 edges
        assert await client.broadcast_actors(
            Cell, "add", targets, {"c": np.ones(128, np.int32)}) == 128
        # the acceptance assertion: 3 bulk ops covering 128 edges + a
        # whole population cost O(1) application envelopes, not O(edges)
        assert silo.stats.get("messaging.received.application") \
            - base <= 6
        assert silo.stats.get("vector.bulk.delivered") == 128
        got = await client.join_when(Cell, list(range(8)),
                                     method="ready", timeout=5.0)
        assert got == 8
    finally:
        await client.close_async()
        await silo.stop()


async def test_client_bulk_ops_partition_across_silos():
    fabric = InProcFabric()
    silos = []
    for i in range(2):
        s = _cell_silo_builder(f"bulk-s{i}", fabric).build()
        await s.start()
        silos.append(s)
    client = await ClusterClient(fabric).connect()
    try:
        for k in range(16):
            await client.get_grain(Cell, k).add(c=np.int32(1))
        live = [int(s.vector.table(Cell).dense_active.sum())
                for s in silos]
        assert sum(live) == 16 and all(v > 0 for v in live), live
        assert await client.map_actors(Cell, "add",
                                       {"c": np.int32(2)}) == 16
        assert int(await client.reduce_actors(Cell, "read")) == 16 * 3
        # keyed map: each key applies EXACTLY once cluster-wide
        assert await client.map_actors(Cell, "add", {"c": np.int32(1)},
                                       keys=list(range(32))) == 32
        # broadcast partitions edges by ring ownership at the anchor
        targets = np.arange(32)
        assert await client.broadcast_actors(
            Cell, "add", targets, {"c": np.full(32, 10, np.int32)}) == 32
        total = await client.reduce_actors(Cell, "read")
        # 16 actors: 1+2+1+10; the other 16: 1+10
        assert int(total) == 16 * 14 + 16 * 11
        got = sum(s.stats.get("vector.bulk.delivered") for s in silos)
        assert got == 32
        mean = await client.reduce_actors(Cell, "read", combine="mean")
        assert float(mean) == pytest.approx((16 * 14 + 16 * 11) / 32)
    finally:
        await client.close_async()
        for s in silos:
            await s.stop()


async def test_broadcast_replicated_feature_arg_not_sliced_at_anchor():
    """Multi-silo regression: a REPLICATED feature-vector arg whose
    length happens to equal the edge count must not be sliced per edge
    by the anchor's partition (the schema, not the array shape, decides
    per-edge vs replicated) — a peer owning k < E edges would receive a
    k-length fragment and fail the whole collective."""
    import jax.numpy as jnp

    from orleans_tpu.dispatch import VectorGrain, actor_method

    class WeightedCell(VectorGrain):
        STATE = {"acc": (jnp.float32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"acc": jnp.float32(0)}

        # w is a REPLICATED (4,)-feature vector; x is per-edge
        @actor_method(args={"w": (jnp.float32, (4,)),
                            "x": (jnp.float32, ())})
        def apply(state, args):
            new = {"acc": state["acc"]
                   + args["x"] * args["w"].sum()}
            return new, new["acc"]

        @actor_method(read_only=True)
        def read(state, args):
            return state, state["acc"]

    fabric = InProcFabric()
    silos = []
    for i in range(2):
        b = SiloBuilder().with_name(f"wcell-s{i}").with_fabric(fabric)
        add_vector_grains(b, WeightedCell, mesh=make_mesh(2),
                          capacity_per_shard=16,
                          dense={WeightedCell: 8})
        s = b.build()
        await s.start()
        silos.append(s)
    client = await ClusterClient(fabric).connect()
    try:
        # E == 4 == len(w): the ambiguous case the shape heuristic got
        # wrong; x (per-edge) must slice, w (feature) must replicate
        targets = np.arange(4)
        w = np.full(4, 0.5, np.float32)
        x = np.arange(1, 5, dtype=np.float32)
        assert await client.broadcast_actors(
            WeightedCell, "apply", targets, {"w": w, "x": x}) == 4
        total = await client.reduce_actors(WeightedCell, "read")
        assert float(total) == pytest.approx(float(x.sum() * w.sum()))
    finally:
        await client.close_async()
        for s in silos:
            await s.stop()


async def test_bulk_storm_holds_qos_invariant():
    """The acceptance gate: a bulk-collective storm on a 2-silo
    MEMBERSHIP cluster must leave the PING lane clean — bulk traffic
    rides APPLICATION end to end (never the QoS queues or flush
    accumulators), so the probe SLI stays >= 90% under the probe
    timeout, zero suspicion votes land, and membership stays stable
    (the gauntlet's flash-crowd QoS gate, re-driven by collectives)."""
    from orleans_tpu.membership import InMemoryMembershipTable, join_cluster
    from orleans_tpu.observability.stats import SLO_STATS, Histogram
    from orleans_tpu.storage import MemoryStorage

    fast = dict(
        membership_probe_period=0.1,
        membership_probe_timeout=0.3,
        membership_missed_probes_limit=3,
        membership_votes_needed=2,
        membership_iam_alive_period=0.5,
        membership_refresh_period=0.3,
        membership_vote_expiration=5.0,
        response_timeout=5.0,
    )
    fabric = InProcFabric()
    table = InMemoryMembershipTable()
    rng = np.random.default_rng(11)
    silos = []
    for i in range(2):
        b = (_cell_silo_builder(f"qos-s{i}", fabric, n_dense=256)
             .with_storage("Default", MemoryStorage())
             .with_config(**fast))
        s = b.build()
        # warm the bulk kernels BEFORE membership probing starts: the
        # first-ever tick/exchange shapes jit-compile synchronously on
        # the shared loop, and a multi-second compile stall would get a
        # healthy silo voted dead before the storm even begins — the
        # storm must measure steady-state QoS, not one-time XLA compiles
        await s.vector.broadcast_actors(
            Cell, "add", rng.integers(0, 256, 512),
            {"c": np.ones(512, np.int32)})
        await s.vector.map_actors(Cell, "add", {"c": np.int32(1)})
        join_cluster(s, table)
        await s.start()
        silos.append(s)
    client = await ClusterClient(fabric).connect()
    try:
        # one CLIENT-path round before the clock starts: the anchor
        # partitions edges into per-silo slices whose bucket shapes
        # differ from the silo-local warmup above, so the first
        # client-path round still compiles (~0.5s here) — that belongs
        # to warmup, not the measured storm window
        await client.broadcast_actors(Cell, "add",
                                      rng.integers(0, 256, 512),
                                      {"c": np.ones(512, np.int32)})
        await client.map_actors(Cell, "add", {"c": np.int32(1)})
        deadline = asyncio.get_running_loop().time() + 1.6
        storms = 0
        while asyncio.get_running_loop().time() < deadline:
            targets = rng.integers(0, 256, 512)
            await client.broadcast_actors(
                Cell, "add", targets,
                {"c": np.ones(512, np.int32)})
            await client.map_actors(Cell, "add", {"c": np.int32(1)})
            storms += 1
        assert storms >= 3  # the storm actually ran
        # probe SLI: >= 90% of probes provably under the timeout
        agg = None
        for s in silos:
            h = s.stats.histograms.get(SLO_STATS["probe_rtt"])
            if h is not None and h.total:
                snap = Histogram.from_snapshot(h.summary())
                agg = snap if agg is None else agg.merge(snap)
        assert agg is not None and agg.total >= 4, "no probes observed"
        sli = agg.good_below(fast["membership_probe_timeout"]) / agg.total
        assert sli >= 0.9, f"probe SLI {sli:.2f} under bulk storm"
        # zero false suspicion votes, membership stable at 2
        snap = await table.read_all()
        votes = sum(len(e.suspect_times) for e, _ in snap.entries)
        assert votes == 0
        assert all(len(s.membership.active) == 2 for s in silos)
    finally:
        await client.close_async()
        for s in silos:
            await s.stop()


async def test_client_bulk_bad_spec_and_unknown_method_error():
    silo = _cell_silo_builder("bulk-err").build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        with pytest.raises(AttributeError):
            await client.map_actors(Cell, "no_such_method")
        with pytest.raises(TypeError):
            await client.map_actors(Cell, "add", {"bogus": 1})
    finally:
        await client.close_async()
        await silo.stop()
