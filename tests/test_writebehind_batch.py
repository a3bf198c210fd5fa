"""The write-behind flush as one columnar pass: a provider's own
``write_many`` (MemoryStorage) against the per-key default of
``GrainStorage`` — same stored records, same etag discipline, same fault
paths — and the pass's cost guards (tasks created, gathers compiled), none
of which reads a clock."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.core.errors import InconsistentStateError
from orleans_tpu.dispatch import (VectorGrain, VectorRuntime, actor_method,
                                  add_vector_grains)
from orleans_tpu.observability.stats import FLUSH_STATS
from orleans_tpu.parallel import make_mesh
from orleans_tpu.runtime import ClusterClient, SiloBuilder
from orleans_tpu.storage import (ADOPT_ETAG, ErrorInjectionStorage,
                                 GrainStorage, LatencyStorage, MemoryStorage,
                                 VectorStorageBridge)
from orleans_tpu.storage.checkpoint import _gather_rows


class PerKeyOnly(GrainStorage):
    """A provider that knows only per-key operations (what FileStorage,
    the injection wrappers and any user provider are): ``write_many`` is
    the base class's default."""

    def __init__(self, inner: GrainStorage | None = None) -> None:
        self.inner = inner or MemoryStorage()

    async def read(self, grain_type, grain_id):
        return await self.inner.read(grain_type, grain_id)

    async def write(self, grain_type, grain_id, state, etag):
        return await self.inner.write(grain_type, grain_id, state, etag)

    async def clear(self, grain_type, grain_id, etag):
        return await self.inner.clear(grain_type, grain_id, etag)


class FailKeys(ErrorInjectionStorage):
    """ErrorInjectionStorage failing the writes of some keys only."""

    def __init__(self, inner: GrainStorage, bad: set) -> None:
        super().__init__(inner)
        self.bad = bad

    async def write(self, grain_type, grain_id, state, etag):
        if grain_id.key in self.bad:
            raise IOError("injected write failure")
        return await super().write(grain_type, grain_id, state, etag)


PROVIDERS = {"batched": MemoryStorage, "per_key": PerKeyOnly,
             "latency": lambda: LatencyStorage(MemoryStorage(), 0.001)}


def _grain(dtype, shape):
    """A vector grain with one STATE field of (dtype, shape) and a method
    that stores its argument into it."""
    class Cell(VectorGrain):
        STATE = {"v": (dtype, shape), "n": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"v": jnp.zeros(shape, dtype), "n": jnp.int32(0)}

        @actor_method(args={"v": (dtype, shape)})
        def put(state, args):
            return {"v": args["v"], "n": state["n"] + 1}, state["n"] + 1

    Cell.__name__ = Cell.__qualname__ = \
        f"Cell_{np.dtype(dtype).name}_{'x'.join(map(str, shape)) or 's'}"
    return Cell


# every STATE dtype the repo's vector grains declare (f32 and i32, scalars
# and vectors), with the values a Python round trip could get wrong
F32_EDGE = np.array([0.1, -0.0, np.inf, -np.inf, np.nan, 1e-45, 3.4028235e38,
                     16777217.0, -1.17549435e-38, 1 / 3], np.float32)
I32_EDGE = np.array([0, -1, 1, 2**31 - 1, -2**31, 256, 257, 16777217,
                     -16777217, 123456789], np.int32)
# on a table sharded over several devices XLA partitions the gather into
# masked per-shard gathers and a sum, which turns -0.0 into 0.0 and flushes
# denormals (the eager gather before the columnar flush did the same): the
# bit-for-bit cases run on one device, the sharded ones without those two
F32_SHARDED = np.where((F32_EDGE == 0) | (np.abs(F32_EDGE) < 1e-38),
                       np.float32(0.5), F32_EDGE)
DTYPES = {
    "f32": (jnp.float32, (), F32_EDGE, 1),
    "f32x2": (jnp.float32, (2,), np.stack([F32_EDGE, F32_EDGE[::-1]], 1), 1),
    "f32x2x3": (jnp.float32, (2, 3),
                np.tile(F32_EDGE[:, None, None], (1, 2, 3)), 1),
    "i32": (jnp.int32, (), I32_EDGE, 1),
    "i32x2": (jnp.int32, (2,), np.stack([I32_EDGE, I32_EDGE[::-1]], 1), 1),
    "f32x2-sharded": (jnp.float32, (2,),
                      np.stack([F32_SHARDED, F32_SHARDED[::-1]], 1), 8),
    "i32-sharded": (jnp.int32, (), I32_EDGE, 8),
}
N = len(F32_EDGE)


def _runtime(cls, n=N, mesh=8, cap=32) -> VectorRuntime:
    rt = VectorRuntime(mesh=make_mesh(mesh), capacity_per_shard=cap)
    rt.table(cls).ensure_dense(n)
    return rt


def _device_rows(rt, cls, n: int) -> dict:
    """Dense rows 0..n-1 of the device table, per field, as host arrays."""
    tbl = rt.table(cls)
    k = np.arange(n)
    return {f: a[k // tbl.dense_per_shard, k % tbl.dense_per_shard]
            for f, a in tbl.snapshot().items()}


def _bits(rows: dict) -> dict:
    return {f: np.ascontiguousarray(a).view(np.uint8)
            for f, a in rows.items()}


def _same(a, b) -> bool:
    """Equal values of equal Python types, NaN equal to NaN."""
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, list):
        return isinstance(b, list) and len(a) == len(b) and \
            all(_same(x, y) for x, y in zip(a, b))
    return type(a) is type(b) and (a == b or (a != a and b != b))


@pytest.mark.parametrize("case", list(DTYPES))
async def test_batched_and_per_key_writes_leave_the_same_records(case):
    dtype, shape, values, mesh = DTYPES[case]
    cls = _grain(dtype, shape)
    rt = _runtime(cls, mesh=mesh)
    keys = list(range(N))
    rt.call_batch(cls, "put", np.arange(N), {"v": values})
    stores = {name: PROVIDERS[name]() for name in ("batched", "per_key")}
    bridges = {name: VectorStorageBridge(rt, cls, s)
               for name, s in stores.items()}
    assert bridges["batched"].batched and not bridges["per_key"].batched

    etags = {}
    for rnd in (1, 2):
        for name, b in bridges.items():
            assert await b.flush(keys) == N
            assert sorted(b._etags) == keys
            for k in keys:  # the bridge remembers what the store holds
                _, stored = await stores[name].read(cls.__name__,
                                                    b._grain_id(k))
                assert stored == b._etags[k]
            if rnd == 2:  # every key's etag moved on, on both paths
                assert all(b._etags[k] != etags[name][k] for k in keys)
            etags[name] = dict(b._etags)
        # value for value what the device holds, on both paths alike
        rows = _device_rows(rt, cls, N)
        for k in keys:
            gid = bridges["batched"]._grain_id(k)
            got = {name: (await s.read(cls.__name__, gid))[0]
                   for name, s in stores.items()}
            assert _same(got["batched"], got["per_key"])
            for f in ("v", "n"):
                assert np.array_equal(np.asarray(got["batched"][f]),
                                      rows[f][k], equal_nan=True)
        if rnd == 1:  # move every row before the second flush
            rt.call_batch(cls, "put", np.arange(N), {"v": values[::-1]})

    # load into a fresh table restores every row bit for bit
    want = _bits(_device_rows(rt, cls, N))
    for name, s in stores.items():
        rt2 = _runtime(cls, mesh=mesh)
        assert await VectorStorageBridge(rt2, cls, s).load(keys) == keys
        got = _bits(_device_rows(rt2, cls, N))
        for f in want:
            assert np.array_equal(got[f], want[f]), (name, f)


async def test_records_in_the_numpy_representation_stay_loadable():
    """What the flush wrote before it was columnar: a dict of numpy
    scalars and arrays per row."""
    dtype, shape, values, mesh = DTYPES["f32x2"]
    cls = _grain(dtype, shape)
    storage = MemoryStorage()
    rt = _runtime(cls, mesh=mesh)
    bridge = VectorStorageBridge(rt, cls, storage)
    for k in range(N):
        await storage.write(cls.__name__, bridge._grain_id(k),
                            {"v": values[k], "n": np.int32(k + 7)}, None)
    assert await bridge.load(range(N)) == list(range(N))
    rows = _device_rows(rt, cls, N)
    assert np.array_equal(_bits(rows)["v"], values.view(np.uint8))
    assert rows["n"].tolist() == [k + 7 for k in range(N)]
    # and the next flush writes over them with the etags load adopted
    assert await bridge.flush(range(N)) == N
    state, _ = await storage.read(cls.__name__, bridge._grain_id(3))
    assert type(state["n"]) is int and state["n"] == 10
    assert isinstance(state["v"], list)


Counter = _grain(jnp.int32, ())


async def _put(rt, keys, value):
    futs = [rt.call(Counter, int(k), "put", v=np.int32(value)) for k in keys]
    await rt.flush()
    await asyncio.gather(*futs)


async def test_injected_failures_remark_only_the_failed_keys():
    rt = _runtime(Counter, 8)
    rt.enable_dirty_tracking()
    inner = MemoryStorage()
    bridge = VectorStorageBridge(rt, Counter, FailKeys(inner, {2, 5}))
    assert not bridge.batched  # a wrapper has only the per-key default
    await _put(rt, range(8), 3)
    rt.drain_dirty(Counter)
    assert await bridge.flush(range(8)) == 6
    assert sorted(rt.drain_dirty(Counter).tolist()) == [2, 5]
    for k in range(8):
        state, _ = await inner.read(Counter.__name__, bridge._grain_id(k))
        assert (state is None) == (k in (2, 5))
    # strict (the final drain at stop) re-raises after re-marking
    with pytest.raises(IOError, match="injected write failure"):
        await bridge.flush(range(8), strict=True)
    assert sorted(rt.drain_dirty(Counter).tolist()) == [2, 5]


async def test_a_bridge_without_dirty_tracking_never_reports_silent_success():
    rt = _runtime(Counter, 4)
    storage = ErrorInjectionStorage(MemoryStorage())
    storage.fail_writes = True
    bridge = VectorStorageBridge(rt, Counter, storage)
    await _put(rt, range(4), 1)
    with pytest.raises(IOError, match="injected write failure"):
        await bridge.flush(range(4))


@pytest.mark.parametrize("kind", list(PROVIDERS))
async def test_etag_conflict_releases_the_row_and_never_overwrites(kind):
    storage = PROVIDERS[kind]()
    rt_old, rt_new = _runtime(Counter, 4), _runtime(Counter, 4)
    for rt in (rt_old, rt_new):
        rt.enable_dirty_tracking()
    old = VectorStorageBridge(rt_old, Counter, storage)
    new = VectorStorageBridge(rt_new, Counter, storage)
    hashed = 1 << 40  # one key of the hashed regime beside the dense ones
    for rt in (rt_old, rt_new):
        rt.table(Counter).lookup_or_allocate(hashed)
    keys = [0, 1, 2, 3, hashed]
    await _put(rt_old, keys, 10)
    assert await old.flush(keys) == 5
    # ownership moved: the new owner loads, writes and persists 1 and hashed
    assert await new.load([1, hashed]) == [1, hashed]
    await _put(rt_new, [1, hashed], 20)
    assert await new.flush([1, hashed]) == 2
    # the stale ex-owner writes again and flushes: its etags for 1 and
    # hashed are stale
    await _put(rt_old, keys, 30)
    rt_old.drain_dirty(Counter)
    assert await old.flush(keys) == 3
    assert old.storage_conflicts == 2
    assert 1 not in old._etags and hashed not in old._etags
    tbl = rt_old.table(Counter)
    assert not tbl.dense_active[1] and tbl.lookup(hashed) is None
    assert rt_old.drain_dirty(Counter).size == 0  # not a failure: no re-mark
    for k, want in ((0, 30), (1, 20), (2, 30), (3, 30), (hashed, 20)):
        state, _ = await storage.read(Counter.__name__, old._grain_id(k))
        assert state["v"] == want, k


@pytest.mark.parametrize("kind", list(PROVIDERS))
async def test_a_bridge_with_no_etag_memory_adopts_the_stored_etag(kind):
    storage = PROVIDERS[kind]()
    rt = _runtime(Counter, 4)
    await _put(rt, range(4), 1)
    assert await VectorStorageBridge(rt, Counter, storage).flush(range(4)) == 4
    await _put(rt, range(4), 2)
    fresh = VectorStorageBridge(rt, Counter, storage)  # e.g. after a restore
    assert await fresh.flush(range(4)) == 4  # no InconsistentStateError
    assert fresh.storage_conflicts == 0
    state, etag = await storage.read(Counter.__name__, fresh._grain_id(2))
    assert state["v"] == 2 and etag == fresh._etags[2]


@pytest.mark.parametrize("kind", list(PROVIDERS))
async def test_write_many_is_a_per_entry_compare_and_swap(kind):
    """The provider interface itself: new etag or exception per entry, in
    order; ADOPT_ETAG matches whatever is stored, None only the absent."""
    from orleans_tpu.core.ids import GrainId, GrainType

    storage = PROVIDERS[kind]()
    gid = [GrainId.for_grain(GrainType.of("T"), k) for k in range(4)]
    e0 = await storage.write("T", gid[0], {"x": 0}, None)
    out = await storage.write_many("T", [
        (gid[0], {"x": 1}, e0),            # the right etag
        (gid[1], {"x": 1}, None),          # absent, expected absent
        (gid[2], {"x": 1}, "stale"),       # absent, etag presented
        (gid[3], {"x": 1}, ADOPT_ETAG),    # absent, adopted
    ])
    assert [isinstance(r, str) for r in out] == [True, True, False, True]
    assert isinstance(out[2], InconsistentStateError)
    out2 = await storage.write_many("T", [
        (gid[0], {"x": 2}, e0),            # stale now
        (gid[1], {"x": 2}, ADOPT_ETAG),    # present, adopted
        (gid[3], {"x": 2}, None),          # present, expected absent
    ])
    assert isinstance(out2[0], InconsistentStateError)
    assert isinstance(out2[1], str) and out2[1] != out[1]
    assert isinstance(out2[2], InconsistentStateError)
    assert [(await storage.read("T", g))[0] for g in gid] == \
        [{"x": 1}, {"x": 2}, None, {"x": 1}]


def test_a_memory_storage_subclass_with_its_own_write_keeps_the_default():
    class Counting(MemoryStorage):
        async def write(self, grain_type, grain_id, state, etag):
            return await super().write(grain_type, grain_id, state, etag)

    class Bulk(Counting):
        async def write_many(self, grain_type, entries):
            return await super().write_many(grain_type, entries)

    class Plain(MemoryStorage):
        pass

    assert Counting.write_many is GrainStorage.write_many
    assert Plain.write_many is MemoryStorage.write_many
    assert Bulk.write_many is not GrainStorage.write_many


async def test_columnar_locate_matches_the_per_key_loop():
    """_locate resolves dense keys as columns; the reference is the loop
    it replaced (dense arithmetic first, then the table's lookup)."""
    rt = _runtime(Counter, 20)
    tbl = rt.table(Counter)
    hashed = [(1 << 40) + 7 * i for i in range(9)]
    for k in hashed[:6]:
        tbl.lookup_or_allocate(k)
    bridge = VectorStorageBridge(rt, Counter, MemoryStorage())
    keys = [3, hashed[0], 19, hashed[7], 0, hashed[5], -4, 20, hashed[2]]

    def reference(keys):
        kept, shards, slots = [], [], []
        for k in keys:
            if 0 <= k < tbl.dense_n:
                loc = (k // tbl.dense_per_shard, k % tbl.dense_per_shard)
            elif (loc := tbl.lookup(k)) is None:
                continue
            kept.append(k)
            shards.append(loc[0])
            slots.append(loc[1])
        return kept, shards, slots

    kept, shards, slots = bridge._locate(keys, drop_missing=True)
    want = reference(keys)
    assert (kept, shards.tolist(), slots.tolist()) == want
    assert all(type(k) is int for k in kept) and len(kept) == 6
    assert shards.dtype == slots.dtype == np.int32
    with pytest.raises(KeyError, match="no activation slot"):
        bridge._locate(keys)
    assert bridge._locate([])[0] == []


class HostedCounter(VectorGrain):
    STATE = {"total": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"total": jnp.int32(0)}

    @actor_method(args={"x": (jnp.int32, ())})
    def add(state, args):
        total = state["total"] + args["x"]
        return {"total": total}, total


def _silo(storage, period):
    b = SiloBuilder().with_name("wb-batch")
    add_vector_grains(b, HostedCounter, mesh=make_mesh(1),
                      dense={HostedCounter: 32}, capacity_per_shard=32,
                      storage=storage, flush_period=period)
    return b.build()


async def test_a_cancelled_flush_remarks_everything_it_drained():
    """stop() cancels the flusher in the middle of the provider's writes
    (the per-key default, every write suspended); the cancel re-mark in
    hosting.flush_all hands every drained key to the final drain."""
    entered = []

    class Stuck(PerKeyOnly):
        block = True

        async def write(self, grain_type, grain_id, state, etag):
            entered.append(grain_id.key)
            if self.block:
                await asyncio.Event().wait()  # until cancelled
            return await super().write(grain_type, grain_id, state, etag)

    storage = Stuck()
    silo = _silo(storage, 0.02)
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        refs = [client.get_grain(HostedCounter, k) for k in range(12)]
        await asyncio.gather(*(g.add(x=np.int32(k + 1))
                               for k, g in enumerate(refs)))
        for _ in range(400):  # until the flusher sits in the writes
            if len(entered) >= 12:
                break
            await asyncio.sleep(0.01)
        assert sorted(entered) == list(range(12))
        assert not storage.inner._data
        storage.block = False  # the final drain's writes go through
    finally:
        await client.close_async()
        await silo.stop()
    assert sorted(entered) == sorted(2 * list(range(12)))
    bridge = silo.vector_bridges[HostedCounter]
    for k in range(12):
        state, _ = await storage.read("HostedCounter", bridge._grain_id(k))
        assert state == {"total": k + 1}
    assert silo.stats.get(FLUSH_STATS["flushed"]) == 12
    assert silo.stats.get(FLUSH_STATS["batched"]) == 0


@pytest.mark.parametrize("kind,share", [("batched", 1.0), ("per_key", 0.0)])
async def test_engagement_counter_counts_rows_through_the_providers_own_batch(
        kind, share):
    silo = _silo(PROVIDERS[kind](), 0.02)
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        await asyncio.gather(*(client.get_grain(HostedCounter, k)
                               .add(x=np.int32(1)) for k in range(20)))
    finally:
        await client.close_async()
        await silo.stop()  # final drain
    flushed = silo.stats.get(FLUSH_STATS["flushed"])
    assert flushed >= 20
    assert FLUSH_STATS["batched"] in silo.stats.counters  # 0, not absent
    assert silo.stats.get(FLUSH_STATS["batched"]) == share * flushed


async def _count_tasks(coro) -> int:
    loop = asyncio.get_running_loop()
    made = []

    def factory(loop, coro, **kwargs):
        task = asyncio.Task(coro, loop=loop, **kwargs)
        made.append(task)
        return task

    loop.set_task_factory(factory)
    try:
        await coro
    finally:
        loop.set_task_factory(None)
    return len(made)


async def test_flushing_2000_rows_to_memory_storage_creates_no_task_per_row():
    rows = 2000
    rt = _runtime(Counter, rows, mesh=1, cap=2048)
    await _put(rt, range(rows), 5)
    batched = VectorStorageBridge(rt, Counter, MemoryStorage())
    assert await _count_tasks(batched.flush(range(rows))) <= 2
    assert len(batched.storage._data) == rows
    # the guard measures something: the per-key default makes one a row
    per_key = VectorStorageBridge(rt, Counter, PerKeyOnly())
    assert await _count_tasks(per_key.flush(range(rows))) >= rows


async def test_forty_dirty_counts_in_one_bucket_compile_one_gather():
    rt = _runtime(Counter, 128, mesh=1, cap=128)
    await _put(rt, range(128), 1)
    bridge = VectorStorageBridge(rt, Counter, MemoryStorage())
    _gather_rows.clear_cache()
    for n in range(65, 105):  # 40 counts, all in the bucket of 128
        assert await bridge.flush(range(n)) == n
    assert _gather_rows._cache_size() == 1
    assert await bridge.flush(range(64)) == 64  # the bucket below: one more
    assert _gather_rows._cache_size() == 2
