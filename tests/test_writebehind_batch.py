"""The write-behind flush as one columnar pass: a provider's own
``write_many`` (MemoryStorage) against the per-key default of
``GrainStorage`` — same stored records, same etag discipline, same fault
paths — and the pass's cost guards (tasks created, gathers compiled), none
of which reads a clock."""

import asyncio
import itertools

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
from orleans_tpu.storage import checkpoint
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


# -- a pass in chunks: download and write at the same time -------------------

Wide = _grain(jnp.uint8, (64,))     # 64 B + n: a 68 B row
Narrow = _grain(jnp.float32, (3,))  # 12 B + n: a 16 B row
CHUNK = 16                          # rows a chunk where `chunked` asks


@pytest.fixture
def chunked(monkeypatch):
    """``Wide``'s rows come down ``CHUNK`` at a time."""
    monkeypatch.setattr(checkpoint, "_CHUNK_BYTES", 68 * CHUNK)


async def _put_rows(rt, cls, n: int, salt: int = 0) -> None:
    """Row k of ``cls`` gets bytes that differ from every other row's."""
    width = cls.STATE["v"][1][0]
    futs = [rt.call(cls, k, "put", v=((np.arange(width) * 7 + k + salt) % 251)
                    .astype(np.uint8)) for k in range(n)]
    await rt.flush()
    await asyncio.gather(*futs)


@pytest.mark.parametrize("mesh", [1, 8])
@pytest.mark.parametrize("kind", ["batched", "per_key"])
async def test_chunks_store_every_row_as_the_device_holds_it(chunked, kind,
                                                             mesh):
    """53 rows in chunks of 16: three full chunks and a tail of 5 padded to
    8. Every stored row is its device row byte for byte, across the chunk
    edges and in the padded tail, and every key's etag is its own."""
    n = 3 * CHUNK + 5
    rt = _runtime(Wide, n, mesh=mesh, cap=64)
    await _put_rows(rt, Wide, n)
    storage = PROVIDERS[kind]()
    bridge = VectorStorageBridge(rt, Wide, storage)
    assert bridge._chunk_rows(rt.table(Wide)) == CHUNK
    keys = np.random.default_rng(3).permutation(n)  # not in slot order
    assert await bridge.flush(keys) == n
    assert bridge.pipelined == 3 * CHUNK  # every chunk's rows but the last's
    device = _device_rows(rt, Wide, n)
    for k in range(n):
        state, etag = await storage.read(Wide.__name__, bridge._grain_id(k))
        assert isinstance(state["v"], np.ndarray)
        assert state["v"].tobytes() == device["v"][k].tobytes()
        assert state["n"] == int(device["n"][k]) == 1
        assert bridge._etags[k] == etag
    # the second pass writes with the etags the first one remembered
    await _put_rows(rt, Wide, n, salt=9)
    assert await bridge.flush(keys) == n
    assert bridge.storage_conflicts == 0
    state, _ = await storage.read(Wide.__name__, bridge._grain_id(CHUNK))
    assert state["n"] == 2


@pytest.mark.parametrize("rows_a_chunk", [CHUNK, None],
                         ids=["chunks-of-16", "one-chunk"])
async def test_a_pass_of_any_width_compiles_nothing_after_the_warm_up(
        monkeypatch, rows_a_chunk):
    """The programs a table compiles are ``_gather_rows`` at powers of two
    up to the chunk; after ``_gather`` ran at every power of two up to
    16,384 (the benchmark's warm-up) no pass compiles."""
    if rows_a_chunk:
        monkeypatch.setattr(checkpoint, "_CHUNK_BYTES", 68 * rows_a_chunk)
    rt = _runtime(Wide, 200, mesh=1, cap=256)
    await _put_rows(rt, Wide, 200)
    tbl = rt.table(Wide)
    bridge = VectorStorageBridge(rt, Wide, MemoryStorage())
    _gather_rows.clear_cache()
    if rows_a_chunk:
        for n in (5, 16, 17, 40, 53, 200):
            assert await bridge.flush(range(n)) == n
        assert _gather_rows._cache_size() == 2  # 8 and 16 rows
    for b in (1 << i for i in range(3, 15)):
        at = np.zeros(b, np.int32)
        with rt.tick_fence():
            host = bridge._gather(tbl, at, at)
        assert {len(c) for c in host.values()} == {b}
    warmed = _gather_rows._cache_size()
    assert warmed == (2 if rows_a_chunk else 12)
    for n in (1, 7, 9, 16, 31, 33, 100, 129, 200):
        assert await bridge.flush(range(n)) == n
    assert _gather_rows._cache_size() == warmed


async def test_a_16_byte_row_pass_is_one_launch_at_its_bucket(monkeypatch):
    launched = []

    def spy(state, index):
        launched.append(index.shape)
        return _gather_rows(state, index)

    monkeypatch.setattr(checkpoint, "_gather_rows", spy)
    rt = _runtime(Narrow, 2000, mesh=1, cap=2048)
    bridge = VectorStorageBridge(rt, Narrow, MemoryStorage())
    assert bridge._chunk_rows(rt.table(Narrow)) == (32 << 20) // 16
    assert await bridge.flush(range(2000)) == 2000
    assert launched == [(2, 2048)]
    assert bridge.pipelined == 0
    assert len(bridge.storage._data) == 2000


async def test_every_host_copy_is_asked_for_before_the_first_write(
        chunked, monkeypatch):
    """Under the fence: every chunk launched and its host copy requested.
    Then chunk by chunk: waited for, written — the later ones coming down
    meanwhile."""
    log = []

    class Leaf:
        """A device column that records when it is asked for and read."""

        def __init__(self, a, chunk):
            self.a, self.chunk = a, chunk

        def copy_to_host_async(self):
            log.append(("copy", self.chunk))
            self.a.copy_to_host_async()

        def __array__(self, dtype=None, copy=None):
            log.append(("land", self.chunk))
            return np.asarray(self.a)

    launches = iter(range(100))

    def gather(state, index):
        chunk = next(launches)
        assert rt.tick_fence()._is_owned()
        return {f: Leaf(a, chunk)
                for f, a in _gather_rows(state, index).items()}

    class Recording(MemoryStorage):
        async def write_many(self, grain_type, entries):
            log.append(("write", None))
            assert not rt.tick_fence()._is_owned()
            return await super().write_many(grain_type, entries)

    n = 2 * CHUNK + 3
    rt = _runtime(Wide, n, mesh=1, cap=64)
    await _put_rows(rt, Wide, n)
    monkeypatch.setattr(checkpoint, "_gather_rows", gather)
    bridge = VectorStorageBridge(rt, Wide, Recording())
    assert await bridge.flush(range(n)) == n
    # a chunk's two leaves are asked for, and read, one after the other
    steps = [step for step, _ in itertools.groupby(log)]
    assert steps == [("copy", 0), ("copy", 1), ("copy", 2),
                     ("land", 0), ("write", None),
                     ("land", 1), ("write", None),
                     ("land", 2), ("write", None)]


async def test_a_launched_gather_holds_the_rows_as_they_stood(chunked):
    """The launch under the fence is the snapshot: a tick that donates the
    state afterwards does not change what lands."""
    n = 2 * CHUNK + 3
    rt = _runtime(Wide, n, mesh=1, cap=64)
    await _put_rows(rt, Wide, n)
    bridge = VectorStorageBridge(rt, Wide, MemoryStorage())
    before = _device_rows(rt, Wide, n)
    _, shards, slots = bridge._locate(range(n))
    with rt.tick_fence():
        chunks = bridge._launch(rt.table(Wide), shards, slots)
    await _put_rows(rt, Wide, n, salt=100)  # ticks over the same rows
    after = _device_rows(rt, Wide, n)
    assert not (after["v"] == before["v"]).all()
    landed = [bridge._land(m, dev) for m, dev in chunks]
    assert [len(c["n"]) for c in landed] == [CHUNK, CHUNK, 3]
    for f in ("v", "n"):
        assert (np.concatenate([c[f] for c in landed]) == before[f]).all()


async def test_a_key_failing_in_the_second_chunk_is_remarked_alone(chunked):
    n = 3 * CHUNK + 5
    rt = _runtime(Wide, n, mesh=1, cap=64)
    rt.enable_dirty_tracking()
    inner = MemoryStorage()
    bad = CHUNK + 4
    bridge = VectorStorageBridge(rt, Wide, FailKeys(inner, {bad}))
    await _put_rows(rt, Wide, n)
    rt.drain_dirty(Wide)
    assert await bridge.flush(range(n)) == n - 1
    assert rt.drain_dirty(Wide).tolist() == [bad]
    assert bridge.pipelined == 3 * CHUNK - 1
    assert bad not in bridge._etags and len(bridge._etags) == n - 1
    assert len(inner._data) == n - 1
    with pytest.raises(IOError, match="injected write failure"):
        await bridge.flush(range(n), strict=True)
    assert rt.drain_dirty(Wide).tolist() == [bad]


def _hosted_chunks(monkeypatch, rows: int) -> None:
    """HostedCounter's 4 B rows come down ``rows`` at a time."""
    monkeypatch.setattr(checkpoint, "_CHUNK_BYTES", 4 * rows)


async def _add(client, n: int) -> None:
    await asyncio.gather(*(client.get_grain(HostedCounter, k)
                           .add(x=np.int32(k + 1)) for k in range(n)))


async def _until(cond) -> None:
    for _ in range(800):
        if cond():
            return
        await asyncio.sleep(0.01)
    raise AssertionError("never happened")


async def test_a_failed_download_remarks_the_pass_and_the_next_persists_it(
        monkeypatch):
    """The third chunk's download fails after two chunks were written: the
    flusher re-marks the whole pass, and because the written chunks' etags
    were remembered as they returned, the next pass rewrites them without
    a conflict — no row is released."""
    _hosted_chunks(monkeypatch, 8)
    storage = MemoryStorage()
    silo = _silo(storage, 0.02)
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    bridge = silo.vector_bridges[HostedCounter]
    tbl = silo.vector.table(HostedCounter)
    flushed = lambda: silo.stats.get(FLUSH_STATS["flushed"])  # noqa: E731
    seen = []
    try:
        await _add(client, 30)  # a first pass: every key has an etag
        await _until(lambda: flushed() >= 30)
        first = dict(bridge._etags)
        land, calls = bridge._land, iter(range(1000))

        def failing_land(m, dev):
            if next(calls) == 2:
                seen.append({k: e for k, e in bridge._etags.items()
                             if e != first[k]})
                raise RuntimeError("the download failed")
            return land(m, dev)

        bridge._land = failing_land
        await _add(client, 30)
        await _until(lambda: flushed() >= 60)
    finally:
        await client.close_async()
        await silo.stop()
    # when the download failed, two chunks of 8 were written and remembered
    assert len(seen) == 1 and len(seen[0]) == 16
    assert bridge.storage_conflicts == 0
    assert tbl.dense_active[:30].all()
    for k in range(30):
        state, etag = await storage.read("HostedCounter", bridge._grain_id(k))
        assert state == {"total": 2 * (k + 1)}
        assert bridge._etags[k] == etag


async def test_a_pass_cancelled_between_chunks_is_persisted_by_the_stop_drain(
        monkeypatch):
    """stop() cancels the flusher while the second chunk's writes are
    suspended: the first chunk's etags are remembered, every key is
    re-marked, and the strict drain writes all of them without a
    conflict."""
    _hosted_chunks(monkeypatch, 8)
    entered = []

    class Stuck(PerKeyOnly):
        block = False

        async def write(self, grain_type, grain_id, state, etag):
            entered.append(grain_id.key)
            if self.block and grain_id.key >= 8:
                await asyncio.Event().wait()  # until cancelled
            return await super().write(grain_type, grain_id, state, etag)

    storage = Stuck()
    silo = _silo(storage, 0.02)
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    bridge = silo.vector_bridges[HostedCounter]
    try:
        await _add(client, 20)
        await _until(lambda: silo.stats.get(FLUSH_STATS["flushed"]) >= 20)
        del entered[:]
        storage.block = True
        await _add(client, 20)
        await _until(lambda: len(entered) >= 16)  # chunk 2 sits in its writes
        await asyncio.sleep(0.05)
        assert sorted(entered) == list(range(16))  # chunk 3 not started
        storage.block = False
    finally:
        await client.close_async()
        await silo.stop()
    assert bridge.storage_conflicts == 0
    assert silo.vector.table(HostedCounter).dense_active[:20].all()
    for k in range(20):
        state, etag = await storage.read("HostedCounter", bridge._grain_id(k))
        assert state == {"total": 2 * (k + 1)}
        assert bridge._etags[k] == etag
