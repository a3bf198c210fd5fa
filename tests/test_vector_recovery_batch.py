"""First-touch recovery once per decoded read: the dispatcher recovers a
read's fresh keys with ONE ``bridge.load`` (one ``storage.read_many``, one
``recover`` span) and their calls join the read's per-method
``call_group`` — against the per-key default of ``GrainStorage.read_many``,
a provider that really suspends, and one that fails some keys. Counts and
order only; nothing reads a clock."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.core.ids import GrainId, GrainType
from orleans_tpu.core.message import ResponseKind, make_request
from orleans_tpu.dispatch import VectorGrain, actor_method, add_vector_grains
from orleans_tpu.observability.stats import RECOVER_STATS
from orleans_tpu.parallel import make_mesh
from orleans_tpu.runtime import SiloBuilder
from orleans_tpu.storage import GrainStorage, MemoryStorage

DENSE = 64
FIRST_TOUCH, PASS_KEYS = RECOVER_STATS["first_touch"], RECOVER_STATS["keys"]


class Tally(VectorGrain):
    STATE = {"total": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"total": jnp.int32(0)}

    @actor_method(args={"x": (jnp.int32, ())})
    def add(state, args):
        total = state["total"] + args["x"]
        return {"total": total}, total

    @actor_method(read_only=True)
    def peek(state, args):
        return state, state["total"]


class CountingBulk(MemoryStorage):
    """MemoryStorage with its one-pass ``read_many``, counted."""

    def __init__(self) -> None:
        super().__init__()
        self.passes: list[int] = []

    async def read_many(self, grain_type, grain_ids):
        grain_ids = list(grain_ids)
        self.passes.append(len(grain_ids))
        return await super().read_many(grain_type, grain_ids)


class PerKeyOnly(GrainStorage):
    """A provider that knows only per-key operations: ``read_many`` is the
    base class's default. Counts the reads of each key; while ``gate`` is
    given and unset every read waits for it, and a key in ``bad`` fails."""

    def __init__(self, inner=None, gate=None, bad=()) -> None:
        self.inner = inner or MemoryStorage()
        self.gate, self.bad = gate, set(bad)
        self.reads: dict = {}
        self.passes: list[int] = []

    async def read(self, grain_type, grain_id):
        self.reads[grain_id.key] = self.reads.get(grain_id.key, 0) + 1
        if self.gate is not None:
            await self.gate.wait()
        if grain_id.key in self.bad:
            raise IOError(f"injected read failure for {grain_id.key}")
        return await self.inner.read(grain_type, grain_id)

    async def read_many(self, grain_type, grain_ids):
        grain_ids = list(grain_ids)
        self.passes.append(len(grain_ids))
        return await super().read_many(grain_type, grain_ids)

    async def write(self, grain_type, grain_id, state, etag):
        return await self.inner.write(grain_type, grain_id, state, etag)

    async def clear(self, grain_type, grain_id, etag):
        return await self.inner.clear(grain_type, grain_id, etag)


class BadBulk(MemoryStorage):
    """A bulk provider that never suspends and fails the keys in ``bad``:
    the pass has landed when it is returned, with errors."""

    def __init__(self, bad=()) -> None:
        super().__init__()
        self.bad = set(bad)

    async def read_many(self, grain_type, grain_ids):
        grain_ids = list(grain_ids)
        out = await super().read_many(grain_type, grain_ids)
        return [IOError(f"injected read failure for {g.key}")
                if g.key in self.bad else r for g, r in zip(grain_ids, out)]


class Harness:
    """One silo with write-behind storage, driven at the dispatcher: a
    "read" is one ``receive_vector_batch`` of hand-made requests, and the
    replies are caught at ``send_response``."""

    def __init__(self, storage, metrics: bool = True) -> None:
        b = (SiloBuilder().with_name("recovery")
             .with_config(metrics_enabled=metrics))
        add_vector_grains(b, Tally, mesh=make_mesh(1),
                          dense={Tally: DENSE}, capacity_per_shard=256,
                          storage=storage, flush_period=3600.0)
        self.silo = b.build()
        self.replies: dict = {}
        self.order: list = []
        self.groups: list = []

    async def __aenter__(self) -> "Harness":
        await self.silo.start()
        d, rt = self.silo.dispatcher, self.silo.vector

        def send_response(request, response):
            self.replies[request.id] = response
            self.order.append(request.id)

        def call_group(cls, method, items, **kw):
            self.groups.append((method, len(items)))
            return call_group_(cls, method, items, **kw)

        d.send_response = send_response
        call_group_, rt.call_group = rt.call_group, call_group
        return self

    async def __aexit__(self, *exc) -> None:
        del self.silo.vector.call_group
        await self.silo.stop()

    @property
    def stats(self):
        return self.silo.stats

    def msg(self, key, method: str = "add", **kwargs):
        return make_request(
            target_grain=GrainId.for_grain(GrainType.of("Tally"), key),
            interface_name="Tally", method_name=method,
            body=((), {k: np.int32(v) for k, v in kwargs.items()}))

    def read(self, msgs) -> None:
        self.silo.dispatcher.receive_vector_batch(Tally, list(msgs))

    async def answers(self, msgs, timeout: float = 20.0) -> list:
        """Each message's reply: the value, or the exception it carries."""
        async def wait():
            while not all(m.id in self.replies for m in msgs):
                await asyncio.sleep(0.002)
        await asyncio.wait_for(wait(), timeout)
        out = []
        for m in msgs:
            r = self.replies[m.id]
            out.append(int(r.body) if r.response_kind == ResponseKind.SUCCESS
                       else r.body)
        return out

    def count(self, name: str) -> tuple:
        h = self.stats.histograms.get(name)
        return (h.total, int(h.sum)) if h is not None else (0, 0)


async def _count_tasks(fn) -> int:
    """Tasks the loop creates while ``fn()`` (synchronous) runs."""
    loop = asyncio.get_running_loop()
    prev = loop.get_task_factory()
    made = []

    def factory(loop, coro, **kw):
        made.append(coro)
        return prev(loop, coro, **kw) if prev is not None \
            else asyncio.Task(coro, loop=loop, **kw)

    loop.set_task_factory(factory)
    try:
        fn()
    finally:
        loop.set_task_factory(prev)
    return len(made)


async def _stored(storage, rows: dict) -> None:
    """Put ``{key hash: total}`` into ``storage`` the way a flush would."""
    gtype = GrainType.of("Tally")
    for k, total in rows.items():
        await storage.write("Tally", GrainId.for_grain(gtype, int(k)),
                            {"total": total}, None)


def _key_hash(key) -> int:
    gid = GrainId.for_grain(GrainType.of("Tally"), key)
    return key if isinstance(key, int) else gid.uniform_hash


KEYS = list(range(12)) + [f"player-{i}" for i in range(12)]


@pytest.mark.parametrize("provider", ["bulk", "per_key"])
async def test_a_read_of_fresh_keys_is_one_pass(provider):
    storage = CountingBulk() if provider == "bulk" else PerKeyOnly()
    async with Harness(storage) as h:
        adds = [h.msg(k, x=1) for k in KEYS]
        peeks = [h.msg(k, "peek") for k in KEYS[:6]]
        tasks = await _count_tasks(lambda: h.read(adds + peeks))
        assert storage.passes == [len(KEYS)]      # one read_many, each key once
        assert h.count("recover.seconds")[0] == 1
        assert h.count(PASS_KEYS) == (1, len(KEYS))
        assert h.stats.get(FIRST_TOUCH) == len(adds) + len(peeks)
        assert h.stats.get("vector.storage.recovered") == 0
        if provider == "bulk":
            # the pass landed on the spot: every message rides its
            # method's one group, and the read made one task — the load
            assert tasks <= 1
            assert h.groups == [("add", len(adds)), ("peek", len(peeks))]
        else:
            # the guard measures something: the per-key default reads
            # each key in a task of its own, and the pass suspends
            assert tasks >= len(KEYS)
            assert sorted(storage.reads.values()) == [1] * len(KEYS)
        assert await h.answers(adds) == [1] * len(adds)
        assert all(v in (0, 1) for v in await h.answers(peeks))
        assert sorted(h.groups) == [("add", len(adds)), ("peek", len(peeks))]
        assert not h.silo.dispatcher._vector_recoveries
        # nothing is fresh any more (a write activates): no second pass
        again = [h.msg(k, x=1) for k in KEYS]
        h.read(again)
        assert await h.answers(again) == [2] * len(KEYS)
        assert storage.passes == [len(KEYS)]
        assert h.count("recover.seconds")[0] == 1
        assert h.stats.get(FIRST_TOUCH) == len(adds) + len(peeks)


@pytest.mark.parametrize("provider", ["bulk", "per_key"])
@pytest.mark.parametrize("keys", ["dense", "hashed"])
async def test_stored_rows_are_in_the_table_before_the_first_tick(keys,
                                                                  provider):
    storage = CountingBulk() if provider == "bulk" else PerKeyOnly()
    names = list(range(8)) if keys == "dense" else \
        [f"player-{i}" for i in range(8)]
    rows = {_key_hash(k): 100 + i for i, k in enumerate(names[:5])}
    await _stored(storage, rows)
    async with Harness(storage) as h:
        msgs = [h.msg(k, x=1) for k in names]
        h.read(msgs)
        # five keys resume from their stored totals, three start fresh
        assert await h.answers(msgs) == [101, 102, 103, 104, 105, 1, 1, 1]
        assert h.stats.get("vector.storage.recovered") == 5
        assert storage.passes == [8]
        assert h.count(PASS_KEYS) == (1, 8)
        tbl = h.silo.vector.table(Tally)
        assert int(tbl.read_row(_key_hash(names[0]))["total"]) == 101
        # the bridge remembers the stored etags: the next flush writes
        # over them instead of conflicting
        assert await h.silo.vector_bridges[Tally].flush(
            [_key_hash(k) for k in names], strict=True) == 8


@pytest.mark.parametrize("reads", ["one_read", "two_reads"])
async def test_a_suspended_pass_keeps_its_keys_order_and_holds_no_other_key(
        reads):
    gate = asyncio.Event()
    storage = PerKeyOnly(gate=gate)
    await _stored(storage, {7: 40})
    async with Harness(storage) as h:
        gate.set()
        warm = [h.msg(3, x=5)]
        h.read(warm)                    # key 3 is active from here on
        assert await h.answers(warm) == [5]
        gate.clear()
        storage.reads.clear()
        storage.passes.clear()
        first, second, other = h.msg(7, x=1), h.msg(7, x=2), h.msg(3, x=1)
        if reads == "one_read":
            h.read([first, other, second])
        else:
            h.read([first, other])
            h.read([second])            # behind its key's pass: no 2nd read
        # the other key's call is not held back by the pass in flight
        assert await h.answers([other]) == [6]
        assert first.id not in h.replies and second.id not in h.replies
        assert set(h.silo.dispatcher._vector_recoveries) == {(Tally, 7)}
        assert h.count("recover.seconds")[0] == 1   # key 3's, long landed
        gate.set()
        assert await h.answers([first, second]) == [41, 43]
        assert h.order.index(first.id) < h.order.index(second.id)
        assert storage.reads == {7: 1}
        assert storage.passes == [1]
        assert h.count("recover.seconds")[0] == 2
        assert h.stats.get("vector.storage.recovered") == 1
        assert not h.silo.dispatcher._vector_recoveries
        # both of key 7's calls went on as one group when the pass landed
        assert h.groups[-1] == ("add", 2)


@pytest.mark.parametrize("provider", ["landed", "suspended"])
async def test_one_keys_failing_read_fails_that_keys_calls_only(provider):
    bad = {5, _key_hash("player-5")}  # storage ids are made of key hashes
    storage = BadBulk(bad) if provider == "landed" else PerKeyOnly(bad=bad)
    await _stored(storage, {4: 10})
    async with Harness(storage) as h:
        names = [4, 5, 6, "player-5", "player-6"]
        msgs = [h.msg(k, x=1) for k in names] + [h.msg(5, "peek")]
        h.read(msgs)
        got = await h.answers(msgs)
        assert got[0] == 11 and got[2] == 1 and got[4] == 1
        for i in (1, 3, 5):
            assert isinstance(got[i], IOError) and "injected" in str(got[i])
        assert h.stats.get("vector.storage.recovered") == 1
        assert h.count(PASS_KEYS) == (1, 5)
        assert not h.silo.dispatcher._vector_recoveries
        # the failed keys stayed fresh: once storage answers, they recover
        storage.bad.clear()
        retry = [h.msg(5, x=1), h.msg("player-5", x=1)]
        h.read(retry)
        assert await h.answers(retry) == [1, 1]
        assert h.count(PASS_KEYS) == (2, 7)


@pytest.mark.parametrize("provider", ["landed", "suspended"])
async def test_a_load_that_fails_whole_fails_its_keys_and_no_other(provider):
    storage = MemoryStorage() if provider == "landed" else PerKeyOnly()
    async with Harness(storage) as h:
        warm = [h.msg(3, x=5)]
        h.read(warm)
        assert await h.answers(warm) == [5]
        bridge = h.silo.vector_bridges[Tally]
        load = bridge.load

        async def broken(keys, errors=None):
            if provider == "suspended":
                await asyncio.sleep(0)
            raise RuntimeError("scatter failed")

        bridge.load = broken
        msgs = [h.msg(8, x=1), h.msg(3, x=1), h.msg(9, x=1)]
        h.read(msgs)
        got = await h.answers(msgs)
        assert got[1] == 6
        assert all(isinstance(got[i], RuntimeError) for i in (0, 2))
        assert not h.silo.dispatcher._vector_recoveries
        bridge.load = load
        retry = [h.msg(8, x=1)]
        h.read(retry)
        assert await h.answers(retry) == [1]


async def test_a_read_only_first_touch_is_read_again_by_the_next_read():
    storage = CountingBulk()
    async with Harness(storage) as h:
        for n in (1, 2):
            peeks = [h.msg(k, "peek") for k in range(6)]
            h.read(peeks)
            assert await h.answers(peeks) == [0] * 6
            assert storage.passes == [6] * n
            assert h.count(PASS_KEYS) == (n, 6 * n)
        assert not h.silo.vector.table(Tally).dense_active[:6].any()


@pytest.mark.parametrize("metrics", [True, False])
async def test_the_per_message_entry_recovers_through_the_same_pass(metrics):
    storage = CountingBulk()
    await _stored(storage, {9: 70})
    async with Harness(storage, metrics) as h:
        m = h.msg(9, x=1)
        h.silo.dispatcher.receive_message(m)
        assert await h.answers([m]) == [71]
        assert storage.passes == [1]
        assert h.groups == [("add", 1)]
        assert h.stats.get("vector.storage.recovered") == 1
        if metrics:
            assert h.count(PASS_KEYS) == (1, 1)
            assert h.count("recover.seconds")[0] == 1
            assert h.stats.get(FIRST_TOUCH) == 1
        else:
            # nothing is stamped with metrics off
            names = set(h.stats.counters) | set(h.stats.histograms)
            assert not names & {FIRST_TOUCH, PASS_KEYS, "recover.seconds"}


async def test_stopping_the_silo_drops_a_pass_in_flight():
    storage = PerKeyOnly(gate=asyncio.Event())
    h = Harness(storage)
    async with h:
        h.silo.config.deactivation_timeout = 0.2
        msgs = [h.msg(k, x=1) for k in range(4)]
        h.read(msgs)
        assert len(h.silo.dispatcher._vector_recoveries) == 4
    assert not h.silo.dispatcher._vector_recoveries
    assert not h.replies


@pytest.mark.parametrize("case", ["own_read", "own_write", "plain",
                                  "own_read_many", "own_read_under_bulk"])
def test_a_memory_storage_subclass_with_its_own_read_keeps_the_default(case):
    class OwnRead(MemoryStorage):
        async def read(self, grain_type, grain_id):
            return await super().read(grain_type, grain_id)

    class OwnWrite(MemoryStorage):
        async def write(self, grain_type, grain_id, state, etag):
            return await super().write(grain_type, grain_id, state, etag)

    class Plain(MemoryStorage):
        pass

    class OwnReadMany(OwnRead):
        async def read_many(self, grain_type, grain_ids):
            return await super().read_many(grain_type, grain_ids)

    class OwnReadUnderBulk(CountingBulk):
        async def read(self, grain_type, grain_id):
            return await super().read(grain_type, grain_id)

    if case == "own_read":
        assert OwnRead.read_many is GrainStorage.read_many
        assert OwnRead.write_many is GrainStorage.write_many
    elif case == "own_write":
        # its reads are MemoryStorage's own: the one-pass read stays
        assert OwnWrite.read_many is MemoryStorage.read_many
        assert OwnWrite.write_many is GrainStorage.write_many
    elif case == "plain":
        assert Plain.read_many is MemoryStorage.read_many
    elif case == "own_read_many":
        assert OwnReadMany.read_many is not GrainStorage.read_many
    else:
        # a read of its own under an inherited bulk read: per key again
        assert OwnReadUnderBulk.read_many is GrainStorage.read_many


class _PerKeyMemory(MemoryStorage):
    async def read(self, grain_type, grain_id):
        return await super().read(grain_type, grain_id)


@pytest.mark.parametrize("case", ["hits", "misses", "mixed", "empty"])
async def test_read_many_default_and_override_give_the_same_list(case):
    gtype = GrainType.of("T")
    gid = [GrainId.for_grain(gtype, k) for k in (0, 1, "a", "b", 2**40)]
    stored = {"hits": gid, "misses": [], "mixed": gid[::2], "empty": gid}[case]
    ask = [] if case == "empty" else gid
    bulk, per_key = MemoryStorage(), _PerKeyMemory()
    assert _PerKeyMemory.read_many is GrainStorage.read_many
    for s in (bulk, per_key):
        for i, g in enumerate(stored):
            await s.write("T", g, {"x": i, "v": [1.5, i]}, None)
    a = await bulk.read_many("T", iter(ask))
    b = await per_key.read_many("T", iter(ask))
    assert len(a) == len(b) == len(ask)
    # same states, item for item; etags are each store's own
    assert [r[0] for r in a] == [r[0] for r in b]
    assert [r[1] is None for r in a] == [r[1] is None for r in b] \
        == [g not in stored for g in ask]
    for g, r in zip(ask, a):
        assert r == await bulk.read("T", g)


async def test_read_many_returns_a_failing_ids_exception_in_its_place():
    gtype = GrainType.of("T")
    gid = [GrainId.for_grain(gtype, k) for k in range(4)]
    storage = PerKeyOnly(bad={2})
    await storage.write("T", gid[1], {"x": 1}, None)
    out = await storage.read_many("T", gid)
    assert out[0] == (None, None) and out[3] == (None, None)
    assert out[1][0] == {"x": 1}
    assert isinstance(out[2], IOError)
    # MemoryStorage: a record that does not decode is that id's failure
    mem = MemoryStorage()
    await mem.write("T", gid[0], {"x": 0}, None)
    await mem.write("T", gid[1], {"x": 1}, None)
    k1 = next(k for k in mem._data if k[2] == "1")
    mem._data[k1] = (b"\xff not a record", mem._data[k1][1])
    out = await mem.read_many("T", gid[:2])
    assert out[0][0] == {"x": 0}
    assert isinstance(out[1], Exception)
