"""Served grain-to-grain calls on the device tier (``@sends``): a sending
method's job is its tick, the loop's word on its receivers and its
exchange — ``route`` (one ``all_to_all`` a pass) and the apply rounds —
before the sender's reply resolves. Held here on 1 and 4 CPU shards at
tiny sizes: against the Chirper deployment's plain reference
(``chipbench/references/chirper.py``) on seeded graphs, and with a small
class whose destinations the test chooses. Counts and equality only.
"""

import asyncio
import importlib.util
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.core.ids import GrainId, GrainType
from orleans_tpu.dispatch import (VectorGrain, VectorRuntime, actor_method,
                                  add_vector_grains, sends)
from orleans_tpu.dispatch import engine as engine_mod
from orleans_tpu.observability.stats import EXCHANGE_STATS, StatsRegistry
from orleans_tpu.parallel import make_mesh
from orleans_tpu.runtime import GatewayClient, SiloBuilder, SocketFabric
from orleans_tpu.storage import MemoryStorage

CHIPBENCH = os.path.join(os.path.dirname(__file__), os.pardir, "chipbench")


def _load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"sends_test_{kind}_{name}", os.path.join(CHIPBENCH, kind,
                                                  f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


app = _load("apps", "chirper")
ref_mod = _load("references", "chirper")
ACCOUNTS, SEED = 256, 7
# small degrees so that a tiny graph shares followers between authors
TABLE = [2, 3, 3, 4, 5, 6, 8, 12]
Account = app.make_account(SEED, ACCOUNTS, TABLE)

K, BOX, N = 4, 8, 64


class Acc(VectorGrain):
    """A box of the last ``BOX`` values received; ``publish`` sends ``v``
    to the first ``cnt`` of the keys ``to``."""

    STATE = {"box": (jnp.int32, (BOX,)), "n": (jnp.int32, ()),
             "seq": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"box": jnp.full((BOX,), -1, jnp.int32), "n": jnp.int32(0),
                "seq": jnp.int32(0)}

    @actor_method(args={"v": (jnp.int32, ())})
    def receive(state, args):
        box = state["box"].at[state["n"] % BOX].set(args["v"])
        return {**state, "box": box, "n": state["n"] + 1}, state["n"] + 1

    @sends("receive", fanout=K, args={"v": (jnp.int32, ()),
                                      "to": (jnp.int32, (K,)),
                                      "cnt": (jnp.int32, ())})
    def publish(state, args):
        return ({**state, "seq": state["seq"] + 1}, state["seq"] + 1,
                (args["to"], jnp.arange(K) < args["cnt"],
                 {"v": jnp.broadcast_to(args["v"], (K,))}))

    @actor_method(args={}, read_only=True)
    def peek(state, args):
        return state, (state["n"], state["box"])


def _runtime(cls, n_shards: int, n_keys: int, stats: bool = True,
             offloop: bool = True) -> VectorRuntime:
    rt = VectorRuntime(mesh=make_mesh(n_shards),
                       capacity_per_shard=n_keys // n_shards)
    rt.register(cls)
    rt.table(cls).ensure_dense(n_keys)
    rt.enable_dirty_tracking()
    rt.offloop_tick = offloop
    if stats:
        rt.stats = StatsRegistry()
    return rt


def _publish(rt, key, v, to, cnt=None):
    to = list(to) + [0] * (K - len(to))
    return rt.call(Acc, key, "publish", v=np.int32(v),
                   to=np.array(to, np.int32),
                   cnt=np.int32(len([t for t in to if t]) if cnt is None
                                else cnt))


def _exch(rt, name: str) -> int:
    return rt.stats.get(EXCHANGE_STATS[name])


async def _close(rt) -> None:
    await rt.flush()
    rt.shutdown_worker()


# ---------------------------------------------------------------------------
# the graph and the rows: the app's jax.numpy against the reference's numpy
# ---------------------------------------------------------------------------

def test_graph_and_initial_rows_are_the_references_bit_for_bit():
    keys = np.arange(ACCOUNTS, dtype=np.int64)
    rows = jax.vmap(Account.initial_state)(jnp.asarray(keys, jnp.int32))
    want = ref_mod.followers_of(np, SEED, ACCOUNTS, TABLE, keys)
    assert np.array_equal(np.asarray(rows["followers"]), want)
    assert np.array_equal(np.asarray(rows["n_followers"]),
                          (want >= 0).sum(axis=1))
    assert not np.asarray(rows["timeline"]).any()
    # nobody twice, nobody their own follower, degrees from the table
    for k, f in zip(keys, want):
        f = f[f >= 0]
        assert len(set(f.tolist())) == len(f) and k not in f
        assert len(f) in TABLE or len(f) + 1 in TABLE


def test_the_deployments_graph_is_what_its_file_says():
    cfg = app.load_config(False)
    g = cfg["graph"]
    table = np.array(g["degree_table"])
    assert len(table) == 1024 and table.max() == g["cap"] == app.FOLLOW_CAP
    assert table.mean() == g["mean_followers"] == 27.0
    assert cfg["grains"][0]["dense"] == 262144 == 4 * cfg["capacity_per_shard"]
    assert cfg["row"]["bytes"] == 33296 == sum(
        np.dtype(d).itemsize * int(np.prod(s, dtype=np.int64))
        for d, s in Account.STATE.values())
    keys = np.arange(0, 262144, 97, dtype=np.int64)
    f = ref_mod.followers_of(np, cfg["data_seed"], 262144, table, keys)
    cross = ((f // 65536) != (keys // 65536)[:, None])[f >= 0].mean()
    assert 0.73 < cross < 0.77
    assert abs((f >= 0).sum(axis=1).mean() - 27.0) < 1.0


# ---------------------------------------------------------------------------
# Chirper through the engine against its reference
# ---------------------------------------------------------------------------

async def _chirper_run(n_shards: int, offloop: bool = True):
    """Every account publishes three chirps, a round of all authors at
    once (so one job carries many chirps for one follower); returns the
    runtime, the reference and each reader's deliveries by author."""
    rt = _runtime(Account, n_shards, ACCOUNTS, offloop=offloop)
    ref = ref_mod.Reference(SEED, ACCOUNTS, TABLE)
    authors = list(range(0, ACCOUNTS, 3))
    delivered: dict = {}
    for seq in (1, 2, 3):
        chirps = {a: ref_mod.make_chirp(a, seq, ref_mod.chirp_text(
            99, a, seq)) for a in authors}
        replies = await asyncio.gather(*(
            rt.call(Account, a, "publish", chirp=chirps[a])
            for a in authors))
        for a, r in zip(authors, replies):
            assert int(r) == ref.publish(a, chirps[a])
            for f in ref.follower_keys(a):
                delivered.setdefault(f, {}).setdefault(a, []).append(
                    chirps[a])
    return rt, ref, authors, delivered


@pytest.mark.parametrize("n_shards", [1, 4])
async def test_chirper_fanout_lands_in_an_order_the_guarantees_allow(
        n_shards):
    rt, ref, authors, delivered = await _chirper_run(n_shards)
    tbl = rt.table(Account)
    keys, want = ref.states()
    assert set(keys) == set(authors) | set(delivered)
    for i, k in enumerate(keys):
        row = tbl.read_row(k)
        for f in ref_mod.FIELDS:   # the order-free fields, exactly
            assert np.array_equal(row[f], want[f][i]), (k, f)
        assert not ref_mod.check_timeline(
            ref_mod.ring_entries(row["timeline"], int(row["n_received"])),
            int(row["n_received"]), delivered.get(k, {}))
    # a read answers the newest ten, to the byte, in such an order too
    for k in list(delivered)[:16]:
        n, data = await rt.call(Account, k, "get_received", n=np.int32(10))
        shown = min(int(n), 10)
        newest_first = ref_mod.split_chirps(np.asarray(data).tobytes(), shown)
        assert int(n) == sum(len(v) for v in delivered[k].values())
        assert not ref_mod.check_timeline(newest_first[::-1], int(n),
                                          delivered[k], complete=shown == n)
        assert not np.asarray(data)[shown * 320:].any()
    # shared followers made rounds, every message was delivered once
    assert _exch(rt, "rounds") > _exch(rt, "jobs") >= 3
    assert _exch(rt, "delivered") == _exch(rt, "sent") \
        == sum(len(ref.follower_keys(a)) for a in authors) * 3
    assert _exch(rt, "dropped") == 0
    assert (_exch(rt, "cross_shard") > 0) == (n_shards > 1)
    await _close(rt)


async def test_chirper_fanout_with_the_lever_off_runs_on_the_loop():
    rt, ref, authors, delivered = await _chirper_run(4, offloop=False)
    assert rt._worker is None
    keys, want = ref.states()
    tbl = rt.table(Account)
    for i, k in enumerate(keys):
        assert int(tbl.read_row(k)["n_received"]) == want["n_received"][i]


async def test_a_single_authors_rows_equal_the_references_to_the_byte():
    rt = _runtime(Account, 4, ACCOUNTS)
    ref = ref_mod.Reference(SEED, ACCOUNTS, TABLE)
    for seq in range(1, 106):    # past the ring of 100
        chirp = ref_mod.make_chirp(5, seq, ref_mod.chirp_text(1, 5, seq))
        assert int(await rt.call(Account, 5, "publish", chirp=chirp)) \
            == ref.publish(5, chirp)
    for k in [5] + ref.follower_keys(5):
        row, want = rt.table(Account).read_row(k), ref.row(k)
        for f, w in want.items():
            assert np.array_equal(row[f], w), (k, f)
    k = ref.follower_keys(5)[0]
    n, data = await rt.call(Account, k, "get_received", n=np.int32(4))
    assert (int(n), np.asarray(data).tobytes()) == ref.get_received(k, 4)
    await _close(rt)


async def test_a_neutral_chirp_is_sent_and_accepted_by_nobody():
    rt = _runtime(Account, 4, ACCOUNTS)
    ref = ref_mod.Reference(SEED, ACCOUNTS, TABLE)
    r = await rt.call(Account, 9, "publish", chirp=ref_mod.NEUTRAL_CHIRP)
    assert int(r) == ref.publish(9, ref_mod.NEUTRAL_CHIRP) > 0
    assert _exch(rt, "delivered") == int(r)
    for k in [9] + ref.follower_keys(9):
        row, want = rt.table(Account).read_row(k), ref.row(k)
        for f, w in want.items():
            assert np.array_equal(row[f], w), (k, f)
    await _close(rt)


@pytest.mark.parametrize("fault", ["lost", "twice", "swapped", "forged"])
def test_check_timeline_finds_what_the_guarantees_forbid(fault):
    chirps = {a: [ref_mod.make_chirp(a, s, ref_mod.chirp_text(3, a, s))
                  for s in (1, 2, 3)] for a in (10, 11)}
    good = [chirps[10][0], chirps[11][0], chirps[10][1], chirps[10][2],
            chirps[11][1], chirps[11][2]]
    assert not ref_mod.check_timeline(good, 6, chirps)
    bad = {"lost": good[:2] + good[3:],
           "twice": good[:3] + [good[2]] + good[3:],
           "swapped": [good[2], good[1], good[0]] + good[3:],
           "forged": good[:5] + [ref_mod.make_chirp(11, 3, b"x" * 280)],
           }[fault]
    assert ref_mod.check_timeline(bad, len(bad), chirps)


# ---------------------------------------------------------------------------
# the mechanism, with destinations the test chooses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 4])
async def test_two_messages_for_one_actor_land_in_successive_rounds_in_lane_order(
        n_shards):
    rt = _runtime(Acc, n_shards, N)
    futs = [_publish(rt, a, 100 + a, [5, 5, 40, 63], cnt=4)
            for a in range(6)]
    assert [int(r) for r in await asyncio.gather(*futs)] == [1] * 6
    n, box = await rt.call(Acc, 5, "peek")
    # twelve deliveries in lane order, the box keeps the last eight
    assert int(n) == 12
    order = [100 + a for a in range(6) for _ in (0, 1)]
    assert [int(box[i % BOX]) for i in range(4, 12)] == order[4:]
    assert _exch(rt, "rounds") >= 12 and _exch(rt, "jobs") == 1
    assert sorted(rt.drain_dirty(Acc).tolist()) == [0, 1, 2, 3, 4, 5, 40, 63]
    await _close(rt)


async def test_every_delivery_crossing_a_shard_is_counted_so():
    rt = _runtime(Acc, 4, N)       # 16 keys a shard
    futs = [_publish(rt, a, a, [16 + a, 32 + a, 48 + a]) for a in range(1, 9)]
    await asyncio.gather(*futs)
    assert _exch(rt, "sent") == _exch(rt, "delivered") \
        == _exch(rt, "cross_shard") == 24
    for a in range(1, 9):
        for k in (16 + a, 32 + a, 48 + a):
            n, box = await rt.call(Acc, k, "peek")
            assert (int(n), int(box[0])) == (1, a)
    await _close(rt)


@pytest.mark.parametrize("n_shards", [1, 4])
async def test_an_outbox_past_the_capacity_is_sent_in_a_second_pass(
        n_shards, monkeypatch):
    """Eight senders, 32 messages, all for one shard: a pass carries
    ``capacity`` of them from one source shard to one destination."""
    monkeypatch.setattr(engine_mod, "_EXCHANGE_CAP", 8)
    rt = _runtime(Acc, n_shards, N)
    targets = list(range(1, 13))   # shard 0 on either mesh
    futs = [_publish(rt, 20 + a, a, [targets[(4 * a + j) % 12]
                                     for j in range(4)], cnt=4)
            for a in range(8)]
    await asyncio.gather(*futs)
    capacity = 8
    passes = _exch(rt, "lanes") // (n_shards * n_shards * capacity)
    assert passes >= 2 and _exch(rt, "dropped") == 0
    assert _exch(rt, "delivered") == _exch(rt, "sent") == 32
    total = 0
    for k in targets:
        n, _box = await rt.call(Acc, k, "peek")
        total += int(n)
    assert total == 32
    await _close(rt)


@pytest.mark.parametrize("n_shards", [1, 4])
async def test_a_receiver_nothing_has_touched_is_activated_by_the_delivery(
        n_shards):
    rt = _runtime(Acc, n_shards, N)
    tbl = rt.table(Acc)
    assert not tbl.dense_active[[50, 51]].any()
    await _publish(rt, 1, 7, [50, 51])
    assert tbl.dense_active[[50, 51]].all()
    assert _exch(rt, "activated") == 2
    for k in (50, 51):      # initial_state, then the delivery
        row = tbl.read_row(k)
        assert row["box"].tolist() == [7] + [-1] * (BOX - 1)
    # activated once: the next delivery does not initialise it again
    await _publish(rt, 2, 8, [50])
    assert tbl.read_row(50)["box"].tolist()[:2] == [7, 8]
    assert _exch(rt, "activated") == 2
    await _close(rt)


async def test_a_delivery_and_an_unclaimed_first_write_both_land():
    """A key whose first write waits unclaimed is initialised by whichever
    runs first — once."""
    rt = _runtime(Acc, 4, N)
    a = _publish(rt, 1, 7, [50])
    b = rt.call(Acc, 50, "receive", v=np.int32(9))   # a client's own call
    await asyncio.gather(a, b)
    n, box = await rt.call(Acc, 50, "peek")
    assert int(n) == 2 and sorted(box.tolist()[:2]) == [7, 9]
    assert box.tolist()[2:] == [-1] * (BOX - 2)
    await _close(rt)


@pytest.mark.parametrize("n_shards", [1, 4])
async def test_the_senders_reply_does_not_resolve_before_the_last_round(
        n_shards):
    rt = _runtime(Acc, n_shards, N)
    await _publish(rt, 3, 0, [9])      # compile, activate
    seen = []
    tick = rt._device_tick

    def spy(*a, **kw):
        out = tick(*a, **kw)
        seen.append((threading.get_ident(), fut.done()))
        return out

    rt._device_tick = spy
    fut = _publish(rt, 1, 7, [9, 9, 9, 9], cnt=4)
    assert int(await fut) == 1
    del rt._device_tick
    # four rounds, on the tick worker, the reply unresolved after each
    assert len(seen) == 4 and not any(done for _t, done in seen)
    assert {t for t, _d in seen} == {rt._worker.ident}
    n, _box = await rt.call(Acc, 9, "peek")
    assert int(n) == 5
    await _close(rt)


@pytest.mark.parametrize("n_shards", [1, 4])
async def test_a_message_nobody_can_receive_fails_its_sender_only(n_shards):
    rt = _runtime(Acc, n_shards, N)
    bad = _publish(rt, 1, 7, [5, 999], cnt=2)
    good = _publish(rt, 2, 8, [6])
    out = await asyncio.gather(bad, good, return_exceptions=True)
    assert isinstance(out[0], KeyError) and "999" in str(out[0])
    assert int(out[1]) == 1
    # the failed sender's other message was delivered; nothing was dropped
    for k, v in ((5, 7), (6, 8)):
        n, box = await rt.call(Acc, k, "peek")
        assert (int(n), int(box[0])) == (1, v)
    assert _exch(rt, "delivered") == _exch(rt, "sent") == 2
    assert _exch(rt, "dropped") == 0
    await _close(rt)


async def test_a_job_that_sends_nothing_skips_the_exchange():
    rt = _runtime(Acc, 4, N)
    assert int(await _publish(rt, 1, 7, [], cnt=0)) == 1
    assert _exch(rt, "jobs") == 0 and "exchange.seconds" \
        not in rt.stats.histograms
    await _close(rt)


async def test_the_counters_add_up_and_nothing_is_stamped_with_metrics_off():
    rt = _runtime(Acc, 4, N, stats=False)
    await asyncio.gather(*(_publish(rt, a, a, [40, 41]) for a in range(1, 5)))
    assert rt.stats is None and rt.messages_processed == 4 + 8
    await _close(rt)
    rt = _runtime(Acc, 4, N)
    await asyncio.gather(*(_publish(rt, a, a, [40, 41]) for a in range(1, 5)))
    st = rt.stats
    assert {k for k in st.counters if k.startswith("exchange.")} \
        == set(EXCHANGE_STATS.values())
    h = st.histograms
    assert h["exchange.seconds"].total == _exch(rt, "jobs") == 1
    assert h["exchange.route.seconds"].total \
        == h["exchange.apply.seconds"].total == 1   # one pass
    assert h["exchange.activate.seconds"].total == 1
    assert _exch(rt, "rounds") == 4 and _exch(rt, "activated") == 2
    assert h["exchange.seconds"].sum >= h["exchange.route.seconds"].sum \
        + h["exchange.apply.seconds"].sum - 1e-9
    assert 0 < _exch(rt, "sent") <= _exch(rt, "lanes")
    await _close(rt)


def test_a_sending_method_is_served_not_bulk():
    rt = _runtime(Acc, 1, N, stats=False)
    with pytest.raises(NotImplementedError, match="sends messages"):
        rt.call_batch(Acc, "publish", np.arange(4),
                      {"v": np.zeros(4, np.int32),
                       "to": np.zeros((4, K), np.int32),
                       "cnt": np.zeros(4, np.int32)})


def test_a_sender_needs_a_declared_writing_destination():
    class Lost(VectorGrain):
        STATE = {"n": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"n": jnp.int32(0)}

        @sends("nowhere", fanout=2, args={})
        def publish(state, args):
            return state, state["n"], (jnp.zeros(2, jnp.int32),
                                       jnp.zeros(2, bool), {})

    with pytest.raises(TypeError, match="Lost.publish sends to"):
        VectorRuntime(mesh=make_mesh(1), capacity_per_shard=8).register(Lost)


# ---------------------------------------------------------------------------
# served: a silo with write-behind storage, a client over the gateway
# ---------------------------------------------------------------------------

def _silo(storage, n_shards: int = 4, period: float = 0.05):
    b = (SiloBuilder().with_name("sends").with_fabric(SocketFabric())
         .with_config(metrics_enabled=True))
    add_vector_grains(b, Acc, mesh=make_mesh(n_shards), dense={Acc: N},
                      capacity_per_shard=N // n_shards, storage=storage,
                      flush_period=period)
    return b.build()


def _gid(key: int) -> GrainId:
    return GrainId.for_grain(GrainType.of("Acc"), key)


def _args(v, to):
    to = list(to) + [0] * (K - len(to))
    return {"v": np.int32(v), "to": np.array(to, np.int32),
            "cnt": np.int32(len([t for t in to if t]))}


async def _stored(storage, key: int, n: int) -> dict:
    for _ in range(400):
        state, _etag = await storage.read("Acc", _gid(key))
        if state is not None and state["n"] == n:
            return state
        await asyncio.sleep(0.025)
    raise AssertionError(f"key {key} never reached n={n} in storage")


@pytest.mark.parametrize("n_shards", [1, 4])
async def test_delivered_rows_are_flushed_and_readable_from_storage(n_shards):
    storage = MemoryStorage()
    silo = _silo(storage, n_shards)
    await silo.start()
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    try:
        g = client.get_grain(Acc, 1)
        assert int(await g.publish(**_args(7, [20, 40, 60]))) == 1
        assert int(await g.publish(**_args(8, [20]))) == 2
        for k, want in ((20, [7, 8]), (40, [7]), (60, [7])):
            state = await _stored(storage, k, len(want))
            assert list(state["box"])[:len(want)] == want
        assert (await _stored(storage, 1, 0))["seq"] == 2   # the author's
        assert silo.stats.get(EXCHANGE_STATS["delivered"]) == 4
        # the silo's registry has the stage spans, replayed on the loop
        assert silo.stats.histograms["exchange.seconds"].total == 2
    finally:
        await client.close_async()
        await silo.stop()


class _GatedStorage(MemoryStorage):
    """A provider whose bulk read really waits."""

    def __init__(self) -> None:
        super().__init__()
        self.gate = asyncio.Event()
        self.reads: list = []

    async def read_many(self, grain_type, grain_ids):
        grain_ids = list(grain_ids)
        self.reads.append([int(g.key) for g in grain_ids])
        await self.gate.wait()
        return await super().read_many(grain_type, grain_ids)


@pytest.mark.parametrize("suspends", [False, True],
                         ids=["eager-read", "read-that-waits"])
async def test_a_receiver_with_stored_state_is_recovered_before_the_delivery(
        suspends):
    storage = _GatedStorage()
    if not suspends:
        storage.gate.set()
    await storage.write("Acc", _gid(50), {
        "box": [3, 4] + [-1] * (BOX - 2), "n": 2, "seq": 0}, None)
    silo = _silo(storage, period=3600.0)
    await silo.start()
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    try:
        storage.gate.set()
        await client.get_grain(Acc, 1).peek()    # the author is known
        storage.reads.clear()
        if suspends:
            storage.gate.clear()
        fut = asyncio.ensure_future(
            client.get_grain(Acc, 1).publish(**_args(7, [50, 51])))
        if suspends:
            for _ in range(400):
                if [50, 51] in storage.reads:
                    break
                await asyncio.sleep(0.005)
            await asyncio.sleep(0.05)
            # the receivers' pass is in flight: no reply, nothing applied
            assert not fut.done()
            assert silo.stats.get(EXCHANGE_STATS["delivered"]) == 0
            storage.gate.set()
        assert int(await fut) == 1
        assert [50, 51] in storage.reads
        n, box = await client.get_grain(Acc, 50).peek()
        assert (int(n), np.asarray(box).tolist()[:3]) == (3, [3, 4, 7])
        n, box = await client.get_grain(Acc, 51).peek()
        assert (int(n), np.asarray(box).tolist()[:2]) == (1, [7, -1])
        assert silo.stats.get("vector.storage.recovered") == 1
        assert silo.stats.get(EXCHANGE_STATS["activated"]) == 1
    finally:
        await client.close_async()
        await silo.stop()


async def test_a_receiver_whose_stored_row_cannot_be_read_fails_its_sender():
    class Broken(MemoryStorage):
        async def read_many(self, grain_type, grain_ids):
            return [OSError("disk") if int(g.key) == 51 else (None, None)
                    for g in grain_ids]

    silo = _silo(Broken(), period=3600.0)
    await silo.start()
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    try:
        await asyncio.gather(client.get_grain(Acc, 1).peek(),
                             client.get_grain(Acc, 2).peek())
        out = await asyncio.gather(
            client.get_grain(Acc, 1).publish(**_args(7, [50, 51])),
            client.get_grain(Acc, 2).publish(**_args(8, [52])),
            return_exceptions=True)
        assert isinstance(out[0], Exception) and int(out[1]) == 1
        n, _box = await client.get_grain(Acc, 52).peek()
        assert int(n) == 1
    finally:
        await client.close_async()
        await silo.stop()


# ---------------------------------------------------------------------------
# the deployment came in as files alone
# ---------------------------------------------------------------------------

def test_the_benchmark_files_that_were_there_are_untouched():
    """Against the parent commit: this deployment added files under
    ``chipbench/`` and entries to ``BENCHMARK.json`` and edited nothing
    the benchmark had — entries only added, a metric's ``workloads`` list
    may gain a new cell's name at its end (``chipbench/tests/test_ycsb.py``
    holds the same rule by hand). Empty once the PR is the HEAD commit."""
    import json
    import subprocess

    root = os.path.join(os.path.dirname(__file__), os.pardir)

    def git(*a: str) -> str:
        return subprocess.run(["git", *a], capture_output=True, text=True,
                              cwd=root, check=True).stdout
    try:
        changed = git("diff", "--name-status", "HEAD", "--", "chipbench")
        before = json.loads(git("show", "HEAD:BENCHMARK.json"))
        with open(os.path.join(root, "ISSUE.md")) as f:
            if "[benchmark]" in f.readline():
                pytest.skip("a PR of kind benchmark may edit the benchmark")
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        pytest.skip(f"not a git checkout with its issue: {e}")
    assert not [x for x in changed.splitlines() if not x.startswith("A")]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        now = json.load(f)
    for k in ("command", "paths", "run_seconds"):
        assert now[k] == before[k], k
    for k in ("end_to_end", "configs", "workloads", "per_layer"):
        assert len(now[k]) >= len(before[k]), k
        for was, is_now in zip(before[k], now[k]):
            la, lb = was.get("workloads"), is_now.get("workloads")
            assert {**was, "workloads": None} == {**is_now, "workloads": None}
            assert (la is None) == (lb is None)
            assert la is None or lb[:len(la)] == la, k
    assert len(now["end_to_end"]) == len(before["end_to_end"])
