"""The YCSB deployment (``chipbench/apps/ycsb.py``: 1 KB records, ``ver``,
``update`` with a ``bytes`` argument, read-only ``read``) against its
plain reference (``chipbench/references/ycsb.py``) at small sizes on the
CPU: through ``VectorRuntime`` and through a served ``Silo`` + client.

What the deployment forced into the program is held here: freshness
decided at the claim (a read-only first touch neither activates a row nor
leaves anything behind), byte-string arguments staged from their buffer,
numpy replies on the wire without the pickle escape, wide rows flushed as
numpy rows, a read-only kernel that returns no table, and the counters of
hot-key deferrals. Counts and equality only; no timing thresholds.
"""

import asyncio
import importlib.util
import os
import pickle
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.core import serialization as ser
from orleans_tpu.core.ids import GrainId, GrainType
from orleans_tpu.dispatch import (VectorGrain, VectorRuntime, actor_method,
                                  add_vector_grains)
from orleans_tpu.observability.stats import StatsRegistry
from orleans_tpu.parallel import make_mesh
from orleans_tpu.runtime import GatewayClient, SiloBuilder, SocketFabric
from orleans_tpu.storage import MemoryStorage

CHIPBENCH = os.path.join(os.path.dirname(__file__), os.pardir, "chipbench")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "samples"))


def _load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"ycsb_test_{kind}_{name}", os.path.join(CHIPBENCH, kind,
                                                 f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


app = _load("apps", "ycsb")
ref_mod = _load("references", "ycsb")
traffic_mod = _load("traffic", "ycsb_ops")
Record = app.RecordVectorGrain
SEED = app.DATA_SEED
N_KEYS = 64


def _value(rng) -> bytes:
    return rng.integers(0, 256, ref_mod.FIELD_BYTES, np.uint8).tobytes()


def _runtime(stats: bool = False) -> VectorRuntime:
    rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=N_KEYS)
    rt.table(Record).ensure_dense(N_KEYS)
    if stats:
        rt.stats = StatsRegistry()
    return rt


def _reply_bytes(r) -> tuple[int, bytes]:
    return int(r[0]), np.asarray(r[1]).tobytes()


# ---------------------------------------------------------------------------
# the data: initial contents, the scrambled Zipfian and its deal
# ---------------------------------------------------------------------------

def test_initial_state_is_the_references_hash_bit_for_bit():
    keys = np.array([0, 1, 7, 999_999, 2**31 - 1], np.int64)
    rows = jax.vmap(Record.initial_state)(jnp.asarray(keys, jnp.int32))
    fields = np.asarray(rows["fields"])
    assert fields.shape == (len(keys), app.ROW_BYTES)
    for k, row in zip(keys.tolist(), fields):
        want = ref_mod.initial_record(SEED, k)
        assert row[:app.RECORD_BYTES].tobytes() == want
        assert not row[app.RECORD_BYTES:].any()     # the padding is zero
    assert not np.asarray(rows["ver"]).any()
    assert ref_mod.initial_record(SEED, 1) != ref_mod.initial_record(SEED, 2)
    assert ref_mod.initial_record(SEED, 1) != ref_mod.initial_record(
        SEED + 1, 1)


async def test_the_sample_and_the_benchmarks_copy_are_one_grain():
    """``samples/ycsb_tpu.py`` is the grain, ``chipbench/apps/ycsb.py`` the
    benchmark's copy of it: same row, same initial bytes, same answers."""
    import ycsb_tpu as sample

    assert sample.DATA_SEED == SEED
    assert sample.RecordVectorGrain.STATE == Record.STATE
    assert sample.RecordVectorGrain.__name__ == Record.__name__
    keys = jnp.asarray([0, 3, 999_999], jnp.int32)
    a = jax.vmap(sample.RecordVectorGrain.initial_state)(keys)
    b = jax.vmap(Record.initial_state)(keys)
    assert all(np.array_equal(a[f], b[f]) for f in ("fields", "ver"))
    value = bytes(range(100))
    out = []
    for cls in (sample.RecordVectorGrain, Record):
        rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=N_KEYS)
        rt.table(cls).ensure_dense(N_KEYS)
        v1 = await rt.call(cls, 3, "update", field=4, value=value)
        v2 = await rt.call(cls, 3, "update", field=10, value=value)
        out.append((int(v1), int(v2), _reply_bytes(
            await rt.call(cls, 3, "read"))))
        rt.shutdown_worker()
    assert out[0] == out[1] and out[0][:2] == (1, 1)


def test_fnv_hash_is_ycsbs():
    # Utils.fnvhash64 of 0 and 1, computed by hand from the definition
    def fnv(v: int) -> int:
        h = traffic_mod.FNV_OFFSET
        for _ in range(8):
            h = ((h ^ (v & 0xFF)) * traffic_mod.FNV_PRIME) & (2**64 - 1)
            v >>= 8
        return abs(h - 2**64 if h >= 2**63 else h)
    got = traffic_mod.fnv1a64(np.array([0, 1, 12345, 999_999]))
    assert got.tolist() == [fnv(0), fnv(1), fnv(12345), fnv(999_999)]


@pytest.fixture(scope="module")
def zipf_1m():
    zipf = traffic_mod.ScrambledZipfian(1_000_000, 0.99)
    mass = zipf.key_mass()
    return zipf, mass, traffic_mod.deal(mass, 4)


def test_zipfian_is_ycsbs_generator(zipf_1m):
    """``ZipfianGenerator.nextLong`` over ScrambledZipfianGenerator's
    10^10 items: its three branches at their edges, by hand."""
    zipf, mass, _owner = zipf_1m
    zetan, items = traffic_mod.ZETAN, traffic_mod.ITEM_COUNT
    assert (items, zetan) == (10_000_000_000, 26.46902820178302)
    eta = (1 - (2 / items) ** 0.01) / (1 - (1 + 0.5 ** 0.99) / zetan)
    u = np.array([0.0, 0.999 / zetan, 1.001 / zetan,
                  (0.999 + 0.5 ** 0.99) / zetan,
                  (1.001 + 0.5 ** 0.99) / zetan, 0.5, 0.999999])
    want = [0, 0, 1, 1] + [int(items * (eta * x - eta + 1) ** 100)
                           for x in u[4:]]
    assert zipf.ranks(u).tolist() == want
    assert want[4] == 2 and want[6] > 9_900_000_000
    assert zipf.keys(u).tolist() == [
        int(h % 1_000_000) for h in traffic_mod.fnv1a64(np.array(want))]
    with pytest.raises(ValueError, match="ZETAN"):
        traffic_mod.ScrambledZipfian(1_000_000, 0.9)
    # the model of it: a probability; rank 0's record holds 1/ZETAN and
    # the even share of the far ranks, and little else
    assert abs(mass.sum() - 1.0) < 1e-9
    hottest = int(traffic_mod.fnv1a64(np.array([0]))[0] % 1_000_000)
    assert int(np.argmax(mass)) == hottest
    assert 1 / zetan < mass[hottest] < 1 / zetan + 2e-6
    assert abs(mass[hottest] - 0.0378) < 0.0001
    assert abs(np.sort(mass)[-10:].sum() - 0.118) < 0.001
    # the far ranks fall on every record: none is colder than their share
    assert mass.min() > 0.3 / 1_000_000
    # the scramble scatters: the ten hottest are not ten neighbours
    assert np.ptp(np.argsort(-mass)[:10]) > 100_000


def test_deal_gives_each_child_a_quarter_of_the_mass(zipf_1m):
    _zipf, mass, owner = zipf_1m
    share = np.bincount(owner, weights=mass, minlength=4)
    assert np.abs(share - 0.25).max() < 0.001
    assert np.bincount(owner, minlength=4).min() > 200_000  # and of the keys


def test_the_generators_draws_are_the_mass_dealt(zipf_1m):
    """Four million draws of the generator itself: each child's records
    take a quarter within 0.1 % (of all draws) and sampling error, and
    the ten hottest records come as often as ``key_mass`` says, within
    four standard errors. A child keeps the draws on its own records, so
    at equal rates the union of the children's draws is this."""
    zipf, mass, owner = zipf_1m
    n = 4_000_000
    keys = zipf.keys(np.random.default_rng(28).random(n))
    share = np.bincount(owner[keys], minlength=4) / n
    assert np.abs(share - 0.25).max() < 0.001 + 4 * np.sqrt(0.1875 / n)
    counts = np.bincount(keys, minlength=len(mass))
    for k in np.argsort(-mass)[:10]:
        p = mass[k]
        assert abs(counts[k] / n - p) < 4 * np.sqrt(p / n)
    # the far ranks: a third of the draws and more, spread evenly
    far = zipf.ranks(np.random.default_rng(29).random(n)) >= traffic_mod.HEAD
    assert 0.34 < far.mean() < 0.37


def test_a_childs_block_is_drawn_from_its_own_records():
    ctx = {
        "params": {"grain": "RecordVectorGrain", "read_proportion": 0.5,
                   "zipfian_constant": 0.99, "warm_ops": 1},
        "config": {"data_seed": SEED, "recordcount": 8192},
        "grains": app.GRAINS, "reference": ref_mod, "response_timeout": 5.0,
        "child": 1, "n_children": 4, "n_callers": 8, "callers": [2, 3],
        "seed": 2_800_000_999,
    }
    t = traffic_mod.Traffic(ctx)
    ops = t._draw(t.rngs[0])
    assert len(ops) == traffic_mod.BLOCK
    keys = np.array([o[0] for o in ops])
    assert (t.owner[keys] == 1).all()
    reads = np.mean([o[1] for o in ops])
    assert 0.4 < reads < 0.6
    assert {o[2] for o in ops} == set(range(10))
    assert all(type(o[3]) is bytes and len(o[3]) == 100 for o in ops)
    # the same seed and caller draw the same block; another caller's differs
    again = traffic_mod.Traffic(ctx)
    assert again._draw(again.rngs[0]) == ops
    assert again._draw(again.rngs[1]) != ops


def test_deal_refuses_a_mass_it_cannot_balance():
    mass = np.array([0.9] + [0.1 / 99] * 99)
    with pytest.raises(ValueError, match="shares"):
        traffic_mod.deal(mass, 4)


# ---------------------------------------------------------------------------
# the reference is strict
# ---------------------------------------------------------------------------

def test_reference_orders_updates_by_ver_and_judges_reads_lazily():
    rng = np.random.default_rng(1)
    ref = ref_mod.Reference(SEED)
    v1, v2 = _value(rng), _value(rng)
    s0 = ref_mod.initial_record(SEED, 5)
    s1 = v1 + s0[100:]
    s2 = s1[:300] + v2 + s1[400:]
    ref.sending_update(5)
    ref.sending_update(5)
    assert ref.read(5, 2, s2) == 0          # waits for ver 2
    assert ref.update(5, 3, v2, 2) == 0     # acknowledged out of order
    assert ref.read(5, 0, s0) == 0
    assert ref.update(5, 0, v1, 1) == 0     # applies 1, then 2, judges
    assert ref.read(5, 1, s1) == 0          # an older ver, from the undo log
    assert ref.read(5, 2, s2) == 0
    # ... but not for a read sent after ver 2 was acknowledged
    assert ref.sending_read(5) == 2
    assert ref.read(5, 1, s1, floor=2) == 1
    assert ref.read(5, 2, s2, floor=2) == 0
    keys, states = ref.states()
    assert keys == [5] and states["ver"].tolist() == [2]
    assert states["fields"][0, :1000].tobytes() == s2
    assert not states["fields"][0, 1000:].any()


@pytest.mark.parametrize("fault", ["byte", "stale", "repeat", "gap",
                                   "unsent", "short", "waiting_byte",
                                   "never_acknowledged", "not_read_back",
                                   "not_read_back_unapplied"])
def test_reference_finds(fault):
    rng = np.random.default_rng(2)
    ref = ref_mod.Reference(SEED)
    v1, v2 = _value(rng), _value(rng)
    s0 = ref_mod.initial_record(SEED, 9)
    s1 = s0[:900] + v1
    ref.sending_update(9)
    if fault == "waiting_byte":
        # the read is wrong, and is found when its update is applied
        assert ref.read(9, 1, s1[:-1] + bytes([s1[-1] ^ 1])) == 0
        assert ref.update(9, 9, v1, 1) == 1
        return
    if fault == "never_acknowledged":
        # a read saw ver 1; no update was ever acknowledged with it
        assert ref.read(9, 1, s1) == 0
        _keys, states = ref.states()
        assert states["ver"].tolist() == [-1]   # no row can match
        return
    if fault == "not_read_back_unapplied":
        # ver 2 acknowledged before ver 1: not applied yet, a floor already
        ref.sending_update(9)
        assert ref.sending_read(9) == 0
        assert ref.update(9, 0, v2, 2) == 0
        assert ref.sending_read(9) == 2
        assert ref.read(9, 0, s0, floor=2) == 1
        return
    assert ref.update(9, 9, v1, 1) == 0
    if fault == "not_read_back":
        # a read sent after the acknowledgement answers the initial
        # record at ver 0: right bytes for its ver, and stale
        floor = ref.sending_read(9)
        assert floor == 1 and ref.read(9, 0, s0, floor) == 1
        assert ref.read(9, 0, s0) == 0      # sent before it: fine
        assert ref.read(9, 1, s1, floor) == 0
        return
    if fault == "byte":       # one bit of one byte of a value
        assert ref.read(9, 1, s1[:950] + bytes([s1[950] ^ 1]) + s1[951:]) == 1
    elif fault == "stale":    # the state of another ver than reported
        assert ref.read(9, 1, s0) == 1
        assert ref.read(9, 0, s1) == 1
    elif fault == "repeat":   # a ver acknowledged twice
        ref.sending_update(9)
        assert ref.update(9, 0, v2, 1) == 1
    elif fault == "gap":      # a ver beyond the updates sent
        ref.sending_update(9)
        assert ref.update(9, 0, v2, 3) == 1
    elif fault == "unsent":   # a read of a ver nobody can have written
        assert ref.read(9, 2, s1) == 1
    elif fault == "short":
        assert ref.read(9, 1, s1[:999]) == 1


def test_a_lost_update_takes_its_key_out_of_judgement():
    ref = ref_mod.Reference(SEED)
    ref.sending_update(3)
    ref.forget(3)
    assert ref.read(3, 1, b"x" * 1000) == 0
    assert ref.update(3, 0, b"y" * 100, 7) == 0


# ---------------------------------------------------------------------------
# the traffic kind against a plain record store: what it books, and how the
# closed loop passes from the warm-up into the window
# ---------------------------------------------------------------------------

class _Store:
    """A client-shaped record store in plain Python. ``stale``: reads
    answer the initial record at ver 0 whatever was written (a cache that
    is never invalidated) — right bytes for the ver reported, and stale."""

    def __init__(self, stale: bool = False) -> None:
        self.rows: dict = {}
        self.stale = stale
        self.in_flight = self.most_in_flight = self.calls = 0

    def _row(self, key):
        return self.rows.setdefault(
            key, [0, bytearray(ref_mod.initial_record(SEED, key))])

    def get_grain(self, _cls, key):
        return _StoreGrain(self, key)

    def call_batch(self, _cls, method, items, timeout=None):
        assert method == "read"
        return [asyncio.ensure_future(self.get_grain(None, k).read())
                for k, _args in items]


class _StoreGrain:
    def __init__(self, store, key) -> None:
        self.store, self.key = store, key

    async def _turn(self) -> None:
        s = self.store
        s.calls += 1
        s.in_flight += 1
        s.most_in_flight = max(s.most_in_flight, s.in_flight)
        await asyncio.sleep(0.001)
        s.in_flight -= 1

    async def read(self):
        await self._turn()
        if self.store.stale:
            data = ref_mod.initial_record(SEED, self.key)
            return np.int32(0), np.frombuffer(data, np.uint8)
        ver, state = self.store._row(self.key)
        return np.int32(ver), np.frombuffer(bytes(state), np.uint8)

    async def update(self, field, value):
        await self._turn()
        row = self.store._row(self.key)
        row[1][field * 100:(field + 1) * 100] = value
        row[0] += 1
        return np.int32(row[0])


def _traffic(callers: int = 4, seed: int = 2_800_000_123, **over):
    ctx = {
        "params": {"grain": "RecordVectorGrain", "read_proportion": 0.5,
                   "zipfian_constant": 0.99, "warm_ops": 3},
        "config": {"data_seed": SEED, "recordcount": 64},
        "grains": app.GRAINS, "reference": ref_mod, "response_timeout": 5.0,
        "child": 0, "n_children": 1, "n_callers": callers,
        "callers": list(range(callers)), "seed": seed, **over,
    }
    return traffic_mod.Traffic(ctx)


@pytest.mark.parametrize("stale", [False, True], ids=["honest", "stale"])
async def test_an_acknowledged_write_not_read_back_is_a_wrong_reply(stale):
    """64 records, so every key is written and read again many times. The
    harness's ``correct`` needs ``wrong_replies`` 0 (``run.py``): a store
    whose reads never see a write is found, an honest one is not."""
    t, store = _traffic(), _Store(stale)
    tot = np.zeros(3, np.int64)
    for _ in range(300):
        tot += await t._operation(store, 0)
    assert tot[1] == 0 and tot.sum() == 300
    if stale:
        assert tot[2] > 50          # every read of a key already written
    else:
        assert tot[2] == 0 and tot[0] == 300
        keys, states = t.ref.states()
        for i, k in enumerate(keys):
            assert states["ver"][i] == store.rows[k][0]
            assert states["fields"][i, :1000].tobytes() == \
                bytes(store.rows[k][1])


async def test_the_window_takes_the_callers_loops_over():
    """``warm_up`` starts every caller's loop and returns when each has
    done ``warm_ops`` operations; the loops go on; the window's first
    ``request`` of a caller is the operation in flight, later ones are
    one operation each; in flight never passes the callers."""
    t, store = _traffic(callers=4), _Store()
    warm = await t.warm_up(store)
    assert warm[1:] == (0, 0) and warm[0] >= 4 * 3
    assert len(t.preroll) == 4 and t.warming
    before, store.most_in_flight = store.calls, 0   # the bursts are over
    await asyncio.sleep(0.05)                   # the harness gets ready
    assert store.calls > before + 20            # ... and the loops go on
    assert store.most_in_flight <= 4
    first = await asyncio.gather(*(t.request(store, s) for s in range(4)))
    assert first == [(1, 0, 0)] * 4 and not t.preroll and not t.warming
    calls = store.calls
    await asyncio.sleep(0.02)
    assert store.calls == calls                 # nothing runs by itself now
    assert await t.request(store, 2) == (1, 0, 0)
    assert store.calls == calls + 1
    assert store.most_in_flight == 4 and store.in_flight == 0


async def test_what_fails_after_ready_is_booked_on_the_first_request():
    t, store = _traffic(callers=2), _Store()
    await t.warm_up(store)
    store.stale = True                          # from here on, stale reads
    await asyncio.sleep(0.1)
    out = [await t.request(store, s) for s in range(2)]
    assert sum(o[2] for o in out) > 1           # those of the loops' 0.1 s
    assert all(o[0] in (0, 1) for o in out)     # one operation's ok at most


async def test_an_unclean_warm_up_ends_the_run():
    t, store = _traffic(callers=2), _Store(stale=True)
    t.ref.sending_update(int(t.mine[0]))        # something acknowledged
    t.ref.update(int(t.mine[0]), 0, b"\1" * 100, 1)
    with pytest.raises(RuntimeError, match="warm-up"):
        await t.warm_up(store)
    await asyncio.sleep(0)
    assert all(task.cancelled() or task.done()
               for task in t.preroll.values())


# ---------------------------------------------------------------------------
# through VectorRuntime
# ---------------------------------------------------------------------------

def _key_hash(rt, key) -> int:
    a = rt.actor(Record, key)
    return a.key_hash if hasattr(a, "key_hash") else rt.key_hash_for(
        key, GrainId.for_grain(GrainType.of("RecordVectorGrain"),
                               key).uniform_hash)


@pytest.mark.parametrize("key", [11, "user-hot"], ids=["dense", "hashed"])
async def test_hot_key_burst_in_one_tick(key):
    """k updates and k reads of one key enqueued together: one of each a
    tick, the rest deferred; ver 1..k; every read is the state at the ver
    it reports."""
    k = 6
    rt = _runtime()
    kh = _key_hash(rt, key)
    rk = kh & 0x7FFFFFFF                      # what initial_state is given
    rng = np.random.default_rng(3)
    ref = ref_mod.Reference(SEED)
    vals = [(int(rng.integers(10)), _value(rng)) for _ in range(k)]
    ups, reads = [], []
    for field, value in vals:
        ref.sending_update(rk)
        ups.append(rt.call(Record, kh, "update", field=field, value=value))
        reads.append(rt.call(Record, kh, "read"))
    await rt.flush()
    vers = [int(await f) for f in ups]
    assert vers == list(range(1, k + 1))      # FIFO within the method
    for (field, value), ver in zip(vals, vers):
        assert ref.update(rk, field, value, ver) == 0
    seen = []
    for f in reads:
        ver, data = _reply_bytes(await f)
        assert ref.read(rk, ver, data) == 0
        seen.append(ver)
    assert seen == sorted(seen)
    # each method defers k-1, then k-2, ... of its own
    assert rt.conflicts_deferred == k * (k - 1)
    _keys, states = ref.states()
    row = rt.table(Record).read_row(kh)
    assert np.array_equal(row["fields"], states["fields"][0])
    assert int(row["ver"]) == k
    rt.shutdown_worker()


async def test_deferral_counters_and_method_mix():
    rt = _runtime(stats=True)
    futs = [rt.call(Record, 4, "read") for _ in range(3)]
    futs += [rt.call(Record, 4, "update", field=0, value=b"\1" * 100)
             for _ in range(2)]
    futs += [rt.call(Record, k, "read") for k in (5, 6)]
    await rt.flush()
    await asyncio.gather(*futs)
    st = rt.stats
    # reads of key 4: two waited (one of them twice); updates: one waited
    assert st.get("engine.deferred") == 3
    assert rt.conflicts_deferred == 2 + 1 + 1
    assert st.histograms["engine.defer_wait.seconds"].total == 3
    assert st.get("ingest.messages.RecordVectorGrain.read") == 5
    assert st.get("ingest.messages.RecordVectorGrain.update") == 2
    assert st.get("ingest.messages") == 7
    assert st.histograms["engine.claim.seconds"].total >= 2
    rt.shutdown_worker()


async def test_deferral_counter_exists_at_zero():
    rt = _runtime(stats=True)
    await rt.call(Record, 1, "read")
    assert rt.stats.counters["engine.deferred"] == 0
    assert "engine.defer_wait.seconds" not in rt.stats.histograms
    rt.shutdown_worker()


@pytest.mark.parametrize("order", ["read_then_update", "same_tick",
                                   "update_first_same_tick"])
@pytest.mark.parametrize("key", [21, "user-abc"], ids=["dense", "hashed"])
async def test_a_read_only_first_touch_does_not_activate(order, key):
    """A read-only method writes nothing back, so the row is initialised
    by the first WRITE that is claimed, whatever was read before it."""
    rt = _runtime()
    kh = _key_hash(rt, key)
    init = jax.vmap(Record.initial_state)(
        jnp.asarray([kh & 0x7FFFFFFF], jnp.int32))
    s0 = np.asarray(init["fields"])[0, :1000].tobytes()
    v = b"\7" * 100
    if order == "read_then_update":
        ver, data = _reply_bytes(await rt.call(Record, kh, "read"))
        assert (ver, data) == (0, s0)
        ver, data = _reply_bytes(await rt.call(Record, kh, "read"))
        assert (ver, data) == (0, s0)           # still fresh, still right
        assert int(await rt.call(Record, kh, "update", field=2,
                                 value=v)) == 1
    else:
        first, second = ("read", "update") if order == "same_tick" \
            else ("update", "read")
        futs = {}
        for m in (first, second):
            futs[m] = rt.call(Record, kh, m, **(
                {"field": 2, "value": v} if m == "update" else {}))
        await rt.flush()
        assert int(await futs["update"]) == 1
        ver, data = _reply_bytes(await futs["read"])
        # the groups run in the order they were first met this tick
        assert ver == (0 if first == "read" else 1)
        assert data == (s0 if ver == 0 else s0[:200] + v + s0[300:])
    ver, data = _reply_bytes(await rt.call(Record, kh, "read"))
    assert ver == 1 and data == s0[:200] + v + s0[300:]
    rt.shutdown_worker()


async def test_bytes_arguments_are_staged_from_their_buffer():
    rt = _runtime()
    rng = np.random.default_rng(4)
    value = _value(rng)
    as_list = list(value)
    await rt.call(Record, 1, "update", field=3, value=value)
    await rt.call(Record, 2, "update", field=3, value=as_list)
    await rt.call(Record, 3, "update", field=3,
                  value=np.frombuffer(value, np.uint8))
    tbl = rt.table(Record)
    got = [tbl.read_row(k)["fields"][300:400].tobytes() for k in (1, 2, 3)]
    assert got == [value] * 3
    with pytest.raises(ValueError, match="takes 100 bytes, got 99"):
        await rt.call(Record, 4, "update", field=0, value=value[:99])
    # the failed batch wrote nothing and the table still ticks
    assert int(await rt.call(Record, 4, "update", field=0, value=value)) == 1
    rt.shutdown_worker()


async def test_update_outside_the_fields_writes_nothing():
    rt = _runtime()
    assert int(await rt.call(Record, 8, "update", field=-1,
                             value=b"\xff" * 100)) == 0
    assert int(await rt.call(Record, 8, "update", field=10,
                             value=b"\xff" * 100)) == 0
    ver, data = _reply_bytes(await rt.call(Record, 8, "read"))
    assert ver == 0 and data == ref_mod.initial_record(SEED, 8)
    rt.shutdown_worker()


class Peek(VectorGrain):
    STATE = {"n": (jnp.int32, (4,))}

    @staticmethod
    def initial_state(key_hash):
        return {"n": jnp.zeros(4, jnp.int32)}

    @actor_method(args={"x": (jnp.int32, ())}, read_only=True)
    def peek(state, args):
        return state, state["n"] + args["x"]


@pytest.mark.parametrize("scan", [0, 4])
def test_read_only_kernel_returns_no_table(scan):
    """A table passed through a jit that does not donate it comes back as
    a copy: a read-only kernel has no state output at all."""
    rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=16)
    tbl = rt.table(Peek)
    kern = rt._build_kernel(Peek, "peek", scan_rounds=scan)
    lead = (scan,) if scan else ()
    lane = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    mask = jax.ShapeDtypeStruct((1, 8), jnp.bool_)
    out_state, results = jax.eval_shape(
        kern, tbl.state, lane, lane, mask, mask,
        {"x": jax.ShapeDtypeStruct((*lead, 1, 8), jnp.int32)})
    assert out_state == ()
    assert results.shape == (*lead, 1, 8, 4)


async def test_read_only_rounds_still_answer():
    """call_batch_rounds over a read-only method (the scanned kernel
    with no table output) and a result-less sync."""
    rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=16)
    rt.table(Peek).ensure_dense(8)
    keys = np.arange(8)
    out = rt.call_batch_rounds(
        Peek, "peek", keys,
        {"x": np.arange(16, dtype=np.int32).reshape(2, 8)})
    assert np.asarray(out).shape == (2, 8, 4)
    assert np.asarray(out)[1, 3].tolist() == [11] * 4
    one = rt.call_batch(Peek, "peek", keys, {"x": np.ones(8, np.int32)})
    assert np.asarray(one).tolist() == [[1] * 4] * 8
    assert int((await rt.call(Peek, 2, "peek", x=5))[0]) == 5
    rt.shutdown_worker()


# ---------------------------------------------------------------------------
# the wire: numpy replies without the pickle escape, equal on both codecs
# ---------------------------------------------------------------------------

REPLIES = {
    "read": (np.int32(3), np.arange(1000, dtype=np.uint8) % 251),
    # a row of a host array that is not C-ordered is packed, not pickled
    "strided": np.arange(24, dtype=np.uint8).reshape(4, 6).T[1],
    "fortran": np.asfortranarray(np.arange(12, dtype=np.int32).reshape(3, 4)),
    "update": np.int32(41),
    "presence": np.int32(-7),
    "f16": np.arange(6, dtype=np.float16).reshape(2, 3),
    "bool": np.bool_(True),
    "empty": np.zeros((0, 4), np.int64),
    "nested": {"a": [np.float32(1.5), np.uint8(200)], "b": b"\0\1"},
}


def _same(a, b) -> bool:
    if isinstance(a, (tuple, list)):
        return type(a) is type(b) and len(a) == len(b) and all(
            _same(x, y) for x, y in zip(a, b))
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, (np.ndarray, np.generic)):
        return type(a) is type(b) and a.dtype == b.dtype and \
            a.shape == b.shape and np.array_equal(a, b)
    return type(a) is type(b) and a == b


@pytest.mark.parametrize("name", sorted(REPLIES))
def test_numpy_replies_ride_natively_and_equal_the_python_codec(name):
    hw = ser._hotwire
    if hw is None:
        pytest.skip("no native codec in this build")
    value = REPLIES[name]
    before = hw.pickle_escapes()
    native = ser.deserialize(ser.serialize(value))
    assert hw.pickle_escapes() == before        # zero pickle escapes
    python = ser.deserialize(ser.serialize_portable(value))  # all pickle
    assert _same(native, python) and _same(native, value)
    assert b"numpy" not in ser.serialize(value)


def test_decoded_arrays_own_their_memory():
    if ser._hotwire is None:
        pytest.skip("no native codec in this build")
    a = ser.deserialize(ser.serialize(np.arange(8, dtype=np.int32)))
    a[0] = 99                                   # writable, like pickle's
    assert a.tolist()[:2] == [99, 1]


@pytest.mark.parametrize("value", [
    np.array(["a", "b"]),                       # no buffer form
    np.complex64(1j), np.datetime64("2026-10-01"), np.bytes_(b"xy"),
    np.array([1j, 2j])[::-1],
], ids=["str", "complex", "datetime", "bytes_", "strided_complex"])
def test_what_the_array_tag_does_not_carry_still_escapes(value):
    hw = ser._hotwire
    if hw is None:
        pytest.skip("no native codec in this build")
    before = hw.pickle_escapes()
    out = ser.deserialize(ser.serialize(value))
    assert hw.pickle_escapes() == before + 2    # encode and decode
    assert _same(out, pickle.loads(pickle.dumps(value)))


@pytest.mark.parametrize("blob", [
    b"\xa7\x01\x12i\x04\x00\x01\x08" + b"\0" * 31,      # one byte short
    b"\xa7\x01\x12x\x04\x00\x01\x01" + b"\0" * 4,       # unknown kind
    b"\xa7\x01\x12i\x03\x00\x01\x01" + b"\0" * 3,       # itemsize 3
    b"\xa7\x01\x12i\x04\x00\x21" + b"\x01" * 33 + b"\0" * 4,  # 33 dims
    b"\xa7\x01\x12i\x08\x00\x02" + b"\xff\xff\xff\xff\xff\xff\xff\xff\x7f" * 2,
], ids=["truncated", "kind", "itemsize", "ndim", "overflow"])
def test_hostile_array_headers_are_refused(blob):
    if ser._hotwire is None:
        pytest.skip("no native codec in this build")
    with pytest.raises(ValueError):
        ser.deserialize(blob)


# ---------------------------------------------------------------------------
# served: Silo + GatewayClient + write-behind storage
# ---------------------------------------------------------------------------

def _silo(storage, metrics: bool = True, period: float = 0.05):
    b = (SiloBuilder().with_name("ycsb-test").with_fabric(SocketFabric())
         .with_config(metrics_enabled=metrics))
    add_vector_grains(b, Record, mesh=make_mesh(1), dense={Record: N_KEYS},
                      capacity_per_shard=N_KEYS, storage=storage,
                      flush_period=period)
    return b.build()


async def _stored(storage, key: int):
    state, _etag = await storage.read(
        "RecordVectorGrain",
        GrainId.for_grain(GrainType.of("RecordVectorGrain"), key))
    return state


async def _until(cond, what: str) -> None:
    for _ in range(400):
        if cond():
            return
        await asyncio.sleep(0.025)
    raise AssertionError(what)


async def test_served_mix_against_the_reference():
    """Seeded skewed reads and updates from concurrent callers, judged
    reply by reply; then every updated row of the table, and every
    acknowledged update read back from storage byte for byte; nothing
    took the pickle escape on the silo's side of the wire."""
    storage = MemoryStorage()
    silo = _silo(storage)
    await silo.start()
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    ref = ref_mod.Reference(SEED)
    rng = np.random.default_rng(2800)
    wrong = []

    async def caller(c: int) -> None:
        for _ in range(40):
            # two hot keys, a tail
            key = int(rng.choice([0, 1, int(rng.integers(2, N_KEYS))],
                                 p=[0.3, 0.2, 0.5]))
            g = client.get_grain(Record, key)
            if rng.random() < 0.5:
                floor = ref.sending_read(key)   # acknowledged by now
                ver, data = _reply_bytes(await g.read())
                wrong.append(ref.read(key, ver, data, floor))
            else:
                field, value = int(rng.integers(10)), _value(rng)
                ref.sending_update(key)
                r = await g.update(field=field, value=value)
                wrong.append(ref.update(key, field, value, int(r)))

    try:
        await asyncio.gather(*(caller(c) for c in range(16)))
        assert sum(wrong) == 0 and len(wrong) == 640
        keys, states = ref.states()
        assert (states["ver"] > 0).all()        # nothing left waiting
        await silo.vector.flush()
        tbl = silo.vector.table(Record)
        for i, k in enumerate(keys):
            row = tbl.read_row(k)
            assert np.array_equal(row["fields"], states["fields"][i])
            assert int(row["ver"]) == states["ver"][i]
        # hot keys collided: the deferral counters saw it
        assert silo.stats.get("engine.deferred") > 0
        assert silo.vector.conflicts_deferred >= silo.stats.get(
            "engine.deferred")
        # every acknowledged update, from storage, byte for byte
        async def all_stored() -> bool:
            for i, k in enumerate(keys):
                s = await _stored(storage, k)
                if s is None or int(s["ver"]) != states["ver"][i] or \
                        not np.array_equal(s["fields"], states["fields"][i]):
                    return False
            return True
        for _ in range(200):
            if await all_stored():
                break
            await asyncio.sleep(0.05)
        else:
            raise AssertionError("an acknowledged update is not readable")
        one = await _stored(storage, keys[0])
        assert isinstance(one["fields"], np.ndarray)   # one buffer, no list
        assert one["fields"].dtype == np.uint8
        # keys that were only read are not in storage
        only_read = [k for k in ref.rows if ref.rows[k].ver == 0]
        for k in only_read:
            assert await _stored(storage, k) is None
        if ser._hotwire is not None:
            assert silo.stats.counters["wire.pickled_values"] == 0
        assert silo.stats.histograms["egress.encode.bytes"].sum > 640 * 40
    finally:
        await client.close_async()
        await silo.stop()


async def test_a_read_only_touch_leaves_nothing_dirty_and_nothing_stored():
    storage = MemoryStorage()
    silo = _silo(storage)
    await silo.start()
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    try:
        for k in range(8):
            ver, data = _reply_bytes(await client.get_grain(Record, k).read())
            assert (ver, data) == (0, ref_mod.initial_record(SEED, k))
        await asyncio.sleep(0.3)                # six flush periods
        rt = silo.vector
        assert rt.drain_dirty(Record).size == 0
        assert not storage._data
        assert silo.stats.get("vector.storage.flushed") == 0
        assert not rt.table(Record).dense_active[:8].any()
        # the first write activates, and only it is written behind
        v = b"\x5a" * 100
        assert int(await client.get_grain(Record, 3).update(
            field=9, value=v)) == 1
        await _until(lambda: silo.stats.get("vector.storage.flushed") >= 1,
                     "the update was never flushed")
        s = await _stored(storage, 3)
        assert s["fields"][:1000].tobytes() == \
            ref_mod.initial_record(SEED, 3)[:900] + v
        assert int(s["ver"]) == 1
        assert len(storage._data) == 1
    finally:
        await client.close_async()
        await silo.stop()


async def test_a_stale_row_fails_the_storage_comparison():
    """What the harness compares: the stored row against the reference's
    newest state; a flush of an older ver differs in ver and in bytes."""
    rng = np.random.default_rng(6)
    ref = ref_mod.Reference(SEED)
    rt = _runtime()
    stale = None
    for ver in (1, 2):
        field, value = ver, _value(rng)
        ref.sending_update(2)
        assert int(await rt.call(Record, 2, "update", field=field,
                                 value=value)) == ver
        assert ref.update(2, field, value, ver) == 0
        if ver == 1:
            stale = {k: np.array(v) for k, v in
                     rt.table(Record).read_row(2).items()}
    _keys, states = ref.states()
    fresh = rt.table(Record).read_row(2)
    assert np.array_equal(fresh["fields"], states["fields"][0])
    assert not np.array_equal(stale["fields"], states["fields"][0])
    assert int(stale["ver"]) != states["ver"][0]
    rt.shutdown_worker()
