"""Batched ingress pipeline (PR 7): vectorized frame-batch codec
(hotwire.c pack_batch/unpack_batch + wire.decode_frames), the batched
wire→message-center→engine hand-off, double-buffered engine staging, the
queue-wait-trend load shed, and the hot lane's batch-aware fairness
yield."""

import asyncio
import random
import struct
import time

import numpy as np
import pytest

import orleans_tpu.core.serialization as ser
from orleans_tpu.core.ids import GrainId, GrainType, SiloAddress
from orleans_tpu.core.message import (Category, Direction, Message,
                                      make_request, set_debug_pool)
from orleans_tpu.observability.stats import QueueWaitTrend
from orleans_tpu.runtime import ClusterClient, Grain, SiloBuilder
from orleans_tpu.runtime.wire import (FrameError, _BodyDecodeError,
                                      decode_frames, decode_message,
                                      encode_message, encode_message_batch)

hw = ser._hotwire

GT = GrainType.of("bi.Echo")
SILO = SiloAddress("10.1.2.3", 7777, 42)


def _corpus_messages(n: int = 40, timeout=None) -> list:
    """Messages with varied headers/bodies (``timeout=None`` keeps the
    TTL out of the frames so two encodes of one message are
    byte-identical)."""
    rng = random.Random(1234)
    bodies = [None, 0, -1, 3.5, "text", b"bytes", (1, "a"), [1, [2]],
              {"k": (GT,)}, ((), {"x": 7}), ((1, 2), {"deep": {"d": [9]}})]
    out = []
    for i in range(n):
        msg = make_request(
            target_grain=GrainId.for_grain(GT, i),
            interface_name="bi.IEcho", method_name=f"m{i % 5}",
            body=rng.choice(bodies),
            direction=rng.choice([Direction.REQUEST, Direction.ONE_WAY]),
            sending_silo=SILO, target_silo=SILO,
            call_chain=(GrainId.for_grain(GT, i - 1),) if i % 3 else (),
            request_context={"trace": f"t-{i}"} if i % 4 == 0 else None,
            timeout=timeout,
        )
        out.append(msg)
    return out


def _split_frames(buf: bytes) -> list:
    frames = []
    pos = 0
    while pos < len(buf):
        hlen, blen = struct.unpack_from("<II", buf, pos)
        h0 = pos + 8
        frames.append((buf[h0:h0 + hlen], buf[h0 + hlen:h0 + hlen + blen]))
        pos = h0 + hlen + blen
    return frames


def _slots_equal(a: Message, b: Message) -> bool:
    for s in Message.__slots__:
        if s in ("received_at", "_pool_free", "_pool_gen", "expires_at"):
            continue
        if getattr(a, s) != getattr(b, s):
            return False
    return True


# ---------------------------------------------------------------------------
# Codec property: batch bytes == per-frame bytes, decode round-trips
# ---------------------------------------------------------------------------

@pytest.mark.skipif(hw is None, reason="native toolchain unavailable")
def test_pack_batch_bytes_identical_to_per_frame():
    msgs = _corpus_messages()
    items = [(m, None, ser.serialize(m.body)) for m in msgs]
    batch = hw.pack_batch(items)
    per_frame = b"".join(hw.pack_frame(*it) for it in items)
    assert batch == per_frame
    # and identical to the public encode path (encode_message emits the
    # same frames; encode_message_batch emits ONE chunk holding them all)
    assert per_frame == b"".join(encode_message(m) for m in msgs)
    chunks = encode_message_batch(msgs, bounce=lambda m, e: None)
    assert b"".join(chunks) == batch


@pytest.mark.skipif(hw is None, reason="native toolchain unavailable")
def test_decode_frames_matches_per_frame_decode():
    msgs = _corpus_messages(timeout=30.0)
    buf = bytearray(b"".join(encode_message(m) for m in msgs))
    consumed, decoded, bounces = decode_frames(buf)
    assert consumed == len(buf) and not bounces
    assert len(decoded) == len(msgs)
    for headers_body, batch_msg, orig in zip(
            _split_frames(bytes(buf)), decoded, msgs):
        single = decode_message(*headers_body)
        assert _slots_equal(single, batch_msg)
        assert _slots_equal(batch_msg, orig)
        # TTL rebased into a live expiry on both paths
        assert batch_msg.expires_at is not None
        assert abs(batch_msg.expires_at - single.expires_at) < 1.0


def test_decode_frames_python_fallback_equivalent(monkeypatch):
    """ORLEANS_TPU_NATIVE=0 path: same wire bytes, per-frame fallback
    codec, identical decoded messages."""
    msgs = _corpus_messages()
    native_frames = b"".join(encode_message(m) for m in msgs)
    monkeypatch.setattr(ser, "_hotwire", None)
    pickle_frames = b"".join(encode_message(m) for m in msgs)
    # native frames are NOT decodable without the extension, but the
    # fallback-encoded frames decode through the same decode_frames entry
    consumed, decoded, bounces = decode_frames(bytearray(pickle_frames))
    assert consumed == len(pickle_frames) and not bounces
    assert len(decoded) == len(msgs)
    for m, orig in zip(decoded, msgs):
        assert _slots_equal(m, orig)
    monkeypatch.setattr(ser, "_hotwire", hw)
    if hw is not None:
        # mixed-build peers: the NATIVE receiver decodes the pickle
        # peer's frames out of one batch buffer
        consumed, decoded, _ = decode_frames(bytearray(pickle_frames))
        assert consumed == len(pickle_frames)
        assert all(_slots_equal(m, o) for m, o in zip(decoded, msgs))
        # and a buffer interleaving both forms decodes in order
        mix = bytearray()
        expect = []
        for i, m in enumerate(msgs[:10]):
            mix += encode_message(m, native=bool(i % 2))
            expect.append(m)
        consumed, decoded, _ = decode_frames(mix)
        assert consumed == len(mix)
        assert all(_slots_equal(m, o) for m, o in zip(decoded, expect))


def test_decode_frames_partial_tail_and_resume():
    msgs = _corpus_messages(8)
    whole = b"".join(encode_message(m) for m in msgs)
    cut = len(whole) - 11  # mid-frame
    buf = bytearray(whole[:cut])
    consumed, decoded, _ = decode_frames(buf)
    assert consumed < len(buf)  # stopped on the frame boundary
    assert len(decoded) == len(msgs) - 1
    del buf[:consumed]
    buf += whole[cut:]  # the rest of the socket stream arrives
    consumed2, decoded2, _ = decode_frames(buf)
    assert consumed2 == len(buf) and len(decoded2) == 1
    assert _slots_equal(decoded2[0], msgs[-1])


def test_decode_frames_bounces_undecodable_body_mid_batch():
    """A frame whose BODY fails to decode, sitting between good frames:
    the good ones decode, the bad one surfaces as a bounce (headers
    intact so the receiver can reject back to the sender)."""
    good1, bad, good2 = _corpus_messages(3)
    bad_frame_headers = _split_frames(encode_message(bad))[0][0]
    from orleans_tpu.runtime.wire import encode_frame
    frames = (encode_message(good1)
              + encode_frame(bad_frame_headers, b"\xa7\x01\x99")  # bad tag
              + encode_message(good2))
    consumed, decoded, bounces = decode_frames(bytearray(frames))
    assert consumed == len(frames)
    assert [m.method_name for m in decoded] == [good1.method_name,
                                                good2.method_name]
    assert len(bounces) == 1 and isinstance(bounces[0], _BodyDecodeError)
    assert bounces[0].message.method_name == bad.method_name
    assert bounces[0].message.body is None


def test_decode_frames_oversized_announcement_drops_connection():
    evil = struct.pack("<II", 1 << 30, 8) + b"x" * 32
    with pytest.raises(FrameError):
        decode_frames(bytearray(evil))


@pytest.mark.skipif(hw is None, reason="native toolchain unavailable")
def test_corrupt_native_headers_scoped_to_frame():
    """Magic-prefixed but garbled headers: that frame drops (logged), the
    rest of the batch decodes — connection survives."""
    good1, good2 = _corpus_messages(2)
    from orleans_tpu.runtime.wire import encode_frame
    frames = (encode_message(good1)
              + encode_frame(b"\xa7\x01\x99", b"")   # unknown tag header
              + encode_message(good2))
    consumed, decoded, bounces = decode_frames(bytearray(frames))
    assert consumed == len(frames) and not bounces
    assert [m.method_name for m in decoded] == [good1.method_name,
                                                good2.method_name]


@pytest.mark.skipif(hw is None, reason="native toolchain unavailable")
def test_encode_message_batch_bounces_per_message():
    msgs = _corpus_messages(4)
    msgs[2].body = lambda: None  # unpicklable: encode must bounce it
    bounced = []
    chunks = encode_message_batch(msgs, lambda m, e: bounced.append(m))
    assert bounced == [msgs[2]]
    consumed, decoded, _ = decode_frames(bytearray(b"".join(chunks)))
    assert [m.method_name for m in decoded] == \
        [m.method_name for i, m in enumerate(msgs) if i != 2]


# ---------------------------------------------------------------------------
# Batched ingress semantics (real sockets)
# ---------------------------------------------------------------------------

def _vector_counter():
    import jax.numpy as jnp

    from orleans_tpu.dispatch import VectorGrain, actor_method

    class CounterVec(VectorGrain):
        STATE = {"count": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"count": jnp.int32(0)}

        @actor_method(args={"x": (jnp.int32, ())})
        def bump(state, args):
            return {"count": state["count"] + 1}, state["count"]

        @actor_method(args={})
        def read(state, args):
            return state, state["count"]

    return CounterVec


async def _socket_cluster(vec_cls=None, n_keys: int = 64,
                          extra_grains=(), **cfg):
    from orleans_tpu.runtime.socket_fabric import GatewayClient, SocketFabric

    class EchoGrain(Grain):
        def __init__(self):
            self.seen = []

        async def record(self, x):
            self.seen.append(x)
            return x

        async def seen_list(self):
            return list(self.seen)

    fabric = SocketFabric()
    b = (SiloBuilder().with_name("bi").with_fabric(fabric)
         .add_grains(EchoGrain, *extra_grains).with_config(**cfg))
    if vec_cls is not None:
        from orleans_tpu.dispatch import add_vector_grains
        from orleans_tpu.parallel import make_mesh
        add_vector_grains(b, vec_cls, mesh=make_mesh(1),
                          dense={vec_cls: n_keys})
    silo = b.build()
    await silo.start()
    client = await GatewayClient([silo.silo_address.endpoint]).connect()
    return silo, client, EchoGrain


async def test_batch_preserves_order_within_grain():
    silo, client, EchoGrain = await _socket_cluster()
    try:
        g = client.get_grain(EchoGrain, "ordered")
        await g.record(-1)  # activate
        # burst without awaiting: the whole window rides few socket
        # reads, so ordering must survive the batched hand-off
        out = await asyncio.gather(*(g.record(i) for i in range(100)))
        assert out == list(range(100))
        assert await g.seen_list() == [-1] + list(range(100))
    finally:
        await client.close_async()
        await silo.stop()


async def test_vector_batch_correct_and_ordered():
    CounterVec = _vector_counter()
    silo, client, _ = await _socket_cluster(CounterVec, n_keys=64,
                                            metrics_enabled=True)
    try:
        refs = [client.get_grain(CounterVec, k) for k in range(64)]
        # concurrent burst across keys: one bump each
        out = await asyncio.gather(*(r.bump(x=np.int32(0)) for r in refs))
        assert all(int(v) == 0 for v in out)
        # same-key burst: conflict-deferred ticks must preserve arrival
        # order (returned counts strictly increasing)
        r0 = refs[0]
        seq = await asyncio.gather(*(r0.bump(x=np.int32(i))
                                     for i in range(10)))
        assert [int(v) for v in seq] == list(range(1, 11))
        reads = await asyncio.gather(*(r.read() for r in refs))
        expect = [11] + [1] * 63
        assert [int(v) for v in reads] == expect
    finally:
        await client.close_async()
        await silo.stop()


async def test_recycle_discipline_under_debug_pool():
    """ORLEANS_TPU_DEBUG_POOL=1 over the batched socket path: no pooled
    shell may be touched after recycle anywhere in the batch pipeline."""
    prev = set_debug_pool(True)
    try:
        CounterVec = _vector_counter()
        silo, client, EchoGrain = await _socket_cluster(CounterVec,
                                                        n_keys=16)
        try:
            g = client.get_grain(EchoGrain, "pool")
            refs = [client.get_grain(CounterVec, k) for k in range(16)]
            for _ in range(3):
                out = await asyncio.gather(
                    *(g.record(i) for i in range(20)),
                    *(r.bump(x=np.int32(0)) for r in refs))
                assert list(out[:20]) == list(range(20))
        finally:
            await client.close_async()
            await silo.stop()
    finally:
        set_debug_pool(prev)


async def test_staging_double_buffer_stale_lane_reset():
    """Alternating batch sizes over one (class, method, B) bucket: a
    large fill followed by a smaller one on the recycled buffer must
    leave the stale tail lanes inert (no ghost writes into rows the
    smaller batch never touched) — the staging reset discipline under
    concurrent fill/tick."""
    CounterVec = _vector_counter()
    silo, client, _ = await _socket_cluster(CounterVec, n_keys=64)
    try:
        refs = [client.get_grain(CounterVec, k) for k in range(64)]
        # wave 1: all 64 keys (fills lanes 0..63 of the B=64 bucket)
        await asyncio.gather(*(r.bump(x=np.int32(0)) for r in refs))
        # waves 2..4: only the first 40 keys — the same bucket's OTHER
        # buffer, then the recycled first buffer with 24 stale lanes
        for _ in range(3):
            await asyncio.gather(*(r.bump(x=np.int32(0))
                                   for r in refs[:40]))
        reads = await asyncio.gather(*(r.read() for r in refs))
        assert [int(v) for v in reads] == [4] * 40 + [1] * 24
        assert silo.vector.staging_lanes() > 0  # double buffers live
    finally:
        await client.close_async()
        await silo.stop()


def test_staging_reset_repoints_all_lanes_on_sink_move():
    """reset() with an unchanged sink only re-arms the used prefix; when
    the sink MOVED (a table grow() made the old sink row — == old
    capacity — a real allocatable slot) every lane must re-point, else a
    stale idle lane scatters into whichever actor lands on that row."""
    from orleans_tpu.dispatch.engine import _StagingSet

    st = _StagingSet(1, 8, 8, {"x": (np.int32, ())})
    st.used = [6]
    st.slots[0, :6] = np.arange(6)
    st.valid[0, :6] = True
    st.fresh[0, :6] = True
    st.reset(8)  # same sink: prefix re-arm
    assert (st.slots == 8).all() and not st.valid.any()
    st.used = [2]
    st.slots[0, :2] = [3, 4]
    st.valid[0, :2] = True
    st.reset(16)  # sink moved: EVERY lane re-points, fresh cleared
    assert (st.slots == 16).all()
    assert not st.valid.any() and not st.fresh.any()
    assert st.used == [0]


async def test_staging_survives_table_growth():
    """End to end over the recycled staging pair: growing the table must
    not let a stale idle lane (still aimed at the old sink) scatter into
    the actor that now occupies the old sink row."""
    from orleans_tpu.dispatch import VectorRuntime

    CounterVec = _vector_counter()
    rt = VectorRuntime(capacity_per_shard=8)
    tbl = rt.table(CounterVec)
    old_sink = tbl.sink_slot

    def group(keys):
        return [(k, {"x": np.int32(0)}, True) for k in keys]

    # two waves through one B-bucket so BOTH staging buffers exist and
    # hold the old sink in their never-used lanes
    for _ in range(2):
        await asyncio.gather(
            *rt.call_group(CounterVec, "bump", group(range(1, 7))))
    # drain the free list → grow(): the old sink row becomes allocatable
    await asyncio.gather(
        *rt.call_group(CounterVec, "bump", group(range(100, 160))))
    assert tbl.sink_slot > old_sink
    victim = next(k for k, (_s, slot) in tbl.key_to_slot.items()
                  if slot == old_sink)
    before = int(await rt.call(CounterVec, victim, "read"))
    # small waves through the recycled pair, victim in the batch: its
    # bump must not race a stale-lane write-back of the pre-bump row
    for _ in range(2):
        await asyncio.gather(*rt.call_group(
            CounterVec, "bump", group([victim, 1, 2])))
    assert int(await rt.call(CounterVec, victim, "read")) == before + 2


async def test_call_group_all_failed_leaves_no_pending_entry():
    """A group whose every item fails (schema violations) must neither
    leave an empty pending entry behind nor schedule a tick over it — an
    empty batch would crash first-batch schema inference (items[0])."""
    from orleans_tpu.dispatch import VectorRuntime

    CounterVec = _vector_counter()
    rt = VectorRuntime()
    await rt.call(CounterVec, 1, "bump", x=np.int32(0))  # infer schema
    ticks = rt.ticks
    futs = rt.call_group(CounterVec, "bump",
                         [(2, {"bogus": np.int32(0)}, True),
                          (3, {}, True)])
    for f in futs:
        with pytest.raises(TypeError):
            await f
    assert not rt.pending
    await asyncio.sleep(0)  # a (wrongly) scheduled tick would run here
    assert rt.ticks == ticks
    assert rt.call_group(CounterVec, "bump", []) == []  # degenerate
    assert not rt.pending


# ---------------------------------------------------------------------------
# Queue-wait-trend load shedding
# ---------------------------------------------------------------------------

def test_queue_wait_trend_windowing():
    tr = QueueWaitTrend(window=1.0)
    t0 = 1000.0
    for i in range(10):
        tr.note(0.2, t0 + i * 0.01)
    assert abs(tr.mean(t0 + 0.1) - 0.2) < 1e-9
    # slide past the window: old samples evict, mean follows the new load
    for i in range(5):
        tr.note(0.0, t0 + 2.0 + i * 0.01)
    assert tr.mean(t0 + 2.1) < 1e-12  # running-sum float residue ok
    assert len(tr) == 5


async def test_shed_on_queue_wait_trend():
    from orleans_tpu.config import LoadSheddingOptions

    class EchoGrain(Grain):
        async def echo(self, x):
            return x

    silo = (SiloBuilder().with_name("trendshed").add_grains(EchoGrain)
            .with_options(LoadSheddingOptions(
                enabled=True, limit=10_000, queue_wait_limit=0.05,
                queue_wait_window=30.0))
            .build())
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        assert silo.shed_trend is not None
        assert await client.get_grain(EchoGrain, 1).echo(1) == 1
        shed0 = silo.stats.get("messaging.gateway.shed")
        assert shed0 == 0
        # push the windowed queue-wait over the limit: ingress sheds even
        # though the queue depth is ~0 (the slow-drain overload regime)
        for _ in range(20):
            silo.shed_trend.note(0.5)
        fut = asyncio.ensure_future(client.get_grain(EchoGrain, 2).echo(2))
        await asyncio.sleep(0.05)
        assert silo.stats.get("messaging.gateway.shed") > 0
        # the client retries shed requests transparently; clear the trend
        # (old samples age out of the window) so the retry lands
        silo.shed_trend._samples.clear()
        silo.shed_trend._sum = 0.0
        assert await asyncio.wait_for(fut, timeout=10.0) == 2
    finally:
        await client.close_async()
        await silo.stop()


# ---------------------------------------------------------------------------
# Hot-lane batch-aware fairness
# ---------------------------------------------------------------------------

async def test_hotlane_amortized_yield_without_ready_work():
    """With NOTHING else ready, the lane may skip per-call yields but
    must still cross the loop at least every _HOT_YIELD_EVERY calls —
    a scheduled callback fires while a tight hot-call loop runs."""

    class Echo(Grain):
        async def ping(self, x):
            return x

    silo = SiloBuilder().with_name("fair2").add_grains(Echo).build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        g = client.get_grain(Echo, 0)
        await g.ping(0)
        fired = []
        asyncio.get_running_loop().call_later(0.0, lambda: fired.append(1))
        for i in range(300):
            await g.ping(i)
        assert fired, "amortized yield never crossed the event loop"
        assert client.hot_hits > 0
    finally:
        await client.close_async()
        await silo.stop()


# ---------------------------------------------------------------------------
# Sampler sources
# ---------------------------------------------------------------------------

async def test_sampler_storage_journal_staging_sources():
    from orleans_tpu.eventsourcing import JournaledGrain

    class MiniJournal(JournaledGrain):
        def initial_state(self):
            return {"n": 0}

        def apply_event(self, state, event):
            return {"n": state["n"] + 1}

        async def bump(self):
            self.raise_event({})
            await self.confirm_events()
            return self.state["n"]

    CounterVec = _vector_counter()
    silo, client, _ = await _socket_cluster(CounterVec, n_keys=8,
                                            metrics_enabled=True,
                                            extra_grains=(MiniJournal,))
    try:
        r = client.get_grain(CounterVec, 1)
        await r.bump(x=np.int32(0))
        assert await client.get_grain(MiniJournal, "j").bump() == 1
        silo.metrics.sample_once()
        snap = silo.stats.snapshot()
        for name in ("storage.inflight_ops", "journal.unconfirmed_events",
                     "vector.staging_lanes", "vector.staging_fill"):
            assert name in snap["gauges"], name
            assert name in silo.metrics.windows
        assert snap["gauges"]["vector.staging_lanes"] > 0
    finally:
        await client.close_async()
        await silo.stop()


async def test_sampler_journal_source_skipped_without_journaled_grains():
    """The O(activations) journal walk is only installed when a
    JournaledGrain class is registered."""
    CounterVec = _vector_counter()
    silo, client, _ = await _socket_cluster(CounterVec, n_keys=4,
                                            metrics_enabled=True)
    try:
        silo.metrics.sample_once()
        assert "journal.unconfirmed_events" not in silo.metrics.windows
        assert "storage.inflight_ops" in silo.metrics.windows
    finally:
        await client.close_async()
        await silo.stop()


async def test_storage_inflight_counter():
    from orleans_tpu.storage.core import (LatencyStorage, MemoryStorage,
                                          StateStorageBridge, StorageManager)

    mgr = StorageManager()
    provider = LatencyStorage(MemoryStorage(), latency=0.05)
    bridge = StateStorageBridge(provider, "G", GrainId.for_grain(GT, 1),
                                manager=mgr)
    assert mgr.inflight == 0
    task = asyncio.ensure_future(bridge.write({"v": 1}))
    await asyncio.sleep(0.01)
    assert mgr.inflight == 1  # op awaiting its provider
    await task
    assert mgr.inflight == 0


# ---------------------------------------------------------------------------
# Review regressions (PR 7 fixes)
# ---------------------------------------------------------------------------

def test_decode_frames_delivers_frames_ahead_of_hostile_prefix():
    """Good frames followed by an oversized announcement: the good frames
    still come back (per-frame parity — they were routable before the
    link must drop); the NEXT call, seeing the hostile prefix lead the
    buffer, raises."""
    msgs = _corpus_messages(3)
    evil = struct.pack("<II", 1 << 30, 8) + b"x" * 16
    buf = bytearray(b"".join(encode_message(m) for m in msgs) + evil)
    consumed, decoded, bounces = decode_frames(buf)
    assert len(decoded) == 3 and not bounces
    assert consumed == len(buf) - len(evil)
    del buf[:consumed]
    with pytest.raises(FrameError):
        decode_frames(buf)


@pytest.mark.skipif(hw is None, reason="native toolchain unavailable")
def test_encode_batch_bounces_poisoned_envelope_under_debug_pool():
    """ORLEANS_TPU_DEBUG_POOL=1: a recycled envelope reaching the batch
    encoder bounces like any per-message failure — the sender task (and
    the rest of the batch) survives."""
    from orleans_tpu.core.message import recycle_message
    prev = set_debug_pool(True)
    try:
        good1, poisoned, good2 = _corpus_messages(3)
        recycle_message(poisoned)
        bounced = []
        chunks = encode_message_batch([good1, poisoned, good2],
                                      lambda m, e: bounced.append((m, e)))
        assert [m for m, _ in bounced] == [poisoned]
        consumed, decoded, _ = decode_frames(bytearray(b"".join(chunks)))
        assert [m.method_name for m in decoded] == [good1.method_name,
                                                    good2.method_name]
    finally:
        set_debug_pool(prev)


async def test_vector_batch_bad_kwargs_scoped_to_one_message():
    """A vector-tier message whose body carries a non-dict kwargs payload
    must bounce alone — the rest of its ingress group still executes
    (previously the whole group was error-bounced AND the enqueued slice
    still ticked)."""
    CounterVec = _vector_counter()
    silo, client, _ = await _socket_cluster(CounterVec, n_keys=8)
    try:
        vecg = GrainType.of("CounterVec")
        batch = []
        for i in range(4):
            body = ((), [1, 2]) if i == 2 else ((), {"x": np.int32(0)})
            batch.append(make_request(
                target_grain=GrainId.for_grain(vecg, i),
                interface_name="CounterVec", method_name="bump",
                body=body, direction=Direction.ONE_WAY))
        silo.message_center.deliver_batch(batch)
        await silo.vector.flush()
        reads = await asyncio.gather(
            *(client.get_grain(CounterVec, k).read() for k in range(4)))
        assert [int(v) for v in reads] == [1, 1, 0, 1]
    finally:
        await client.close_async()
        await silo.stop()


@pytest.mark.parametrize("observed", ["shedding", "backlog"])
async def test_deliver_batch_goes_per_message_on_what_it_observes(observed):
    """No option chooses the route of a grouped read. The silo routes it
    as a unit unless shedding is on (queue depth is the shed signal, so
    ingress must accumulate) or a category is backlogged (what is queued
    goes first): then every message takes :meth:`deliver`, in order, and
    the calls still land once each."""
    cfg = {"load_shedding_enabled": True} if observed == "shedding" else {}
    CounterVec = _vector_counter()
    silo, client, EchoGrain = await _socket_cluster(
        CounterVec, n_keys=4, **cfg)
    try:
        mc = silo.message_center
        assert not hasattr(silo.config, "batched_ingress")
        routed, single = [], []
        route_batch, deliver = mc._route_batch, mc.deliver
        mc._route_batch = lambda msgs: (routed.append(len(msgs)),
                                        route_batch(msgs))
        mc.deliver = lambda m: (single.append(m.target_grain.key),
                                deliver(m))

        def burst():
            vecg = GrainType.of("CounterVec")
            return [make_request(
                target_grain=GrainId.for_grain(vecg, k),
                interface_name="CounterVec", method_name="bump",
                body=((), {"x": np.int32(0)}), direction=Direction.ONE_WAY)
                for k in range(4)]

        if observed == "backlog":
            mc.deliver_batch(burst())            # nothing queued: a unit
            assert routed == [4] and not single
            # something queued ahead in any category: no overtaking
            mc.inbound[Category.APPLICATION].put_nowait(make_request(
                target_grain=GrainId.for_grain(GrainType.of("EchoGrain"),
                                               "q"),
                interface_name="EchoGrain", method_name="record",
                body=((7,), {}), direction=Direction.ONE_WAY))
        mc.deliver_batch(burst())
        assert single == list(range(4))
        assert routed == ([4] if observed == "backlog" else [])
        bursts = len(routed) + 1                 # the reads below route too
        await asyncio.sleep(0.05)                # the category pump drains
        await silo.vector.flush()
        reads = await asyncio.gather(
            *(client.get_grain(CounterVec, k).read() for k in range(4)))
        assert [int(v) for v in reads] == [bursts] * 4
    finally:
        await client.close_async()
        await silo.stop()


async def test_shed_trend_fed_by_vector_tier_without_metrics():
    """The device-tier queue-wait feed must reach the shed trend even
    with metrics disabled (t_enq/batch-start stamps are gated on
    stats-OR-trend, not stats alone)."""
    CounterVec = _vector_counter()
    silo, client, _ = await _socket_cluster(
        CounterVec, n_keys=8, load_shedding_enabled=True,
        load_shedding_queue_wait=10.0)
    try:
        assert silo.ingest_stats is None  # metrics off
        assert silo.vector.shed_trend is silo.shed_trend
        await asyncio.gather(
            *(client.get_grain(CounterVec, k).bump(x=np.int32(0))
              for k in range(8)))
        assert len(silo.shed_trend) > 0, \
            "vector batch starts never fed the shed trend"
    finally:
        await client.close_async()
        await silo.stop()


def test_leads_hostile_frame_peek():
    from orleans_tpu.runtime.wire import leads_hostile_frame
    good = encode_message(_corpus_messages(1)[0])
    evil = struct.pack("<II", 1 << 30, 8) + b"xxxx"
    assert not leads_hostile_frame(b"")
    assert not leads_hostile_frame(good[:7])   # short prefix: keep reading
    assert not leads_hostile_frame(good)
    assert leads_hostile_frame(evil)
    # decode_frames + peek compose: the valid frame decodes, the peek
    # then flags the hostile remainder for an immediate connection drop
    buf = bytearray(good + evil)
    consumed, msgs, _ = decode_frames(buf)
    del buf[:consumed]
    assert len(msgs) == 1 and leads_hostile_frame(buf)
