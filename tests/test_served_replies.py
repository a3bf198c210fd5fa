"""A served device-tier call's reply: the call rides to its job with its
reply token (the request and its device span) and no future, and the
job's completion answers every served call of the job in one pass —
``VectorRuntime._resolve_batch`` indexes the results flattened once and
hands ``(token, result)`` pairs to the dispatcher's reply function, which
builds the responses and gives them to egress together.

Held here on the CPU: the reply bytes against the same calls resolved
through ``rt.call`` futures (the Presence and YCSB deployments' grains,
served over the gateway), every failure path answering its callers, a
key's replies in send order, no future made on the served path, the
``engine.replies_batched`` counter, and a sampled call's device span.
Counts, bytes and order only; nothing reads a clock."""

import asyncio
import importlib.util
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.core.ids import GrainId, GrainType
from orleans_tpu.core.message import Direction, make_request, make_response
from orleans_tpu.dispatch import (VectorGrain, VectorRuntime, actor_method,
                                  add_vector_grains, sends)
from orleans_tpu.observability.tracing import TRACE_KEY
from orleans_tpu.parallel import make_mesh
from orleans_tpu.runtime import GatewayClient, SiloBuilder, SocketFabric
from orleans_tpu.runtime import wire

CHIPBENCH = os.path.join(os.path.dirname(__file__), os.pardir, "chipbench")
N = 64
REPLIES = "engine.replies_batched"


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        f"served_replies_app_{name}",
        os.path.join(CHIPBENCH, "apps", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


presence = _load("presence")
ycsb = _load("ycsb")
Player = presence.PlayerVectorGrain
Record = ycsb.RecordVectorGrain


class Tally(VectorGrain):
    STATE = {"total": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"total": jnp.int32(0)}

    @actor_method(args={"x": (jnp.int32, ())})
    def add(state, args):
        total = state["total"] + args["x"]
        return {"total": total}, total


K = 2


class Acc(VectorGrain):
    """``publish`` sends ``v`` to the keys ``to`` and answers its count;
    ``receive`` keeps the sum of what reached it."""

    STATE = {"sum": (jnp.int32, ()), "seq": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"sum": jnp.int32(0), "seq": jnp.int32(0)}

    @actor_method(args={"v": (jnp.int32, ())})
    def receive(state, args):
        return {**state, "sum": state["sum"] + args["v"]}, state["sum"]

    @sends("receive", fanout=K, args={"v": (jnp.int32, ()),
                                      "to": (jnp.int32, (K,))})
    def publish(state, args):
        seq = state["seq"] + 1
        return ({**state, "seq": seq}, seq,
                (args["to"], jnp.ones(K, bool),
                 {"v": jnp.broadcast_to(args["v"], (K,))}))


# ---------------------------------------------------------------------------
# (a) through the gateway: the reply bytes of rt.call's futures
# ---------------------------------------------------------------------------

def _presence_calls(rng, rounds: int):
    return [[(k, "heartbeat",
              {"pos": rng.normal(size=2).astype(np.float16),
               "delta": np.int32(rng.integers(-5, 100))})
             for k in range(N)] for _ in range(rounds)]


def _ycsb_calls(rng, rounds: int):
    out = []
    for _ in range(rounds):
        rnd = []
        for k in range(N):
            if rng.random() < 0.5:
                rnd.append((k, "read", {}))
            else:
                rnd.append((k, "update", {
                    "field": np.int32(rng.integers(0, ycsb.FIELD_COUNT)),
                    "value": rng.integers(0, 256, ycsb.FIELD_BYTES,
                                          np.uint8).tobytes()}))
        out.append(rnd)
    return out


def _kind(v):
    """A result's make-up: each leaf's type, dtype and shape."""
    return jax.tree_util.tree_map(
        lambda x: (type(x), x.dtype, x.shape), v)


I32 = (np.int32, np.dtype(np.int32), ())
# what the tick's results indexed [s, i] are: numpy scalars and row views
KINDS = {"heartbeat": I32, "update": I32,
         "read": (I32, (np.ndarray, np.dtype(np.uint8),
                        (ycsb.RECORD_BYTES,)))}


@pytest.mark.parametrize("cls,calls", [(Player, _presence_calls),
                                       (Record, _ycsb_calls)],
                         ids=["presence-heartbeat", "ycsb-read-update"])
async def test_served_replies_are_the_bytes_of_rt_call_futures(cls, calls):
    rounds = calls(np.random.default_rng(11), 3)
    # the same calls through rt.call on a twin runtime: the reference
    ref = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=N)
    ref.register(cls)
    ref.table(cls).ensure_dense(N)
    want: dict = {}
    for j, rnd in enumerate(rounds):
        got = await asyncio.gather(*(ref.call(cls, k, m, **kw)
                                     for k, m, kw in rnd))
        for (k, m, _kw), v in zip(rnd, got):
            assert _kind(v) == KINDS[m]
            want[k, j] = v
    ref.shutdown_worker()

    b = (SiloBuilder().with_name("replies").with_fabric(SocketFabric()))
    add_vector_grains(b, cls, mesh=make_mesh(1), dense={cls: N},
                      capacity_per_shard=N)
    silo = b.build()
    await silo.start()
    d = silo.dispatcher
    send = d.send_response
    seen: dict = {}
    pairs: list = []

    def capture(request, response):
        # encoded here, as egress may recycle the envelope once it is
        # sent, and both at one instant of the clock the TTL is read on
        k = int(request.target_grain.key)
        j = seen[k] = seen.get(k, -1) + 1
        now = time.monotonic()
        wire.time = types.SimpleNamespace(monotonic=lambda: now)
        try:
            pairs.append((wire.encode_message(response),
                          wire.encode_message(make_response(request,
                                                            want[k, j]))))
        finally:
            wire.time = time
        send(request, response)

    d.send_response = capture
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    try:
        for rnd in rounds:
            await asyncio.gather(*(getattr(client.get_grain(cls, k), m)(**kw)
                                   for k, m, kw in rnd))
        assert len(pairs) == len(rounds) * N
        for served, through_future in pairs:
            assert served == through_future
    finally:
        await client.close_async()
        await silo.stop()


# ---------------------------------------------------------------------------
# at the dispatcher: hand-made reads, replies caught at send_response
# ---------------------------------------------------------------------------

class Harness:
    """One silo driven at the dispatcher: a read is one
    ``receive_vector_batch`` of hand-made requests, and the replies are
    caught at ``send_response`` in the order they are sent."""

    def __init__(self, cls=Tally, **config) -> None:
        self.cls = cls
        b = SiloBuilder().with_name("replies").with_config(**config)
        add_vector_grains(b, cls, mesh=make_mesh(1), dense={cls: N},
                          capacity_per_shard=N)
        self.silo = b.build()
        self.replies: dict = {}
        self.order: list = []

    async def __aenter__(self) -> "Harness":
        await self.silo.start()

        def send_response(request, response):
            if request.direction == Direction.ONE_WAY:
                return
            self.replies[request.id] = response
            self.order.append(request)

        self.silo.dispatcher.send_response = send_response
        return self

    async def __aexit__(self, *exc) -> None:
        await self.silo.stop()

    @property
    def rt(self):
        return self.silo.vector

    def msg(self, key, method: str = "add", one_way: bool = False, **kw):
        return make_request(
            target_grain=GrainId.for_grain(GrainType.of(self.cls.__name__),
                                           key),
            interface_name=self.cls.__name__, method_name=method,
            direction=Direction.ONE_WAY if one_way else Direction.REQUEST,
            body=((), {k: (v if isinstance(v, np.ndarray) else np.int32(v))
                       for k, v in kw.items()}))

    def read(self, msgs) -> None:
        self.silo.dispatcher.receive_vector_batch(self.cls, list(msgs))

    async def answers(self, msgs, timeout: float = 20.0) -> list:
        """Each message's reply: the value, or the exception it carries."""
        async def wait():
            while not all(m.id in self.replies for m in msgs):
                await asyncio.sleep(0.002)
        await asyncio.wait_for(wait(), timeout)
        return [self.replies[m.id].body for m in msgs]


def rt_call(rt, cls, key, method, **kw):
    return rt.call(cls, key, method,
                   **{k: np.int32(v) for k, v in kw.items()})


@pytest.mark.parametrize("offloop", [True, False], ids=["worker", "on-loop"])
@pytest.mark.parametrize("where", ["tick", "completion"])
async def test_a_failed_job_answers_every_two_way_call_and_no_one_way(
        where, offloop):
    async with Harness(offloop_tick=offloop) as h:
        rt = h.rt

        def boom(*a, **kw):
            raise RuntimeError("boom")

        # the worker's tick, or the loop-side completion after it
        setattr(rt, "_execute_batch" if where == "tick"
                else "_record_tick_span", boom)
        two = [h.msg(k, x=k) for k in range(6)]
        one = [h.msg(k, one_way=True, x=k) for k in range(6, 10)]
        h.read(two[:3] + one + two[3:])
        out = await h.answers(two)
        await rt.flush()
        assert all(isinstance(e, RuntimeError) and "boom" in str(e)
                   for e in out)
        # one reply a two-way call, none for a one-way call
        assert sorted(m.id for m in h.order) == sorted(m.id for m in two)


async def test_a_sender_whose_delivery_fails_alone_gets_the_error():
    async with Harness(Acc) as h:
        to = lambda *ks: np.array(ks, np.int32)  # noqa: E731
        bad = h.msg(1, "publish", v=7, to=to(5, 999))
        good = [h.msg(2, "publish", v=8, to=to(6, 6)),
                h.msg(3, "publish", v=9, to=to(7, 8))]
        h.read([good[0], bad, good[1]])
        out = await h.answers([good[0], bad, good[1]])
        assert isinstance(out[1], KeyError) and "999" in str(out[1])
        assert [int(out[0]), int(out[2])] == [1, 1]
        # the failed sender's deliverable message landed all the same
        for k, total in ((5, 7), (6, 16), (7, 9), (8, 9)):
            assert int(await rt_call(h.rt, Acc, k, "receive", v=0)) == total


async def test_a_keys_replies_arrive_in_send_order():
    async with Harness() as h:
        msgs = []
        for i in range(6):
            msgs += [h.msg(3, x=1), h.msg(5, x=10), h.msg(9 + i, x=i)]
        h.read(msgs)
        await h.answers(msgs)
        for key, step in ((3, 1), (5, 10)):
            sent = [m for m in msgs if int(m.target_grain.key) == key]
            got = [m for m in h.order if int(m.target_grain.key) == key]
            assert [m.id for m in got] == [m.id for m in sent]
            assert [int(h.replies[m.id].body) for m in got] == \
                [step * (i + 1) for i in range(6)]


async def test_the_served_path_makes_no_future_a_call():
    async with Harness() as h:
        rt, loop = h.rt, asyncio.get_running_loop()
        made = []
        create = loop.create_future

        def counting():
            made.append(1)
            return create()

        complete = rt._complete_job
        in_completion = []

        def completing(*a):
            before = len(made)
            complete(*a)
            in_completion.append(len(made) - before)

        rt._complete_job = completing
        loop.create_future = counting
        try:
            msgs = [h.msg(k, x=1) for k in range(N)]
            h.read(msgs)
            assert made == []          # the enqueue: no future a call
            await h.answers(msgs)
            assert in_completion and not any(in_completion)
            # the count does see a future where one is asked for
            before = len(made)
            futs = rt.call_group(Tally, "add",
                                 [(k, {"x": np.int32(1)}, True)
                                  for k in range(4)])
            assert len(made) - before == 4
            await asyncio.gather(*futs)
        finally:
            del loop.create_future


async def test_replies_batched_counts_the_two_way_calls_served():
    async with Harness(metrics_enabled=True) as h:
        st = h.silo.stats
        assert await rt_call(h.rt, Tally, 1, "add", x=2) == 2
        await h.rt.flush()
        # a job went through the resolve with nothing served: present, 0
        assert REPLIES in st.counters and st.get(REPLIES) == 0
        two = [h.msg(k, x=1) for k in range(10)]
        one = [h.msg(k, one_way=True, x=1) for k in range(20, 23)]
        h.read(two + one)
        await h.answers(two)
        await h.rt.flush()
        assert st.get(REPLIES) == len(two)


async def test_nothing_is_stamped_with_metrics_off():
    async with Harness() as h:
        msgs = [h.msg(k, x=1) for k in range(4)]
        h.read(msgs)
        await h.answers(msgs)
        assert REPLIES not in h.silo.stats.counters


@pytest.mark.parametrize("one_way", [False, True], ids=["two-way", "one-way"])
async def test_a_sampled_calls_device_span_closes_at_its_reply(one_way):
    async with Harness(trace_enabled=True, trace_sample_rate=1.0) as h:
        tid, parent = 0x5EED, 0xABC
        m = h.msg(4, one_way=one_way, x=3)
        m.request_context = {TRACE_KEY: (tid, parent, time.time())}
        plain = h.msg(5, x=1)
        h.read([m, plain])
        await h.answers([plain] if one_way else [m, plain])
        await h.rt.flush()
        dev = [s for s in h.silo.tracer.snapshot()
               if s["kind"] == "device" and s["trace_id"] == tid]
        assert [s["name"] for s in dev] == ["Tally.add"]
        assert dev[0]["parent_id"] == parent
        if not one_way:
            # the response carries the span's send-side stamp
            stamp = h.replies[m.id].request_context[TRACE_KEY]
            assert stamp[:2] == (tid, dev[0]["span_id"])
            assert int(h.replies[m.id].body) == 3
        # the unsampled call carried no span and no stamp
        assert not h.replies[plain.id].request_context
