"""Stage spans (observability.stats.StageSpan): one primitive at every
layer boundary of the served device path — host-clock histogram,
``otpu:<stage>`` annotation on the profiler's clock, compile attribution.

Counts and containment only; no timing thresholds. One served silo
(SocketFabric + GatewayClient + write-behind storage) is driven once per
traffic shape of a benchmark cell and the per-stage cases read its
registry:

* ``heartbeat`` — ``presence_heartbeat``'s shape: dense int keys, one
  writing method, ``call_batch`` frames;
* ``ycsb`` — ``ycsb_a_zipf``'s shape: hashed string keys, single calls,
  a ``read_only`` method beside a writing one on the same keys, two
  updates of a key in flight at once, so that ``engine.claim`` defers
  and ``_tick`` holds;
* ``heartbeat-lever-off`` — the first shape with ``offloop_tick=False``
  (the one lever PR 30 kept): the job runs on the loop through the same
  functions, so every stage keeps its name, unit and count.
"""

import asyncio
import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.dispatch import (VectorGrain, actor_method,
                                  add_vector_grains, sends)
from orleans_tpu.observability import stats as stats_mod
from orleans_tpu.observability.stats import (EXCHANGE_STATS, FLUSH_STATS,
                                             RECOVER_STATS,
                                             STAGES, StageSpan,
                                             StatsRegistry,
                                             close_stage_registry,
                                             open_stage_registry)
from orleans_tpu.parallel import make_mesh
from orleans_tpu.runtime import GatewayClient, SiloBuilder, SocketFabric
from orleans_tpu.storage import MemoryStorage, checkpoint
from orleans_tpu.storage.checkpoint import _gather_rows

N_KEYS = 16
ROUNDS = 4
PER_TICK = ("engine.claim", "engine.worker_queue", "engine.fence_wait",
            "ingest.staging", "ingest.transfer", "ingest.tick.dispatch",
            "ingest.tick.sync", "engine.complete_hop", "engine.complete",
            "engine.resolve")
PER_FLUSH = ("flush", "flush.locate", "flush.gather", "flush.write")


class CounterVec(VectorGrain):
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


def _build(metrics: bool, storage=None, period: float = 0.05,
           offloop: bool = True):
    b = (SiloBuilder().with_name(f"ss-{metrics}")
         .with_fabric(SocketFabric())
         .with_config(metrics_enabled=metrics, offloop_tick=offloop))
    add_vector_grains(b, CounterVec, mesh=make_mesh(1),
                      dense={CounterVec: 64}, capacity_per_shard=128,
                      **({"storage": storage, "flush_period": period}
                         if storage is not None else {}))
    return b.build()


async def _rounds(client, keys, rounds: int = ROUNDS) -> None:
    refs = [client.get_grain(CounterVec, k) for k in keys]
    for r in range(rounds):
        out = await asyncio.gather(*(g.add(x=np.int32(1)) for g in refs))
        assert [int(v) for v in out] == [r + 1] * len(refs)


async def _settle(silo, rows: int) -> None:
    """Until the write-behind flusher has written ``rows`` rows."""
    for _ in range(400):
        if silo.stats.get(FLUSH_STATS["flushed"]) >= rows:
            return
        await asyncio.sleep(0.025)
    raise AssertionError("the flusher never drained")


async def _frames(client) -> None:
    """``presence_heartbeat``'s shape: one ``call_batch`` frame a round
    over dense int keys, one writing method."""
    calls = [(k, {"x": np.int32(1)}) for k in range(N_KEYS)]
    for r in range(ROUNDS):
        out = await asyncio.gather(*client.call_batch(CounterVec, "add",
                                                      calls))
        assert [int(v) for v in out] == [r + 1] * N_KEYS


async def _single_calls(client) -> None:
    """``ycsb_a_zipf``'s shape: single calls on hashed string keys; after
    every key's first touch, each round has two updates and a read of
    every key in flight at once (the second update of a key cannot share
    the first one's tick)."""
    refs = [client.get_grain(CounterVec, f"user-{k}") for k in range(N_KEYS)]
    one = np.int32(1)
    out = await asyncio.gather(*(g.add(x=one) for g in refs))
    assert [int(v) for v in out] == [1] * N_KEYS
    for r in range(ROUNDS):
        base = 1 + 2 * r
        first = [g.add(x=one) for g in refs]
        reads = [g.peek() for g in refs]
        second = [g.add(x=one) for g in refs]
        # replies of a key in send order; a read sees a state between
        assert [int(v) for v in await asyncio.gather(*first)] \
            == [base + 1] * N_KEYS
        assert [int(v) for v in await asyncio.gather(*second)] \
            == [base + 2] * N_KEYS
        assert all(base <= int(v) <= base + 2
                   for v in await asyncio.gather(*reads))


SHAPES = {"heartbeat": _frames, "ycsb": _single_calls}


async def _serve(metrics: bool, shape: str, offloop: bool = True) -> dict:
    """Drive one served silo; returns what the cases compare."""
    silo = _build(metrics, MemoryStorage(), offloop=offloop)
    await silo.start()
    loop_thread = threading.get_ident()
    observers: set = set()
    sunk: set = set()
    observe = silo.stats.observe
    complete = silo.vector._complete_job

    def spy_observe(key, value):
        observers.add(threading.get_ident())
        observe(key, value)

    def spy_complete(job, host, err):
        sunk.update(k for k, _v in job.stats if isinstance(k, str))
        complete(job, host, err)

    silo.stats.observe = spy_observe
    silo.vector._complete_job = spy_complete
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    try:
        await SHAPES[shape](client)
        await _settle(silo, N_KEYS)
    finally:
        await client.close_async()
        await silo.stop()
    return {"stats": silo.stats, "observers": observers, "sunk": sunk,
            "loop_thread": loop_thread,
            "stage_left": stats_mod._thread.stage,
            "deferred": silo.vector.conflicts_deferred}


@pytest.fixture(scope="module",
                params=[("heartbeat", True), ("ycsb", True),
                        ("heartbeat", False)],
                ids=["heartbeat", "ycsb", "heartbeat-lever-off"])
def served(request):
    shape, offloop = request.param
    return shape, asyncio.run(_serve(True, shape, offloop))


def _count(stats, name: str) -> int:
    h = stats.histograms.get(name)
    return h.total if h is not None else 0


@pytest.mark.parametrize("stage", STAGES)
def test_stage_observed_once_per_unit_of_work(served, stage):
    _shape, run = served
    st = run["stats"]
    got = _count(st, stage + ".seconds")
    if stage in PER_TICK:
        # one claimed batch = one job = one of each tick stage
        assert got == _count(st, "ingest.tick.seconds") >= ROUNDS
    elif stage in PER_FLUSH:
        assert got == st.get(FLUSH_STATS["flushes"]) >= 1
    elif stage == "pump.batch":
        # one per decoded socket read that carried messages
        assert got == _count(st, "ingest.frame_batch.size") >= 1
    elif stage == "egress.flush":
        assert got == _count(st, "egress.build.seconds") >= 1
    elif stage == "recover":
        # once a recovery pass (a decoded read's fresh keys), not once a
        # message: the passes read every key's first touch, and only that
        keys = st.histograms[RECOVER_STATS["keys"]]
        assert 1 <= got == keys.total <= N_KEYS
        assert keys.sum == N_KEYS == st.get(RECOVER_STATS["first_touch"])
    elif stage.startswith("exchange"):
        # these shapes have no sending method: the sender's cases are
        # below (test_exchange_stages_*)
        assert got == 0
    else:
        raise AssertionError(f"stage {stage} has no case")


def test_tick_is_tiled_by_dispatch_and_sync(served):
    _shape, run = served
    h = run["stats"].histograms
    tick, disp, sync = (h["ingest.tick.seconds"],
                        h["ingest.tick.dispatch.seconds"],
                        h["ingest.tick.sync.seconds"])
    assert tick.total == disp.total == sync.total
    assert tick.sum >= disp.sum + sync.sum - 1e-9


def test_worker_stages_reach_the_registry_through_the_sink(served):
    shape, run = served
    # nothing but the loop thread ever wrote the registry
    assert run["observers"] == {run["loop_thread"]}
    worker_side = {f"{s}.seconds" for s in (
        "engine.fence_wait", "engine.worker_queue", "ingest.staging",
        "ingest.transfer", "ingest.tick.dispatch", "ingest.tick.sync")}
    assert worker_side <= run["sunk"]
    assert run["stage_left"] is None
    st = run["stats"]
    # both counters exist at 0 too; the second update of a key in flight
    # with the first either met it in one claim (deferred) or found its
    # group still with the worker (held)
    assert st.counters["engine.deferred"] >= 0 <= st.counters["engine.held"]
    if shape == "ycsb":
        assert run["deferred"] + st.get("engine.held") >= 1
        assert st.get("ingest.messages.CounterVec.peek") == N_KEYS * ROUNDS
        assert st.get("ingest.messages.CounterVec.add") \
            == N_KEYS * (1 + 2 * ROUNDS)
    else:
        assert st.get("ingest.messages") == N_KEYS * ROUNDS


def test_flush_rows_sum_to_flushed(served):
    _shape, run = served
    st = run["stats"]
    rows = st.histograms[FLUSH_STATS["rows"]]
    assert rows.sum == st.get(FLUSH_STATS["flushed"]) >= N_KEYS
    assert rows.total == st.get(FLUSH_STATS["flushes"])
    assert st.get("vector.storage.recovered") == 0  # counted, none stored
    # every pass here fits one chunk: the counter is there, at 0
    assert st.counters[FLUSH_STATS["pipelined"]] == 0


async def test_a_pass_in_chunks_is_one_observation_a_stage(monkeypatch):
    """30 rows in chunks of 8 (the stop drain's one pass): flush.locate,
    flush.gather and flush.write are still observed once, their seconds
    summed over the chunks, and tile the pass's ``flush``; the rows of
    every chunk but the last count as pipelined; the one program the
    launches compiled is booked to compile.flush.gather."""
    monkeypatch.setattr(checkpoint, "_CHUNK_BYTES", 4 * 8)
    silo = _build(True, MemoryStorage(), period=3600.0)
    await silo.start()
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    _gather_rows.clear_cache()
    try:
        await _rounds(client, range(30), rounds=1)
    finally:
        await client.close_async()
        await silo.stop()
    st = silo.stats
    assert st.get(FLUSH_STATS["flushes"]) == 1
    assert st.get(FLUSH_STATS["flushed"]) == 30
    assert st.get(FLUSH_STATS["pipelined"]) == 24
    h = st.histograms
    parts = [h[f"flush.{s}.seconds"] for s in ("locate", "gather", "write")]
    assert [p.total for p in parts] == [1, 1, 1]
    assert h["flush.seconds"].total == 1
    assert all(p.sum > 0 for p in parts)
    assert h["flush.seconds"].sum >= sum(p.sum for p in parts) - 1e-9
    compiled = {n: x.total for n, x in h.items()
                if n.startswith("compile.flush.")}
    assert compiled == {"compile.flush.gather.seconds": 1}  # 8 rows
    assert "compile.other.seconds" not in h
    assert stats_mod._thread.stage is None


async def test_metrics_off_registers_none_of_the_new_names():
    silo = _build(False, MemoryStorage())
    await silo.start()
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    try:
        await _rounds(client, range(4), rounds=2)
        await _settle(silo, 4)
    finally:
        await client.close_async()
        await silo.stop()
    names = set(silo.stats.histograms) | set(silo.stats.counters)
    new = {s + ".seconds" for s in STAGES} | {
        FLUSH_STATS["rows"], FLUSH_STATS["flushes"]} | set(
        RECOVER_STATS.values())
    assert not names & new
    assert not [n for n in names if n.startswith("compile.")]
    assert silo.stats.get(FLUSH_STATS["flushed"]) >= 4  # it did flush


class Pinger(VectorGrain):
    """``ping(to)`` sends one message to ``to``'s ``pong``."""

    STATE = {"pongs": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"pongs": jnp.int32(0)}

    @actor_method(args={"x": (jnp.int32, ())})
    def pong(state, args):
        n = state["pongs"] + args["x"]
        return {"pongs": n}, n

    @sends("pong", fanout=2, args={"to": (jnp.int32, ())})
    def ping(state, args):
        return state, state["pongs"], (
            jnp.stack([args["to"], args["to"]]), jnp.array([True, True]),
            {"x": jnp.ones(2, jnp.int32)})


async def _serve_pings(metrics: bool, offloop: bool):
    b = (SiloBuilder().with_name(f"ex-{metrics}-{offloop}")
         .with_fabric(SocketFabric())
         .with_config(metrics_enabled=metrics, offloop_tick=offloop))
    add_vector_grains(b, Pinger, mesh=make_mesh(4), dense={Pinger: 64},
                      capacity_per_shard=16, storage=MemoryStorage(),
                      flush_period=0.05)
    silo = b.build()
    await silo.start()
    observers: set = set()
    observe, increment = silo.stats.observe, silo.stats.increment

    def spy_observe(key, value):
        observers.add(threading.get_ident())
        observe(key, value)

    def spy_increment(key, value=1):
        observers.add(threading.get_ident())
        increment(key, value)

    silo.stats.observe, silo.stats.increment = spy_observe, spy_increment
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    try:
        for r in range(ROUNDS):
            await asyncio.gather(*(
                client.get_grain(Pinger, k).ping(to=np.int32(63 - k))
                for k in range(8)))
    finally:
        await client.close_async()
        await silo.stop()
    return silo, observers, threading.get_ident()


@pytest.mark.parametrize("offloop", [True, False],
                         ids=["worker", "lever-off"])
async def test_exchange_stages_reach_the_registry_through_the_sink(offloop):
    silo, observers, loop_thread = await _serve_pings(True, offloop)
    st = silo.stats
    assert observers == {loop_thread}
    jobs = st.get(EXCHANGE_STATS["jobs"])
    assert ROUNDS <= jobs <= 8 * ROUNDS
    # one exchange and one activate span a sending job; a route and an
    # apply span a pass (16 messages of one shard for one other, 8 a
    # pass: two passes a job); two dedup rounds a pass
    for stage in ("exchange", "exchange.activate"):
        assert _count(st, stage + ".seconds") == jobs, stage
    passes = st.get(EXCHANGE_STATS["lanes"]) // (4 * 4 * 8)
    assert passes >= jobs
    for stage in ("exchange.route", "exchange.apply"):
        assert _count(st, stage + ".seconds") == passes, stage
    assert st.get(EXCHANGE_STATS["rounds"]) == 2 * passes
    assert st.get(EXCHANGE_STATS["sent"]) \
        == st.get(EXCHANGE_STATS["delivered"]) == 2 * 8 * ROUNDS
    assert st.get(EXCHANGE_STATS["cross_shard"]) == 2 * 8 * ROUNDS
    assert st.get(EXCHANGE_STATS["activated"]) == 8
    assert st.get(EXCHANGE_STATS["dropped"]) == 0
    assert st.get(EXCHANGE_STATS["lanes"]) >= st.get(EXCHANGE_STATS["sent"])
    assert stats_mod._thread.stage is None
    # the first pass's programs compiled inside the stages that ran them
    compiled = [n for n in st.histograms if n.startswith("compile.")]
    assert any(n.startswith("compile.exchange.") for n in compiled)
    assert "compile.other.seconds" not in compiled


async def test_exchange_stamps_nothing_with_metrics_off():
    silo, observers, _loop = await _serve_pings(False, True)
    names = set(silo.stats.histograms) | set(silo.stats.counters)
    assert not [n for n in names if n.startswith(("exchange", "compile."))]
    assert not names & set(EXCHANGE_STATS.values())
    # it did deliver: the receivers' rows were flushed
    assert silo.stats.get(FLUSH_STATS["flushed"]) >= 8


class _GatedStorage(MemoryStorage):
    """A provider that really waits: every read parks on ``gate``."""

    def __init__(self) -> None:
        super().__init__()
        self.gate = asyncio.Event()

    async def read(self, grain_type, grain_id):
        await self.gate.wait()
        return await super().read(grain_type, grain_id)


async def test_recover_span_is_held_across_a_suspending_load():
    """One ``recover`` span a pass, open while the provider waits and
    closed when the load lands; a window without a first touch leaves the
    two recovery stats in place, unmoved."""
    storage = _GatedStorage()
    silo = _build(True, storage, period=3600.0)
    await silo.start()
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    st = silo.stats
    try:
        calls = [(k, {"x": np.int32(1)}) for k in range(N_KEYS)]
        futs = client.call_batch(CounterVec, "add", calls)
        for _ in range(400):
            if silo.dispatcher._vector_recoveries:
                break
            await asyncio.sleep(0.005)
        # the pass is in flight: its span is open, nothing observed yet
        assert len(silo.dispatcher._vector_recoveries) == N_KEYS
        assert _count(st, "recover.seconds") == 0
        assert st.get(RECOVER_STATS["first_touch"]) == N_KEYS
        await asyncio.sleep(0.05)
        storage.gate.set()
        assert [int(v) for v in await asyncio.gather(*futs)] == [1] * N_KEYS
        assert _count(st, "recover.seconds") == 1
        assert st.histograms["recover.seconds"].sum >= 0.05
        keys = st.histograms[RECOVER_STATS["keys"]]
        assert (keys.total, keys.sum) == (1, N_KEYS)
        assert not silo.dispatcher._vector_recoveries
        # a second round touches nothing fresh: both stats stay, unmoved
        before = dict(st.counters)
        out = await asyncio.gather(*client.call_batch(CounterVec, "add",
                                                      calls))
        assert [int(v) for v in out] == [2] * N_KEYS
        assert st.counters[RECOVER_STATS["first_touch"]] \
            == before[RECOVER_STATS["first_touch"]] == N_KEYS
        assert (keys.total, keys.sum) == (1, N_KEYS)
        assert _count(st, "recover.seconds") == 1
    finally:
        await client.close_async()
        await silo.stop()


async def test_first_touch_counter_exists_before_any_first_touch():
    """0 and not absent: a read that meets no fresh key still stamps the
    counter, so a window without a first touch reads 0."""
    silo = _build(True, MemoryStorage(), period=3600.0)
    await silo.start()
    rt = silo.vector
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    try:
        # activated in-process, past the dispatcher: no first touch there
        assert int(await rt.call(CounterVec, 5, "add", x=np.int32(1))) == 1
        assert RECOVER_STATS["first_touch"] not in silo.stats.counters
        g = client.get_grain(CounterVec, 5)
        assert int(await g.add(x=np.int32(1))) == 2
        assert silo.stats.counters[RECOVER_STATS["first_touch"]] == 0
        assert _count(silo.stats, "recover.seconds") == 0
    finally:
        await client.close_async()
        await silo.stop()


async def test_compiles_are_booked_to_the_stage_that_compiled():
    """A gather in a size bucket the process has not seen compiles under
    flush.gather; a tick at a warm bucket books nothing."""
    silo = _build(True, MemoryStorage(), period=3600.0)
    await silo.start()
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    bridge = silo.vector_bridges[CounterVec]
    st = silo.stats

    def compiles(prefix: str) -> int:
        return sum(h.total for n, h in st.histograms.items()
                   if n.startswith(prefix))

    try:
        await _rounds(client, range(8), rounds=1)  # compiles the bucket
        tick0 = compiles("compile.ingest.") + compiles("compile.engine.")
        assert tick0 >= 1
        await _rounds(client, range(8, 16), rounds=1)  # same bucket: warm
        assert compiles("compile.ingest.") + compiles("compile.engine.") \
            == tick0
        _gather_rows.clear_cache()  # whatever other tests left behind
        for n in (13, 17):  # two gather buckets (16, 32) new to the process
            before = compiles("compile.flush.gather")
            assert await bridge.flush(range(n)) == n
            assert compiles("compile.flush.gather") > before
        assert compiles("compile.flush.") == compiles("compile.flush.gather")
        before = compiles("compile.flush.gather")
        for n in (11, 16, 9, 31):  # new lengths inside those buckets: none
            assert await bridge.flush(range(n)) == n
        assert compiles("compile.flush.gather") == before
    finally:
        await client.close_async()
        await silo.stop()


async def test_profiler_capture_carries_dispatch_events_with_their_tick(
        tmp_path):
    silo = _build(True)
    await silo.start()
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    try:
        await _rounds(client, range(8), rounds=1)  # compile outside
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            await _rounds(client, range(8, 16), rounds=3)
        finally:
            jax.profiler.stop_trace()
        ticks_now = silo.vector.ticks
    finally:
        await client.close_async()
        await silo.stop()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    found = [dict(e.stats)
             for p in jax.profiler.ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events
             if e.name == "otpu:ingest.tick.dispatch"]
    assert len(found) >= 3
    assert all(0 <= int(s["tick"]) < ticks_now for s in found)


async def test_failed_batch_closes_its_stages():
    """A batch that raises mid-stage records the failed step and leaves
    the worker thread with no current stage."""
    silo = _build(True)
    await silo.start()
    rt = silo.vector
    left = []
    run_job = rt._run_job
    try:
        def boom(*_a, **_k):
            raise RuntimeError("no kernel")

        def spy_run_job(job):
            run_job(job)
            left.append((threading.current_thread().name,
                         stats_mod._thread.stage))

        rt._kernel = boom
        rt._run_job = spy_run_job
        fut = rt.call(CounterVec, 3, "add", x=np.int32(1))
        await rt.flush()
        with pytest.raises(RuntimeError, match="no kernel"):
            await fut
        rt.shutdown_worker()  # joins: the spy has run to its end
        assert left == [("orleans-tick-worker", None)]
        assert stats_mod._thread.stage is None
        assert silo.stats.histograms["ingest.transfer.seconds"].total == 1
        assert "ingest.tick.dispatch.seconds" not in silo.stats.histograms
    finally:
        del rt._kernel, rt._run_job
        await silo.stop()


async def test_compile_listener_is_installed_once(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        calls.append)
    monkeypatch.setattr(stats_mod, "_listening", False)
    silos = [_build(True), _build(True), _build(False)]
    for s in silos:
        await s.start()
    try:
        assert calls == [stats_mod._book_compile]
    finally:
        for s in silos:
            await s.stop()
    assert stats_mod._thread.home is None


def test_compile_outside_any_stage_books_to_other():
    reg = StatsRegistry()
    open_stage_registry(reg)
    try:
        jax.jit(lambda x: x * 3 + 11)(jnp.arange(5)).block_until_ready()
        assert reg.histograms["compile.other.seconds"].total >= 1
        with StageSpan(reg, "unit.step"):
            jax.jit(lambda x: x * 5 + 13)(jnp.arange(5)).block_until_ready()
        assert reg.histograms["compile.unit.step.seconds"].total >= 1
        other = reg.histograms["compile.other.seconds"].total
    finally:
        close_stage_registry(reg)
    jax.jit(lambda x: x * 7 + 17)(jnp.arange(5)).block_until_ready()
    assert reg.histograms["compile.other.seconds"].total == other
