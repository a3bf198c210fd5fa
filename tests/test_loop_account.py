"""The loop account (observability.profiling, installed by
``metrics_enabled``): the silo's loop time keyed on the stage spans and
folded into its ``StatsRegistry`` — ``loop.idle.seconds``,
``loop.stage.<stage>.seconds``, ``loop.other.<category>.seconds`` — the
stops (``loop.stop.seconds``, ``loop.stops.<owner>``), and the tick
worker's wait (``engine.worker_idle.seconds``).

One served silo (SocketFabric + GatewayClient + write-behind storage) is
driven for a few seconds and the cases read its registry; the rest are
small loops of their own.
"""

import asyncio
import importlib.util
import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.dispatch import VectorGrain, actor_method, add_vector_grains
from orleans_tpu.observability import stats as stats_mod
from orleans_tpu.observability.profiling import (FOLD_S, install_loop_profiler,
                                                 uninstall_loop_profiler)
from orleans_tpu.observability.stats import StageSpan, StatsRegistry
from orleans_tpu.parallel import make_mesh
from orleans_tpu.runtime import (ClusterClient, GatewayClient, Grain,
                                 SiloBuilder, SocketFabric)
from orleans_tpu.storage import MemoryStorage

SERVED_S = 3.0


class TallyVec(VectorGrain):
    STATE = {"total": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"total": jnp.int32(0)}

    @actor_method(args={"x": (jnp.int32, ())})
    def add(state, args):
        total = state["total"] + args["x"]
        return {"total": total}, total


class SlowGrain(Grain):
    async def crunch(self) -> int:
        end = time.perf_counter() + 0.02
        while time.perf_counter() < end:
            pass  # hog the loop synchronously
        return 1


def _loop_sums(stats) -> dict:
    return {n: h.sum for n, h in stats.histograms.items()
            if n.startswith("loop.")}


async def _serve() -> dict:
    b = (SiloBuilder().with_name("acct").with_fabric(SocketFabric())
         .with_config(metrics_enabled=True))
    add_vector_grains(b, TallyVec, mesh=make_mesh(1), dense={TallyVec: 64},
                      capacity_per_shard=128, storage=MemoryStorage(),
                      flush_period=0.05)
    silo = b.build()
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
    refs = [client.get_grain(TallyVec, k) for k in range(32)]
    try:
        await asyncio.gather(*(g.add(x=np.int32(1)) for g in refs))  # warm
        await asyncio.sleep(0.2)
        t0, s0 = time.perf_counter(), _loop_sums(silo.stats)
        while time.perf_counter() - t0 < SERVED_S:
            await asyncio.gather(*(g.add(x=np.int32(1)) for g in refs))
        t1, s1 = time.perf_counter(), _loop_sums(silo.stats)
        hooks = "call_soon" in asyncio.get_running_loop().__dict__
    finally:
        await client.close_async()
        await silo.stop()
    return {"stats": silo.stats, "wall": t1 - t0, "observers": observers,
            "loop_thread": loop_thread, "sunk": sunk, "hooks": hooks,
            "delta": {n: v - s0.get(n, 0.0) for n, v in s1.items()}}


@pytest.fixture(scope="module")
def served():
    return asyncio.run(_serve())


def test_the_loops_seconds_sum_to_the_wall_clock(served):
    """Read as the benchmark reads it — no flush at the read — idle, the
    stages' self time and the time in no stage cover the wall clock."""
    assert served["hooks"]
    d = served["delta"]
    parts = {n for n in d if n.startswith(("loop.idle.", "loop.stage.",
                                           "loop.other."))}
    assert "loop.idle.seconds" in parts
    for stage in ("pump.batch", "engine.claim", "engine.complete",
                  "engine.resolve", "egress.flush"):
        assert d[f"loop.stage.{stage}.seconds"] > 0, stage
    total = sum(d[n] for n in parts)
    assert total == pytest.approx(served["wall"], rel=0.02)


def test_a_stages_self_time_is_within_its_span(served):
    st = served["stats"]
    staged = [n for n in st.histograms if n.startswith("loop.stage.")]
    assert staged
    for n in staged:
        stage = n[len("loop.stage."):-len(".seconds")]
        span = st.histograms[stage + ".seconds"]
        # (the switch's own call apart at each end of a span)
        assert st.histograms[n].sum <= span.sum + span.total * 5e-5, stage
    # engine.resolve runs inside engine.complete: the outer's self time
    # leaves the inner's out; pump.batch holds its read's recovery pass
    # (held across the load, closed inside it) and nothing else: the two
    # self times are the span
    h = st.histograms
    pump = h["pump.batch.seconds"]
    assert h["loop.stage.pump.batch.seconds"].sum \
        + h["loop.stage.recover.seconds"].sum \
        == pytest.approx(pump.sum, rel=0.05)
    outer = h["engine.complete.seconds"]
    assert h["loop.stage.engine.complete.seconds"].sum <= \
        outer.sum - h["loop.stage.engine.resolve.seconds"].sum \
        + outer.total * 5e-5


def test_the_workers_idle_arrives_through_the_sink(served):
    assert "engine.worker_idle.seconds" in served["sunk"]
    assert served["observers"] == {served["loop_thread"]}
    idle = served["stats"].histograms["engine.worker_idle.seconds"]
    assert 0 < idle.sum


def test_stops_are_counted_by_their_owner(served):
    st = served["stats"]
    stops = st.histograms["loop.stop.seconds"]
    owners = {n: v for n, v in st.counters.items()
              if n.startswith("loop.stops.")}
    assert sum(owners.values()) == stops.total
    assert all(v > 0 for v in owners.values())


async def test_a_turn_that_hogs_the_loop_is_busy_and_one_stop():
    """The blind spot the account had: the callback that installs it runs
    unwrapped, and turns run eagerly inside it. A 20 ms turn books 20 ms
    outside ``loop.idle.seconds`` and is exactly one stop, whichever
    callback it runs in."""
    silo = (SiloBuilder().with_name("acct-slow").add_grains(SlowGrain)
            .with_config(metrics_enabled=True, hot_lane_enabled=False)
            .build())
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    client.hot_lane_enabled = False
    st, lp = silo.stats, silo.loop_account
    try:
        for _ in range(2):  # the first right after start, then a later one
            lp._flush()
            lp._fold()
            busy0 = sum(v for n, v in _loop_sums(st).items()
                        if n != "loop.idle.seconds"
                        and n != "loop.stop.seconds")
            stops0 = st.histograms["loop.stop.seconds"].total
            assert await client.get_grain(SlowGrain, 1).crunch() == 1
            await asyncio.sleep(0.01)
            lp._flush()
            lp._fold()
            busy = sum(v for n, v in _loop_sums(st).items()
                       if n != "loop.idle.seconds"
                       and n != "loop.stop.seconds") - busy0
            assert busy >= 0.02
            assert st.histograms["loop.stop.seconds"].total - stops0 == 1
            assert st.histograms["loop.stop.seconds"].sum >= 0.02
    finally:
        await client.close_async()
        await silo.stop()


async def test_a_span_held_across_an_await_books_only_its_own_steps():
    loop = asyncio.get_running_loop()
    reg = StatsRegistry()
    install_loop_profiler(loop, stats=reg)
    try:
        async def held():
            with StageSpan(reg, "unit.held", nest=False):
                await asyncio.sleep(0.05)

        async def other():
            for _ in range(5):
                end = time.perf_counter() + 0.006
                while time.perf_counter() < end:
                    pass
                await asyncio.sleep(0)

        await asyncio.gather(held(), other())
        await asyncio.sleep(FOLD_S + 0.01)
        await asyncio.sleep(0)
    finally:
        uninstall_loop_profiler(loop)
    h = reg.histograms
    assert h["unit.held.seconds"].sum >= 0.05
    # its own two steps, not the other task's 30 ms of spinning
    assert h["loop.stage.unit.held.seconds"].sum < 0.01
    assert h["loop.other.other.seconds"].sum >= 0.03
    assert reg.counters["loop.stops.other"] == 5
    assert stats_mod._thread.account is None


async def test_a_held_span_closed_in_a_nesting_one_gives_it_back_the_time():
    loop = asyncio.get_running_loop()
    reg = StatsRegistry()
    install_loop_profiler(loop, stats=reg)

    def read():
        with StageSpan(reg, "unit.outer"):
            StageSpan(reg, "unit.held", nest=False).close()
            end = time.perf_counter() + 0.01
            while time.perf_counter() < end:
                pass

    try:
        loop.call_soon(read)
        await asyncio.sleep(0.02)
    finally:
        uninstall_loop_profiler(loop)
    h = reg.histograms
    assert h["loop.stage.unit.outer.seconds"].sum >= 0.01
    assert reg.counters == {"loop.stops.unit.outer": 1}


async def test_metrics_off_installs_no_hooks_and_builds_no_span(monkeypatch):
    built = []
    init = StageSpan.__init__

    def spy_init(self, *a, **k):
        built.append(a[1] if len(a) > 1 else k.get("name"))
        init(self, *a, **k)

    monkeypatch.setattr(StageSpan, "__init__", spy_init)
    b = SiloBuilder().with_name("acct-off").with_fabric(SocketFabric())
    add_vector_grains(b, TallyVec, mesh=make_mesh(1), dense={TallyVec: 64},
                      capacity_per_shard=128, storage=MemoryStorage(),
                      flush_period=0.05)
    silo = b.build()
    await silo.start()
    client = await GatewayClient([silo.gateway_endpoint]).connect()
    try:
        loop = asyncio.get_running_loop()
        out = await asyncio.gather(*(client.get_grain(TallyVec, k)
                                     .add(x=np.int32(1)) for k in range(8)))
        assert [int(v) for v in out] == [1] * 8
        await asyncio.sleep(0.15)  # a flush pass too
        assert silo.loop_account is None and silo.loop_prof is None
        assert not {"call_soon", "call_at", "call_soon_threadsafe",
                    "_add_reader"} & set(loop.__dict__)
        assert stats_mod._thread.account is None
    finally:
        await client.close_async()
        await silo.stop()
    assert built == []
    names = set(silo.stats.histograms) | set(silo.stats.counters)
    assert not [n for n in names if n.startswith(("loop.", "engine."))]


def _reader(name: str):
    path = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                        "readers", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_prefix_share_reader_sums_a_family_or_reads_nothing():
    read = _reader("histogram_prefix_share")
    ctx = {"seconds": 20.0, "histograms": {
        "loop.other.pump.seconds": {"count": 400, "sum": 2.0},
        "loop.other.other.seconds": {"count": 400, "sum": 1.0},
        "loop.idle.seconds": {"count": 400, "sum": 9.0}}}
    assert read(ctx, prefixes=["loop.other."], scale=100) == \
        pytest.approx(15.0)
    assert read(ctx, prefixes=["loop.none."], scale=100) is None
    assert read({"seconds": 20.0, "histograms": {}},
                prefixes=["loop.other."]) is None


def test_what_waited_before_the_install_is_wrapped_where_it_waits():
    """A handle already in the ready queue or among the timers, and the
    selector's readers (the self-pipe ``call_soon_threadsafe`` wakes),
    were scheduled through the class methods: the install wraps them
    where they wait, so their time is busy and not idle."""
    loop = asyncio.new_event_loop()
    reg = StatsRegistry()

    def spin():
        end = time.perf_counter() + 0.01
        while time.perf_counter() < end:
            pass

    try:
        loop.call_soon(spin)
        loop.call_later(0.001, spin)
        prof = install_loop_profiler(loop, stats=reg)
        selector = loop._selector.get_map().values()
        assert all(h._callback == prof._entry()
                   for key in selector for h in key.data if h is not None)
        loop.call_later(0.05, loop.stop)
        loop.run_forever()
        prof._fold()
        assert reg.histograms["loop.stop.seconds"].total == 2
        assert reg.histograms["loop.other.other.seconds"].sum >= 0.02
    finally:
        uninstall_loop_profiler(loop)
        loop.close()
