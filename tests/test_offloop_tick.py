"""The device-tick pipeline: the tick worker, the tick-serialization
fence for donated state/staging, and the deliberate client-side
``call_batch`` path.

The hard invariants under test (ISSUE 9 tentpole):

* worker-side ticks produce the results a plain per-key total gives,
  with turn semantics (one message per activation per tick) preserved
  under concurrent enqueue-during-tick;
* ``grow()`` (loop-side, triggered by hashed allocation) can never
  interleave with a worker-side batch whose donated state/staging upload
  is in flight — the table fence serializes them;
* the migration fence sees worker-in-flight keys
  (``pending_key_hashes``), so a rebalance shard move can never race an
  executing batch;
* ``flush()`` drains worker-side in-flight batches;
* a bare ``VectorRuntime`` starts its worker on the first claimed job
  like a silo-hosted one, and an abandoned runtime's worker exits;
* the batched client path honors ``ORLEANS_TPU_DEBUG_POOL=1`` pool
  discipline end to end;
* the hand-off to the worker is bounded (ISSUE 29): a (class, method)
  group with a job at the worker is held in ``pending`` and coalesces;
  completion re-arms the claim. Those tests hold the worker with the tick
  fence or a failing batch, never with time.
"""

import asyncio
import gc

import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.core.message import set_debug_pool
from orleans_tpu.dispatch import (VectorGrain, VectorRuntime,
                                  actor_method, add_vector_grains)
from orleans_tpu.dispatch.engine import _HANDOFF_DEPTH as DEPTH
from orleans_tpu.parallel import make_mesh
from orleans_tpu.runtime import ClusterClient, Grain, SiloBuilder


class CounterVec(VectorGrain):
    STATE = {"total": (jnp.float32, ()), "ticks": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"total": jnp.float32(0.0), "ticks": jnp.int32(0)}

    @actor_method(args={"x": (jnp.float32, ())})
    def add(state, args):
        return ({"total": state["total"] + args["x"],
                 "ticks": state["ticks"] + 1}, state["total"] + args["x"])

    @actor_method(read_only=True)
    def read(state, args):
        return state, state["total"]


class EchoGrain(Grain):
    async def ping(self, x: int) -> int:
        return x


def _build(*, dense: int | None = 64,
           capacity: int = 64, n_shards: int = 1, **cfg):
    b = SiloBuilder().with_name("ot").add_grains(EchoGrain).with_config(**cfg)
    add_vector_grains(b, CounterVec, mesh=make_mesh(n_shards),
                      capacity_per_shard=capacity,
                      dense={CounterVec: dense} if dense else None)
    return b.build()


async def test_worker_results_match_plain_totals():
    """Served traffic through the worker → the per-key state a plain
    Python running total gives."""
    want = [0.0] * 16
    silo = _build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        assert silo.vector._worker is None  # started lazily, on traffic
        refs = [client.get_grain(CounterVec, k) for k in range(16)]
        for rnd in range(5):
            got = await asyncio.gather(*(r.add(x=float(rnd + k))
                                         for k, r in enumerate(refs)))
            for k in range(16):
                want[k] += float(rnd + k)
            assert [float(v) for v in got] == want
        out = await asyncio.gather(*(r.read() for r in refs))
        assert [float(v) for v in out] == want
        assert silo.vector._worker.is_alive()
    finally:
        await client.close_async()
        await silo.stop()
    assert silo.vector._worker is None  # silo stop ended the thread


async def test_concurrent_enqueue_during_tick_preserves_turns():
    """Calls racing in WHILE worker ticks are in flight: every call lands
    in some tick, one-per-activation-per-tick, and per-key sums come out
    exact (the donation/rotation discipline never loses or doubles a
    write)."""
    silo = _build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        n_keys, rounds = 8, 40
        refs = [client.get_grain(CounterVec, k) for k in range(n_keys)]

        async def hammer(k: int):
            # no awaits between sends inside a round: same-key calls
            # pile into the same pending batch and conflict-defer
            for _ in range(rounds):
                await refs[k].add(x=1.0)

        await asyncio.gather(*(hammer(k) for k in range(n_keys)))
        out = await asyncio.gather(*(r.read() for r in refs))
        assert [float(v) for v in out] == [float(rounds)] * n_keys
        rt = silo.vector
        assert rt.messages_processed >= n_keys * rounds
        assert not rt.pending and rt._inflight == 0
    finally:
        await client.close_async()
        await silo.stop()


async def test_grow_racing_worker_upload():
    """Hashed-regime allocation grows the table (state swap + staging
    sink re-point) while worker batches are continuously in flight: the
    table fence serializes the swap against donated uploads, and no
    write is lost across the growth."""
    silo = _build(dense=None, capacity=8)
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        tbl = silo.vector.table(CounterVec)
        cap0 = tbl.capacity
        # wave after wave of NEW keys (never awaited between sends within
        # a wave) so lookup_or_allocate exhausts the free lists and
        # grows mid-traffic, repeatedly
        key = 1 << 40  # far outside any dense range
        keys = []
        for wave in range(6):
            wave_keys = [key + wave * 64 + i for i in range(48)]
            keys.extend(wave_keys)
            await asyncio.gather(*(
                client.get_grain(CounterVec, k).add(x=1.0)
                for k in wave_keys))
        assert tbl.capacity > cap0, "growth never triggered"
        out = await asyncio.gather(*(
            client.get_grain(CounterVec, k).read() for k in keys))
        assert all(float(v) == 1.0 for v in out)
    finally:
        await client.close_async()
        await silo.stop()


async def test_migration_fence_sees_inflight_keys():
    """A batch handed to the worker (but not yet completed) keeps its
    keys in ``pending_key_hashes`` — the set the rebalance executor
    fences shard moves on — until the loop-side completion runs. Made
    deterministic by holding the tick fence from the test: the worker
    blocks on it, so the batch is provably in flight."""
    silo = _build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        rt = silo.vector
        # prime: compile the kernel and start the worker
        await client.get_grain(CounterVec, 0).add(x=1.0)
        fence = rt.tick_fence()
        fence.acquire()
        try:
            futs = [client.get_grain(CounterVec, k).add(x=2.0)
                    for k in (3, 4)]
            # let the loop run the tick hand-off; the worker then blocks
            # on the fence we hold
            for _ in range(20):
                await asyncio.sleep(0)
                if rt._inflight:
                    break
            assert rt._inflight >= 1
            fenced = rt.pending_key_hashes(CounterVec)
            assert {3, 4} <= fenced
        finally:
            fence.release()
        await asyncio.gather(*futs)
        # completed: the in-flight fence released the keys
        assert not (rt.pending_key_hashes(CounterVec) & {3, 4})
        assert rt._inflight == 0
    finally:
        await client.close_async()
        await silo.stop()


async def test_flush_drains_worker_inflight():
    """``flush()`` returns only after pending AND worker-in-flight work
    retired (one-way calls leave no futures to await, so flush is the
    only drain)."""
    silo = _build()
    await silo.start()
    try:
        rt = silo.vector
        for k in range(12):
            rt.call(CounterVec, k, "add", x=float(k))
        await rt.flush()
        assert not rt.pending and rt._inflight == 0
        assert rt.messages_processed >= 12
    finally:
        await silo.stop()


async def test_bare_runtime_ticks_on_its_worker():
    """A bare VectorRuntime (no silo, no options) runs the served tick:
    its worker starts on the first claimed job and ``flush()`` drains
    it."""
    rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=16)
    assert rt._worker is None
    fut = rt.call(CounterVec, 5, "add", x=3.0)
    assert rt._worker is None  # nothing claimed yet
    await rt.flush()
    assert fut.done() and float(fut.result()) == 3.0
    assert rt._worker.is_alive()
    assert rt._worker.name == "orleans-tick-worker" and rt._worker.daemon
    assert not rt.pending and rt._inflight == 0 and rt._quiesced.is_set()
    rt.shutdown_worker()


async def test_shutdown_worker_twice_then_a_call_restarts_it():
    from orleans_tpu.config import DispatchOptions
    rt = VectorRuntime(mesh=make_mesh(1),
                       options=DispatchOptions(capacity_per_shard=16))
    rt.shutdown_worker()  # never started: nothing to stop
    assert float(await rt.call(CounterVec, 5, "add", x=3.0)) == 3.0
    first = rt._worker
    rt.shutdown_worker()
    rt.shutdown_worker()
    assert rt._worker is None and not first.is_alive()
    assert float(await rt.call(CounterVec, 5, "add", x=1.0)) == 4.0
    assert rt._worker is not first and rt._worker.is_alive()
    rt.shutdown_worker()
    assert not rt._worker_stop.alive  # the restart's finaliser went too


def test_abandoned_runtime_worker_exits():
    """Hundreds of short-lived bare runtimes are built in one process:
    the idle worker must not pin its runtime, and collecting the runtime
    ends the thread."""
    import weakref

    async def use() -> tuple:
        rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=16)
        assert float(await rt.call(CounterVec, 1, "add", x=2.0)) == 2.0
        return rt._worker, weakref.ref(rt)

    worker, ref = asyncio.run(use())
    for _ in range(3):
        gc.collect()
    assert ref() is None, "the idle worker (or a cycle) pins the runtime"
    worker.join(10.0)
    assert not worker.is_alive()


def test_bare_runtime_survives_a_second_event_loop():
    """One runtime driven by two ``asyncio.run`` calls: completions post
    to the loop that claimed, and ``flush()`` waits on that loop's
    event."""
    rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=16)

    async def round_(x: float) -> float:
        fut = rt.call(CounterVec, 3, "add", x=x)
        await rt.flush()
        return float(fut.result())

    try:
        assert asyncio.run(round_(1.0)) == 1.0
        first = rt._worker
        assert asyncio.run(round_(2.0)) == 3.0
        assert rt._worker is first and first.is_alive()
    finally:
        rt.shutdown_worker()


@pytest.mark.parametrize("hosted", [True, False], ids=["silo", "bare"])
async def test_lever_off_runs_the_same_job_on_the_loop(hosted):
    """``offloop_tick=False`` (the one lever left, PERF.md section 6, PR
    30): every claimed job runs on the event loop, in place, through the
    same ``_run_job`` → ``_execute_batch`` → ``_complete_job``. No worker
    starts, the answers are the plain per-key totals, a key's burst is
    served one message a tick in send order, and ``flush()`` drains."""
    import threading
    silo = None
    if hosted:
        silo = _build(offloop_tick=False, metrics_enabled=True)
        await silo.start()
        rt = silo.vector
    else:
        rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=64)
        rt.offloop_tick = False
    threads = set()
    execute = rt._execute_batch

    def spy(*a, **k):
        threads.add(threading.current_thread())
        return execute(*a, **k)

    rt._execute_batch = spy
    try:
        assert rt.offloop_tick is False
        futs = [rt.call(CounterVec, k, "add", x=float(k + 1))
                for k in range(8)]
        burst = [rt.call(CounterVec, 3, "add", x=1.0) for _ in range(4)]
        await rt.flush()
        assert [float(f.result()) for f in futs] \
            == [float(k + 1) for k in range(8)]
        assert [float(f.result()) for f in burst] == [5.0, 6.0, 7.0, 8.0]
        assert rt.conflicts_deferred == 4 + 3 + 2 + 1
        assert threads == {threading.main_thread()}
        assert rt._worker is None and rt._inflight == 0
        assert not rt._inflight_groups and rt._quiesced.is_set()
        if hosted:
            # the observations took the one route: stamped in the job's
            # sink, replayed by _complete_job
            h = silo.stats.histograms
            ticks = h["ingest.tick.seconds"].total
            assert ticks == 5 == h["engine.claim.seconds"].total \
                == h["engine.resolve.seconds"].total
            assert silo.stats.get("ingest.messages") == 12
    finally:
        del rt._execute_batch
        if silo is not None:
            await silo.stop()


async def test_lever_off_a_failed_batch_fails_its_callers_only():
    rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=16)
    rt.offloop_tick = False

    def boom(*_a, **_k):
        raise RuntimeError("no kernel")

    rt._kernel = boom
    fut = rt.call(CounterVec, 2, "add", x=1.0)
    await rt.flush()
    with pytest.raises(RuntimeError, match="no kernel"):
        fut.result()
    assert rt._inflight == 0 and not rt._inflight_groups
    del rt._kernel
    assert float(await rt.call(CounterVec, 2, "add", x=2.0)) == 2.0
    assert rt._worker is None


async def test_call_batch_debug_pool_discipline():
    """ORLEANS_TPU_DEBUG_POOL=1 over the batched client path: envelope
    recycling stays disciplined through call_batch → deliver_batch →
    call_group → off-loop tick → response correlation."""
    prev = set_debug_pool(True)
    try:
        silo = _build()
        await silo.start()
        client = await ClusterClient(silo.fabric).connect()
        try:
            for rnd in range(3):
                futs = client.call_batch(
                    CounterVec, "add",
                    [(k, {"x": float(rnd + 1)}) for k in range(8)])
                await asyncio.gather(*futs)
            futs = client.call_batch(EchoGrain, "ping",
                                     [(k, {"x": k}) for k in range(8)])
            assert await asyncio.gather(*futs) == list(range(8))
        finally:
            await client.close_async()
            await silo.stop()
    finally:
        set_debug_pool(prev)


async def test_call_batch_per_item_error_isolation():
    """A schema-violating item resolves ITS awaitable with the error;
    the rest of the batch proceeds."""
    silo = _build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        futs = client.call_batch(
            CounterVec, "add",
            [(0, {"x": 1.0}), (1, {"bogus": 1.0}), (2, {"x": 2.0})])
        r0, r1, r2 = await asyncio.gather(*futs, return_exceptions=True)
        assert float(r0) == 1.0
        assert isinstance(r1, Exception)
        assert float(r2) == 2.0
    finally:
        await client.close_async()
        await silo.stop()


async def test_checkpoint_capture_fenced_under_traffic():
    """Donation-safe capture while worker ticks are continuously in
    flight: the fence means the D2H copy never materializes a donated
    array (a race here raises 'Array has been deleted')."""
    silo = _build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        rt = silo.vector
        refs = [client.get_grain(CounterVec, k) for k in range(32)]
        stop = asyncio.Event()

        async def traffic():
            i = 0
            while not stop.is_set():
                await asyncio.gather(*(r.add(x=1.0) for r in refs))
                i += 1

        t = asyncio.ensure_future(traffic())
        tbl = rt.table(CounterVec)
        for _ in range(25):
            snap = tbl.snapshot()  # fenced D2H of the whole table
            assert set(snap) == {"total", "ticks"}
            await asyncio.sleep(0)
        stop.set()
        await t
    finally:
        await client.close_async()
        await silo.stop()


async def test_call_batch_partial_gateway_failure_isolated():
    """transmit_batch contract: a gateway slice that fails transport
    fails ONLY its own items' awaitables; slices already delivered to
    healthy gateways complete normally (no unregistered-callback drops,
    no hangs)."""
    from orleans_tpu.core.errors import SiloUnavailableError
    from orleans_tpu.runtime.cluster import InProcFabric

    fabric = InProcFabric()
    silos = []
    for i in range(2):
        s = (SiloBuilder().with_name(f"gw{i}").with_fabric(fabric)
             .add_grains(EchoGrain).build())
        await s.start()
        silos.append(s)
    client = await ClusterClient(fabric).connect()
    client.hot_lane_enabled = False  # force the transmit_batch path
    try:
        down = silos[1].silo_address
        orig = fabric.deliver_via_gateway_batch

        def flaky(gw, msgs, _orig=orig, _down=down):
            if gw == _down:
                raise SiloUnavailableError("gateway down mid-batch")
            _orig(gw, msgs)

        fabric.deliver_via_gateway_batch = flaky
        futs = client.call_batch(EchoGrain, "ping",
                                 [(k, {"x": k}) for k in range(16)])
        results = await asyncio.wait_for(
            asyncio.gather(*futs, return_exceptions=True), 10.0)
        ok = [r for r in results if isinstance(r, int)]
        bad = [r for r in results if isinstance(r, SiloUnavailableError)]
        assert len(ok) + len(bad) == 16
        assert ok, "healthy gateway's slice should have completed"
        assert bad, "failed gateway's slice should carry the error"
        assert not client.callbacks, "no orphaned callbacks"
    finally:
        fabric.deliver_via_gateway_batch = orig
        await client.close_async()
        for s in silos:
            await s.stop()


# ---------------------------------------------------------------------------
# the bounded, completion-driven hand-off (ISSUE 29)
# ---------------------------------------------------------------------------

def _bare_rt() -> VectorRuntime:
    return VectorRuntime(mesh=make_mesh(1), capacity_per_shard=256)


def _spy(rt: VectorRuntime) -> tuple[list, list]:
    """Record every job handed to the worker and every ``_tick``
    callback the loop runs."""
    jobs, ticks = [], []
    submit, tick = rt._submit_job, rt._tick

    def submit_spy(job):
        jobs.append(job)
        submit(job)

    def tick_spy():
        ticks.append(rt.ticks)
        tick()

    rt._submit_job, rt._tick = submit_spy, tick_spy
    return jobs, ticks


async def _spin(n: int = 30) -> None:
    for _ in range(n):
        await asyncio.sleep(0)


def _enqueue(rt: VectorRuntime, entry: str, key: int, x: float):
    if entry == "call":
        return rt.call(CounterVec, key, "add", x=x)
    if entry == "call_group":
        return rt.call_group(CounterVec, "add", [(key, {"x": x}, True)])[0]
    return rt.call_packed(CounterVec, "add", [key], {"x": [x]}, [True])[0]


@pytest.mark.parametrize("entry,n", [("call", 8), ("call", 40),
                                     ("call_group", 24),
                                     ("call_packed", 24)])
async def test_held_calls_coalesce(entry, n):
    """N single calls over N loop iterations while the worker is held:
    DEPTH jobs of one call reach the worker, the other N - DEPTH wait in
    ``pending`` and ride ONE job when the first completes — DEPTH + 1
    jobs, not N — and every answer is the key's plain total (one
    ``add`` of k + 1 on a fresh key k)."""
    want = [float(k + 1) for k in range(n)]

    rt = _bare_rt()
    try:
        await rt.call(CounterVec, 999, "add", x=0.0)  # worker up, compiled
        jobs, _ticks = _spy(rt)
        with rt.tick_fence():
            futs = []
            for k in range(n):
                futs.append(_enqueue(rt, entry, k, float(k + 1)))
                await asyncio.sleep(0)
            await _spin()
            assert [len(j.ready) for j in jobs] == [1] * DEPTH
            assert len(rt.pending[(CounterVec, "add")]) == n - DEPTH
            assert rt.queue_depth() == n
        got = [float(v) for v in await asyncio.gather(*futs)]
        assert got == want
        assert [len(j.ready) for j in jobs] == [1] * DEPTH + [n - DEPTH]
        assert not rt.pending and not rt._inflight_groups
        assert rt._inflight == 0
    finally:
        rt.shutdown_worker()


@pytest.mark.parametrize("raises", [False, True],
                         ids=["completes", "raises"])
async def test_completion_rearms_held_group(raises):
    """A held group costs the loop nothing while it waits — ``_tick``
    does not reschedule itself and ``rt.ticks`` stands still — and the
    completion of one of its jobs claims it, also when that batch
    raised (its callers get the error, the held ones their answers)."""
    rt = _bare_rt()
    try:
        await rt.call(CounterVec, 999, "add", x=0.0)
        jobs, ticks = _spy(rt)
        if raises:
            execute = rt._execute_batch

            def failing(cls, method, ready, *a, **kw):
                if ready[0].key_hash == 0:
                    raise RuntimeError("boom")
                return execute(cls, method, ready, *a, **kw)

            rt._execute_batch = failing
        with rt.tick_fence():
            futs = []
            for k in range(6):
                futs.append(rt.call(CounterVec, k, "add", x=1.0))
                await asyncio.sleep(0)
            await _spin()
            n_ticks, rt_ticks = len(ticks), rt.ticks
            assert len(jobs) == DEPTH
            await _spin(50)
            # nothing was enqueued, nothing completed: no callback ran
            assert len(ticks) == n_ticks and rt.ticks == rt_ticks
            # an enqueue still schedules one pass; it claims nothing and
            # does not count as a tick
            futs.append(rt.call(CounterVec, 6, "add", x=1.0))
            await _spin(50)
            assert len(ticks) == n_ticks + 1 and rt.ticks == rt_ticks
            assert len(jobs) == DEPTH
        out = await asyncio.gather(*futs, return_exceptions=True)
        if raises:
            assert isinstance(out[0], RuntimeError)
            out = out[1:]
        assert [float(v) for v in out] == [1.0] * len(out)
        assert len(jobs) == DEPTH + 1
        assert len(jobs[DEPTH].ready) == 7 - DEPTH
        assert not rt.pending and not rt._inflight_groups
        assert rt._inflight == 0 and rt._quiesced.is_set()
    finally:
        rt.shutdown_worker()


@pytest.mark.parametrize("burst", [1, 3, 8])
async def test_per_key_order_across_deferral_and_hold(burst):
    """One key's calls, sent in bursts (the burst's later calls are
    conflict-deferred) while the group is held and after: replies come in
    send order with totals 1..n, and a read enqueued after an update's
    future resolved sees that update."""
    rt = _bare_rt()
    try:
        await rt.call(CounterVec, 999, "add", x=0.0)
        jobs, _ticks = _spy(rt)
        order: list[int] = []
        futs = []

        def send(i: int, key: int = 7):
            f = rt.call(CounterVec, key, "add", x=1.0)
            f.add_done_callback(lambda _f, i=i: order.append(i))
            futs.append(f)

        n = 0
        with rt.tick_fence():
            for _ in range(4):
                for _ in range(burst):
                    send(n)
                    n += 1
                # other keys keep the group's place at the worker taken
                rt.call(CounterVec, 100 + n, "add", x=1.0)
                await asyncio.sleep(0)
            await _spin()
            assert len(jobs) == DEPTH
            assert rt._inflight_groups == {(CounterVec, "add"): DEPTH}
        for _ in range(burst):  # and a burst while the backlog drains
            send(n)
            n += 1
        got = [float(v) for v in await asyncio.gather(*futs)]
        assert got == [float(i + 1) for i in range(n)]
        assert order == list(range(n))
        # one message per key per tick held throughout
        for j in jobs:
            keys = [p.key_hash for p in j.ready]
            assert len(keys) == len(set(keys))
        last = await rt.call(CounterVec, 7, "add", x=1.0)
        assert float(await rt.call(CounterVec, 7, "read")) == float(last)
        await rt.flush()
        assert not rt.pending and not rt._inflight_groups
    finally:
        rt.shutdown_worker()


@pytest.mark.parametrize("drain", ["flush", "shutdown_worker", "gather"])
async def test_held_groups_drain(drain):
    """Held calls are pending work like any other: ``flush()`` returns
    only after they ran; ``shutdown_worker()`` ends the thread after the
    jobs it holds and the held calls start a fresh one; the migration
    fence (``pending_key_hashes``) covers held keys all along."""
    rt = _bare_rt()
    try:
        await rt.call(CounterVec, 999, "add", x=0.0)
        first = rt._worker
        with rt.tick_fence():
            futs = []
            for k in range(10):
                futs.append(rt.call(CounterVec, k, "add", x=2.0))
                await asyncio.sleep(0)
            await _spin()
            assert len(rt.pending[(CounterVec, "add")]) == 10 - DEPTH
            assert set(range(10)) <= rt.pending_key_hashes(CounterVec)
        if drain == "flush":
            await rt.flush()
            assert all(f.done() for f in futs)
        elif drain == "shutdown_worker":
            rt.shutdown_worker()
            assert rt._worker is None and not first.is_alive()
            # the jobs it held ran; their completions wait for the loop
            assert len(rt.pending[(CounterVec, "add")]) == 10 - DEPTH
            assert set(range(DEPTH, 10)) <= rt.pending_key_hashes(CounterVec)
        out = await asyncio.wait_for(asyncio.gather(*futs), 30.0)
        assert [float(v) for v in out] == [2.0] * 10
        await rt.flush()
        assert not rt.pending and not rt._inflight_groups
        assert not rt.pending_key_hashes(CounterVec) & set(range(10))
        assert rt._inflight == 0 and rt.queue_depth() == 0
    finally:
        rt.shutdown_worker()


@pytest.mark.parametrize("other", ["read", "add_other_class"])
async def test_busy_group_does_not_hold_another(other):
    """The bound is per (class, method): while one group is held, a call
    of another method — or of another class — goes straight to the
    worker."""
    class OtherVec(CounterVec):
        pass

    rt = _bare_rt()
    try:
        await rt.call(CounterVec, 999, "add", x=0.0)
        jobs, _ticks = _spy(rt)
        with rt.tick_fence():
            futs = []
            for k in range(5):
                futs.append(rt.call(CounterVec, k, "add", x=1.0))
                await asyncio.sleep(0)
            await _spin()
            assert len(jobs) == DEPTH
            if other == "read":
                group = (CounterVec, "read")
                futs.append(rt.call(CounterVec, 50, "read"))
            else:
                group = (OtherVec, "add")
                futs.append(rt.call(OtherVec, 50, "add", x=1.0))
            await _spin()
            assert len(jobs) == DEPTH + 1
            assert (jobs[DEPTH].cls, jobs[DEPTH].method) == group
            assert rt._inflight_groups == {(CounterVec, "add"): DEPTH,
                                           group: 1}
            assert len(rt.pending[(CounterVec, "add")]) == 5 - DEPTH
            assert group not in rt.pending
        await asyncio.gather(*futs)
        assert not rt.pending and not rt._inflight_groups
    finally:
        rt.shutdown_worker()


@pytest.mark.parametrize("hold", [False, True], ids=["no_hold", "hold"])
async def test_engine_held_counter(hold):
    """``engine.held`` counts a message at its first hold, once however
    many passes find it held, and reads 0 — not absent — where nothing
    was held."""
    b = (SiloBuilder().with_name(f"ot-held-{hold}").add_grains(EchoGrain)
         .with_config(metrics_enabled=True))
    add_vector_grains(b, CounterVec, mesh=make_mesh(1),
                      dense={CounterVec: 64})
    silo = b.build()
    await silo.start()
    try:
        rt = silo.vector
        for k in range(4):  # one call in flight at a time: never held
            await rt.call(CounterVec, k, "add", x=1.0)
        counters = silo.stats.counters
        assert counters["engine.held"] == 0
        if hold:
            with rt.tick_fence():
                futs = []
                for k in range(12):
                    futs.append(rt.call(CounterVec, k, "add", x=1.0))
                    await asyncio.sleep(0)
                await _spin()
                # each pass found the earlier ones held again
                assert counters["engine.held"] == 12 - DEPTH
            await asyncio.gather(*futs)
            await rt.flush()
            assert counters["engine.held"] == 12 - DEPTH
            assert counters["ingest.messages"] == 16
    finally:
        await silo.stop()
