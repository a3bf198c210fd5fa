"""Host-loop occupancy profiler + flight recorder
(observability.profiling.LoopProfiler): category attribution under
concurrent turns and device ticks, anomaly-triggered snapshots, the
management surface, and the disabled-installs-nothing contract."""

import asyncio

import numpy as np
import pytest

from orleans_tpu.observability.profiling import (
    LOOP_CATEGORIES,
    LOOP_CATEGORY,
    LoopProfiler,
    install_loop_profiler,
    loop_profiler,
    uninstall_loop_profiler,
)
from orleans_tpu.config import LoadSheddingOptions, ProfilingOptions
from orleans_tpu.runtime import ClusterClient, Grain, SiloBuilder


class EchoGrain(Grain):
    async def ping(self, x: int) -> int:
        return x


def _make_vector_grain():
    import jax.numpy as jnp

    from orleans_tpu.dispatch import VectorGrain, actor_method

    class EchoVec(VectorGrain):
        STATE = {"pings": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"pings": jnp.int32(0)}

        @actor_method(args={"x": (jnp.int32, ())})
        def ping(state, args):
            return {"pings": state["pings"] + 1}, args["x"]

    return EchoVec


# ---------------------------------------------------------------------------
# LoopProfiler unit mechanics (wrapped callbacks are directly callable)
# ---------------------------------------------------------------------------

def test_profiler_attribution_and_windows():
    prof = LoopProfiler(window=0.0)  # finalize a window per callback

    def work():
        prof.set_category("turns")
        t = __import__("time").perf_counter() + 0.002
        while __import__("time").perf_counter() < t:
            pass

    prof._wrap(work)()
    assert prof.totals.get("turns", 0.0) > 0.0
    assert prof.ring, "window did not finalize"
    sl = prof.ring[-1]
    assert abs(sum(sl["shares"].values()) - 1.0) < 0.05
    assert sl["top"], "top-K empty"
    # idle accrues between callbacks
    __import__("time").sleep(0.005)
    prof._wrap(lambda: None)()
    assert prof.totals.get("idle", 0.0) > 0.0
    occ = prof.occupancy()
    assert abs(sum(occ.values()) - 1.0) < 1e-6


def test_top_records_carry_within_window_offsets():
    """ISSUE 13 satellite: every top-K record stamps its callback's
    start offset within the window (both the pure-Python reference here
    and the native runner below), so the Perfetto flame row places
    records exactly instead of end-to-end from the window start."""
    import time as _t
    prof = LoopProfiler(window=60.0)
    run = prof._wrap

    def spin(ms):
        end = _t.perf_counter() + ms / 1e3
        while _t.perf_counter() < end:
            pass

    run(lambda: spin(2))()
    _t.sleep(0.01)  # real gap: the second record's offset must see it
    run(lambda: spin(2))()
    prof._finalize_window(_t.perf_counter())
    top = prof.ring[-1]["top"]
    assert len(top) == 2
    offs = sorted(r["offset"] for r in top)
    assert all(o is not None and o >= 0.0 for o in offs)
    # the second callback started after the first one's 2ms + the 10ms
    # sleep — its offset reflects WHERE it ran, not a cursor sum
    assert offs[1] - offs[0] >= 0.010
    # offsets sit inside the window's wall
    assert offs[1] <= prof.ring[-1]["wall_s"]


def test_native_runner_stamps_offsets():
    """The C hot path (hotloop.c) stamps the same offsets as the Python
    reference; skipped where the toolchain is unavailable."""
    import time as _t

    from orleans_tpu.observability import profiling
    if profiling._hotloop is None:
        import pytest
        pytest.skip("native hotloop unavailable")
    loop = asyncio.new_event_loop()
    try:
        prof = install_loop_profiler(loop, window=60.0)
        assert type(prof) is not LoopProfiler

        def spin():
            end = _t.perf_counter() + 0.002
            while _t.perf_counter() < end:
                pass

        def done():
            loop.stop()

        loop.call_soon(spin)
        loop.call_later(0.02, spin)
        loop.call_later(0.04, done)
        loop.run_forever()
        prof._finalize_window(_t.perf_counter())
        top = [r for r in prof.ring[-1]["top"] if r["seconds"] >= 0.002]
        assert len(top) >= 2
        offs = sorted(r["offset"] for r in top)
        assert all(o is not None and o >= 0.0 for o in offs)
        assert offs[1] - offs[0] >= 0.015  # the call_later gap is real
    finally:
        uninstall_loop_profiler(loop)
        loop.close()


def test_profiler_enter_exit_restores_category():
    prof = LoopProfiler(window=60.0)

    def work():
        assert LOOP_CATEGORY.get() == "other"
        tok = prof.enter("storage")
        assert LOOP_CATEGORY.get() == "storage"
        prof.exit(tok)
        assert LOOP_CATEGORY.get() == "other"

    prof._wrap(work)()
    prof._flush()  # outside a callback: must be a no-op, not a crash
    # the hot path folds into totals only at window boundaries; the
    # cumulative read merges the open window
    assert "storage" in prof._cumulative()
    assert "storage" not in prof.totals  # window (60s) never finalized


def test_trigger_rate_limit_and_hooks():
    prof = LoopProfiler(window=60.0, trigger_interval=60.0)
    seen = []
    prof.trigger_hooks.append(seen.append)
    snap = prof.trigger("load_shed", queue_depth=7)
    assert snap is not None and snap["reason"] == "load_shed"
    assert snap["attrs"] == {"queue_depth": 7}
    assert prof.trigger("load_shed") is None  # rate-limited
    assert prof.trigger_counts["load_shed"] == 2  # still counted
    assert len(prof.snapshots) == 1 and len(seen) == 1


def test_pure_python_fallback_matches_native_semantics(monkeypatch):
    """Without the native runner (no toolchain / ORLEANS_TPU_NATIVE=0)
    install falls back to the pure-Python hot path with identical
    semantics — attribution, idle accounting, nesting, uninstall
    passthrough."""
    from orleans_tpu.observability import profiling

    monkeypatch.setattr(profiling, "_hotloop", None)
    loop = asyncio.new_event_loop()
    try:
        prof = install_loop_profiler(loop, window=0.0)
        assert type(prof) is LoopProfiler  # not the native subclass

        def work():
            prof.set_category("turns")
            t = __import__("time").perf_counter() + 0.002
            while __import__("time").perf_counter() < t:
                pass
            loop.stop()

        loop.call_soon(work)
        loop.run_forever()
        assert prof.totals.get("turns", 0.0) > 0.0
        occ = prof.occupancy()
        assert abs(sum(occ.values()) - 1.0) < 1e-6
        uninstall_loop_profiler(loop)
        assert prof.closed and "call_soon" not in loop.__dict__
    finally:
        loop.close()


def test_install_refcount_and_uninstall():
    loop = asyncio.new_event_loop()
    try:
        p1 = install_loop_profiler(loop, window=60.0)
        p2 = install_loop_profiler(loop)
        assert p1 is p2 is loop_profiler(loop)
        assert "call_soon" in loop.__dict__
        uninstall_loop_profiler(loop)
        assert loop_profiler(loop) is p1  # one ref still holds
        uninstall_loop_profiler(loop)
        assert loop_profiler(loop) is None
        assert "call_soon" not in loop.__dict__
        assert p1.closed
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# Silo integration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("offloop", [True, False],
                         ids=["worker", "lever-off"])
async def test_occupancy_under_concurrent_turns_and_ticks(offloop):
    """Concurrent host turns + device ticks attribute into their own
    categories and shares sum to ~1.0 of loop wall (incl. idle). The
    tick's loop side — the claim and the completion — books to
    ``tick_schedule``; staging, transfer, dispatch and sync run on the
    tick worker and are no loop time, so no other ``tick_*`` category
    exists (with ``offloop_tick=False`` the job runs inside the claim's
    callback and books there too)."""
    from orleans_tpu.dispatch import add_vector_grains
    from orleans_tpu.parallel import make_mesh

    EchoVec = _make_vector_grain()
    b = (SiloBuilder().with_name("prof-silo").add_grains(EchoGrain)
         .with_config(offloop_tick=offloop)
         .with_options(ProfilingOptions(enabled=True, window=0.05)))
    add_vector_grains(b, EchoVec, mesh=make_mesh(1), dense={EchoVec: 32})
    silo = b.build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    client.hot_lane_enabled = False  # force full messaging turns
    try:
        host = [client.get_grain(EchoGrain, k) for k in range(16)]
        vec = [silo.vector.actor(EchoVec, k) for k in range(16)]

        async def host_load():
            for i in range(120):
                await host[i % 16].ping(i)

        async def vec_load():
            for i in range(120):
                await vec[i % 16].ping(x=np.int32(i))

        await asyncio.gather(host_load(), vec_load(),
                             host_load(), vec_load())
        prof = silo.loop_prof.profile()
        shares = prof["shares"]
        assert abs(sum(shares.values()) - 1.0) < 0.02, shares
        assert prof["seconds"].get("turns", 0.0) > 0.0
        assert prof["seconds"].get("tick_schedule", 0.0) > 0.0, prof
        assert [c for c in prof["seconds"] if c.startswith("tick_")] \
            == ["tick_schedule"], prof["seconds"]
        assert [c for c in LOOP_CATEGORIES if c.startswith("tick_")] \
            == ["tick_schedule"]
        assert prof["windows"], "no occupancy slices collected"
        # per-category occupancy gauges registered and live
        snap = silo.stats.snapshot()
        assert "loop.occupancy.turns" in snap["gauges"]
    finally:
        await client.close_async()
        await silo.stop()
    # teardown removed the interposition
    assert "call_soon" not in asyncio.get_running_loop().__dict__


async def test_flight_recorder_on_forced_shed_via_management():
    """A forced shed event snapshots the flight recorder; the snapshot is
    retrievable through ManagementGrain.get_cluster_loop_profile."""
    from orleans_tpu.management import add_management
    from orleans_tpu.management.grain import ManagementGrain

    b = (SiloBuilder().with_name("prof-shed").add_grains(EchoGrain)
         .with_options(LoadSheddingOptions(enabled=True, limit=2),
                       ProfilingOptions(enabled=True, window=0.05,
                                        trigger_interval=0.01)))
    add_management(b)
    silo = b.build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        # burst without yielding: the application queue backs past the
        # limit before any pump runs (test_load_shedding pattern)
        futs = [asyncio.ensure_future(
            client.get_grain(EchoGrain, k).ping(k)) for k in range(20)]
        await asyncio.wait_for(asyncio.gather(*futs), timeout=10.0)
        assert silo.stats.get("messaging.gateway.shed") > 0
        lp = silo.loop_prof
        assert lp.snapshots, "shed did not trigger a flight snapshot"
        snap = lp.snapshots[0]
        assert snap["reason"] in ("load_shed", "queue_wait_trend")
        assert "queue_depth" in snap["attrs"]
        # retrievable cluster-wide through the management grain
        mg = client.get_grain(ManagementGrain, 0)
        prof = await mg.get_cluster_loop_profile()
        assert prof["snapshot_count"] >= 1
        per = list(prof["per_silo"].values())[0]
        assert per["snapshots"][0]["reason"] == snap["reason"]
        # pid labels (ISSUE 20): under worker processes several silos'
        # recorders feed one cluster view — every payload and snapshot
        # names the process it was captured in
        import os
        assert per["pid"] == os.getpid()
        assert per["snapshots"][0]["pid"] == os.getpid()
        assert abs(sum(prof["shares"].values()) - 1.0) < 0.02
    finally:
        await client.close_async()
        await silo.stop()


async def test_profiling_disabled_installs_nothing():
    """The off path is structurally zero-overhead: no interposition on
    the loop, no profiler object, one None on the silo."""
    silo = SiloBuilder().with_name("noprof").add_grains(EchoGrain).build()
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    try:
        loop = asyncio.get_running_loop()
        assert silo.loop_prof is None
        assert silo.dispatcher._loop_prof is None
        assert "call_soon" not in loop.__dict__
        assert "call_at" not in loop.__dict__
        assert await client.get_grain(EchoGrain, 1).ping(1) == 1
        # and the management surface answers {} rather than erroring
        assert await silo.silo_control.ctl_loop_profile() == {} \
            if hasattr(silo, "silo_control") else True
    finally:
        await client.close_async()
        await silo.stop()


async def test_slow_turn_lands_in_top_k_with_label():
    """A deliberately slow turn shows up in the window's top-K with its
    grain-class/method label — the flight recorder's 'what was that
    spike' answer."""

    class SlowGrain(Grain):
        async def crunch(self) -> int:
            t = asyncio.get_event_loop().time() + 0.02
            while asyncio.get_event_loop().time() < t:
                pass  # hog the loop synchronously
            return 1

    silo = (SiloBuilder().with_name("prof-slow")
            .add_grains(SlowGrain)
            .with_config(profiling_enabled=True, profiling_window=60.0,
                         hot_lane_enabled=False)
            .build())
    await silo.start()
    client = await ClusterClient(silo.fabric).connect()
    client.hot_lane_enabled = False
    try:
        assert await client.get_grain(SlowGrain, 1).crunch() == 1
        lp = silo.loop_prof
        lp._flush()
        labels = [lb if isinstance(lb, str) else ".".join(map(str, lb))
                  for _, _, lb, _off in lp._win_top]
        assert any("SlowGrain.crunch" in lb for lb in labels), labels
    finally:
        await client.close_async()
        await silo.stop()
