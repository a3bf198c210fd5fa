"""System tests running the sample applications' grain logic over the
TestCluster harness (the reference's samples double as its system tests:
Presence fan-in, GPSTracker streams, Chirper fan-out — BASELINE.md PR1
configs)."""

import asyncio
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "samples"))

from chirper import ChirperAccount
from gpstracker import STREAM_NS, DeviceGrain, PushNotifierGrain
from presence import GameGrain, PlayerGrain

from orleans_tpu.testing import TestClusterBuilder


async def test_presence_heartbeat_fan_in():
    cluster = (TestClusterBuilder(3)
               .add_grains(PlayerGrain, GameGrain).build())
    async with cluster:
        players = [cluster.grain(PlayerGrain, k) for k in range(30)]
        await asyncio.gather(*(p.join_game(k % 4)
                               for k, p in enumerate(players)))
        for r in range(3):
            await asyncio.gather(*(
                p.heartbeat((float(k), float(r)), r)
                for k, p in enumerate(players)))
        for game in range(4):
            status = await cluster.grain(GameGrain, game).game_status()
            mine = [k for k in range(30) if k % 4 == game]
            assert sorted(status) == mine
            assert all(v["score"] == 2 for v in status.values())


async def test_presence_survives_silo_kill():
    cluster = (TestClusterBuilder(3)
               .add_grains(PlayerGrain, GameGrain).build())
    async with cluster:
        players = [cluster.grain(PlayerGrain, k) for k in range(12)]
        await asyncio.gather(*(p.join_game(0) for p in players))
        victim = cluster.alive_silos[-1]
        await cluster.kill_silo(victim)
        await cluster.wait_for_death(victim)
        # heartbeats keep flowing; players re-activate wherever needed.
        # Players that died with the silo lose their volatile _game field
        # (it is not persisted state) — they re-join, as devices re-register
        # in the reference sample.
        await asyncio.gather(*(p.join_game(0) for p in players))
        for r in range(2):
            await asyncio.gather(*(
                p.heartbeat((1.0, 2.0), r) for p in players))
        status = await cluster.grain(GameGrain, 0).game_status()
        assert sorted(status) == list(range(12))


async def test_gpstracker_stream_push():
    cluster = (TestClusterBuilder(2)
               .add_grains(DeviceGrain, PushNotifierGrain)
               .with_sms_streams("sms").build())
    async with cluster:
        for seq in range(3):
            await asyncio.gather(*(
                cluster.grain(DeviceGrain, d).process_message(
                    {"lat": 1.0, "lon": 2.0, "region": "sf", "seq": seq})
                for d in range(10)))
        batch = await cluster.grain(PushNotifierGrain, "sf").flush()
        assert len(batch) == 30
        assert {b["device"] for b in batch} == set(range(10))
        assert (await cluster.grain(DeviceGrain, 3).last_position())["seq"] == 2


async def test_presence_tpu_two_tier_sample():
    """samples/presence_tpu.py end to end with a small population."""
    import presence_tpu as pt

    pt.N_PLAYERS, pt.N_GAMES = 512, 8
    await pt.main()


async def test_chirper_fan_out_and_graph_updates():
    cluster = TestClusterBuilder(3).add_grains(ChirperAccount).build()
    async with cluster:
        star = cluster.grain(ChirperAccount, "star")
        followers = [cluster.grain(ChirperAccount, f"u{i}") for i in range(20)]
        await asyncio.gather(*(f.follow("star") for f in followers))
        assert await star.follower_count() == 20

        delivered = await star.publish_chirp("first!")
        assert delivered == 20
        for f in followers:
            tl = await f.timeline()
            assert tl == [{"author": "star", "text": "first!"}]

        await followers[0].unfollow("star")
        assert await star.follower_count() == 19
        delivered = await star.publish_chirp("second")
        assert delivered == 19
        assert len(await followers[0].timeline()) == 1  # no new delivery
        assert len(await followers[1].timeline()) == 2


async def test_telemetry_sample_end_to_end():
    """samples/telemetry.py: durable sqlite ingest, live + rewound
    dashboards (replay beyond the tiny cache window), mesh-replicated
    endpoint meters with collective read fan-in, custom wire codec."""
    import telemetry
    report = await telemetry.main(n_devices=20, rounds=3)
    assert report["replayed"] >= report["ingested"]
    assert sum(report["requests_by_endpoint"]) == report["ingested"]


async def test_bank_sample_end_to_end():
    """samples/bank.py: atomic audited transfers, over-draw rollback,
    cancellable sweep, batch audit ledger — run the sample's own main."""
    import bank
    await bank.main()


def test_chip_smoke_refuses_without_a_tpu():
    """``chip_smoke.py`` is the proof that a run was on the chip: on a
    machine where jax finds no TPU (these tests pin the CPU) it must exit
    non-zero and never print its verdict line."""
    import subprocess

    root = os.path.join(os.path.dirname(__file__), "..")
    r = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr
