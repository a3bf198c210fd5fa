"""Presence on the device tier — the north-star configuration, end to end.

The reference Presence sample (/root/reference/Samples/Presence/: PlayerGrain
heartbeats fan into GameGrain summaries) re-expressed two-tier:

* PlayerGrain is a **VectorGrain**: 100k concurrent players live as rows of
  a sharded device table; heartbeat waves arrive as bulk batches and run as
  ONE kernel per tick (the bulk path; `chip_smoke.py` drives it at 1M
  players on the chip).
* GameGrain stays a **host grain**: low-rate queries, arbitrary Python.
  Game summaries are computed from the device table with an MXU segment
  reduction (ops.segment_sum) — the fan-in without 100k messages.
* Individual player queries go through the ordinary client surface —
  `client.get_grain(PlayerVectorGrain, k).whereis()` — and coalesce into
  ticks with everyone else's.
* Write-behind persistence keeps per-player state durable (MemoryStorage
  here; any GrainStorage provider works).

Run: python samples/presence_tpu.py   (on whatever backend jax selects; set
JAX_PLATFORMS=cpu to keep it off the chip — the first lines it prints name
the device)
"""

import asyncio
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from orleans_tpu.dispatch import (
    VectorGrain,
    actor_method,
    add_vector_grains,
)
from orleans_tpu.ops import segment_sum_onehot
from orleans_tpu.runtime import ClusterClient, Grain, SiloBuilder
from orleans_tpu.storage import MemoryStorage

N_PLAYERS = 100_000
N_GAMES = 64


class PlayerVectorGrain(VectorGrain):
    """PlayerGrain (Samples/Presence/Grains/PlayerGrain.cs:14), vectorized:
    heartbeat updates position/score; game id fixed at activation."""

    STATE = {
        "pos": (jnp.float32, (2,)),
        "score": (jnp.int32, ()),
        "game": (jnp.int32, ()),
    }

    @staticmethod
    def initial_state(key_hash):
        return {"pos": jnp.zeros(2, jnp.float32), "score": jnp.int32(0),
                "game": key_hash % N_GAMES}

    @actor_method(args={"pos": (jnp.float16, (2,)), "delta": (jnp.int32, ())})
    def heartbeat(state, args):
        new = {"pos": args["pos"].astype(jnp.float32),
               "score": state["score"] + args["delta"],
               "game": state["game"]}
        return new, new["score"]

    @actor_method(args={}, read_only=True)
    def whereis(state, args):
        return state, state["pos"]


class GameGrain(Grain):
    """GameGrain (host tier): summarizes its players from the device table
    — one MXU reduction instead of one message per player."""

    async def summary(self) -> dict:
        rt = self.runtime.vector
        tbl = rt.table(PlayerVectorGrain)
        game = int(self.primary_key)
        per = tbl.dense_per_shard
        with rt.tick_fence():
            # never slice state an off-loop tick has donated mid-flight
            games = tbl.state["game"][:, :per].reshape(-1)
            scores = tbl.state["score"][:, :per].reshape(-1)
        # rows in key order; only players that have been activated count
        # (out-of-range segment ids contribute nothing)
        live = np.zeros(games.shape[0], bool)
        live[:tbl.dense_n] = tbl.dense_active
        live = jnp.asarray(live)
        seg = jnp.where(live, games, N_GAMES)
        totals = segment_sum_onehot(scores.astype(jnp.float32), seg, N_GAMES)
        members = segment_sum_onehot(live.astype(jnp.float32), seg, N_GAMES)
        return {"game": game,
                "total_score": int(totals[game]),
                "players": int(members[game])}


async def main() -> None:
    storage = MemoryStorage()
    b = SiloBuilder().with_name("presence-tpu").add_grains(GameGrain)
    add_vector_grains(b, PlayerVectorGrain,
                      dense={PlayerVectorGrain: N_PLAYERS},
                      capacity_per_shard=N_PLAYERS,
                      storage=storage, flush_period=0.5)
    silo = b.build()
    await silo.start()
    print(f"device: {jax.devices()[0].platform} "
          f"({jax.devices()[0].device_kind}) x {len(jax.devices())}")
    client = await ClusterClient(silo.fabric).connect()

    # --- bulk heartbeat waves: the device-tier hot path ------------------
    rt = silo.vector
    keys = np.arange(N_PLAYERS)
    rng = np.random.default_rng(0)
    plan = rt.make_dense_plan(PlayerVectorGrain, keys)
    t0 = time.perf_counter()
    waves = 5
    for w in range(waves):
        rt.call_batch(
            PlayerVectorGrain, "heartbeat", keys,
            {"pos": rng.random((N_PLAYERS, 2), np.float32).astype(np.float16),
             "delta": np.ones(N_PLAYERS, np.int32)},
            plan=plan)
    dt = time.perf_counter() - t0
    print(f"{waves} heartbeat waves x {N_PLAYERS:,} players = "
          f"{waves * N_PLAYERS / dt:,.0f} msgs/sec")

    # --- individual player call through the ordinary client surface ------
    pos = await client.get_grain(PlayerVectorGrain, 42).whereis()
    print(f"player 42 is at {np.round(np.asarray(pos), 3)}")

    # --- host-tier fan-in summary ----------------------------------------
    s = await client.get_grain(GameGrain, 7).summary()
    print(f"game 7: {s['players']:,} players, total score "
          f"{s['total_score']:,} (expect score == players x {waves})")
    assert s["total_score"] == s["players"] * waves

    await client.close_async()
    await silo.stop()   # final write-behind flush happens here


if __name__ == "__main__":
    asyncio.run(main())
