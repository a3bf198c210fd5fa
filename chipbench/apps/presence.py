"""The Presence deployment's grain classes — a copy of
``samples/presence_tpu.py``'s ``PlayerVectorGrain`` (Orleans
Samples/Presence PlayerGrain.cs:14), kept with the benchmark so the sample
may change without moving the yardstick. Row: pos f32x2, score i32,
game i32 (16 B); ``heartbeat(pos f16x2, delta i32)`` answers the new score.
"""

import jax.numpy as jnp

from orleans_tpu.dispatch import VectorGrain, actor_method

N_GAMES = 64


class PlayerVectorGrain(VectorGrain):
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


GRAINS = {"PlayerVectorGrain": PlayerVectorGrain}
