"""Upstream's Ping benchmark grain (Orleans test/Benchmarks/Ping: EchoGrain)
as a device row, so that the cell drives the tick: state ``n`` i32,
``ping(x i32)`` answers ``x`` and writes nothing.
"""

import jax.numpy as jnp

from orleans_tpu.dispatch import VectorGrain, actor_method


class EchoVectorGrain(VectorGrain):
    STATE = {"n": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"n": jnp.int32(0)}

    @actor_method(args={"x": (jnp.int32, ())}, read_only=True)
    def ping(state, args):
        return state, args["x"]


GRAINS = {"EchoVectorGrain": EchoVectorGrain}
