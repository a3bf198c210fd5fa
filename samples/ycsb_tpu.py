"""A session store on the device tier: YCSB's ``usertable`` record as a
VectorGrain (Cooper et al., "Benchmarking Cloud Serving Systems with YCSB",
SoCC'10; core workload A: ``fieldcount`` 10 x ``fieldlength`` 100 B, half
reads, half single-field updates, a few users far hotter than the rest).

One record is one device row: ``fields`` u8[1024] — the ten 100-byte
fields back to back and 24 bytes of zero padding — and ``ver`` i32, the
count of updates applied. The width is the TPU's doing: the table holds a
leaf as ``[shards, rows, *shape]``, and for one shard the chip's default
layout of ``[1, 2^20+1, 1000]`` u8 (or ``[.., 250]`` i32) is not row-major,
so that every tick would copy the whole table into a row-major layout and
back; ``[.., 1024]`` u8 is ``{2,1,0:T(8,128)(4,1)}`` and the tick gathers
and scatters in place (compiled for a described v5e:
``tests/test_chip_compile.py``).

* ``initial_state`` is the load phase done by provisioning: word ``w`` of
  record ``k`` is a counter-based hash of (``DATA_SEED``, k, w) in uint32
  arithmetic — no table is loaded, a row is derived at its first touch.
* ``update(field, value)`` takes its 100 bytes as ``bytes`` (staged with
  one ``np.frombuffer``), overwrites that field, adds one to ``ver`` and
  answers it; a ``field`` outside 0..9 writes nothing.
* ``read()`` is read-only: it answers ``(ver, the 1,000 bytes)``, leaves
  nothing dirty, and a record that was only ever read is never written
  behind.

The benchmark keeps its own copy (``chipbench/apps/ycsb.py``), so the
sample may change without moving the yardstick; ``tests/test_ycsb.py``
holds the two to the same bytes.

Run: python samples/ycsb_tpu.py   (set JAX_PLATFORMS=cpu to keep it off the
chip — the first line it prints names the device)
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from orleans_tpu.dispatch import VectorGrain, actor_method, add_vector_grains
from orleans_tpu.runtime import ClusterClient, SiloBuilder
from orleans_tpu.storage import MemoryStorage

N_RECORDS = 4096
FIELD_COUNT = 10
FIELD_BYTES = 100
RECORD_BYTES = FIELD_COUNT * FIELD_BYTES
RECORD_WORDS = RECORD_BYTES // 4
ROW_BYTES = 1024   # the record and 24 bytes of padding
DATA_SEED = 1498633025


def initial_words(xp, seed: int, keys, words):
    """uint32 contents of word ``words`` of record ``keys`` (broadcast
    against each other), for ``xp`` = numpy or jax.numpy: three odd
    multipliers, then murmur3's 32-bit finaliser."""
    u = xp.uint32
    h = (keys.astype(u) * u(0x9E3779B1)) ^ (words.astype(u) * u(0x85EBCA77)) \
        ^ u((seed * 0xC2B2AE3D) & 0xFFFFFFFF)
    h = (h ^ (h >> u(16))) * u(0x85EBCA6B)
    h = (h ^ (h >> u(13))) * u(0xC2B2AE35)
    return h ^ (h >> u(16))


class RecordVectorGrain(VectorGrain):
    STATE = {
        "fields": (jnp.uint8, (ROW_BYTES,)),
        "ver": (jnp.int32, ()),
    }

    @staticmethod
    def initial_state(key_hash):
        words = initial_words(jnp, DATA_SEED, key_hash,
                              jnp.arange(RECORD_WORDS, dtype=jnp.uint32))
        # little-endian bytes of each word, by arithmetic
        octets = (words[:, None] >> jnp.arange(0, 32, 8, dtype=jnp.uint32)
                  ) & jnp.uint32(0xFF)
        return {"fields": jnp.pad(octets.astype(jnp.uint8).reshape(-1),
                                  (0, ROW_BYTES - RECORD_BYTES)),
                "ver": jnp.int32(0)}

    @actor_method(args={"field": (jnp.int32, ()),
                        "value": (jnp.uint8, (FIELD_BYTES,))})
    def update(state, args):
        field = args["field"]
        ok = (field >= 0) & (field < FIELD_COUNT)
        written = jax.lax.dynamic_update_slice(
            state["fields"], args["value"],
            (jnp.clip(field, 0, FIELD_COUNT - 1) * FIELD_BYTES,))
        ver = state["ver"] + ok.astype(jnp.int32)
        return {"fields": jnp.where(ok, written, state["fields"]),
                "ver": ver}, ver

    @actor_method(args={}, read_only=True)
    def read(state, args):
        return state, (state["ver"], state["fields"][:RECORD_BYTES])


async def main() -> None:
    storage = MemoryStorage()
    b = SiloBuilder().with_name("ycsb-tpu")
    add_vector_grains(b, RecordVectorGrain,
                      dense={RecordVectorGrain: N_RECORDS},
                      capacity_per_shard=N_RECORDS,
                      storage=storage, flush_period=0.1)
    silo = b.build()
    await silo.start()
    print(f"device: {jax.devices()[0].platform} "
          f"({jax.devices()[0].device_kind}) x {len(jax.devices())}")
    client = await ClusterClient(silo.fabric).connect()

    # a hot user and a tail: reads and single-field updates, concurrently
    rng = np.random.default_rng(0)
    hot, ops = 7, 200
    keys = np.where(rng.random(ops) < 0.2, hot,
                    rng.integers(0, N_RECORDS, ops)).tolist()
    writes = (rng.random(ops) < 0.5).tolist()
    replies = await asyncio.gather(*(
        client.get_grain(RecordVectorGrain, k).update(
            field=int(rng.integers(FIELD_COUNT)),
            value=rng.integers(0, 256, FIELD_BYTES, np.uint8).tobytes())
        if w else client.get_grain(RecordVectorGrain, k).read()
        for k, w in zip(keys, writes)))
    hot_writes = sum(w and k == hot for k, w in zip(keys, writes))
    ver, record = await client.get_grain(RecordVectorGrain, hot).read()
    print(f"{ops} operations, {sum(writes)} updates; user {hot}: "
          f"{hot_writes} updates, ver {int(ver)}, "
          f"{np.asarray(record).nbytes} B a read; "
          f"{silo.vector.conflicts_deferred} same-record calls deferred "
          f"to a later tick")
    assert int(ver) == hot_writes and len(replies) == ops

    await client.close_async()
    await silo.stop()   # the final write-behind flush happens here
    written = {k for k, w in zip(keys, writes) if w}
    print(f"{len(storage._data)} records in storage "
          f"({len(written)} were updated; a record only read is not stored)")
    assert len(storage._data) == len(written)


if __name__ == "__main__":
    asyncio.run(main())
