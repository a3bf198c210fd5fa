"""The YCSB deployment's grain class: one record of YCSB's ``usertable``
(Cooper et al., SoCC'10; ``fieldcount`` 10 x ``fieldlength`` 100 B) as one
device row. Row: ``fields`` u8[1024] — the ten 100-byte fields back to
back and 24 bytes of zero padding — and ``ver`` i32, the count of updates
applied. The width is the TPU's doing: the table holds a leaf as
``[shards, rows, *shape]``, and for one shard the chip's default layout of
``[1, 2^20+1, 1000]`` u8 (or ``[.., 250]`` / ``[.., 256]`` i32) is not
row-major, so that every tick would copy the whole 1 GiB table into a
row-major layout and back; ``[.., 1024]`` u8 is ``{2,1,0:T(8,128)(4,1)}``
and the tick gathers and scatters in place (compiled for a described
v5e: ``tests/test_chip_compile.py``). A ``u8[10, 100]`` leaf would pad
to (32, 128) tiles, four times the bytes.

``initial_state`` is YCSB's load phase done by provisioning: word ``w`` of
record ``k`` is a counter-based hash of (data seed, k, w) in uint32
arithmetic, which ``jax.numpy`` on the chip and ``numpy`` in the reference
(``references/ycsb.py``, the same few lines) compute bit for bit alike.
The data seed is the configuration's ``data_seed`` (the harness hands an
app module nothing, so the module reads its own configuration's file).

``update(field, value)`` overwrites one field (``writeallfields=false``),
adds one to ``ver`` and answers the new ``ver``; a ``field`` outside 0..9
writes nothing and answers ``ver`` as it is (the harness's neutral warm-up
call). ``read()`` is read-only (``readallfields=true``) and answers
``(ver, fields)``.
"""

import json
import os

import jax
import jax.numpy as jnp

from orleans_tpu.dispatch import VectorGrain, actor_method

FIELD_COUNT = 10
FIELD_BYTES = 100
RECORD_BYTES = FIELD_COUNT * FIELD_BYTES
RECORD_WORDS = RECORD_BYTES // 4
ROW_BYTES = 1024   # the record and 24 bytes of padding

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                       "configs", "ycsb-1kb.json")) as _f:
    DATA_SEED = int(json.load(_f)["data_seed"])


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


GRAINS = {"RecordVectorGrain": RecordVectorGrain}
