"""The Chirper deployment's grain class (Orleans ``Samples/Chirper``,
``ChirperAccount``: an account holds its followers and a cache of the last
100 chirps it received; ``publish`` sends the chirp to every follower's
account). One account is one device row, 33,296 bytes:

* ``timeline`` u8[32768] — the ring of the last 100 chirps received,
  100 x 320 B, and 768 B of zeros: a u8 leaf whose minor dimension is a
  multiple of 1,024 ticks in place on the chip (``apps/ycsb.py`` says why;
  compiled for a described v5e in ``tests/test_chip_compile.py``);
* ``followers`` i32[128] — the dense keys of the accounts that follow this
  one, -1 where there is none (the follower capacity, 128, is the sending
  method's static fan-out K);
* ``n_followers``, ``n_received`` (chirps ever applied), ``head`` (the
  ring slot the next one takes) and ``seq`` (publishes accepted), i32.

``publish(chirp u8[320])`` is a **sending method** (``orleans_tpu.dispatch.
sends``): it writes nothing to its own timeline, counts the publish in
``seq``, answers ``n_followers`` and emits the chirp to each follower's
``receive`` — made on the device, carried by the engine's exchange (one
``all_to_all`` a pass) and applied before the publisher is answered.
``receive(chirp)`` writes the ring. ``get_received(n)`` is read-only and
answers ``(n_received, the newest min(n, 10) chirps, newest first, in
3,200 bytes)``. A chirp is 320 bytes, a 40-byte header (author, the
author's sequence number, text length: i32 little-endian; zeros) and 280
bytes of text; one whose length is not in 1..280 is accepted by nobody —
``publish`` still sends it, ``receive`` writes nothing: the harness's
neutral warm-up call, which so compiles the exchange's programs too.

The graph and the initial rows are ``initial_state``'s doing (the load
phase, by provisioning): ``followers_of`` below is ``references/
chirper.py``'s, the same lines in ``jax.numpy``; the seed, the number of
accounts and the degree table are the configuration's (the harness hands
an app module nothing, so the module reads its configuration's file —
``apps/chirper-rehearsal.py`` is this module over the file's ``rehearse``
block, because the graph depends on the number of accounts).

Notes for whoever adds to the benchmark (``chipbench/README.md`` cannot
be edited by the PR that brought this file): the traffic kind is
``traffic/chirper_ops.py``; the readers this deployment brought are
``readers/trace_op_per_count.py`` and ``readers/exchange_roofline.py``,
and ``exchange_bytes.py`` reckons a delivery's bytes; every ``ctx`` key
they read is one ``README.md`` lists.
"""

import json
import os

import jax.numpy as jnp
import numpy as np

from orleans_tpu.dispatch import VectorGrain, actor_method, sends

CHIRP_BYTES = 320
HEADER_BYTES = 40
TEXT_BYTES = 280
RING = 100
TIMELINE_BYTES = 32768
FOLLOW_CAP = 128
READ_N = 10
TILES = -(-TIMELINE_BYTES // CHIRP_BYTES)

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "chirper-256k.json")


def hash32(xp, seed: int, a, b):
    """``references/chirper.py``'s ``hash32``, word for word."""
    u = xp.uint32
    h = (a.astype(u) * u(0x9E3779B1)) ^ (b.astype(u) * u(0x85EBCA77)) \
        ^ u((seed * 0xC2B2AE3D) & 0xFFFFFFFF)
    h = (h ^ (h >> u(16))) * u(0x85EBCA6B)
    h = (h ^ (h >> u(13))) * u(0xC2B2AE35)
    return h ^ (h >> u(16))


def followers_of(xp, seed: int, accounts: int, table, keys):
    """``references/chirper.py``'s ``followers_of``, word for word."""
    if accounts & (accounts - 1):
        raise ValueError(f"accounts must be a power of two, got {accounts}")
    u = xp.uint32
    k = keys.astype(u)
    table = xp.asarray(table, dtype=xp.int32)
    degree = table[(hash32(xp, seed, k, xp.zeros_like(k))
                    % u(table.shape[0])).astype(xp.int32)]
    base = hash32(xp, seed, k, xp.ones_like(k))
    stride = hash32(xp, seed, k, xp.full_like(k, 2)) | u(1)
    j = xp.arange(FOLLOW_CAP, dtype=u)
    f = ((base[..., None] + j * stride[..., None]) % u(accounts)
         ).astype(xp.int32)
    keep = (j.astype(xp.int32) < degree[..., None]) \
        & (f != keys.astype(xp.int32)[..., None])
    return xp.where(keep, f, xp.int32(-1))


def _accepted(chirp):
    """A chirp somebody accepts: text length 1..280 in its header."""
    length = (chirp[8:12].astype(jnp.int32)
              << jnp.arange(0, 32, 8, dtype=jnp.int32)).sum()
    return (length > 0) & (length <= TEXT_BYTES)


def make_account(data_seed: int, accounts: int, degree_table) -> type:
    """The ``ChirperAccount`` class of one graph."""
    table = np.asarray(degree_table, np.int32)
    # ring slot of every byte of the timeline (the padding belongs to no
    # slot); a chirp repeated TILES times covers the timeline
    at = np.arange(TIMELINE_BYTES)
    slot_of = np.where(at < RING * CHIRP_BYTES, at // CHIRP_BYTES, -1)

    class ChirperAccount(VectorGrain):
        STATE = {
            "timeline": (jnp.uint8, (TIMELINE_BYTES,)),
            "followers": (jnp.int32, (FOLLOW_CAP,)),
            "n_followers": (jnp.int32, ()),
            "n_received": (jnp.int32, ()),
            "head": (jnp.int32, ()),
            "seq": (jnp.int32, ()),
        }

        @staticmethod
        def initial_state(key_hash):
            f = followers_of(jnp, data_seed, accounts, table, key_hash)
            return {"timeline": jnp.zeros(TIMELINE_BYTES, jnp.uint8),
                    "followers": f,
                    "n_followers": (f >= 0).sum().astype(jnp.int32),
                    "n_received": jnp.int32(0), "head": jnp.int32(0),
                    "seq": jnp.int32(0)}

        @actor_method(args={"chirp": (jnp.uint8, (CHIRP_BYTES,))})
        def receive(state, args):
            chirp = args["chirp"]
            ok = _accepted(chirp)
            written = jnp.where(ok & (slot_of == state["head"]),
                                jnp.tile(chirp, TILES)[:TIMELINE_BYTES],
                                state["timeline"])
            n = state["n_received"] + ok.astype(jnp.int32)
            return {**state, "timeline": written, "n_received": n,
                    "head": (state["head"] + ok.astype(jnp.int32)) % RING}, n

        @sends("receive", fanout=FOLLOW_CAP,
               args={"chirp": (jnp.uint8, (CHIRP_BYTES,))})
        def publish(state, args):
            chirp = args["chirp"]
            seq = state["seq"] + _accepted(chirp).astype(jnp.int32)
            to = state["followers"]
            return ({**state, "seq": seq}, state["n_followers"],
                    (to, to >= 0,
                     {"chirp": jnp.broadcast_to(chirp,
                                                (FOLLOW_CAP, CHIRP_BYTES))}))

        @actor_method(args={"n": (jnp.int32, ())}, read_only=True)
        def get_received(state, args):
            ring = state["timeline"][:RING * CHIRP_BYTES].reshape(
                RING, CHIRP_BYTES)
            j = jnp.arange(READ_N, dtype=jnp.int32)
            newest = ring[(state["head"] - 1 - j) % RING]
            shown = jnp.minimum(jnp.minimum(args["n"], READ_N),
                                state["n_received"])
            return state, (state["n_received"], jnp.where(
                (j < shown)[:, None], newest, jnp.uint8(0)).reshape(-1))

    return ChirperAccount


def grains_of(cfg: dict) -> dict:
    g = cfg["graph"]
    return {"ChirperAccount": make_account(
        cfg["data_seed"], cfg["grains"][0]["dense"], g["degree_table"])}


def load_config(rehearse: bool) -> dict:
    with open(CONFIG) as f:
        cfg = json.load(f)
    over = cfg.pop("rehearse", {})
    return {**cfg, **over} if rehearse else cfg


GRAINS = grains_of(load_config(False))
