"""ICI mesh transport: cross-shard grain messages as device collectives.

The TPU-native replacement for the reference's TCP message fabric
(/root/reference/src/Orleans.Core/Messaging/SocketManager.cs, framed
``Message`` wire format IncomingMessageBuffer.cs:125-163, hash-picked sender
lanes OutboundMessageQueue.cs:38-44,125): intra-slice actor messages are
serialized into fixed-layout tensors and exchanged with ONE ``all_to_all``
along the silo mesh axis per dispatch tick (SURVEY.md §5 "Distributed
communication backend"). Every shard enters the collective every tick —
empty lanes are padding — so the mesh can never deadlock on a partial
exchange (SURVEY.md §7 hard parts #3).

Capacity discipline: each shard can send at most ``capacity`` messages to
each destination shard per tick. Overflow messages are DROPPED and counted
(the overload-shedding analog of ``ActivationData.CheckOverloaded``); the
host reads the drop counter and re-submits on the next tick — the same
at-most-once-per-tick + retry semantics the reference gets from rejection
+ resend (Dispatcher.cs:433-439, InsideRuntimeClient resend logic).
"""

from __future__ import annotations

from functools import partial

import jax
from jax.sharding import PartitionSpec as P

from .mesh import SILO_AXIS

__all__ = ["build_exchange"]


def _pack_outbox(dest: jax.Array, valid: jax.Array, payload: dict,
                 n_shards: int, capacity: int):
    """Slot local messages into per-destination buckets.

    dest: [B] int32 destination shard per message; valid: [B] bool;
    payload: dict of [B, ...]. Returns (outbox payload dict
    [n_shards, capacity, ...], outbox_valid [n_shards, capacity],
    drops scalar).

    Implemented sort-free: within-destination ranks come from the MXU
    prefix-count kernel (ops.route) rather than an argsort — sorts are the
    weak op on TPU; matmuls are the strong one.
    """
    from ..ops.route import pack_by_dest

    return pack_by_dest(dest, valid, payload, n_shards, capacity)


def build_exchange(mesh, capacity: int):
    """Compile the per-tick message exchange for ``mesh``.

    Returns ``fn(dest, valid, payload) -> (recv_payload, recv_valid, drops)``:
    * dest: [n_shards, B] destination shard index of each local message
    * valid: [n_shards, B]
    * payload: dict of [n_shards, B, ...]
    * recv_*: [n_shards, n_shards * capacity, ...] — messages delivered to
      each shard, flattened over (source shard, lane)
    * drops: [n_shards] overflow counts (host re-submits next tick)

    One ``all_to_all`` on the silo axis per call — the entire cross-silo
    message fabric for a tick.
    """
    n_shards = mesh.devices.size

    def local(dest, valid, payload):
        d, v, p = dest[0], valid[0], \
            jax.tree_util.tree_map(lambda a: a[0], payload)
        outbox, ovalid, drops = _pack_outbox(d, v, p, n_shards, capacity)
        if n_shards > 1:
            swap = partial(jax.lax.all_to_all, axis_name=SILO_AXIS,
                           split_axis=0, concat_axis=0, tiled=True)
            inbox = jax.tree_util.tree_map(swap, outbox)
            ivalid = swap(ovalid)
        else:
            inbox, ivalid = outbox, ovalid
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape(n_shards * capacity, *a.shape[2:])[None],
            inbox)
        return flat, ivalid.reshape(n_shards * capacity)[None], drops[None]

    if n_shards > 1:
        spec = P(SILO_AXIS)
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=(spec, spec, spec),
            check_vma=False)
    else:
        fn = local
    return jax.jit(fn)
