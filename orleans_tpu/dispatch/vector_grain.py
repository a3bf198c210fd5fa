"""VectorGrain: device-tier grains with jax-traceable handlers.

This is the TPU-native inversion of the reference's per-message dispatch
(SURVEY.md §7; /root/reference/src/Orleans.Runtime/Core/Dispatcher.cs hot
path): instead of scheduling one turn per message on a thread, all pending
invocations of one grain class are coalesced each tick into ONE vectorized
actor-update kernel over a slot-table of activation state
(orleans_tpu.dispatch.table/engine). Per-activation single-threaded-turn
semantics hold by construction: a tick applies at most one message per
activation (conflicts defer to the next tick — the mailbox semantics of
``ActivationData.EnqueueMessage``, ActivationData.cs:566).

A VectorGrain declares:
* ``STATE`` — dict of field → (dtype, shape): the activation state row.
* ``initial_state(key_hash)`` — pure fn: int64 scalar → state row pytree
  (on-device activation, the ``OnActivateAsync`` analog fused into the tick).
* handler methods decorated ``@actor_method``: pure
  ``(state_row, args_row) -> (new_state_row, result)`` functions, vmapped
  by the engine. No Python side effects; jnp ops only.
* optionally, handlers decorated ``@sends(...)``: a handler that also
  EMITS MESSAGES to other device-tier actors from inside the tick —
  ``(state_row, args_row) -> (new_state_row, result, (keys, valid,
  payload))`` with ``keys`` the ``[K]`` dense keys of the destination
  class, ``valid`` the ``[K]`` mask of the lanes that carry a message and
  ``payload`` one ``[K, ...]`` array per field of the destination
  method's arguments. The engine carries the job's outboxes over the
  mesh exchange (one ``all_to_all``) and applies them as invocations of
  the destination method before the sender's reply resolves
  (``VectorRuntime._exchange``): grain-to-grain calls on the device tier.
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np

__all__ = ["VectorGrain", "actor_method", "sends", "vector_methods"]


class ActorMethod:
    """Descriptor wrapper marking a jax-traceable handler."""

    def __init__(self, fn: Callable, args_schema: dict | None,
                 read_only: bool):
        self.fn = fn
        self.name = fn.__name__
        # args schema: field → (dtype, shape); inferred from the first call
        # when not declared (declared = better errors + no first-call probe)
        self.args_schema = args_schema
        self.read_only = read_only

    def __get__(self, obj, objtype=None):
        # accessed on the class: return self so the engine can find it
        return self

    def infer_schema(self, args: dict[str, Any]) -> dict:
        if self.args_schema is None:
            self.args_schema = {
                k: (np.asarray(v).dtype, np.asarray(v).shape)
                for k, v in args.items()
            }
        return self.args_schema


def actor_method(fn: Callable | None = None, *, args: dict | None = None,
                 read_only: bool = False):
    """Mark a VectorGrain handler.

    ``@actor_method`` or ``@actor_method(args={"pos": (jnp.float32, (2,))})``.
    ``read_only=True`` handlers skip the state scatter (no write-back) — the
    device analog of ``[ReadOnly]`` interleaving.
    """
    def wrap(f: Callable) -> ActorMethod:
        return ActorMethod(f, args, read_only)
    if fn is not None:
        return wrap(fn)
    return wrap


class SendingMethod(ActorMethod):
    """A handler that emits up to ``fanout`` messages a call for
    ``dest_method`` of ``dest_class`` (None: the declaring class).
    ``fn`` keeps the two-value form every tool that traces a handler
    expects, ``(new_state, (result, outbox))``: the outbox is part of
    what the tick writes."""

    def __init__(self, fn: Callable, args_schema: dict | None,
                 dest_class: type | None, dest_method: str, fanout: int):
        def paired(state, args):
            new_state, result, outbox = fn(state, args)
            return new_state, (result, outbox)

        paired.__name__ = fn.__name__
        super().__init__(paired, args_schema, read_only=False)
        self.dest_class = dest_class
        self.dest_method = dest_method
        self.fanout = int(fanout)


def sends(method: str, *, fanout: int, to: type | None = None,
          args: dict | None = None):
    """Mark a VectorGrain handler that messages other actors.

    ``@sends("receive", fanout=K)``: every call may emit up to K messages
    (K is static: the class's follower capacity) for ``to.<method>``
    (``to=None``: the declaring class), whose declared ``args`` schema is
    the payload's. The handler returns ``(new_state, result, (keys,
    valid, payload))`` — see the module docstring. A sending method
    writes (it cannot be read-only): its reply is acknowledged only
    after every one of its messages has been applied.
    """
    def wrap(f: Callable) -> SendingMethod:
        return SendingMethod(f, args, to, method, fanout)
    return wrap


class VectorGrain:
    """Base marker class for device-tier grains.

    Subclasses are never instantiated: state lives in the silo's
    ShardedActorTable; handlers are static pure functions.
    """

    STATE: dict[str, tuple] = {}

    @staticmethod
    def initial_state(key_hash):  # pragma: no cover — must override
        """key_hash: int64 scalar (GrainId.uniform_hash mod 2^63) → state
        row pytree matching STATE."""
        raise NotImplementedError

    # Idle collection age for table slots (host-driven); None = never.
    COLLECTION_AGE: float | None = None


def vector_methods(cls: type) -> dict[str, ActorMethod]:
    out = {}
    for name in dir(cls):
        v = getattr(cls, name)
        if isinstance(v, ActorMethod):
            out[name] = v
    return out
