"""Device-tier stateless workers: a VectorGrain class REPLICATED over the
mesh axis — the device analog of ``[StatelessWorker]``
(/root/reference/src/Orleans.Core.Abstractions/Placement/
StatelessWorkerPlacement.cs:6, StatelessWorkerDirector.cs:8; SURVEY §2.4
"replicate actor class across mesh axis; no directory entry").

Semantics, mapped tpu-first:

* **No directory entry / no owner**: every shard holds its own replica row
  for every key; a call for key k may run on ANY shard (assignment is
  round-robin — the stateless-worker scale-out: work spreads over the
  mesh instead of hashing to one owner).
* **Workers are independent**: per-shard replicas diverge by design, like
  N stateless-worker activations of the same grain each accumulating
  local state (the reference's canonical use: local caches/aggregators).
* **Reads fan in via collectives**: :meth:`ReplicatedWorkerHost.read_merged`
  folds the per-shard replicas with the class's ``MERGE`` spec — one
  ``psum`` / ``pmax`` / ``pmin`` over the silo axis per field — so a read
  sees the cluster-wide aggregate without any cross-shard messaging.

Classes opt in with :func:`replicated_worker` and declare how fields merge::

    @replicated_worker
    class HitCounter(VectorGrain):
        STATE = {"hits": (jnp.int32, ()), "peak": (jnp.int32, ())}
        MERGE = {"hits": "sum", "peak": "max"}
        ...

Hosted through ``VectorRuntime.replicated_host(cls, n_keys)``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import SILO_AXIS, replicated_spec, shard_spec
from .engine import _validate_args
from .vector_grain import VectorGrain, vector_methods

__all__ = ["replicated_worker", "ReplicatedWorkerHost"]

_MERGE_COLLECTIVES = {
    "sum": jax.lax.psum,
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
}


def replicated_worker(cls: type) -> type:
    """Mark a VectorGrain class for mesh-axis replication. Requires a
    ``MERGE`` dict naming a collective ("sum" | "max" | "min") per STATE
    field — the read fan-in semantics."""
    merge = getattr(cls, "MERGE", None)
    if not isinstance(merge, dict) or set(merge) != set(cls.STATE):
        raise TypeError(
            f"{cls.__name__} needs MERGE covering exactly its STATE fields "
            f"({sorted(cls.STATE)}); got {merge!r}")
    bad = {f: op for f, op in merge.items() if op not in _MERGE_COLLECTIVES}
    if bad:
        raise TypeError(f"unknown merge ops {bad}; choose from "
                        f"{sorted(_MERGE_COLLECTIVES)}")
    cls.__vector_replicated__ = True
    return cls


class ReplicatedWorkerHost:
    """Replicated table + dispatch for one stateless-worker class.

    State layout: ``[n_shards, n_keys + 1, *field]`` (row ``n_keys`` is
    the padding write sink), committed to the mesh sharding on the shard
    axis — each device owns ITS replica block, exactly like the sharded
    actor table, but the key space is the full range on every shard."""

    def __init__(self, cls: type[VectorGrain], mesh, n_keys: int):
        if not getattr(cls, "__vector_replicated__", False):
            raise TypeError(
                f"{cls.__name__} is not @replicated_worker-decorated")
        self.cls = cls
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        self.n_keys = int(n_keys)
        self.methods = vector_methods(cls)
        self._sharding = shard_spec(mesh)
        self._replicated = replicated_spec(mesh)
        self._rr = 0  # round-robin shard assignment (the scale-out knob)
        # per-(shard, key) activation bitmap: first touch runs
        # initial_state on that shard's replica row (OnActivate per
        # stateless-worker activation)
        self.active = np.zeros((self.n_shards, self.n_keys), dtype=bool)
        self.state: dict[str, jax.Array] = {}
        for name, (dtype, shape) in cls.STATE.items():
            self.state[name] = self._put(jnp.zeros(
                (self.n_shards, self.n_keys + 1, *shape), dtype=dtype))
        self._kernel_cache: dict[tuple, Any] = {}
        self.calls = 0

    def _put(self, arr):
        return jax.device_put(arr, self._sharding)

    # ------------------------------------------------------------------
    def call_batch(self, method: str, keys: np.ndarray,
                   args: dict[str, np.ndarray] | None = None):
        """Run ``method`` for each key on a round-robin-assigned shard,
        in as many kernel ticks as duplicate pressure requires; returns
        results in caller order.

        Duplicate keys spread over shards (independent workers run in
        parallel); when more than one call lands on the same (shard, key)
        they serialize across ticks — one turn per worker per tick, like
        the owned table's conflict defer. No call is ever dropped."""
        m = self.methods.get(method)
        if m is None:
            raise AttributeError(
                f"{self.cls.__name__} has no @actor_method {method!r}")
        keys = np.asarray(keys)
        self._check_keys(keys)
        M = keys.shape[0]
        args = args or {}
        n = self.n_shards
        if m.args_schema is None and args:
            m.args_schema = {k: (np.asarray(v).dtype,
                                 np.asarray(v).shape[1:])
                             for k, v in args.items()}
        if m.args_schema is not None:
            _validate_args(self.cls, method, m.args_schema, args)
        shard = (np.arange(self._rr, self._rr + M) % n).astype(np.int64)
        self._rr = int((self._rr + M) % n)
        results_by_idx: list = [None] * M
        remaining = list(range(M))
        while remaining:
            claimed: set = set()
            this_round: list = []
            deferred: list = []
            for idx in remaining:
                loc = (shard[idx], int(keys[idx]))
                if loc in claimed:
                    deferred.append(idx)
                else:
                    claimed.add(loc)
                    this_round.append(idx)
            self._one_tick(m, method, keys, args, shard, this_round,
                           results_by_idx)
            remaining = deferred
        self.calls += M
        if not results_by_idx:
            return np.zeros(0)
        return jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *results_by_idx)

    def _one_tick(self, m, method: str, keys, args, shard,
                  idxs: list, results_by_idx: list) -> None:
        n = self.n_shards
        sh = shard[idxs]
        ks = keys[idxs]
        counts = np.bincount(sh, minlength=n)
        B = max(8, 1 << int(counts.max() - 1).bit_length())
        order = np.argsort(sh, kind="stable")
        starts = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(counts, out=starts[1:])
        lane = np.arange(len(idxs)) - starts[sh[order]]
        slots = np.full((n, B), self.n_keys, dtype=np.int32)
        valid = np.zeros((n, B), dtype=bool)
        fresh = np.zeros((n, B), dtype=bool)
        slots[sh[order], lane] = ks[order]
        valid[sh[order], lane] = True
        fresh[sh[order], lane] = ~self.active[sh[order], ks[order]]
        if not m.read_only:
            # a read-only first touch views initial_state in-kernel but
            # persists nothing — the key stays fresh so the first WRITE
            # still runs initial_state (otherwise a nonzero initial state
            # would be silently replaced by the zero fill)
            self.active[sh[order], ks[order]] = True
        args_b = {}
        for fname, (dtype, shape) in (m.args_schema or {}).items():
            buf = np.zeros((n, B, *shape), dtype=dtype)
            buf[sh[order], lane] = \
                np.asarray(args[fname], dtype=dtype)[idxs][order]
            args_b[fname] = self._put(jnp.asarray(buf))
        kern = self._tick_kernel(method, B)
        new_state, results = kern(
            self.state, self._put(jnp.asarray(slots)),
            self._put(jnp.asarray(fresh)), self._put(jnp.asarray(valid)),
            args_b)
        if not m.read_only:
            self.state = new_state
        host = jax.tree_util.tree_map(np.asarray, results)
        for pos, idx in enumerate(np.asarray(idxs)[order]):
            results_by_idx[idx] = jax.tree_util.tree_map(
                lambda a, p=pos: a[sh[order][p], lane[p]], host)

    def _check_keys(self, keys: np.ndarray) -> None:
        if keys.size and (keys.min() < 0 or keys.max() >= self.n_keys):
            raise ValueError(
                f"{self.cls.__name__} keys must be in [0, {self.n_keys}); "
                f"got range [{keys.min()}, {keys.max()}]")

    def _tick_kernel(self, method: str, B: int):
        key = ("tick", method, B, self.n_keys)
        k = self._kernel_cache.get(key)
        if k is not None:
            return k
        m = self.methods[method]
        handler, init = m.fn, self.cls.initial_state
        read_only = m.read_only

        def sel(mask, a, b):
            return jnp.where(
                mask.reshape(mask.shape + (1,) * (a.ndim - 1)), a, b)

        def local(state, slots, fresh, valid, args):
            st = jax.tree_util.tree_map(lambda a: a[0], state)
            slots_l, fresh_l, valid_l = slots[0], fresh[0], valid[0]
            args_l = jax.tree_util.tree_map(lambda a: a[0], args)
            rows = jax.tree_util.tree_map(lambda f: f[slots_l], st)
            init_rows = jax.vmap(init)(slots_l.astype(jnp.int32))
            rows = jax.tree_util.tree_map(
                lambda ir, r: sel(fresh_l, ir, r), init_rows, rows)
            new_rows, results = jax.vmap(handler)(rows, args_l)
            if read_only:
                out = state
            else:
                new_st = jax.tree_util.tree_map(
                    lambda f, nr, r: f.at[slots_l].set(
                        sel(valid_l, nr, r)), st, new_rows, rows)
                out = jax.tree_util.tree_map(lambda a: a[None], new_st)
            return out, jax.tree_util.tree_map(lambda a: a[None], results)

        if self.n_shards > 1:
            spec = P(SILO_AXIS)
            local = jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(spec, spec, spec, spec, spec),
                out_specs=(spec, spec), check_vma=False)
        # donation only when state is actually replaced: a read-only tick
        # keeps self.state pointing at the input arrays, which donation
        # would have invalidated (engine._build_kernel guards identically)
        k = jax.jit(local, donate_argnums=(0,) if not read_only else ())
        self._kernel_cache[key] = k
        return k

    # ------------------------------------------------------------------
    def read_merged(self, keys: np.ndarray) -> dict[str, np.ndarray]:
        """Cluster-wide view of ``keys``: every shard reads its replica
        rows, then ONE collective per field folds them with the class's
        MERGE spec (psum/pmax/pmin over the silo axis) — the read fan-in
        of N stateless workers, with zero cross-shard messages."""
        keys = np.asarray(keys, dtype=np.int32)
        self._check_keys(keys)
        kern = self._merge_kernel(keys.shape[0])
        d_keys = jax.device_put(jnp.asarray(keys), self._replicated)
        out = kern(self.state, d_keys)
        return jax.tree_util.tree_map(np.asarray, out)

    def _merge_kernel(self, M: int):
        key = ("merge", M)
        k = self._kernel_cache.get(key)
        if k is not None:
            return k
        merge = self.cls.MERGE
        # never merge uninitialized replica rows as real zeros for
        # max/min of signed data? zeros are the declared initial fill of
        # the table; initial_state defines per-actor semantics on first
        # touch per shard. Untouched shards contribute the zero fill —
        # the documented contract (stateless workers that never saw a
        # key contribute the identity only if initial_state is the zero
        # fill; classes needing a different identity must encode it in
        # their merge field choice).

        # static closure value, hoisted deliberately (like `merge` above):
        # reading self.* inside the traced body would freeze host object
        # state into the kernel invisibly (OTPU006) — the shard count is a
        # trace-time constant by construction (mesh size is fixed for the
        # host's lifetime and the kernel cache is per-shape)
        sharded = self.n_shards > 1

        def local(state, keys):
            st = jax.tree_util.tree_map(lambda a: a[0], state)
            rows = {f: st[f][keys] for f in st}
            if sharded:
                rows = {f: _MERGE_COLLECTIVES[merge[f]](v, SILO_AXIS)
                        for f, v in rows.items()}
            return jax.tree_util.tree_map(lambda a: a[None], rows)

        if sharded:
            local = jax.shard_map(
                local, mesh=self.mesh, in_specs=(P(SILO_AXIS), P()),
                out_specs=P(None), check_vma=False)

        def run(state, keys):
            out = local(state, keys)
            return jax.tree_util.tree_map(lambda a: a[0], out)

        k = jax.jit(run)
        self._kernel_cache[key] = k
        return k
