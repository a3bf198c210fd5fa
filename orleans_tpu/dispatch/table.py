"""ShardedActorTable: device-resident activation state, sharded over the mesh.

The fusion of the reference's ``ActivationDirectory`` (local activation map,
ActivationDirectory.cs) and ``GrainDirectoryPartition`` (consistent-hash
ownership, GrainDirectoryPartition.cs:207) re-expressed as device arrays
(SURVEY.md §7): activation state for one VectorGrain class lives in a slot
pool of shape ``[n_shards, capacity+1, *field]`` sharded over the ``silo``
mesh axis. Slot ``capacity`` (the last row) is a write sink for padding
lanes, so masked scatters never collide with real rows.

Key → shard is ``uniform_hash % n_shards`` (the ring's CalculateTargetSilo,
LocalGrainDirectory.cs:477, degenerated to a static mesh mapping); slot
within the shard comes from a host-side free list (the dynamic-activation-
table hard part: slot pool + free list, SURVEY.md §7 hard parts #2).

Two key regimes:
* **hashed** (general): host dict key→(shard, slot); per-key alloc/free.
* **dense** (bulk workloads, e.g. 1M Presence players with keys 0..N-1):
  ``ensure_dense(n)`` pre-provisions key i → (i % n_shards, i // n_shards)
  so bulk batches compute slots with vectorized integer math — no per-key
  Python. This is the 1M-msgs/sec path.
"""

from __future__ import annotations

import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.mesh import SILO_AXIS, make_mesh, shard_spec
from .vector_grain import VectorGrain, vector_methods

# directory-value encoding stride: loc = shard * _LOC_STRIDE + slot.
# Fixed (not the live capacity) so encoded values survive table growth;
# bounds per-shard capacity at 2^20 slots and shards at 2^10 within int32.
_LOC_STRIDE = 1 << 20

__all__ = ["ShardedActorTable"]


@partial(jax.jit, donate_argnums=0)
def _accumulate_hits(hits, slots_b, valid_b, scale):
    """Per-slot invocation counters, accumulated ON DEVICE as part of the
    dispatch tick (the hot-spot telemetry feed of orleans_tpu.rebalance):
    one masked scatter-add per tick — padding lanes address the sink row,
    so no host sync and no data-dependent shapes."""
    n = hits.shape[0]
    shard = jnp.arange(n, dtype=jnp.int32)[:, None]
    return hits.at[shard, slots_b].add(
        valid_b.astype(jnp.int32) * scale)


@jax.jit
def _move_state_rows(state, src_shard, src_slot, dst_shard, dst_slot):
    """Copy state rows (src_shard[i], src_slot[i]) → (dst_shard[i],
    dst_slot[i]) across every field — the device half of a live
    shard-to-shard migration. Purely functional (NO donation): the caller
    keeps the old arrays as the implicit rollback snapshot until the swap
    commits."""
    def one(arr):
        rows = arr[src_shard, src_slot]
        return arr.at[dst_shard, dst_slot].set(rows)
    return jax.tree_util.tree_map(one, state)


class ShardedActorTable:
    def __init__(self, grain_class: type[VectorGrain], mesh=None,
                 capacity_per_shard: int = 1024):
        self.grain_class = grain_class
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_shards = self.mesh.devices.size
        # power-of-two capacity: bounds distinct kernel shapes (grow() keeps
        # this invariant) and lets padded batch buckets (_bucket, also po2)
        # slice the slot pool contiguously in the dense fast path
        self.capacity = 1 << (int(capacity_per_shard) - 1).bit_length()
        self.methods = vector_methods(grain_class)
        # every array of the table is committed to the mesh's devices —
        # a 1-device mesh included, so a table built over chip 2 of a
        # four-chip host lives on chip 2, not on the default device
        self.sharding = shard_spec(self.mesh)
        # tick-serialization fence: a reentrant lock the off-loop tick
        # worker holds for every batch. State mutators/materializers
        # below (grow, move_rows, snapshot/restore, read_row) take it so
        # they never observe — or clobber — tbl.state while a worker-side
        # kernel has it donated mid-flight. Always present (uncontended
        # acquire is ~100ns on these cold paths, so standalone tables
        # just pay a no-op); VectorRuntime.register replaces it with the
        # owning engine's lock so every table in one engine shares the
        # worker's fence.
        self.fence = threading.RLock()

        # host bookkeeping
        self.key_to_slot: dict[int, tuple[int, int]] = {}  # key_hash → (shard, slot)
        # device-queryable mirror of the hashed-key directory: full 62-bit
        # key identity, value = shard * (capacity+1) ... encoded lazily per
        # lookup as shard/slot below. Lets sparse keys ride the on-device
        # routing path (route/apply_received sparse mode) — the on-chip
        # directory tier (ops.hash_probe; AdaptiveGrainDirectoryCache.cs:178)
        from ..ops.hash_probe import DeviceDirectory64
        self.device_dir = DeviceDirectory64()
        # key_hash → the GrainId uniform hash that ROUTES it (differs for
        # small-int keys, where key_hash is the key itself): ring-ownership
        # sweeps need the routing hash to decide who owns a resident row
        self.route_hash: dict[int, int] = {}
        self.free: list[list[int]] = [
            list(range(self.capacity - 1, -1, -1)) for _ in range(self.n_shards)]
        self.dense_n = 0  # keys [0, dense_n) are dense-mapped
        self.dense_per_shard = 0
        self.dense_active = np.zeros(0, dtype=bool)
        # keys the per-key path has activated (a dense key's first write
        # enqueued, a hashed key given its slot) for whose row no writing
        # tick has been claimed yet: their lanes start from
        # initial_state (VectorRuntime._claim)
        self.uninit: set[int] = set()

        # device state: [n_shards, capacity+1, *shape]; row `capacity` is the
        # padding write sink
        self.state: dict[str, jax.Array] = {}
        # allocated where it will live, a shard a device: zeros made on
        # one device and then spread would hold the whole table there
        # first (8.7 GB of 33 KB rows do not fit one chip's 16 GB once the
        # slices' layout pads them)
        for name, (dtype, shape) in grain_class.STATE.items():
            self.state[name] = jnp.zeros(
                (self.n_shards, self.capacity + 1, *shape), dtype=dtype,
                device=self.sharding)
        # hot-spot telemetry: per-slot invocation counters, [n_shards,
        # capacity+1] with the sink row absorbing padding lanes. Off by
        # default (an extra scatter-add per tick is pure overhead unless a
        # rebalancer consumes it) — see enable_hit_tracking.
        self.hits: jax.Array | None = None
        # cost attribution (observability.ledger, ISSUE 17): per-slot
        # accumulated tick cost in MICROSECONDS, same [n_shards,
        # capacity+1] layout / sink-row / donation / fence discipline as
        # the hit counters (int32 µs holds ~35 minutes of charged wall
        # per slot between reset_cost readouts). Off by default — see
        # enable_cost_tracking.
        self.cost: jax.Array | None = None

    # ------------------------------------------------------------------
    def _put(self, arr):
        """Commit to the mesh sharding."""
        return jax.device_put(arr, self.sharding)

    def _put_rounds(self, arr):
        """Commit a [K, n_shards, ...] stacked-rounds array: sharded on the
        shard axis (dim 1), replicated over rounds."""
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(
            arr, NamedSharding(self.mesh, PartitionSpec(None, SILO_AXIS)))

    @property
    def sink_slot(self) -> int:
        return self.capacity

    def active_count(self) -> int:
        """Live activations: hashed slots + dense keys actually touched
        (dense pre-provisioning reserves keyspace; activation is first
        touch — the dense_active bitmap)."""
        return len(self.key_to_slot) + int(self.dense_active.sum())

    # -- hot-spot telemetry (consumed by orleans_tpu.rebalance) -----------
    # All four accessors are under the tick fence: record_hits DONATES
    # the counter buffer (_accumulate_hits, donate_argnums=0) and runs
    # inside off-loop worker batches — an unfenced loop-side read could
    # materialize the donated (deleted) array, and an unfenced reset
    # could be overwritten by a worker accumulate over pre-reset
    # counters (double-counted load, defeating the int32-overflow
    # protection the reset exists for).
    def enable_hit_tracking(self) -> None:
        with self.fence:
            if self.hits is None:
                self.hits = self._put(
                    jnp.zeros((self.n_shards, self.capacity + 1),
                              jnp.int32))

    def record_hits(self, slots_b, valid_b, scale: int = 1) -> None:
        """Fold one tick's [n_shards, B] batch into the per-slot counters
        (no-op until enable_hit_tracking). ``scale``: messages per lane —
        K for a scanned K-round kernel. Reentrant under the engine fence
        the tick paths already hold."""
        with self.fence:
            if self.hits is None:
                return
            self.hits = _accumulate_hits(
                self.hits, jnp.asarray(slots_b, jnp.int32),
                jnp.asarray(valid_b), jnp.int32(scale))

    def shard_hits(self) -> np.ndarray:
        """[n_shards] invocation totals since the last reset (sink row
        excluded) — the load view a rebalance planner reads."""
        with self.fence:
            if self.hits is None:
                return np.zeros(self.n_shards, dtype=np.int64)
            return np.asarray(
                jnp.sum(self.hits[:, :self.capacity],
                        axis=1)).astype(np.int64)

    def slot_hits(self) -> np.ndarray:
        """Host copy of the per-slot counters [n_shards, capacity+1]
        (planner-rate readout, not tick-rate)."""
        with self.fence:
            if self.hits is None:
                return np.zeros((self.n_shards, self.capacity + 1),
                                np.int32)
            return np.asarray(self.hits)

    def reset_hits(self) -> None:
        """Zero the counters (each rebalance round plans against the load
        observed since the previous round)."""
        with self.fence:
            if self.hits is not None:
                self.hits = self._put(
                    jnp.zeros((self.n_shards, self.capacity + 1),
                              jnp.int32))

    # -- cost attribution (consumed by observability.ledger) --------------
    # The hit-counter discipline verbatim (same donation, same fence —
    # see the comment block above): the cost buffer is one more masked
    # scatter-add folded into the tick, reusing _accumulate_hits with
    # the per-row µs charge as the scale.
    def enable_cost_tracking(self) -> None:
        with self.fence:
            if self.cost is None:
                self.cost = self._put(
                    jnp.zeros((self.n_shards, self.capacity + 1),
                              jnp.int32))

    def record_cost(self, slots_b, valid_b, cost_us: int) -> None:
        """Fold one tick's [n_shards, B] batch into the per-slot cost
        accumulators: every valid lane is charged ``cost_us``
        microseconds (the tick wall — each resident row occupied the
        whole tick). No-op until enable_cost_tracking; reentrant under
        the engine fence like record_hits."""
        with self.fence:
            if self.cost is None or cost_us <= 0:
                return
            self.cost = _accumulate_hits(
                self.cost, jnp.asarray(slots_b, jnp.int32),
                jnp.asarray(valid_b), jnp.int32(cost_us))

    def slot_cost(self) -> np.ndarray:
        """Host copy of the per-slot cost µs [n_shards, capacity+1]
        (ledger/planner-rate readout, not tick-rate)."""
        with self.fence:
            if self.cost is None:
                return np.zeros((self.n_shards, self.capacity + 1),
                                np.int32)
            return np.asarray(self.cost)

    def cost_seconds(self) -> float:
        """Total charged row-seconds since the last reset, folded ON
        DEVICE via ``ops.segment_reduce.masked_reduce`` (sink column
        masked out) — ONE scalar crosses the host boundary, the DrJAX
        masked-reduction shape the ledger's readout rides."""
        with self.fence:
            if self.cost is None:
                return 0.0
            from ..ops.segment_reduce import masked_reduce
            valid = jnp.broadcast_to(
                jnp.arange(self.capacity + 1) < self.capacity,
                (self.n_shards, self.capacity + 1))
            total = masked_reduce(self.cost, valid, "sum")
            return float(np.asarray(total)) * 1e-6

    def reset_cost(self) -> None:
        """Zero the cost accumulators (int32-overflow protection, same
        rationale as reset_hits)."""
        with self.fence:
            if self.cost is not None:
                self.cost = self._put(
                    jnp.zeros((self.n_shards, self.capacity + 1),
                              jnp.int32))

    # -- dense regime -----------------------------------------------------
    def ensure_dense(self, n: int) -> None:
        """Pre-provision keys 0..n-1 with the static dense mapping. Must be
        called before any hashed allocation (the two regimes share slots
        only if dense claims the low slot range first).

        The mapping is BLOCK-wise — key → (key // per_shard, key % per_shard)
        — so a contiguous key range is an exact reshape onto the
        [n_shards, B] batch layout (zero-shuffle bulk dispatch)."""
        if self.key_to_slot:
            raise RuntimeError("dense mapping must be set up before hashed keys")
        if self.dense_per_shard:
            # the block mapping is frozen at first provisioning: changing
            # per_shard would remap every existing key to another row
            # (silent cross-actor state leak); growth within the provisioned
            # keyspace is free, beyond it requires migration
            if n <= self.dense_per_shard * self.n_shards:
                if n > self.dense_n:
                    self.dense_active = np.concatenate(
                        [self.dense_active, np.zeros(n - self.dense_n, bool)])
                    self.dense_n = n
                return
            raise RuntimeError(
                f"dense keyspace exhausted ({n} > "
                f"{self.dense_per_shard * self.n_shards}); provision the "
                f"maximum population in the first ensure_dense call")
        per_shard = -(-n // self.n_shards)  # ceil
        if per_shard > self.capacity:
            self.grow(per_shard)
        self.dense_n = n
        self.dense_per_shard = per_shard
        # host-side activation bitmap: which dense keys have been fresh-
        # initialized (the OnActivate bookkeeping for the dense regime)
        self.dense_active = np.zeros(n, dtype=bool)
        # carve dense slots out of the free lists
        for s in range(self.n_shards):
            self.free[s] = [i for i in self.free[s]
                            if i >= self.dense_per_shard]

    def dense_fresh_mask(self, keys: np.ndarray) -> np.ndarray | None:
        """Bool [M] mask of dense keys not yet activated, or None when every
        key is already active (the common steady-state — no upload needed)."""
        if self.dense_active.size == 0:
            return None
        m = ~self.dense_active[keys]
        return m if m.any() else None

    def mark_dense_active(self, keys: np.ndarray) -> None:
        if self.dense_active.size:
            self.dense_active[keys] = True

    def dense_shard_slot(self, keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized key→(shard, slot) for dense keys (int array)."""
        per = max(self.dense_per_shard, 1)
        return keys // per, keys % per

    # -- hashed regime ----------------------------------------------------
    def lookup_or_allocate(self, key_hash: int) -> tuple[int, int, bool]:
        """Returns (shard, slot, fresh)."""
        loc = self.key_to_slot.get(key_hash)
        if loc is not None:
            return loc[0], loc[1], False
        shard = key_hash % self.n_shards
        if not self.free[shard]:
            self.grow(self.capacity * 2)
        slot = self.free[shard].pop()
        self.key_to_slot[key_hash] = (shard, slot)
        self.device_dir.insert(key_hash, self._encode_loc(shard, slot))
        return shard, slot, True

    def _encode_loc(self, shard: int, slot: int) -> int:
        """Pack (shard, slot) into one int32 directory value. Slots are
        encoded against a fixed 2^20 stride (not the live capacity) so
        values survive table growth without re-encoding the directory."""
        assert slot < _LOC_STRIDE
        return shard * _LOC_STRIDE + slot

    def lookup(self, key_hash: int) -> tuple[int, int] | None:
        return self.key_to_slot.get(key_hash)

    def release(self, key_hash: int) -> bool:
        """Free a slot (deactivation). The row data is left in place; the
        slot is reused by the next activation (fresh-init overwrites it)."""
        loc = self.key_to_slot.pop(key_hash, None)
        if loc is None:
            return False
        self.free[loc[0]].append(loc[1])
        self.device_dir.remove(key_hash)
        self.route_hash.pop(key_hash, None)
        self.uninit.discard(key_hash)
        return True

    def move_rows(self, keys, dest_shards) -> int:
        """Tick-fenced wrapper (see ``fence``): a shard move gathers and
        scatters ``state``, which must never interleave with an off-loop
        tick whose donated state is mid-dispatch. The key-level fencing
        contract (no pending/in-flight invocation for a moving key) stays
        the caller's job via ``VectorRuntime.pending_key_hashes``."""
        with self.fence:
            return self._move_rows(keys, dest_shards)

    def _move_rows(self, keys, dest_shards) -> int:
        """Live-migrate hashed-regime rows to new shards: extract the state
        rows, insert them at freshly-allocated slots on the destination
        shards, and atomically re-point the host directory maps + the
        on-device DeviceDirectory64 (the executor half of
        orleans_tpu.rebalance; the reference's activation repartitioning
        move, re-expressed as one batched gather+scatter).

        Keys not resident, already on their destination, or whose
        destination shard has no free slot are skipped. The caller is
        responsible for fencing (no pending invocation may hold a stale
        (shard, slot) for a moving key). Returns the number of rows moved;
        on device failure nothing is mutated (the copy is functional and
        the slot/directory bookkeeping only commits after it succeeds)."""
        src_sh, src_sl, dst_sh, dst_sl, moved_keys = [], [], [], [], []
        taken: dict[int, int] = {}  # dest shard → slots claimed this call
        seen: set[int] = set()  # a duplicate key would free its source
        # slot twice and leak a destination slot — skip repeats
        for key, dest in zip(keys, dest_shards):
            key, dest = int(key), int(dest)
            loc = self.key_to_slot.get(key)
            if key in seen or loc is None or loc[0] == dest or \
                    not (0 <= dest < self.n_shards):
                continue
            seen.add(key)
            n_taken = taken.get(dest, 0)
            if n_taken >= len(self.free[dest]):
                continue  # destination full: skip, never grow mid-move
            taken[dest] = n_taken + 1
            src_sh.append(loc[0])
            src_sl.append(loc[1])
            dst_sh.append(dest)
            # peek (no pop) so failure below leaves the free lists intact
            dst_sl.append(self.free[dest][-1 - n_taken])
            moved_keys.append(key)
        if not moved_keys:
            return 0
        idx = (jnp.asarray(src_sh, jnp.int32), jnp.asarray(src_sl, jnp.int32),
               jnp.asarray(dst_sh, jnp.int32), jnp.asarray(dst_sl, jnp.int32))
        new_state = _move_state_rows(self.state, *idx)
        if self.hits is not None:
            # counters travel with the row (the planner's next view must
            # see the key's heat at its new home, not a ghost at the old)
            moved_hits = self.hits[idx[0], idx[1]]
            self.hits = self.hits.at[idx[2], idx[3]].set(moved_hits) \
                .at[idx[0], idx[1]].set(0)
        if self.cost is not None:
            # charged cost travels with the row too (same ghost rule)
            moved_cost = self.cost[idx[0], idx[1]]
            self.cost = self.cost.at[idx[2], idx[3]].set(moved_cost) \
                .at[idx[0], idx[1]].set(0)
        self.state = new_state  # commit point
        for key, s_sh, s_sl, d_sh, d_sl in zip(
                moved_keys, src_sh, src_sl, dst_sh, dst_sl):
            self.free[d_sh].remove(d_sl)
            self.free[s_sh].append(s_sl)
            self.key_to_slot[key] = (d_sh, d_sl)
            self.device_dir.remove(key)
            self.device_dir.insert(key, self._encode_loc(d_sh, d_sl))
        return len(moved_keys)

    def note_route(self, key_hash: int, uniform_hash: int) -> None:
        """Record the routing hash for a (resident or incoming) hashed
        key — every entry point that knows the GrainId calls this."""
        if key_hash != uniform_hash:
            self.route_hash[key_hash] = uniform_hash

    def note_route_many(self, pairs) -> None:
        """Batched :meth:`note_route` — worker-process proxies buffer
        their (key_hash, uniform_hash) notes and ship them with the
        packed call record, so the ownership sweep sees the same routes
        it would have in-process (the pairs arrive pre-filtered:
        proxies only buffer key_hash != uniform_hash)."""
        self.route_hash.update(pairs)

    def unowned_keys(self, still_owned) -> list[int]:
        """Hashed-regime rows whose ring ownership left this silo (the
        membership-change sweep's release set). A row surviving on an
        ex-owner is a STALE COPY — if ownership ever returns, serving it
        would fork the key's state from what the interim owner wrote
        (and persisted); releasing forces recovery-on-first-touch from
        storage instead. The host-tier analog is activation deactivation
        on directory re-registration. Dense-regime rows are NOT swept
        (their multi-silo re-range is the explicit reshard_dense path).
        Keys with no recorded route hash use the key hash itself — exact
        for non-int keys (whose key_hash IS the uniform hash) and for
        every key that entered through a routed call; bulk-loaded int
        keys must have had note_route called (the bridge does)."""
        return [kh for kh in self.key_to_slot
                if not still_owned(self.route_hash.get(kh, kh))]

    # -- growth -----------------------------------------------------------
    def grow(self, new_capacity: int) -> None:
        """Grow every shard's slot pool (doubling amortizes recompiles —
        kernels specialize on capacity). Under the tick fence when the
        owning engine runs off-loop: growth swaps ``state`` wholesale and
        re-points the staging sink, so it must never interleave with a
        worker-side batch that read the old state (the worker would
        commit a pre-growth tree over the grown one and truncate every
        row above the old capacity)."""
        with self.fence:
            return self._grow(new_capacity)

    def _grow(self, new_capacity: int) -> None:
        new_capacity = max(new_capacity, self.capacity * 2)
        # round to power of two to bound the number of distinct kernel shapes
        new_capacity = 1 << (new_capacity - 1).bit_length()
        old = self.capacity
        for name, arr in self.state.items():
            dtype, shape = self.grain_class.STATE[name]
            grown = jnp.zeros(
                (self.n_shards, new_capacity + 1, *shape), dtype=dtype)
            # old sink row (index `old`) is junk; copy only real rows
            grown = grown.at[:, :old].set(arr[:, :old])
            self.state[name] = self._put(grown)
        if self.hits is not None:
            grown_hits = jnp.zeros((self.n_shards, new_capacity + 1),
                                   jnp.int32)
            self.hits = self._put(
                grown_hits.at[:, :old].set(self.hits[:, :old]))
        if self.cost is not None:
            grown_cost = jnp.zeros((self.n_shards, new_capacity + 1),
                                   jnp.int32)
            self.cost = self._put(
                grown_cost.at[:, :old].set(self.cost[:, :old]))
        for s in range(self.n_shards):
            self.free[s] = list(range(new_capacity - 1, old - 1, -1)) + self.free[s]
        self.capacity = new_capacity

    # -- host access (tests, persistence flush) ---------------------------
    def read_row(self, key_hash: int) -> dict[str, np.ndarray] | None:
        with self.fence:  # never materialize a donated-in-flight array
            return self._read_row(key_hash)

    def _read_row(self, key_hash: int) -> dict[str, np.ndarray] | None:
        loc = self.key_to_slot.get(key_hash)
        if loc is None:
            if 0 <= key_hash < self.dense_n:
                loc = (key_hash // self.dense_per_shard,
                       key_hash % self.dense_per_shard)
            else:
                return None
        shard, slot = loc
        return {k: np.asarray(v[shard, slot]) for k, v in self.state.items()}

    def snapshot(self) -> dict[str, np.ndarray]:
        """Full host copy of the state arrays (checkpoint path; orbax-style
        async checkpointing can hook here). Fenced against off-loop ticks
        — a donated in-flight state array cannot be materialized."""
        with self.fence:
            return {k: np.asarray(v) for k, v in self.state.items()}

    def restore(self, snap: dict[str, np.ndarray]) -> None:
        with self.fence:  # a worker batch mid-flight would commit over it
            for k, arr in snap.items():
                self.state[k] = self._put(jnp.asarray(arr))
