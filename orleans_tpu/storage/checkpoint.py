"""Checkpoint/resume for the device tier: orbax table snapshots +
write-behind per-actor persistence.

The reference has no cluster-wide checkpoint — durable truth is per-grain
storage (Grain<TState> via StateStorageBridge.cs:11,49,80,107) plus the
membership table (SURVEY.md §5 "Checkpoint / resume"). The TPU build keeps
that contract and adds the device-tier analog the survey prescribes:
sharded activation-state arrays periodically flushed via orbax-style async
checkpointing, plus a write-behind bridge that maps individual VectorGrain
rows onto the ordinary ``GrainStorage`` providers (the "TpuGrainStorage
IStorageProvider" of the north-star design) so a single actor's state
survives restart even without a full table snapshot.

Two recovery paths:
* **whole-silo resume** — ``VectorCheckpointer.save(step)`` every N ticks
  (synchronous D2H copy + write — see __init__ on why not async); after
  restart ``restore()`` rebuilds every table + its host bookkeeping.
* **per-actor lazy resume** — ``VectorStorageBridge.flush(keys)`` write-
  behind after ticks; on re-activation ``load(keys)`` scatters stored rows
  back into the table (the virtual-actor guarantee: the next call finds
  the state, wherever the actor lands).
"""

from __future__ import annotations

import logging
import math
from typing import TYPE_CHECKING, Iterable

import jax
import numpy as np

from ..core.errors import InconsistentStateError
from ..core.ids import GrainId, GrainType
from ..dispatch.engine import _bucket
from ..observability.stats import NO_SPAN, StageSpan
from .core import ADOPT_ETAG, GrainStorage

if TYPE_CHECKING:
    from ..dispatch.engine import VectorRuntime

__all__ = ["VectorCheckpointer", "VectorStorageBridge"]

# a flushed row's vector field of up to this many values becomes a Python
# list; a wider one stays a numpy row (VectorStorageBridge._rows)
_LIST_MAX = 16


@jax.jit
def _gather_rows(state: dict, index: jax.Array) -> dict:
    """Rows ``a[index[0], index[1]]`` of every field of a table's state
    tree, as one program per (table, index length): the write-behind
    flush pads ``index`` to a power of two, so the programs a process
    compiles follow the size buckets and not each distinct dirty count."""
    shards, slots = index[0], index[1]
    return {f: a[shards, slots] for f, a in state.items()}


def _table_meta(tbl) -> dict:
    return {
        "capacity": tbl.capacity,
        "dense_n": tbl.dense_n,
        "dense_per_shard": tbl.dense_per_shard,
        "dense_active": [int(i) for i in np.flatnonzero(tbl.dense_active)],
        "key_to_slot": {str(k): list(v) for k, v in tbl.key_to_slot.items()},
        "route_hash": {str(k): int(v) for k, v in tbl.route_hash.items()},
        "free": [list(f) for f in tbl.free],
    }


def _apply_meta(tbl, meta: dict) -> None:
    # capacity is taken from the checkpoint verbatim (the state arrays are
    # replaced wholesale right after; grow() would only churn buffers)
    tbl.capacity = meta["capacity"]
    tbl.dense_n = meta["dense_n"]
    tbl.dense_per_shard = meta["dense_per_shard"]
    tbl.dense_active = np.zeros(tbl.dense_n, dtype=bool)
    if meta["dense_active"]:
        tbl.dense_active[np.asarray(meta["dense_active"], int)] = True
    tbl.key_to_slot = {int(k): tuple(v)
                       for k, v in meta["key_to_slot"].items()}
    tbl.route_hash = {int(k): int(v)
                      for k, v in meta.get("route_hash", {}).items()}
    tbl.free = [list(f) for f in meta["free"]]


class VectorCheckpointer:
    """Orbax-backed snapshot of every ShardedActorTable in a VectorRuntime
    (state arrays + host bookkeeping), with retention and async writes."""

    def __init__(self, runtime: "VectorRuntime", directory: str,
                 max_to_keep: int = 3):
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self.runtime = runtime
        # synchronous writes: the D2H copy (donation-safety, _state_tree)
        # is the dominant sync cost anyway, and orbax's async writer
        # shares process-global executors that race across manager
        # restarts (the in-process resume scenario TestCluster exercises)
        self.manager = ocp.CheckpointManager(
            directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=False))

    def _state_tree(self) -> dict:
        # host copies, not device arrays: tick kernels DONATE the state
        # buffers (in-place updates), so a device array handed to the
        # writer can be deleted mid-save by the very next tick. The D2H
        # copy is the part that must happen before another tick runs.
        return {cls.__name__:
                {f: np.asarray(a) for f, a in tbl.state.items()}
                for cls, tbl in self.runtime.tables.items()}

    def capture(self) -> tuple[dict, dict]:
        """Donation-safe snapshot (synchronous D2H copy + bookkeeping).
        Taken under the engine's tick fence: with the off-loop tick
        worker, "runs on the loop" is no longer enough — a worker-side
        batch may have the state donated mid-dispatch, so the copy
        serializes against it. The returned tree is plain numpy — write
        it from any thread."""
        with self.runtime.tick_fence():
            state = self._state_tree()
            meta = {cls.__name__: _table_meta(tbl)
                    for cls, tbl in self.runtime.tables.items()}
        return state, meta

    def write(self, step: int, captured: tuple[dict, dict]) -> None:
        """Persist a captured snapshot (thread-safe; hosting runs this in
        a worker thread so the silo event loop keeps serving)."""
        ocp = self._ocp
        state, meta = captured
        self.manager.wait_until_finished()
        self.manager.save(step, args=ocp.args.Composite(
            state=ocp.args.StandardSave(state),
            meta=ocp.args.JsonSave(meta)))

    def save(self, step: int) -> None:
        """capture() + write() in one synchronous call."""
        self.write(step, self.capture())

    def wait(self) -> None:
        self.manager.wait_until_finished()

    def latest_step(self) -> int | None:
        return self.manager.latest_step()

    def restore(self, step: int | None = None) -> int:
        """Rebuild every registered table from the checkpoint. The runtime
        must have the same grain classes registered (the schema IS the
        codegen contract; mismatch raises)."""
        ocp = self._ocp
        step = self.manager.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError("no checkpoint to restore")
        by_name = {cls.__name__: tbl
                   for cls, tbl in self.runtime.tables.items()}
        # phase 1: bookkeeping only — validates registration before orbax
        # compares state trees
        meta = self.manager.restore(step, args=ocp.args.Composite(
            meta=ocp.args.JsonRestore()))["meta"]
        missing = set(meta) - set(by_name)
        if missing:
            raise KeyError(
                f"checkpoint has tables {sorted(missing)} not registered "
                f"on this runtime — register the grain classes first")
        # template shapes come from the checkpoint's own capacity, so a
        # runtime built with a different capacity_per_shard still restores
        template = {}
        for name in meta:
            tbl = by_name[name]
            cap = meta[name]["capacity"]
            template[name] = {
                f: jax.ShapeDtypeStruct(
                    (tbl.n_shards, cap + 1, *shape), dtype)
                for f, (dtype, shape) in tbl.grain_class.STATE.items()}
        state = self.manager.restore(step, args=ocp.args.Composite(
            state=ocp.args.StandardRestore(template)))["state"]
        for name in meta:
            tbl = by_name[name]
            _apply_meta(tbl, meta[name])
            tbl.restore({k: np.asarray(v) for k, v in state[name].items()})
        return step

    def close(self) -> None:
        self.manager.close()


class VectorStorageBridge:
    """Write-behind per-actor persistence for one VectorGrain class: rows
    flushed to / loaded from an ordinary ``GrainStorage`` provider, with
    the same etag discipline host grains get from StateStorageBridge."""

    def __init__(self, runtime: "VectorRuntime", grain_class: type,
                 storage: GrainStorage):
        self.runtime = runtime
        self.grain_class = grain_class
        self.storage = storage
        self.grain_type = grain_class.__name__
        self._gtype = GrainType.of(self.grain_type)
        self._etags: dict[int, str | None] = {}
        self._ids: dict[int, GrainId] = {}  # beside _etags: one per key seen
        self.storage_conflicts = 0
        self.flushes = 0  # flush() calls: the stage spans' unit of work
        # observed, not configured: does the provider bring its own
        # write_many, or does a flush go through the per-key default?
        self.batched = (type(storage).write_many
                        is not GrainStorage.write_many)

    def _grain_id(self, key: int) -> GrainId:
        gid = self._ids.get(key)
        if gid is None:
            gid = self._ids[key] = GrainId.for_grain(self._gtype, int(key))
        return gid

    def _locate(self, keys, drop_missing: bool = False
                ) -> tuple[list[int], np.ndarray, np.ndarray]:
        """Resolve keys to (surviving_keys, shards, slots). Keys with no
        activation slot raise KeyError, or are dropped with a log when
        ``drop_missing`` (a released slot has no row left to persist).
        Dense keys resolve as columns; only hashed keys are looked up one
        by one."""
        tbl = self.runtime.table(self.grain_class)
        k = np.asarray(keys, np.int64).reshape(-1)
        per = max(tbl.dense_per_shard, 1)
        dense = (k >= 0) & (k < tbl.dense_n)
        shards = np.where(dense, k // per, 0).astype(np.int32)
        slots = np.where(dense, k % per, 0).astype(np.int32)
        keep = np.ones(len(k), bool)
        lookup = tbl.lookup
        for i in np.flatnonzero(~dense).tolist():
            loc = lookup(int(k[i]))
            if loc is not None:
                shards[i], slots[i] = loc
            elif drop_missing:
                logging.getLogger("orleans.vector").warning(
                    "write-behind: key %d has no activation slot; dropping",
                    k[i])
                keep[i] = False
            else:
                raise KeyError(f"key {k[i]} has no activation slot")
        if not keep.all():
            k, shards, slots = k[keep], shards[keep], slots[keep]
        return k.tolist(), shards, slots

    def _gather(self, tbl, shards: np.ndarray, slots: np.ndarray
                ) -> dict[str, np.ndarray]:
        """The rows at (shards, slots) as host columns: one compiled
        gather over the whole state tree, its index array padded to the
        engine's power-of-two bucket (slot (0, 0) always exists; the
        padding rows are sliced off on the host). Call under the tick
        fence."""
        n = len(shards)
        index = np.zeros((2, _bucket(n)), np.int32)
        index[0, :n] = shards
        index[1, :n] = slots
        host = jax.device_get(_gather_rows(tbl.state, index))
        return {f: v[:n] for f, v in host.items()}

    @staticmethod
    def _rows(host: dict[str, np.ndarray], n: int):
        """Columns to per-row state dicts of Python-native values, lazily:
        one ``tolist()`` per scalar field up front, and each row's dict
        (and the list of a vector field) built when the consumer asks for
        it, so that a consumer that lets go of a row before it takes the
        next keeps the collector out of the pass — thousands of
        containers held at once are thousands of survivors, promoted and
        traversed again. Bool, integer and float dtypes survive the trip
        to Python and back bit for bit (f32 -> float -> f32 and
        i32 -> int -> i32 are exact; a signalling NaN comes back quiet);
        a field of any other dtype keeps its numpy values, and so does a
        vector field wider than ``_LIST_MAX`` values: its row stays one
        numpy row (a view of the pass's column), which the wire codec
        writes as one buffer, where a list would be a Python object and
        a tag per value."""
        if not host:
            return ({} for _ in range(n))
        fields = tuple(host)
        cols = [c if c.dtype.kind not in "biuf"
                or math.prod(c.shape[1:]) > _LIST_MAX
                else c.tolist() if c.ndim == 1
                else map(np.ndarray.tolist, c)
                for c in host.values()]
        return (dict(zip(fields, vals)) for vals in zip(*cols))

    def _entries(self, kept: list[int], host: dict[str, np.ndarray]):
        """``write_many``'s entries for the located keys, one at a time:
        (grain id, row, the etag this bridge remembers or ADOPT_ETAG)."""
        etag_of, gid = self._etags.get, self._grain_id
        for key, row in zip(kept, self._rows(host, len(kept))):
            etag = etag_of(key)
            yield gid(key), row, ADOPT_ETAG if etag is None else etag

    def _release_conflicted(self, tbl, key: int) -> None:
        # another silo flushed this key since our last write: an
        # ownership move happened (partition-era vote, failover,
        # re-range). Reference semantics
        # (InsideRuntimeClient.cs:390-402): the conflicted
        # activation DEACTIVATES and rebuilds from storage on
        # next touch — never overwrite. Overwriting would let a
        # stale ex-owner silently REVERT durable state the live
        # owner wrote (fatal once the key goes quiet: no later
        # flush corrects it); releasing loses at most this
        # silo's not-yet-durable tail, which is the documented
        # write-behind loss window. The stale etag must also be
        # dropped or it would wedge this key's flushes forever
        self.storage_conflicts += 1
        self._etags.pop(key, None)
        if 0 <= key < tbl.dense_n:
            tbl.dense_active[key] = False
        else:
            tbl.release(key)
        logging.getLogger("orleans.vector").info(
            "write-behind: etag conflict on key %d — row "
            "released for rebuild from storage", key)

    async def flush(self, keys: Iterable[int], strict: bool = False) -> int:
        """Write-behind: persist the current device rows for ``keys`` in
        one columnar pass: one compiled device→host gather (``_gather``),
        the columns turned into rows as the provider consumes them
        (``_rows``), and one ``storage.write_many`` with each key's
        remembered etag (or ``ADOPT_ETAG`` where this bridge has none: a
        fresh bridge after a checkpoint restore has no etag memory but IS
        the legitimate writer — the device row is the truth being
        flushed).

        What the loop does meanwhile is the provider's choice. One that
        overrides ``write_many`` (``MemoryStorage``) takes the whole
        batch in a single synchronous pass: no coroutine or task per row,
        and nothing else runs on the loop until it returns. Every other
        provider gets the base class's default, one concurrent
        ``read``/``write`` per key, and the loop interleaves wherever
        those suspend (never, under an eager task factory, for a
        provider that does not await).

        Per-key failure isolation: keys whose activation slot is gone
        (released) are dropped with a log — there is no row left to
        persist — and keys whose storage write fails are re-marked dirty
        individually, so one bad key cannot wedge write-behind for the
        whole class. An etag conflict is not a failure: the row is
        released for rebuild from storage (``_release_conflicted``).
        Failures re-raise (after re-marking) when ``strict``
        is set OR when the runtime has no dirty tracking to hold the
        retry — a standalone bridge must never report silent success.

        With the runtime's stage metrics on, three spans of unit
        ``flush=<n>``: flush.locate and flush.gather (both holding the
        fence, so ticks wait for them) and flush.write (building the
        rows and the provider's ``write_many``)."""
        keys = np.asarray(keys if isinstance(keys, np.ndarray)
                          else list(keys), np.int64)
        if not keys.size:
            return 0
        tbl = self.runtime.table(self.grain_class)
        st = self.runtime.stats
        self.flushes += 1
        n = self.flushes
        # under the tick fence: the gather materializes state rows, which
        # must not race an off-loop tick that has the state donated
        with self.runtime.tick_fence():
            with StageSpan(st, "flush.locate", flush=n) \
                    if st is not None else NO_SPAN:
                kept, shards, slots = self._locate(keys, drop_missing=True)
            if not kept:
                return 0
            with StageSpan(st, "flush.gather", flush=n, rows=len(kept)) \
                    if st is not None else NO_SPAN:
                host = self._gather(tbl, shards, slots)

        with StageSpan(st, "flush.write", nest=False, flush=n) \
                if st is not None else NO_SPAN:
            results = await self.storage.write_many(
                self.grain_type, self._entries(kept, host))
        etags = self._etags
        failed, first, conflicts = [], None, 0
        for key, r in zip(kept, results):
            if not isinstance(r, BaseException):
                etags[key] = r
            elif isinstance(r, InconsistentStateError):
                self._release_conflicted(tbl, key)
                conflicts += 1
            else:
                failed.append(key)
                if first is None:
                    first = r
        if failed:
            self.runtime._mark_dirty(self.grain_class, failed)
            logging.getLogger("orleans.vector").warning(
                "write-behind: %d/%d key writes failed (re-marked): %r",
                len(failed), len(kept), first)
            if strict or not self.runtime.track_dirty:
                # no retry mechanism will see the re-mark (or the caller
                # demanded completeness — the final stop() drain): surface
                # the failure instead of reporting partial success
                raise first
        return len(kept) - len(failed) - conflicts

    async def load(self, keys: Iterable[int],
                   errors: dict | None = None) -> list[int]:
        """Resume: read stored rows and scatter them into the table.
        Returns the keys that had persisted state (missing keys keep
        their fresh-init state — the lazy-recreate contract).

        The keys are read in bulk: one ``storage.read_many`` for the
        whole list (a single synchronous pass for a provider that
        overrides it, one concurrent ``read`` per key otherwise), and the
        rows found are scattered under one hold of the tick fence.

        Per-key failure: a key whose read raised is left as it was
        (fresh, no etag remembered) and its exception is put into
        ``errors[key]``; the other keys' rows are restored all the same.
        Without an ``errors`` dict to hold them the first such exception
        is raised before anything is scattered — a caller that cannot
        see a failure must never be told the pass succeeded."""
        keys = [int(k) for k in keys]
        if not keys:
            return []
        tbl = self.runtime.table(self.grain_class)
        rows = await self.storage.read_many(
            self.grain_type, [self._grain_id(k) for k in keys])
        found = []
        for k, r in zip(keys, rows):
            if isinstance(r, BaseException):
                if errors is None:
                    raise r
                errors[k] = r
            elif r[0] is not None:
                found.append((k, r[0], r[1]))
        if not found:
            return []
        for k, _, e in found:
            self._etags[k] = e
        fkeys = [k for k, _, _ in found]
        # claim slots for hashed keys that have no activation yet, and
        # record their routing hash (ownership sweeps need it for rows
        # that never entered through a routed call)
        for k in fkeys:
            if not (0 <= k < tbl.dense_n):
                if tbl.lookup(k) is None:
                    tbl.lookup_or_allocate(k)
                tbl.note_route(k, self._grain_id(k).uniform_hash)
        if tbl.dense_active.size:
            dense = [k for k in fkeys if 0 <= k < tbl.dense_n]
            if dense:
                tbl.dense_active[np.asarray(dense, int)] = True
        _, shards, slots = self._locate(fkeys)
        # under the tick fence: the per-field scatter reads and replaces
        # state arrays, which must not interleave with an off-loop tick
        # (the tick would commit a tree that predates — and erases — the
        # rehydrated rows)
        with self.runtime.tick_fence():
            for f, arr in tbl.state.items():
                # in the table's own dtype: a record holds Python-native
                # values (exact for the dtypes _rows converts) or, from
                # before the columnar flush, numpy ones
                vals = np.stack([np.asarray(s[f], arr.dtype)
                                 for _, s, _ in found])
                tbl.state[f] = tbl._put(arr.at[shards, slots].set(
                    jax.numpy.asarray(vals)))
        return fkeys
