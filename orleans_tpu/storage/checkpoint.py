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
from ..core.serialization import serialize_rows
from ..dispatch.engine import MIN_BUCKET, _bucket
from ..observability.stats import NO_SPAN, StageSpan
from .core import ADOPT_ETAG, GrainStorage

if TYPE_CHECKING:
    from ..dispatch.engine import VectorRuntime

__all__ = ["VectorCheckpointer", "VectorStorageBridge"]

# a flushed row's vector field of up to this many values becomes a Python
# list; a wider one stays a numpy row (VectorStorageBridge._rows)
_LIST_MAX = 16
# a pass's rows come down in chunks of at most this many bytes, counted
# over every leaf of the table's state (VectorStorageBridge._chunk_rows)
_CHUNK_BYTES = 32 << 20


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


def _observe_laps(stats, laps: list) -> None:
    """A pass's deferred stage observations: each stage's seconds summed
    over the pass's chunks into one observation, every compile by itself
    (its count is what ``compile.<stage>`` is read for)."""
    sums: dict[str, float] = {}
    for key, secs in laps:
        if key.startswith("compile."):
            stats.observe(key, secs)
        else:
            sums[key] = sums.get(key, 0.0) + secs
    for key, secs in sums.items():
        stats.observe(key, secs)


class _ChunkEntries:
    """``write_many``'s entries for one landed chunk of a pass. Iterated,
    they are the (grain id, row, etag) triples every provider takes, each
    row built from the chunk's columns as it is asked for
    (``VectorStorageBridge._rows``). ``serialized()`` offers the same
    entries with each row as ``serialize`` encodes it — the chunk's blobs
    from one codec call on its columns (``serialize_rows``) — or None
    where the codec does not carry them; ``encoded`` says whether a
    provider took them."""

    __slots__ = ("ids", "etags", "host", "encoded")

    def __init__(self, ids: list, etags: list, host: dict[str, np.ndarray]):
        self.ids, self.etags, self.host = ids, etags, host
        self.encoded = False

    def __iter__(self):
        return zip(self.ids, VectorStorageBridge._rows(self.host,
                                                      len(self.ids)),
                   self.etags)

    def serialized(self):
        blobs = serialize_rows(self.host, len(self.ids), _LIST_MAX)
        if blobs is None:
            return None
        self.encoded = True
        return zip(self.ids, blobs, self.etags)


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
        # rows written while a later chunk of their pass was still to
        # come down (every chunk's but the last's): how far flush() runs
        # download and write at the same time
        self.pipelined = 0
        # rows written whose stored blob the provider took from the
        # chunk's one codec call (``_ChunkEntries.serialized``)
        self.encoded = 0
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

    @staticmethod
    def _chunk_rows(tbl) -> int:
        """Rows a chunk of a pass: the largest power of two whose rows,
        over every leaf of the table's state, stay under ``_CHUNK_BYTES``
        (512 rows of 33 KB; a pass of 16 B or 1 KB rows is one chunk)."""
        row = sum(math.prod(a.shape[2:]) * a.dtype.itemsize
                  for a in tbl.state.values())
        rows = max(MIN_BUCKET, _CHUNK_BYTES // max(row, 1))
        return 1 << (rows.bit_length() - 1)

    def _launch(self, tbl, shards: np.ndarray, slots: np.ndarray
                ) -> list[tuple[int, dict]]:
        """Launch the gather of the rows at (shards, slots) and ask for
        the host copies, a chunk at a time: ``(rows, device columns)`` per
        chunk, in order. Full chunks are ``_chunk_rows`` long, the tail is
        padded to the engine's power-of-two bucket (slot (0, 0) always
        exists; ``_land`` slices the padding off), so the programs a table
        compiles are ``_gather_rows`` at powers of two up to the chunk.
        Call under the tick fence: the launches are enqueued before any
        later tick and their results are arrays no tick donates, so what
        lands later is the rows as they stood here."""
        n = len(shards)
        chunk = self._chunk_rows(tbl)
        out = []
        for lo in range(0, max(n, 1), chunk):
            m = min(chunk, n - lo)
            index = np.zeros((2, _bucket(m)), np.int32)
            index[0, :m] = shards[lo:lo + m]
            index[1, :m] = slots[lo:lo + m]
            dev = _gather_rows(tbl.state, index)
            for a in dev.values():
                a.copy_to_host_async()
            out.append((m, dev))
        return out

    @staticmethod
    def _land(m: int, dev: dict) -> dict[str, np.ndarray]:
        """Wait for one launched chunk: its ``m`` rows as host columns."""
        return {f: np.asarray(a)[:m] for f, a in dev.items()}

    def _gather(self, tbl, shards: np.ndarray, slots: np.ndarray
                ) -> dict[str, np.ndarray]:
        """The rows at (shards, slots) as host columns: ``_launch`` and
        every chunk's ``_land`` in one synchronous call (the launches and
        waits of a pass, without its writes). Call under the tick fence."""
        chunks = [self._land(m, dev)
                  for m, dev in self._launch(tbl, shards, slots)]
        if len(chunks) == 1:
            return chunks[0]
        return {f: np.concatenate([c[f] for c in chunks])
                for f in chunks[0]}

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

    def _entries(self, kept: list[int], host: dict[str, np.ndarray]
                 ) -> _ChunkEntries:
        """``write_many``'s entries for the located keys of one chunk:
        (grain id, row, the etag this bridge remembers or ADOPT_ETAG)."""
        etag_of, gid = self._etags.get, self._grain_id
        return _ChunkEntries(
            [gid(k) for k in kept],
            [ADOPT_ETAG if (e := etag_of(k)) is None else e for k in kept],
            host)

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
        one columnar pass. Under the tick fence the keys are located and
        the device→host gather is launched, a chunk of ``_chunk_rows``
        rows at a time, with every chunk's host copy asked for
        (``_launch``): that is the snapshot, and the fence goes back
        there. Then, in chunk order: wait for a chunk's columns
        (``_land``), hand them to ``storage.write_many`` as entries that
        are rows built as the provider consumes them (``_rows``) or, for
        a provider that stores ``serialize``'s bytes (``MemoryStorage``),
        the rows' blobs from one codec call (``_ChunkEntries``), with each
        key's remembered etag (or ``ADOPT_ETAG`` where this bridge has none: a
        fresh bridge after a checkpoint restore has no etag memory but IS
        the legitimate writer — the device row is the truth being
        flushed) — while the runtime's own threads bring the later chunks
        down. A pass that fits one chunk is one launch at the power-of-two
        bucket, one wait and one write.

        What the loop does meanwhile is the provider's choice. One that
        overrides ``write_many`` (``MemoryStorage``) takes a chunk in a
        single synchronous pass: no coroutine or task per row, and
        nothing else runs on the loop from the launch to the last chunk's
        return. Every other provider gets the base class's default, one
        concurrent ``read``/``write`` per key, and the loop interleaves
        wherever those suspend (never, under an eager task factory, for a
        provider that does not await).

        The bookkeeping is done a chunk at a time, as its ``write_many``
        returns: etags remembered, conflicted rows released, failed keys
        re-marked. So a later chunk that raises (its download, the
        provider) leaves no written key with a stale etag: the caller
        re-marks the pass (``hosting.flush_all``) and the next pass
        rewrites it.

        Per-key failure isolation: keys whose activation slot is gone
        (released) are dropped with a log — there is no row left to
        persist — and keys whose storage write fails are re-marked dirty
        individually, so one bad key cannot wedge write-behind for the
        whole class. An etag conflict is not a failure: the row is
        released for rebuild from storage (``_release_conflicted``).
        Failures re-raise (after re-marking) when ``strict``
        is set OR when the runtime has no dirty tracking to hold the
        retry — a standalone bridge must never report silent success.

        With the runtime's stage metrics on, three observations of unit
        ``flush=<n>`` a pass: flush.locate (under the fence),
        flush.gather (loop time obtaining rows: the launches under the
        fence and the waits for the chunks, summed) and flush.write
        (building the rows or their blobs and the provider's
        ``write_many``, summed over the chunks)."""
        keys = np.asarray(keys if isinstance(keys, np.ndarray)
                          else list(keys), np.int64)
        if not keys.size:
            return 0
        tbl = self.runtime.table(self.grain_class)
        st = self.runtime.stats
        self.flushes += 1
        n = self.flushes
        # a chunk's spans defer into ``laps``: one observation a pass
        laps: list = []

        def span(stage: str, **unit):
            return StageSpan(st, stage, sink=laps, flush=n, **unit) \
                if st is not None else NO_SPAN

        try:
            # under the tick fence: the gather reads state rows, which
            # must not race an off-loop tick that has the state donated
            with self.runtime.tick_fence():
                with span("flush.locate"):
                    kept, shards, slots = self._locate(keys,
                                                       drop_missing=True)
                if not kept:
                    return 0
                with span("flush.gather", rows=len(kept)):
                    chunks = self._launch(tbl, shards, slots)
            chunks.reverse()  # popped in launch order, let go as written
            etags = self._etags
            failed, first, written, lo = [], None, 0, 0
            while chunks:
                m, dev = chunks.pop()
                with span("flush.gather"):
                    host = self._land(m, dev)
                part = kept[lo:lo + m]
                lo += m
                with span("flush.write", nest=False):
                    entries = self._entries(part, host)
                    results = await self.storage.write_many(
                        self.grain_type, entries)
                wrote, bad = 0, []
                for key, r in zip(part, results):
                    if not isinstance(r, BaseException):
                        etags[key] = r
                        wrote += 1
                    elif isinstance(r, InconsistentStateError):
                        self._release_conflicted(tbl, key)
                    else:
                        bad.append(key)
                        if first is None:
                            first = r
                if bad:
                    self.runtime._mark_dirty(self.grain_class, bad)
                    failed += bad
                written += wrote
                if entries.encoded:
                    self.encoded += wrote
                if chunks:  # a later chunk was still to come down
                    self.pipelined += wrote
        finally:
            if laps:
                _observe_laps(st, laps)
        if failed:
            logging.getLogger("orleans.vector").warning(
                "write-behind: %d/%d key writes failed (re-marked): %r",
                len(failed), len(kept), first)
            if strict or not self.runtime.track_dirty:
                # no retry mechanism will see the re-mark (or the caller
                # demanded completeness — the final stop() drain): surface
                # the failure instead of reporting partial success
                raise first
        return written

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
