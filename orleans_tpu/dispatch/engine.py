"""The tick engine: coalesce VectorGrain invocations into batched kernels.

This replaces the reference's hot path — IncomingMessageAgent → Dispatcher →
scheduler turn → invoke (SURVEY.md §3.3) — with a vectorized dispatch tick
(§7): every event-loop iteration, all pending invocations per (class, method)
are packed into fixed-bucket batches and executed as ONE pjit'ed kernel over
the sharded actor table:

    gather rows → fresh-init (on-device activation) → vmapped handler
    → masked scatter (skipped for read-only methods)

run under ``shard_map`` so each mesh shard touches only its slot block
(gathers/scatters are shard-local; no cross-device traffic inside a tick —
cross-shard *messages* are the transport layer's job).

Turn-semantics guarantee: within a tick at most one message per activation;
same-activation conflicts defer to the next tick (the mailbox ordering of
``ActivationData.EnqueueMessage``, ActivationData.cs:566).

Static-shape discipline: batch buckets are powers of two with a floor, so
XLA compiles O(log max-batch) kernel variants per method, all reused across
ticks (no data-dependent shapes; SURVEY.md §7 hard parts #3).
"""

from __future__ import annotations

import asyncio
import logging
import queue as _queue
import threading
import time
import warnings
import weakref
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..compile_cache import ensure_compile_cache
from ..core.ids import GrainId
from ..observability.stats import COUNT_BOUNDS
from ..observability.stats import EXCHANGE_STATS as _EXCH
from ..observability.stats import INGEST_STATS as _INGEST
from ..observability.stats import MESH_STATS as _MESH
from ..observability.stats import NO_SPAN, StageSpan
from ..parallel.mesh import SILO_AXIS, make_mesh
from .table import ShardedActorTable
from .vector_grain import ActorMethod, SendingMethod, VectorGrain

_QUEUE_WAIT = _INGEST["queue_wait"]
_TICK = _INGEST["tick"]
_MESSAGES = _INGEST["messages"]
_TRANSFER_JOBS = _INGEST["transfer_jobs"]
_TRANSFER_PUTS = _INGEST["transfer_puts"]
_TRANSFER_BYTES = _INGEST["transfer_bytes"]
_JOB_LANES = _MESH["lanes"]
_JOB_MAX_SHARD_LANES = _MESH["max_shard_lanes"]
_JOB_SLOTS = _MESH["slots"]
# the sink's keys that replay as counter increments, not observations
_COUNTERS = frozenset(
    (_MESSAGES, _TRANSFER_JOBS, _TRANSFER_PUTS, _TRANSFER_BYTES,
     _JOB_LANES, _JOB_MAX_SHARD_LANES, _JOB_SLOTS,
     *(v for k, v in _EXCH.items() if k != "depth")))
_DEPTH = _EXCH["depth"]   # replays into a COUNT_BOUNDS histogram
_WORKER_QUEUE = "engine.worker_queue.seconds"
_DEFERRED = "engine.deferred"               # counter: msgs deferred >= once
_DEFER_WAIT = "engine.defer_wait.seconds"   # first deferral -> claimed
_HELD = "engine.held"                       # counter: msgs held >= once
_COMPLETE_HOP = "engine.complete_hop.seconds"
_REPLIES_BATCHED = "engine.replies_batched"  # counter: served replies
_WORKER_IDLE = "engine.worker_idle.seconds"  # previous job's end -> dequeue
_IDLE_PIECE_S = 0.05  # a longer wait for a job is posted in pieces of this
# worker-side ledger stamp (cost attribution, observability.ledger): the
# payload rides the job's deferred-stats list and replays loop-side in
# _complete_job — the CostLedger is loop-confined like the registries
_LEDGER = object()

log = logging.getLogger("orleans.vector")

__all__ = ["VectorRuntime", "VectorActorRef"]

MIN_BUCKET = 8

# How many of one (class, method) group's jobs the off-loop worker may
# hold, unresolved, before ``_tick`` stops claiming that group. One: a
# job costs the worker milliseconds whatever it carries, so every job
# should take all that arrived while the one before it ran. At 2 (one
# running, one ready behind it, the ``_StagingSet`` pair's depth) the
# second job is claimed the moment anything is pending and carries next
# to nothing, so wide and narrow jobs alternate; read on the chip
# (PERF.md §6, PR 29), 2 lost to 1 in every cell: a third of the calls/s
# where one group carries single calls, a third of the median latency
# under two groups, nothing gained where frames arrive in batches — the
# worker's wait through the completion hop is time the loop, which
# shares the interpreter lock with it, uses. A constant, not an option:
# nothing a deployment knows changes it.
_HANDOFF_DEPTH = 1

# The most messages one source shard sends one destination shard in one
# pass of a sending job's exchange (``_exchange``): a pass's receive tick
# is ``n_shards * capacity`` lanes, and with rows of tens of KB its
# gather and scatter temporaries are that many rows — 4,096 lanes of a
# 32 KB row are 134 MB a shard. What does not fit goes in the next pass.
_EXCHANGE_CAP = 1024
# the exchange's own payload fields: "initialise this receiver's row
# first", and the message's turn: its rank among the messages of its pass
# for the same receiver, in lane order (``_turns``)
_FRESH = "__fresh__"
_TURN = "__turn__"


def _turns(keys: np.ndarray, mask: np.ndarray) -> tuple[np.ndarray, int, int]:
    """Each message's turn in one pass of the exchange: its rank among the
    pass's messages (``mask``) for the same receiver, counted in lane
    order — the order ``route`` delivers them in: source shard, then the
    source's lanes. Returns the turns (``[n_shards, lanes]`` int32, 0 off
    the mask), the pass's depth (the most messages one receiver got) and
    its distinct receivers."""
    at = np.flatnonzero(mask)             # row-major: source shard, lane
    k = keys.ravel()[at]
    order = np.argsort(k, kind="stable")  # by receiver, lane order kept
    ks = k[order]
    first = np.ones(len(ks), bool)
    first[1:] = ks[1:] != ks[:-1]
    pos = np.arange(len(ks))
    rank = pos - np.maximum.accumulate(np.where(first, pos, 0))
    turn = np.zeros(keys.size, np.int32)
    turn[at[order]] = rank
    depth = int(rank.max()) + 1 if len(rank) else 0
    return turn.reshape(keys.shape), depth, int(first.sum())


def _bucket(n: int) -> int:
    return max(MIN_BUCKET, 1 << max(0, (n - 1).bit_length()))


async def join_poll(reduce_once, need: int, timeout: float | None,
                    poll: float) -> int:
    """The ONE join_when poll driver, shared by the engine surface
    (local reductions) and the client surface (one envelope per poll):
    await ``reduce_once()`` — a sum-reduction over the key set — until
    the first leaf reaches ``need`` or ``timeout`` elapses. Extracted so
    readiness semantics (leaf extraction, deadline handling) cannot
    drift between the two surfaces of the same primitive."""
    loop = asyncio.get_running_loop()
    deadline = None if timeout is None else loop.time() + timeout
    while True:
        val = await reduce_once()
        ready = 0
        if val is not None:
            leaves = jax.tree_util.tree_leaves(val)
            ready = int(leaves[0]) if leaves else 0
        if ready >= need:
            return ready
        if deadline is not None and loop.time() >= deadline:
            raise asyncio.TimeoutError(
                f"join_when: {ready}/{need} ready after {timeout}s")
        await asyncio.sleep(poll)


def _validate_args(cls: type, method: str, schema: dict, args: dict) -> None:
    missing = set(schema) - set(args)
    extra = set(args) - set(schema)
    if missing or extra:
        raise TypeError(
            f"{cls.__name__}.{method} args mismatch: "
            f"missing {sorted(missing)}, unexpected {sorted(extra)} "
            f"(schema: {sorted(schema)})")


class _DensePlan:
    """Cached batch layout for a recurring dense key set. The constant batch
    operands (slots/key-hashes/valid mask/zero fresh mask) are uploaded to
    device once and reused every tick — only the message payload crosses the
    host↔device boundary per round."""

    __slots__ = ("keys", "order", "inv", "sorted_shard", "lane_sorted", "B",
                 "slots_b", "valid_b", "khash_b", "_dev", "identity", "counts")

    def __init__(self, keys, order, inv, sorted_shard, lane_sorted, B,
                 slots_b, valid_b, khash_b, identity=False, counts=None):
        self.keys = keys
        self.order = order
        self.inv = inv
        self.sorted_shard = sorted_shard
        self.lane_sorted = lane_sorted
        self.B = B
        self.slots_b = slots_b
        self.valid_b = valid_b
        self.khash_b = khash_b
        self._dev = None
        # identity plans (keys == 0..M-1 under the block-wise dense mapping)
        # repack by contiguous slice copies instead of fancy indexing — the
        # zero-shuffle bulk path
        self.identity = identity
        self.counts = counts

    def pack(self, x: np.ndarray, dtype, shape) -> np.ndarray:
        """[M, ...] caller-order payload → [n_shards, B, ...] batch buffer."""
        n = self.valid_b.shape[0]
        buf = np.zeros((n, self.B, *shape), dtype=dtype)
        if self.identity:
            off = 0
            for s in range(n):
                c = self.counts[s]
                buf[s, :c] = x[off:off + c]
                off += c
        else:
            buf[self.sorted_shard, self.lane_sorted] = \
                np.asarray(x, dtype=dtype)[self.order]
        return buf

    def device_operands(self, put):
        if self._dev is None:
            self._dev = (
                put(jnp.asarray(self.slots_b)),
                put(jnp.asarray(self.khash_b)),
                put(jnp.asarray(self.valid_b)),
                put(jnp.zeros(self.valid_b.shape, jnp.bool_)),
            )
        return self._dev

    def unpack(self, results):
        """[n_shards, B, ...] device results → [M, ...] host rows in the
        caller's original key order (synchronizes)."""
        def one(a):
            a = np.asarray(a)
            if self.identity:
                return np.concatenate(
                    [a[s, :c] for s, c in enumerate(self.counts)])
            return a[self.sorted_shard, self.lane_sorted][self.inv]
        return jax.tree_util.tree_map(one, results)


class _PackedLayout(NamedTuple):
    """Where each operand of a tick lies in one shard's row of the
    packed staging buffer. The row is ``words`` int32 words, one block
    of ``B`` lanes per operand, blocks back to back: slots, key hashes,
    fresh, valid, then every argument field of the method's schema by
    name (``names``). ``fields`` holds one ``(dtype, shape, offset,
    count)`` a block, offset and count in words: ``B`` is a multiple of
    8, so every block is whole words and starts on an 8-byte boundary.
    A dtype is the one the device sees: with x64 off an 8-byte schema
    dtype is staged as its 4-byte twin, so the fill loop's assignment
    does the narrowing ``jnp.asarray`` used to do. Hashable: it is part
    of the packed kernel's cache key."""

    B: int
    words: int
    names: tuple
    fields: tuple


def _packed_layout(B: int, schema: dict) -> _PackedLayout:
    assert B % 8 == 0, B  # _bucket's floor: blocks are whole words
    args = sorted(schema.items())
    cols = [(np.int32, ()), (np.int32, ()), (np.bool_, ()), (np.bool_, ())]
    fields, off = [], 0
    for dtype, shape in cols + [v for _f, v in args]:
        dt = np.dtype(jax.dtypes.canonicalize_dtype(dtype))
        shape = tuple(int(d) for d in shape)
        count = B * dt.itemsize * int(np.prod(shape, dtype=np.int64)) // 4
        fields.append((dt, shape, off, count))
        off += count
    return _PackedLayout(B, off, tuple(f for f, _v in args), tuple(fields))


def _unpack_words(words, dt: np.dtype, shape: tuple):
    """``[..., k]`` int32 words → ``[..., *shape]`` of ``dt``, bits
    reinterpreted and never converted (a bool is its byte, 0 or 1)."""
    if dt == np.bool_:
        return _unpack_words(words, np.dtype(np.uint8), shape) != 0
    lead = words.shape[:-1]
    if dt.kind == "c":
        parts = _unpack_words(words, np.dtype(f"f{dt.itemsize // 2}"),
                              (*shape, 2))
        return jax.lax.complex(parts[..., 0], parts[..., 1])
    if dt.itemsize > 4:
        words = words.reshape(*lead, -1, dt.itemsize // 4)
    return jax.lax.bitcast_convert_type(words, dt).reshape(*lead, *shape)


class _StagingSet:
    """One preallocated host staging buffer for a (class, method) batch
    bucket: ``packed``, ``[n_shards, words]`` int32, which crosses to the
    device in ONE transfer a job (``_PackedLayout`` has the row's plan;
    the packed kernel unpacks it). The batch operands ``slots`` /
    ``khash`` / ``fresh`` / ``valid`` and one ``args[f]`` per schema
    field are numpy VIEWS into it, ``[n_shards, B, ...]`` each, so
    filling, ``reset`` and the hit / cost folds read and write the
    operands by name. Two sets per bucket alternate between "filling
    from ingress" and "donated to the tick kernel" (see
    ``VectorRuntime._staging_acquire``), so steady-state ingest never
    allocates — and never touches a buffer whose device upload could
    still be in flight."""

    __slots__ = ("packed", "layout", "slots", "khash", "fresh", "valid",
                 "args", "used", "sink", "scalars", "arrays")

    def __init__(self, n: int, B: int, sink: int, schema: dict):
        lay = self.layout = _packed_layout(B, schema)
        self.packed = np.zeros((n, lay.words), dtype=np.int32)
        views = []
        for dt, shape, off, _count in lay.fields:
            strides = [dt.itemsize]
            for d in reversed(shape):
                strides.append(strides[-1] * d)
            views.append(np.ndarray(
                (n, B, *shape), dt, self.packed, off * 4,
                (lay.words * 4, *reversed(strides))))
        self.slots, self.khash, self.fresh, self.valid = views[:4]
        self.slots[:] = sink
        self.args = dict(zip(lay.names, views[4:]))
        # the fill loop's two kinds of field: scalars are assigned as
        # they come; an array field may arrive as ``bytes`` (the wire's
        # native tag for a byte string), which numpy cannot assign to a
        # row, so those carry what ``np.frombuffer`` needs
        self.scalars = [(f, a) for f, a in self.args.items() if a.ndim == 2]
        self.arrays = [(f, a, a.dtype, a.shape[2:], a[0, 0].nbytes)
                       for f, a in self.args.items() if a.ndim > 2]
        self.used = [0] * n  # lanes filled per shard on the LAST use
        self.sink = sink     # the junk row every idle lane points at

    def reset(self, sink: int) -> None:
        """Re-arm for the next fill: only the previously-used lane prefix
        needs slots→sink + valid→False (stale khash/fresh/args lanes are
        inert once their slot is the junk sink row and valid is False;
        re-filled lanes are fully overwritten). When the sink itself
        moved — a table grow() turns the OLD sink row (== old capacity)
        into a real allocatable slot — every lane must re-point, not
        just the used prefix: a stale idle lane still aimed at the old
        sink would otherwise scatter into a live actor's row."""
        if sink != self.sink:
            self.slots[:] = sink
            self.valid[:] = False
            self.fresh[:] = False
            self.sink = sink
            self.used = [0] * len(self.used)
            return
        for s, c in enumerate(self.used):
            if c:
                self.slots[s, :c] = sink
                self.valid[s, :c] = False
            self.used[s] = 0


class _Pending:
    """One queued invocation in the hashed (per-key) path. ``t_enq`` is
    the monotonic enqueue stamp (0.0 with metrics off): the engine's
    queue-wait stage measures it against batch start, so tick-scheduling
    delay AND conflict-deferred extra ticks are attributed, on the owning
    silo only. ``future`` may be None (one-way batched-ingress calls —
    nothing consumes the per-lane result, so the batch skips the
    future/callback machinery for them entirely). ``token`` is a served
    call's reply token (None for every other): such a call carries no
    future, and its job's completion hands ``(token, result)`` to the
    runtime's reply function with the rest of the job's. ``trace`` is an
    optional ``(trace_id, parent_span_id)`` request trace context (set
    by the dispatcher's vector bridge and by the cross-process staging
    ring): a batch containing traced items records a correctly-parented
    device-tick child span even when the engine's own head-sample roll
    misses. ``origin`` labels the originating worker process for packed
    cross-process batches (ledger per-worker attribution); None for
    in-process calls."""

    __slots__ = ("key_hash", "shard", "slot", "fresh", "args", "future",
                 "token", "t_enq", "trace", "origin", "t_defer", "held")

    def __init__(self, key_hash, shard, slot, args, future,
                 t_enq=0.0, trace=None, origin=None, token=None):
        self.key_hash = key_hash
        self.shard = shard
        self.slot = slot
        self.fresh = False  # decided when a tick claims it (_claim)
        self.t_defer = 0.0  # perf_counter of the first deferral
        self.held = False   # counted in engine.held (metrics on only)
        self.args = args
        self.future = future
        self.token = token
        self.t_enq = t_enq
        self.trace = trace
        self.origin = origin


class _TickJob:
    """One claimed (class, method) batch bound for the off-loop tick
    worker. ``ready`` holds the conflict-free claim (turn semantics were
    decided loop-side); ``trace`` is the device-tick sampling roll (also
    loop-side — the SpanCollector is not thread-safe, so the worker only
    stamps timings and the completion callback records the span).
    ``per_shard``/``span`` are filled by the worker for the loop-side
    resolve; ``stats`` collects the worker's deferred stage observations
    — ``(key, value)`` with None = shed-trend note and _MESSAGES =
    counter increment — replayed loop-side (the registries are
    loop-confined). ``tick`` is ``rt.ticks`` at the claim (the unit of
    work the stage spans name); ``t_hand`` is the perf_counter stamp of
    the last hand-off between threads (submit, then completion), 0.0
    with metrics off. ``outbox`` is a sending method's messages between
    its tick and their delivery (``_Outbox``; None for every other job,
    and again once delivered); ``host`` keeps the tick's results across
    the exchange's stages. ``replied`` is set once the job's served
    calls have been handed to the reply function, so that no failure
    after it answers them twice."""

    __slots__ = ("cls", "method", "ready", "trace", "per_shard", "span",
                 "stats", "tick", "t_hand", "outbox", "host", "replied")

    def __init__(self, cls, method, ready, trace=False, tick=0):
        self.cls = cls
        self.method = method
        self.ready = ready
        self.trace = trace
        self.per_shard = None
        self.span = None
        self.stats: list = []
        self.tick = tick
        self.t_hand = 0.0
        self.outbox: _Outbox | None = None
        self.host = None
        self.replied = False


class _Outbox:
    """What one sending job's tick emitted, until it is delivered:
    ``keys`` / ``valid`` ``[n_shards, B * K]`` on the host (lane
    ``i * K + j`` is message j of the shard's call i), ``keys_dev`` and
    ``payload`` the same lanes still on the device. ``failed`` maps
    ``(shard, call)`` to the error of a sender one of whose messages
    cannot be delivered; ``fresh`` is the loop's verdict
    (``_activate_receivers``): the receivers whose rows the delivery
    has to initialise first, None until it is given."""

    __slots__ = ("cls", "method", "fanout", "keys", "valid", "keys_dev",
                 "payload", "failed", "fresh", "delivered")

    def __init__(self, cls, method, fanout, keys, valid, keys_dev, payload):
        self.cls = cls
        self.method = method
        self.fanout = fanout
        self.keys = keys
        self.valid = valid
        self.keys_dev = keys_dev
        self.payload = payload
        self.failed: dict = {}
        self.fresh: np.ndarray | None = None
        self.delivered = 0

    def fail(self, bad: np.ndarray, error_of) -> None:
        """Take the lanes ``bad`` out of the outbox; the sender of each
        fails with ``error_of(key)`` (its first such message's)."""
        self.valid = self.valid & ~bad
        for s, lane in np.argwhere(bad).tolist():
            self.failed.setdefault((s, lane // self.fanout),
                                   error_of(int(self.keys[s, lane])))


def _worker_main(ref: "weakref.ref[VectorRuntime]",
                 q: "_queue.SimpleQueue") -> None:
    """The tick worker's thread: jobs FIFO until the stop sentinel. It
    holds the runtime only while a job runs. The finished job stays
    referenced here until the next one arrives, so its arrays are freed
    on this thread and not on the loop's. With metrics on, its wait for
    a job (the previous job's end -> this dequeue) rides the job's sink
    to the loop like every worker observation; a longer wait is posted
    to the loop every ``_IDLE_PIECE_S`` as it goes, so a window of the
    registry holds the worker's idle of that window and not of a quiet
    stretch before it."""
    idle_from = None
    while True:
        try:
            job = q.get() if idle_from is None \
                else q.get(timeout=_IDLE_PIECE_S)
        except _queue.Empty:
            rt = ref()
            if rt is None:
                return
            now = time.perf_counter()
            rt._post_idle(now - idle_from)
            idle_from = now
            del rt
            continue
        rt = ref()
        if job is None or rt is None:
            return
        if idle_from is not None:
            job.stats.append((_WORKER_IDLE, time.perf_counter() - idle_from))
        rt._run_job(job)
        idle_from = time.perf_counter() if rt.stats is not None else None
        del rt


class VectorActorRef:
    """Typed handle to one device-tier activation (GrainReference analog)."""

    __slots__ = ("runtime", "grain_class", "key", "key_hash")

    def __init__(self, runtime: "VectorRuntime", grain_class: type, key: int,
                 key_hash: int):
        self.runtime = runtime
        self.grain_class = grain_class
        self.key = key
        self.key_hash = key_hash

    def __getattr__(self, name: str):
        self.runtime.method_of(self.grain_class, name)  # raise if unknown
        return partial(self.runtime.call, self.grain_class, self.key_hash, name)

    def __repr__(self) -> str:
        return f"VectorActorRef({self.grain_class.__name__}, {self.key!r})"


class VectorRuntime:
    """Per-silo device-tier runtime: tables + tick loop + kernel cache."""

    def __init__(self, mesh=None, capacity_per_shard: int = 1024,
                 options=None):
        if options is not None:  # config.DispatchOptions
            options.validate()
            capacity_per_shard = options.capacity_per_shard
        ensure_compile_cache()  # before this runtime's first compile
        self.mesh = mesh if mesh is not None else make_mesh()
        self.capacity_per_shard = capacity_per_shard
        self.tables: dict[type, ShardedActorTable] = {}
        # pending per (class, method): list[_Pending]
        self.pending: dict[tuple[type, str], list[_Pending]] = {}
        # slots already claimed by the current tick per class → conflict defer
        self._tick_scheduled = False
        self._kernel_cache: dict[tuple, Any] = {}
        self._flush_waiters: list[asyncio.Future] = []
        self.ticks = 0
        self.messages_processed = 0
        self.exchange_lanes = 0  # device-valid lanes (see call_batch_device)
        # write-behind dirty tracking (off by default: marking 1M keys per
        # bulk tick is pure overhead unless a storage bridge consumes it)
        self.track_dirty = False
        self._dirty: dict[type, list[np.ndarray]] = {}
        # hot-spot load tracking (off by default, same rationale): when on,
        # every tick folds its batch into the table's on-device per-slot
        # hit counters — the telemetry feed of orleans_tpu.rebalance.
        # conflicts_deferred is the cumulative same-slot deferral count
        # (SiloControl's vector stats lens; always maintained, it's one
        # integer add on an already-deferring path)
        self.track_load = False
        self.conflicts_deferred = 0
        # double-buffered host staging (the batched-ingress hand-off):
        # per (class, method) → per buffer signature → two _StagingSets
        # alternating fill/in-flight, plus the last-batch fill count (the
        # sampler's staging-occupancy gauge)
        self._staging: dict[tuple, dict] = {}
        self.staging_fill = 0
        # load-shed queue-wait trend (observability.stats.QueueWaitTrend),
        # set by dispatch.hosting when the owning silo sheds on trend:
        # device batch starts feed it beside the INGEST queue_wait stage
        self.shed_trend = None
        # distributed-tracing collector (observability.tracing), set by
        # dispatch.hosting when the owning silo traces: each sampled batch
        # records a "device_tick" span
        self.tracer = None
        # ingest stage metrics (observability.stats.INGEST_STATS), set by
        # dispatch.hosting when the owning silo has metrics enabled: each
        # message batch splits into staging (pending -> host arrays),
        # transfer (host -> device, one packed buffer), and tick (dispatch +
        # device execution + host materialize) histograms — the device
        # half of the socket->tick ingest attribution — each a StageSpan,
        # so the same intervals lie on a jax.profiler capture
        self.stats = None
        # cost-attribution ledger (observability.ledger), set by
        # dispatch.hosting when the owning silo runs ledger_enabled: the
        # batch epilogue charges rows × tick wall to the (class, method)
        # row and the per-key sketch; track_cost mirrors track_load for
        # the on-device per-slot cost twin (table.record_cost)
        self.ledger = None
        self.track_cost = False
        # stateless-worker (mesh-replicated) hosts per class — see
        # dispatch.replicated (StatelessWorkerPlacement.cs:6 on device)
        self._replicated_hosts: dict[type, Any] = {}
        # first-touch recovery for the receivers of device-made messages
        # (``@sends``), set by dispatch.hosting where the silo has
        # write-behind storage: ``fn(cls, keys, then)`` rehydrates the
        # keys that storage holds and calls ``then(errors)`` on the loop
        # when every read has landed (Dispatcher.recover_receivers);
        # None = a receiver nothing has touched starts from
        # initial_state
        self.receiver_recovery = None
        # the served calls' reply function, set by dispatch.hosting to
        # the silo dispatcher's: ``fn(pairs)`` takes one completed job's
        # ``(reply token, result or exception)`` pairs in lane order and
        # answers them together (call_group's reply-token items)
        self.replies = None
        # the tick worker: claimed batches run on a dedicated per-engine
        # thread, started lazily by the first claimed job — staging fill,
        # operand upload, kernel dispatch and the host materialize sync
        # all happen off the event loop; the loop-side _tick is claim/
        # conflict-defer plus a queue hand-off, and futures resolve back
        # on the loop via call_soon_threadsafe. The _fence is the tick-
        # serialization lock: the worker holds it for the whole batch
        # (donated state + donated staging operands are in flight), and
        # loop-side table mutation/materialization — grow(), shard moves,
        # bulk call_batch*, checkpoint capture, write-behind gathers —
        # takes it around the touch so neither side ever sees a donated
        # buffer mid-dispatch. Worker FIFO order serializes state
        # donation per table (tick N+1 runs strictly after tick N's sync
        # proved N's uploads complete, so staging lanes never rotate back
        # to "filling" under an in-flight transfer).
        # The hand-off is BOUNDED and completion-driven: _tick claims a
        # (class, method) group only while the worker holds fewer than
        # _HANDOFF_DEPTH (1, its reasons beside it) of that group's
        # jobs; a held group's calls wait in self.pending, in arrival
        # order, later enqueues append behind them, and _complete_job
        # re-arms the claim. A job costs the worker milliseconds
        # whatever it carries, so what waits must wait where it can
        # coalesce — here, not as one-call jobs in the worker's FIFO —
        # and a closed loop of single calls rides wide ticks. Groups
        # overlap each other at the worker; _inflight_groups is the
        # per-group count the claim reads.
        # offloop_tick is the one lever left (hosting.install sets it from
        # SiloConfig.offloop_tick): False runs each claimed job on the
        # loop instead, synchronously, through the same _run_job and
        # _complete_job. It stays because its reading on the chip was a
        # trade, not a loss (PERF.md section 6, PR 30: hot-record tail
        # against the median); a bare runtime ticks on its worker.
        self.offloop_tick = True
        self._fence = threading.RLock()
        self._worker: threading.Thread | None = None
        self._worker_q: "_queue.SimpleQueue | None" = None
        self._worker_stop: "weakref.finalize | None" = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._quiesced: asyncio.Event | None = None
        self._inflight = 0        # jobs handed to the worker, unresolved
        self._inflight_msgs = 0   # messages inside those jobs
        # class -> {key_hash: count} for in-flight jobs: these keys are
        # FENCED exactly like pending ones (pending_key_hashes) — a
        # migration moving one mid-flight would let the worker's scatter
        # land in the abandoned source row
        self._inflight_keys: dict[type, dict[int, int]] = {}
        # (class, method) -> that group's jobs with the worker, unresolved
        self._inflight_groups: dict[tuple[type, str], int] = {}
        # lax.scan unroll for scanned (call_batch_rounds) kernels: each
        # scan step carries a fixed per-iteration cost (loop bookkeeping,
        # staged-payload dynamic slicing) that dominates small-population
        # rounds; unrolling amortizes it across U rounds per step at the
        # cost of a longer compile. 1 = plain scan
        self.scan_unroll = 1

    def validate_pipeline_depth(self, depth: int,
                                allow_unproven: bool = False) -> int:
        """Refuse to keep more than one super-round in flight on a
        multi-shard mesh.

        Overlapping collective programs (the ``all_to_all`` route fabric)
        DEADLOCK the single-host CPU backend: concurrently-executing
        programs contend for the shared cross-device rendezvous pool, and
        two half-started all_to_alls each hold rendezvous slots the other
        needs. On real multi-chip hardware the combination (fused pipeline
        × collectives) has never been executed by this runtime, so it is
        refused there too until proven; pass ``allow_unproven=True`` to
        try it on a non-CPU backend at your own risk. Single-shard meshes
        run no collectives and pipeline freely."""
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        n_dev = int(self.mesh.devices.size)
        if depth > 1 and n_dev > 1:
            platform = self.mesh.devices.flat[0].platform
            if platform == "cpu" or not allow_unproven:
                raise ValueError(
                    f"pipeline_depth={depth} is not supported on a "
                    f"{n_dev}-shard mesh ({platform}): overlapping "
                    "collective programs deadlock the CPU backend's "
                    "shared rendezvous pool, and the combination is "
                    "unproven on multi-chip hardware. Run cross-shard "
                    "supers at depth 1 (sequential), or pass "
                    "allow_unproven=True on a non-CPU backend.")
        return depth

    def replicated_host(self, cls: type, n_keys: int | None = None):
        """Host ``cls`` as a mesh-replicated stateless worker (no
        directory entry; any shard serves any key; reads fan in via the
        class's MERGE collectives). ``n_keys`` is required on first call."""
        host = self._replicated_hosts.get(cls)
        if host is None:
            if n_keys is None:
                raise ValueError(
                    f"first replicated_host({cls.__name__}) needs n_keys")
            from .replicated import ReplicatedWorkerHost
            host = ReplicatedWorkerHost(cls, self.mesh, n_keys)
            self._replicated_hosts[cls] = host
        elif n_keys is not None and n_keys != host.n_keys:
            raise ValueError(
                f"{cls.__name__} already hosted with n_keys="
                f"{host.n_keys}; cannot re-host with n_keys={n_keys}")
        return host

    # ------------------------------------------------------------------
    def register(self, *grain_classes: type[VectorGrain],
                 capacity_per_shard: int | None = None) -> None:
        for cls in grain_classes:
            if cls not in self.tables:
                self.tables[cls] = ShardedActorTable(
                    cls, self.mesh,
                    capacity_per_shard or self.capacity_per_shard)
                # tick-serialization fence: table-level state mutators/
                # materializers (grow, move_rows, snapshot/restore,
                # read_row) serialize against worker-side batch execution
                # through the engine's lock
                self.tables[cls].fence = self._fence
                if self.track_load:
                    self.tables[cls].enable_hit_tracking()
                if self.track_cost:
                    self.tables[cls].enable_cost_tracking()
                for m in self.tables[cls].methods.values():
                    if isinstance(m, SendingMethod):
                        self._bind_sender(cls, m)

    def _bind_sender(self, cls: type, m: SendingMethod) -> None:
        """Resolve a sending method's destination at registration: the
        class gets its table now (never from the tick worker), and the
        destination method's declared arguments are the payload."""
        if m.dest_class is None:
            m.dest_class = cls
        self.register(m.dest_class)
        dest = self.tables[m.dest_class].methods.get(m.dest_method)
        if dest is None or dest.args_schema is None or dest.read_only \
                or isinstance(dest, SendingMethod):
            raise TypeError(
                f"{cls.__name__}.{m.name} sends to "
                f"{m.dest_class.__name__}.{m.dest_method}, which has to be "
                f"a writing @actor_method with a declared args schema")
        if {_FRESH, _TURN, "__key__"} & set(dest.args_schema):
            raise TypeError(f"{m.dest_class.__name__}.{m.dest_method}: "
                            f"argument names {_FRESH!r}, {_TURN!r} and "
                            f"'__key__' are the exchange's own")

    def table(self, cls: type) -> ShardedActorTable:
        if cls not in self.tables:
            self.register(cls)
        return self.tables[cls]

    def method_of(self, cls: type, name: str) -> ActorMethod:
        m = self.table(cls).methods.get(name)
        if m is None:
            raise AttributeError(
                f"{cls.__name__} has no @actor_method {name!r}")
        return m

    @staticmethod
    def key_hash_for(key, uniform_hash: int) -> int:
        """The one key→hash rule for both entry points (in-process
        VectorActorRefs and the dispatcher's client bridge): small
        non-negative int keys map directly (enabling the dense regime);
        everything else uses the GrainId uniform hash."""
        if isinstance(key, int) and 0 <= key < 2**62:
            return key
        return uniform_hash

    def actor(self, grain_class: type, key: int | str) -> VectorActorRef:
        """Reference to one device-tier activation."""
        from ..core.ids import GrainType
        gid = GrainId.for_grain(GrainType.of(grain_class.__name__), key)
        kh = self.key_hash_for(key, gid.uniform_hash)
        self.table(grain_class).note_route(kh, gid.uniform_hash)
        return VectorActorRef(self, grain_class, key, kh)

    # ------------------------------------------------------------------
    # Per-key path (general; conflict-safe)
    # ------------------------------------------------------------------
    def call(self, grain_class: type, key_hash: int, method: str,
             **args) -> asyncio.Future:
        """Queue one invocation; resolves after the tick that runs it."""
        m = self.method_of(grain_class, method)
        if m.args_schema is not None:
            _validate_args(grain_class, method, m.args_schema, args)
        tbl = self.table(grain_class)
        if 0 <= key_hash < tbl.dense_n:
            shard = key_hash // tbl.dense_per_shard
            slot = key_hash % tbl.dense_per_shard
            # first WRITE to a dense-provisioned key activates it; the
            # row still needs its on-device initial_state (the
            # OnActivate analog), which the claim hands out (_claim)
            if not m.read_only and not tbl.dense_active[key_hash]:
                tbl.dense_active[key_hash] = True
                tbl.uninit.add(key_hash)
        else:
            shard, slot, fresh = tbl.lookup_or_allocate(key_hash)
            if fresh:
                tbl.uninit.add(key_hash)
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self.pending.setdefault((grain_class, method), []).append(
            _Pending(key_hash, shard, slot, args, fut,
                     time.monotonic()
                     if (self.stats is not None
                         or self.shed_trend is not None) else 0.0))
        self._schedule_tick(loop)
        return fut

    def call_group(self, grain_class: type, method: str,
                   items: list, traces: list | None = None,
                   origin: str | None = None) -> list:
        """Grouped enqueue — the engine half of the batched ingress
        hand-off. ``items`` is a list of ``(key_hash, kwargs, reply)``
        triples for ONE (class, method); every invocation joins the
        pending batch with a single method/table resolution, one enqueue
        stamp, and one tick schedule, instead of N :meth:`call` hops.
        ``reply`` says where the call's result goes: ``True`` to a
        future, a false value nowhere (one-way calls), anything else is
        a served call's reply token — the call carries it instead of a
        future, and its job's completion answers it through
        :attr:`replies` together with the rest of the job's
        (:meth:`_resolve_batch`). Returns one entry per item in item
        order (within-batch arrival order is preserved into the tick's
        lane layout): a future where ``reply`` is ``True``, else None.
        A per-item schema violation resolves THAT item's future with the
        error, answers its token with it (one reply call for the group's
        failures), or drops the one-way item (the per-message one-way
        contract); the rest of the group proceeds.

        ``traces`` is an optional parallel list of per-item
        ``(trace_id, parent_span_id)`` contexts (None entries for
        untraced items): the tick records a correctly-parented
        device-tick child span for each distinct context. ``origin``
        labels every item with the originating worker process (the
        cross-process ledger attribution key)."""
        m = self.method_of(grain_class, method)
        schema = m.args_schema
        skeys = schema.keys() if schema is not None else None
        tbl = self.table(grain_class)
        loop = asyncio.get_running_loop()
        t_enq = time.monotonic() if (self.stats is not None or
                                     self.shed_trend is not None or
                                     traces is not None) else 0.0
        pend: list | None = None  # created on first ENQUEUED item so an
        # all-failed group never leaves an empty pending entry behind (a
        # tick over it would crash first-batch schema inference)
        dense_n, per = tbl.dense_n, tbl.dense_per_shard
        writes, active, uninit = not m.read_only, tbl.dense_active, tbl.uninit
        futs: list = []
        failed: list | None = None
        idx = -1
        for key_hash, args, reply in items:
            idx += 1
            if reply is True:
                fut, token = loop.create_future(), None
            else:
                fut, token = None, reply or None
            futs.append(fut)
            try:
                if skeys is not None and args.keys() != skeys:
                    _validate_args(grain_class, method, schema, args)
                if 0 <= key_hash < dense_n:
                    shard = key_hash // per
                    slot = key_hash % per
                    if writes and not active[key_hash]:
                        active[key_hash] = True
                        uninit.add(key_hash)
                else:
                    shard, slot, fresh = tbl.lookup_or_allocate(key_hash)
                    if fresh:
                        uninit.add(key_hash)
            except Exception as e:  # noqa: BLE001 — schema violation or
                # slot-allocation failure: scoped to THIS item (a raise
                # escaping mid-loop would error-bounce the whole group
                # while already-enqueued items still tick)
                if fut is not None:
                    fut.set_exception(e)
                elif token is not None:
                    if failed is None:
                        failed = []
                    failed.append((token, e))
                continue
            if pend is None:
                pend = self.pending.setdefault((grain_class, method), [])
            pend.append(_Pending(key_hash, shard, slot, args, fut,
                                 t_enq,
                                 traces[idx] if traces is not None else None,
                                 origin, token))
        if pend is not None:
            self._schedule_tick(loop)
        if failed is not None:
            self.replies(failed)
        return futs

    def call_packed(self, grain_class: type, method: str, key_hashes: list,
                    columns: dict, wants: list,
                    traces: list | None = None,
                    origin: str | None = None) -> list:
        """Columnar enqueue — the owner-process half of the cross-process
        staging ring (runtime.multiproc): a worker packs one ingress
        batch's calls column-major (one ``columns[name]`` list per
        argument) into the shared segment, and this unpacks them into
        the SAME pending batch ``call_group`` would have built — one
        method/table resolution, one enqueue stamp, one tick schedule
        for the whole record, and bit-for-bit the ``call_group`` result
        semantics (that is what the shm-parity test asserts).
        ``traces``/``origin`` carry the ring record's per-sub trace
        contexts and originating-worker label through to the tick (see
        :meth:`call_group`).

        Deliberately NOT a direct scatter into the ``[n_shards, B]``
        staging buffers: lane allocation is owner state under the tick
        fence (slot lookup, conflict deferral, double-buffer rotation),
        so the fence-owning process does the staging fill exactly as it
        does for in-process calls."""
        names = tuple(columns)
        cols = [columns[n] for n in names]
        return self.call_group(grain_class, method, [
            (kh, {n: col[i] for n, col in zip(names, cols)}, want)
            for i, (kh, want) in enumerate(zip(key_hashes, wants))],
            traces=traces, origin=origin)

    # -- write-behind dirty tracking (consumed by storage.checkpoint) ----
    def enable_dirty_tracking(self) -> None:
        self.track_dirty = True

    # -- hot-spot load telemetry (consumed by orleans_tpu.rebalance) -----
    def enable_load_tracking(self) -> None:
        self.track_load = True
        for tbl in self.tables.values():
            tbl.enable_hit_tracking()

    # -- per-slot cost telemetry (consumed by observability.ledger) ------
    def enable_cost_tracking(self) -> None:
        self.track_cost = True
        for tbl in self.tables.values():
            tbl.enable_cost_tracking()

    def queue_depth(self) -> int:
        """Invocations queued for future ticks (incl. conflict-deferred
        and batches in flight on the off-loop worker) — the device tier's
        inbound-queue-depth load signal."""
        return sum(len(v) for v in self.pending.values()) + \
            self._inflight_msgs

    def pending_key_hashes(self, cls: type) -> set[int]:
        """Keys with queued invocations for ``cls``, plus keys inside
        batches currently executing on the off-loop worker. Queued
        ``_Pending`` entries cache their (shard, slot), so these keys are
        FENCED: a migration moving one mid-flight would let the next (or
        in-flight) tick scatter into the abandoned source row."""
        keys = {p.key_hash for (c, _m), items in self.pending.items()
                if c is cls for p in items}
        ctr = self._inflight_keys.get(cls)
        if ctr:
            keys.update(ctr)
        return keys

    def shard_loads(self) -> dict[type, np.ndarray]:
        """Per-class per-shard invocation totals since the last reset."""
        return {cls: tbl.shard_hits() for cls, tbl in self.tables.items()}

    def _mark_dirty(self, cls: type, keys) -> None:
        if self.track_dirty:
            self._dirty.setdefault(cls, []).append(
                np.atleast_1d(np.asarray(keys)))

    def drain_dirty(self, cls: type) -> np.ndarray:
        """Keys written since the last drain (deduplicated). The pop is
        under the tick fence: ``_mark_dirty`` runs worker-side inside an
        off-loop batch (which holds the fence for its whole duration),
        so an unfenced pop could orphan a list the worker is about to
        append to — keys written by that batch would silently never
        flush."""
        with self._fence:
            batches = self._dirty.pop(cls, None)
        if not batches:
            return np.zeros(0, dtype=np.int64)
        return np.unique(np.concatenate(batches))

    def _staging_acquire(self, cls: type, method: str, tbl,
                         B: int, schema: dict) -> _StagingSet:
        """Check out the "filling" half of the double-buffered staging
        pair for this (class, method, B, schema) bucket. The OTHER half
        is the one the in-flight tick's device upload consumed — by the
        time a buffer rotates back here its tick has synced (the batch
        materializes results on host before resolving futures), so
        refilling can never race a kernel still reading it."""
        pool = self._staging.get((cls, method))
        if pool is None:
            pool = self._staging[(cls, method)] = {}
        sig = (tbl.n_shards, B, tuple(sorted(
            (f, np.dtype(d).str, tuple(int(x) for x in shape))
            for f, (d, shape) in schema.items())))
        entry = pool.get(sig)
        if entry is None:
            entry = pool[sig] = [[], 0]
        sets, idx = entry
        if len(sets) < 2:
            st = _StagingSet(tbl.n_shards, B, tbl.sink_slot, schema)
            sets.append(st)
            entry[1] = len(sets) % 2
            return st
        st = sets[idx]
        entry[1] = idx ^ 1
        st.reset(tbl.sink_slot)
        return st

    def staging_lanes(self) -> int:
        """Total preallocated staging lanes across every double-buffer
        set (the staging-buffer footprint gauge). Read loop-side while
        the off-loop worker may be growing the pools — retried on a
        concurrent-mutation error rather than fenced (the sampler must
        never block the loop behind an in-flight batch)."""
        for _ in range(4):
            try:
                total = 0
                for pool in list(self._staging.values()):
                    for (n, B, _sig), (sets, _idx) in list(pool.items()):
                        total += n * B * len(sets)
                return total
            except RuntimeError:  # dict mutated during iteration
                continue
        return 0

    def _schedule_tick(self, loop) -> None:
        if not self._tick_scheduled:
            self._tick_scheduled = True
            loop.call_soon(self._tick)

    # -- tick worker ---------------------------------------------------
    def tick_fence(self):
        """The tick-serialization fence (a reentrant lock usable as a
        context manager): loop-side code that mutates or materializes
        table state outside the tick path — rebalance shard moves,
        checkpoint capture, write-behind gathers — takes it around the
        touch so it can never interleave with a worker-side batch whose
        donated state/staging upload is still in flight. A write-behind
        pass holds it while it locates its keys and launches its gathers
        (``VectorStorageBridge.flush``: the launches are the snapshot, so
        the waits for the rows and the writes run without it, and it is
        never held across an ``await``); checkpoint capture holds it
        through its device→host copy."""
        return self._fence

    def _bind_loop(self) -> None:
        loop = asyncio.get_running_loop()
        if loop is not self._loop:
            # first job, or a bare runtime carried into a second event
            # loop (one asyncio.run after another): completions post to
            # the loop that claims, and the quiescence event belongs to
            # it. Held calls outlive shutdown_worker and flush() may be
            # waiting on the event, so it survives a worker restart on
            # the same loop.
            self._loop = loop
            self._quiesced = asyncio.Event()
            if not self._inflight:
                self._quiesced.set()

    def _ensure_worker(self) -> None:
        if self._worker is not None:
            return
        q = self._worker_q = _queue.SimpleQueue()
        # the idle thread holds only a weak reference to the runtime, and
        # collecting the runtime posts the stop sentinel: an abandoned
        # runtime's worker exits instead of pinning it for the life of
        # the process
        self._worker_stop = weakref.finalize(self, q.put, None)
        t = threading.Thread(target=_worker_main,
                             args=(weakref.ref(self), q),
                             name="orleans-tick-worker", daemon=True)
        self._worker = t
        t.start()

    def shutdown_worker(self, timeout: float = 10.0) -> None:
        """Stop the tick worker (silo stop): jobs already queued finish
        FIFO, then the thread exits. Completion callbacks posted to the
        loop still run when control next returns to it. Idempotent; a
        later tick after shutdown lazily starts a fresh worker
        (restart-in-process)."""
        w, self._worker = self._worker, None
        if w is None:
            return
        self._worker_stop.detach()
        self._worker_q.put(None)
        w.join(timeout)

    def _run_job(self, job: _TickJob, offloop: bool = True) -> None:
        """One claimed batch under the tick fence, then its completion:
        on the worker, posted back to the loop; with the lever off, on
        the loop and called in place. A sending job comes here twice:
        for its tick, and — once the loop has said which receivers are
        fresh (``_activate_receivers``) — for its exchange."""
        host, err = job.host, None
        st = self.stats
        wait = None
        try:
            if st is not None:
                # the job waited while this thread ran the previous
                # tick; from here it waits for whoever holds the
                # fence (a write-behind gather, a snapshot)
                wait = StageSpan(st, "engine.fence_wait", job.stats,
                                 tick=job.tick)
                job.stats.append((_WORKER_QUEUE, wait.t0 - job.t_hand))
            # the fence is held for the WHOLE batch: donated tbl.state
            # and donated staging operands are in flight until the
            # sync at the end of _execute_batch proves the uploads
            # completed
            with self._fence:
                if wait is not None:
                    wait.close()
                if job.outbox is None:
                    job.per_shard, host, job.span = self._execute_batch(
                        job.cls, job.method, job.ready, job.stats,
                        trace_roll=job.trace, tick=job.tick, job=job)
                    job.host = host
                else:
                    self._exchange(job)
        except BaseException as e:  # noqa: BLE001 — futures fail loop-side
            err = e
        if st is not None:
            if err is not None:
                StageSpan.unwind()
            job.t_hand = time.perf_counter()
        if not offloop:
            self._complete_job(job, host, err)
            return
        try:
            self._loop.call_soon_threadsafe(
                self._complete_job, job, host, err)
        except RuntimeError:
            # loop closed (ungraceful stop): the runtime client is
            # breaking outstanding futures; nothing left to resolve
            pass

    def _post_idle(self, seconds: float) -> None:
        """Tick worker: a piece of a long wait for a job, observed on the
        loop (the registry is loop-confined)."""
        try:
            self._loop.call_soon_threadsafe(self.stats.observe,
                                            _WORKER_IDLE, seconds)
        except RuntimeError:
            pass  # loop closed: nobody reads it

    def _submit_job(self, job: _TickJob) -> None:
        self._bind_loop()
        self._inflight += 1
        self._inflight_msgs += len(job.ready)
        self._quiesced.clear()
        group = (job.cls, job.method)
        self._inflight_groups[group] = self._inflight_groups.get(group, 0) + 1
        ctr = self._inflight_keys.setdefault(job.cls, {})
        for p in job.ready:
            ctr[p.key_hash] = ctr.get(p.key_hash, 0) + 1
        if self.stats is not None:
            job.t_hand = time.perf_counter()
        if self.offloop_tick:
            self._ensure_worker()
            self._worker_q.put(job)

    def _record_tick_span(self, span, ready: list, error: bool = False
                          ) -> None:
        """Loop-side record of a device-tick span from worker-stamped
        timings; ``span`` = (name, wall_start,
        duration[, batch_wall, batch_mono]) or None. The error form is
        what tail retention keys on, so failing sampled ticks stay
        visible in retained traces.

        Items carrying a request trace context additionally get (a) a
        device-tick child span parented into THEIR trace, spanning
        batch start (staging fill) through host materialize — the
        owner-side leg of the cross-process waterfall — and (b) a
        queue-wait server span covering enqueue → batch start, so the
        ring-dwell / queue-wait / tick segments read contiguously. One
        pair per distinct context (the tick is one event)."""
        tracer = self.tracer
        if span is None or tracer is None:
            return
        name, start_wall, dur = span[0], span[1], span[2]
        n = len(ready)
        if error:
            tracer.record(tracer.device_trace_id, None, name,
                          "device_tick", start_wall, dur, batch=n,
                          error=True)
        else:
            tracer.record(tracer.device_trace_id, None, name,
                          "device_tick", start_wall, dur, batch=n)
        if len(span) < 5:
            return
        batch_wall, batch_mono = span[3], span[4]
        end_wall = start_wall + dur
        seen: set = set()
        for p in ready:
            tr = p.trace
            if tr is None or tr in seen:
                continue
            seen.add(tr)
            tid, psid = tr
            if error:
                tracer.record(tid, psid, name, "device_tick", batch_wall,
                              max(0.0, end_wall - batch_wall), batch=n,
                              error=True)
            else:
                tracer.record(tid, psid, name, "device_tick", batch_wall,
                              max(0.0, end_wall - batch_wall), batch=n)
            if p.t_enq and batch_mono > p.t_enq:
                q = batch_mono - p.t_enq
                tracer.record(tid, psid, "engine.queue_wait", "server",
                              batch_wall - q, q, queue_s=q, exec_s=0.0)

    def _complete_job(self, job: _TickJob, host, err) -> None:
        """Loop-side completion: resolve futures (or fail them), record
        the sampled device-tick span (the collector is loop-confined;
        the worker only stamped timings), and — in a finally, so no
        resolve/record error can ever wedge it — release the in-flight
        key fence, re-arm the quiescence event and give the job's group
        its place with the worker back: the group's count drops, and a
        tick is scheduled if anything is pending, because a group that
        ``_tick`` held at ``_HANDOFF_DEPTH`` waits for exactly this
        (an errored batch releases its group like any other). A
        loop-side failure here fails the batch's futures; it never
        leaves callers hanging."""
        st = self.stats
        with StageSpan(st, "engine.complete", tick=job.tick) \
                if st is not None else NO_SPAN:
            self._finish_job(job, host, err)

    def _finish_job(self, job: _TickJob, host, err) -> None:
        """:meth:`_complete_job` inside its ``engine.complete`` span."""
        st = self.stats
        ob = job.outbox
        if err is None and ob is not None and ob.fresh is None:
            # a sending job back from its tick: its messages wait for
            # the loop's word on their receivers, then it returns to the
            # worker for the exchange; it stays in flight meanwhile
            try:
                if st is not None and job.t_hand:
                    st.observe(_COMPLETE_HOP,
                               time.perf_counter() - job.t_hand)
                self._activate_receivers(job)
                return
            except BaseException as e:  # noqa: BLE001 — fails the job
                err = e
        try:
            if st is not None and job.t_hand:
                # the hop: this callback waited behind whatever the loop
                # ran since the worker posted it
                st.observe(_COMPLETE_HOP, time.perf_counter() - job.t_hand)
            # replay the worker's deferred observations into the loop-
            # confined registries (timings were stamped off-loop); on an
            # errored batch the list holds whatever stages completed
            if job.stats:
                trend = self.shed_trend
                for key, val in job.stats:
                    if key is None:
                        if trend is not None:
                            trend.note(val)
                    elif key is _LEDGER:
                        # NOT metrics-gated: the ledger runs with the
                        # stats registry off (sanctioned replay — the
                        # worker stamped, the loop charges)
                        if self.ledger is not None:
                            self.ledger.charge_tick(val)
                    elif st is None:
                        continue
                    elif key in _COUNTERS:
                        st.increment(key, val)
                    elif key == _DEPTH:
                        st.histogram_with(key, COUNT_BOUNDS).observe(val)
                    else:
                        st.observe(key, val)
            if err is not None:
                log.error("vector tick failed for %s.%s",
                          job.cls.__name__, job.method, exc_info=err)
                self._record_tick_span(getattr(err, "_tick_span", None),
                                       job.ready, error=True)
                self._fail_job(job, err)
            else:
                self._record_tick_span(job.span, job.ready)
                if ob is not None:
                    # a sender one of whose messages could not be
                    # delivered fails alone; the others' replies follow
                    # (a served sender's in _resolve_batch's one pass)
                    for (s, i), e in ob.failed.items():
                        fut = job.per_shard[s][i].future
                        if fut is not None and not fut.done():
                            fut.set_exception(e)
                    self.messages_processed += ob.delivered
                self._resolve_batch(job, host)
        except BaseException as e2:  # noqa: BLE001 — fail callers, not loop
            log.exception("vector tick completion failed for %s.%s",
                          job.cls.__name__, job.method)
            self._fail_job(job, e2)
        finally:
            self._inflight -= 1
            self._inflight_msgs -= len(job.ready)
            ctr = self._inflight_keys.get(job.cls)
            if ctr is not None:
                for p in job.ready:
                    left = ctr.get(p.key_hash, 0) - 1
                    if left <= 0:
                        ctr.pop(p.key_hash, None)
                    else:
                        ctr[p.key_hash] = left
            if self._inflight == 0:
                self._quiesced.set()
            group = (job.cls, job.method)
            left = self._inflight_groups.get(group, 0) - 1
            if left <= 0:
                self._inflight_groups.pop(group, None)
            else:
                self._inflight_groups[group] = left
            if self.pending:
                self._schedule_tick(self._loop)

    async def flush(self) -> None:
        """Run ticks until all pending work (incl. conflict-deferred and
        worker-side in-flight batches) drains: between rounds it awaits
        the worker's quiescence event instead of busy-spinning the
        loop."""
        while self.pending or self._inflight:
            if self.pending:
                self._tick()
            if self._inflight:
                await self._quiesced.wait()
            else:
                await asyncio.sleep(0)

    # ------------------------------------------------------------------
    def _tick(self) -> None:
        """One claim pass over ``self.pending``. The hand-off to the
        worker is bounded: a group with ``_HANDOFF_DEPTH`` jobs (one)
        still with the worker is HELD — its items stay in
        ``self.pending`` in arrival order and later enqueues append
        behind them, so they coalesce into one job when
        ``_complete_job`` re-arms the claim.
        The pass reschedules itself only for a group it could claim now
        (conflict-deferred items of a group with room); it never spins
        on held groups, and ``self.ticks`` counts only passes that
        claimed something."""
        self._tick_scheduled = False
        if not self.pending:
            return
        st = self.stats
        busy = self._inflight_groups
        held = 0
        if busy:
            work = {}
            for g, items in self.pending.items():
                if busy.get(g, 0) < _HANDOFF_DEPTH:
                    work[g] = items
                elif st is not None:
                    # first holds: a hold marks everything present, and
                    # the list is in arrival order, so the marked are a
                    # prefix
                    for p in reversed(items):
                        if p.held:
                            break
                        p.held = True
                        held += 1
            for g in work:
                del self.pending[g]
        else:  # nothing in flight
            work, self.pending = self.pending, {}
        if st is not None:
            st.increment(_HELD, held)  # 0 too: it exists
        if not work:
            return  # every group held: a completion re-arms the claim
        tracer = self.tracer
        tick = self.ticks
        for (cls, method), items in work.items():
            with StageSpan(st, "engine.claim", tick=tick) \
                    if st is not None else NO_SPAN:
                ready = self._claim(cls, method, items)
                # device-tick sampling rolls HERE (loop-side): the
                # worker must not touch the collector. A batch
                # carrying request trace contexts (threaded over the
                # cross-process staging ring or the vector bridge) records
                # regardless of the roll: header presence IS the upstream
                # sampled decision
                roll = bool(ready) and tracer is not None and (
                    tracer.sample()
                    or any(p.trace is not None for p in ready))
                if ready:
                    job = _TickJob(cls, method, ready, roll, tick)
                    self._submit_job(job)
            if ready and not self.offloop_tick:
                # the lever: the job runs here, outside the claim's span
                self._run_job(job, offloop=False)
        self.ticks += 1
        # conflict-deferred work → next tick, unless its group is now held
        if any(busy.get(g, 0) < _HANDOFF_DEPTH for g in self.pending):
            self._schedule_tick(asyncio.get_running_loop())

    def _claim(self, cls: type, method: str,
               items: list[_Pending]) -> list[_Pending]:
        """Turn-semantics claim, always loop-side (it mutates
        ``self.pending``): one message per slot per tick; same-slot
        conflicts defer to the next tick.

        Freshness is decided here and not at enqueue, because claims
        happen in the order in which the kernels run (the worker is
        FIFO). A row is initialised by the first WRITING method claimed
        for it: the enqueue of that write put the key in ``tbl.uninit``,
        and until a write is claimed every lane for the key starts from
        ``initial_state`` — a read claimed ahead of it, in this tick or
        an earlier one, included. A read-only method writes nothing
        back, so it neither activates a dense key nor clears the mark: a
        read of a record nothing has written derives its initial state
        again, and leaves nothing dirty behind."""
        tbl = self.tables[cls]
        writes = not tbl.methods[method].read_only
        active, dense_n, uninit = tbl.dense_active, tbl.dense_n, tbl.uninit
        st = self.stats
        now = time.perf_counter() if st is not None else 0.0
        claimed: set[tuple[int, int]] = set()
        ready: list[_Pending] = []
        deferred: list[_Pending] | None = None
        first_deferrals = 0
        for p in items:
            loc = (p.shard, p.slot)
            if loc in claimed:
                if deferred is None:
                    deferred = self.pending.setdefault((cls, method), [])
                deferred.append(p)
                self.conflicts_deferred += 1
                if st is not None and not p.t_defer:
                    p.t_defer = now
                    first_deferrals += 1
                continue
            claimed.add(loc)
            k = p.key_hash
            if uninit and k in uninit:
                p.fresh = True
                if writes:
                    uninit.discard(k)
            elif not writes and 0 <= k < dense_n and not active[k]:
                p.fresh = True
            if p.t_defer:
                st.observe(_DEFER_WAIT, now - p.t_defer)
            ready.append(p)
        if st is not None:
            st.increment(_DEFERRED, first_deferrals)  # 0 too: it exists
        return ready

    def _fail_job(self, job: _TickJob, exc: BaseException) -> None:
        """Answer every caller of a job that failed with ``exc``: its
        futures not yet done, and — unless they were handed over already
        — its served calls, in one reply call."""
        for p in job.ready:
            if p.future is not None and not p.future.done():
                p.future.set_exception(exc)
        if job.replied:
            return
        job.replied = True
        pairs = [(p.token, exc) for p in job.ready if p.token is not None]
        if pairs:
            self.replies(pairs)

    def _resolve_batch(self, job: _TickJob, host) -> None:
        """A job's results to its callers in one pass, shard by shard in
        lane order (a key's replies keep their order): ``host`` is
        flattened once, and each call's result is its leaves indexed
        ``[s, i]`` — the objects ``a[s, i]`` gives, so a reply encodes
        as it always has. A future is resolved; a served call's token
        joins the job's ``(token, result)`` pairs (a sender whose
        delivery failed: its error), and the pairs go to the reply
        function once, at the end, inside the ``engine.resolve`` span."""
        st = self.stats
        ready = job.ready
        if st is not None:
            # the mix that reached the device, by (class, method); their
            # sum is ``ingest.messages``
            st.increment(f"{_MESSAGES}.{job.cls.__name__}.{job.method}",
                         len(ready))
        with StageSpan(st, "engine.resolve", tick=job.tick) \
                if st is not None else NO_SPAN:
            leaves, treedef = jax.tree_util.tree_flatten(host)
            # a bare array needs no unflatten
            leaf = leaves[0] if treedef.num_nodes == 1 and leaves else None
            unflatten = treedef.unflatten
            failed = job.outbox.failed if job.outbox is not None else None
            pairs: list = []
            for s, ps in enumerate(job.per_shard):
                for i, p in enumerate(ps):
                    token = p.token
                    if token is None:
                        fut = p.future
                        if fut is None or fut.done():
                            continue
                    v = leaf[s, i] if leaf is not None \
                        else unflatten([a[s, i] for a in leaves])
                    if token is None:
                        fut.set_result(v)
                    elif failed and (s, i) in failed:
                        pairs.append((token, failed[s, i]))
                    else:
                        pairs.append((token, v))
            if st is not None:
                st.increment(_REPLIES_BATCHED, len(pairs))  # 0: it exists
            if pairs:
                job.replied = True
                self.replies(pairs)
        self.messages_processed += len(ready)

    def _execute_batch(self, cls: type, method: str, ready: list[_Pending],
                       sink: list, trace_roll: bool = False, tick: int = 0,
                       job: _TickJob | None = None):
        """Staging fill → operand upload (one buffer, one transfer) →
        kernel dispatch → host materialize sync for one claimed,
        conflict-free batch, on the tick worker (or in place on the
        loop). ``sink`` is the job's deferred-stats list: every
        observation is STAMPED here and recorded loop-side in
        _complete_job, because StatsRegistry/Histogram/QueueWaitTrend/
        the ledger are loop-confined.
        With metrics on the batch is four contiguous stage spans of unit
        ``tick`` — ingest.staging, ingest.transfer, ingest.tick.dispatch,
        ingest.tick.sync — the last two tiling ingest.tick; a raising
        batch leaves its open span to the caller's ``StageSpan.unwind``.
        Returns ``(per_shard, host_results, span_timing)`` where
        ``span_timing`` is ``(name, wall_start, duration)`` for a sampled
        tick (recorded by the caller on the loop) or None. A sending
        method's outbox is read back inside the sync stage (the
        destination keys and the valid mask, not the payload) and left
        on ``job.outbox`` where it holds a message."""
        st = self.stats
        led = self.ledger
        now_mono = batch_wall = 0.0
        stage = None
        if st is not None:
            stage = StageSpan(st, "ingest.staging", sink, tick=tick)
        if st is not None or self.shed_trend is not None or trace_roll:
            now_mono = time.monotonic()  # queue-wait ends at batch start
            # (the shed trend needs the stamp even with metrics off —
            # t_enq is gated the same way in call/call_group; traced
            # batches need it for the queue-wait child span)
        if trace_roll:
            # wall twin of the batch-start stamp: the traced device-tick
            # child span opens HERE (staging fill onward), so the
            # waterfall's queue-wait → staging/transfer/tick segments
            # are contiguous (the sampled device_trace_id span keeps its
            # kernel-dispatch-onward semantics)
            batch_wall = time.time()
        tbl = self.tables[cls]
        m = tbl.methods[method]
        # schema inference is committed only after a successful batch so a
        # bad first call cannot poison the class-level schema
        schema = m.args_schema
        inferred = schema is None
        if inferred:
            schema = {k: (np.asarray(v).dtype, np.asarray(v).shape)
                      for k, v in ready[0].args.items()}
        n, cap = tbl.n_shards, tbl.capacity
        per_shard: list[list[_Pending]] = [[] for _ in range(n)]
        for p in ready:
            per_shard[p.shard].append(p)
        B = _bucket(max(len(ps) for ps in per_shard))
        # double-buffered staging: one preallocated buffer set fills here
        # while its twin may still back the previous tick's device upload
        # — steady-state ingest allocates nothing host-side
        stg = self._staging_acquire(cls, method, tbl, B, schema)
        slots, khash = stg.slots, stg.khash
        fresh, valid = stg.fresh, stg.valid
        scalars, arrays = stg.scalars, stg.arrays
        for s, ps in enumerate(per_shard):
            stg.used[s] = len(ps)
            for i, p in enumerate(ps):
                slots[s, i] = p.slot
                # key hashes ride to the device as 31-bit ints (x64 is
                # disabled; initial_state only needs a per-actor seed)
                khash[s, i] = p.key_hash & 0x7FFFFFFF
                fresh[s, i] = p.fresh
                valid[s, i] = True
                args = p.args
                for fname, buf in scalars:
                    buf[s, i] = args[fname]
                for fname, buf, dtype, shape, nbytes in arrays:
                    v = args[fname]
                    if type(v) is bytes:
                        # one memcpy into the staging row, no Python
                        # object per element
                        if len(v) != nbytes:
                            raise ValueError(
                                f"{cls.__name__}.{method}: {fname!r} "
                                f"takes {nbytes} bytes, got {len(v)}")
                        v = np.frombuffer(v, dtype).reshape(shape)
                    buf[s, i] = v
        self.staging_fill = len(ready)
        if inferred:
            m.args_schema = schema  # needed by the kernel builder
        t_tick = 0.0
        if st is not None:
            stage.close()
            stage = StageSpan(st, "ingest.transfer", sink, tick=tick)
            # per-item queue wait: enqueue (rt.call) -> this batch start —
            # tick scheduling plus any conflict-deferred full ticks; items
            # enqueued by non-call paths carry no stamp and are skipped
            for p in ready:
                if p.t_enq:
                    sink.append((_QUEUE_WAIT,
                                 max(0.0, now_mono - p.t_enq)))
        if self.shed_trend is not None:
            # feed the load-shed trend with this batch's mean queue wait
            # (QueueWaitTrend is not thread-safe, and the dispatcher
            # feeds it from the loop)
            stamped = [now_mono - p.t_enq for p in ready if p.t_enq]
            if stamped:
                sink.append((None, max(0.0, sum(stamped) / len(stamped))))
        span_name = span_start = t_span0 = None
        try:
            # ONE host→device transfer a job: the staging set's packed
            # buffer, which the kernel unpacks by the set's layout. The
            # device array is fresh per tick (never a cached _DensePlan
            # operand) and is donated with the state, so XLA may reuse
            # it as the kernel's scratch
            kernel = self._kernel(cls, method, B, layout=stg.layout)
            kernel_args = (tbl.state, jnp.asarray(stg.packed))
            if st is not None:
                stage.close()
                sink.append((_TRANSFER_JOBS, 1))
                sink.append((_TRANSFER_PUTS, 1))
                sink.append((_TRANSFER_BYTES, stg.packed.nbytes))
                # the job's shape on the mesh: every shard computes the
                # fullest shard's bucket
                sink.append((_JOB_LANES, len(ready)))
                sink.append((_JOB_MAX_SHARD_LANES, max(stg.used)))
                sink.append((_JOB_SLOTS, n * B))
                # the stage span bridges host tracing to the XLA
                # timeline: on a jax.profiler capture this tick's kernel
                # launch nests under otpu:ingest.tick.dispatch
                stage = StageSpan(st, "ingest.tick.dispatch", sink,
                                 tick=tick)
                t_tick = stage.t0
            elif led is not None:
                t_tick = time.perf_counter()  # ledger-only tick wall start
            if trace_roll:
                span_name = f"tick {cls.__name__}.{method}"
                span_start = time.time()
                t_span0 = time.perf_counter()
            new_state, results, *sent = kernel(*kernel_args)
            if st is not None:
                stage.close()
                stage = StageSpan(st, "ingest.tick.sync", sink, tick=tick)
        except BaseException as e:
            if inferred:
                m.args_schema = None  # do not poison the class schema
            if span_start is not None:
                # a sampled tick whose kernel raised still records an
                # errored device span (tail retention keys on the error
                # attr) — the collector is loop-confined, so the timing
                # rides the exception to the loop-side completion
                # (best-effort: an exception type rejecting attributes
                # just loses the span, never the error)
                try:
                    e._tick_span = (span_name, span_start,
                                    time.perf_counter() - t_span0,
                                    batch_wall, now_mono)
                except AttributeError:
                    pass
            raise
        if not m.read_only:
            tbl.state = new_state
            # dirty marks happen at state-apply time, not enqueue time: a
            # write-behind flush between enqueue and tick would otherwise
            # drain the key and persist the pre-write row forever
            self._mark_dirty(cls, np.fromiter(
                (p.key_hash for p in ready), dtype=np.int64,
                count=len(ready)))
        if self.track_load:
            tbl.record_hits(slots, valid)
        host = jax.tree_util.tree_map(np.asarray, results)
        if not jax.tree_util.tree_leaves(host):
            # result-less method: no np.asarray above synced anything, so
            # block on the state output before this tick's staging
            # buffers can rotate back to "filling" — on async-transfer
            # backends (TPU) the operands' host→device upload must have
            # provably completed before the numpy buffers are reused
            # (free on CPU, where the transfer copies synchronously).
            # This sync is ALSO the staging pin: the worker runs
            # batches FIFO, so by the time a staging set rotates back its
            # tick has provably synced here. (A read-only kernel returns
            # no state: its operands are not donated, so they are what
            # there is to wait for: the packed buffer.)
            jax.block_until_ready(
                kernel_args[1] if m.read_only else new_state)
        if sent:
            job.outbox = self._read_outbox(m, sent[0])
        if st is not None:
            # tick closes AFTER the host transfer for the same reason the
            # span timing does: jax dispatch is async, and the np.asarray
            # sync is where device execution is actually paid
            sink.append((_TICK, stage.t0 + stage.close() - t_tick))
            sink.append((_MESSAGES, len(ready)))
        if led is not None:
            # cost-attribution epilogue: every resident row is charged
            # this tick's wall (row-seconds = rows × wall); the per-slot
            # device twin folds the same batch via record_cost (the
            # _accumulate_hits scatter with the µs charge as scale).
            # The payload is stamped for loop-side replay — same
            # discipline as the stage observations above.
            tick_s = max(0.0, time.perf_counter() - t_tick)
            payload = (cls.__name__, method, len(ready), tick_s,
                       tuple(f"{cls.__name__}#{p.key_hash}"
                             for p in ready))
            if any(p.origin is not None for p in ready):
                # cross-process batch: per-item originating-worker labels
                # ride as a parallel 6th element (the ledger's per-process
                # device-time attribution key); in-process payloads stay
                # 5-tuples so merged snapshots are stable across versions
                payload = payload + (
                    tuple(p.origin for p in ready),)
            sink.append((_LEDGER, payload))
            if self.track_cost:
                tbl.record_cost(slots, valid, int(tick_s * 1e6))
        span = None
        if trace_roll and span_name is not None:
            # duration closes AFTER the host transfer: closing at kernel
            # return would record ~0 for exactly the hot ticks tracing
            # exists to attribute. Recorded by the caller (loop-side);
            # the batch-start stamps parent traced items' child spans.
            span = (span_name, span_start, time.perf_counter() - t_span0,
                    batch_wall, now_mono)
        return per_shard, host, span

    # ------------------------------------------------------------------
    # Bulk path (dense keys; the ≥1M msgs/sec route)
    # ------------------------------------------------------------------
    def make_dense_plan(self, grain_class: type, keys: np.ndarray) -> "_DensePlan":
        """Precompute the key→(shard, lane) batch layout for a recurring bulk
        key set (amortizes the argsort across ticks — e.g. every Presence
        heartbeat round touches the same 1M players)."""
        tbl = self.table(grain_class)
        keys = np.asarray(keys)
        M = keys.shape[0]
        n = tbl.n_shards
        if keys.shape[0] and np.unique(keys).shape[0] != keys.shape[0]:
            # duplicate keys in one bulk tick would scatter twice into one
            # row (nondeterministic write order — a silent turn-semantics
            # violation); the per-key path serializes them across ticks
            raise ValueError(
                "call_batch keys must be unique within a tick; route "
                "duplicate-key traffic through VectorRuntime.call")
        shard, slot = tbl.dense_shard_slot(keys)
        order = np.argsort(shard, kind="stable")
        inv = np.empty_like(order)
        inv[order] = np.arange(M)
        counts = np.bincount(shard, minlength=n)
        B = _bucket(int(counts.max()) if M else MIN_BUCKET)
        sorted_shard = shard[order]
        starts = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(counts, out=starts[1:])
        lane_sorted = np.arange(M) - starts[sorted_shard]
        slots_b = np.full((n, B), tbl.sink_slot, dtype=np.int32)
        valid_b = np.zeros((n, B), dtype=bool)
        khash_b = np.zeros((n, B), dtype=np.int32)
        slots_b[sorted_shard, lane_sorted] = slot[order]
        valid_b[sorted_shard, lane_sorted] = True
        khash_b[sorted_shard, lane_sorted] = keys[order] & 0x7FFFFFFF
        identity = bool(M) and keys[0] == 0 and keys[-1] == M - 1 and \
            np.array_equal(keys, np.arange(M))
        return _DensePlan(keys, order, inv, sorted_shard, lane_sorted, B,
                          slots_b, valid_b, khash_b,
                          identity=identity, counts=counts)

    def call_batch(self, grain_class: type, method: str,
                   keys: np.ndarray, args: dict[str, np.ndarray],
                   fresh: np.ndarray | None = None,
                   plan: "_DensePlan | None" = None,
                   device_results: bool = False):
        """Invoke ``method`` on many dense-keyed activations in one tick.

        ``keys``: int array [M] of dense keys (must be ensure_dense'd and
        unique within the call). ``args``: dict of [M, ...] arrays. Returns
        the stacked result pytree with leading axis [M]. Runs synchronously
        (one kernel launch) — the caller IS the tick. Pass a reusable
        ``plan`` from :meth:`make_dense_plan` for recurring key sets.
        """
        tbl = self.table(grain_class)
        m = self.method_of(grain_class, method)
        if m.args_schema is None:
            m.args_schema = {
                k: (np.asarray(v).dtype, np.asarray(v).shape[1:])
                for k, v in args.items()}
        _validate_args(grain_class, method, m.args_schema, args)
        if plan is None:
            plan = self.make_dense_plan(grain_class, keys)
        M = plan.keys.shape[0]
        d_slots, d_khash, d_valid, d_fresh0 = plan.device_operands(tbl._put)
        if fresh is None:
            # auto-activate: keys never touched get initial_state this tick
            fresh = tbl.dense_fresh_mask(plan.keys)
        if fresh is not None:
            d_fresh = tbl._put(
                jnp.asarray(plan.pack(np.asarray(fresh), bool, ())))
            tbl.mark_dense_active(plan.keys)
        else:
            d_fresh = d_fresh0
        args_b = {}
        for fname, (dtype, shape) in m.args_schema.items():
            args_b[fname] = tbl._put(
                jnp.asarray(plan.pack(np.asarray(args[fname]), dtype, shape)))
        kern = self._kernel(grain_class, method, plan.B,
                            contiguous=self._plan_contiguous(tbl, plan))
        led = self.ledger
        t_led = time.perf_counter() if led is not None else 0.0
        # tick fence: the bulk path is its own tick on the CALLER's
        # thread — it must not read (or commit over) tbl.state while an
        # off-loop worker batch has it donated mid-dispatch
        with self._fence:
            new_state, results = kern(
                tbl.state, d_slots, d_khash, d_fresh, d_valid, args_b)
            if not m.read_only:
                tbl.state = new_state
                self._mark_dirty(grain_class, plan.keys)
        if self.track_load:
            tbl.record_hits(d_slots, d_valid)
        if led is not None:
            # bulk ticks charge dispatch wall (loop-side, synchronous
            # caller) with no per-key labels — labeling a 1M-key bulk
            # tick would cost more than the tick; per-key detail for the
            # bulk regime lives in the on-device per-slot cost twin
            wall = max(0.0, time.perf_counter() - t_led)
            led.charge_tick((grain_class.__name__, method, M, wall, ()))
            if self.track_cost:
                tbl.record_cost(d_slots, d_valid, int(wall * 1e6))
        self.ticks += 1
        self.messages_processed += M
        if device_results:
            # async path: raw [n, B, ...] device results, no host sync —
            # use plan.unpack(...) to materialize caller-order rows later
            return results
        return plan.unpack(results)

    def call_batch_rounds(self, grain_class: type, method: str,
                          keys: np.ndarray,
                          args_rounds: dict[str, np.ndarray],
                          plan: "_DensePlan | None" = None,
                          device_results: bool = False):
        """Sustained-streaming dispatch: K message rounds to the same dense
        key set in ONE kernel call (``lax.scan`` over ticks on device).

        ``args_rounds``: dict of [K, M, ...] arrays — K sequential rounds.
        Turn semantics hold: round k+1 sees the state written by round k
        (ticks are sequential inside the scan). One payload upload + one
        dispatch per K·M messages — the streaming-gateway hot path (the
        PersistentStreamPullingAgent pump re-expressed as a scanned kernel,
        PersistentStreamPullingAgent.cs:141,350-368).
        """
        tbl = self.table(grain_class)
        m = self.method_of(grain_class, method)
        if not args_rounds:
            raise TypeError(
                "call_batch_rounds requires at least one [K, M, ...] args "
                "array to define K; use call_batch for single no-arg ticks")
        if m.args_schema is None:
            m.args_schema = {
                k: (np.asarray(v).dtype, np.asarray(v).shape[2:])
                for k, v in args_rounds.items()}
        _validate_args(grain_class, method, m.args_schema, args_rounds)
        if plan is None:
            plan = self.make_dense_plan(grain_class, keys)
        K = next(iter(args_rounds.values())).shape[0]
        M = plan.keys.shape[0]
        fresh0 = tbl.dense_fresh_mask(plan.keys)
        d_slots, d_khash, d_valid, d_zeros = plan.device_operands(tbl._put)
        if fresh0 is not None:
            d_fresh = tbl._put(
                jnp.asarray(plan.pack(np.asarray(fresh0), bool, ())))
            tbl.mark_dense_active(plan.keys)
        else:
            d_fresh = d_zeros
        args_b = {}
        for fname, (dtype, shape) in m.args_schema.items():
            a = args_rounds[fname]
            if isinstance(a, jax.Array) and plan.identity \
                    and (M == tbl.n_shards * plan.B
                         or tbl.n_shards == 1):
                # DEVICE-resident staged payload on an identity plan: the
                # [K, M, ...] → [K, n, B, ...] layout is a reshape (plus
                # an on-device zero-pad to the bucket size when single-
                # shard), so keep it on device. The host path below would
                # round-trip the whole payload (device→host gather +
                # repack + re-upload), which is what the streaming hot
                # path exists to avoid
                a2 = a.astype(dtype)
                pad = tbl.n_shards * plan.B - M
                if pad:
                    a2 = jnp.pad(
                        a2, ((0, 0), (0, pad)) + ((0, 0),) * len(shape))
                args_b[fname] = tbl._put_rounds(
                    a2.reshape(K, tbl.n_shards, plan.B, *shape))
                continue
            a = np.asarray(a)
            packed = np.stack([plan.pack(a[k], dtype, shape)
                               for k in range(K)])
            args_b[fname] = tbl._put_rounds(jnp.asarray(packed))
        kern = self._scan_kernel(
            grain_class, method, plan.B, K,
            contiguous=self._plan_contiguous(tbl, plan),
            # static select-elision is ONLY safe when every lane is real:
            # a padded lane in contiguous mode addresses by position, and
            # an unmasked write there could corrupt a hashed activation's
            # slot beyond the dense range
            all_valid=bool(plan.valid_b.all()))
        led = self.ledger
        t_led = time.perf_counter() if led is not None else 0.0
        with self._fence:  # see call_batch: bulk ticks serialize with
            # the off-loop worker's donated in-flight batches
            new_state, results = kern(
                tbl.state, d_slots, d_khash, d_fresh, d_valid, args_b)
            if not m.read_only:
                tbl.state = new_state
                self._mark_dirty(grain_class, plan.keys)
        if self.track_load:
            tbl.record_hits(d_slots, d_valid, scale=K)
        if led is not None:
            # the wall already spans all K scanned rounds, so the µs
            # charge needs no scale=K (unlike the per-round hit counts)
            wall = max(0.0, time.perf_counter() - t_led)
            led.charge_tick(
                (grain_class.__name__, method, K * M, wall / max(1, K),
                 ()))
            if self.track_cost:
                tbl.record_cost(d_slots, d_valid, int(wall * 1e6))
        self.ticks += K
        self.messages_processed += K * M
        if device_results:
            return results  # [K, n, B, ...]
        return jax.tree_util.tree_map(
            lambda a: np.stack([plan.unpack(a[k]) for k in range(K)]),
            results)

    def _scan_kernel(self, cls: type, method: str, B: int, K: int,
                     contiguous: bool = False, all_valid: bool = False):
        tbl = self.tables[cls]
        key = ("scan", cls, method, B, K, tbl.capacity, tbl.n_shards,
               contiguous, self.scan_unroll, all_valid)
        k = self._kernel_cache.get(key)
        if k is None:
            k = self._build_kernel(cls, method, scan_rounds=K,
                                   contiguous=contiguous,
                                   scan_all_valid=all_valid)
            self._kernel_cache[key] = k
        return k

    def _plan_contiguous(self, tbl, plan: "_DensePlan") -> bool:
        """Identity plans touch slots [0, counts[s]) per shard in lane
        order — the gather/scatter degenerates to a contiguous slice of the
        slot pool (the 1M-actor bulk regime; ~1000x cheaper on TPU than a
        dynamic 1M-row gather)."""
        return plan.identity and plan.B <= tbl.capacity

    def call_batch_device(self, grain_class: type, method: str,
                          slots_b, khash_b, fresh_b, valid_b, args_b):
        """Zero-copy tick for callers that already hold device-layout
        [n_shards, B] batches (the transport layer / benchmarks). Returns
        the raw [n_shards, B, ...] result pytree without host transfer."""
        tbl = self.table(grain_class)
        m = self.method_of(grain_class, method)
        t_led = time.perf_counter() if self.ledger is not None else 0.0
        with self._fence:  # see call_batch: serialize with off-loop ticks
            new_state, results = self._kernel(
                grain_class, method, slots_b.shape[1])(
                    tbl.state, slots_b, khash_b, fresh_b, valid_b, args_b)
            if not m.read_only:
                tbl.state = new_state
        self._device_telemetry(tbl, method, slots_b, valid_b, t_led)
        self.ticks += 1
        if isinstance(valid_b, np.ndarray):
            self.messages_processed += int(valid_b.sum())
        else:
            # valid mask lives on device (exchange flows): counting it
            # would force a sync — track lanes separately so
            # messages_processed stays an honest delivered count
            self.exchange_lanes += int(valid_b.shape[0] * slots_b.shape[1])
        return results

    def _device_telemetry(self, tbl, method: str, slots_b, valid_b,
                          t_led: float, sink: list | None = None) -> None:
        """The telemetry of a tick whose lanes stay on the device (a
        device batch, a pass of the exchange): the hit and cost folds and
        the ledger's charge. ``sink`` is a job's deferred-stats list when
        the caller is the tick worker (the ledger is loop-confined: its
        charge replays there)."""
        if self.track_load:
            # device-resident masks fold without a host sync — the
            # telemetry stays all-device exactly like the exchange flow
            tbl.record_hits(slots_b, valid_b)
        led = self.ledger
        if led is not None:
            # rows = all lanes (a device-resident valid mask must not be
            # host-synced just to count); per-slot precision comes from
            # record_cost, whose masked scatter stays all-device too
            wall = max(0.0, time.perf_counter() - t_led)
            payload = (tbl.grain_class.__name__, method,
                       int(slots_b.shape[0] * slots_b.shape[1]), wall, ())
            if sink is not None:
                sink.append((_LEDGER, payload))
            else:
                led.charge_tick(payload)
            if self.track_cost:
                tbl.record_cost(slots_b, valid_b, int(wall * 1e6))

    # ------------------------------------------------------------------
    # Device-tier actor→actor messaging (the ICI fabric as an engine API)
    # ------------------------------------------------------------------
    def route(self, dest_class: type, dest_keys, payload: dict, valid,
              capacity: int = 256, sparse: bool = False):
        """Route per-message payloads to the shards owning ``dest_keys``
        over the tick exchange (ONE all_to_all on the silo axis —
        parallel.transport; the reference's silo-to-silo TCP fabric,
        SURVEY §2.4 "Point-to-point messaging backend").

        dest_keys/valid: [n_shards, B] device arrays (dense keys of
        ``dest_class``); payload: dict of [n_shards, B, ...]. Returns
        (recv_keys, recv_payload, recv_valid, drops) with recv lanes
        [n_shards, n_shards*capacity]. Overflow beyond ``capacity`` lanes
        per (src, dst) pair is dropped and counted (overload shedding —
        the host re-routes next tick).

        ``sparse=True``: dest_keys is a ``(keys_lo, keys_hi)`` int32 pair
        (62-bit uniform hashes split via ops.hash_probe.split64) and the
        owning shard is resolved ON DEVICE through the table's
        DeviceDirectory64 — the on-chip directory tier in the routing
        path (AdaptiveGrainDirectoryCache.cs:178). Unregistered keys are
        routed invalid (dropped + countable by the caller).
        """
        from ..parallel.transport import build_exchange

        if "__key__" in payload:
            raise ValueError("payload field name '__key__' is reserved")
        tbl = self.table(dest_class)
        key = ("exchange", tbl.n_shards, capacity)
        ex = self._kernel_cache.get(key)
        if ex is None:
            ex = build_exchange(self.mesh, capacity=capacity)
            self._kernel_cache[key] = ex
        if sparse:
            from ..ops.hash_probe import device_lookup64
            from .table import _LOC_STRIDE
            keys_lo, keys_hi = dest_keys
            tk_lo, tk_hi, tv = tbl.device_dir.device_arrays()
            loc, found = device_lookup64(
                tk_lo, tk_hi, tv,
                keys_lo.reshape(-1), keys_hi.reshape(-1),
                tbl.device_dir.max_probes)
            loc = loc.reshape(keys_lo.shape)
            found = found.reshape(keys_lo.shape)
            dest_shard = (loc // _LOC_STRIDE).astype(jnp.int32)
            routable = valid & found
            recv, recv_valid, drops = ex(
                dest_shard, routable,
                {"__key__": keys_lo, "__key_hi__": keys_hi, **payload})
            # unregistered destinations count as drops per source shard
            # (the caller's re-route/shed accounting), like overflow
            drops = drops + jnp.sum(valid & ~found, axis=-1)
            recv_lo = recv.pop("__key__")
            recv_hi = recv.pop("__key_hi__")
            return (recv_lo, recv_hi), recv, recv_valid, drops
        per = max(tbl.dense_per_shard, 1)
        dest_shard = (dest_keys // per).astype(jnp.int32)
        recv, recv_valid, drops = ex(
            dest_shard, valid, {"__key__": dest_keys, **payload})
        recv_keys = recv.pop("__key__")
        return recv_keys, recv, recv_valid, drops

    def apply_received(self, dest_class: type, method: str, recv_keys,
                       recv_valid, args: dict, sparse: bool = False):
        """Apply routed messages as invocations on ``dest_class`` — the
        receive half of a cross-shard actor call, entirely on device.

        Turn semantics under fan-in: at most one message per actor per
        tick. Duplicate same-actor deliveries within this batch are masked
        off ON DEVICE (first occurrence wins — deterministic lane order)
        and reported in the returned ``applied`` mask so the caller can
        re-route them next tick (the mailbox-defer analog). Requires the
        dest table's dense regime (keys pre-provisioned + activated; use
        fan-in reductions — ops.segment_sum — for aggregation patterns
        instead of high-duplication apply).

        Returns (results, applied): results [n_shards, L, ...] per-lane
        method results (junk on unapplied lanes), applied [n_shards, L].

        Write-behind dirty tracking is the CALLER's: this API never
        brings the applied keys to the host. The callers that hold the
        keys on the host mark them (``_broadcast_chunk``, and the served
        exchange of a sending method, ``_exchange``, which marks every
        delivered receiver dirty before its sender is acknowledged); a
        flow that keeps its keys on the device persists through
        scheduled table checkpoints
        (``add_vector_grains(checkpoint_dir=...)``).
        """
        tbl = self.table(dest_class)
        self.method_of(dest_class, method)  # validate the method exists

        if sparse:
            recv_lo, recv_hi = recv_keys
            tk_lo, tk_hi, tv = tbl.device_dir.device_arrays()
            slots, applied, khash = self._apply_resolver(
                dest_class, True)(recv_lo, recv_hi, recv_valid,
                                  tk_lo, tk_hi, tv)
            fresh = jnp.zeros_like(applied)
            results = self.call_batch_device(dest_class, method, slots,
                                             khash, fresh, applied, args)
            return results, applied

        slots, applied, khash = self._apply_resolver(
            dest_class, False)(recv_keys, recv_valid)
        fresh = jnp.zeros_like(applied)
        results = self.call_batch_device(dest_class, method, slots, khash,
                                         fresh, applied, args)
        return results, applied

    def _apply_resolver(self, dest_class: type, sparse: bool):
        """The cached jitted slot-resolution half of
        :meth:`apply_received` (key → local slot + first-delivery dedup
        mask). Cached per (class, regime, capacity, shard layout): a
        fresh ``jax.jit(local)`` per call would RETRACE on every
        delivery round — the repeated-fan-out hot path
        (broadcast_actors' dedup rounds) pays a full compile per round
        without this."""
        from ..ops.route import rank_dense_keys

        tbl = self.table(dest_class)
        per = max(tbl.dense_per_shard, 1)
        key = ("apply", dest_class, sparse, per, tbl.capacity,
               tbl.n_shards,
               tbl.device_dir.max_probes if sparse else 0)
        cached = self._kernel_cache.get(key)
        if cached is not None:
            return cached
        capacity = tbl.capacity
        n_shards = tbl.n_shards

        if sparse:
            from ..ops.hash_probe import device_lookup64
            from .table import _LOC_STRIDE
            probes = tbl.device_dir.max_probes

            def local(klo, khi, ok, dlo, dhi, dv):
                lo, hi, v = klo[0], khi[0], ok[0]
                loc, found = device_lookup64(dlo, dhi, dv, lo, hi, probes)
                if n_shards > 1:
                    myshard = jax.lax.axis_index(SILO_AXIS)
                else:
                    myshard = 0
                # defensive: a lane misrouted against a stale directory
                # must not scribble another actor's slot on this shard
                v = v & found & ((loc // _LOC_STRIDE) == myshard)
                slot = jnp.where(v, loc % _LOC_STRIDE, capacity)
                first = rank_dense_keys(jnp.where(v, slot,
                                                  capacity + 1)) == 0
                applied = v & first
                slot = jnp.where(applied, slot, capacity)
                return slot[None], applied[None], lo[None]

            if n_shards > 1:
                spec = P(SILO_AXIS)
                local = jax.shard_map(
                    local, mesh=self.mesh,
                    in_specs=(spec, spec, spec, P(), P(), P()),
                    out_specs=(spec, spec, spec), check_vma=False)
        else:
            def local(keys, ok):
                k, v = keys[0], ok[0]
                slot = jnp.where(v, k % per, capacity)
                # dedup: only the first delivery per actor applies this
                # tick
                first = rank_dense_keys(jnp.where(v, slot,
                                                  capacity + 1)) == 0
                applied = v & first
                slot = jnp.where(applied, slot, capacity)
                return slot[None], applied[None], \
                    (k & 0x7FFFFFFF).astype(jnp.int32)[None]

            if n_shards > 1:
                spec = P(SILO_AXIS)
                local = jax.shard_map(
                    local, mesh=self.mesh, in_specs=(spec, spec),
                    out_specs=(spec,) * 3, check_vma=False)
        cached = jax.jit(local)
        self._kernel_cache[key] = cached
        return cached

    # ------------------------------------------------------------------
    # Served grain-to-grain calls: a sending method's job (``@sends``)
    # is its tick, the loop's word on its receivers, and its exchange —
    # route / apply_received's own machinery, under the tick fence,
    # before the senders' replies resolve.
    # ------------------------------------------------------------------
    def _read_outbox(self, m: SendingMethod, sent) -> _Outbox | None:
        """Tick worker, inside the tick's sync: the outbox's destination
        keys and valid mask come to the host (the planner of the passes,
        the dirty marks and the counters need them; the payload stays on
        the device). A message for a key its class never provisioned
        cannot be delivered: it leaves the outbox and fails its sender.
        None where the job sent nothing."""
        keys_dev, valid_dev, payload = sent
        keys, valid = np.asarray(keys_dev), np.asarray(valid_dev)
        if not valid.any():
            return None
        ob = _Outbox(m.dest_class, m.dest_method, m.fanout, keys, valid,
                     keys_dev, payload)
        bad = valid & ((keys < 0) | (keys >= self.tables[m.dest_class].dense_n))
        if bad.any():
            ob.fail(bad, lambda key: KeyError(
                f"{m.name}: no {m.dest_class.__name__} with key {key} is "
                f"provisioned; the message was not delivered"))
        return ob

    def _activate_receivers(self, job: _TickJob) -> None:
        """Loop-side stage of a sending job, between its tick and its
        exchange: which receivers does the delivery have to initialise?
        Activation is the loop's bookkeeping (``dense_active``,
        ``uninit``, the recovery passes in flight), and the worker runs
        jobs in the order the loop hands them over — so the verdict is
        given here and the exchange is queued behind every job claimed
        before it. A receiver nothing has touched first gets its
        first-touch recovery pass (``receiver_recovery``, where the silo
        has storage); one the pass did not find, or whose first write
        waits unclaimed, starts from ``initial_state`` on delivery. A
        receiver whose read failed fails the senders that message it."""
        ob = job.outbox
        tbl = self.tables[ob.cls]
        st = self.stats
        span = StageSpan(st, "exchange.activate", nest=False,
                         tick=job.tick) if st is not None else None
        uniq = np.unique(ob.keys[ob.valid])
        untouched = uniq[~tbl.dense_active[uniq]]

        def verdict(errors: dict) -> None:
            try:
                decide(errors)
            except BaseException as e:  # noqa: BLE001 — a late pass's
                # callback has no caller to raise to: the job fails
                ob.fresh = np.zeros(0, np.int64)
                self._complete_job(job, job.host, e)

        def decide(errors: dict) -> None:
            if errors:
                ob.fail(ob.valid & np.isin(ob.keys, np.fromiter(
                    errors, np.int64, len(errors))), errors.get)
            live = np.unique(ob.keys[ob.valid])
            waiting = tbl.uninit.intersection(live.tolist()) \
                if tbl.uninit else ()
            fresh = live[~tbl.dense_active[live]]
            if waiting:
                # their first write is enqueued and unclaimed: the
                # delivery initialises the row, the write finds it made
                tbl.uninit.difference_update(waiting)
                fresh = np.union1d(fresh, np.fromiter(
                    waiting, np.int64, len(waiting)))
            tbl.mark_dense_active(fresh)
            ob.fresh = fresh
            if span is not None:
                span.close()
                job.t_hand = time.perf_counter()
            if self.offloop_tick:
                self._ensure_worker()
                self._worker_q.put(job)
            else:
                self._run_job(job, offloop=False)

        if untouched.size and self.receiver_recovery is not None:
            self.receiver_recovery(ob.cls, untouched.tolist(), verdict)
        else:
            verdict({})

    def _exchange(self, job: _TickJob) -> None:
        """Tick worker, under the fence: deliver a sending job's outbox.
        The host plans PASSES from the keys it read back: a source
        shard's messages for one destination shard, in lane order,
        ``capacity`` a pass — so ``route`` (one ``all_to_all`` a pass)
        never meets an overflow, and what is past the capacity is sent in
        the next pass, not dropped. A pass is applied in ONE launch
        however many of its messages reach one receiver: the host ranks
        each message among its receiver's (``_turns``), the rank rides
        the exchange beside the message, and the launch runs the pass's
        turns one after another (``_build_apply``) — turn k applies every
        receiver's k-th message and turn k + 1 reads the rows turn k
        wrote, so a receiver's messages land serially, in lane order, as
        successive ticks would land them. A fresh receiver's row is
        initialised by its first message of the first pass that reaches
        it. Every delivered receiver is marked dirty for the write-behind.
        The launch reports what it applied, the pass's one host read; a
        count that does not add up fails the job: nothing is dropped
        silently."""
        ob = job.outbox
        st, sink = self.stats, job.stats
        tbl = self.tables[ob.cls]
        n, per = tbl.n_shards, max(tbl.dense_per_shard, 1)
        keys, valid = ob.keys, ob.valid
        span = StageSpan(st, "exchange", sink, tick=job.tick) \
            if st is not None else None
        lanes = keys.shape[1]
        capacity = min(_EXCHANGE_CAP, max(MIN_BUCKET, lanes // (2 * n)))
        dest = keys // per
        rank = np.zeros(keys.shape, np.int64)
        for d in range(n):
            to_d = valid & (dest == d)
            rank[to_d] = (np.cumsum(to_d, axis=1) - 1)[to_d]
        in_pass = rank // capacity
        sent = int(valid.sum())
        fresh_left = ob.fresh
        apply = self._apply_kernel(ob.cls, ob.method, n * capacity)
        dropped = delivered = folded = 0
        n_passes = int(in_pass[valid].max()) + 1 if sent else 0
        for p in range(n_passes):
            mask = valid & (in_pass == p)
            fresh = mask & np.isin(keys, fresh_left)
            turn, depth, receivers = _turns(keys, mask)
            with StageSpan(st, "exchange.route", sink, tick=job.tick) \
                    if st is not None else NO_SPAN:
                recv_keys, recv, recv_valid, drops = self.route(
                    ob.cls, ob.keys_dev,
                    {**ob.payload, _FRESH: tbl._put(fresh),
                     _TURN: tbl._put(turn)},
                    tbl._put(mask), capacity=capacity)
                recv_fresh, recv_turn = recv.pop(_FRESH), recv.pop(_TURN)
            with StageSpan(st, "exchange.apply", sink, tick=job.tick) \
                    if st is not None else NO_SPAN:
                t_led = time.perf_counter() if self.ledger is not None \
                    else 0.0
                tbl.state, slots, got = apply(
                    tbl.state, recv_keys, recv_valid, recv_fresh,
                    recv_turn, recv)
                got = int(np.asarray(got).sum())
                self._device_telemetry(tbl, ob.method, slots, recv_valid,
                                       t_led, sink)
            msgs = int(mask.sum())
            delivered += got
            folded += msgs - receivers
            dropped += int(np.asarray(drops).sum())
            if got != msgs:
                raise RuntimeError(
                    f"{job.cls.__name__}.{job.method}: pass {p} of the "
                    f"exchange applied {got} of {msgs} messages "
                    f"({dropped} reported dropped)")
            if st is not None:
                sink.append((_DEPTH, depth))
            if fresh.any():
                fresh_left = np.setdiff1d(fresh_left, keys[fresh])
        self._mark_dirty(ob.cls, np.unique(keys[valid]))
        ob.delivered = delivered
        if st is not None:
            span.close()
            src = np.arange(n)[:, None]
            for name, v in (
                    ("jobs", 1), ("sent", sent), ("delivered", delivered),
                    ("cross_shard", int((valid & (dest != src)).sum())),
                    ("rounds", n_passes), ("passes", n_passes),
                    ("folded", folded),
                    ("lanes", n_passes * n * n * capacity),
                    ("activated", int(ob.fresh.size)),
                    ("dropped", dropped)):
                sink.append((_EXCH[name], v))

    def _apply_kernel(self, cls: type, method: str, lanes: int):
        tbl = self.tables[cls]
        key = ("apply_pass", cls, method, lanes, tbl.capacity, tbl.n_shards,
               tbl.dense_per_shard)
        k = self._kernel_cache.get(key)
        if k is None:
            k = self._kernel_cache[key] = self._build_apply(cls, method,
                                                            lanes)
        return k

    def _build_apply(self, cls: type, method: str, lanes: int):
        """The jitted launch that applies one pass of the exchange:
        ``(state, keys, valid, fresh, turn, args) -> (state, slots,
        applied)`` over a shard's ``lanes`` received lanes, ``turn`` each
        message's rank among its receiver's (``_turns``). The lanes are
        sorted by turn, and a ``while_loop`` — whose trip count is the
        pass's own, so no depth compiles anything — applies them turn by
        turn in chunks of ``lanes // 64`` lanes (at least ``MIN_BUCKET``):
        a turn's receivers are distinct, so a chunk gathers, initialises
        (a fresh receiver, on its first message), runs the method and
        scatters its rows at once, and the next turn reads what it wrote.
        A sparse late turn moves one chunk of rows, not the pass's.
        ``slots`` are the lanes' rows (the sink off the mask) for the
        hit and cost folds; ``applied`` counts the messages applied, a
        shard's one number."""
        import jax.lax as lax

        tbl = self.tables[cls]
        handler = tbl.methods[method].fn
        init = cls.initial_state
        capacity, per = tbl.capacity, max(tbl.dense_per_shard, 1)
        chunk = max(MIN_BUCKET, lanes // 64)
        tree = jax.tree_util.tree_map

        def sel(mask, a, b):
            return jnp.where(
                mask.reshape(mask.shape + (1,) * (a.ndim - 1)), a, b)

        def local(state, keys, valid, fresh, turn, args):
            rows_of = tree(lambda a: a[0], state)
            k, v, fr = keys[0], valid[0], fresh[0]
            args_l = tree(lambda a: a[0], args)
            t = jnp.where(v, turn[0], lanes)   # off the mask: past all
            order = jnp.argsort(t, stable=True)
            # bounds[d]: where turn d starts among the sorted lanes
            bounds = jnp.searchsorted(
                t[order], jnp.arange(lanes + 1, dtype=t.dtype))
            depth = jnp.max(jnp.where(v, turn[0], -1)) + 1
            step = jnp.arange(chunk, dtype=jnp.int32)

            def body(carry):
                st, d, j, n = carry
                at = bounds[d] + j * chunk + step
                ok = at < bounds[d + 1]
                lane = order[jnp.minimum(at, lanes - 1)]
                key = k[lane]
                slot = jnp.where(ok, key % per, capacity)
                rows = tree(lambda f: f[slot], st)
                first = ok & fr[lane] & (d == 0)
                init_rows = jax.vmap(init)(
                    (key & 0x7FFFFFFF).astype(jnp.int32))
                rows = tree(lambda ir, r: sel(first, ir, r), init_rows, rows)
                new_rows, _results = jax.vmap(handler)(
                    rows, tree(lambda a: a[lane], args_l))
                st = tree(lambda f, nr, r: f.at[slot].set(sel(ok, nr, r)),
                          st, new_rows, rows)
                more = bounds[d] + (j + 1) * chunk < bounds[d + 1]
                return (st, jnp.where(more, d, d + 1),
                        jnp.where(more, j + 1, 0),
                        n + ok.sum(dtype=jnp.int32))

            zero = jnp.int32(0)
            rows_of, _d, _j, applied = lax.while_loop(
                lambda c: c[1] < depth, body, (rows_of, zero, zero, zero))
            slots = jnp.where(v, k % per, capacity)
            return tree(lambda a: a[None], rows_of), slots[None], \
                applied[None]

        if tbl.n_shards > 1:
            spec = P(SILO_AXIS)
            local = jax.shard_map(
                local, mesh=tbl.mesh, in_specs=(spec,) * 6,
                out_specs=(spec,) * 3, check_vma=False)
        return jax.jit(local, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # Bulk-population collectives (MapReduce over actors — ROADMAP's
    # DrJAX direction, arXiv 2403.07128): population-wide fan-out/fan-in
    # compiled onto the sharded table as single-dispatch ticks instead of
    # message-per-edge RPC trains. All three primitives serialize with
    # the off-loop tick worker through the PR-9 fence, re-resolve key
    # locations per round (so grow/migration/checkpoint interleaving at
    # their await points is safe by construction), and defer keys that
    # have in-flight per-key turns exactly like call_group conflicts
    # defer (turn semantics: at most one message per activation per
    # tick, bulk or not).
    # ------------------------------------------------------------------
    def _bulk_resolve(self, cls: type, keys: np.ndarray | None
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
        """Resolve a bulk target set into ``(keys, shard, slot, fresh)``
        numpy arrays. ``keys=None`` targets every LIVE activation (dense
        keys actually touched + resident hashed rows — the "apply to the
        whole population" form). An explicit key subset may include
        dense-provisioned keys not yet activated (they fresh-init this
        tick, the call_batch auto-activate contract); hashed keys must
        be resident — non-resident ones are skipped, mirroring the
        live-actor semantics (the returned keys array is the applied
        set). Locations are resolved HERE, per call: bulk rounds never
        cache a (shard, slot) across an await, so a migration or grow
        between rounds can never strand a stale address."""
        tbl = self.table(cls)
        if keys is None:
            dense = np.flatnonzero(tbl.dense_active).astype(np.int64)
            n_h = len(tbl.key_to_slot)
            hashed = np.fromiter(tbl.key_to_slot, dtype=np.int64,
                                 count=n_h)
            fresh = np.zeros(dense.size + n_h, dtype=bool)
        else:
            # np.unique deduplicates: one message per actor per bulk op
            keys = np.unique(np.asarray(keys, dtype=np.int64))
            is_dense = (keys >= 0) & (keys < tbl.dense_n)
            dense = keys[is_dense]
            resident = np.fromiter(
                (k in tbl.key_to_slot for k in keys[~is_dense].tolist()),
                dtype=bool, count=int((~is_dense).sum()))
            hashed = keys[~is_dense][resident]
            fresh = np.concatenate([
                ~tbl.dense_active[dense] if dense.size else
                np.zeros(0, bool),
                np.zeros(hashed.size, bool)])
        d_sh, d_sl = tbl.dense_shard_slot(dense)
        d_shard, d_slot = d_sh.astype(np.int32), d_sl.astype(np.int32)
        if hashed.size:
            locs = np.array([tbl.key_to_slot[int(k)] for k in hashed],
                            dtype=np.int32).reshape(-1, 2)
            h_shard, h_slot = locs[:, 0], locs[:, 1]
        else:
            h_shard = h_slot = np.zeros(0, dtype=np.int32)
        out_keys = np.concatenate([dense, hashed]) if hashed.size \
            else dense
        shard = np.concatenate([d_shard, h_shard])
        slot = np.concatenate([d_slot, h_slot])
        return out_keys, shard, slot, fresh

    def _bulk_pack(self, tbl, shard: np.ndarray, slot: np.ndarray,
                   keys: np.ndarray, fresh: np.ndarray):
        """Arbitrary-location analog of ``make_dense_plan``'s layout:
        group M (shard, slot) targets into padded ``[n_shards, B]``
        batch buffers (idle lanes aim at the sink row)."""
        n = tbl.n_shards
        order = np.argsort(shard, kind="stable")
        counts = np.bincount(shard, minlength=n)
        B = _bucket(int(counts.max()) if shard.size else MIN_BUCKET)
        starts = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(counts, out=starts[1:])
        ss = shard[order]
        lane = np.arange(shard.size) - starts[ss]
        slots_b = np.full((n, B), tbl.sink_slot, dtype=np.int32)
        valid_b = np.zeros((n, B), dtype=bool)
        khash_b = np.zeros((n, B), dtype=np.int32)
        fresh_b = np.zeros((n, B), dtype=bool)
        slots_b[ss, lane] = slot[order]
        valid_b[ss, lane] = True
        khash_b[ss, lane] = (keys[order] & 0x7FFFFFFF).astype(np.int32)
        fresh_b[ss, lane] = fresh[order]
        return slots_b, khash_b, fresh_b, valid_b, B

    def _bulk_args(self, cls: type, m, kwargs: dict | None, n: int,
                   B: int) -> dict:
        """Broadcast ONE kwargs row to every lane of a ``[n, B]`` batch
        (the map/reduce payload form: same message to the whole
        population; per-actor payloads are call_batch's job)."""
        kwargs = kwargs or {}
        if m.args_schema is None:
            m.args_schema = {
                k: (np.asarray(v).dtype, np.asarray(v).shape)
                for k, v in kwargs.items()}
        _validate_args(cls, m.name, m.args_schema, kwargs)
        return {f: np.broadcast_to(
                    np.asarray(kwargs[f], dtype=dtype), (n, B, *shape))
                for f, (dtype, shape) in m.args_schema.items()}

    def _bulk_apply_once(self, cls: type, method: str, keys: np.ndarray,
                         shard: np.ndarray, slot: np.ndarray,
                         fresh: np.ndarray, kwargs: dict | None):
        """One bulk tick over resolved targets: pack → kernel → commit,
        under the tick fence (the caller IS the tick, like call_batch).
        Returns ``(results_device, valid_b)`` for the reduce half."""
        tbl = self.table(cls)
        m = self.method_of(cls, method)
        slots_b, khash_b, fresh_b, valid_b, B = self._bulk_pack(
            tbl, shard, slot, keys, fresh)
        args_b = self._bulk_args(cls, m, kwargs, tbl.n_shards, B)
        # the fence/kernel/commit/telemetry block is call_batch_device's
        # (one tick-semantics implementation, not two that drift); this
        # wrapper only adds the host-side bulk bookkeeping it can do
        # because it HOLDS the keys: write-behind dirty marks and dense
        # activation
        results = self.call_batch_device(
            cls, method, slots_b,
            jnp.asarray(khash_b), jnp.asarray(fresh_b), valid_b,
            {k: jnp.asarray(v) for k, v in args_b.items()})
        if not m.read_only:
            self._mark_dirty(cls, keys)
            if fresh.any():
                # read-only bulk ticks never write the fresh-init rows
                # back (the kernel skips the scatter), so marking those
                # keys active would hand later writes an uninitialized
                # row; the fresh mask just re-derives next call —
                # idempotent reads
                tbl.mark_dense_active(keys[fresh])
        return results, valid_b

    def _busy_split(self, cls: type, keys: np.ndarray):
        """Split targets into ``(ready, deferred, busy_mask)`` against
        keys with queued or worker-in-flight per-key turns — the bulk
        analog of ``_claim``'s same-slot conflict defer. ``busy_mask``
        is None when nothing is busy (the common case — callers use it
        to slice parallel arrays without recomputing the membership
        test)."""
        busy = self.pending_key_hashes(cls)
        if not busy:
            return keys, keys[:0], None
        mask = np.isin(keys, np.fromiter(busy, dtype=np.int64,
                                         count=len(busy)))
        return keys[~mask], keys[mask], mask

    async def _bulk_yield(self) -> None:
        """Let deferred per-key turns drain one round: run the pending
        tick (or await the off-loop worker's quiescence) before the next
        bulk round re-resolves."""
        if self.pending:
            self._tick()
        if self._inflight:
            await self._quiesced.wait()
        else:
            await asyncio.sleep(0)

    async def _bulk_rounds(self, grain_class: type, method: str,
                           kwargs: dict | None, keys, skip_busy: bool,
                           on_apply) -> None:
        """The ONE deferral-round driver behind map_actors and
        reduce_actors: resolve targets → split off keys with queued/
        in-flight per-key turns (unless ``skip_busy`` — read-only
        reductions have no turn to conflict with) → bulk-apply the
        ready slice → yield a tick round for the deferred rest and
        re-resolve. ``on_apply(results, valid_b, n_ready)`` accumulates
        per round. Shared so the conflict/selection logic cannot drift
        between the two primitives."""
        target_keys = keys
        while True:
            ks, shard, slot, fresh = self._bulk_resolve(grain_class,
                                                        target_keys)
            if skip_busy:
                ready, deferred, bmask = ks, ks[:0], None
            else:
                ready, deferred, bmask = self._busy_split(grain_class,
                                                          ks)
            if ready.size:
                sel = slice(None) if bmask is None else ~bmask
                results, valid_b = self._bulk_apply_once(
                    grain_class, method, ks[sel], shard[sel], slot[sel],
                    fresh[sel], kwargs)
                on_apply(results, valid_b, int(ready.size))
            if not deferred.size:
                return
            target_keys = deferred
            await self._bulk_yield()

    async def map_actors(self, grain_class: type, method: str,
                         kwargs: dict | None = None,
                         keys: np.ndarray | None = None) -> int:
        """Apply ``method`` (one broadcast kwargs row) to every live
        activation of ``grain_class`` — or a key subset — as bulk ticks:
        ONE kernel dispatch per conflict-free round instead of N per-key
        messages. Keys with in-flight per-key turns defer to later
        rounds (call_group conflict semantics); locations re-resolve per
        round, so migration/grow/checkpoint racing the await points stay
        safe under the tick fence. Returns the number of activations
        applied."""
        m = self.method_of(grain_class, method)
        if m.args_schema is not None:
            # validate up front: a schema mismatch must fail even when
            # the live population is empty (no batch ever runs)
            _validate_args(grain_class, method, m.args_schema,
                           kwargs or {})
        applied = 0

        def on_apply(_results, _valid_b, n: int) -> None:
            nonlocal applied
            applied += n

        await self._bulk_rounds(grain_class, method, kwargs, keys,
                                False, on_apply)
        return applied

    async def reduce_actors(self, grain_class: type, method: str,
                            kwargs: dict | None = None,
                            keys: np.ndarray | None = None,
                            combine: str = "sum"):
        """Run ``method`` over the population and reduce the per-actor
        results ON DEVICE (ops.segment_reduce.masked_reduce): ONE
        scalar/row crosses the host boundary instead of N responses.
        ``combine``: "sum" | "max" | "min" | "mean" (mean = sum/count,
        combined exactly across rounds and silos as (sum, count) pairs).
        Returns the reduced result pytree (host numpy); None when no
        live actor matched."""
        value, count = await self.reduce_actors_partial(
            grain_class, method, kwargs, keys, combine)
        if value is None or count == 0:
            return None
        if combine == "mean":
            return jax.tree_util.tree_map(lambda v: v / count, value)
        return value

    async def reduce_actors_partial(self, grain_class: type, method: str,
                                    kwargs: dict | None = None,
                                    keys: np.ndarray | None = None,
                                    combine: str = "sum"):
        """The combinable form of :meth:`reduce_actors`: returns
        ``(partial_value, count)`` where mean partials carry the SUM
        (divide at the top) — what the dispatcher's cross-silo merge
        folds, and what multi-round conflict deferral folds locally."""
        from ..ops.segment_reduce import (REDUCE_OPS, host_fold,
                                          masked_reduce)
        op = "sum" if combine == "mean" else combine
        if op not in REDUCE_OPS:
            raise ValueError(
                f"combine must be one of {REDUCE_OPS + ('mean',)}, "
                f"got {combine!r}")
        m = self.method_of(grain_class, method)
        if m.args_schema is not None:
            _validate_args(grain_class, method, m.args_schema,
                           kwargs or {})  # fail fast on empty tables too
        total = None
        count = 0
        fold = host_fold(op)

        def on_apply(results, valid_b, n: int) -> None:
            nonlocal total, count
            part = jax.tree_util.tree_map(
                np.asarray,
                masked_reduce(results, jnp.asarray(valid_b), op=op))
            count += n
            total = part if total is None else \
                jax.tree_util.tree_map(fold, total, part)

        # read-only reductions never write, so there is no turn to
        # conflict with — they run in one tick over everything
        await self._bulk_rounds(grain_class, method, kwargs, keys,
                                m.read_only, on_apply)
        return total, count

    def _init_kernel(self, cls: type, B: int):
        """Bulk OnActivate kernel: scatter ``initial_state(khash)`` rows
        at masked lanes — no handler, so it serves read-only methods
        too. Cached per (class, B, capacity, shards) like the tick
        kernels."""
        tbl = self.tables[cls]
        key = ("bulkinit", cls, B, tbl.capacity, tbl.n_shards)
        k = self._kernel_cache.get(key)
        if k is not None:
            return k
        init = cls.initial_state
        mesh = tbl.mesh

        def local(state, slots, khash, fresh):
            state_l = jax.tree_util.tree_map(lambda a: a[0], state)
            slots_l, khash_l, fresh_l = slots[0], khash[0], fresh[0]
            rows = jax.tree_util.tree_map(lambda f: f[slots_l], state_l)
            init_rows = jax.vmap(init)(khash_l)

            def sel(a, b):
                return jnp.where(
                    fresh_l.reshape(fresh_l.shape
                                    + (1,) * (a.ndim - 1)), a, b)

            new_state_l = jax.tree_util.tree_map(
                lambda f, ir, r: f.at[slots_l].set(sel(ir, r)),
                state_l, init_rows, rows)
            return jax.tree_util.tree_map(lambda a: a[None], new_state_l)

        body = local
        if tbl.n_shards > 1:
            spec = P(SILO_AXIS)
            body = jax.shard_map(
                body, mesh=mesh, in_specs=(spec, spec, spec, spec),
                out_specs=spec, check_vma=False)
        k = jax.jit(body, donate_argnums=(0,))
        self._kernel_cache[key] = k
        return k

    def _bulk_activate(self, cls: type, keys: np.ndarray) -> None:
        """Bulk OnActivate for dense keys a broadcast is about to
        scatter into: fresh-init rows land BEFORE apply_received's
        zero-fresh batches touch them (the per-key paths do this one
        activation at a time; bulk fan-out does it as one scatter)."""
        tbl = self.table(cls)
        fresh = tbl.dense_fresh_mask(keys)
        if fresh is None:
            return
        ks = np.unique(keys[fresh])
        sh, sl = tbl.dense_shard_slot(ks)
        shard, slot = sh.astype(np.int32), sl.astype(np.int32)
        slots_b, khash_b, fresh_b, _valid_b, B = self._bulk_pack(
            tbl, shard, slot, ks, np.ones(ks.size, bool))
        kern = self._init_kernel(cls, B)
        with self._fence:
            tbl.state = kern(tbl.state, jnp.asarray(slots_b),
                             jnp.asarray(khash_b), jnp.asarray(fresh_b))
        tbl.mark_dense_active(ks)

    async def broadcast_actors(self, grain_class: type, method: str,
                               targets: np.ndarray,
                               args: dict | None = None,
                               chunk: int = 16384) -> int:
        """Edge-list fan-out as device collectives: deliver ``method``
        to ``targets[i]`` with per-edge payload ``args[f][i]`` — the
        celebrity-post multicast as a handful of batched dispatches
        instead of O(edges) messages. Targets must be dense-regime keys
        (the follower-list case); each host-side chunk rides ONE
        ``parallel.transport`` exchange to the owning shards (capacity
        sized so overflow drops are impossible) and scatters into target
        rows via :meth:`apply_received`, whose on-device dedup gives
        duplicate targets the mailbox-defer semantics across ticks.
        Edge targets with in-flight per-key turns defer to later rounds
        like map_actors. Returns the number of edges delivered."""
        tbl = self.table(grain_class)
        targets = np.asarray(targets, dtype=np.int64).reshape(-1)
        if targets.size and (targets.min() < 0
                             or targets.max() >= tbl.dense_n):
            raise ValueError(
                "broadcast_actors targets must be dense-regime keys "
                f"in [0, {tbl.dense_n}); route hashed-key traffic "
                "through map_actors/call paths")
        m = self.method_of(grain_class, method)
        E = targets.shape[0]
        args = args or {}
        if m.args_schema is None:
            m.args_schema = {
                k: (np.asarray(v).dtype, np.asarray(v).shape[1:]
                    if np.asarray(v).ndim else ())
                for k, v in args.items()}
        schema = m.args_schema
        if set(args) != set(schema):
            _validate_args(grain_class, method, schema, args)
        # per-edge [E, *shape] payloads; scalars broadcast to every edge
        flat_args = {f: np.broadcast_to(
                         np.asarray(args[f], dtype=dtype), (E, *shape))
                     for f, (dtype, shape) in schema.items()}
        delivered = 0
        pending = (targets, flat_args)
        while pending[0].size:
            tg, fa = pending
            _ready, deferred, bmask = self._busy_split(grain_class, tg)
            if deferred.size:
                pending = (tg[bmask],
                           {f: a[bmask] for f, a in fa.items()})
                tg, fa = tg[~bmask], \
                    {f: a[~bmask] for f, a in fa.items()}
            else:
                pending = (tg[:0], {f: a[:0] for f, a in fa.items()})
            for off in range(0, tg.shape[0], chunk):
                if off:
                    # loop fairness between chunk dispatches: a
                    # celebrity-sized edge list is dozens of chunks and
                    # each is a synchronous device call — without a
                    # yield the whole pass blocks the loop past the
                    # membership probe timeout (the gauntlet QoS
                    # failure). One chunk stays the atomic quantum;
                    # chunks execute in order, so stacked item-major
                    # stream batches keep per-key token order.
                    await asyncio.sleep(0)
                ce = tg[off:off + chunk]
                ca = {f: a[off:off + chunk] for f, a in fa.items()}
                delivered += self._broadcast_chunk(grain_class, method,
                                                   ce, ca)
            if not pending[0].size:
                return delivered
            await self._bulk_yield()
        return delivered

    async def stream_fanout(self, grain_class: type, method: str,
                            targets: np.ndarray,
                            args: dict | None = None,
                            chunk: int = 16384) -> int:
        """Device-tier stream delivery entry (streams.device): one
        publish batch's per-subscriber fan-out rides the broadcast
        machinery unchanged — ``_bulk_activate`` fresh-init scatter,
        ``route`` edge exchange, ``apply_received`` dedup rounds, all
        under the tick fence (so grow/migration/checkpoint serialize
        with every delivery round exactly like PR-13 bulk ticks). The
        caller stacks a batch's items item-major, so the dedup rounds'
        first-occurrence-wins lane order IS per-key token order — the
        per-consumer event-order invariant. Returns edge-events
        delivered."""
        targets = np.asarray(targets, dtype=np.int64).reshape(-1)
        d = await self.broadcast_actors(grain_class, method, targets,
                                        args, chunk=chunk)
        self.last_stream_group = int(targets.size)
        if self.stats is not None:
            self.stats.increment("streams.device.fanout_rounds")
        return d

    def _broadcast_chunk(self, cls: type, method: str,
                         targets: np.ndarray, args: dict) -> int:
        """Route one edge chunk to its owning shards (one all_to_all)
        and apply it, re-applying deduped duplicate-target lanes tick by
        tick until every edge lands. Synchronous: the dedup rounds are
        back-to-back device calls (each under the tick fence via
        call_batch_device), so no per-key turn can interleave
        mid-chunk."""
        tbl = self.table(cls)
        self._bulk_activate(cls, targets)
        n = tbl.n_shards
        E = targets.shape[0]
        if E == 0:
            return 0
        schema = tbl.methods[method].args_schema
        if n == 1:
            # lane count bucketed to a power of two so partition-size
            # jitter across rounds reuses the same compiled kernels
            B = _bucket(E)
            pad = B - E
            recv_keys = jnp.asarray(np.concatenate(
                [targets, np.zeros(pad, dtype=targets.dtype)])[None, :])
            recv_valid = jnp.asarray(np.concatenate(
                [np.ones(E, bool), np.zeros(pad, bool)])[None, :])
            recv_args = {}
            for f, (dtype, shape) in schema.items():
                a = np.asarray(args[f], dtype=dtype)
                recv_args[f] = jnp.asarray(np.concatenate(
                    [a, np.zeros((pad, *shape), dtype=dtype)])[None])
        else:
            # split edges across source shards (the host is every
            # shard's ingress here), pad to equal POWER-OF-TWO lanes
            # (bucketed so varying edge counts reuse the compiled
            # exchange), capacity = lanes-per-shard so per-(src, dst)
            # overflow is impossible by construction (rank < L <=
            # capacity)
            L = _bucket(-(-E // n))
            pad = n * L - E
            tg = np.concatenate([targets,
                                 np.zeros(pad, dtype=targets.dtype)])
            vd = np.concatenate([np.ones(E, bool), np.zeros(pad, bool)])
            payload = {}
            for f, (dtype, shape) in schema.items():
                a = np.asarray(args[f], dtype=dtype)
                a = np.concatenate(
                    [a, np.zeros((pad, *shape), dtype=dtype)])
                payload[f] = jnp.asarray(a.reshape(n, L, *shape))
            recv_keys, recv_args, recv_valid, drops = self.route(
                cls, jnp.asarray(tg.reshape(n, L)), payload,
                jnp.asarray(vd.reshape(n, L)), capacity=L)
            # capacity == L makes overflow impossible; a nonzero count
            # here means the invariant broke, not load
            assert int(np.asarray(drops).sum()) == 0
        delivered = 0
        valid = recv_valid
        while True:
            _res, applied = self.apply_received(cls, method, recv_keys,
                                                valid, recv_args)
            valid = valid & ~applied
            got = int(np.asarray(jnp.sum(applied)))
            delivered += got
            left = int(np.asarray(jnp.sum(valid)))
            if left == 0 or got == 0:
                # got == 0 with lanes left cannot happen for in-range
                # dense keys (dedup always applies the first of each);
                # the guard keeps a logic bug from spinning forever
                break
        if delivered and not tbl.methods[method].read_only:
            # write-behind dirty marks: apply_received's device-resident
            # exchange exemption does NOT apply here — broadcast holds
            # the target keys on the host, so the flusher must see the
            # written rows or a restart silently reverts every
            # broadcast-applied update
            self._mark_dirty(cls, np.unique(targets))
        return delivered

    async def join_when(self, grain_class: type, keys: np.ndarray,
                        k: int | None = None, *, method: str,
                        kwargs: dict | None = None,
                        timeout: float | None = None,
                        poll: float = 0.02) -> int:
        """Join-calculus readiness step (arXiv 1302.6329 direction):
        resolve when at least ``k`` of ``keys`` (default: all) report
        ready through ``method`` — a read-only actor method returning
        0/1 per actor. Each poll is ONE reduce_actors sum (a single
        device reduction, one scalar to host) instead of K host futures
        bouncing through the loop. Returns the ready count observed."""
        keys = np.asarray(keys, dtype=np.int64)
        need = int(keys.size if k is None else k)
        return await join_poll(
            lambda: self.reduce_actors(grain_class, method, kwargs,
                                       keys=keys, combine="sum"),
            need, timeout, poll)

    # ------------------------------------------------------------------
    # Kernel construction
    # ------------------------------------------------------------------
    def _kernel(self, cls: type, method: str, B: int,
                contiguous: bool = False,
                layout: _PackedLayout | None = None):
        tbl = self.tables[cls]
        key = (cls, method, B, tbl.capacity, tbl.n_shards, contiguous,
               layout)
        k = self._kernel_cache.get(key)
        if k is None:
            k = self._build_kernel(cls, method, contiguous=contiguous,
                                   layout=layout)
            self._kernel_cache[key] = k
            if layout is not None:
                # first invocation compiles, and compiling a kernel that
                # donates its packed operand emits a known-benign
                # UserWarning: no output has the buffer's shape, so XLA
                # cannot alias it (donation stays correct). Suppress it
                # for THAT call only: the cache holds the raw kernel, so
                # steady-state ticks never touch the process warnings
                # filter, and application JAX code keeps the diagnostic
                # for its own kernels.
                raw = k

                def k(*a, _raw=raw):
                    with warnings.catch_warnings():
                        warnings.filterwarnings(
                            "ignore",
                            message="Some donated buffers were not usable")
                        return _raw(*a)
        return k

    def _build_kernel(self, cls: type, method: str, scan_rounds: int = 0,
                      contiguous: bool = False,
                      scan_all_valid: bool = False,
                      layout: _PackedLayout | None = None):
        """The jitted tick of one (class, method): ``(state, slots,
        khash, fresh, valid, args)``, or with ``layout`` the served
        tick's ``(state, packed)`` — the same step behind a prologue
        that slices each operand out of the packed buffer's words."""
        tbl = self.tables[cls]
        m = tbl.methods[method]
        handler = m.fn
        init = cls.initial_state
        mesh = tbl.mesh
        read_only = m.read_only
        sending = isinstance(m, SendingMethod)
        if sending and layout is None:
            raise NotImplementedError(
                f"{cls.__name__}.{method} sends messages: it is served "
                f"through call / call_group (a client's call), not "
                f"through the bulk and device-batch entry points")

        def make_access(slots_l):
            """(read, write_at) for this tick's slot addressing. The
            contiguous variant replaces the dynamic gather/scatter with
            static slices of the slot pool (identity plans: lane i ==
            slot i; ~1000x cheaper than a 1M-row gather on TPU)."""
            B = slots_l.shape[0]
            if contiguous:
                return (lambda f: f[:B]), \
                    (lambda f, v: f.at[:B].set(v))
            return (lambda f: f[slots_l]), \
                (lambda f, v: f.at[slots_l].set(v))

        def sel(mask, a, b):
            return jnp.where(
                mask.reshape(mask.shape + (1,) * (a.ndim - 1)), a, b)

        def local_step(state, slots, khash, fresh, valid, args):
            # block shapes: state [1, C+1, ...]; slots/khash/fresh/valid
            # [1, B]; args [1, B, ...] — squeeze the shard-block axis
            state_l = jax.tree_util.tree_map(lambda a: a[0], state)
            slots_l, khash_l = slots[0], khash[0]
            fresh_l, valid_l = fresh[0], valid[0]
            args_l = jax.tree_util.tree_map(lambda a: a[0], args)
            read, write_at = make_access(slots_l)

            rows = jax.tree_util.tree_map(read, state_l)
            init_rows = jax.vmap(init)(khash_l)
            rows = jax.tree_util.tree_map(
                lambda ir, r: sel(fresh_l, ir, r), init_rows, rows)
            new_rows, results = jax.vmap(handler)(rows, args_l)
            if sending:
                results, sent = results
            if read_only:
                # no state output: a table passed through a jit that
                # does not donate it comes back as a COPY, a whole-table
                # read and write a tick and a second table's memory
                out_state = ()
            else:
                write = valid_l
                new_state_l = jax.tree_util.tree_map(
                    lambda f, nr, r: write_at(f, sel(write, nr, r)),
                    state_l, new_rows, rows)
                out_state = jax.tree_util.tree_map(
                    lambda a: a[None], new_state_l)
            out = (out_state, jax.tree_util.tree_map(
                lambda a: a[None], results))
            if sending:
                # the outbox, [B, K, ...] a leaf, flattened to the
                # exchange's lanes: lane i * K + j is message j of call i,
                # and an idle call's messages are no messages
                okeys, ovalid, payload = sent
                ovalid = ovalid & valid_l[:, None]
                out += (jax.tree_util.tree_map(
                    lambda a: a.reshape(-1, *a.shape[2:])[None],
                    (okeys.astype(jnp.int32), ovalid, payload)),)
            return out

        if scan_rounds:
            import jax.lax as lax

            def init_pass(state, slots, khash, fresh, valid):
                # fresh-init BEFORE the scan: the OnActivate pre-pass, so
                # round 0 of the scan sees initialized rows and later rounds
                # never re-init
                st = jax.tree_util.tree_map(lambda a: a[0], state)
                slots_l, khash_l = slots[0], khash[0]
                write = fresh[0] & valid[0]
                read, write_at = make_access(slots_l)
                rows = jax.tree_util.tree_map(read, st)
                init_rows = jax.vmap(init)(khash_l)
                new_st = jax.tree_util.tree_map(
                    lambda f, ir, r: write_at(f, sel(write, ir, r)),
                    st, init_rows, rows)
                return jax.tree_util.tree_map(lambda a: a[None], new_st)

            def scan_step(carry, slots, valid, args_k):
                """The per-round scan body, statically specialized: the
                init pass already ran, so fresh-init is GONE by
                construction (not a runtime-zero mask the simplifier
                must fold), and when the plan covers every lane
                (scan_all_valid) the per-field validity select — a full
                extra read+where of each state field per round, a
                measurable slice of the MXU-handler engine tax — is
                dropped statically too."""
                state_l = jax.tree_util.tree_map(lambda a: a[0], carry)
                slots_l = slots[0]
                args_l = jax.tree_util.tree_map(lambda a: a[0], args_k)
                read, write_at = make_access(slots_l)
                rows = jax.tree_util.tree_map(read, state_l)
                new_rows, results = jax.vmap(handler)(rows, args_l)
                if read_only:
                    out_state = carry
                else:
                    if scan_all_valid:
                        new_state_l = jax.tree_util.tree_map(
                            write_at, state_l, new_rows)
                    else:
                        valid_l = valid[0]
                        new_state_l = jax.tree_util.tree_map(
                            lambda f, nr, r: write_at(
                                f, sel(valid_l, nr, r)),
                            state_l, new_rows, rows)
                    out_state = jax.tree_util.tree_map(
                        lambda a: a[None], new_state_l)
                return out_state, jax.tree_util.tree_map(
                    lambda a: a[None], results)

            def scanned(state, slots, khash, fresh, valid, args_rounds):
                # args_rounds leaves: [K, n, B, ...] — scan over K ticks;
                # tick k+1 reads the state tick k wrote (serial turns)
                state = init_pass(state, slots, khash, fresh, valid)

                def one(carry, args_k):
                    return scan_step(carry, slots, valid, args_k)
                out_state, results = lax.scan(
                    one, state, args_rounds,
                    unroll=max(1, self.scan_unroll))
                # as in local_step: nothing was written, return no table
                return (() if read_only else out_state), results

            body = scanned
        elif layout is not None:
            def body(state, packed):
                # block shape [1, words]: every operand is a static
                # slice of the row, reinterpreted to its dtype
                cols = [_unpack_words(packed[:, off:off + count], dt,
                                      (layout.B, *shape))
                        for dt, shape, off, count in layout.fields]
                return local_step(state, *cols[:4],
                                  dict(zip(layout.names, cols[4:])))
        else:
            body = local_step

        if tbl.n_shards > 1:
            spec = P(SILO_AXIS)
            pspec = P(None, SILO_AXIS) if scan_rounds else spec
            body = jax.shard_map(
                body, mesh=mesh,
                in_specs=(spec, spec) if layout is not None else
                (spec, spec, spec, spec, spec, pspec),
                out_specs=(spec, P(None, SILO_AXIS) if scan_rounds else spec)
                + (spec,) * sending,
                check_vma=False)
        # else: single-shard — shard_map would be a no-op; plain jit over
        # the table's committed arrays runs on the mesh's one device
        if read_only:
            donate: tuple = ()
        elif layout is not None:
            # the packed operand is a fresh array a tick that the caller
            # never reuses: donated alongside the state. (Kernels fed by
            # cached _DensePlan.device_operands never donate theirs:
            # those persist across ticks by design.)
            donate = (0, 1)
        else:
            donate = (0,)
        return jax.jit(body, donate_argnums=donate)
