"""Compiles for the real chip, kept as tests (no chip needed).

The TPU's compiler is installed in the sandbox and compiles for a chip
that is described, not attached. These are the programs ``chip_smoke.py``
runs on a v5e, at its sizes: both Pallas kernels, the one-chip tick, bulk
and scan kernels over a 1M-row table, and the four-device ``shard_map``
kernel plus the exchange (``all_to_all`` with the Pallas rank inside).
What the chip's compiler would refuse — a misaligned slice, too much
fast memory, a program that does not fit — fails here, at no chip time.
Nothing executes, so nothing here says anything about results or speed.

The topology is described inside a module-scoped fixture of THIS file and
nowhere else: only one process may load libtpu, the driver runs the suite
under several workers, and each worker imports every test file — so
nothing at import time, in a ``skipif`` or in ``conftest.py`` may touch
it. Keep every such test in this one file.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "samples"))

N_PLAYERS = 1_000_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without the chip: keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    from orleans_tpu.parallel import SILO_AXIS
    assert len(topo.devices) == 4
    return Mesh(np.array(topo.devices), (SILO_AXIS,))


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


# ---------------------------------------------------------------------------
# the two Pallas kernels at the smoke's shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,D", [(1 << 20, 1024, 128), (1 << 16, 1024, 0)])
def test_segment_sum_pallas_compiles_for_v5e(one_chip, B, S, D):
    from orleans_tpu.ops import segment_sum_pallas

    vals = _struct((B, D) if D else (B,), jnp.float32, one_chip)
    ids = _struct((B,), jnp.int32, one_chip)
    compiled, text = _compile(
        lambda v, s: segment_sum_pallas(v, s, S), vals, ids)
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= B * max(D, 1) * 4


@pytest.mark.parametrize("B,S", [(32768, 5), (4096, 9)])
def test_rank_by_dest_pallas_compiles_for_v5e(one_chip, B, S):
    from orleans_tpu.ops import rank_by_dest

    dest = _struct((B,), jnp.int32, one_chip)
    _, text = _compile(
        lambda d: rank_by_dest(d, S, use_pallas=True), dest)
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# the engine's kernels over a 1M-row table, one chip
# ---------------------------------------------------------------------------

def _presence_runtime(mesh, n_players):
    from orleans_tpu.dispatch import VectorRuntime
    from presence_tpu import PlayerVectorGrain

    n = mesh.devices.size
    rt = VectorRuntime(mesh=mesh, capacity_per_shard=-(-n_players // n))
    tbl = rt.table(PlayerVectorGrain)
    tbl.ensure_dense(n_players)
    return rt, tbl, PlayerVectorGrain


def _kernel_operands(tbl, B, sharding, rounds=0, rounds_sharding=None):
    n = tbl.n_shards
    state = {k: _struct(v.shape, v.dtype, sharding)
             for k, v in tbl.state.items()}
    lane = (n, B)
    lead = (rounds,) if rounds else ()
    args = {"pos": _struct((*lead, *lane, 2), jnp.float16,
                           rounds_sharding or sharding),
            "delta": _struct((*lead, *lane), jnp.int32,
                             rounds_sharding or sharding)}
    return (state, _struct(lane, jnp.int32, sharding),
            _struct(lane, jnp.int32, sharding),
            _struct(lane, jnp.bool_, sharding),
            _struct(lane, jnp.bool_, sharding), args)


@pytest.fixture(scope="module")
def presence_1m():
    from orleans_tpu.parallel import make_mesh
    return _presence_runtime(make_mesh(1), N_PLAYERS)


def _served_tick(rt, cls, method, B, state, sharding, leaves_read=None):
    """The per-tick kernel as the served path launches it, compiled for
    the described chip: ``(state, packed)``, one operand buffer that the
    kernel unpacks by the staging set's layout, both donated.
    ``leaves_read``: how many state leaves the method reads, where a
    read-only method leaves some out (the compiler drops those)."""
    from orleans_tpu.dispatch.engine import _packed_layout

    layout = _packed_layout(B, rt.method_of(cls, method).args_schema)
    kern = rt._build_kernel(cls, method, layout=layout)
    n_shards = next(iter(state.values())).shape[0]
    compiled = kern.lower(
        state, _struct((n_shards, layout.words), jnp.int32,
                       sharding)).compile()
    # exactly two parameters: the table and the one staged buffer (to
    # the chip: one array a state leaf, and one more)
    (args, kwargs) = compiled.in_avals
    assert not kwargs and len(args) == 2
    assert len(jax.tree_util.tree_leaves(args[1])) == 1
    entry = compiled.as_text().split("ENTRY", 1)[1]
    assert entry.count(" parameter(") == (leaves_read or len(state)) + 1
    return compiled


def _table_sized_copies(compiled, table_shape) -> list[str]:
    """The compiled program's ``copy`` instructions that produce an array
    of the table leaf's shape."""
    dims = ",".join(str(d) for d in table_shape)
    return [line.strip() for line in compiled.as_text().splitlines()
            if " copy(" in line and f"[{dims}]" in line.split(" copy(")[0]]


def test_served_tick_kernel_compiles_for_v5e(one_chip, presence_1m):
    """A 1024-lane batch gathered from the 1M-row table and scattered
    back in place."""
    rt, tbl, Player = presence_1m
    state = {k: _struct(v.shape, v.dtype, one_chip)
             for k, v in tbl.state.items()}
    compiled = _served_tick(rt, Player, "heartbeat", 1024, state, one_chip)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0          # the state really aliases
    assert mem.argument_size_in_bytes < 1 << 30
    assert mem.temp_size_in_bytes < 1 << 20     # no second table
    for leaf in tbl.state.values():
        assert not _table_sized_copies(compiled, leaf.shape)


def test_bulk_tick_kernel_compiles_for_v5e(one_chip, presence_1m):
    """call_batch over the whole population: the contiguous plan."""
    rt, tbl, Player = presence_1m
    kern = rt._build_kernel(Player, "heartbeat", contiguous=True)
    compiled = kern.lower(
        *_kernel_operands(tbl, tbl.capacity, one_chip)).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0


def test_scan_kernel_compiles_for_v5e(one_chip, presence_1m):
    """call_batch_rounds: K=8 rounds scanned in one launch."""
    rt, tbl, Player = presence_1m
    kern = rt._build_kernel(Player, "heartbeat", scan_rounds=8,
                            contiguous=True, scan_all_valid=False)
    compiled = kern.lower(*_kernel_operands(
        tbl, tbl.capacity, one_chip, rounds=8)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0
    assert mem.temp_size_in_bytes < 8 << 30     # fits the 16 GB chip


# ---------------------------------------------------------------------------
# the YCSB record's kernels over a 1M-row, 1 GiB table, one chip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method,B", [("update", 1024), ("read", 1024),
                                      ("update", 8), ("read", 8)])
def test_ycsb_tick_kernels_touch_rows_not_the_table(one_chip, method, B):
    """``fields`` is u8[1024] a row because the chip's default layout of
    that leaf is row-major: the update scatters in place (the table
    aliases, no table-sized temporary) and the read returns no table. A
    leaf the chip lays out otherwise (u8[1000], i32[250]) costs two
    copies of the 1 GiB table a tick, and shows here as a 1 GiB temp."""
    from orleans_tpu.dispatch import VectorRuntime
    from orleans_tpu.parallel import make_mesh

    from ycsb_tpu import RecordVectorGrain as Record
    # the kernel builder needs the class and the mesh, not a 1 GiB table
    rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=64)
    rt.table(Record)
    rows = (1 << 20) + 1
    state = {"fields": _struct((1, rows, 1024), jnp.uint8, one_chip),
             "ver": _struct((1, rows), jnp.int32, one_chip)}
    compiled = _served_tick(rt, Record, method, B, state, one_chip)
    mem = compiled.memory_analysis()
    table = rows * 1028
    assert mem.temp_size_in_bytes < 64 << 20
    assert not _table_sized_copies(compiled, (1, rows, 1024))
    if method == "update":
        assert mem.alias_size_in_bytes >= table     # in place
    else:
        assert mem.alias_size_in_bytes == 0
        assert mem.output_size_in_bytes < 4 << 20   # replies, no table


# ---------------------------------------------------------------------------
# four chips: the shard_map kernel and the exchange
# ---------------------------------------------------------------------------

_COLLECTIVES = ("all-to-all", "all-reduce", "all-gather",
                "collective-permute", "reduce-scatter")


@pytest.fixture(scope="module")
def presence_4m():
    from orleans_tpu.parallel import make_mesh
    return _presence_runtime(make_mesh(4), 4 * N_PLAYERS)


@pytest.mark.parametrize("B", [1024, 4096])
def test_sharded_served_tick_compiles_for_v5e_2x2(four_chips, presence_4m,
                                                  B):
    """presence-4m's tick as the served path launches it on the 2x2: 4M
    rows, a quarter on each chip, ``(state, packed)`` sharded on the
    leading axis, ticked in place — and no collective: a client's call
    is routed to its shard on the host, so the shards do not talk."""
    from orleans_tpu.parallel import SILO_AXIS

    # built on four CPU devices; the kernel builder gets the described mesh
    rt, tbl, Player = presence_4m
    tbl.mesh = four_chips
    shard = NamedSharding(four_chips, P(SILO_AXIS))
    state = {k: _struct(v.shape, v.dtype, shard)
             for k, v in tbl.state.items()}
    compiled = _served_tick(rt, Player, "heartbeat", B, state, shard)
    mem = compiled.memory_analysis()
    state_bytes = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                      for v in tbl.state.values())
    assert 0 < mem.alias_size_in_bytes <= state_bytes // 4 + 4096
    assert mem.temp_size_in_bytes < 1 << 20     # no second table
    for leaf in tbl.state.values():
        per_chip = (1, *leaf.shape[1:])
        assert not _table_sized_copies(compiled, per_chip)
    text = compiled.as_text()
    assert not [c for c in _COLLECTIVES if c in text]


def test_sharded_scan_and_exchange_compile_for_v5e_2x2(
        four_chips, monkeypatch):
    """The 1M-player table over the described 2x2: the scanned heartbeat
    kernel under ``shard_map`` and ``build_exchange`` with the Pallas rank
    inside it, each argument a NamedSharding on the described mesh."""
    from orleans_tpu.parallel import SILO_AXIS, make_mesh
    from orleans_tpu.parallel.transport import build_exchange

    # the runtime allocates its table where it is built, and nothing can
    # be copied to a described device: build on four CPU devices, then
    # hand the kernel builder the described mesh
    rt, tbl, Player = _presence_runtime(make_mesh(4), N_PLAYERS)
    tbl.mesh = four_chips
    shard = NamedSharding(four_chips, P(SILO_AXIS))
    rounds = NamedSharding(four_chips, P(None, SILO_AXIS))
    B = 1 << 18                                  # 250k lanes, bucketed
    kern = rt._build_kernel(Player, "heartbeat", scan_rounds=8,
                            contiguous=True)
    compiled = kern.lower(*_kernel_operands(
        tbl, B, shard, rounds=8, rounds_sharding=rounds)).compile()
    mem = compiled.memory_analysis()
    state_bytes = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                      for v in tbl.state.values())
    # per device: a quarter of the table, not all of it
    assert mem.alias_size_in_bytes <= state_bytes // 4 + 4096

    # ops.route asks jax.default_backend() whether the MXU rank kernel
    # applies; the process's backend is the CPU, the program is for a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lanes, capacity = 32768, 10240
    ex = build_exchange(four_chips, capacity=capacity)
    compiled = ex.lower(
        _struct((4, lanes), jnp.int32, shard),
        _struct((4, lanes), jnp.bool_, shard),
        {"__key__": _struct((4, lanes), jnp.int32, shard),
         "score": _struct((4, lanes), jnp.int32, shard)}).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text
    assert compiled.memory_analysis().output_size_in_bytes < 1 << 30


# ---------------------------------------------------------------------------
# Chirper on the 2x2: 262,144 accounts of 33 KB, a publish's tick, its
# exchange and the delivery rounds (chipbench/apps/chirper.py)
# ---------------------------------------------------------------------------

CHIRPER_ROWS = 65536 + 1     # a chip's accounts and the sink row


@pytest.fixture(scope="module")
def chirper(four_chips):
    import importlib.util

    from orleans_tpu.dispatch import VectorRuntime
    from orleans_tpu.parallel import SILO_AXIS, make_mesh

    spec = importlib.util.spec_from_file_location(
        "chip_compile_chirper", os.path.join(
            os.path.dirname(__file__), "..", "chipbench", "apps",
            "chirper.py"))
    app = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(app)
    Account = app.GRAINS["ChirperAccount"]
    # the kernel builder needs the class and a mesh, not an 8.7 GB table
    rt = VectorRuntime(mesh=make_mesh(4), capacity_per_shard=64)
    rt.register(Account)
    rt.table(Account).ensure_dense(256)
    rt.mesh = rt.table(Account).mesh = four_chips
    shard = NamedSharding(four_chips, P(SILO_AXIS))
    state = {f: _struct((4, CHIRPER_ROWS, *shape), dtype, shard)
             for f, (dtype, shape) in Account.STATE.items()}
    return rt, Account, state, shard


def _chip_table_bytes(state) -> int:
    return sum(int(np.prod(v.shape[1:])) * v.dtype.itemsize
               for v in state.values())


@pytest.mark.parametrize("method,B", [("publish", 32), ("publish", 256),
                                      ("get_received", 256)])
def test_chirper_served_ticks_touch_rows_not_the_table(chirper, method, B):
    """The 32 KB-row table (``timeline`` u8[32768], a 1,024-multiple) is
    ticked in place on every chip: ``publish`` aliases the table and
    writes its outbox beside it, ``get_received`` returns no table; no
    copy of a chip's 2.1 GB leaf, and no collective — a client's call is
    routed to its shard on the host."""
    rt, Account, state, shard = chirper
    compiled = _served_tick(
        rt, Account, method, B, state, shard,
        # a read answers from the ring, its head and the count
        leaves_read=3 if method == "get_received" else None)
    mem = compiled.memory_analysis()
    table = _chip_table_bytes(state)
    assert table == CHIRPER_ROWS * 33296
    assert not _table_sized_copies(compiled, (1, CHIRPER_ROWS, 32768))
    assert mem.temp_size_in_bytes < 512 << 20
    if method == "publish":
        assert mem.alias_size_in_bytes >= table          # in place
        # the outbox: B x 128 lanes of a 320-byte chirp, a key and a mask
        assert mem.output_size_in_bytes - table < 2 * B * 128 * 512
    else:
        assert mem.alias_size_in_bytes == 0
        assert mem.output_size_in_bytes < 4 << 20        # replies only
    text = compiled.as_text()
    assert not [c for c in _COLLECTIVES if c in text]


@pytest.mark.parametrize("lanes", [512, 4096])
def test_chirper_delivery_round_ticks_in_place(chirper, lanes):
    """One apply round of the exchange: ``receive`` over the lanes a pass
    delivered, the table aliased, rows gathered and scattered (``lanes``
    rows of temporaries, not a table), no collective; and the resolver
    that dedups the lanes before it."""
    rt, Account, state, shard = chirper
    kern = rt._build_kernel(Account, "receive")
    lane = lambda dt, *s: _struct((4, lanes, *s), dt, shard)  # noqa: E731
    compiled = kern.lower(
        state, lane(jnp.int32), lane(jnp.int32), lane(jnp.bool_),
        lane(jnp.bool_), {"chirp": lane(jnp.uint8, 320)}).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _chip_table_bytes(state)
    assert not _table_sized_copies(compiled, (1, CHIRPER_ROWS, 32768))
    assert mem.temp_size_in_bytes < 8 * lanes * 32768 + (64 << 20)
    text = compiled.as_text()
    assert not [c for c in _COLLECTIVES if c in text]
    resolver = rt._apply_resolver(Account, False).lower(
        lane(jnp.int32), lane(jnp.bool_)).compile()
    assert not [c for c in _COLLECTIVES if c in resolver.as_text()]


def test_chirper_exchange_is_one_all_to_all_and_no_other_collective(
        chirper, monkeypatch):
    """A pass of a publish job's exchange on the 2x2, at the shapes of a
    job of 32 publishes a shard (4,096 outbox lanes, 512 a pair): the
    chirps, their keys and the fresh marks cross in ``all-to-all``s and
    nothing else talks — no all-reduce, all-gather, collective-permute
    or reduce-scatter."""
    from orleans_tpu.parallel.transport import build_exchange

    rt, Account, state, shard = chirper
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lanes, capacity = 4096, 512
    ex = build_exchange(rt.table(Account).mesh, capacity=capacity)
    lane = lambda dt, *s: _struct((4, lanes, *s), dt, shard)  # noqa: E731
    compiled = ex.lower(
        lane(jnp.int32), lane(jnp.bool_),
        {"__key__": lane(jnp.int32), "__fresh__": lane(jnp.bool_),
         "chirp": lane(jnp.uint8, 320)}).compile()
    text = compiled.as_text()
    assert "all-to-all" in text and "tpu_custom_call" in text
    assert [c for c in _COLLECTIVES if c in text] == ["all-to-all"]
    assert compiled.memory_analysis().output_size_in_bytes < 64 << 20


def test_chirper_write_behind_gather_compiles_at_the_chunk(chirper):
    """A write-behind pass brings Chirper's 33 KB rows down 512 at a time
    (``VectorStorageBridge._chunk_rows``: the largest power of two under
    ``_CHUNK_BYTES``): that gather on the 2x2 is 17 MB of result a device
    and no temporaries; its result is replicated (an ``all-reduce`` of
    what each shard gathered), so any one device's copy is the rows."""
    from types import SimpleNamespace

    from orleans_tpu.storage.checkpoint import (VectorStorageBridge,
                                                _gather_rows)

    rt, _Account, state, _shard = chirper
    mesh = rt.table(_Account).mesh
    rows = VectorStorageBridge._chunk_rows(SimpleNamespace(state=state))
    assert rows == 512
    compiled = _gather_rows.lower(
        state, _struct((2, rows), jnp.int32, NamedSharding(mesh, P()))
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes <= rows * 33296 + 4096
    assert mem.temp_size_in_bytes < 1 << 20
    assert all(s.is_fully_replicated
               for s in jax.tree.leaves(compiled.output_shardings))
