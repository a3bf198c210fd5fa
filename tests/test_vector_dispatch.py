"""Batched device-tier dispatch tests — the PingBenchmark acceptance tier
(reference test/Benchmarks/Ping/PingBenchmark.cs shape: many EchoGrains,
batched no-op invokes) plus turn-semantics guarantees under batching."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.dispatch import VectorGrain, VectorRuntime, actor_method
from orleans_tpu.parallel import make_mesh


class EchoActor(VectorGrain):
    """EchoGrain analog: state counts calls, echo returns the payload."""

    STATE = {"calls": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"calls": jnp.int32(0)}

    @actor_method(args={"x": (jnp.float32, ())})
    def echo(state, args):
        return {"calls": state["calls"] + 1}, {"x": args["x"],
                                               "calls": state["calls"] + 1}


class CounterActor(VectorGrain):
    STATE = {"value": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"value": jnp.int32(0)}

    @actor_method(args={"n": (jnp.int32, ())})
    def add(state, args):
        v = state["value"] + args["n"]
        return {"value": v}, v

    @actor_method(args={}, read_only=True)
    def get(state, args):
        return state, state["value"]


class PlayerActor(VectorGrain):
    """Presence PlayerGrain analog: position + heartbeat counter."""

    STATE = {"pos": (jnp.float32, (2,)), "beats": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"pos": jnp.zeros(2, jnp.float32), "beats": jnp.int32(0)}

    @actor_method(args={"pos": (jnp.float32, (2,))})
    def heartbeat(state, args):
        new = {"pos": args["pos"], "beats": state["beats"] + 1}
        return new, new["beats"]


async def test_single_call_roundtrip():
    rt = VectorRuntime()
    ref = rt.actor(EchoActor, 7)
    out = await ref.echo(x=np.float32(3.5))
    assert out["x"] == np.float32(3.5)
    assert out["calls"] == 1


async def test_state_persists_across_ticks():
    rt = VectorRuntime()
    c = rt.actor(CounterActor, 1)
    assert await c.add(n=5) == 5
    assert await c.add(n=3) == 8
    assert await c.get() == 8


async def test_batched_fanout_10k_echo_actors():
    """10k distinct actors in one gather → one tick, not 10k turns."""
    rt = VectorRuntime(capacity_per_shard=2048)
    futs = [rt.call(EchoActor, i, "echo", x=np.float32(i))
            for i in range(10_000)]
    out = await asyncio.gather(*futs)
    assert rt.ticks <= 3  # coalesced, not per-message
    assert out[1234]["x"] == np.float32(1234)
    assert all(o["calls"] == 1 for o in out[:100])


async def test_same_actor_conflicts_defer_to_next_tick():
    """Two messages to one activation in one batch: serial turns."""
    rt = VectorRuntime()
    c = rt.actor(CounterActor, 9)
    r = await asyncio.gather(c.add(n=1), c.add(n=10), c.add(n=100))
    assert sorted(int(x) for x in r) == [1, 11, 111]
    assert rt.ticks >= 3


async def test_fresh_init_on_first_message():
    rt = VectorRuntime()
    out = await rt.actor(PlayerActor, 55).heartbeat(
        pos=np.array([1.0, 2.0], np.float32))
    assert out == 1
    row = rt.table(PlayerActor).read_row(55)
    assert row["beats"] == 1
    np.testing.assert_allclose(row["pos"], [1.0, 2.0])


async def test_table_growth():
    rt = VectorRuntime(capacity_per_shard=8)
    tbl = rt.table(CounterActor)
    start_cap = tbl.capacity
    futs = [rt.call(CounterActor, i, "add", n=np.int32(1))
            for i in range(1000)]
    await asyncio.gather(*futs)
    assert tbl.capacity > start_cap
    # state survives growth
    assert await rt.actor(CounterActor, 3).get() == 1


async def test_deactivation_frees_slot_and_reinit():
    rt = VectorRuntime()
    c = rt.actor(CounterActor, 4)
    await c.add(n=42)
    assert rt.table(CounterActor).release(4)
    # next call re-activates fresh (virtual actor identity)
    assert await c.add(n=1) == 1


async def test_multi_shard_distribution():
    """8-device CPU mesh: actors spread across all shards."""
    mesh = make_mesh(8)
    rt = VectorRuntime(mesh=mesh)
    futs = [rt.call(CounterActor, i, "add", n=np.int32(i))
            for i in range(64)]
    await asyncio.gather(*futs)
    tbl = rt.table(CounterActor)
    shards = {s for (s, _) in tbl.key_to_slot.values()}
    assert shards == set(range(8))
    assert await rt.actor(CounterActor, 63).get() == 63


async def test_dense_bulk_call_batch():
    """The 1M-msgs/sec path: vectorized key mapping, one kernel launch."""
    mesh = make_mesh(8)
    rt = VectorRuntime(mesh=mesh, capacity_per_shard=4096)
    tbl = rt.table(PlayerActor)
    n = 10_000
    tbl.ensure_dense(n)
    keys = np.arange(n)
    pos = np.random.rand(n, 2).astype(np.float32)
    ticks_before = rt.ticks
    out = rt.call_batch(PlayerActor, "heartbeat", keys,
                        {"pos": pos}, fresh=np.ones(n, bool))
    assert rt.ticks == ticks_before + 1
    assert out.shape == (n,)
    assert (out == 1).all()
    out2 = rt.call_batch(PlayerActor, "heartbeat", keys, {"pos": pos})
    assert (out2 == 2).all()
    row = tbl.read_row(777)
    np.testing.assert_allclose(row["pos"], pos[777])


async def test_read_only_method_skips_writeback():
    rt = VectorRuntime()
    c = rt.actor(CounterActor, 11)
    await c.add(n=7)
    before = rt.table(CounterActor).state["value"]
    await c.get()
    assert rt.table(CounterActor).state["value"] is before  # same buffer


async def test_unknown_method_raises():
    rt = VectorRuntime()
    with pytest.raises(AttributeError):
        rt.actor(CounterActor, 0).nope()


async def test_scanned_rounds_serial_turn_semantics():
    """K rounds in one scanned kernel: round k+1 must see round k's state."""
    mesh = make_mesh(8)
    rt = VectorRuntime(mesh=mesh, capacity_per_shard=64)
    tbl = rt.table(CounterActor)
    n, K = 100, 5
    tbl.ensure_dense(n)
    keys = np.arange(n)
    adds = np.ones((K, n), np.int32)
    out = rt.call_batch_rounds(CounterActor, "add", keys, {"n": adds})
    assert out.shape == (K, n)
    # each round increments: results are 1, 2, ..., K per actor
    for k in range(K):
        assert (out[k] == k + 1).all()


async def test_scanned_rounds_single_shard():
    rt = VectorRuntime(capacity_per_shard=64)
    tbl = rt.table(CounterActor)
    tbl.ensure_dense(8)
    adds = np.full((3, 8), 2, np.int32)
    out = rt.call_batch_rounds(CounterActor, "add", np.arange(8), {"n": adds})
    assert (out[-1] == 6).all()


async def test_duplicate_keys_rejected_in_bulk():
    rt = VectorRuntime(capacity_per_shard=64)
    rt.table(CounterActor).ensure_dense(8)
    with pytest.raises(ValueError, match="unique"):
        rt.call_batch(CounterActor, "add", np.array([1, 1, 2]),
                      {"n": np.zeros(3, np.int32)})


async def test_wrong_arg_name_is_clear_error():
    rt = VectorRuntime()
    with pytest.raises(TypeError, match="args mismatch"):
        await rt.actor(CounterActor, 0).add(wrong=np.int32(1))


async def test_scanned_rounds_fresh_init_nonzero_initial_state():
    """First-ever scanned call must apply initial_state (pre-pass), and
    must NOT re-apply it on later rounds."""
    class SeededActor(VectorGrain):
        STATE = {"v": (jnp.int32, ())}
        @staticmethod
        def initial_state(kh):
            return {"v": kh * 10}
        @actor_method(args={"n": (jnp.int32, ())})
        def add(state, args):
            v = state["v"] + args["n"]
            return {"v": v}, v

    rt = VectorRuntime(capacity_per_shard=16)
    rt.table(SeededActor).ensure_dense(4)
    adds = np.ones((3, 4), np.int32)
    out = rt.call_batch_rounds(SeededActor, "add", np.arange(4), {"n": adds})
    # key k starts at 10k, then +1 per round
    for k in range(4):
        assert out[0][k] == 10 * k + 1
        assert out[2][k] == 10 * k + 3


async def test_call_auto_fresh_on_dense_key():
    """Per-key call on a dense-provisioned key must run initial_state."""
    class Seeded2(VectorGrain):
        STATE = {"v": (jnp.int32, ())}
        @staticmethod
        def initial_state(kh):
            return {"v": jnp.int32(100)}
        @actor_method(args={})
        def get(state, args):
            return state, state["v"]

    rt = VectorRuntime(capacity_per_shard=16)
    rt.table(Seeded2).ensure_dense(4)
    assert await rt.actor(Seeded2, 2).get() == 100


def test_pipeline_depth_guard_on_multi_shard_mesh():
    """Overlapping collective programs deadlock the CPU backend's shared
    rendezvous pool: the runtime must refuse depth>1 on a multi-shard
    mesh instead of hanging (bench.py documents the failure; this guard
    makes it a loud error, not a convention)."""
    import pytest

    multi = VectorRuntime(mesh=make_mesh(8))
    assert multi.validate_pipeline_depth(1) == 1
    with pytest.raises(ValueError, match="rendezvous"):
        multi.validate_pipeline_depth(2)
    # allow_unproven only unlocks non-CPU backends; CPU always refuses
    with pytest.raises(ValueError, match="rendezvous"):
        multi.validate_pipeline_depth(2, allow_unproven=True)
    with pytest.raises(ValueError):
        multi.validate_pipeline_depth(0)
    # single-shard meshes run no collectives: any depth pipelines freely
    solo = VectorRuntime(mesh=make_mesh(1))
    assert solo.validate_pipeline_depth(4) == 4


async def test_bad_first_call_does_not_poison_inferred_schema():
    """A schema-less method infers its args schema from the first batch,
    committed only on success: a first call with a non-numeric arg must
    fail ONCE and leave the schema unset, so the next valid call
    re-infers and succeeds (the kernel build and device-put of the batch
    run inside the same guard as the kernel launch)."""
    import numpy as np
    import pytest

    class InferVec(VectorGrain):
        STATE = {"n": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"n": jnp.int32(0)}

        @actor_method
        def bump(state, args):
            new = {"n": state["n"] + args["x"]}
            return new, new["n"]

    rt = VectorRuntime(capacity_per_shard=16)
    rt.register(InferVec)
    with pytest.raises(TypeError):
        await rt.call(InferVec, 1, "bump", x="abc")  # '<U3' is not jax-able
    m = rt.table(InferVec).methods["bump"]
    assert m.args_schema is None, f"schema poisoned: {m.args_schema}"
    assert int(await rt.call(InferVec, 1, "bump", x=np.int32(5))) == 5
    assert m.args_schema["x"][0] == np.dtype(np.int32)


# ----------------------------------------------------------------------
# The packed tick: one staged buffer a job, unpacked by the kernel
# ----------------------------------------------------------------------
_YCSB_UPDATE = {"field": (jnp.int32, ()), "value": (jnp.uint8, (100,))}
# case → (schema, how a value of an array field is handed in)
_PACKED_CASES = {
    "presence_f16x2_i32": ({"pos": (jnp.float16, (2,)),
                            "delta": (jnp.int32, ())}, "ndarray"),
    "ycsb_update_bytes": (_YCSB_UPDATE, "bytes"),
    "ycsb_update_ndarray": (_YCSB_UPDATE, "ndarray"),
    "no_arguments_read": ({}, "ndarray"),
    "lone_bool": ({"b": (jnp.bool_, ())}, "ndarray"),
    "lone_f32": ({"x": (jnp.float32, ())}, "ndarray"),
    "odd_u8x3": ({"v": (jnp.uint8, (3,))}, "ndarray"),
    # an 8-byte host dtype is staged as the 4-byte twin the device sees
    "i64_narrows_to_i32": ({"q": (np.int64, ())}, "ndarray"),
    "c64_f32_pairs": ({"z": (jnp.complex64, (2,))}, "ndarray"),
}


def _packed_grain(schema: dict) -> type:
    """A grain whose ``put`` stores every argument in its row and answers
    with it, so the arguments' bits come back twice: in the reply and in
    the table. ``read`` is the read-only, argument-less tick."""
    held = {f"s_{f}": (jax.dtypes.canonicalize_dtype(dt), shape)
            for f, (dt, shape) in schema.items()}

    class PackedCase(VectorGrain):
        STATE = {"n": (jnp.int32, ()), "h": (jnp.int32, ()), **held}

        @staticmethod
        def initial_state(key_hash):
            row = {k: jnp.zeros(shape, dt) for k, (dt, shape) in held.items()}
            return {"n": jnp.int32(0), "h": key_hash * 3 + 1, **row}

        @actor_method(args=schema)
        def put(state, args):
            new = {"n": state["n"] + 1, "h": state["h"],
                   **{f"s_{f}": args[f] for f in schema}}
            return new, (new["n"], state["h"], dict(args))

        @actor_method(args={}, read_only=True)
        def read(state, args):
            return state, (state["n"], state["h"])

    return PackedCase


def _random_args(rng, schema: dict, form: str) -> dict:
    """One call's arguments with every bit random (NaNs, denormals and
    negative zeros among the floats): nothing may be rounded on its way."""
    out = {}
    for f, (dtype, shape) in schema.items():
        dt = np.dtype(dtype)
        if dt == np.bool_:
            out[f] = np.bool_(rng.integers(2))
            continue
        if dt == np.int64:
            # with x64 off the device holds an int32: the value has to
            # fit it, as numpy's assignment into the staged view insists
            out[f] = np.int64(rng.integers(-2**31, 2**31))
            continue
        raw = rng.integers(0, 256, int(np.prod(shape, dtype=int)) *
                           dt.itemsize, dtype=np.uint8).tobytes()
        if shape and form == "bytes":
            out[f] = raw
        else:
            v = np.frombuffer(raw, dt).reshape(shape)
            out[f] = v.copy() if shape else v[()]
    return out


def _bits(tree) -> list:
    return [(np.asarray(a).dtype, np.asarray(a).shape,
             np.asarray(a).tobytes())
            for a in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("shards", [1, 8])
@pytest.mark.parametrize("B", [8, 1024])
@pytest.mark.parametrize("case", list(_PACKED_CASES))
async def test_packed_job_is_bit_identical_to_six_operands(case, B, shards):
    """A served job (one packed buffer, one transfer, the packed kernel)
    answers and leaves the table exactly as the six-operand kernel does
    when it is fed the same staging views."""
    schema, form = _PACKED_CASES[case]
    G = _packed_grain(schema)
    method = "put" if schema else "read"
    rng = np.random.default_rng([list(_PACKED_CASES).index(case), B, shards])
    per = 2048
    rt = VectorRuntime(mesh=make_mesh(shards), capacity_per_shard=per)
    tbl = rt.table(G)
    tbl.ensure_dense(per * shards)
    acquired = []
    acquire = rt._staging_acquire
    rt._staging_acquire = lambda *a: acquired.append(acquire(*a)) \
        or acquired[-1]
    # the last shard carries the bucket, the others three lanes each
    wide = 5 if B == 8 else B // 2 + 1
    keys = [s * per + 3 * i for s in range(shards)
            for i in range(wide if s == shards - 1 else 3)]
    rng.shuffle(keys)
    # a first job writes every third key, so the compared job carries
    # fresh and initialised lanes side by side
    await asyncio.gather(*rt.call_group(
        G, "put", [(k, _random_args(rng, schema, form), True)
                   for k in keys[::3]]))
    with rt.tick_fence():
        before = jax.tree_util.tree_map(jnp.copy, tbl.state)
    calls = [(k, _random_args(rng, schema, form), True) for k in keys]
    replies = await asyncio.gather(*rt.call_group(G, method, calls))
    stg = acquired[-1]
    assert stg.layout.B == B and sum(stg.used) == len(keys)
    assert all(np.shares_memory(v, stg.packed) for v in
               (stg.slots, stg.khash, stg.fresh, stg.valid,
                *stg.args.values()))
    assert stg.fresh.any() and not stg.fresh[stg.valid].all()
    ref_state, ref = rt._build_kernel(G, method)(
        before, jnp.asarray(stg.slots), jnp.asarray(stg.khash),
        jnp.asarray(stg.fresh), jnp.asarray(stg.valid),
        {f: jnp.asarray(v) for f, v in stg.args.items()})
    with rt.tick_fence():
        assert _bits(tbl.state) == _bits(ref_state or before)
    ref = jax.tree_util.tree_map(np.asarray, ref)
    lane = [0] * shards
    for (k, _a, _w), reply in zip(calls, replies):
        s = k // per
        assert _bits(reply) == _bits(jax.tree_util.tree_map(
            lambda a: a[s, lane[s]], ref)), (k, s, lane[s])
        lane[s] += 1
    rt.shutdown_worker()


async def test_packed_job_counts_one_transfer():
    """Metrics on: a job books itself, its one host→device transfer and
    the staged buffer's bytes. Metrics off: its sink stays empty."""
    from orleans_tpu.observability.stats import StatsRegistry

    jobs = []

    def served(rt):
        done = rt._complete_job
        rt._complete_job = lambda job, *a: (jobs.append(job), done(job, *a))
        return rt

    rt = served(VectorRuntime(mesh=make_mesh(1)))
    rt.stats = reg = StatsRegistry()
    for wave in (range(5), range(40), range(3)):
        await asyncio.gather(*(rt.call(CounterActor, k, "add", n=np.int32(1))
                               for k in wave))
    assert await rt.call(CounterActor, 1, "get") == 3
    assert len(jobs) == 4
    assert reg.get("ingest.transfer.jobs") == 4
    assert reg.get("ingest.transfer.puts") == 4
    sets = [st for pool in rt._staging.values()
            for sets, _i in pool.values() for st in sets]
    by_bucket = {(bool(st.args), st.layout.B): st.packed.nbytes
                 for st in sets}
    # add at buckets 8, 64, 8 (10 bytes of header + 4 a lane), get at 8
    assert by_bucket == {(True, 8): 112, (True, 64): 896, (False, 8): 80}
    assert reg.get("ingest.transfer.bytes") == 112 + 896 + 112 + 80
    rt.shutdown_worker()

    jobs.clear()
    quiet = served(VectorRuntime(mesh=make_mesh(1)))
    await asyncio.gather(*(quiet.call(CounterActor, k, "add", n=np.int32(1))
                           for k in range(5)))
    assert len(jobs) == 1 and jobs[0].stats == []
    quiet.shutdown_worker()
