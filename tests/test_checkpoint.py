"""Device-tier checkpoint/resume: orbax table snapshots (whole-silo
resume) + write-behind per-actor persistence (lazy per-actor resume) —
SURVEY.md §5 "Checkpoint / resume" TPU mapping."""

import numpy as np
import pytest

import jax.numpy as jnp

from orleans_tpu.dispatch import VectorGrain, VectorRuntime, actor_method
from orleans_tpu.parallel import make_mesh
from orleans_tpu.storage import (
    MemoryStorage,
    VectorCheckpointer,
    VectorStorageBridge,
)


class CounterGrain(VectorGrain):
    STATE = {"count": (jnp.int32, ()), "last": (jnp.float32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"count": jnp.int32(0), "last": jnp.float32(0)}

    @actor_method(args={"x": (jnp.float32, ())})
    def bump(state, args):
        return {"count": state["count"] + 1, "last": args["x"]}, \
            state["count"] + 1


def _runtime(n_players=64) -> VectorRuntime:
    rt = VectorRuntime(mesh=make_mesh(8), capacity_per_shard=32)
    rt.table(CounterGrain).ensure_dense(n_players)
    return rt


def _bump_all(rt, n, x):
    keys = np.arange(n)
    return rt.call_batch(CounterGrain, "bump", keys,
                         {"x": np.full(n, x, np.float32)})


class TestVectorCheckpointer:
    def test_save_restore_roundtrip(self, tmp_path):
        rt = _runtime()
        _bump_all(rt, 64, 1.5)
        _bump_all(rt, 64, 2.5)
        ckpt = VectorCheckpointer(rt, str(tmp_path), max_to_keep=2)
        ckpt.save(step=2)
        ckpt.wait()

        # "restart": brand-new runtime, same registrations
        rt2 = _runtime()
        ckpt2 = VectorCheckpointer(rt2, str(tmp_path))
        assert ckpt2.restore() == 2
        row = rt2.table(CounterGrain).read_row(17)
        assert int(row["count"]) == 2 and float(row["last"]) == 2.5
        # resumed table keeps serving — counts continue from the snapshot
        out = _bump_all(rt2, 64, 9.0)
        assert (np.asarray(out) == 3).all()
        ckpt.close()
        ckpt2.close()

    def test_retention_and_latest(self, tmp_path):
        rt = _runtime(8)
        ckpt = VectorCheckpointer(rt, str(tmp_path), max_to_keep=2)
        for s in (1, 2, 3):
            _bump_all(rt, 8, float(s))
            ckpt.save(s)
        ckpt.wait()
        assert ckpt.latest_step() == 3
        assert set(ckpt.manager.all_steps()) == {2, 3}
        ckpt.close()

    def test_restore_requires_registration(self, tmp_path):
        rt = _runtime(8)
        _bump_all(rt, 8, 1.0)
        ckpt = VectorCheckpointer(rt, str(tmp_path))
        ckpt.save(1)
        ckpt.wait()
        empty = VectorRuntime(mesh=make_mesh(8), capacity_per_shard=32)
        with pytest.raises(KeyError, match="not registered"):
            VectorCheckpointer(empty, str(tmp_path)).restore()
        ckpt.close()

    def test_restore_into_different_capacity_runtime(self, tmp_path):
        rt = _runtime()          # capacity_per_shard=32
        _bump_all(rt, 64, 7.0)
        ckpt = VectorCheckpointer(rt, str(tmp_path))
        ckpt.save(1)
        ckpt.wait()
        rt2 = VectorRuntime(mesh=make_mesh(8), capacity_per_shard=128)
        rt2.table(CounterGrain).ensure_dense(64)
        VectorCheckpointer(rt2, str(tmp_path)).restore()
        tbl = rt2.table(CounterGrain)
        assert tbl.capacity == 32  # checkpoint's capacity wins
        assert int(tbl.read_row(63)["count"]) == 1
        ckpt.close()

    def test_hashed_keys_roundtrip(self, tmp_path):
        rt = VectorRuntime(mesh=make_mesh(8), capacity_per_shard=16)
        rt.register(CounterGrain)
        tbl = rt.table(CounterGrain)
        big = 10**9 + 7  # hashed regime (beyond any dense range)
        shard, slot, fresh = tbl.lookup_or_allocate(big)
        assert fresh
        ckpt = VectorCheckpointer(rt, str(tmp_path))
        ckpt.save(1)
        ckpt.wait()
        rt2 = VectorRuntime(mesh=make_mesh(8), capacity_per_shard=16)
        rt2.register(CounterGrain)
        VectorCheckpointer(rt2, str(tmp_path)).restore()
        assert rt2.table(CounterGrain).lookup(big) == (shard, slot)
        ckpt.close()


class TestVectorStorageBridge:
    async def test_flush_then_load_after_restart(self):
        storage = MemoryStorage()
        rt = _runtime(16)
        _bump_all(rt, 16, 4.25)
        bridge = VectorStorageBridge(rt, CounterGrain, storage)
        assert await bridge.flush(range(16)) == 16

        # restart: new runtime; rows come back from storage, not checkpoint
        rt2 = _runtime(16)
        bridge2 = VectorStorageBridge(rt2, CounterGrain, storage)
        loaded = await bridge2.load(range(16))
        assert loaded == list(range(16))
        row = rt2.table(CounterGrain).read_row(5)
        assert int(row["count"]) == 1 and float(row["last"]) == 4.25
        # loaded actors are active (no fresh re-init on next call)
        out = _bump_all(rt2, 16, 0.0)
        assert (np.asarray(out) == 2).all()

    async def test_flush_after_checkpoint_restore_adopts_etags(self, tmp_path):
        """The two recovery paths compose: write-behind flush, whole-silo
        checkpoint restore, then flush again from the fresh bridge — the
        bridge adopts stored etags instead of failing CAS."""
        storage = MemoryStorage()
        rt = _runtime(8)
        _bump_all(rt, 8, 1.0)
        await VectorStorageBridge(rt, CounterGrain, storage).flush(range(8))
        ckpt = VectorCheckpointer(rt, str(tmp_path))
        ckpt.save(1)
        ckpt.wait()

        rt2 = _runtime(8)
        VectorCheckpointer(rt2, str(tmp_path)).restore()
        _bump_all(rt2, 8, 2.0)  # newer device state than storage
        bridge2 = VectorStorageBridge(rt2, CounterGrain, storage)
        assert await bridge2.flush(range(8)) == 8  # no InconsistentState
        state, _ = await storage.read(
            "CounterGrain", bridge2._grain_id(3))
        assert int(state["count"]) == 2 and float(state["last"]) == 2.0
        ckpt.close()

    async def test_load_missing_keys_stay_fresh(self):
        storage = MemoryStorage()
        rt = _runtime(8)
        bridge = VectorStorageBridge(rt, CounterGrain, storage)
        assert await bridge.load([3, 4]) == []

    async def test_flush_unknown_key_dropped(self):
        # a key with no activation slot has no row to persist: it is
        # dropped (logged), not raised — one bad key must not wedge
        # write-behind for the whole class
        rt = _runtime(8)
        bridge = VectorStorageBridge(rt, CounterGrain, MemoryStorage())
        assert await bridge.flush([999]) == 0

    async def test_flush_isolates_per_key_storage_failures(self):
        # a storage failure on one key re-marks only that key dirty;
        # the rest of the batch still persists
        rt = _runtime(8)
        rt.enable_dirty_tracking()

        # a subclass that brings its own write gets the per-key default
        # write_many back (MemoryStorage's batched pass never calls write)
        class FlakyStorage(MemoryStorage):
            async def write(self, grain_type, grain_id, state, etag):
                if grain_id.key == 2:
                    raise RuntimeError("injected storage fault")
                return await super().write(grain_type, grain_id, state, etag)

        storage = FlakyStorage()
        bridge = VectorStorageBridge(rt, CounterGrain, storage)
        assert not bridge.batched
        tbl = rt.table(CounterGrain)
        for k in (1, 2, 3):
            tbl.lookup_or_allocate(k)
        rt.drain_dirty(CounterGrain)  # clear allocation dirt
        assert await bridge.flush([1, 2, 3]) == 2
        # only the failed key was re-marked for the next period
        assert sorted(int(k) for k in rt.drain_dirty(CounterGrain)) == [2]
        s1, _ = await storage.read("CounterGrain", bridge._grain_id(1))
        s2, _ = await storage.read("CounterGrain", bridge._grain_id(2))
        assert s1 is not None and s2 is None
