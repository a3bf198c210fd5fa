"""A ``Silo`` whose vector table is sharded over a mesh, serving a client:
``GatewayClient`` over loopback TCP → gateway → dispatcher → the tick on
1 and on 4 shards → egress → reply, with write-behind to storage. Every
reply, every row of every shard and every stored row is held to a plain
dict of running totals kept here; a key is served by one shard for the
whole run; the ``mesh.job.*`` counters add up. The small twin of the
benchmark's ``presence_4chip`` cell (chipbench/workloads)."""

import asyncio

import numpy as np
import pytest

import jax.numpy as jnp

from orleans_tpu.core.ids import GrainId, GrainType
from orleans_tpu.dispatch import VectorGrain, actor_method, add_vector_grains
from orleans_tpu.membership import FileMembershipTable, join_cluster
from orleans_tpu.observability.stats import MESH_STATS
from orleans_tpu.parallel import make_mesh
from orleans_tpu.runtime import GatewayClient, SiloBuilder, SocketFabric
from orleans_tpu.storage import MemoryStorage

N_GAMES = 64
DENSE, HASHED, CAPACITY = 512, 256, 1024     # over all shards / per shard
CALLERS, FRAMES, FRAME = 8, 6, 32            # 16 dense + 16 string keys
LIMIT_S = 120.0                              # this test's own time limit


class MeshPlayer(VectorGrain):
    """The Presence player's row (samples/presence_tpu.py)."""
    STATE = {"pos": (jnp.float32, (2,)), "score": (jnp.int32, ()),
             "game": (jnp.int32, ())}

    @staticmethod
    def initial_state(key_hash):
        return {"pos": jnp.zeros(2, jnp.float32), "score": jnp.int32(0),
                "game": key_hash % N_GAMES}

    @actor_method(args={"pos": (jnp.float16, (2,)), "delta": (jnp.int32, ())})
    def heartbeat(state, args):
        new = {"pos": args["pos"].astype(jnp.float32),
               "score": state["score"] + args["delta"],
               "game": state["game"]}
        return new, new["score"]


def _record_jobs(rt) -> list:
    """Every finished job, as the engine ran it (who sat on which shard,
    what its sink carried), appended as it completes."""
    jobs, done = [], rt._complete_job
    rt._complete_job = lambda job, *a: (jobs.append(job), done(job, *a))[1]
    return jobs


def _frames(rng, caller: int) -> list:
    """One caller's frames: keys of its own partition, without
    replacement inside a frame, so a key repeats from frame to frame."""
    lo = caller * DENSE // CALLERS
    dense = list(range(lo, lo + DENSE // CALLERS))
    named = [f"player-{int(rng.integers(1 << 62)):016x}-{caller}"
             for _ in range(HASHED // CALLERS)]
    out = []
    for _ in range(FRAMES):
        keys = [int(k) for k in rng.choice(dense, FRAME // 2, replace=False)]
        keys += [str(k) for k in rng.choice(named, FRAME // 2, replace=False)]
        pos = (rng.integers(0, 1024, size=(FRAME, 2)) / 64.0).tolist()
        delta = rng.integers(257, 5000, size=FRAME).tolist()
        out.append([(k, {"pos": p, "delta": d})
                    for k, p, d in zip(keys, pos, delta)])
    return out


async def _serve(n_shards: int, tmp_path) -> None:
    storage = MemoryStorage()
    b = (SiloBuilder().with_name(f"mesh{n_shards}")
         .with_fabric(SocketFabric())
         .with_config(metrics_enabled=True, response_timeout=30.0))
    add_vector_grains(b, MeshPlayer, mesh=make_mesh(n_shards),
                      dense={MeshPlayer: DENSE}, capacity_per_shard=CAPACITY,
                      storage=storage, flush_period=0.05)
    silo = b.build()
    join_cluster(silo, FileMembershipTable(str(tmp_path / "mbr.json")))
    await silo.start()
    rt, client = silo.vector, None
    tbl = rt.table(MeshPlayer)
    assert tbl.n_shards == n_shards
    assert all(len(leaf.devices()) == n_shards for leaf in tbl.state.values())

    jobs = _record_jobs(rt)
    gtype = GrainType.of(MeshPlayer.__name__)

    def key_hash(key) -> int:
        return rt.key_hash_for(
            key, GrainId.for_grain(gtype, key).uniform_hash)

    rng = np.random.default_rng([32, n_shards])
    scripts = [_frames(rng, g) for g in range(CALLERS)]
    totals: dict = {}                         # key -> [pos0, pos1, score]
    try:
        client = await GatewayClient([silo.gateway_endpoint],
                                     response_timeout=30.0).connect()

        async def caller(frames: list) -> None:
            for calls in frames:
                got = await asyncio.gather(
                    *client.call_batch(MeshPlayer, "heartbeat", calls))
                for (key, kw), reply in zip(calls, got):
                    row = totals.setdefault(key, [0.0, 0.0, 0])
                    p = np.asarray(kw["pos"], np.float16).astype(np.float32)
                    row[0], row[1] = float(p[0]), float(p[1])
                    row[2] += kw["delta"]
                    assert int(reply) == row[2], (key, int(reply), row[2])

        await asyncio.gather(*(caller(s) for s in scripts))
        await rt.flush()

        # ---- every row of every shard, in one snapshot under the fence
        snap = tbl.snapshot()
        assert snap["score"].shape[0] == n_shards
        per = tbl.dense_per_shard
        where = {}
        for key in totals:
            kh = key_hash(key)
            where[key] = (kh // per, kh % per) if isinstance(key, int) \
                else tbl.lookup(kh)
            assert where[key] is not None, key
            if not isinstance(key, int):
                assert where[key][0] == kh % n_shards
        assert len(set(where.values())) == len(totals)
        for key, (pos0, pos1, score) in totals.items():
            s, slot = where[key]
            assert snap["score"][s, slot] == score, key
            assert snap["pos"][s, slot].tolist() == [pos0, pos1], key
            assert snap["game"][s, slot] == \
                (key_hash(key) & 0x7FFFFFFF) % N_GAMES, key
        # ... and nothing else was written anywhere: a key served by a
        # second shard would leave part of its score in a second row
        live = snap["score"][:, :tbl.capacity]
        assert np.count_nonzero(live) == len(totals)
        assert int(live.sum()) == sum(r[2] for r in totals.values())
        for key in list(totals)[:8]:
            row = tbl.read_row(key_hash(key))
            assert int(row["score"]) == totals[key][2]

        # ---- a key rode one shard in every job that carried it
        seen: dict = {}
        for job in jobs:
            for s, ps in enumerate(job.per_shard):
                for p in ps:
                    assert seen.setdefault(p.key_hash, s) == s == p.shard
        assert seen == {key_hash(k): w[0] for k, w in where.items()}

        # ---- the counters: per job and summed
        lanes = mx = slots = 0
        for job in jobs:
            st = {k: v for k, v in job.stats if k in MESH_STATS.values()}
            fullest = max(len(ps) for ps in job.per_shard)
            assert st[MESH_STATS["lanes"]] == len(job.ready)
            assert st[MESH_STATS["max_shard_lanes"]] == fullest
            assert st[MESH_STATS["slots"]] % n_shards == 0
            assert fullest <= st[MESH_STATS["slots"]] // n_shards
            lanes += len(job.ready)
            mx += fullest
            slots += st[MESH_STATS["slots"]]
        reg = silo.stats
        assert reg.get(MESH_STATS["lanes"]) == lanes == \
            reg.get("ingest.messages") == CALLERS * FRAMES * FRAME
        assert reg.get(MESH_STATS["max_shard_lanes"]) == mx
        assert reg.get(MESH_STATS["slots"]) == slots
        assert lanes <= n_shards * mx <= slots
        if n_shards == 1:
            assert mx == lanes
        else:
            assert lanes < n_shards * mx   # frames of 16 dense keys skew

        # ---- every acknowledged heartbeat readable from storage
        async def unreadable() -> int:
            bad = 0
            for key, (pos0, pos1, score) in totals.items():
                state, _etag = await storage.read(
                    MeshPlayer.__name__,
                    GrainId.for_grain(gtype, key_hash(key)))
                bad += (state is None or int(state["score"]) != score
                        or np.asarray(state["pos"]).tolist() != [pos0, pos1])
            return bad

        for _ in range(100):
            if not await unreadable():
                break
            await asyncio.sleep(0.05)
        assert await unreadable() == 0
        assert tbl.capacity == CAPACITY
    finally:
        if client is not None:
            await client.close_async()
        await silo.stop()


@pytest.mark.parametrize("n_shards", [1, 4])
async def test_silo_on_a_mesh_serves_a_client(n_shards, tmp_path):
    await asyncio.wait_for(_serve(n_shards, tmp_path), LIMIT_S)


@pytest.mark.parametrize("n_shards", [1, 4])
async def test_mesh_counters_cost_nothing_with_metrics_off(n_shards):
    """Metrics off: a job's sink stays empty, on one shard and on four."""
    from orleans_tpu.dispatch import VectorRuntime

    rt = VectorRuntime(mesh=make_mesh(n_shards), capacity_per_shard=64)
    jobs = _record_jobs(rt)
    rt.table(MeshPlayer).ensure_dense(128)
    got = await asyncio.wait_for(asyncio.gather(*(
        rt.call(MeshPlayer, k, "heartbeat", pos=[0.5, 1.0], delta=k + 1)
        for k in range(0, 128, 3))), LIMIT_S)
    assert [int(g) for g in got] == [k + 1 for k in range(0, 128, 3)]
    assert jobs and all(job.stats == [] for job in jobs)
    rt.shutdown_worker()
