#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served Presence path starts
on the chip.

Run with no arguments on a machine with one TPU chip. It drives the
system's main path once through the entry points a user would call, at
the north-star population (BASELINE.json: Samples/Presence, 1M concurrent
PlayerGrains — the ``PlayerVectorGrain`` of ``samples/presence_tpu.py``),
and compares everything that comes out with a plain reference kept in
this file (numpy rows stepped one round at a time, independent of
``orleans_tpu``):

* **environment** — versions, devices, the wire codec, the compile cache;
* **kernels** — ``segment_sum`` (Pallas on a TPU), ``segment_sum_onehot``
  and ``pack_by_dest`` on both sides of its 32768-lane switch, compiled,
  compared exactly with numpy on integers far above 256;
* **engine** — a ``VectorRuntime`` with 1M dense players: fresh
  activation, ``call_batch`` ticks, ``call_batch_rounds`` (K=8), then
  hashed 62-bit keys through ``rt.actor(...)`` with same-key conflicts,
  and the on-device directory through a sparse ``route``;
* **served** — a ``Silo`` on a ``SocketFabric`` with the 1M-player table,
  a ``GatewayClient`` over loopback TCP (32 concurrent callers, a client
  ``call_batch``, a read-only call, a host-grain call), write-behind
  read-back from storage, clean stop.

One JSON line per phase; the LAST line of stdout is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed comparison or exception exits non-zero and never prints it.
Without a TPU the script fails (``--rehearse-cpu`` is the explicit,
tiny-size CPU rehearsal; the last line then names the CPU).

``--chips 4`` runs ONLY the sharded path and its one-device twin: the
1M-player table over a four-device mesh, K heartbeat rounds under
``shard_map``, every player→game message through ``VectorRuntime.route``
into a sharded ``GameGrain`` fan-in, sparse hashed keys over the exchange
with the dedup/defer loop, and ``reshard_dense`` 4→3→4 — compared row for
row with the same traffic on a one-device mesh in the same process. Then
the **served** phase again, its silo's table sharded over the four
devices (``make_mesh()`` takes every local device): the same callers,
client ``call_batch``, ``whereis``, host-grain call, storage read-back
and clean stop, against the same reference.

``--worker-procs N`` runs ONLY the served phase with
``SiloConfig(worker_procs=N)`` under a hard time limit (the question of
what forked workers do to a process that already holds the chip).

One process uses the chip; the script starts no child that needs it.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))


def _parse_args() -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the sharded path and its 1-device twin")
    p.add_argument("--players", type=int, default=1_000_000,
                   help="dense PlayerGrain population (default 1M)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="explicit CPU rehearsal (tiny kernel shapes, Pallas "
                        "in interpret mode); never passed by the driver")
    p.add_argument("--worker-procs", type=int, default=0,
                   help="run only the served phase with worker_procs=N "
                        "under --limit seconds")
    p.add_argument("--limit", type=float, default=240.0,
                   help="hard time limit of the --worker-procs run")
    p.add_argument("--inject-fault", default=None,
                   choices=("kernels", "engine", "served", "mesh"),
                   help="corrupt that phase's reference (proves a failed "
                        "comparison fails the run)")
    return p.parse_args()


ARGS: argparse.Namespace  # set by main(), before jax is first imported

sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "samples"))

import numpy as np  # noqa: E402

N_GAMES = 64  # samples/presence_tpu.py


# ---------------------------------------------------------------------------
# output + failure
# ---------------------------------------------------------------------------

def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


class SmokeFailure(AssertionError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def check_equal(got, want, what: str) -> int:
    """Exact comparison; returns the number of values compared."""
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape,
          f"{what}: shape {got.shape} != {want.shape}")
    bad = got != want
    if bad.any():
        idx = tuple(int(i) for i in np.argwhere(bad)[0])
        raise SmokeFailure(
            f"{what}: {int(bad.sum())}/{bad.size} values differ; first at "
            f"{idx}: got {got[idx]!r}, want {want[idx]!r}")
    return int(want.size)


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits, from jax's own
    monitoring events (a cache hit still passes through the compile event,
    so cold and cached runs report the same quantity)."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def run_phase(name: str, meter: CompileMeter, fn, *args) -> bool:
    """Run one phase and print its record. A failed phase is reported and
    the next one still runs (one chip call then shows every fault); the
    run as a whole fails if any phase did."""
    t0, c0 = time.perf_counter(), meter.seconds
    try:
        compared = fn(*args)
    except Exception as e:  # noqa: BLE001 — the boundary that reports
        import traceback
        traceback.print_exc()
        emit({"phase": name, "ok": False,
              "seconds": round(time.perf_counter() - t0, 3),
              "error": f"{type(e).__name__}: {e}"})
        return False
    emit({"phase": name, "ok": True,
          "seconds": round(time.perf_counter() - t0, 3),
          "compile_seconds": round(meter.seconds - c0, 3),
          "compared": compared})
    return True


def cache_entries(path: str | None) -> int:
    """Compiled programs in the cache directory (none kept: 0)."""
    try:
        return sum(1 for f in os.listdir(path)
                   if not f.endswith("-atime")) if path else 0
    except OSError:
        return 0


# ---------------------------------------------------------------------------
# the plain reference: Presence players as numpy rows
# ---------------------------------------------------------------------------

class PresenceReference:
    """``samples/presence_tpu.py``'s PlayerVectorGrain, restated: a player
    activates on first touch (pos 0, score 0, game = key mod 64), a
    heartbeat stores the f16 position as f32 and adds ``delta`` to the
    score, and answers the new score. Dense keys are array rows; hashed
    keys live in a dict. One message per player per round."""

    def __init__(self, n_dense: int):
        self.n = n_dense
        self.pos = np.zeros((n_dense, 2), np.float32)
        self.score = np.zeros(n_dense, np.int64)
        self.game = np.zeros(n_dense, np.int64)
        self.active = np.zeros(n_dense, bool)
        self.sparse: dict[int, list] = {}  # key_hash -> [pos, score, game]

    def round(self, keys, pos16, delta) -> np.ndarray:
        """One heartbeat round over unique dense ``keys``."""
        keys = np.asarray(keys)
        fresh = keys[~self.active[keys]]
        self.pos[fresh] = 0.0
        self.score[fresh] = 0
        self.game[fresh] = (fresh & 0x7FFFFFFF) % N_GAMES
        self.active[keys] = True
        self.pos[keys] = np.asarray(pos16, np.float16).astype(np.float32)
        self.score[keys] += np.asarray(delta, np.int64)
        return self.score[keys].copy()

    def one(self, key_hash: int, pos16, delta: int) -> int:
        """One heartbeat to one player (dense or hashed key)."""
        if 0 <= key_hash < self.n:
            return int(self.round(np.array([key_hash]),
                                  np.asarray(pos16, np.float16)[None],
                                  np.array([delta]))[0])
        row = self.sparse.get(key_hash)
        if row is None:
            row = self.sparse[key_hash] = [
                np.zeros(2, np.float32), 0,
                (key_hash & 0x7FFFFFFF) % N_GAMES]
        row[0] = np.asarray(pos16, np.float16).astype(np.float32)
        row[1] += int(delta)
        return row[1]

    def row(self, key_hash: int):
        if 0 <= key_hash < self.n:
            return self.pos[key_hash], int(self.score[key_hash]), \
                int(self.game[key_hash])
        pos, score, game = self.sparse[key_hash]
        return pos, score, game

    def game_summary(self, game: int) -> tuple[int, int]:
        """(live dense players in the game, their total score)."""
        m = self.active & (self.game == game)
        return int(m.sum()), int(self.score[m].sum())


def rand_pos16(rng, *shape) -> np.ndarray:
    """Positions exactly representable in float16 (k/64 in [0, 16))."""
    return (rng.integers(0, 1024, size=(*shape, 2)) / 64.0).astype(np.float16)


def dense_rows(tbl) -> dict[str, np.ndarray]:
    """Every dense row of a table in key order (public snapshot +
    the documented block mapping key -> (key // per, key % per))."""
    snap = tbl.snapshot()
    per = tbl.dense_per_shard
    return {name: a[:, :per].reshape(a.shape[0] * per, *a.shape[2:])
            [:tbl.dense_n] for name, a in snap.items()}


def compare_dense(tbl, ref: PresenceReference, what: str) -> int:
    rows = dense_rows(tbl)
    n = check_equal(rows["score"], ref.score, f"{what}: score of every row")
    n += check_equal(rows["pos"], ref.pos, f"{what}: pos of every row")
    live = ref.active
    n += check_equal(rows["game"][live], ref.game[live],
                     f"{what}: game of every live row")
    return n


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

def np_segment_sum(values: np.ndarray, seg: np.ndarray, S: int) -> np.ndarray:
    """Exact integer segment sum (every ``seg`` in [0, S))."""
    if values.ndim == 1:  # float64 weights are exact far past these totals
        return np.bincount(seg, weights=values, minlength=S).astype(np.int64)
    order = np.argsort(seg, kind="stable")
    ss = seg[order]
    starts = np.flatnonzero(np.r_[True, ss[1:] != ss[:-1]])
    out = np.zeros((S, values.shape[1]), np.int64)
    out[ss[starts]] = np.add.reduceat(values[order], starts, axis=0,
                                      dtype=np.int64)
    return out


def np_pack_by_dest(dest, valid, payload, n_dest, capacity):
    """First ``capacity`` valid messages per destination, in lane order."""
    out = np.zeros((n_dest, capacity), payload.dtype)
    out_valid = np.zeros((n_dest, capacity), bool)
    in_range = (dest >= 0) & (dest < n_dest)
    drops = int((valid & ~in_range).sum())
    for d in range(n_dest):
        lanes = np.flatnonzero(valid & (dest == d))
        keep = lanes[:capacity]
        out[d, :keep.size] = payload[keep]
        out_valid[d, :keep.size] = True
        drops += lanes.size - keep.size
    return out, out_valid, drops


def phase_kernels(on_tpu: bool, rng) -> dict:
    import jax
    import jax.numpy as jnp

    from orleans_tpu.ops import (pack_by_dest, segment_sum,
                                 segment_sum_onehot, segment_sum_pallas)

    small = not on_tpu
    interp = {} if on_tpu else {"interpret": True}  # rehearsal only
    compared: dict = {}
    errors: list[str] = []
    custom_calls = 0

    def kernel_case(label, fn, args, compare, expect_pallas) -> None:
        """Compile ``fn`` for this backend (never interpreted on a TPU:
        the compiled text must hold a tpu_custom_call exactly where a
        Pallas kernel is expected), run it, compare. A failing case is
        recorded and the next one still runs."""
        nonlocal custom_calls
        try:
            c = jax.jit(fn).lower(*args).compile()
            has = "tpu_custom_call" in c.as_text()
            if on_tpu:
                check(has == expect_pallas,
                      f"tpu_custom_call in compiled text is {has}, "
                      f"expected {expect_pallas}")
                custom_calls += has
            compared[label] = compare(jax.block_until_ready(c(*args)))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(f"{label}: {type(e).__name__}: {e}")

    def exact(want, what):
        return lambda got: check_equal(
            np.asarray(got).astype(np.int64), want, what)

    # -- segment_sum, the shape that selects the Pallas kernel on a TPU:
    # one message per player into 1024 segments, 128-wide values
    B, S, D = (4096, 256, 128) if small else (1 << 20, 1024, 128)
    seg = rng.integers(0, S, size=B).astype(np.int32)
    vals = rng.integers(300, 12_000, size=(B, D)).astype(np.int32)
    want = np_segment_sum(vals, seg, S)
    check(300 < int(vals.max()) and int(want.max()) < 1 << 24,
          "segment_sum inputs outside the documented exact range")
    d_args = (jnp.asarray(vals, jnp.float32), jnp.asarray(seg))
    kernel_case(f"segment_sum[B={B},S={S},D={D}]",
                lambda v, s: segment_sum(v, s, S), d_args,
                exact(want, "segment_sum vs numpy"), True)
    kernel_case("segment_sum_pallas[2-D]",
                lambda v, s: segment_sum_pallas(v, s, S, **interp), d_args,
                exact(want, "segment_sum_pallas vs numpy"), True)
    del d_args

    # -- 1-D values with one segment's total at the documented bound:
    # segment 0 sums to exactly 2^24 - 1
    B1, S1 = (4096, 256) if small else (1 << 16, 1024)
    seg1 = rng.integers(1, S1, size=B1).astype(np.int32)
    vals1 = rng.integers(257, 60_000, size=B1).astype(np.int32)
    n0 = 300
    seg1[:n0] = 0
    vals1[:n0] = ((1 << 24) - 1) // n0
    vals1[0] += ((1 << 24) - 1) - int(vals1[:n0].sum())
    want1 = np_segment_sum(vals1, seg1, S1)
    check(int(want1[0]) == (1 << 24) - 1 and int(want1.max()) < 1 << 24,
          "1-D segment_sum inputs outside the documented exact range")
    if ARGS.inject_fault == "kernels":
        want1 = want1 + 1
    d_args1 = (jnp.asarray(vals1, jnp.float32), jnp.asarray(seg1))
    kernel_case(f"segment_sum_onehot[B={B1},S={S1}]",
                lambda v, s: segment_sum_onehot(v, s, S1), d_args1,
                exact(want1, "segment_sum_onehot vs numpy"), False)
    kernel_case("segment_sum_pallas[1-D]",
                lambda v, s: segment_sum_pallas(v, s, S1, **interp),
                d_args1, exact(want1, "segment_sum_pallas 1-D vs numpy"),
                True)

    # -- pack_by_dest on both sides of its 32768-lane switch (the MXU
    # prefix-count kernel up to it, the sort rank past it), plus the
    # 8-shard shape; capacity tight enough that some lanes drop
    shapes = [(1024, 4), (2048, 8), (1536, 4)] if small else \
        [(32768, 4), (4096, 8), (65536, 4)]
    for i, (Bp, n_dest) in enumerate(shapes):
        past_switch = (Bp > 32768) if on_tpu else (i == 2)
        dest = rng.integers(-1, n_dest + 1, size=Bp).astype(np.int32)
        valid = rng.random(Bp) < 0.9
        payload = rng.integers(0, 1 << 30, size=Bp).astype(np.int32)
        cap = int(Bp / n_dest * 0.8)
        wanted = np_pack_by_dest(dest, valid, payload, n_dest, cap)
        # defaults everywhere but the rehearsal's MXU-rank shapes, which
        # ask for the interpreter by name (the CPU default is the sort rank)
        kw = {} if (on_tpu or past_switch) else \
            {"use_pallas": True, **interp}

        def compare(got, _w=wanted, _B=Bp):
            (out, out_valid, drops), (w_out, w_valid, w_drops) = got, _w
            n = check_equal(out_valid, w_valid, f"pack_by_dest[B={_B}] valid")
            n += check_equal(np.where(w_valid, np.asarray(out["x"]), 0),
                             np.where(w_valid, w_out, 0),
                             f"pack_by_dest[B={_B}] payload")
            check(int(drops) == w_drops and w_drops > 0,
                  f"pack_by_dest[B={_B}] drops {int(drops)} != {w_drops}")
            return n

        kernel_case(
            f"pack_by_dest[B={Bp},n={n_dest}]",
            lambda d, v, p, _n=n_dest, _c=cap, _kw=kw: pack_by_dest(
                d, v, {"x": p}, _n, _c, **_kw),
            (jnp.asarray(dest), jnp.asarray(valid), jnp.asarray(payload)),
            compare, not past_switch)
    compared["tpu_custom_call_programs"] = custom_calls
    check(not errors, f"{len(errors)} kernel case(s) failed: "
          + " | ".join(errors))
    return compared


# ---------------------------------------------------------------------------
# phase: engine
# ---------------------------------------------------------------------------

def phase_engine(N: int, rng) -> dict:
    import jax.numpy as jnp

    from orleans_tpu.dispatch import VectorRuntime
    from orleans_tpu.ops.hash_probe import split64
    from orleans_tpu.parallel import make_mesh
    from presence_tpu import PlayerVectorGrain as Player

    ref = PresenceReference(N)
    rt = VectorRuntime(mesh=make_mesh(1), capacity_per_shard=N)
    tbl = rt.table(Player)
    tbl.ensure_dense(N)
    keys = np.arange(N)
    plan = rt.make_dense_plan(Player, keys)
    compared: dict = {}

    # first tick: every player activates fresh
    pos, delta = rand_pos16(rng, N), rng.integers(257, 1000, size=N)
    out = rt.call_batch(Player, "heartbeat", keys,
                        {"pos": pos, "delta": delta.astype(np.int32)},
                        fresh=np.ones(N, bool), plan=plan)
    n = check_equal(out, ref.round(keys, pos, delta), "fresh tick replies")
    # bulk ticks over the whole population (the contiguous plan) ...
    for _ in range(3):
        pos, delta = rand_pos16(rng, N), rng.integers(1, 1000, size=N)
        out = rt.call_batch(Player, "heartbeat", keys,
                            {"pos": pos, "delta": delta.astype(np.int32)},
                            plan=plan)
        n += check_equal(out, ref.round(keys, pos, delta),
                         "call_batch replies")
    # ... and over a scattered subset (the gather/scatter plan)
    sub = np.sort(rng.choice(N, size=max(8, N // 10), replace=False))
    pos, delta = rand_pos16(rng, sub.size), \
        rng.integers(1, 1000, size=sub.size)
    out = rt.call_batch(Player, "heartbeat", sub,
                        {"pos": pos, "delta": delta.astype(np.int32)})
    n += check_equal(out, ref.round(sub, pos, delta),
                     "call_batch (subset) replies")
    compared["call_batch_replies"] = n

    # K=8 scanned rounds per launch
    K, n = 8, 0
    for _ in range(2):
        pos = rand_pos16(rng, K, N)
        delta = rng.integers(1, 1000, size=(K, N))
        out = rt.call_batch_rounds(
            Player, "heartbeat", keys,
            {"pos": pos, "delta": delta.astype(np.int32)}, plan=plan)
        want = np.stack([ref.round(keys, pos[k], delta[k])
                         for k in range(K)])
        n += check_equal(out, want, "call_batch_rounds replies")
    compared["call_batch_rounds_replies"] = n
    compared["dense_rows_after_bulk"] = compare_dense(tbl, ref, "engine bulk")

    # hashed 62-bit keys through the per-key path: rt.actor(...).heartbeat,
    # several messages per key in one burst so same-slot conflicts defer
    n_keys, per_key = (512, 4) if N >= 100_000 else (64, 4)
    hashed = np.unique(rng.integers(1 << 40, 1 << 62, size=n_keys,
                                    dtype=np.int64))
    mixed = [int(k) for k in hashed] + \
        [int(k) for k in rng.choice(N, size=n_keys, replace=False)]

    async def burst():
        calls, wants = [], []
        for rep in range(per_key):
            for k in mixed:
                p16 = rand_pos16(rng)
                d = int(rng.integers(1, 1000))
                calls.append(rt.actor(Player, k).heartbeat(
                    pos=p16, delta=np.int32(d)))
                wants.append(ref.one(k, p16, d))
        got = await asyncio.gather(*calls)  # a failed tick raises here
        await rt.flush()
        return np.array([int(g) for g in got]), np.array(wants)

    got, want = asyncio.run(burst())
    compared["hashed_key_replies"] = check_equal(
        got, want, "per-key replies (hashed + dense, in arrival order)")
    check(rt.conflicts_deferred >= (per_key - 1) * len(mixed),
          f"conflict-defer never ran: {rt.conflicts_deferred}")
    check(tbl.device_dir.count == hashed.size,
          f"DeviceDirectory64 holds {tbl.device_dir.count} keys, "
          f"expected {hashed.size}")

    # the on-device directory: route one message to every hashed key by
    # its 62-bit hash (sparse=True resolves the owner through
    # DeviceDirectory64 ON DEVICE), then apply with the dedup/defer loop
    lanes = 1 << int(hashed.size).bit_length()
    dest = np.zeros((1, lanes), np.int64)
    dest[0, :hashed.size] = hashed
    # every spare lane repeats a key (a second turn must defer a tick) ...
    dest[0, hashed.size:] = hashed[:lanes - hashed.size]
    unknown = (1 << 61) + 12345      # ... but one: never registered, drops
    dest[0, -1] = unknown
    d_pos = rand_pos16(rng, 1, lanes)
    d_delta = rng.integers(1, 1000, size=(1, lanes)).astype(np.int32)
    lo, hi = split64(dest)
    rkeys, rpay, rvalid, drops = rt.route(
        Player, (jnp.asarray(lo), jnp.asarray(hi)),
        {"pos": jnp.asarray(d_pos), "delta": jnp.asarray(d_delta)},
        jnp.ones((1, lanes), bool), capacity=lanes, sparse=True)
    check(int(np.asarray(drops).sum()) == 1,
          f"sparse route drops {np.asarray(drops)} != 1 (the unknown key)")
    delivered = int(np.asarray(rvalid).sum())
    check(delivered == lanes - 1, f"sparse route delivered {delivered}")
    # reference: lanes apply in lane order, one per key per tick
    rk = np.asarray(rkeys[0]).astype(np.int64) | \
        (np.asarray(rkeys[1]).astype(np.int64) << 31)  # split64's halves
    check_equal(rk[0][np.asarray(rvalid)[0]],
                dest[0][dest[0] != unknown], "sparse route keys")
    pending = np.asarray(rvalid).copy()
    rounds = applied_total = 0
    while pending.any() and rounds <= lanes:
        res, applied = rt.apply_received(
            Player, "heartbeat", rkeys, jnp.asarray(pending), rpay,
            sparse=True)
        a = np.asarray(applied)
        check(not (a & ~pending).any(), "applied a lane that was not pending")
        for lane in np.flatnonzero(a[0]):
            want_score = ref.one(int(rk[0, lane]),
                                 np.asarray(rpay["pos"])[0, lane],
                                 int(np.asarray(rpay["delta"])[0, lane]))
            check(int(np.asarray(res)[0, lane]) == want_score,
                  f"apply_received lane {lane}: got "
                  f"{int(np.asarray(res)[0, lane])}, want {want_score}")
        applied_total += int(a.sum())
        pending &= ~a
        rounds += 1
    check(applied_total == delivered and rounds >= 2,
          f"dedup/defer loop applied {applied_total}/{delivered} in "
          f"{rounds} rounds")
    compared["sparse_route_applied"] = applied_total
    compared["sparse_route_rounds"] = rounds

    # every row once more: dense rows from the snapshot, hashed rows by key
    n = compare_dense(tbl, ref, "engine final")
    for k in hashed:
        row = tbl.read_row(int(k))
        pos, score, game = ref.row(int(k))
        check(int(row["score"]) == score and int(row["game"]) == game
              and (np.asarray(row["pos"]) == pos).all(),
              f"hashed key {int(k)} row {row} != reference "
              f"{(pos, score, game)}")
        n += 4
    compared["rows_final"] = n
    compared["ticks"] = rt.ticks
    compared["conflicts_deferred"] = rt.conflicts_deferred
    if ARGS.inject_fault == "engine":
        ref.score[0] += 1
        compare_dense(tbl, ref, "injected fault")
    return compared


# ---------------------------------------------------------------------------
# phase: served
# ---------------------------------------------------------------------------

async def _served(N: int, rng, worker_procs: int) -> dict:
    import jax

    from orleans_tpu.core.ids import GrainId, GrainType
    from orleans_tpu.dispatch import add_vector_grains
    from orleans_tpu.membership import FileMembershipTable, join_cluster
    from orleans_tpu.runtime import GatewayClient, SiloBuilder, SocketFabric
    from orleans_tpu.storage import MemoryStorage
    from presence_tpu import GameGrain, PlayerVectorGrain as Player

    ref = PresenceReference(N)
    storage = MemoryStorage()
    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    b = (SiloBuilder().with_name("chip-smoke").with_fabric(SocketFabric())
         .add_grains(GameGrain))
    if worker_procs > 1:
        b = b.with_config(worker_procs=worker_procs)
    # no mesh is passed: the runtime's own make_mesh() takes every local
    # device, one shard a chip, as a deployment's silo does
    n_dev = jax.device_count()
    add_vector_grains(b, Player, dense={Player: N},
                      capacity_per_shard=-(-N // n_dev),
                      storage=storage, flush_period=0.25)
    silo = b.build()
    join_cluster(silo, FileMembershipTable(os.path.join(tmp, "mbr.json")))
    await silo.start()
    rt = silo.vector
    tbl = rt.table(Player)
    on = {d for leaf in tbl.state.values() for d in leaf.devices()}
    check(tbl.n_shards == len(on) == n_dev,
          f"the served table has {tbl.n_shards} shards on {len(on)} of "
          f"{n_dev} devices")
    compared: dict = {"worker_procs": silo.config.worker_procs,
                      "offloop_tick": silo.config.offloop_tick,
                      "shards": tbl.n_shards}
    client = None
    written: set = set()  # every key whose write was acknowledged

    def key_hash(key) -> int:
        # dense and 62-bit int keys are their own hash; a string key's is
        # the framework's GrainId hash (identity, not behaviour under test)
        if isinstance(key, int):
            return key
        return GrainId.for_grain(GrainType.of(Player.__name__),
                                 key).uniform_hash

    try:
        client = await GatewayClient([silo.gateway_endpoint],
                                     response_timeout=60.0).connect()

        # 32 concurrent callers, each with its own players (dense ints,
        # GUID-like strings, 62-bit ints), visited 4 times in sequence;
        # every caller also beats one shared hot player once
        n_callers, own, visits = 32, 8, 4
        hot, hot_delta = int(rng.integers(0, N)), 7
        dense_pool = [int(k) for k in rng.choice(
            np.setdiff1d(np.arange(N), [hot]), size=n_callers * own,
            replace=False)]
        scripts = []
        for w in range(n_callers):
            mine: list = dense_pool[w * own:(w + 1) * own]
            mine[-1] = f"player-{rng.integers(1 << 62):016x}-{w}"
            mine[-2] = int(rng.integers(1 << 40, 1 << 62))
            script = []
            for v in range(visits):
                for k in mine:
                    script.append((k, rand_pos16(rng),
                                   int(rng.integers(257, 5000))))
            scripts.append(script)
        hot_pos = rand_pos16(rng)

        async def caller(script) -> list:
            got = []
            for i, (k, p16, d) in enumerate(script):
                if i == len(script) // 2:
                    got.append(("hot", int(await client.get_grain(
                        Player, hot).heartbeat(
                            pos=hot_pos.tolist(), delta=hot_delta))))
                got.append((k, int(await client.get_grain(
                    Player, k).heartbeat(pos=p16.tolist(), delta=d))))
            return got

        replies = await asyncio.gather(*(caller(s) for s in scripts))
        n = 0
        hot_replies = []
        for script, got in zip(scripts, replies):
            it = iter(got)
            for i, (k, p16, d) in enumerate(script):
                if i == len(script) // 2:
                    hot_replies.append(next(it)[1])
                rk, score = next(it)
                want = ref.one(key_hash(k), p16, d)
                check(rk == k and score == want,
                      f"served heartbeat {k!r}: got {score}, want {want}")
                written.add(key_hash(k))
                n += 1
        for _ in range(n_callers):
            ref.one(hot, hot_pos, hot_delta)
        written.add(hot)
        # the hot player's turns serialize in SOME order: each caller saw
        # a distinct multiple of the delta
        check(sorted(hot_replies) ==
              [hot_delta * (i + 1) for i in range(n_callers)],
              f"hot player replies {sorted(hot_replies)}")
        compared["heartbeat_calls"] = n + n_callers

        # one client call_batch: 256 distinct players in one wire batch
        bkeys = [int(k) for k in rng.choice(N, size=256, replace=False)]
        calls, wants = [], []
        for k in bkeys:
            p16, d = rand_pos16(rng), int(rng.integers(257, 5000))
            calls.append((k, {"pos": p16.tolist(), "delta": d}))
            wants.append(ref.one(k, p16, d))
            written.add(k)
        got = await asyncio.gather(
            *client.call_batch(Player, "heartbeat", calls))
        compared["client_call_batch"] = check_equal(
            [int(g) for g in got], wants, "client call_batch replies")

        # one read-only call
        k = scripts[0][0][0]
        got = await client.get_grain(Player, k).whereis()
        compared["read_only_call"] = check_equal(
            np.asarray(got, np.float32), ref.row(key_hash(k))[0],
            f"whereis({k!r})")

        if worker_procs <= 1:
            # one host-grain call: GameGrain sums its players' scores
            # from the device table (an MXU segment sum over 1M rows)
            g = ref.game[hot]
            s = await client.get_grain(GameGrain, int(g)).summary()
            players, total = ref.game_summary(int(g))
            check(s["players"] == players and s["total_score"] == total,
                  f"GameGrain({int(g)}).summary {s} != reference "
                  f"players={players} total_score={total}")
            compared["host_grain_call"] = {"game": int(g), **s}

            # write-behind: every acknowledged write becomes readable
            # from storage (the periodic flush; no stop needed)
            gtype = GrainType.of(Player.__name__)

            async def stored_matches() -> int:
                bad = 0
                for kh in written:
                    state, _etag = await storage.read(
                        Player.__name__, GrainId.for_grain(gtype, int(kh)))
                    pos, score, _game = ref.row(kh)
                    if state is None or int(state["score"]) != score or \
                            not (np.asarray(state["pos"]) == pos).all():
                        bad += 1
                return bad

            deadline = time.monotonic() + 30.0
            while (bad := await stored_matches()) and \
                    time.monotonic() < deadline:
                await asyncio.sleep(0.25)
            check(bad == 0, f"{bad}/{len(written)} acknowledged writes not "
                            f"readable from storage after the flush")
            compared["write_behind_read_back"] = len(written)
        compared["ticks"] = rt.ticks
        compared["conflicts_deferred"] = rt.conflicts_deferred
        if ARGS.inject_fault == "served":
            check_equal([1], [2], "injected fault")
    finally:
        if client is not None:
            await client.close_async()
        await silo.stop()
        shutil.rmtree(tmp, ignore_errors=True)
    workers = [t.name for t in threading.enumerate()
               if t.name == "orleans-tick-worker" and t.is_alive()]
    check(not workers, "the tick worker thread survived silo.stop()")
    compared["stopped"] = True
    return compared


def phase_served(N: int, rng, worker_procs: int = 0) -> dict:
    return asyncio.run(_served(N, rng, worker_procs))


# ---------------------------------------------------------------------------
# phase: mesh (--chips 4 only)
# ---------------------------------------------------------------------------

def _mesh_traffic(n_dev: int, N: int, seed: int) -> dict:
    """The sharded traffic on an ``n_dev``-device mesh, through public
    calls; returns everything observable as numpy for exact comparison."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from orleans_tpu.dispatch import (VectorGrain, VectorRuntime,
                                      actor_method, reshard_dense)
    from orleans_tpu.ops import segment_sum
    from orleans_tpu.ops.hash_probe import split64
    from orleans_tpu.parallel import SILO_AXIS, make_mesh
    from presence_tpu import PlayerVectorGrain as Player

    class GameGrain(VectorGrain):
        """Sharded fan-in target: heartbeats delivered, scores summed."""
        STATE = {"count": (jnp.int32, ()), "total": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"count": jnp.int32(0), "total": jnp.int32(0)}

        @actor_method(args={"n": (jnp.int32, ()), "s": (jnp.int32, ())})
        def accumulate(state, args):
            new = {"count": state["count"] + args["n"],
                   "total": state["total"] + args["s"]}
            return new, new["count"]

    class CounterVec(VectorGrain):
        STATE = {"total": (jnp.int32, ()), "hits": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"total": jnp.int32(0), "hits": jnp.int32(0)}

        @actor_method(args={"amount": (jnp.int32, ())})
        def add(state, args):
            new = {"total": state["total"] + args["amount"],
                   "hits": state["hits"] + 1}
            return new, new["total"]

    rng = np.random.default_rng(seed)
    out: dict = {}
    mesh = make_mesh(n_dev)
    n = mesh.devices.size
    check(n == n_dev, f"mesh has {n} devices, wanted {n_dev}")
    rt = VectorRuntime(mesh=mesh, capacity_per_shard=-(-N // n))
    tbl = rt.table(Player)
    tbl.ensure_dense(N)
    keys = np.arange(N)
    plan = rt.make_dense_plan(Player, keys)

    # placement: each leaf's shards sit on n distinct devices
    if n > 1:
        stats = {}
        for name, leaf in tbl.state.items():
            devs = {s.device for s in leaf.addressable_shards}
            check(len(devs) == n,
                  f"table leaf {name!r} lives on {len(devs)} devices")
        for d in mesh.devices.flat:
            ms = d.memory_stats() or {}
            stats[str(d)] = ms.get("bytes_in_use")
        shard_bytes = sum(int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
                          for leaf in tbl.state.values())
        out["placement"] = {"bytes_in_use": stats,
                            "table_shard_bytes": shard_bytes}
        have = [v for v in stats.values() if v is not None]
        if have:  # the CPU rehearsal reports no memory stats
            check(len(have) == n and min(have) >= shard_bytes,
                  f"a device holds less than its table shard: {stats}")

    # K heartbeat rounds in one launch (shard_map when n > 1)
    K = 8
    pos = rand_pos16(rng, K, N)
    delta = rng.integers(1, 500, size=(K, N))
    res = rt.call_batch_rounds(
        Player, "heartbeat", keys,
        {"pos": pos, "delta": delta.astype(np.int32)}, plan=plan,
        device_results=True)                       # [K, n, B] scores
    score_b = res[-1]
    out["scores_after_rounds"] = np.stack(
        [plan.unpack(np.asarray(res[k])) for k in range(K)])

    # every player -> its game, over the exchange, into the sharded
    # GameGrain table. Chunks of <= 32768 lanes per shard take the MXU
    # prefix-count kernel inside shard_map on a TPU; one full-width route
    # (counts only: 15625 scores per game exceed the f32-exact 2^24)
    # takes the sort rank past the switch.
    gt = rt.table(GameGrain)
    gt.ensure_dense(N_GAMES)
    gps = gt.dense_per_shard
    rt.call_batch(GameGrain, "accumulate", np.arange(N_GAMES),
                  {"n": np.zeros(N_GAMES, np.int32),
                   "s": np.zeros(N_GAMES, np.int32)})
    shard = NamedSharding(mesh, P(SILO_AXIS))
    B = plan.B
    game_b = jax.device_put(
        jnp.asarray(plan.pack(keys % N_GAMES, np.int32, ())), shard)
    valid_b = jax.device_put(jnp.asarray(plan.valid_b), shard)
    lanes = np.broadcast_to(np.arange(gps, dtype=np.int32), (n, gps)).copy()
    g_slots = jax.device_put(jnp.asarray(lanes), shard)
    g_valid = jax.device_put(jnp.ones((n, gps), bool), shard)
    g_fresh = jax.device_put(jnp.zeros((n, gps), bool), shard)

    def agg_local(rk, rv, rs):
        k, v, s = rk[0], rv[0], rs[0]
        ones = jnp.where(v, 1, 0).astype(jnp.int32)
        cnt = segment_sum(ones, k % gps, gps)
        tot = segment_sum(jnp.where(v, s, 0), k % gps, gps)
        return cnt[None], tot[None], jnp.sum(ones)[None]

    spec = P(SILO_AXIS)
    agg = jax.jit(jax.shard_map(
        agg_local, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec), check_vma=False)) if n > 1 \
        else jax.jit(agg_local)

    sent = delivered = dropped = 0
    chunk = min(B, 32768)
    for c0 in list(range(0, B, chunk)) + [None]:
        if c0 is None:      # the full-width route, counts only
            sl, cap, carry_scores = slice(0, B), -(-5 * B // (4 * n)), False
        else:
            sl, cap, carry_scores = slice(c0, c0 + chunk), \
                -(-5 * chunk // (4 * n)), True
        v = valid_b[:, sl]
        rk, recv, rv, drops = rt.route(
            GameGrain, game_b[:, sl], {"score": score_b[:, sl]}, v,
            capacity=cap)
        cnt, tot, dl = agg(rk, rv, recv["score"])
        rt.call_batch_device(
            GameGrain, "accumulate", g_slots, g_slots, g_fresh, g_valid,
            {"n": cnt, "s": tot if carry_scores else jnp.zeros_like(tot)})
        sent += int(np.asarray(v).sum())
        delivered += int(np.asarray(dl).sum())
        dropped += int(np.asarray(drops).sum())
    out["exchange"] = {"sent": sent, "delivered": delivered,
                       "dropped": dropped}
    out["games"] = dense_rows(gt)

    # sparse hashed keys over the exchange, with the dedup/defer loop
    ctbl = rt.table(CounterVec)
    hashes = np.unique(rng.integers(1 << 40, 1 << 62, size=2048,
                                    dtype=np.int64))

    async def activate():
        await asyncio.gather(*(
            rt.call(CounterVec, int(h), "add", amount=np.int32(0))
            for h in hashes))
        await rt.flush()
    asyncio.run(activate())
    # the only per-key calls of this phase: stop the tick worker they
    # started, so the served phase that follows can tell its own apart
    rt.shutdown_worker()
    check(ctbl.device_dir.count == hashes.size, "sparse activation count")
    B2 = 4096 // n            # the same 4096 messages on any mesh
    dest = rng.choice(hashes, size=n * B2)
    dest[1] = dest[0]         # a same-actor pair from one source shard
    dest = dest.reshape(n, B2)
    amount = rng.integers(1, 1000, size=n * B2).astype(
        np.int32).reshape(n, B2)
    lo, hi = split64(dest)
    rkeys, rpay, rvalid, sdrops = rt.route(
        CounterVec, (jnp.asarray(lo), jnp.asarray(hi)),
        {"amount": jnp.asarray(amount)}, jnp.ones((n, B2), bool),
        capacity=B2, sparse=True)
    s_delivered = int(np.asarray(rvalid).sum())
    pending = np.asarray(rvalid).copy()
    rounds = applied_total = 0
    while pending.any() and rounds <= n * B2:
        _, applied = rt.apply_received(
            CounterVec, "add", rkeys, jnp.asarray(pending), rpay,
            sparse=True)
        a = np.asarray(applied)
        applied_total += int(a.sum())
        pending &= ~a
        rounds += 1
    out["sparse"] = {"sent": n * B2, "delivered": s_delivered,
                     "dropped": int(np.asarray(sdrops).sum()),
                     "applied": applied_total}
    check(rounds >= 2, "the duplicate never deferred a tick")
    out["sparse_dest"] = dest.reshape(-1)
    out["sparse_amount"] = amount.reshape(-1)
    out["sparse_totals"] = np.array(
        [int(ctbl.read_row(int(h))["total"]) for h in hashes])
    out["sparse_hashes"] = hashes

    # elastic reshard n -> n-1 -> n with a heartbeat round in each era
    tbl.dense_active[:] = True
    survivors = max(1, n - 1)
    rt2 = VectorRuntime(mesh=make_mesh(survivors),
                        capacity_per_shard=-(-N // survivors))
    tbl2 = reshard_dense(tbl, rt2)
    check(tbl2.n_shards == survivors, "shrink reshard shard count")
    pos2, delta2 = rand_pos16(rng, N), rng.integers(1, 500, size=N)
    out["scores_shrunk"] = rt2.call_batch(
        Player, "heartbeat", keys,
        {"pos": pos2, "delta": delta2.astype(np.int32)})
    rt3 = VectorRuntime(mesh=make_mesh(n), capacity_per_shard=-(-N // n))
    tbl3 = reshard_dense(tbl2, rt3)
    check(tbl3.n_shards == n, "grow reshard shard count")
    pos3, delta3 = rand_pos16(rng, N), rng.integers(1, 500, size=N)
    out["scores_regrown"] = rt3.call_batch(
        Player, "heartbeat", keys,
        {"pos": pos3, "delta": delta3.astype(np.int32)})
    out["rows_final"] = dense_rows(tbl3)
    out["inputs"] = {"pos": pos, "delta": delta, "pos2": pos2,
                     "delta2": delta2, "pos3": pos3, "delta3": delta3}
    return out


def _tree_equal(a, b, what: str) -> int:
    if isinstance(a, dict):
        check(a.keys() == b.keys(), f"{what}: keys differ")
        return sum(_tree_equal(a[k], b[k], f"{what}.{k}") for k in a)
    return check_equal(a, b, what)


def phase_mesh(N: int, seed: int, n_dev: int) -> dict:
    wide = _mesh_traffic(n_dev, N, seed)
    one = _mesh_traffic(1, N, seed)
    placement = wide.pop("placement")
    compared = {"placement": placement}
    for o in (wide, one):
        ex, sp = o["exchange"], o["sparse"]
        check(ex["delivered"] + ex["dropped"] == ex["sent"]
              and ex["dropped"] == 0, f"exchange accounting {ex}")
        check(sp["delivered"] + sp["dropped"] == sp["sent"]
              and sp["dropped"] == 0 and sp["applied"] == sp["delivered"],
              f"sparse exchange accounting {sp}")
    compared["exchange"] = wide["exchange"]
    compared["sparse"] = wide["sparse"]
    compared[f"mesh{n_dev}_vs_mesh1_values"] = _tree_equal(
        wide, one, f"{n_dev}-device mesh vs 1-device mesh")

    # ... and both against the plain reference
    keys = np.arange(N)
    ref = PresenceReference(N)
    inp = wide["inputs"]
    K = inp["pos"].shape[0]
    want = np.stack([ref.round(keys, inp["pos"][k], inp["delta"][k])
                     for k in range(K)])
    n = check_equal(wide["scores_after_rounds"], want, "scanned rounds")
    per_game = np.bincount(keys % N_GAMES, minlength=N_GAMES)
    n += check_equal(wide["games"]["count"], 2 * per_game,
                     "GameGrain delivered counts")
    n += check_equal(
        wide["games"]["total"],
        np.bincount(keys % N_GAMES, weights=ref.score,
                    minlength=N_GAMES).astype(np.int64),
        "GameGrain score totals")
    n += check_equal(wide["scores_shrunk"],
                     ref.round(keys, inp["pos2"], inp["delta2"]),
                     "post-shrink round")
    n += check_equal(wide["scores_regrown"],
                     ref.round(keys, inp["pos3"], inp["delta3"]),
                     "post-grow round")
    n += check_equal(wide["rows_final"]["score"], ref.score, "final scores")
    n += check_equal(wide["rows_final"]["pos"], ref.pos, "final positions")
    totals = {int(h): 0 for h in wide["sparse_hashes"]}
    for h, a in zip(wide["sparse_dest"].reshape(-1),
                    wide["sparse_amount"].reshape(-1)):
        totals[int(h)] += int(a)
    n += check_equal(wide["sparse_totals"],
                     [totals[int(h)] for h in wide["sparse_hashes"]],
                     "sparse counter totals")
    compared["vs_reference_values"] = n
    if ARGS.inject_fault == "mesh":
        check_equal([1], [2], "injected fault")
    return compared


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def _watchdog(limit: float) -> None:
    """Hard limit for the --worker-procs run: a hang becomes a failure
    line and a dead process group, never a stuck chip call."""
    import signal
    os.setpgrp()

    def fire() -> None:
        emit({"phase": "served", "ok": False,
              "error": f"no end after {limit:.0f}s: hung"})
        os.killpg(0, signal.SIGKILL)

    t = threading.Timer(limit, fire)
    t.daemon = True
    t.start()


def main() -> int:
    global ARGS
    t_start = time.perf_counter()
    ARGS = _parse_args()
    if ARGS.rehearse_cpu:
        # the rehearsal is CPU by name (set before jax is first imported);
        # the real run leaves jax's choice of platform alone
        os.environ["JAX_PLATFORMS"] = "cpu"
        if ARGS.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                f"platform_device_count={ARGS.chips}").strip()
    if ARGS.worker_procs > 1:
        _watchdog(ARGS.limit)
    import importlib.metadata as md

    import jax

    from orleans_tpu import native
    from orleans_tpu.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    meter = CompileMeter()
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    device = {"platform": platform, "kind": kind, "count": len(devs)}
    entries_before = cache_entries(cache_dir)
    emit({"phase": "environment",
          "python": sys.version.split()[0],
          "versions": {p: md.version(p)
                       for p in ("jax", "jaxlib", "libtpu", "numpy")},
          "devices": [str(d) for d in devs], "device": device,
          "wire_codec": native.wire_codec(),
          "compile_cache": {
              "dir": cache_dir,
              "placed_by_env": bool(
                  os.environ.get("JAX_COMPILATION_CACHE_DIR")),
              "entries_before": entries_before},
          "argv": sys.argv[1:]})
    if platform != "tpu" and not ARGS.rehearse_cpu:
        print(f"chip_smoke: jax found no TPU (platform {platform!r}); "
              f"refusing to run", file=sys.stderr)
        return 2
    if native.load("_hotwire") is None:
        print("chip_smoke: the C wire codec did not build/load; the "
              "pure-Python fallback is not the served path",
              file=sys.stderr)
        return 2
    if len(devs) < ARGS.chips:
        print(f"chip_smoke: --chips {ARGS.chips} but jax reports "
              f"{len(devs)} devices", file=sys.stderr)
        return 2

    on_tpu = platform == "tpu"
    rng = np.random.default_rng(ARGS.seed)
    N = ARGS.players
    if ARGS.chips > 1:
        phases = [("mesh", phase_mesh, N, ARGS.seed, ARGS.chips),
                  ("served", phase_served, N, rng)]
    elif ARGS.worker_procs > 1:
        phases = [("served", phase_served, N, rng, ARGS.worker_procs)]
    else:
        phases = [("kernels", phase_kernels, on_tpu, rng),
                  ("engine", phase_engine, N, rng),
                  ("served", phase_served, N, rng)]
    failed = [name for name, fn, *args in phases
              if not run_phase(name, meter, fn, *args)]
    emit({"phase": "summary",
          "seconds": round(time.perf_counter() - t_start, 3),
          "compile_seconds": round(meter.seconds, 3),
          "compile_cache": {"dir": cache_dir,
                            "entries_before": entries_before,
                            "entries_after": cache_entries(cache_dir),
                            "hits": meter.hits, "misses": meter.misses}})
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
