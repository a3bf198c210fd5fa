"""MXU-shaped actor handler — real per-message compute on the dispatch
engine.

Every earlier device-tier harness used the 40-byte Presence
heartbeat, a pure HBM-bandwidth workload. This benchmark drives the SAME
fused/scanned dispatch machinery (``call_batch_rounds`` — the engine of
BENCH_r04) with a handler whose state update is matmul-shaped: each
actor carries a 512-wide bf16 hidden state and one message applies a
two-layer recurrent cell

    a   = tanh(h @ W1 + x @ Win)        # [D] <- [D][D,D] + [DIN][DIN,D]
    out = tanh(a @ W2)                  # readout (nonlinear: XLA cannot
    h'  = a                             # fold the sum through it)

vmapped over the lane axis, so the whole tick is [B,D]@[D,D] matmuls on
the MXU. Arithmetic intensity ~2.1 MFLOP / ~2.2 KB per actor-round
(~950 FLOP/byte) — solidly MXU-bound on v5e (ridge ~240 FLOP/byte),
making this the compute-roofline companion to bench.py's bandwidth
roofline. Reference shape: a Samples-style grain whose handler does real
model math per message (the reference has no TPU analog — this is the
capability the device tier exists for).

Attribution: two-point blocking fit (benchmarks/attribution.py) splits
per-dispatch host cost from device time; roofline reports pct_of_mxu_peak.
"""

import argparse
import json
import os
import time
from collections import deque

import numpy as np

if __package__ in (None, ""):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from benchmarks.attribution import (roofline_fields, staged_cache,
                                    two_point_fit)
from orleans_tpu.dispatch import VectorGrain, VectorRuntime, actor_method
from orleans_tpu.parallel import make_mesh

D = 512          # hidden width (bf16): 1 KiB state row per actor
DIN = 16         # message width: keeps K-round staged buffers small


def _make_grain(seed: int = 0):
    rng = np.random.default_rng(seed)
    scale = 1.0 / np.sqrt(D)
    w1 = jnp.asarray(rng.standard_normal((D, D)) * scale, jnp.bfloat16)
    w2 = jnp.asarray(rng.standard_normal((D, D)) * scale, jnp.bfloat16)
    win = jnp.asarray(rng.standard_normal((DIN, D)), jnp.bfloat16)

    def cell(h, x):
        """The ONE cell definition — the grain handler and the bare
        ceiling kernel both call this, so engine_tax_factor can never
        silently measure two different computations. Square (not a
        second tanh) on the readout: nonlinear, so XLA cannot fold the
        sum through the matmul and delete it, but ~10x cheaper on the
        VPU — the MXU stays the bottleneck."""
        a = jnp.tanh(h @ w1 + x.astype(jnp.bfloat16) @ win)
        out = a @ w2
        return (a.astype(jnp.bfloat16),
                jnp.sum(jnp.square(out.astype(jnp.float32)), axis=-1))

    class CellGrain(VectorGrain):
        STATE = {"h": (jnp.bfloat16, (D,)), "n": (jnp.int32, ())}

        @staticmethod
        def initial_state(key_hash):
            return {"h": jnp.zeros(D, jnp.bfloat16), "n": jnp.int32(0)}

        @actor_method(args={"x": (jnp.float16, (DIN,))})
        def step(state, args):
            a, emit = cell(state["h"], args["x"])
            return {"h": a, "n": state["n"] + 1}, emit

    return CellGrain, cell


# per actor-round: h@W1 + x@Win + a@W2 (2 FLOPs per MAC)
FLOPS_PER_ACTOR_ROUND = 2 * D * D + 2 * DIN * D + 2 * D * D
# per actor-round HBM traffic: h read+write (bf16), x read (fp16),
# scalar result write (f32); W1/W2/Win are shared and cache-resident
BYTES_PER_ACTOR_ROUND = D * 2 * 2 + DIN * 2 + 4


def run(n_actors: int = 65536, fuse: int | None = None,
        seconds: float = 8.0, pipeline_depth: int = 4,
        reps: int = 3) -> dict:
    fuse = fuse if fuse is not None else int(
        os.environ.get("MXU_FUSE", "64"))
    CellGrain, cell = _make_grain()
    mesh = make_mesh(1)
    rt = VectorRuntime(mesh=mesh, capacity_per_shard=n_actors)
    tbl = rt.table(CellGrain)
    tbl.ensure_dense(n_actors)
    keys = np.arange(n_actors)
    plan = rt.make_dense_plan(CellGrain, keys)
    rng = np.random.default_rng(1)

    def staged(k: int):
        # DEVICE-resident staged rounds: a host-side payload would
        # re-transfer ~1 MB/round per launch and swamp both throughput
        # and the fit (bench.py stages the same way)
        return jnp.asarray(
            rng.standard_normal((k, n_actors, DIN)).astype(np.float16))

    depth = rt.validate_pipeline_depth(pipeline_depth)
    payload = staged(fuse)
    dispatched = {"rounds": 0}

    def launch(buf):
        dispatched["rounds"] += int(buf.shape[0])
        return rt.call_batch_rounds(CellGrain, "step", keys, {"x": buf},
                                    plan=plan, device_results=True)

    # warmup / compile
    jax.block_until_ready(launch(payload))

    # ---- throughput: pipelined fused launches -------------------------
    inflight: deque = deque()
    completions = []
    launches = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        inflight.append(launch(payload))
        launches += 1
        if len(inflight) >= depth:
            jax.block_until_ready(inflight.popleft())
            completions.append(time.perf_counter())
    while inflight:
        jax.block_until_ready(inflight.popleft())
        completions.append(time.perf_counter())
    comp = np.asarray(completions)
    elapsed = comp[-1] - comp[0] if len(comp) > 1 else seconds
    intervals = np.diff(comp)
    actor_rounds = (len(comp) - 1) * fuse * n_actors
    per_sec = actor_rounds / elapsed if elapsed > 0 else 0.0

    # ---- attribution: two-point blocking fit over round counts -------
    get_staged = staged_cache(staged)

    def run_blocking(k: int) -> float:
        buf = payload[:k] if k <= fuse else get_staged(k)
        t0 = time.perf_counter()
        jax.block_until_ready(launch(buf))
        return time.perf_counter() - t0

    s_a = max(8, fuse // 2)
    fit = two_point_fit(run_blocking, s_a, 2 * s_a, reps=reps)

    # correctness: every actor saw every dispatched round exactly once
    n_rounds = int(np.asarray(tbl.read_row(0)["n"]))
    assert n_rounds == dispatched["rounds"], (n_rounds, dispatched)

    # ---- engine tax: the BARE cell as the hardware ceiling ------------
    # the same math without actor semantics (no slot gather/scatter, no
    # fresh-init select, no valid masking, no per-round emit packing):
    # its fitted per-round time is what THIS computation can do on this
    # chip, so device_unit_ms / bare_unit_ms is the measured price of
    # dispatch semantics — the residual below MXU peak is then split
    # into (engine tax) x (bare-kernel efficiency)
    @jax.jit
    def bare(h, xs):
        return jax.lax.scan(cell, h, xs)

    h0 = jnp.zeros((n_actors, D), jnp.bfloat16)

    def bare_blocking(k: int) -> float:
        xs = (payload[:k] if k <= fuse else get_staged(k))
        t0 = time.perf_counter()
        jax.block_until_ready(bare(h0, xs))
        return time.perf_counter() - t0

    bare_fit = two_point_fit(bare_blocking, s_a, 2 * s_a, reps=reps)
    bare_ms = bare_fit["device_unit_ms"]
    bare_roof = roofline_fields(
        bare_fit, flops_per_unit=FLOPS_PER_ACTOR_ROUND * n_actors)
    tax = round(fit["device_unit_ms"] / bare_ms, 2) \
        if bare_ms > 0 and fit["device_unit_ms"] > 0 else None
    roof = roofline_fields(
        fit,
        bytes_per_unit=BYTES_PER_ACTOR_ROUND * n_actors,
        flops_per_unit=FLOPS_PER_ACTOR_ROUND * n_actors)

    extra = {
        "n_actors": n_actors, "hidden": D, "msg_width": DIN,
        "rounds_per_launch": fuse, "pipeline_depth": depth,
        "launches": launches,
        "dispatch_interval_ms_p50": round(
            float(np.percentile(intervals, 50)) * 1e3, 2)
        if intervals.size else None,
        "flops_per_actor_round": FLOPS_PER_ACTOR_ROUND,
        "bytes_per_actor_round": BYTES_PER_ACTOR_ROUND,
        "verified_rounds": n_rounds,
        "bare_cell_ms_per_round": bare_ms,
        "bare_cell_pct_of_mxu_peak": bare_roof.get("pct_of_mxu_peak"),
        "engine_tax_factor": tax,
        **fit, **roof,
    }
    extra.pop("device_unit_s", None)
    return {
        "metric": "mxu_handler_actor_rounds_per_sec",
        "value": round(per_sec, 1),
        "unit": "actor-rounds/sec/chip",
        "vs_baseline": None,
        "extra": extra,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--actors", type=int, default=65536)
    ap.add_argument("--fuse", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=8.0)
    a = ap.parse_args()
    print(json.dumps(run(a.actors, a.fuse, a.seconds)))


if __name__ == "__main__":
    main()
