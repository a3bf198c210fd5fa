#!/usr/bin/env python3
"""chipbench — the benchmark's one command.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The process this starts IS the silo and alone holds the chip: a
``SiloBuilder`` on a ``SocketFabric`` with the deployment's vector grains,
gateway on loopback TCP, shipped ``SiloConfig`` defaults (``--trace 1``
switches ``metrics_enabled`` on; ``--trace 0`` switches nothing). It spawns
the cell's load generators (``loadgen.py``, ``JAX_PLATFORMS=cpu``), each
with its own ``GatewayClient``; all end-to-end numbers are taken at those
clients, merged. No real link is crossed: client and silo share one host.

Everything that belongs to one cell, deployment or per-layer metric is a
file found by name — ``workloads/<cell>.json``, ``configs/<config>.json``,
``apps/<app>.py``, ``references/<ref>.py``, ``traffic/<kind>.py``,
``layer_metrics/<metric>.json``, ``readers/<reader>.py`` — and this file
names none of them (see ``README.md``).

Without a TPU the run fails (``--rehearse-cpu`` is the explicit tiny
rehearsal; its last line names the CPU and the driver never passes it), as
it does on the pure-Python wire codec. The last line of stdout is the
contract's JSON object; earlier lines are JSON records worth reading.
"""

from __future__ import annotations

import time

T_COMMAND = time.monotonic()  # set-up counts from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import NoReturn  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from loadgen import Records, load_by_name, say as emit  # noqa: E402

TRACE_SLICE_S = 3.0   # the profiler traces this much, mid-window
MIN_BUCKET = 8        # the engine's smallest tick bucket (dispatch/engine.py)
MAX_FLUSH_ROWS = 16384  # the widest write-behind pass set-up compiles for
CHILD_LIMIT_S = 120.0  # a child silent for this long has hung
QUANTILES = ("50", "90", "95", "99")  # of a latency population, printed


def fail(msg: str, code: int = 2) -> NoReturn:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def parse_args() -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="explicit tiny CPU rehearsal; never a measurement")
    p.add_argument("--inject-fault", choices=("reply", "row"), default=None,
                   help="corrupt the reference (proves correct turns false)")
    p.add_argument("--keep-trace", default=None,
                   help="copy the traced run's .xplane.pb into this directory")
    return p.parse_args()


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def load_json(kind: str, name: str, rehearse: bool) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    if not os.path.isfile(path):
        fail(f"no file {path}")
    with open(path) as f:
        d = json.load(f)
    over = d.pop("rehearse", {})
    return merged(d, over) if rehearse else d


def layer_metrics_for(cell: str) -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            m = json.load(f)
        if m["cells"] == "all" or cell in m["cells"]:
            out.append(m)
    return out


class CompileMeter:
    """Backend compiles and persistent-cache hits from jax's own monitoring
    events (a cache hit still passes through the compile event, so cold
    and cached runs count the same thing). Copied from chip_smoke.py, with
    the instant of each compile kept so the window's can be counted."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.seconds = 0.0
        self.at: list[float] = []
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.at.append(time.monotonic())

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.at)


def place_compile_cache(rehearse: bool) -> str | None:
    """Where jax keeps compiled programs: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed ``<checkout>/.jax_cache`` (the program's own
    default, so it takes the one given); every program is kept either way
    (the engine's per-bucket kernels compile in well under jax's default
    one-second floor). The CPU rehearsal keeps none unless told to."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed and rehearse:
        return None
    if not placed:
        placed = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

class Child:
    """One load generator: a ``subprocess.Popen`` talked to in JSON lines
    (started before this process touches jax, so it imports meanwhile)."""

    def __init__(self, proc, out_path: str, idx: int) -> None:
        self.proc, self.out, self.idx = proc, out_path, idx

    async def expect(self, state: str) -> dict:
        loop = asyncio.get_running_loop()
        try:
            line = await asyncio.wait_for(
                loop.run_in_executor(None, self.proc.stdout.readline),
                CHILD_LIMIT_S)
        except asyncio.TimeoutError:
            self.proc.kill()  # unblocks the reader thread
            raise RuntimeError(f"load generator {self.idx} silent for "
                               f"{CHILD_LIMIT_S:.0f} s waiting for {state!r}")
        if not line:
            raise RuntimeError(f"load generator {self.idx} exited before "
                               f"{state!r} (code {self.proc.poll()})")
        msg = json.loads(line)
        if msg.get("state") != state:
            raise RuntimeError(f"load generator {self.idx}: wanted {state!r},"
                               f" got {msg}")
        return msg

    def tell(self, obj: dict) -> None:
        self.proc.stdin.write((json.dumps(obj) + "\n").encode())
        self.proc.stdin.flush()


def spawn_children(wl: dict, cfg: dict, args, tmp: str) -> list[Child]:
    n = wl["client_procs"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)  # a child compiles nothing
    children = []
    for i in range(n):
        out = os.path.join(tmp, f"child{i}.npz")
        spec = {
            "root": ROOT, "workload": wl, "config": cfg, "child": i,
            "n_children": n, "seed": args.seed, "seconds": args.seconds,
            # caller g belongs to child g mod n: shares differ by at most 1
            "callers": list(range(i, wl["callers"], n)),
            "out": out, "fault": args.inject_fault if i == 0 else None,
        }
        spec_path = os.path.join(tmp, f"child{i}.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"), spec_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env)
        children.append(Child(proc, out, i))
    return children


def reap(children: list[Child]) -> None:
    """Stop every child and wait until each has ended."""
    for c in children:
        if c.proc.poll() is None:
            c.proc.kill()
    for c in children:
        c.proc.wait()
        c.proc.stdin.close()
        c.proc.stdout.close()


def load_child(path: str) -> dict:
    with np.load(path, allow_pickle=False) as z:
        d = {k: z[k] for k in z.files}
    d["extra"] = json.loads(str(d["extra"]))
    return d


# ---------------------------------------------------------------------------
# the silo side
# ---------------------------------------------------------------------------

def snapshot_stats(silo) -> dict:
    rt = silo.vector
    counters = dict(silo.stats.counters)
    counters["rt.ticks"] = rt.ticks
    counters["rt.conflicts_deferred"] = rt.conflicts_deferred
    counters["rt.messages_processed"] = rt.messages_processed
    return {"counters": counters,
            "histograms": {k: {"count": h.total, "sum": h.sum}
                           for k, h in silo.stats.histograms.items()},
            "cpu_s": time.process_time(), "at": time.monotonic()}


def stats_delta(a: dict, b: dict) -> dict:
    zero = {"count": 0, "sum": 0.0}
    ha = a["histograms"]
    return {
        "counters": {k: v - a["counters"].get(k, 0)
                     for k, v in b["counters"].items()},
        "histograms": {k: {f: h[f] - ha.get(k, zero)[f] for f in zero}
                       for k, h in b["histograms"].items()},
        "cpu_s": b["cpu_s"] - a["cpu_s"], "seconds": b["at"] - a["at"]}


async def warm_buckets(rt, cls, warm: dict) -> list[int]:
    """Compile every power-of-two tick bucket the cell can meet, from
    MIN_BUCKET to the bucket of its in-flight calls, deterministically:
    one tick of exactly B neutral calls to B distinct dense keys each."""
    top = max(MIN_BUCKET, 1 << (warm["max_tick_calls"] - 1).bit_length())
    buckets = [1 << i for i in range(MIN_BUCKET.bit_length() - 1,
                                     top.bit_length())]
    for b in buckets:
        futs = [rt.call(cls, k, warm["method"], **warm["args"])
                for k in range(b)]
        await rt.flush()
        await asyncio.gather(*futs)
    return buckets


def warm_flush_gather(silo, rt) -> list[int]:
    """Compile the write-behind flush's device→host gather (one program a
    table and power-of-two row bucket) for every bucket up to
    ``MAX_FLUSH_ROWS``, over twice the widest pass a cell makes today (6.1k
    rows): a pass wider than any the warm-up's traffic happened to make
    compiles inside the window otherwise. The bridge's own gather, of row
    (0, 0) as its padding reads it; nothing is written or marked."""
    if not getattr(silo, "vector_bridges", None):
        return []
    buckets = [1 << i for i in range(MIN_BUCKET.bit_length() - 1,
                                     MAX_FLUSH_ROWS.bit_length())]
    for cls, bridge in silo.vector_bridges.items():
        tbl = rt.table(cls)
        for b in buckets:
            at = np.zeros(b, np.int32)
            with rt.tick_fence():
                bridge._gather(tbl, at, at)
    return buckets


async def settle_flusher(silo, period: float, limit: float = 20.0) -> None:
    """Wait until the write-behind flusher has drained the warm-up's writes:
    its counter unchanged over two flush periods."""
    deadline = time.monotonic() + limit
    last, same = -1, 0
    while time.monotonic() < deadline and same < 2:
        await asyncio.sleep(period)
        now = silo.stats.get("vector.storage.flushed")
        same = same + 1 if now == last else 0
        last = now


async def trace_slice(silo, t0: float, seconds: float, trace_dir: str) -> dict:
    """Profile a slice in the middle of the window; returns the slice's
    counter deltas and its length on the host clock."""
    import jax

    length = min(TRACE_SLICE_S, seconds / 2.0)
    loop = asyncio.get_running_loop()
    await asyncio.sleep(max(0.0, t0 + (seconds - length) / 2.0
                            - time.monotonic()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    await loop.run_in_executor(
        None, lambda: jax.profiler.start_trace(trace_dir,
                                               profiler_options=opts))
    a = snapshot_stats(silo)
    await asyncio.sleep(length)
    b = snapshot_stats(silo)
    await loop.run_in_executor(None, jax.profiler.stop_trace)
    return stats_delta(a, b)


def key_hashes_of(rt, cls, child: dict) -> np.ndarray:
    """Each reported key's table hash: a small int key is its own hash, a
    string key's is the framework's GrainId hash (identity, not behaviour
    under test)."""
    from orleans_tpu.core.ids import GrainId, GrainType

    gtype = GrainType.of(cls.__name__)
    kh = child["key.int"].copy()
    for i in np.flatnonzero(child["key.is_str"]):
        key = str(child["key.str"][i])
        kh[i] = rt.key_hash_for(key,
                                GrainId.for_grain(gtype, key).uniform_hash)
    return kh


def compare_rows(rt, cls, ref_mod, results: list[dict], fault: str | None
                 ) -> tuple[dict, list]:
    """Every touched row of the device table against the children's
    expected states (one snapshot under the tick fence, then a few rows
    again through ``read_row``). Returns the record and, per child, the
    (key_hashes, expected states) the storage comparison reuses."""
    tbl = rt.table(cls)
    snap = tbl.snapshot()
    per = max(tbl.dense_per_shard, 1)
    rec = {"rows": 0, "bad_rows": 0, "excluded": 0, "read_row": 0}
    kept = []
    for child in results:
        kh = key_hashes_of(rt, cls, child)
        want = ref_mod.derive(
            {k[len("state."):]: v for k, v in child.items()
             if k.startswith("state.")}, kh)
        if fault == "row" and len(kh):
            f0 = ref_mod.FIELDS[0]
            want[f0] = want[f0].copy()
            want[f0][0] += 1
            fault = None
        dense = (kh >= 0) & (kh < tbl.dense_n)
        shard, slot = np.where(dense, kh // per, 0), np.where(dense, kh % per,
                                                              0)
        missing = np.zeros(len(kh), bool)
        for i in np.flatnonzero(~dense):
            if (loc := tbl.lookup(int(kh[i]))) is not None:
                shard[i], slot[i] = loc
            else:
                missing[i] = True
        bad = missing.copy()
        for f, w in want.items():
            got = snap[f][shard, slot]
            diff = got != w
            bad |= diff.reshape(len(kh), -1).any(axis=1)
        live = ~child["key.excluded"]
        rec["rows"] += int(live.sum())
        rec["excluded"] += int((~live).sum())
        rec["bad_rows"] += int((bad & live).sum())
        for i in np.flatnonzero(live)[:8]:   # the public per-row read too
            row = tbl.read_row(int(kh[i]))
            rec["read_row"] += 1
            if row is None or any(
                    not np.array_equal(np.asarray(row[f]), w[i])
                    for f, w in want.items()):
                rec["bad_rows"] += 1
        kept.append((kh[live], {f: w[live] for f, w in want.items()}))
    return rec, kept


async def compare_storage(storage, cls, kept: list, period: float) -> dict:
    """Every acknowledged key's stored state against its acknowledged
    value, within a few flush periods of the last reply."""
    from orleans_tpu.core.ids import GrainId, GrainType

    gtype = GrainType.of(cls.__name__)
    todo = [(int(k), {f: w[i] for f, w in want.items()})
            for kh, want in kept for i, k in enumerate(kh.tolist())]
    n, t0 = len(todo), time.monotonic()
    deadline = t0 + max(20 * period, 10.0)
    while True:
        bad = []
        for k, want in todo:
            state, _etag = await storage.read(
                cls.__name__, GrainId.for_grain(gtype, k))
            if state is None or any(
                    not np.array_equal(np.asarray(state[f]), w)
                    for f, w in want.items()):
                bad.append((k, want))
        todo = bad
        if not todo or time.monotonic() > deadline:
            break
        await asyncio.sleep(period)
    return {"acknowledged_keys": n, "not_readable": len(todo),
            "seconds": time.monotonic() - t0}


def verdict(cols: dict, wrong_in_warm_up: int, ok_calls: int, rows: dict,
            stored: dict | None, rows_grown: int) -> tuple[bool, dict]:
    """``correct``, and each number it was decided from beside its limit
    (every comparison is exact: the limit is 0). Every reply the children
    judged counts, whenever it came and whatever record it was for."""
    compared = {
        "wrong_replies": int(cols["wrong"].sum()) + wrong_in_warm_up,
        "bad_rows": rows["bad_rows"],
        "stored_not_readable": stored["not_readable"] if stored else 0,
        "table_rows_grown": rows_grown,
        "windows_without_a_right_answer": int(ok_calls <= 0),
    }
    return (not any(compared.values()),
            {k: {"value": v, "limit": 0} for k, v in compared.items()})


def percentile(vals: np.ndarray, q: float) -> float | None:
    return float(np.percentile(vals, q)) if len(vals) else None


def client_numbers(cols: dict, t0: float, seconds: float) -> dict:
    """What the clients saw, from the children's per-request records
    (``loadgen.Records``' columns, merged). A request counts where it
    completed inside the window; a latency sample is one that neither
    failed nor was wrong. ``latency_ms`` is over all of them;
    ``latency_ms_cold`` and ``latency_ms_hot`` are the same samples apart,
    by whether the traffic kind aimed the request at a hot record (a kind
    that marks none has every sample cold and the first two equal)."""
    inside = (cols["done"] >= t0) & (cols["done"] <= t0 + seconds)
    sample = inside & (cols["failed"] == 0) & (cols["wrong"] == 0)
    hot = cols["hot"] > 0
    ms = (cols["done"] - cols["due"]) * 1e3
    late = (cols["send"] - cols["due"])[inside] * 1e3
    n, n_hot = int(inside.sum()), int((inside & hot).sum())

    def percentiles_of(mask: np.ndarray, qs: tuple) -> dict:
        return {q: percentile(ms[mask], float(q)) for q in qs}

    return {
        "requests_in_window": n,
        "requests_in_flight_at_end": int((~inside).sum()),
        "ok_calls": int(cols["ok"][inside].sum()),
        "failed_calls": int(cols["failed"][inside].sum()),
        "wrong_calls": int(cols["wrong"][inside].sum()),
        "hot_requests": n_hot,
        "hot_share_pct": 100.0 * n_hot / n if n else None,
        "latency_samples": int(sample.sum()),
        "latency_ms": percentiles_of(sample, QUANTILES + ("100",)),
        "latency_ms_cold": percentiles_of(sample & ~hot, QUANTILES),
        "latency_ms_hot": percentiles_of(sample & hot, QUANTILES),
        "generator_lateness_ms": {
            "mean": float(late.mean()) if len(late) else None,
            "p95": percentile(late, 95.0),
            "max": float(late.max()) if len(late) else None},
        "ok_calls_by_second": np.histogram(
            cols["done"][inside] - t0, bins=max(1, int(seconds)),
            range=(0.0, seconds),
            weights=cols["ok"][inside])[0].astype(int).tolist()}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

async def serve(args, wl: dict, cfg: dict, device: dict, meter: CompileMeter,
                tmp: str, children: list[Child]) -> tuple[dict, bool]:
    import jax

    from orleans_tpu.dispatch import add_vector_grains
    from orleans_tpu.membership import FileMembershipTable, join_cluster
    from orleans_tpu.runtime import SiloBuilder, SocketFabric
    from orleans_tpu.storage import MemoryStorage

    import kernel_bytes
    import peaks
    import trace_reduce

    app = load_by_name("apps", cfg["app"])
    ref_mod = load_by_name("references", cfg["reference"])
    classes = {g["class"]: app.GRAINS[g["class"]] for g in cfg["grains"]}
    cls = classes[wl["params"]["grain"]]
    period = cfg["storage"].get("flush_period", 0.25)
    storage = MemoryStorage() if cfg["storage"]["kind"] == "memory" else None
    if cfg["storage"]["kind"] not in ("memory", "none"):
        fail(f"unknown storage kind {cfg['storage']['kind']!r}")

    b = SiloBuilder().with_name("chipbench").with_fabric(SocketFabric())
    if args.trace:
        b = b.with_config(metrics_enabled=True)
    add_vector_grains(
        b, *classes.values(),
        dense={classes[g["class"]]: g["dense"] for g in cfg["grains"]
               if g.get("dense")},
        capacity_per_shard=cfg["capacity_per_shard"],
        **({"storage": storage, "flush_period": period} if storage else {}))
    silo = b.build()
    join_cluster(silo, FileMembershipTable(os.path.join(tmp, "mbr.json")))
    await silo.start()
    rt = silo.vector
    tbl = rt.table(cls)
    capacity0 = tbl.capacity
    try:
        buckets = await warm_buckets(rt, classes[wl["warm"]["grain"]],
                                     wl["warm"])
        flush_buckets = warm_flush_gather(silo, rt)
        about = []
        for c in children:
            built = await c.expect("built")
            if not built["codec"].startswith("native"):
                raise RuntimeError(f"load generator {c.idx} runs the wire "
                                   f"codec {built['codec']!r}")
            about.append(built["traffic"])
            c.tell({"endpoint": silo.gateway_endpoint})
        if any(a != about[0] for a in about):
            raise RuntimeError(f"the load generators disagree about the "
                               f"traffic: {about}")
        warm = [(await c.expect("ready"))["warm"] for c in children]
        if storage is not None:
            await settle_flusher(silo, period)
        emit({"phase": "set-up", "tick_buckets_warmed": buckets,
              "flush_buckets_warmed": flush_buckets, "traffic": about[0],
              "warm_up_calls": [int(sum(w[0] for w in warm)),
                                int(sum(w[1] for w in warm)),
                                int(sum(w[2] for w in warm))],
              "compile_seconds": meter.seconds,
              "compile_cache": {"hits": meter.hits, "misses": meter.misses},
              "table_capacity": capacity0})

        # ---- the window --------------------------------------------------
        t0 = time.monotonic() + 0.25
        for c in children:
            c.tell({"t0": t0})
        setup_s = t0 - T_COMMAND
        tracer = None
        if args.trace:
            tracer = asyncio.ensure_future(trace_slice(
                silo, t0, args.seconds, os.path.join(tmp, "trace")))
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        s0, c0 = snapshot_stats(silo), (meter.hits, meter.misses,
                                        meter.seconds)
        await asyncio.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
        s1, c1 = snapshot_stats(silo), (meter.hits, meter.misses,
                                        meter.seconds)
        window = stats_delta(s0, s1)
        compiles_in_window = meter.between(t0, t0 + args.seconds)
        emit({"phase": "window", "compiles": compiles_in_window,
              "compile_seconds": c1[2] - c0[2],
              "compile_cache": {"hits": c1[0] - c0[0],
                                "misses": c1[1] - c0[1]},
              "ticks": window["counters"]["rt.ticks"],
              "flushed_rows": window["counters"].get(
                  "vector.storage.flushed", 0),
              "silo_cpu_per_wall": window["cpu_s"] / window["seconds"]})
        sl = await tracer if tracer is not None else None
        for c in children:
            await c.expect("done")
            c.proc.wait(CHILD_LIMIT_S)
        results = [load_child(c.out) for c in children]

        # ---- correct? ----------------------------------------------------
        await rt.flush()
        rows, kept = compare_rows(rt, cls, ref_mod, results,
                                  args.inject_fault)
        stored = await compare_storage(storage, cls, kept, period) \
            if storage is not None else None
        mem = jax.devices()[0].memory_stats() or {}
    finally:
        await silo.stop()

    # ---- the clients' numbers ---------------------------------------------
    cols = {c: np.concatenate([r[f"rec.{c}"] for r in results])
            for c in Records.COLS}
    cl = client_numbers(cols, t0, args.seconds)
    ok = cl["ok_calls"]
    unsent = sum(r["extra"].get("unsent", 0) for r in results)
    failed_warm = sum(r["extra"]["warm"][1] for r in results)
    attempted = ok + cl["wrong_calls"] + cl["failed_calls"] + unsent
    failed = cl["wrong_calls"] + cl["failed_calls"] + unsent
    correct, compared = verdict(
        cols, sum(r["extra"]["warm"][2] for r in results), ok, rows, stored,
        tbl.capacity - capacity0)

    emit({"phase": "clients", "loop": wl["loop"], **cl,
          "unsent_at_end": unsent, "failed_in_warm_up": failed_warm,
          "per_child": [{"requests": int(len(r["rec.done"])),
                         "cpu_s": r["extra"]["cpu_s"]} for r in results]})
    emit({"phase": "correct",
          "wrong_replies": compared["wrong_replies"]["value"], "rows": rows,
          "storage": stored, "table_grew": tbl.capacity != capacity0})

    # every request of the window counts in each of these, the ones aimed
    # at a hot record included: the populations apart are the clients'
    # line's and the per-layer metrics'
    e2e = {
        "calls_per_s": (ok / args.seconds / cfg["chips"], "calls/s"),
        "latency_p50_ms": (cl["latency_ms"]["50"], "ms"),
        "latency_p95_ms": (cl["latency_ms"]["95"], "ms"),
        "setup_s": (setup_s, "s"),
    }
    for name in wl.get("end_to_end_left_out", ()):  # the cell's file says
        del e2e[name]
    dev = dict(device, memory_peak_bytes=int(
        mem.get("peak_bytes_in_use", mem.get("bytes_in_use", 0))))
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed}
    if not args.trace:
        out["metrics"] = {k: {"value": v, "unit": u}
                          for k, (v, u) in e2e.items()}
        out["device"] = dev
        out["compared"] = compared
        return out, correct

    # ---- the layers' numbers (traced run) -----------------------------------
    emit({"phase": "end-to-end (traced run, not the record)",
          **{k: v for k, (v, _u) in e2e.items()}})
    reduced = None
    xplane = trace_reduce.find_xplane(os.path.join(tmp, "trace"))
    if xplane is not None:
        reduced = trace_reduce.reduce(
            trace_reduce.load(xplane), sl["seconds"],
            cpu_fallback=device["platform"] == "cpu")
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(xplane, os.path.join(
                args.keep_trace, f"{wl['name']}.xplane.pb"))
    if reduced is None:
        fail("the traced run saw no operation on the device", 1)
    ctx = {
        "seconds": args.seconds,
        "counters": window["counters"], "histograms": window["histograms"],
        "slice": sl, "trace": reduced, "clients": cl,
        "cpu": {"silo": [{"cpu_s": window["cpu_s"],
                          "wall_s": window["seconds"]}],
                "clients": [{"cpu_s": r["extra"]["cpu_s"],
                             "wall_s": args.seconds} for r in results]},
        "compiles": {"in_window": compiles_in_window},
        "bytes_per_message": kernel_bytes.tick_bytes_per_message(
            classes[wl["warm"]["grain"]], wl["warm"]["method"]),
        "peaks": peaks.device_peaks(device["platform"], device["kind"]),
    }
    metrics = {}
    for m in layer_metrics_for(wl["name"]):
        v = load_by_name("readers", m["reader"]).read(ctx, **m["args"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    emit({"phase": "trace", "slice_s": sl["seconds"],
          "idle_share": reduced["idle_share"], "ops": reduced["ops"],
          "longest_gap_s": reduced["longest_gap_s"],
          "slice_counters": {k: v for k, v in sl["counters"].items() if v},
          "bytes_per_message": ctx["bytes_per_message"],
          "window_counters": {k: v for k, v in window["counters"].items()
                              if v}})
    out["metrics"] = metrics
    out["device"] = dict(dev, busy_s=reduced["busy_s"],
                         window_s=reduced["window_s"])
    out["breakdown"] = {"device_ops": reduced["device_ops"],
                        "idle_gaps": reduced["idle_gaps"]}
    out["compared"] = compared
    return out, correct


def main() -> int:
    args = parse_args()
    rehearse = args.rehearse_cpu
    wl = load_json("workloads", args.workload, rehearse)
    cfg = load_json("configs", wl["config"], rehearse)
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is first imported
    sys.path.insert(0, ROOT)
    from orleans_tpu import native

    codec = native.wire_codec()  # builds the C extensions before any child
    native.load("_hotloop")
    if not codec.startswith("native"):
        fail(f"the wire codec is {codec!r}; the Python fallback is not the "
             f"served path")
    tmp = tempfile.mkdtemp(prefix="chipbench-")
    children = spawn_children(wl, cfg, args, tmp)  # they import meanwhile
    try:
        return measure(args, wl, cfg, codec, tmp, children)
    finally:
        reap(children)
        shutil.rmtree(tmp, ignore_errors=True)


def measure(args, wl: dict, cfg: dict, codec: str, tmp: str,
            children: list[Child]) -> int:
    rehearse = args.rehearse_cpu
    import jax

    cache_dir = place_compile_cache(rehearse)
    meter = CompileMeter()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit({"phase": "environment", "argv": sys.argv[1:], "device": device,
          "wire_codec": codec, "jax": jax.__version__,
          "compile_cache": {"dir": cache_dir, "placed_by_env": bool(
              os.environ.get("JAX_COMPILATION_CACHE_DIR"))},
          "workload": wl, "config": {k: cfg[k] for k in (
              "name", "app", "grains", "capacity_per_shard", "population",
              "storage")}})
    if device["platform"] != "tpu" and not rehearse:
        fail(f"jax found no TPU (platform {device['platform']!r})")
    if len(devs) < cfg["chips"]:
        fail(f"the cell asks for {cfg['chips']} chips, jax reports "
             f"{len(devs)}")
    out, correct = asyncio.run(
        serve(args, wl, cfg, device, meter, tmp, children))
    print(json.dumps(out), flush=True)
    for name, c in out["compared"].items():
        print(f"chipbench: compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())
