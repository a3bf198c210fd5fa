#!/usr/bin/env python3
"""chipbench load generator — one child process of ``run.py``.

Started with ``JAX_PLATFORMS=cpu`` in its environment (importing
``orleans_tpu.runtime`` imports jax but never initialises a backend; the
variable makes sure this process cannot take the chip). It builds its
share of the cell's traffic from the seed, opens its own ``GatewayClient``
over loopback TCP, warms up, reports ready, takes one start instant from
the parent, and records per request (due, send, reply, calls ok / failed /
wrong, whether the traffic kind aimed it at a hot record) in numpy arrays,
which it writes with its reference's expected states to the file the parent
named.

Protocol: one JSON object per line, parent -> stdin, child -> stdout:
``{"state": "built"}`` <- ``{"endpoint": ...}`` -> ``{"state": "ready"}``
<- ``{"t0": monotonic}`` -> ``{"state": "done"}``.

``loop``: ``closed`` (each caller sends its next request when the last
one is answered) or ``open`` (Poisson arrivals at ``rate`` requests/s over
all children; a request is timed FROM WHEN IT WAS DUE, waits for one of the
``callers`` slots if none is free, and the generator's lateness — send
minus due — is reported).
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_by_name(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class Records:
    """Per-request records in preallocated numpy arrays."""

    COLS = ("due", "send", "done", "ok", "failed", "wrong", "hot")

    def __init__(self, n: int = 1 << 14) -> None:
        self.a = np.zeros((n, len(self.COLS)), np.float64)
        self.n = 0

    def add(self, *row) -> None:
        if self.n == len(self.a):
            self.a = np.concatenate([self.a, np.zeros_like(self.a)])
        self.a[self.n] = row
        self.n += 1

    def columns(self) -> dict:
        return {c: self.a[:self.n, i] for i, c in enumerate(self.COLS)}


async def drive(traffic, client, loop_kind: str, t0: float, seconds: float,
                rate: float | None, seed: int) -> tuple[Records, dict]:
    """Run the cell's loop from ``t0`` for ``seconds``; requests in flight
    at the end are awaited (their effect on state is part of the
    reference) but complete outside the window."""
    rec = Records()
    t_end = t0 + seconds
    cpu: dict = {}
    extra: dict = {}

    hot_of = getattr(traffic, "hot_of", None)  # a kind may mark hot records

    async def stamp_cpu() -> None:
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        cpu["t0"] = time.process_time()
        await asyncio.sleep(max(0.0, t_end - time.monotonic()))
        cpu["t1"] = time.process_time()

    async def issue(slot: int, due: float | None = None) -> None:
        send = time.monotonic()
        due = send if due is None else due  # closed loop: due when sent
        ok, failed, wrong = await traffic.request(client, slot)
        rec.add(due, send, time.monotonic(), ok, failed, wrong,
                hot_of(slot) if hot_of else 0)

    async def closed_caller(slot: int) -> None:
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        while time.monotonic() < t_end:
            await issue(slot)

    stamper = asyncio.ensure_future(stamp_cpu())
    if loop_kind == "closed":
        await asyncio.gather(*(closed_caller(s)
                               for s in range(traffic.n_callers)))
    elif loop_kind == "open":
        if not rate or rate <= 0:
            raise ValueError("an open loop needs a rate > 0")
        rng = np.random.default_rng([seed, 0xA221])
        gaps = rng.exponential(1.0 / rate, size=int(rate * seconds * 2) + 16)
        due = t0 + np.cumsum(gaps)
        due = due[due < t_end]
        q: asyncio.Queue = asyncio.Queue()

        async def arrivals() -> None:
            for d in due:
                await asyncio.sleep(max(0.0, d - time.monotonic()))
                q.put_nowait(float(d))
            for _ in range(traffic.n_callers):
                q.put_nowait(None)

        async def open_caller(slot: int) -> None:
            while (d := await q.get()) is not None:
                if time.monotonic() >= t_end:
                    # due in the window, never sent: missed, not forgotten
                    extra["unsent"] = extra.get("unsent", 0) \
                        + traffic.calls_per_request
                    continue
                await issue(slot, d)

        await asyncio.gather(arrivals(), *(open_caller(s)
                                           for s in range(traffic.n_callers)))
        extra["due"] = int(len(due))
    else:
        raise ValueError(f"loop must be 'closed' or 'open', got {loop_kind!r}")
    await stamper
    extra["cpu_s"] = cpu["t1"] - cpu["t0"]
    return rec, extra


def write_result(path: str, rec: Records, extra: dict, traffic) -> None:
    keys, states, excluded = traffic.states()
    is_str = np.array([isinstance(k, str) for k in keys], bool)
    out = {f"rec.{c}": v for c, v in rec.columns().items()}
    out["key.is_str"] = is_str
    out["key.int"] = np.array([0 if s else k for k, s in zip(keys, is_str)],
                              np.int64)
    out["key.str"] = np.array([k if s else "" for k, s in zip(keys, is_str)],
                              dtype=np.str_)
    out["key.excluded"] = np.array([k in excluded for k in keys], bool)
    for f, v in states.items():
        out[f"state.{f}"] = np.asarray(v)
    out["extra"] = np.array(json.dumps(extra))
    tmp = path + ".tmp.npz"
    np.savez(tmp, **out)
    os.replace(tmp, path)


async def main(spec: dict) -> int:
    sys.path.insert(0, spec["root"])
    from orleans_tpu import native
    from orleans_tpu.runtime import GatewayClient

    wl, cfg = spec["workload"], spec["config"]
    app = load_by_name("apps", cfg["app"])
    ctx = {
        "config": cfg, "workload": wl, "params": wl["params"],
        "grains": app.GRAINS,
        "reference": load_by_name("references", cfg["reference"]),
        "child": spec["child"], "n_children": spec["n_children"],
        "callers": spec["callers"], "n_callers": wl["callers"],
        "seed": spec["seed"], "fault": spec.get("fault"),
        "response_timeout": wl["response_timeout"],
    }
    traffic = load_by_name("traffic", wl["generator"]).Traffic(ctx)
    say({"state": "built", "codec": native.wire_codec(),
         "traffic": getattr(traffic, "about", dict)(),
         "jax_backend_initialised": _backend_initialised()})
    loop = asyncio.get_running_loop()
    msg = json.loads(await loop.run_in_executor(None, sys.stdin.readline))
    client = await GatewayClient(
        [msg["endpoint"]],
        response_timeout=wl["response_timeout"]).connect()
    try:
        warm = await traffic.warm_up(client)
        say({"state": "ready", "warm": warm})
        msg = json.loads(await loop.run_in_executor(None, sys.stdin.readline))
        n_children = spec["n_children"]
        rate = wl.get("rate")
        rec, extra = await drive(
            traffic, client, wl["loop"], msg["t0"], spec["seconds"],
            rate / n_children if rate else None,
            spec["seed"] * 1000 + spec["child"])
        extra["warm"] = warm
    finally:
        await client.close_async()
    write_result(spec["out"], rec, extra, traffic)
    say({"state": "done", "requests": rec.n,
         "jax_backend_initialised": _backend_initialised()})
    return 0


def _backend_initialised() -> bool:
    """True if this process has initialised a jax backend (it must not)."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return bool(xb is not None and getattr(xb, "_backends", None))


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        _spec = json.load(f)
    sys.exit(asyncio.run(main(_spec)))
