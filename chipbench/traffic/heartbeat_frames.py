"""Traffic kind ``heartbeat_frames``: each caller is a game server that
sends one client ``call_batch`` of ``frame`` heartbeats and waits for every
reply. A caller owns a disjoint partition of the population (dense int keys
and GUID-like string keys); a frame draws ``dense_per_frame`` +
``hashed_per_frame`` keys without replacement from it, ``pos`` = k/64
(exact in float16) and ``delta`` uniform in ``delta``. Every seed gives the
same sizes; only keys, positions and deltas change.

Parameters (the workload file's ``params``): ``grain``, ``frame``,
``dense_per_frame``, ``hashed_per_frame``, ``delta`` [lo, hi),
``warm_dense_frames``.
"""

import asyncio

import numpy as np

BLOCK = 64  # frames of random draws made at a time


class _Caller:
    def __init__(self, g: int, n_callers: int, dense: int, hashed: int,
                 seed: int, p: dict):
        self.rng = rng = np.random.default_rng([seed, g])
        lo, hi = g * dense // n_callers, (g + 1) * dense // n_callers
        self.dense = rng.permutation(hi - lo) + lo
        n_h = hashed // n_callers
        rs = set()
        while len(rs) < n_h:
            rs.add(int(rng.integers(1 << 62)))
        self.hashed = [f"player-{r:016x}-{g}" for r in sorted(rs)]
        self.hashed = [self.hashed[i] for i in rng.permutation(n_h)]
        self.nd, self.nh = p["dense_per_frame"], p["hashed_per_frame"]
        self.delta = p["delta"]
        self.di = self.hi = 0
        self.block: list = []

    def _draw(self, nd: int, nh: int) -> list:
        """One frame: [(key, {"pos": [x, y], "delta": d}), ...]."""
        n = nd + nh
        if len(self.block) < n:
            m = BLOCK * (self.nd + self.nh)
            pos = (self.rng.integers(0, 1024, size=(m, 2)) / 64.0).tolist()
            delta = self.rng.integers(*self.delta, size=m).tolist()
            self.block = list(zip(pos, delta))
        keys = []
        for _ in range(nd):
            keys.append(int(self.dense[self.di % len(self.dense)]))
            self.di += 1
        for _ in range(nh):
            keys.append(self.hashed[self.hi % len(self.hashed)])
            self.hi += 1
        args, self.block = self.block[-n:], self.block[:-n]
        return [(k, {"pos": p, "delta": d}) for k, (p, d) in zip(keys, args)]

    def frame(self) -> list:
        return self._draw(self.nd, self.nh)


class Traffic:
    def __init__(self, ctx: dict):
        p = ctx["params"]
        self.cls = ctx["grains"][p["grain"]]
        self.ref = ctx["reference"].Reference()
        self.timeout = ctx["response_timeout"]
        self.fault = ctx.get("fault")
        self.excluded: set = set()
        pop = ctx["config"]["population"]
        self.frame = p["frame"]
        if p["dense_per_frame"] + p["hashed_per_frame"] != self.frame:
            raise ValueError("dense_per_frame + hashed_per_frame != frame")
        self.callers = [
            _Caller(g, ctx["n_callers"], pop["dense"], pop["hashed"],
                    ctx["seed"], p) for g in ctx["callers"]]
        self.warm_dense_frames = p["warm_dense_frames"]

    @property
    def n_callers(self) -> int:
        return len(self.callers)

    @property
    def calls_per_request(self) -> int:
        return self.frame

    async def _send(self, client, calls: list) -> tuple[int, int, int]:
        futs = client.call_batch(self.cls, "heartbeat", calls,
                                 timeout=self.timeout)
        got = await asyncio.gather(*futs, return_exceptions=True)
        ok = failed = wrong = 0
        for (key, kw), r in zip(calls, got):
            if isinstance(r, BaseException):
                if isinstance(r, asyncio.CancelledError):
                    raise r
                # the owner cannot know whether the write landed: the key
                # leaves the row and storage comparison from here on
                self.excluded.add(key)
                failed += 1
                continue
            want = self.ref.heartbeat(key, kw["pos"], kw["delta"])
            if self.fault == "reply":
                want, self.fault = want + 1, None
            if key in self.excluded or int(r) == want:
                ok += 1  # an excluded key's later replies cannot be judged
            else:
                wrong += 1
        return ok, failed, wrong

    async def warm_up(self, client) -> tuple[int, int, int]:
        """Set-up, not window: every caller sends each of its string keys
        once (frames of hashed keys only) and a few dense frames."""
        async def one(c: _Caller) -> list:
            out = []
            for _ in range(-(-len(c.hashed) // self.frame)):
                n = min(self.frame, len(c.hashed) - c.hi)
                if n > 0:
                    out.append(await self._send(client, c._draw(0, n)))
            for _ in range(self.warm_dense_frames):
                out.append(await self._send(client, c._draw(self.frame, 0)))
            return out
        res = await asyncio.gather(*(one(c) for c in self.callers))
        tot = np.array([r for rs in res for r in rs] or [(0, 0, 0)]).sum(0)
        return int(tot[0]), int(tot[1]), int(tot[2])

    async def request(self, client, slot: int) -> tuple[int, int, int]:
        return await self._send(client, self.callers[slot].frame())

    def states(self):
        keys, states = self.ref.states()
        return keys, states, self.excluded
