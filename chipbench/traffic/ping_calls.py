"""Traffic kind ``ping_calls``: each caller awaits one ``grain(k).ping(x)``
at a time, ``k`` uniform over the dense population, ``x`` a random int32
(upstream's PingBenchmark: one message per request, no batching by the
client). Parameters: ``grain``, ``warm_batch``.
"""

import asyncio

import numpy as np

BLOCK = 4096


class Traffic:
    def __init__(self, ctx: dict):
        p = ctx["params"]
        self.cls = ctx["grains"][p["grain"]]
        self.ref = ctx["reference"].Reference()
        self.timeout = ctx["response_timeout"]
        self.fault = ctx.get("fault")
        self.excluded: set = set()
        self.n_keys = ctx["config"]["population"]["dense"]
        self.child, self.n_children = ctx["child"], ctx["n_children"]
        self.warm_batch = p["warm_batch"]
        self.rngs = [np.random.default_rng([ctx["seed"], g])
                     for g in ctx["callers"]]
        self.blocks: list = [[] for _ in self.rngs]
        self.grains: dict = {}

    calls_per_request = 1

    @property
    def n_callers(self) -> int:
        return len(self.rngs)

    def _check(self, key, x: int, r) -> tuple[int, int, int]:
        want = self.ref.ping(key, x)
        if self.fault == "reply":
            want, self.fault = want + 1, None
        return (1, 0, 0) if int(r) == want else (0, 0, 1)

    async def warm_up(self, client) -> tuple[int, int, int]:
        """Set-up: this child's share of the keys is pinged once, in client
        batches, so every grain is active before the window."""
        keys = list(range(self.child, self.n_keys, self.n_children))
        tot = np.zeros(3, np.int64)
        for i in range(0, len(keys), self.warm_batch):
            part = keys[i:i + self.warm_batch]
            futs = client.call_batch(self.cls, "ping",
                                     [(k, {"x": k}) for k in part],
                                     timeout=self.timeout)
            for k, r in zip(part, await asyncio.gather(
                    *futs, return_exceptions=True)):
                if isinstance(r, asyncio.CancelledError):
                    raise r
                tot += (0, 1, 0) if isinstance(r, BaseException) \
                    else self._check(k, k, r)
        return int(tot[0]), int(tot[1]), int(tot[2])

    async def request(self, client, slot: int) -> tuple[int, int, int]:
        block = self.blocks[slot]
        if not block:
            rng = self.rngs[slot]
            block.extend(zip(
                rng.integers(0, self.n_keys, size=BLOCK).tolist(),
                rng.integers(0, 2**31 - 1, size=BLOCK).tolist()))
        key, x = block.pop()
        grain = self.grains.get(key)
        if grain is None:
            grain = self.grains[key] = client.get_grain(self.cls, key)
        try:
            r = await grain.ping(x=x)
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — a failed or timed-out request
            self.excluded.add(key)
            return 0, 1, 0
        return self._check(key, x, r)

    def states(self):
        keys, states = self.ref.states()
        return keys, states, self.excluded
