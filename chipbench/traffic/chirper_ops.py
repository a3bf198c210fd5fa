"""Traffic kind ``chirper_ops``: a social feed's two operations, one at a
time. Each caller awaits one call a request: with probability
``publish_proportion`` ``grain(k).publish(chirp)`` — ``k`` uniform over
the accounts the caller owns, ``chirp`` 280 bytes of text under its
header, sent as ``bytes`` — else ``grain(k).get_received(read_n)``, ``k``
uniform over the accounts of the caller's process. Keys are uniform:
nothing is hot, and the kind has no ``hot_of``.

**Who owns what.** The accounts are dealt in blocks: client process ``c``
of ``n`` has accounts ``[c * N / n, (c + 1) * N / n)`` (with four
processes and four chips a process's accounts are one shard's), and its
callers split that block evenly. An account has one owning caller, so one
author's publishes are never concurrent and the author's sequence numbers
are the caller's own count. A process reads only its own block. The
followers of an author lie anywhere (``references/chirper.py``'s graph).

**Judging a publish**: the reply is the author's number of followers. A
failed or timed-out publish takes its author and every follower of it out
of the row comparison (nobody knows what landed).

**Judging a read.** The text of a chirp follows from its header and the
run's seed (``chirp_text``), so a reader can hold every entry to its
bytes whoever published it: each entry is a whole chirp of an account the
reader follows, byte for byte; one author's entries stand newest first
with consecutive sequence numbers (no gap, no repeat); the entries are
``min(n_received, read_n)`` and zeros follow; ``n_received`` is at least
the deliveries this process's own acknowledged publishes made to the
reader before the read was sent, and such a publish's chirp (or a newer
one of its author) is among the entries unless ``read_n`` newer ones
stand there; an entry of one of this process's authors carries a sequence
number that author has sent. What other processes' authors published by
then is not known inside a process (the clocks of the load generators are
one host's monotonic clock, but the order of two processes' records says
nothing the reply does not): the totals are held exactly after the
window, when the parent compares every touched row with every process's
log (``references/chirper.py`` ``derive``).

**Warm-up** (set-up, not window). The harness compiles the tick buckets
of ``publish`` with the neutral chirp, and with them the exchange's and
the apply rounds' programs; this brings ``get_received``'s in as
``ycsb_ops`` brings ``read``'s: bursts of ``b`` reads of ``b`` distinct
own accounts in one ``call_batch``, twice, for every power of two up to
the process's callers, and process 0 floods the cell's callers' worth.
Then every caller starts its loop of the real mix (the pre-roll, as in
``ycsb_ops``: a closed loop of single calls settles over tens of seconds)
and ``warm_up`` returns once each has completed ``warm_ops`` operations;
the window's first request of a caller is the operation it has in flight.
A failed or wrong call by then raises: the run ends before its window.

``states`` reports the keys this process's publishes touched (authors and
followers), its log of acknowledged publishes, the graph's parameters and
the run's directory (where the children's result files lie side by side:
the directory of the spec file this process was started with).

Parameters (the workload file's ``params``): ``grain``,
``publish_proportion``, ``read_n``, ``warm_ops``.
"""

import asyncio
import os
import sys

import numpy as np

BLOCK = 1024   # operations drawn at a time, per caller


class Traffic:
    def __init__(self, ctx: dict):
        p, cfg = ctx["params"], ctx["config"]
        self.cls = ctx["grains"][p["grain"]]
        self.mod = ctx["reference"]
        self.accounts = cfg["grains"][0]["dense"]
        self.table = np.asarray(cfg["graph"]["degree_table"], np.int32)
        self.ref = self.mod.Reference(cfg["data_seed"], self.accounts,
                                      self.table)
        self.data_seed, self.run_seed = cfg["data_seed"], ctx["seed"]
        self.timeout = ctx["response_timeout"]
        self.fault = ctx.get("fault")
        self.warming = False
        self.child, n = ctx["child"], ctx["n_children"]
        self.total_callers = ctx["n_callers"]
        self.publish_proportion = p["publish_proportion"]
        self.read_n, self.warm_ops = p["read_n"], p["warm_ops"]
        # this process's block of accounts, and each caller's share of it
        self.lo = self.child * self.accounts // n
        self.hi = (self.child + 1) * self.accounts // n
        m = max(len(ctx["callers"]), 1)
        self.span = (self.hi - self.lo) // m
        if self.span < 1:
            raise ValueError(f"{m} callers over {self.hi - self.lo} accounts")
        self.rngs = [np.random.default_rng([ctx["seed"], g])
                     for g in ctx["callers"]]
        self.blocks: list = [[] for _ in self.rngs]
        self.grains: dict = {}
        self.sent: dict = {}       # author -> publishes sent (its seq)
        self.acked: dict = {}      # author -> publishes acknowledged
        self.failed_authors: set = set()
        # own block's readers: account -> [(author, seq)] acknowledged
        self.own: dict = {}
        self.preroll: dict = {}
        self.unreported = np.zeros((len(self.rngs), 3), np.int64)
        self.to_warm = len(self.rngs)
        self.warmed = asyncio.Event()

    calls_per_request = 1

    @property
    def n_callers(self) -> int:
        return len(self.rngs)

    def about(self) -> dict:
        """What of the traffic follows from the data files alone."""
        return {"accounts": int(self.accounts),
                "mean_followers": float(self.table.mean()),
                "follower_cap": int(self.mod.FOLLOW_CAP),
                "publish_proportion": self.publish_proportion}

    def _draw(self, slot: int) -> list:
        """BLOCK operations of one caller: (is_publish, key)."""
        rng = self.rngs[slot]
        pub = rng.random(BLOCK) < self.publish_proportion
        mine = self.lo + slot * self.span + rng.integers(0, self.span, BLOCK)
        any_ = rng.integers(self.lo, self.hi, BLOCK)
        return list(zip(pub.tolist(), np.where(pub, mine, any_).tolist()))

    def _grain(self, client, key: int):
        g = self.grains.get(key)
        if g is None:
            g = self.grains[key] = client.get_grain(self.cls, key)
        return g

    # -- judging -------------------------------------------------------------
    def _sending_read(self, key: int) -> tuple:
        """What this process knows the reader has to show: the number of
        deliveries its own acknowledged publishes made, and each own
        author's newest acknowledged sequence number."""
        mine = self.own.get(key, ())
        newest: dict = {}
        for a, s in mine:
            newest[a] = s
        return len(mine), newest

    def _judge_read(self, key: int, r, floor: tuple) -> tuple[int, int, int]:
        n_recv, data = int(r[0]), np.asarray(r[1]).tobytes()
        if self.fault == "reply" and not self.warming:
            data, self.fault = bytes([data[0] ^ 1]) + data[1:], None
        return (0, 0, 1) if self._read_is_wrong(key, n_recv, data, floor) \
            else (1, 0, 0)

    def _read_is_wrong(self, key: int, n_recv: int, data: bytes,
                       floor: tuple) -> bool:
        mod = self.mod
        floor_n, newest = floor
        shown = min(n_recv, self.read_n, mod.READ_N)
        if len(data) != mod.READ_N * mod.CHIRP_BYTES or n_recv < floor_n \
                or any(data[shown * mod.CHIRP_BYTES:]):
            return True
        prev: dict = {}    # author -> its entry last seen, newest first
        first: dict = {}   # author -> its newest entry shown
        for e in mod.split_chirps(data, shown):
            author, seq, length = mod.header_of(e)
            if not 0 <= author < self.accounts or seq < 1 \
                    or length != mod.TEXT_BYTES \
                    or key not in self.ref.follower_keys(author) \
                    or e != mod.make_chirp(author, seq, mod.chirp_text(
                        self.run_seed, author, seq)):
                return True
            if self.lo <= author < self.hi and seq > self.sent.get(author, 0):
                return True   # one of ours, and never sent
            if author in prev and seq != prev[author] - 1:
                return True   # a gap, a repeat or out of order
            prev[author] = seq
            first.setdefault(author, seq)
        full = shown == min(self.read_n, mod.READ_N)
        for author, seq in newest.items():
            # acknowledged before the read left: it, or a newer one of its
            # author, is shown unless the reply is full of newer entries
            if first.get(author, seq if full else 0) < seq:
                return True
        return False

    async def _burst(self, client, b: int) -> np.ndarray:
        """``b`` reads of ``b`` distinct own accounts in one call_batch."""
        keys = (self.lo + np.arange(b) * max(1, (self.hi - self.lo) // b)
                ).tolist()
        floors = [self._sending_read(k) for k in keys]
        futs = client.call_batch(
            self.cls, "get_received",
            [(k, {"n": self.read_n}) for k in keys], timeout=self.timeout)
        tot = np.zeros(3, np.int64)
        for k, floor, r in zip(keys, floors, await asyncio.gather(
                *futs, return_exceptions=True)):
            if isinstance(r, asyncio.CancelledError):
                raise r
            tot += (0, 1, 0) if isinstance(r, BaseException) \
                else self._judge_read(k, r, floor)
        return tot

    async def _caller(self, client, slot: int) -> tuple:
        """One caller's loop from the warm-up on, until the window's first
        request takes its slot (``ycsb_ops``' pre-roll)."""
        acc, done = self.unreported[slot], 0
        while True:
            last = await self._operation(client, slot)
            acc += last
            done += 1
            if done == self.warm_ops:
                self.to_warm -= 1
                if not self.to_warm:
                    self.warmed.set()
            if slot not in self.preroll:
                return last[0], int(acc[1]), int(acc[2])

    async def warm_up(self, client) -> tuple[int, int, int]:
        self.warming = True
        tot = np.zeros(3, np.int64)
        b = 2
        while b <= min(max(self.n_callers, 2), self.hi - self.lo):
            for _ in range(2):
                tot += await self._burst(client, b)
            b *= 2
        if self.child == 0:
            for flood in (1, 1, 2):
                tot += await self._burst(client, min(
                    flood * self.total_callers, self.hi - self.lo))
        for s in range(self.n_callers):
            self.preroll[s] = asyncio.ensure_future(self._caller(client, s))
        await self.warmed.wait()
        tot += self.unreported.sum(axis=0)
        self.unreported[:] = 0
        if tot[1] or tot[2]:
            for task in self.preroll.values():
                task.cancel()
            raise RuntimeError(f"warm-up: {tot[1]} calls failed, "
                               f"{tot[2]} replies wrong")
        return int(tot[0]), int(tot[1]), int(tot[2])

    async def request(self, client, slot: int) -> tuple[int, int, int]:
        task = self.preroll.pop(slot, None)
        if task is not None:
            self.warming = False
            return await task
        return await self._operation(client, slot)

    async def _operation(self, client, slot: int) -> tuple[int, int, int]:
        block = self.blocks[slot]
        if not block:
            block.extend(self._draw(slot))
        is_publish, key = block.pop()
        grain = self._grain(client, key)
        if not is_publish:
            floor = self._sending_read(key)
            try:
                r = await grain.get_received(n=self.read_n)
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — failed or timed out
                return 0, 1, 0
            return self._judge_read(key, r, floor)
        seq = self.sent[key] = self.sent.get(key, 0) + 1
        chirp = self.mod.make_chirp(
            key, seq, self.mod.chirp_text(self.run_seed, key, seq))
        try:
            r = await grain.publish(chirp=chirp)
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — nobody knows what landed
            self.failed_authors.add(key)
            return 0, 1, 0
        followers = self.ref.follower_keys(key)
        self.acked[key] = self.acked.get(key, 0) + 1
        for f in followers:
            if self.lo <= f < self.hi:
                self.own.setdefault(f, []).append((key, seq))
        return (1, 0, 0) if int(r) == len(followers) else (0, 0, 1)

    def states(self):
        authors = sorted(self.sent)
        touched = set(authors)
        for a in authors:
            touched.update(self.ref.follower_keys(a))
        excluded = set(self.failed_authors)
        for a in self.failed_authors:
            excluded.update(self.ref.follower_keys(a))
        spec = sys.argv[1] if len(sys.argv) > 1 else ""
        run_dir = os.path.dirname(os.path.abspath(spec)) \
            if spec.endswith(".json") and os.path.isfile(spec) else ""
        states = {
            "log.author": np.array(authors, np.int64),
            "log.count": np.array([self.acked.get(a, 0) for a in authors],
                                  np.int64),
            "log.run_dir": np.array(run_dir),
            "graph.params": np.array([self.data_seed, self.accounts],
                                     np.int64),
            "graph.table": self.table,
        }
        return sorted(touched), states, excluded
