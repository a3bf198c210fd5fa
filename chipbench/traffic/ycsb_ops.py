"""Traffic kind ``ycsb_ops``: YCSB's core workload as single operations.
Each caller awaits one operation at a time — ``grain(k).read()`` with
probability ``read_proportion``, else ``grain(k).update(field, value)``
with ``field`` uniform over the record's fields and ``value`` 100 random
bytes sent as ``bytes`` — on a key drawn from YCSB's scrambled Zipfian.

**Keys** are drawn as YCSB draws them (``ScrambledZipfian``, below):
``ZipfianGenerator.nextLong`` over ``ScrambledZipfianGenerator``'s
10,000,000,000 items with its ``ZETAN`` 26.469..., then ``fnvhash64`` of
the rank, mod ``recordcount``. Rank 0 holds 1/ZETAN = 3.78 % of the draws
and rank 1 1.90 %; the ranks beyond the first few million (about 36 % of
the draws) fall nearly evenly over the records. ``zipfian_constant`` must
be YCSB's 0.99: its ``ZETAN`` is that constant's. The harness compares
one expected state per key per client process, so a key needs one owner:
the records are dealt to the ``n_children`` processes so that each holds
the same share of the mass (``key_mass``: the first ``HEAD`` ranks one by
one, the rest evenly; the hottest few thousand records to whoever is
lightest, the tail in a back-and-forth order; an imbalance above
``MASS_TOLERANCE`` is an error), and a process keeps the draws that land
on its own records. With equal rates per process the union is the
source's distribution. Every seed gives the same owners and the same hot
records; operations, fields and values change.

**Hot records.** A record is *hot* where the expected number of the cell's
in-flight operations on it is at least ``hot_in_flight``:
``key_mass()[k] * callers >= hot_in_flight``, ``callers`` the cell's callers
over all processes. The set follows from the source's distribution and the
cell's own traffic alone, never from the program, and is the same for every
seed and every client process (``about()`` reports its size and mass, the
harness prints them in its ``set-up`` record and checks that the children
agree). ``hot_of(slot)`` says whether the operation a caller's ``request``
just returned was aimed at a hot record (the load generator asks right
after the reply; a kind without the method marks nothing). Without
``hot_in_flight`` no record is hot. In a closed loop a hot operation queues behind the
record's other callers (one message an actor a tick and method), so its
latency is the record's queue length over its service rate; the harness
counts it in every end-to-end metric and reports the two populations'
latencies apart beside them.

**Judging.** Replies are judged by ``references/ycsb.py`` through the
``ver`` each carries (see there); a read may not report a ``ver`` below
the highest acknowledged when it was sent. A failed or timed-out update
takes its key out of every comparison from then on; a failed read takes
nothing out.

**Warm-up** (set-up, not window). The harness compiles the tick buckets of
one method (the cell's ``warm.method``, ``update``); this brings ``read``'s
in: a burst of ``b`` reads of ``b`` distinct own keys in one client
``call_batch``, twice, for every power of two up to the process's callers.
A client writes at most 256 messages a socket write and an idle silo ticks
each write as it arrives, so the buckets above 256 are met only when the
silo falls behind: process 0 (the kernels are the silo's, one process is
enough) then floods it with the cell's callers' worth of reads in one
``call_batch``, twice, and twice that once, so that 64 KiB socket reads of
~700 requests follow each other. The window's ``compiles`` and
``tick.compiles_in_window`` = 0 are the proof, run by run.

**The closed loop starts here, not at the window** (the pre-roll). A
closed loop of single calls that all start at one instant begins with a
few wide ticks and takes tens of seconds to settle into its steady state
of many narrow ones; a window opened on that start would measure the
decay. So every caller starts its loop of the real mix in the warm-up.
``warm_up`` returns once each has completed ``warm_ops`` operations (a
failed or wrong call by then raises: the run ends before its window — a
program without byte-string arguments fails every update here; an injected
fault is held back until the window) and the callers go on while the
harness gets ready: its wait for the write-behind flusher to fall quiet
runs to its limit (20 s) because updates keep coming, and that is the
settling time. The window's first ``request`` of a caller takes the loop
over where it stands: it is the operation the caller has in flight, timed
from the window's start (so each caller's first request, one in six of a
20 s window at 310 calls/s, is timed short of its true latency; every
operation that completes in the window is counted once), with whatever
failed or was wrong since ``ready`` booked on it. In flight is ``callers``
throughout.

Parameters (the workload file's ``params``): ``grain``,
``read_proportion``, ``zipfian_constant``, ``warm_ops``, ``hot_in_flight``.
"""

import asyncio

import numpy as np

BLOCK = 1024             # operations drawn at a time, per caller
GREEDY = 4096            # the hottest records, dealt one by one
MASS_TOLERANCE = 0.001   # a process's share of the mass, around 1/n
HEAD = 1 << 22           # ranks whose mass key_mass computes one by one

FNV_OFFSET, FNV_PRIME = 0xCBF29CE484222325, 0x100000001B3
# ScrambledZipfianGenerator: ITEM_COUNT, ZETAN (zeta(ITEM_COUNT, 0.99)) and
# USED_ZIPFIAN_CONSTANT
ITEM_COUNT, ZETAN, ZIPFIAN_CONSTANT = 10_000_000_000, 26.46902820178302, 0.99


def fnv1a64(vals: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` (FNV-1a over the value's eight octets,
    then ``Math.abs``), on a uint64 array."""
    vals = vals.astype(np.uint64)
    h = np.full(vals.shape, FNV_OFFSET, np.uint64)
    for _ in range(8):
        h = (h ^ (vals & np.uint64(0xFF))) * np.uint64(FNV_PRIME)
        vals = vals >> np.uint64(8)
    return np.abs(h.view(np.int64)).view(np.uint64)


class ScrambledZipfian:
    """YCSB's ``ScrambledZipfianGenerator(0, n - 1)``: a
    ``ZipfianGenerator`` over ``ITEM_COUNT`` items (Gray et al.'s closed
    form, as ``nextLong`` has it), its draw hashed and folded onto the
    ``n`` records."""

    def __init__(self, n: int, theta: float) -> None:
        if theta != ZIPFIAN_CONSTANT:
            raise ValueError(f"ZETAN is zeta({ITEM_COUNT}, "
                             f"{ZIPFIAN_CONSTANT}), not of {theta}")
        self.n = n
        self.zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = (1.0 - (2.0 / ITEM_COUNT) ** (1.0 - theta)) \
            / (1.0 - self.zeta2 / ZETAN)

    def ranks(self, u: np.ndarray) -> np.ndarray:
        """``nextLong`` for uniform draws ``u`` in [0, 1)."""
        r = (ITEM_COUNT * (self.eta * u - self.eta + 1.0) ** self.alpha
             ).astype(np.int64)
        uz = u * ZETAN
        r[uz < self.zeta2] = 1
        r[uz < 1.0] = 0
        return r

    def keys(self, u: np.ndarray) -> np.ndarray:
        return (fnv1a64(self.ranks(u)) % np.uint64(self.n)).astype(np.int64)

    def key_mass(self) -> np.ndarray:
        """The probability of each record: the first ``HEAD`` ranks' own
        (rank r is drawn for u in [u_r, u_r+1), ranks 0 and 1 besides by
        ``nextLong``'s first two branches), added up where the scramble
        collides; the ranks beyond fall on ~``ITEM_COUNT / n`` ranks a
        record and are taken as even."""
        r = np.arange(HEAD + 1, dtype=np.float64)
        edge = 1.0 - (1.0 - (r / ITEM_COUNT) ** (1.0 / self.alpha)) / self.eta
        edge = np.clip(edge, self.zeta2 / ZETAN, 1.0)
        p = np.diff(edge)
        p[0] += 1.0 / ZETAN
        p[1] += (self.zeta2 - 1.0) / ZETAN
        keys = (fnv1a64(np.arange(HEAD)) % np.uint64(self.n)).astype(np.int64)
        return np.bincount(keys, weights=p, minlength=self.n) \
            + (1.0 - edge[-1]) / self.n


def deal(mass: np.ndarray, m: int) -> np.ndarray:
    """Owner (0..m-1) of every record, shares of the mass equal within
    ``MASS_TOLERANCE``: the ``GREEDY`` heaviest go one by one to the
    lightest owner, the rest in order 0..m-1, m-1..0, ..."""
    order = np.argsort(-mass, kind="stable")
    owner = np.empty(len(mass), np.int8)
    load = [0.0] * m
    head = order[:GREEDY]
    for k, w in zip(head.tolist(), mass[head].tolist()):
        i = load.index(min(load))
        owner[k] = i
        load[i] += w
    snake = np.concatenate([np.arange(m), np.arange(m)[::-1]])
    # the lightest owner after the head takes the first of each round
    snake = np.argsort(load, kind="stable")[snake]
    tail = order[GREEDY:]
    owner[tail] = snake[np.arange(len(tail)) % (2 * m)]
    share = np.bincount(owner, weights=mass, minlength=m)
    if np.abs(share - 1.0 / m).max() > MASS_TOLERANCE:
        raise ValueError(f"the deal left the shares {share.tolist()}")
    return owner


class Traffic:
    def __init__(self, ctx: dict):
        p, cfg = ctx["params"], ctx["config"]
        self.cls = ctx["grains"][p["grain"]]
        ref_mod = ctx["reference"]
        self.ref = ref_mod.Reference(cfg["data_seed"])
        self.field_count = ref_mod.FIELD_COUNT
        self.field_bytes = ref_mod.FIELD_BYTES
        self.timeout = ctx["response_timeout"]
        self.fault = ctx.get("fault")
        self.warming = False
        self.excluded: set = set()
        self.child, self.total_callers = ctx["child"], ctx["n_callers"]
        self.read_proportion = p["read_proportion"]
        self.warm_ops = p["warm_ops"]
        self.zipf = ScrambledZipfian(cfg["recordcount"],
                                     p["zipfian_constant"])
        mass = self.zipf.key_mass()
        self.owner = deal(mass, ctx["n_children"])
        self.hot = mass * self.total_callers >= p.get("hot_in_flight",
                                                      float("inf"))
        self.hot_mass = float(mass[self.hot].sum())
        self.draws = 2 * BLOCK * ctx["n_children"]  # to keep ~2 BLOCKs
        # this process's records, hottest first (the warm-up's bursts)
        mine = np.flatnonzero(self.owner == self.child)
        self.mine = mine[np.argsort(-mass[mine], kind="stable")]
        self.rngs = [np.random.default_rng([ctx["seed"], g])
                     for g in ctx["callers"]]
        self.blocks: list = [[] for _ in self.rngs]
        self.slot_hot = [0] * len(self.rngs)  # a caller's operation in flight
        self.grains: dict = {}
        # the pre-roll: a caller's loop until the window takes it over
        self.preroll: dict = {}    # slot -> task
        self.unreported = np.zeros((len(self.rngs), 3), np.int64)
        self.to_warm = len(self.rngs)  # callers short of warm_ops
        self.warmed = asyncio.Event()

    calls_per_request = 1

    @property
    def n_callers(self) -> int:
        return len(self.rngs)

    def about(self) -> dict:
        """What of the traffic follows from the data files alone (the same
        in every process and for every seed)."""
        return {"hot_records": int(self.hot.sum()),
                "hot_mass_pct": 100.0 * self.hot_mass}

    def hot_of(self, slot: int) -> int:
        """1 where the caller's last operation was aimed at a hot record (a
        caller has one operation in flight, so after ``request`` returns
        this is the operation it returned)."""
        return self.slot_hot[slot]

    def _draw(self, rng) -> list:
        """BLOCK operations: (key, is_read, field, value); the keys are
        the generator's draws that land on this process's records."""
        keys = np.empty(0, np.int64)
        while len(keys) < BLOCK:
            k = self.zipf.keys(rng.random(self.draws))
            keys = np.concatenate([keys, k[self.owner[k] == self.child]])
        reads = rng.random(BLOCK) < self.read_proportion
        fields = rng.integers(0, self.field_count, BLOCK)
        values = rng.integers(0, 256, (BLOCK, self.field_bytes), np.uint8)
        return list(zip(keys[:BLOCK].tolist(), reads.tolist(),
                        fields.tolist(), map(np.ndarray.tobytes, values)))

    def _judge_read(self, key, r, floor: int) -> tuple[int, int, int]:
        if key in self.excluded:
            return 1, 0, 0   # nothing about the key can be judged any more
        ver, data = int(r[0]), np.asarray(r[1]).tobytes()
        if self.fault == "reply" and not self.warming:
            data, self.fault = bytes([data[0] ^ 1]) + data[1:], None
        wrong = self.ref.read(key, ver, data, floor)
        return (0, 0, 1) if wrong else (1, 0, 0)

    async def _burst(self, client, b: int) -> np.ndarray:
        """``b`` reads of ``b`` distinct own records in one call_batch."""
        keys = self.mine[:b].tolist()
        floors = [self.ref.sending_read(k) for k in keys]
        futs = client.call_batch(self.cls, "read", [(k, {}) for k in keys],
                                 timeout=self.timeout)
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
        request takes its slot: the outcome of its last operation, with
        the failed and the wrong that ``warm_up`` has not reported."""
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
        while b <= min(max(self.n_callers, 2), len(self.mine)):
            for _ in range(2):
                tot += await self._burst(client, b)
            b *= 2
        if self.child == 0:
            for flood in (1, 1, 2):
                tot += await self._burst(client, min(
                    flood * self.total_callers, len(self.mine)))
        for s in range(self.n_callers):
            self.preroll[s] = asyncio.ensure_future(self._caller(client, s))
        await self.warmed.wait()
        tot += self.unreported.sum(axis=0)
        self.unreported[:] = 0
        if tot[1] or tot[2]:
            # a system that cannot serve the deployment before the window
            # is not measured in it
            for task in self.preroll.values():
                task.cancel()
            raise RuntimeError(f"warm-up: {tot[1]} calls failed, "
                               f"{tot[2]} replies wrong")
        return int(tot[0]), int(tot[1]), int(tot[2])

    async def request(self, client, slot: int) -> tuple[int, int, int]:
        task = self.preroll.pop(slot, None)
        if task is not None:
            # the window's first request of this caller: the operation its
            # loop has in flight (the loop ends when it sees its slot gone)
            self.warming = False
            return await task
        return await self._operation(client, slot)

    async def _operation(self, client, slot: int) -> tuple[int, int, int]:
        block = self.blocks[slot]
        if not block:
            block.extend(self._draw(self.rngs[slot]))
        key, is_read, field, value = block.pop()
        self.slot_hot[slot] = int(self.hot[key])
        grain = self.grains.get(key)
        if grain is None:
            grain = self.grains[key] = client.get_grain(self.cls, key)
        try:
            if is_read:
                floor = self.ref.sending_read(key)
                r = await grain.read()
            else:
                self.ref.sending_update(key)
                r = await grain.update(field=field, value=value)
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — a failed or timed-out request
            if not is_read:
                # nobody knows whether the write landed
                self.excluded.add(key)
                self.ref.forget(key)
            return 0, 1, 0
        if is_read:
            return self._judge_read(key, r, floor)
        if key in self.excluded:
            return 1, 0, 0
        # a read that was waiting for this update is judged now, and a
        # wrong one is booked here
        wrong = self.ref.update(key, field, value, int(r))
        return (0, 0, wrong) if wrong else (1, 0, 0)

    def states(self):
        keys, states = self.ref.states()
        return keys, states, self.excluded
