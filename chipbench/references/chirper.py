"""The plain reference of the Chirper deployment (Orleans ``Samples/Chirper``:
``ChirperAccount`` keeps its followers and a cache of the last 100 chirps
it received; ``publish`` sends the chirp to every follower). Accounts,
follower lists and timelines are dicts and lists; nothing here imports
``orleans_tpu`` or jax.

**The graph** is data: account ``a`` has ``degree_table[h(seed, a, 0) %
len(table)]`` followers (the configuration's table: mean 27.0, the
source's 27,000 edges over 1,000 nodes, skewed, capped at 128), follower
``j`` is ``(h(seed, a, 1) + j * (h(seed, a, 2) | 1)) % accounts`` — the
accounts are a power of two and the stride is odd, so a list never names
an account twice; an account is not its own follower (that lane is left
out). ``h`` is the counter-based uint32 hash ``references/ycsb.py`` uses,
the same lines in ``numpy`` here and in ``jax.numpy`` in
``apps/chirper.py``.

**A chirp** is 320 bytes: a 40-byte header (author i32, the author's
sequence number i32, text length i32, little-endian, then zeros) and up
to 280 bytes of text. The text of chirp ``seq`` of ``author`` in a run is
``chirp_text(run_seed, author, seq)``, so whoever reads a chirp can say,
from its header alone, what its bytes have to be. A chirp of length 0 is
the harness's neutral warm-up call: sent to every follower, accepted by
none.

**What order a timeline may be in.** Deliveries of different authors reach
a follower in an order no client fixes, so a timeline is held to every
order the guarantees allow and no other (``check_timeline``): each entry is
a chirp really published, byte for byte, by an account the reader follows;
one author's chirps stand in that author's publish order without gap or
repeat; the ring holds the last 100 applied, so of each author a suffix of
what was delivered; ``n_received`` is the number of deliveries. ``Reference``
itself applies publishes in the order it is told them, which is one of
those orders.
"""

import glob
import os

import numpy as np

CHIRP_BYTES = 320
HEADER_BYTES = 40
TEXT_BYTES = 280
RING = 100                      # the source's received-chirps cache
TIMELINE_BYTES = 32768          # 100 x 320 B and 768 B of zeros
FOLLOW_CAP = 128
READ_N = 10                     # chirps a get_received answers with

# the fields the harness compares row by row (device table and storage):
# exact, and the same in every order the guarantees allow
FIELDS = ("n_received", "head", "seq", "n_followers", "followers")


def hash32(xp, seed: int, a, b):
    """uint32 hash of (seed, a, b), for ``xp`` = numpy or jax.numpy: three
    odd multipliers, then murmur3's 32-bit finaliser."""
    u = xp.uint32
    h = (a.astype(u) * u(0x9E3779B1)) ^ (b.astype(u) * u(0x85EBCA77)) \
        ^ u((seed * 0xC2B2AE3D) & 0xFFFFFFFF)
    h = (h ^ (h >> u(16))) * u(0x85EBCA6B)
    h = (h ^ (h >> u(13))) * u(0xC2B2AE35)
    return h ^ (h >> u(16))


def followers_of(xp, seed: int, accounts: int, table, keys):
    """``[..., FOLLOW_CAP]`` int32 follower lists of ``keys`` (-1 where
    there is none), for ``xp`` = numpy or jax.numpy."""
    if accounts & (accounts - 1):
        raise ValueError(f"accounts must be a power of two, got {accounts}")
    u = xp.uint32
    k = keys.astype(u)
    table = xp.asarray(table, dtype=xp.int32)
    degree = table[(hash32(xp, seed, k, xp.zeros_like(k))
                    % u(table.shape[0])).astype(xp.int32)]
    base = hash32(xp, seed, k, xp.ones_like(k))
    stride = hash32(xp, seed, k, xp.full_like(k, 2)) | u(1)
    j = xp.arange(FOLLOW_CAP, dtype=u)
    f = ((base[..., None] + j * stride[..., None]) % u(accounts)
         ).astype(xp.int32)
    keep = (j.astype(xp.int32) < degree[..., None]) \
        & (f != keys.astype(xp.int32)[..., None])
    return xp.where(keep, f, xp.int32(-1))


def chirp_text(run_seed: int, author: int, seq: int) -> bytes:
    """The 280 bytes of text of chirp ``seq`` (1, 2, ...) of ``author``."""
    w = np.arange(TEXT_BYTES // 4, dtype=np.uint32)
    a = np.full_like(w, author)
    words = hash32(np, run_seed & 0xFFFFFFFF,
                   a * np.uint32(0x01000193) + np.uint32(seq), w)
    return words.astype("<u4").tobytes()


def make_chirp(author: int, seq: int, text: bytes) -> bytes:
    head = np.array([author, seq, len(text)], "<i4").tobytes()
    return head + bytes(HEADER_BYTES - len(head)) + text \
        + bytes(TEXT_BYTES - len(text))


NEUTRAL_CHIRP = make_chirp(-1, 0, b"")


def header_of(chirp) -> tuple[int, int, int]:
    """(author, seq, text length) of a chirp's bytes."""
    a, s, n = np.frombuffer(bytes(chirp[:12]), "<i4").tolist()
    return a, s, n


def split_chirps(data, n: int) -> list[bytes]:
    """The first ``n`` chirps of a reply's or a ring's bytes."""
    b = bytes(data)
    return [b[i * CHIRP_BYTES:(i + 1) * CHIRP_BYTES] for i in range(n)]


class Reference:
    """Plain Chirper: ``publish`` appends the chirp to every follower's
    list; ``get_received(n)`` answers the count and the newest n."""

    def __init__(self, data_seed: int, accounts: int, table) -> None:
        self.seed, self.accounts = int(data_seed), int(accounts)
        self.table = np.asarray(table, np.int32)
        self.timeline: dict[int, list[bytes]] = {}   # every chirp applied
        self.seq: dict[int, int] = {}                # publishes accepted
        self._followers: dict[int, np.ndarray] = {}

    def followers(self, key: int) -> np.ndarray:
        """The row's ``followers`` field: [FOLLOW_CAP] int32, -1 = none."""
        f = self._followers.get(key)
        if f is None:
            f = self._followers[key] = followers_of(
                np, self.seed, self.accounts, self.table,
                np.asarray([key], np.int64))[0]
        return f

    def follower_keys(self, key: int) -> list[int]:
        f = self.followers(key)
        return f[f >= 0].tolist()

    def publish(self, key: int, chirp: bytes) -> int:
        """The reply the system owes: the number of followers."""
        to = self.follower_keys(key)
        _a, _s, length = header_of(chirp)
        if 0 < length <= TEXT_BYTES:
            self.seq[key] = self.seq.get(key, 0) + 1
            for f in to:
                self.timeline.setdefault(f, []).append(bytes(chirp))
        else:
            self.seq.setdefault(key, 0)
            for f in to:
                self.timeline.setdefault(f, [])
        return len(to)

    def get_received(self, key: int, n: int) -> tuple[int, bytes]:
        """(``n_received``, the newest ``min(n, READ_N)`` chirps, newest
        first, in ``READ_N`` x 320 bytes, zeros after them)."""
        tl = self.timeline.get(key, [])
        newest = tl[::-1][:max(0, min(n, READ_N, RING))]
        data = b"".join(newest)
        return len(tl), data + bytes(READ_N * CHIRP_BYTES - len(data))

    def row(self, key: int) -> dict:
        """The whole device row of ``key`` as this reference has it."""
        tl = self.timeline.get(key, [])
        ring = np.zeros(TIMELINE_BYTES, np.uint8)
        for i in range(max(0, len(tl) - RING), len(tl)):
            at = (i % RING) * CHIRP_BYTES
            ring[at:at + CHIRP_BYTES] = np.frombuffer(tl[i], np.uint8)
        f = self.followers(key)
        return {"timeline": ring, "followers": f,
                "n_followers": int((f >= 0).sum()), "n_received": len(tl),
                "head": len(tl) % RING, "seq": self.seq.get(key, 0)}

    def states(self) -> tuple[list, dict]:
        keys = sorted(set(self.timeline) | set(self.seq))
        rows = [self.row(k) for k in keys]
        return keys, {f: np.array([r[f] for r in rows]) for f in FIELDS}


def ring_entries(timeline, n_received: int) -> list[bytes]:
    """A row's ring as the chirps it holds, oldest first."""
    held = min(n_received, RING)
    ring = split_chirps(timeline, RING)
    return [ring[i % RING] for i in range(n_received - held, n_received)]


def check_timeline(entries: list, n_received: int, delivered: dict,
                   complete: bool = True) -> list[str]:
    """What is wrong with a timeline, as a list of complaints (empty: it is
    one of the orders the guarantees allow). ``entries``: the chirps held,
    oldest first (a whole ring, or the newest few of a reply);
    ``delivered``: author -> the chirps that author's acknowledged
    publishes sent this reader, in publish order. ``complete``: every
    publish that could have reached the reader is in ``delivered`` and
    acknowledged, so the counts have to add up and every author's entries
    end at its last chirp."""
    bad = []
    total = sum(len(v) for v in delivered.values())
    if complete and n_received != total:
        bad.append(f"n_received {n_received}, deliveries {total}")
    if complete and len(entries) > min(total, RING):
        bad.append(f"{len(entries)} entries of {total} deliveries")
    seen: dict[int, list[int]] = {}
    for e in entries:
        author, seq, _n = header_of(e)
        theirs = delivered.get(author)
        if theirs is None:
            bad.append(f"a chirp of {author}, whom the reader does not "
                       f"follow or who published nothing")
            continue
        at = next((i for i, c in enumerate(theirs) if c == bytes(e)), None)
        if at is None:
            bad.append(f"chirp {seq} of {author} is not one it published")
            continue
        seen.setdefault(author, []).append(at)
    for author, ats in seen.items():
        if ats != list(range(ats[0], ats[0] + len(ats))):
            bad.append(f"{author}'s chirps out of order, repeated or with a "
                       f"gap: {ats}")
        elif complete and len(entries) >= min(total, RING) \
                and ats[-1] != len(delivered[author]) - 1:
            bad.append(f"{author}'s newest chirp is missing")
    if complete and total <= RING and len(entries) == total:
        want = sorted(c for v in delivered.values() for c in v)
        if sorted(bytes(e) for e in entries) != want:
            bad.append("the entries are not the deliveries")
    return bad


# -- the parent's side: the expected rows, from every child's record ---------

_MERGED: dict = {}


def _merged(run_dir: str, own: dict) -> dict:
    """Publishes acknowledged, over every load generator of the run: a
    follower's row is written by authors of several client processes, so
    its expected state follows from all their records together. The
    children's result files lie beside each other in the run's directory
    (each child reports the directory; the parent has awaited them all
    before it compares). Without a directory (a test that holds one
    record) the one record is all there is."""
    if run_dir in _MERGED:
        return _MERGED[run_dir]
    logs = [own]
    if run_dir:
        logs = []
        for path in sorted(glob.glob(os.path.join(run_dir, "child*.npz"))):
            with np.load(path, allow_pickle=False) as z:
                logs.append({k[len("state."):]: z[k] for k in z.files
                             if k.startswith("state.log.")
                             or k.startswith("state.graph.")})
    out = {"author": np.concatenate([g["log.author"] for g in logs]),
           "count": np.concatenate([g["log.count"] for g in logs])}
    if run_dir:
        _MERGED[run_dir] = out
    return out


def derive(states: dict, key_hashes: np.ndarray) -> dict:
    """The rows the harness compares, for one child's keys: ``states``
    carries the child's own log of acknowledged publishes (``log.author``,
    ``log.count``: author -> chirps accepted), the graph's parameters and
    the run's directory; the counters of a row follow from every child's
    log and the graph: ``seq`` the author's accepted publishes,
    ``n_received`` the chirps its followees' publishes sent it, ``head``
    that modulo the ring."""
    seed, accounts = (int(v) for v in states["graph.params"][:2])
    table = states["graph.table"]
    run_dir = str(states["log.run_dir"])
    log = _merged(run_dir, states)
    seq = np.zeros(accounts, np.int64)
    np.add.at(seq, log["author"], log["count"])
    received = np.zeros(accounts, np.int64)
    authors = np.flatnonzero(seq)
    f = followers_of(np, seed, accounts, table, authors)
    np.add.at(received, f[f >= 0],
              np.broadcast_to(seq[authors][:, None], f.shape)[f >= 0])
    k = np.asarray(key_hashes, np.int64)
    fk = followers_of(np, seed, accounts, table, k)
    return {"n_received": received[k].astype(np.int32),
            "head": (received[k] % RING).astype(np.int32),
            "seq": seq[k].astype(np.int32),
            "n_followers": (fk >= 0).sum(axis=1).astype(np.int32),
            "followers": fk}
