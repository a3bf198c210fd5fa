"""Plain reference of the YCSB record (imports nothing from
``orleans_tpu``): a dict of records stepped one operation at a time.

A record is ten 100-byte fields and ``ver``, the count of updates applied
(the table's row holds the 1,000 bytes and 24 of zero padding, which is
how ``states()`` reports it).
Its initial contents are YCSB's load phase: word ``w`` of record ``k`` is a
counter-based uint32 hash of (data seed, k, w), little-endian — the same
few lines of integer arithmetic as the app's ``initial_state``, in numpy.
``update(field, value)`` overwrites one field and adds one to ``ver``; a
``field`` outside 0..9 changes nothing.

Unlike the other references this one cannot say what a reply must be
before it sees it: a key's reads and updates ride different (class,
method) groups of a tick, so the order in which they were sent does not
fix the order in which they ran. The row's ``ver`` does. So each method
here takes the reply and judges it:

* ``update`` — the acknowledged ``ver`` must be new and in 1..(updates
  sent to the key so far); once every update sent has been acknowledged
  that makes them exactly 1..n, no gap, no repeat. Updates are applied in
  ``ver`` order as their acknowledgements fill in.
* ``read`` — the 1,000 bytes must be the record's state at the ``ver`` the
  reply reports. A read's reply may arrive before that of the update it
  saw; it then waits, and is judged when that update is applied (the
  finding is returned by the ``update`` call that applied it). A ``ver``
  older than the newest applied is rebuilt from the undo log. And the
  ``ver`` may not be stale: ``sending_read``, called before the read
  leaves, gives the highest ``ver`` acknowledged to this caller's process
  by then (the key's one owner), and a reply below that floor is wrong —
  an acknowledged write is read back.

Both return the number of wrong replies they found (0 = fine). A key
whose update failed or timed out is ``forget``-ed: nobody knows whether it
landed, so nothing about the key can be judged from then on. ``states()``
reports the keys with an applied update; a key that still has a reply
waiting there (no update of that ``ver`` was ever acknowledged) is
reported with ``ver`` -1, which no row can match.
"""

import numpy as np

FIELD_COUNT = 10
FIELD_BYTES = 100
RECORD_BYTES = FIELD_COUNT * FIELD_BYTES
ROW_BYTES = 1024                   # the record, zero-padded, in the table
FIELDS = ("fields", "ver")         # what an update writes
DERIVED = ()                       # nothing follows from the key's hash


def initial_words(seed: int, keys: np.ndarray, words: np.ndarray
                  ) -> np.ndarray:
    """uint32 contents of word ``words`` of record ``keys``: three odd
    multipliers, then murmur3's 32-bit finaliser (wrapping arithmetic)."""
    u = np.uint32
    h = (keys.astype(u) * u(0x9E3779B1)) ^ (words.astype(u) * u(0x85EBCA77)) \
        ^ u((seed * 0xC2B2AE3D) & 0xFFFFFFFF)
    h = (h ^ (h >> u(16))) * u(0x85EBCA6B)
    h = (h ^ (h >> u(13))) * u(0xC2B2AE35)
    return h ^ (h >> u(16))


def initial_record(seed: int, key: int) -> bytes:
    """The 1,000 bytes a never-updated record holds."""
    return initial_words(seed, np.array([key], np.int64),
                         np.arange(RECORD_BYTES // 4)).astype("<u4").tobytes()


class _Record:
    __slots__ = ("state", "ver", "sent", "hi", "acked", "undo", "reads",
                 "lost")

    def __init__(self, state: bytes) -> None:
        self.state = bytearray(state)  # at ``ver``
        self.ver = 0                   # updates applied, in order
        self.sent = 0                  # updates sent
        self.hi = 0                    # the highest ver acknowledged
        self.acked: dict = {}          # ver -> (field, value), not applied
        self.undo: list = []           # [v - 1] -> (field, bytes before v)
        self.reads: dict = {}          # ver -> [data, ...] waiting for it
        self.lost = False              # an update's fate is unknown

    def at(self, ver: int) -> bytes:
        """The record's bytes at an applied ``ver``."""
        state = bytearray(self.state)
        for v in range(self.ver, ver, -1):
            field, before = self.undo[v - 1]
            state[field * FIELD_BYTES:(field + 1) * FIELD_BYTES] = before
        return bytes(state)


class Reference:
    def __init__(self, data_seed: int) -> None:
        self.seed = data_seed
        self.rows: dict = {}       # key -> _Record, made at first touch

    def _row(self, key) -> _Record:
        row = self.rows.get(key)
        if row is None:
            row = self.rows[key] = _Record(initial_record(self.seed, key))
        return row

    def sending_update(self, key) -> None:
        """Call before an update leaves: its ``ver`` may come back in a
        read before its own acknowledgement does."""
        self._row(key).sent += 1

    def sending_read(self, key) -> int:
        """Call before a read leaves: the floor of the ``ver`` its reply
        may report, to be handed to ``read`` with the reply."""
        return self._row(key).hi

    def forget(self, key) -> None:
        self._row(key).lost = True

    def update(self, key, field: int, value: bytes, ver: int) -> int:
        row = self._row(key)
        if row.lost:
            return 0
        if not 0 <= field < FIELD_COUNT:   # writes nothing, ver as it is
            return int(not 0 <= ver <= row.sent)
        if not row.ver < ver <= row.sent or ver in row.acked:
            return 1
        row.hi = max(row.hi, ver)
        row.acked[ver] = (field, bytes(value))
        wrong = 0
        while row.ver + 1 in row.acked:
            field, value = row.acked.pop(row.ver + 1)
            lo = field * FIELD_BYTES
            row.undo.append((field, bytes(row.state[lo:lo + FIELD_BYTES])))
            row.state[lo:lo + FIELD_BYTES] = value
            row.ver += 1
            waiting = row.reads.pop(row.ver, ())
            wrong += sum(data != row.state for data in waiting)
        return wrong

    def read(self, key, ver: int, data: bytes, floor: int = 0) -> int:
        row = self._row(key)
        if row.lost:
            return 0
        if not floor <= ver <= row.sent or len(data) != len(row.state):
            return 1
        if ver > row.ver:
            row.reads.setdefault(ver, []).append(bytes(data))
            return 0
        return int(data != row.at(ver))

    def states(self) -> tuple[list, dict]:
        """Every key with an applied update and its expected row."""
        keys = [k for k, r in self.rows.items() if r.ver or r.reads
                or r.acked]
        rows = [self.rows[k] for k in keys]
        fields = np.zeros((len(rows), ROW_BYTES), np.uint8)
        fields[:, :RECORD_BYTES] = np.frombuffer(
            b"".join(bytes(r.state) for r in rows),
            np.uint8).reshape(len(rows), RECORD_BYTES)
        ver = np.array([-1 if (r.reads or r.acked) and not r.lost else r.ver
                        for r in rows], np.int64)
        return keys, {"fields": fields, "ver": ver}


def derive(states: dict, key_hashes: np.ndarray) -> dict:
    return dict(states)
