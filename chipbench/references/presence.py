"""Plain reference of the Presence player (imports nothing from
``orleans_tpu``): a player starts at pos (0, 0), score 0, in game
``hash mod 64``; a heartbeat stores the float16 position as float32, adds
``delta`` to the score and answers the new score. Each key has one owner
(one caller of one load generator), so the order of its heartbeats is the
order they were sent in.
"""

import numpy as np

N_GAMES = 64
FIELDS = ("pos", "score")          # what a heartbeat writes
DERIVED = ("game",)                # what follows from the key's hash


class Reference:
    def __init__(self) -> None:
        self.rows: dict = {}       # key -> [pos0, pos1, score]

    def heartbeat(self, key, pos, delta: int) -> int:
        """Apply one heartbeat; the reply the system owes is the new score."""
        row = self.rows.get(key)
        if row is None:
            row = self.rows[key] = [0.0, 0.0, 0]
        p = np.asarray(pos, np.float16).astype(np.float32)
        row[0], row[1] = float(p[0]), float(p[1])
        row[2] += int(delta)
        return row[2]

    def states(self) -> tuple[list, dict]:
        """Every touched key and its expected state, field by field."""
        keys = list(self.rows)
        rows = [self.rows[k] for k in keys]
        return keys, {
            "pos": np.array([[r[0], r[1]] for r in rows],
                            np.float32).reshape(len(rows), 2),
            "score": np.array([r[2] for r in rows], np.int64)}


def derive(states: dict, key_hashes: np.ndarray) -> dict:
    """Add the fields that follow from the key's hash alone (the hash is
    identity, not behaviour under test, so the harness supplies it)."""
    out = dict(states)
    out["game"] = (np.asarray(key_hashes, np.int64) & 0x7FFFFFFF) % N_GAMES
    return out
