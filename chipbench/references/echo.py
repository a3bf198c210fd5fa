"""Plain reference of the Ping benchmark's echo grain (imports nothing from
``orleans_tpu``): the reply equals the argument, and the row keeps its
initial ``n`` = 0 whatever is sent.
"""

import numpy as np

FIELDS = ("n",)
DERIVED = ()


class Reference:
    def __init__(self) -> None:
        self.touched: set = set()

    def ping(self, key, x: int) -> int:
        self.touched.add(key)
        return int(x)

    def states(self) -> tuple[list, dict]:
        keys = list(self.touched)
        return keys, {"n": np.zeros(len(keys), np.int64)}


def derive(states: dict, key_hashes: np.ndarray) -> dict:
    return dict(states)
