"""Bytes one delivered message of the exchange has to move, from the
shapes alone (whatever implements the delivery): the payload read out of
the sender's outbox and written into the receiver's inbox, the
destination key beside it both times, and what the receiving method has to
touch of the receiver's row at the least — the payload written into it
(the ring slot, for Chirper) and the row's scalar fields read and written
(its counters). Gathering and scattering whole rows, padding lanes and
masks are overhead the roofline share should show, not work.
``kernel_bytes.py`` reckons a client's call; this reckons a delivery.
"""

import numpy as np


def _nbytes(dtype, shape) -> int:
    return int(np.dtype(dtype).itemsize * int(np.prod(shape, dtype=np.int64)))


def delivery_bytes(grain_class, method: str) -> dict:
    """{"payload_out", "payload_in", "keys", "row_written", "counters",
    "total"} in bytes, for a message to ``grain_class.method``."""
    m = grain_class.__dict__[method]
    payload = sum(_nbytes(d, s) for d, s in m.args_schema.values())
    scalars = sum(_nbytes(d, s) for d, s in grain_class.STATE.values()
                  if not tuple(s))
    out = {"payload_out": payload, "payload_in": payload, "keys": 8,
           "row_written": payload, "counters": 2 * scalars}
    out["total"] = sum(out.values())
    return out
