"""Bytes the dispatch tick needs per message, from its shapes alone: the
row read, the row written (nothing for a read-only method), the arguments
and the result. This is the algorithm's traffic — padding lanes, slot
indices and masks are overhead the roofline share should show, not work.
"""

import numpy as np


def _nbytes(dtype, shape) -> int:
    return int(np.dtype(dtype).itemsize * int(np.prod(shape, dtype=np.int64)))


def tick_bytes_per_message(grain_class, method: str) -> dict:
    """{"row_read", "row_written", "args", "result", "total"} in bytes."""
    import jax

    m = grain_class.__dict__[method]
    state = {k: jax.ShapeDtypeStruct(tuple(shape), dtype)
             for k, (dtype, shape) in grain_class.STATE.items()}
    args = {k: jax.ShapeDtypeStruct(tuple(shape), dtype)
            for k, (dtype, shape) in (m.args_schema or {}).items()}
    _new, result = jax.eval_shape(m.fn, state, args)
    row = sum(_nbytes(s.dtype, s.shape) for s in state.values())
    out = {
        "row_read": row,
        "row_written": 0 if m.read_only else row,
        "args": sum(_nbytes(s.dtype, s.shape) for s in args.values()),
        "result": sum(_nbytes(s.dtype, s.shape)
                      for s in jax.tree_util.tree_leaves(result)),
    }
    out["total"] = sum(out.values())
    return out
