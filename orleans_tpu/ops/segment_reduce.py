"""Fan-in segment reduction as MXU matmuls.

The fan-in hot op: N messages carrying values land on S target actors
(Presence GameGrain aggregating player heartbeats — reference
/root/reference/Samples/Presence/Grains/GameGrain.cs; every stream-consumer
fan-in has the same shape). The obvious ``jax.ops.segment_sum`` lowers to
an XLA scatter-add, which TPUs execute (mostly) serially — it is the
classic TPU anti-pattern. Both implementations here instead ride the MXU:

``segment_sum_onehot``
    out[s] = sum_i (seg_ids[i] == s) * values[i]  ==  onehot(seg_ids).T @ values
    — one [S, B] x [B, D] matmul. XLA fuses the one-hot mask into the
    matmul operand, so the O(S*B) mask is never materialized in HBM.

``segment_sum_pallas``
    The same contraction, hand-blocked: grid over (segment tiles, message
    tiles), the mask block built in VMEM from a broadcasted iota and fed
    straight to the MXU via ``jnp.dot``. Accumulates across message tiles
    in the output block (grid is sequential on TPU), so HBM traffic is
    one read of values/ids + one write of out.

``segment_sum`` picks the Pallas path on TPU for well-tiled shapes, the
one-hot path for other TPU shapes, and a plain scatter-add on non-TPU
backends (where the one-hot operand is pure overhead — the scatter IS the
fast path there). Nothing here chooses Pallas interpret mode by itself:
``interpret=True`` is something a test asks for by name.

Accumulation note: the MXU paths multiply and accumulate in float32 at
``Precision.HIGHEST`` — a TPU's default precision rounds f32 matmul
operands to bfloat16, which is exact only up to 256 — so integer values
are summed exactly while each value and each per-segment total stays
below 2^24; the CPU scatter path sums exactly in the input dtype.
Per-segment totals beyond 2^24 should accumulate across calls in caller
state (as the bench's GameGrain does), not per call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["segment_sum", "segment_sum_onehot", "segment_sum_pallas",
           "masked_reduce", "host_fold", "REDUCE_OPS"]


# f32 x f32 on the MXU without rounding the operands to bfloat16 (the
# exactness contract of the module docstring)
_EXACT = jax.lax.Precision.HIGHEST


def _as_2d(values: jax.Array) -> tuple[jax.Array, bool]:
    if values.ndim == 1:
        return values[:, None], True
    if values.ndim == 2:
        return values, False
    raise ValueError(f"values must be [B] or [B, D], got {values.shape}")


def segment_sum_onehot(values: jax.Array, seg_ids: jax.Array,
                       num_segments: int) -> jax.Array:
    """MXU segment sum: ``onehot(seg_ids).T @ values``.

    values: [B] or [B, D]; seg_ids: [B] int (out-of-range ids contribute
    nothing). Returns [S] or [S, D] in values.dtype (accumulated in f32).
    """
    v, squeeze = _as_2d(values)
    ids = seg_ids.astype(jnp.int32)
    seg_range = jax.lax.broadcasted_iota(jnp.int32, (num_segments, 1), 0)
    mask = (seg_range == ids[None, :]).astype(jnp.float32)  # [S, B]
    out = jnp.dot(mask, v.astype(jnp.float32),
                  preferred_element_type=jnp.float32, precision=_EXACT)
    out = out.astype(values.dtype)
    return out[:, 0] if squeeze else out


def _seg_kernel(ids_ref, v_ref, out_ref, *, block_s: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    i = pl.program_id(0)
    seg_base = i * block_s
    ids = ids_ref[0, :]                                  # [TB]
    seg = jax.lax.broadcasted_iota(jnp.int32, (block_s, ids.shape[0]), 0)
    mask = (seg + seg_base == ids[None, :]).astype(jnp.float32)  # [TS, TB]
    out_ref[:] += jnp.dot(mask, v_ref[:].astype(jnp.float32),
                          preferred_element_type=jnp.float32,
                          precision=_EXACT)


def segment_sum_pallas(values: jax.Array, seg_ids: jax.Array,
                       num_segments: int, *, block_s: int = 256,
                       block_b: int = 512,
                       interpret: bool = False) -> jax.Array:
    """Blocked-MXU segment sum (see module docstring). Pads B and S up to
    tile multiples; out-of-range ids never match a segment tile.
    Compiled for the backend it runs on unless a test passes
    ``interpret=True``."""
    v, squeeze = _as_2d(values)
    B, D = v.shape
    ids = seg_ids.astype(jnp.int32)
    block_s = min(block_s, max(8, num_segments))
    block_b = min(block_b, max(128, B))
    Bp = -(-B // block_b) * block_b
    Sp = -(-num_segments // block_s) * block_s
    if Bp != B:
        v = jnp.pad(v, ((0, Bp - B), (0, 0)))
        ids = jnp.pad(ids, (0, Bp - B), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_seg_kernel, block_s=block_s),
        grid=(Sp // block_s, Bp // block_b),
        in_specs=[
            pl.BlockSpec((1, block_b), lambda i, j: (0, j)),
            pl.BlockSpec((block_b, D), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Sp, D), jnp.float32),
        interpret=interpret,
    )(ids[None, :], v)
    out = out[:num_segments].astype(values.dtype)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# Masked full reduction (the reduce_actors device half)
# ---------------------------------------------------------------------------

# combine ops reduce_actors accepts. "mean" is NOT here deliberately: it
# is not associative per-silo — callers combine it as (sum, count) pairs
# and divide once at the top (the engine and the dispatcher's cross-silo
# merge both do), so partial reductions stay exactly combinable.
REDUCE_OPS = ("sum", "max", "min")


def host_fold(op: str):
    """The numpy fold that combines :func:`masked_reduce` partials
    host-side (across deferral rounds and across silos) — the ONE place
    the op → fold mapping lives, so the engine's round combiner and the
    dispatcher's cross-silo merge cannot drift when an op is added.
    ``mean`` partials carry sums (divide once at the top)."""
    if op in ("sum", "mean"):
        return np.add
    if op == "max":
        return np.maximum
    if op == "min":
        return np.minimum
    raise ValueError(f"op must be one of {REDUCE_OPS + ('mean',)}, "
                     f"got {op!r}")


def _reduce_identity(op: str, dtype) -> jax.Array:
    """The op's identity element in ``dtype`` — what masked-off lanes
    contribute. Integer sums stay in the integer dtype (exact,
    order-independent: the determinism contract reduce_actors tests pin);
    float sums keep the value dtype and are bit-stable only per layout."""
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        v = -jnp.inf if op == "max" else jnp.inf
        return jnp.asarray(v, dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(op == "min", jnp.bool_)
    info = np.iinfo(np.dtype(dtype))
    return jnp.asarray(info.min if op == "max" else info.max, dtype)


@functools.partial(jax.jit, static_argnames=("op",))
def masked_reduce(values, valid: jax.Array, op: str = "sum"):
    """Full tree reduction of per-lane results down to ONE row.

    values: pytree of ``[n_shards, B, *feature]`` arrays (a tick's
    per-actor results); valid: ``[n_shards, B]`` bool. Reduces every leaf
    over the two lane axes — masked lanes contribute the op's identity —
    returning a pytree of ``[*feature]`` arrays: the single row that
    crosses the host boundary instead of N per-actor responses
    (DrJAX-style MapReduce leaf, arXiv 2403.07128).

    Accumulation dtype is the value dtype: integer sums are exact and
    layout-independent (the reduce_actors determinism contract — bool
    promotes to int32, the readiness-count case); float sums reduce in a
    deterministic tree order per shape but differ across shard layouts
    by normal float reassociation. All-masked max/min returns the
    identity — callers hold the valid count and decide."""
    if op not in REDUCE_OPS:
        raise ValueError(f"op must be one of {REDUCE_OPS}, got {op!r}")

    def one(v):
        dtype = v.dtype
        if op == "sum" and dtype == jnp.bool_:
            v = v.astype(jnp.int32)   # bool sum = count of True lanes
            dtype = v.dtype
        mask = valid.reshape(valid.shape + (1,) * (v.ndim - valid.ndim))
        filled = jnp.where(mask, v, _reduce_identity(op, dtype))
        if op == "sum":
            return jnp.sum(filled, axis=(0, 1))
        if op == "max":
            return jnp.max(filled, axis=(0, 1))
        return jnp.min(filled, axis=(0, 1))

    return jax.tree_util.tree_map(one, values)


def segment_sum(values: jax.Array, seg_ids: jax.Array,
                num_segments: int) -> jax.Array:
    """Fan-in reduction, backend-dispatched: the Pallas MXU kernel on TPU
    when the shape tiles well, the fused one-hot matmul for other TPU
    shapes (scatter-add is the weak op there), and a plain scatter-add
    everywhere else — on CPU the one-hot path materializes an O(B×S)
    operand for no benefit (measured 2.3× slower at B=156k, S=128 in the
    multi-shard bench's fan-in)."""
    v2, _ = _as_2d(values)  # enforce the [B]/[B,D] contract on EVERY
    # backend, so shapes that would fail on TPU fail on CPU too
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        return jax.ops.segment_sum(values, seg_ids,
                                   num_segments=num_segments)
    B, D = v2.shape
    if B >= 1024 and num_segments >= 256 and D % 128 == 0:
        return segment_sum_pallas(values, seg_ids, num_segments)
    return segment_sum_onehot(values, seg_ids, num_segments)
