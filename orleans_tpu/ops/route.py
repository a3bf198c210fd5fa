"""Message routing (outbox pack) without a sort.

The ICI transport packs each tick's outbound messages into per-destination
buckets (``parallel.transport._pack_outbox`` delegates here). The obvious
implementation ranks messages within their destination group via
``argsort`` — but sorts are among the weakest ops on TPU (O(B log^2 B)
sorting networks on the VPU). The rank is really a *prefix count*:

    rank[i] = #{ j < i : dest[j] == dest[i] }  ==  (L @ onehot(dest))[i, dest[i]]

with L the strictly-lower-triangular ones matrix — one [B, B] x [B, S]
matmul on the MXU. The Pallas kernel builds both the L block and the
one-hot block in VMEM from iotas (neither ever touches HBM), so the kernel
reads B int32 ids and writes the [B, S] prefix-count table; XLA's matmul
would have to materialize L (O(B^2)) and onehot (O(B*S)) in HBM first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["rank_by_dest", "rank_dense_keys", "pack_by_dest"]


def _prefix_kernel(ids_ref, out_ref, *, block: int, n_dest: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # rows of C = messages i; contraction axis = earlier messages j
    @pl.when(j <= i)
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0) \
            + i * block
        col = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1) \
            + j * block
        lower = (col < row).astype(jnp.float32)             # [TI, TJ]
        ids_j = ids_ref[0, :]                               # [TJ]
        seg = jax.lax.broadcasted_iota(jnp.int32,
                                       (block, n_dest), 1)  # [TJ, S]
        onehot = (seg == ids_j[:, None]).astype(jnp.float32)
        out_ref[:] += jnp.dot(lower, onehot,
                              preferred_element_type=jnp.float32)


def rank_by_dest(dest: jax.Array, n_dest: int, *, block: int = 256,
                 use_pallas: bool | None = None,
                 interpret: bool = False) -> jax.Array:
    """rank[i] = position of message i within its destination group.

    dest: [B] int32 in [0, n_dest) — map invalid lanes to a sink id in
    [0, n_dest) *before* calling. Returns [B] int32.

    ``use_pallas=None`` (what the main path passes): the compiled MXU
    kernel on a TPU for B >= 512, plain XLA everywhere else — the sort
    rank off-TPU, the pairwise mask for small batches. Interpret mode is
    never chosen here; a test asks for it with ``interpret=True``.
    """
    B = dest.shape[0]
    d = dest.astype(jnp.int32)
    if use_pallas is None:
        use_pallas = B >= 512 and (
            interpret or jax.default_backend() == "tpu")
    if not use_pallas:
        if B >= 512:
            return rank_dense_keys(d)
        # small batches: the O(B^2) pairwise mask fits comfortably on-chip
        row = d[:, None] == d[None, :]
        lower = jnp.tril(jnp.ones((B, B), jnp.bool_), -1)
        return jnp.sum(row & lower, axis=1).astype(jnp.int32)
    block = min(block, B)
    Bp = -(-B // block) * block
    Sp = max(8, -(-n_dest // 8) * 8)
    dp = jnp.pad(d, (0, Bp - B), constant_values=Sp - 1) if Bp != B else d
    counts = pl.pallas_call(
        functools.partial(_prefix_kernel, block=block, n_dest=Sp),
        grid=(Bp // block, Bp // block),
        in_specs=[pl.BlockSpec((1, block), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((block, Sp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Sp), jnp.float32),
        interpret=interpret,
    )(dp[None, :])
    rank = jnp.take_along_axis(counts[:B], d[:, None], axis=1)[:, 0]
    return rank.astype(jnp.int32)


def rank_dense_keys(keys: jax.Array) -> jax.Array:
    """rank[i] = position of element i within its key group — the same
    prefix count as :func:`rank_by_dest`, for LARGE key spaces.

    Regime split: the MXU prefix-count builds an O(B x S) table — ideal
    when S is the shard count (routing), ruinous when S is an actor space
    (fan-in append to 64k timelines). Here the rank comes from one stable
    argsort + a cumulative max (O(B log^2 B) sort beats an O(B*S) table
    once S >> log^2 B). keys: [B] int32 (any values). Returns [B] int32.
    """
    B = keys.shape[0]
    order = jnp.argsort(keys, stable=True)
    ks = keys[order]
    idx = jnp.arange(B, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    group_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    rank_sorted = idx - group_start
    return jnp.zeros((B,), jnp.int32).at[order].set(rank_sorted)


def pack_by_dest(dest: jax.Array, valid: jax.Array, payload: dict,
                 n_dest: int, capacity: int, **rank_kw):
    """Sort-free outbox pack (drop-in for transport._pack_outbox semantics).

    Returns (out_payload dict [n_dest, capacity, ...], out_valid
    [n_dest, capacity], drops scalar). Overflow beyond ``capacity`` per
    destination is dropped and counted — the overload-shedding analog of
    ``ActivationData.CheckOverloaded`` (ActivationData.cs:616).
    """
    in_range = (dest >= 0) & (dest < n_dest)
    ok = valid & in_range
    d = jnp.where(ok, dest, n_dest).astype(jnp.int32)
    if dest.shape[0] > 32768 and not rank_kw:
        # the MXU prefix count is O(B^2); past ~32k lanes the sort-based
        # rank's O(B log^2 B) wins even on TPU
        rank = rank_dense_keys(d)
    else:
        rank = rank_by_dest(d, n_dest + 1, **rank_kw)
    keep = ok & (rank < capacity)
    drops = jnp.sum(ok & ~keep) + jnp.sum(valid & ~in_range)
    sink = n_dest * capacity
    flat = jnp.where(keep, d * capacity + jnp.minimum(rank, capacity - 1),
                     sink)

    def scatter(x):
        buf = jnp.zeros((n_dest * capacity + 1, *x.shape[1:]), x.dtype)
        return buf.at[flat].set(x)[:-1].reshape(
            n_dest, capacity, *x.shape[1:])

    out_payload = jax.tree_util.tree_map(scatter, payload)
    out_valid = jnp.zeros((n_dest * capacity + 1,), bool).at[flat].set(
        keep)[:-1].reshape(n_dest, capacity)
    return out_payload, out_valid, drops
