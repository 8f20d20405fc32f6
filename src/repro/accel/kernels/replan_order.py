"""Segmented-rank Pallas-TPU kernel: the replan's intra-group ordering step.

The incremental replan engine (:mod:`repro.accel.replan`) orders each job
group by ``(demand_key, job_id)`` ascending — Alg. 1 lines 2-3 as a segmented
argsort over the concatenated job arrays of every group.  On TPU the natural
formulation is a **masked compare-count**: for each job row ``i``,

    rank[i] = |{ j : seg[j] == seg[i]
                 and (key[j], tie[j]) <lex (key[i], tie[i]) }|

which is each job's position within its group's sorted order (ranks are a
permutation of ``0..len(segment)-1`` because ties are broken by the unique
job id).  The O(n^2) compare matrix is one VPU pass per row tile: the column
arrays stay resident (padded to the 128-lane boundary), the grid tiles the
row axis, and each tile is two broadcast compares + a masked row-sum — no
gathers, no sorting network.

This is the ride-along demonstrator for the replan path (f32 keys, same
``interpret``-off-TPU convention as :mod:`.schedule_match`); the production
CPU engine stays NumPy ``lexsort`` on f64 because the exactness bar there is
bit-identity with Python-float scalar sorts.  The pure-jnp oracle
(:func:`repro.accel.kernels.ref.segmented_rank_ref`) is the correctness
contract; ``segmented_order`` shows ranks -> per-segment permutation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .schedule_match import _default_interpret


def _kernel(seg_r, key_r, tie_r, seg_c, key_c, tie_c, o_ref):
    # row blocks (bn, 1) against the full resident column axis (1, np)
    same = seg_c[...] == seg_r[...]
    less = (key_c[...] < key_r[...]) | ((key_c[...] == key_r[...])
                                        & (tie_c[...] < tie_r[...]))
    o_ref[...] = jnp.sum((same & less).astype(jnp.int32), axis=1,
                         keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def segmented_rank(seg_ids: jax.Array, keys: jax.Array, ties: jax.Array,
                   *, block_n: int = 128, interpret: bool = None
                   ) -> jax.Array:
    """``(n,)`` int32 ``seg_ids`` (group of each job, >= 0) + ``(n,)`` f32
    ``keys`` (demand keys) + ``(n,)`` int32 ``ties`` (job ids, unique within
    a segment) -> ``(n,)`` int32 rank of each job within its segment under
    ``(key, tie)`` ascending."""
    interpret = _default_interpret() if interpret is None else interpret
    n = seg_ids.shape[0]
    np_ = max(128, -(-n // 128) * 128)
    bn = min(block_n, max(8, -(-n // 8) * 8))
    pn = np_ - n
    seg = seg_ids.astype(jnp.int32)
    key = keys.astype(jnp.float32)
    tie = ties.astype(jnp.int32)
    if pn:
        # padded columns get segment -1: they never match a real row's
        # segment, so they contribute nothing to any real rank
        seg = jnp.pad(seg, (0, pn), constant_values=-1)
        key = jnp.pad(key, (0, pn))
        tie = jnp.pad(tie, (0, pn))
    rows = -(-np_ // bn)

    out = pl.pallas_call(
        _kernel,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda ni: (ni, 0)),
            pl.BlockSpec((bn, 1), lambda ni: (ni, 0)),
            pl.BlockSpec((bn, 1), lambda ni: (ni, 0)),
            pl.BlockSpec((1, np_), lambda ni: (0, 0)),
            pl.BlockSpec((1, np_), lambda ni: (0, 0)),
            pl.BlockSpec((1, np_), lambda ni: (0, 0)),
        ],
        # (bn, 1) column blocks, as in schedule_match: Mosaic refuses a
        # 1-D output block on a multi-block grid
        out_specs=pl.BlockSpec((bn, 1), lambda ni: (ni, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.int32),
        interpret=interpret,
    )(seg[:, None], key[:, None], tie[:, None],
      seg[None, :], key[None, :], tie[None, :])
    return out[:n, 0]


def segmented_order(seg_ids: jax.Array, keys: jax.Array, ties: jax.Array,
                    *, interpret: bool = None) -> jax.Array:
    """Ranks -> the sorting permutation: ``perm[seg_start + rank[i]] = i``
    for each segment laid out contiguously in first-appearance order.  The
    scatter target is ``segment offset + within-segment rank`` — exactly the
    ``job_order`` layout the replan engine publishes per group."""
    rank = segmented_rank(seg_ids, keys, ties, interpret=interpret)
    seg = seg_ids.astype(jnp.int32)
    nseg = jnp.max(seg, initial=-1) + 1
    counts = jnp.zeros((nseg,), jnp.int32).at[seg].add(1)
    starts = jnp.cumsum(counts) - counts
    slot = starts[seg] + rank
    n = seg.shape[0]
    return jnp.zeros((n,), jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32))
