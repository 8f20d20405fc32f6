"""Masked first-fit Pallas-TPU kernel: the inner step of batched matching.

For a block of check-in rows, fuse the availability mask (eligibility x
"request not yet filled at this position") with the first-true-lane reduction
that picks each row's candidate slot:

    avail[i, k] = elig[i, k] != 0  and  fillcand[i, k] >= pos[i]
    kidx[i]     = min { k : avail[i, k] },  or K when empty

The candidate axis is padded to the 128-lane boundary and kept resident per
block, so the whole step is one VPU compare + masked lane-min per tile — no
gathers (the per-candidate fill positions are pre-gathered by the caller,
which is a cheap ``fill[safe_req]`` index outside the kernel).  Grid tiles
the row axis only; blocks are ``(block_n, Kp)`` int32 in VMEM, and the
result is written as a ``(block_n, 1)`` column.

``interpret`` defaults to True off-TPU (same convention as
:mod:`repro.kernels.ops`), which is how the CPU tests run it against the
oracle in :mod:`repro.accel.kernels.ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(elig_ref, fill_ref, pos_ref, o_ref, *, kp: int):
    avail = (elig_ref[...] != 0) & (fill_ref[...] >= pos_ref[...])
    iota = jax.lax.broadcasted_iota(jnp.int32, avail.shape, 1)
    o_ref[...] = jnp.min(jnp.where(avail, iota, jnp.int32(kp)), axis=1,
                         keepdims=True)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def masked_first_fit(elig: jax.Array, fillcand: jax.Array, pos: jax.Array,
                     *, block_n: int = 256, interpret: bool = None
                     ) -> jax.Array:
    """``(n, K)`` int32 ``elig``/``fillcand`` + ``(n,)`` int32 ``pos`` ->
    ``(n,)`` int32 first available candidate index (``K`` = none).

    The returned index refers to the *unpadded* candidate axis: lanes added
    by 128-padding are never eligible, and any index >= K means "no slot".
    """
    interpret = _default_interpret() if interpret is None else interpret
    n, K = elig.shape
    kp = max(128, -(-K // 128) * 128)
    bn = min(block_n, max(8, -(-n // 8) * 8))
    pn = (-n) % bn
    pk = kp - K
    elig_i = elig.astype(jnp.int32)
    fill_i = fillcand.astype(jnp.int32)
    if pk:
        elig_i = jnp.pad(elig_i, ((0, 0), (0, pk)))        # padded lanes: 0
        fill_i = jnp.pad(fill_i, ((0, 0), (0, pk)))
    if pn:
        elig_i = jnp.pad(elig_i, ((0, pn), (0, 0)))
        fill_i = jnp.pad(fill_i, ((0, pn), (0, 0)))
    pos_i = jnp.pad(pos.astype(jnp.int32), (0, pn))[:, None]
    np_, _ = elig_i.shape

    out = pl.pallas_call(
        functools.partial(_kernel, kp=kp),
        grid=(np_ // bn,),
        in_specs=[
            pl.BlockSpec((bn, kp), lambda ni: (ni, 0)),
            pl.BlockSpec((bn, kp), lambda ni: (ni, 0)),
            pl.BlockSpec((bn, 1), lambda ni: (ni, 0)),
        ],
        # a (bn, 1) column block: a 1-D (bn,) output block is refused by
        # Mosaic once the grid has more than one block (its tiling differs
        # from the XLA layout of the whole array)
        out_specs=pl.BlockSpec((bn, 1), lambda ni: (ni, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.int32),
        interpret=interpret,
    )(elig_i, fill_i, pos_i)
    return jnp.minimum(out[:n, 0], jnp.int32(K))
