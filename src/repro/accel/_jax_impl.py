"""Jitted fill-position fixed point (the JAX backend of the array engine).

Mirrors :func:`repro.accel.engine.match_chunk` on static padded shapes:
``lax.while_loop`` over the fill-position vector, with the inner masked
first-fit either as the pure-jnp oracle or the Pallas kernel.  The caller
packs the inputs into one int32 buffer and reads one int32 buffer back
(layouts in :func:`repro.accel.engine.match_chunk_jax`); shapes are
power-of-two padded so many segment sizes share a handful of compiled
programs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernels.ref import masked_first_fit_ref
from .kernels.schedule_match import masked_first_fit


@functools.partial(jax.jit, static_argnames=("rows", "slots", "use_kernel"))
def _match_jax(buf, rows, slots, use_kernel=False):
    """``buf``: (rows * slots + R,) int32, the ``(rows, slots)`` candidate
    matrix with eligibility folded in (``-1`` = not eligible) followed by
    ``rem``.  Padded rows have no eligible slot, padded requests have
    ``rem == 0``.  Returns one (2 * rows + 1,) int32 array: ``choice`` and
    ``granted`` over the padded row axis, then the fixed point's iteration
    count."""
    n, K = rows, slots
    reqix = buf[:n * K].reshape(n, K)
    rem = buf[n * K:]
    R = rem.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    elig = reqix >= 0
    safe = jnp.where(elig, reqix, 0)
    elig_i = elig.astype(jnp.int32)
    first_fit = masked_first_fit if use_kernel else masked_first_fit_ref

    def choice_of(fill):
        kidx = first_fit(elig_i, fill[safe], pos)
        has = kidx < K
        kcl = jnp.minimum(kidx, K - 1)[:, None]
        return jnp.where(has,
                         jnp.take_along_axis(reqix, kcl, axis=1)[:, 0], -1)

    def ranks_of(choice):
        """Stable (request, position) sort -> per-request chooser ranks."""
        ch_key = jnp.where(choice >= 0, choice, R).astype(jnp.int32)
        order = jnp.lexsort((pos, ch_key))
        ch_s = ch_key[order]
        p_s = pos[order]
        newgrp = jnp.concatenate(
            [jnp.ones(1, dtype=bool), ch_s[1:] != ch_s[:-1]])
        starts = jax.lax.cummax(jnp.where(newgrp, pos, 0), axis=0)
        rank = pos - starts                     # pos == arange(n) here
        valid = ch_s < R
        return ch_s, p_s, rank, valid

    def fills_of(choice):
        ch_s, p_s, rank, valid = ranks_of(choice)
        remg = rem[jnp.minimum(ch_s, R - 1)]
        is_last = valid & (remg > 0) & (rank == remg - 1)
        new_fill = jnp.where(rem > 0, n, -1).astype(jnp.int32)
        idx = jnp.where(is_last, ch_s, R)       # R = dropped (out of bounds)
        return new_fill.at[idx].set(jnp.where(is_last, p_s, 0), mode="drop")

    fill0 = jnp.where(rem > 0, n, -1).astype(jnp.int32)

    def cond(carry):
        prev, cur, it = carry
        return jnp.any(prev != cur) & (it < R + 2)

    def body(carry):
        _, cur, it = carry
        return cur, fills_of(choice_of(cur)), it + 1

    _, fill, iters = jax.lax.while_loop(
        cond, body, (fill0 - 1, fill0, jnp.int32(0)))
    choice = choice_of(fill)
    ch_s, p_s, rank, valid = ranks_of(choice)
    remg = rem[jnp.minimum(ch_s, R - 1)]
    g_sorted = valid & (rank < remg)
    granted = jnp.zeros(n, dtype=jnp.int32).at[p_s].set(
        g_sorted.astype(jnp.int32))
    return jnp.concatenate([choice, granted, iters[None]])
