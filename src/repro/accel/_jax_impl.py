"""Jitted fill-position fixed point (the JAX backend of the array engine).

Mirrors :func:`repro.accel.engine.match_chunk` on static padded shapes:
``lax.while_loop`` over the fill-position vector, with the inner masked
first-fit either as the pure-jnp oracle or the Pallas kernel.  Inputs are
int32 and power-of-two padded by the caller so many segment sizes share a
handful of compiled programs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernels.ref import masked_first_fit_ref
from .kernels.schedule_match import masked_first_fit


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _match_jax(reqix, elig, rem, use_kernel=False):
    """``reqix``/``elig``: (n, K); ``rem``: (R,).  Padded rows have no
    eligible slot, padded requests have ``rem == 0``.  Returns
    ``(choice, granted)`` over the padded row axis and the fixed point's
    iteration count."""
    n, K = reqix.shape
    R = rem.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    safe = jnp.where(reqix >= 0, reqix, 0).astype(jnp.int32)
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
    granted = jnp.zeros(n, dtype=bool).at[p_s].set(g_sorted)
    return choice, granted, iters
