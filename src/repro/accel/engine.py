"""Batched check-in matching: one call per drain segment instead of one
``scheduler.checkin`` per device.

Between two control events the scheduler's decision state is frozen (plans
only change on request arrival/completion, which are heap events), except
that requests *fill* as grants are handed out.  Matching a whole segment is
therefore a sequential-capacity problem: process check-ins in time order,
give each its first eligible live slot, decrement that request's remaining
demand.  :func:`match_chunk` solves it without a per-device loop via a
**fill-position fixed point**:

1. assume no request fills inside the segment (``fillpos[r] = n``);
2. give every check-in its first candidate slot whose tier band accepts its
   speed and whose request is not yet filled *at the check-in's position*
   (a masked first-fit over the ``(n, K)`` candidate matrix — the step the
   Pallas kernel accelerates);
3. recompute each request's fill position (the position of its
   ``remaining[r]``-th chooser, via one stable argsort + segment counts);
4. repeat from 2 until the fill positions stop moving.

Fill positions only ever move earlier (a device falls to a lower-priority
slot only when an earlier fill invalidates its pick, adding choosers —
never removing early ones), so the loop converges in at most
``#requests-that-fill + 1`` iterations — typically 1–3 — each fully
vectorized.  The result is bit-identical to the sequential scan; a
sequential reference (:func:`match_chunk_seq`) backs the property tests and
serves as a safety net on non-convergence.

Backends: ``numpy`` (the fixed point in NumPy), ``jax`` (jitted
``lax.while_loop`` on padded shapes), and the ``jax`` backend with
``use_kernel=True`` routing the inner masked first-fit through the Pallas
kernel (:mod:`repro.accel.kernels.schedule_match`).  The platform chooses
(:func:`platform_backend`): ``jax`` with the kernel where JAX runs on a
TPU, ``numpy`` on the CPU.  Explicit ``backend=``/``use_kernel=`` arguments
exist for tests.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional, Tuple

import numpy as np

from ..obs import audit as _obsaudit
from ..obs import metrics as _obsmetrics
from ..obs import trace as _obstrace
from .state import MatchState

__all__ = ["ArrayMatchEngine", "MatchResult", "SEG_ROWS", "match_chunk",
           "match_chunk_seq", "platform_backend", "use_compile_cache"]

# Upper bound on check-in rows per match call.  Prefix consistency makes
# slicing exact (a device's outcome depends only on earlier devices), and the
# cap bounds the dense (rows x candidates) working set regardless of how
# quiet the control heap is.
SEG_ROWS = 16384

# Below this many rows a segment is processed scalar-style (per-device
# ``checkin``): fixed NumPy call overhead (~20-30us per match) beats the
# Python loop only once a segment amortizes it.  Keeps the array engine
# no-worse-than-python on workloads whose control events chop the stream
# finely, while platform-scale streams ride the vectorized path.
SCALAR_SEG_ROWS = 32


class NeedWiderExport(Exception):
    """A capped-export row exhausted its prefix mid-match: the engine has
    widened its cap and invalidated the state; the caller re-prepares and
    re-matches the same segment (exact — no side effects happened yet)."""


@dataclass
class MatchResult:
    """Outcome of one segment match.

    ``choice[i]`` is the request index (into ``state.requests``) check-in
    ``i`` would be assigned, ``-1`` if no slot wants it; ``granted[i]`` is
    True where the assignment holds under capacity (the first
    ``remaining[r]`` choosers of each request ``r``, in time order)."""

    choice: np.ndarray
    granted: np.ndarray


# --------------------------------------------------------------------------- #
# Sequential reference (the semantics contract)
# --------------------------------------------------------------------------- #

def match_chunk_seq(atom_ids: np.ndarray, speeds: np.ndarray,
                    state: MatchState) -> MatchResult:
    """Per-device sequential matching — the oracle ``match_chunk`` must equal.

    Mirrors ``DispatchTable.assign`` / ``BaseScheduler.checkin``: scan the
    atom's candidate slots in priority order, skip filled requests and
    mismatched tier bands, grant the first fit."""
    n = len(atom_ids)
    rem = state.remaining.copy()
    cand_req, lo, hi = state.cand_req, state.cand_lo, state.cand_hi
    choice = np.full(n, -1, dtype=np.int64)
    granted = np.zeros(n, dtype=bool)
    K = cand_req.shape[1]
    for i in range(n):
        a = int(atom_ids[i])
        s = float(speeds[i])
        for k in range(K):
            r = cand_req[a, k]
            if r < 0:
                break
            if rem[r] > 0 and lo[a, k] <= s < hi[a, k]:
                choice[i] = r
                granted[i] = True
                rem[r] -= 1
                break
    return MatchResult(choice, granted)


# --------------------------------------------------------------------------- #
# Vectorized fixed point (NumPy)
# --------------------------------------------------------------------------- #

def _group_ranks(choice: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                              np.ndarray, np.ndarray]:
    """For check-ins with a choice: stable sort by request, returning
    ``(sel_idx, sorted_choice, sorted_pos, rank_within_request)``."""
    sel = np.flatnonzero(choice >= 0)
    ch = choice[sel]
    order = np.argsort(ch, kind="stable")         # positions stay ascending
    ch_s = ch[order]
    p_s = sel[order]
    new_grp = np.empty(len(ch_s), dtype=bool)
    if len(ch_s):
        new_grp[0] = True
        np.not_equal(ch_s[1:], ch_s[:-1], out=new_grp[1:])
    starts = np.flatnonzero(new_grp)
    grp = np.cumsum(new_grp) - 1
    rank_s = np.arange(len(ch_s)) - starts[grp] if len(ch_s) \
        else np.zeros(0, dtype=np.int64)
    return sel, ch_s, p_s, rank_s


def match_chunk(atom_ids: np.ndarray, speeds: np.ndarray,
                state: MatchState, max_iters: Optional[int] = None
                ) -> MatchResult:
    """Vectorized segment matching (NumPy fill-position fixed point)."""
    n = len(atom_ids)
    rem = state.remaining
    R = len(rem)
    if n == 0 or R == 0:
        return MatchResult(np.full(n, -1, dtype=np.int64),
                           np.zeros(n, dtype=bool))
    reqix = state.cand_req[atom_ids]                       # (n, K)
    sp = speeds[:, None]
    elig = (reqix >= 0) & (state.cand_lo[atom_ids] <= sp) \
        & (sp < state.cand_hi[atom_ids])
    safe = np.where(reqix >= 0, reqix, 0)
    pos = np.arange(n, dtype=np.int64)
    fillpos = np.where(rem > 0, n, -1).astype(np.int64)
    iters = max_iters if max_iters is not None else R + 2
    choice = None
    for it in range(iters):
        avail = elig & (fillpos[safe] >= pos[:, None])
        anyav = avail.any(axis=1)
        kfirst = np.argmax(avail, axis=1)
        choice = np.where(anyav, reqix[pos, kfirst], -1)
        new_fill = np.where(rem > 0, n, -1).astype(np.int64)
        sel, ch_s, p_s, rank_s = _group_ranks(choice)
        if len(ch_s):
            last = rank_s == rem[ch_s] - 1        # the filling grant per req
            new_fill[ch_s[last]] = p_s[last]
        if np.array_equal(new_fill, fillpos):
            reg = _obsmetrics.REGISTRY
            if reg.enabled:
                reg.histogram("accel.fixedpoint_iters",
                              lo=1.0, hi=1e3,
                              buckets_per_decade=20).record(it + 1)
            granted = np.zeros(n, dtype=bool)
            granted[p_s] = rank_s < rem[ch_s]
            return MatchResult(choice, granted)
        fillpos = new_fill
    # Safety net: the fixed point is proven to converge within R+2 rounds;
    # fall back to the sequential scan rather than crash if that ever breaks.
    return match_chunk_seq(atom_ids, speeds, state)       # pragma: no cover


# --------------------------------------------------------------------------- #
# JAX backend (jitted fixed point on padded shapes)
# --------------------------------------------------------------------------- #

def _pow2(x: int) -> int:
    return 1 << max(0, (x - 1)).bit_length()


def _pack_jax(atom_ids: np.ndarray, speeds: np.ndarray, state: MatchState
              ) -> Tuple[np.ndarray, int, int]:
    """The one input buffer of :func:`match_chunk_jax` (layout there) and
    its padded ``(np_pad, kp)``."""
    rem = state.remaining
    reqix = state.cand_req[atom_ids]
    sp = speeds[:, None]
    elig = (reqix >= 0) & (state.cand_lo[atom_ids] <= sp) \
        & (sp < state.cand_hi[atom_ids])
    n, K = reqix.shape
    np_pad, kp, rp = _pow2(n), _pow2(K), _pow2(len(rem))
    cells = np_pad * kp
    buf = np.full(cells + rp, -1, dtype=np.int32)
    buf[:cells].reshape(np_pad, kp)[:n, :K] = np.where(elig, reqix, -1)
    buf[cells:] = 0
    buf[cells:cells + len(rem)] = rem
    return buf, np_pad, kp


def match_chunk_jax(atom_ids: np.ndarray, speeds: np.ndarray,
                    state: MatchState, use_kernel: bool = False
                    ) -> MatchResult:
    """Jitted fixed point, fed by one host-to-device copy and read by one
    device-to-host copy.  Shapes are padded to powers of two (``np_pad``
    rows, ``kp`` slots, ``rp`` requests) so replaying many segment sizes
    reuses a handful of compiled programs; with ``use_kernel=True`` the
    inner masked first-fit runs as the Pallas kernel (interpret mode
    off-TPU).

    In: one flat int32 buffer of ``np_pad * kp + rp`` entries, the
    row-major ``(np_pad, kp)`` candidate matrix with eligibility folded in
    (``reqe = where(elig, reqix, -1)``; padding ``-1``) followed by
    ``remaining`` (padding ``0``).  Folding is exact: the fixed point reads
    a slot's request only where the slot is available, and available
    implies eligible, so ``elig = reqe >= 0`` and ``reqix = reqe`` give the
    same ``choice``, ``granted`` and iteration count.  The tier-band test
    stays here on the host, in float64.

    Out: one int32 array of ``2 * np_pad + 1`` entries, ``choice``, then
    ``granted`` (0/1), then the fixed point's iteration count; its copy to
    the host starts as soon as the call is dispatched.

    Traced as four children of ``accel.match``: ``accel.jax.pack`` (host
    gather and packing), ``.put`` (empty, with the copy's ``bytes``),
    ``.run`` (the host-to-device copy and the jitted call, dispatched) and
    ``.fetch`` (the host waits for the device and reads the output)."""
    from ._jax_impl import _match_jax
    n = len(atom_ids)
    if n == 0 or len(state.remaining) == 0:
        return MatchResult(np.full(n, -1, dtype=np.int64),
                           np.zeros(n, dtype=bool))
    tr = _obstrace.TRACER
    reg = _obsmetrics.REGISTRY
    tok = tr.begin("accel.jax.pack", cat="accel") if tr.enabled else None
    buf, np_pad, kp = _pack_jax(atom_ids, speeds, state)
    if tok is not None:
        tr.end(tok, n=np_pad, r=len(buf) - np_pad * kp, k=kp)
        # the jitted call copies the NumPy buffer to the device as it
        # dispatches, which the chip does faster than a device_put of its
        # own: ``put`` only marks the copy's bytes, ``run`` holds its time
        tr.end(tr.begin("accel.jax.put", cat="accel", bytes=buf.nbytes))
        tok = tr.begin("accel.jax.run", cat="accel")
    out = _match_jax(buf, np_pad, kp, use_kernel=use_kernel)
    out.copy_to_host_async()
    if tok is not None:
        tr.end(tok)
        tok = tr.begin("accel.jax.fetch", cat="accel")
    host = np.asarray(out)
    res = MatchResult(host[:n].astype(np.int64),
                      host[np_pad:np_pad + n].astype(bool))
    if reg.enabled:
        reg.counter("accel.jax_calls").inc()
        reg.counter("accel.host_copies").inc(2)
        reg.counter("accel.h2d_bytes").inc(buf.nbytes)
        reg.histogram("accel.fixedpoint_iters", lo=1.0, hi=1e3,
                      buckets_per_decade=20).record(int(host[2 * np_pad]))
    if tok is not None:
        tr.end(tok)
    return res


# JAX's persistent compilation cache, when JAX_COMPILATION_CACHE_DIR does not
# place it: a fixed path inside the checkout (the path is part of the
# cache's key, so a moving directory never hits)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads
    it itself), else :data:`COMPILE_CACHE_DIR`.  Entry points call this;
    importing the package never does."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def platform_backend() -> Tuple[str, bool]:
    """``(backend, use_kernel)`` for the platform JAX runs on: the jitted
    fixed point with the Pallas kernel on a TPU, NumPy on the CPU."""
    import jax
    if jax.default_backend() == "tpu":
        return "jax", True
    return "numpy", False


# --------------------------------------------------------------------------- #
# Simulator-facing driver
# --------------------------------------------------------------------------- #

class ArrayMatchEngine:
    """Owns the :class:`MatchState` cache and backend selection for a
    :class:`~repro.sim.simulator.Simulator` running with ``engine="array"``.

    Protocol (driven by the simulator's array drain):

    * ``prepare(sched, now)`` — make the scheduler's compiled state current
      (its lazy replan, at the same instant the scalar path would run it) and
      return the cached/rebuilt :class:`MatchState`;
    * ``match(atom_ids, speeds)`` — batched segment matching;
    * grants the simulator applies are mirrored via ``state.consume``.
    """

    def __init__(self, backend: Optional[str] = None,
                 use_kernel: Optional[bool] = None, kcap: int = 32,
                 replan_budget_s: Optional[float] = None):
        if backend is None:
            backend, kernel = platform_backend()
            if use_kernel is None:
                use_kernel = kernel
        if backend not in ("numpy", "jax"):
            raise ValueError(f"unknown accel backend {backend!r}")
        self.backend = backend
        self.use_kernel = bool(use_kernel)
        self.kcap = kcap                # adaptive candidate cap, sticky upward
        self.state: Optional[MatchState] = None
        self.rebuilds = 0
        self.segments = 0
        self.backend_calls = 0          # segments matched by the backend
        self.expansions = 0
        # ---- mirror deltas ----
        # On a token change the engine asks the scheduler for the dirty-atom
        # set since the mirror's token (match_delta) and patches only those
        # rows; a None answer (structural change: atom-universe growth,
        # partition refinement, fairness drift, restore) falls back to the
        # full rebuild.  REPRO_MATCH_DELTA=0 pins the full-rebuild path;
        # REPRO_MATCH_CHECK=1 re-derives the mirror from scheduler truth
        # after every patch and raises on drift (the paranoid mode,
        # mirroring REPRO_REPLAN_CHECK).
        self.delta_enabled = os.environ.get("REPRO_MATCH_DELTA", "1") != "0"
        self.check_deltas = bool(os.environ.get("REPRO_MATCH_CHECK"))
        self.patches = 0                # token changes served by st.patch
        self.rebuild_s = 0.0            # wall time in full mirror rebuilds
        self.patch_s = 0.0              # wall time in mirror patches
        # request-table compaction: patched mirrors keep inert entries for
        # retired requests; once the table outgrows the last rebuild's size
        # 4x, rebuild (geometric, so the amortized cost stays O(1)/replan)
        self._rebuilt_requests = 0
        # ---- graceful degradation (opt-in / counters) ----
        # replan_budget_s: minimum simulated seconds between replans; a dirty
        # plan inside the budget is served stale (sanitized for dead
        # requests) instead of recompiled.  Trades exactness for bounded
        # replan cost under churn — OFF by default, and incompatible with
        # cross-engine bit-equality when it actually fires.
        self.replan_budget_s = replan_budget_s
        self.degraded_segments = 0      # vectorized calls served by the
        #                                 sequential oracle (guard tripped)
        self.stale_plans_served = 0     # replans skipped under the budget
        self.staleness_s = 0.0          # cumulative age of stale plans served
        self._last_replan_t = -np.inf

    def __getstate__(self):
        # MatchState caches id()-keyed request maps — meaningless across a
        # pickle boundary.  Snapshot without it; the next prepare() rebuilds
        # from restored scheduler state (exactness via the usual protocol).
        d = dict(self.__dict__)
        d["state"] = None
        return d

    def prepare(self, sched, now: float) -> MatchState:
        if (self.replan_budget_s is not None and self.state is not None
                and getattr(sched, "_plan_dirty", False)
                and now - self._last_replan_t < self.replan_budget_s):
            # serve the stale plan: zero capacity of requests that are no
            # longer live so no grant can reach them; new requests simply
            # wait out the budget (recorded staleness, never corruption)
            st = self.state
            rem = st.remaining
            for i, r in enumerate(st.requests):
                if rem[i] > 0 and (r.complete_time is not None
                                   or r.job.current is not r):
                    rem[i] = 0
            self.stale_plans_served += 1
            self.staleness_s += now - self._last_replan_t
            tr = _obstrace.TRACER
            if tr.enabled:
                tr.instant("accel.stale_plan", cat="accel", sim_t=now,
                           age_s=now - self._last_replan_t)
            aud = _obsaudit.AUDIT
            if aud.enabled:
                # flight recorder: grants served off this stale plan are
                # flagged — stale serving is the documented waiver of the
                # audit stream's cross-engine byte-identity
                aud.stale_plan(now)
            return st
        was_dirty = bool(getattr(sched, "_plan_dirty", True))
        sched.prepare_match(now)
        token = sched.match_token()
        st = self.state
        if st is None or st.token != token:
            tr = _obstrace.TRACER
            reg = _obsmetrics.REGISTRY
            dirty = None
            if st is not None and self.delta_enabled:
                delta = getattr(sched, "match_delta", None)
                if delta is not None:
                    dirty = delta(st.token)
                if dirty is not None and len(st.requests) > max(
                        128, 4 * self._rebuilt_requests):
                    # patched mirrors accrete inert entries for retired
                    # requests; compact via a full rebuild once the table
                    # outgrows the last rebuild 4x (geometric amortization)
                    dirty = None
            if dirty is not None:
                tok = tr.begin("accel.state_delta", cat="accel") \
                    if tr.enabled else None
                t0 = time.perf_counter()
                st.patch(sched, token, dirty)
                self.patch_s += time.perf_counter() - t0
                if tok is not None:
                    tr.end(tok, atoms=len(dirty), requests=len(st.requests))
                self.patches += 1
                if reg.enabled:
                    reg.counter("accel.state_patches").inc()
                if self.check_deltas:
                    st.verify_against(sched)
            else:
                tok = tr.begin("accel.state_rebuild", cat="accel") \
                    if tr.enabled else None
                t0 = time.perf_counter()
                st = self.state = MatchState.from_scheduler(
                    sched, token, kcap=self.kcap,
                    # exported prefixes keep the per-replan rebuild
                    # O(atoms x limit); exhaustion re-exports wider
                    export_limit=max(4 * self.kcap, 128))
                self.rebuild_s += time.perf_counter() - t0
                if tok is not None:
                    tr.end(tok, num_atoms=st.num_atoms,
                           requests=len(st.requests))
                self.rebuilds += 1
                self._rebuilt_requests = len(st.requests)
                if reg.enabled:
                    reg.counter("accel.state_rebuilds").inc()
            # NOTE: classify() can intern new atom ids without a version
            # bump, so callers must re-check num_atoms per segment —
            # miss_free alone only certifies the id space seen at build
            st.miss_free = st.all_covered \
                and st.num_atoms == sched.index.num_atoms
        if was_dirty or self._last_replan_t == -np.inf:
            self._last_replan_t = now
        return st

    def invalidate(self) -> None:
        self.state = None

    def match(self, atom_ids: np.ndarray, speeds: np.ndarray) -> MatchResult:
        """Match one segment slice (all atoms covered — MISS rows are bounded
        out by the caller).  Rows of candidate-free atoms can never match, so
        the fixed point runs on the live subset only; dead traffic costs one
        gather."""
        tr = _obstrace.TRACER
        if not tr.enabled:
            return self._match_impl(atom_ids, speeds)
        tok = tr.begin("accel.match", cat="accel", rows=len(atom_ids),
                       backend=self.backend)
        try:
            res = self._match_impl(atom_ids, speeds)
        except NeedWiderExport:
            tr.end(tok, outcome="need_wider_export")
            raise
        tr.end(tok, granted=int(res.granted.sum()))
        return res

    def _match_impl(self, atom_ids: np.ndarray, speeds: np.ndarray
                    ) -> MatchResult:
        self.segments += 1
        st = self.state
        n = len(atom_ids)
        live = st.has_cand[atom_ids]
        idx = np.flatnonzero(live)
        choice = np.full(n, -1, dtype=np.int64)
        granted = np.zeros(n, dtype=bool)
        if len(idx) == 0:
            return MatchResult(choice, granted)
        sub_ids = atom_ids[idx]
        sub_speeds = speeds[idx]
        while True:
            if self.backend == "numpy" and len(idx) <= 24:
                # tiny live subset: the per-row scan beats a dozen NumPy
                # calls on 10-element arrays
                res = match_chunk_seq(sub_ids, sub_speeds, st)
            else:
                res = self._match_guarded(sub_ids, sub_speeds, st)
            # a truncated atom's row that exhausted its capped prefix might
            # have a deeper live slot: widen the cap and re-match (exact;
            # needs ~cap fills inside one segment, so it is rare)
            suspect = (res.choice < 0) & st.truncated[sub_ids]
            if not suspect.any():
                break
            self.expansions += 1
            tr = _obstrace.TRACER
            if tr.enabled:
                tr.instant("accel.expand", cat="accel", kcap=st.kcap)
            reg = _obsmetrics.REGISTRY
            if reg.enabled:
                reg.counter("accel.expansions").inc()
            if not st.expand():
                # the stored rows themselves were export-capped prefixes:
                # widen the cap and have the caller rebuild + re-match
                if reg.enabled:
                    reg.counter("accel.wider_exports").inc()
                self.kcap = max(self.kcap * 2, st.kcap * 2)
                self.state = None
                raise NeedWiderExport
            self.kcap = max(self.kcap, st.kcap)
        choice[idx] = res.choice
        granted[idx] = res.granted
        return MatchResult(choice, granted)

    # ------------------------------------------------- graceful degradation

    def _match_guarded(self, sub_ids: np.ndarray, sub_speeds: np.ndarray,
                       st: MatchState) -> MatchResult:
        """Vectorized match, except for non-finite speeds: those segments
        go to the sequential oracle (bit-identical semantics), counted.  A
        backend failure propagates — it is never served from the host."""
        if not bool(np.isfinite(sub_speeds).all()):
            # corrupted speed readings: the sequential scan's comparisons
            # reject NaN/inf rows exactly like the scalar engine's checkin
            # does, while backend kernels aren't audited for non-finite
            # inputs — serve the whole segment scalar-side
            self.degraded_segments += 1
            tr = _obstrace.TRACER
            if tr.enabled:
                tr.instant("accel.degraded", cat="accel", reason="nonfinite",
                           rows=len(sub_ids))
            return match_chunk_seq(sub_ids, sub_speeds, st)
        self.backend_calls += 1
        if self.backend == "jax":
            return match_chunk_jax(sub_ids, sub_speeds, st,
                                   use_kernel=self.use_kernel)
        return match_chunk(sub_ids, sub_speeds, st)
