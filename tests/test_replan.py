"""Incremental replan engine vs full recompute — the exactness contract.

The array-native replan (:mod:`repro.accel.replan`) must be *plan-equivalent*
to the scalar ``venn_schedule`` + ``compile_plan`` pair after every delta
step, not just at steady state:

* **step-level**: two scheduler universes (``replan="scalar"`` vs
  ``replan="array"``) are driven through identical randomized event scripts —
  job arrivals, round completions/resubmits, grants (including fills and
  stale-request grants), supply feed — and after every replan the published
  ``SchedulePlan`` (group order, job order, demand keys, atom priorities,
  allocations) and the ``DispatchTable.snapshot()`` must match structurally;
* **scenario-level**: full simulations (plain + faulted, both drain engines)
  must produce identical ``SimMetrics`` and *byte-identical* audit streams
  across replan modes;
* the paranoid self-check (``REPRO_REPLAN_CHECK=1``) stays silent throughout
  — the engine's event-maintained mirror never drifts from the group truth.
"""
import os
import sys
from dataclasses import replace

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                               # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.core import VennScheduler
from repro.core.types import Job, JobRequest
from repro.scenarios import fast_scaled, get_scenario, run_scenario
from repro.sim.devices import REQUIREMENT_CLASSES


@pytest.fixture(autouse=True)
def _paranoid(monkeypatch):
    """Every test runs the engine's per-replan self-verification."""
    monkeypatch.setenv("REPRO_REPLAN_CHECK", "1")


# ------------------------------------------------------- step-level harness

class _Universe:
    """One scheduler plus its own private job/request objects (universes
    share nothing mutable, so a grant applied to both stays independent)."""

    def __init__(self, mode: str, epsilon: float = 0.0):
        self.sched = VennScheduler(seed=0, epsilon=epsilon, replan=mode)
        self.jobs = {}           # job_id -> Job

    def arrive(self, job_id, cls_i, demand, rounds, prio, t):
        req_cls = REQUIREMENT_CLASSES[cls_i % len(REQUIREMENT_CLASSES)]
        j = Job(job_id=job_id, requirement=req_cls, demand_per_round=demand,
                total_rounds=rounds, arrival_time=t, priority=prio)
        r = JobRequest(job=j, round_index=0, demand=demand, submit_time=t)
        j.current = r
        self.jobs[job_id] = j
        self.sched.on_request(r, t)

    def grant(self, job_id):
        r = self.jobs[job_id].current
        r.granted += 1
        self.sched.on_grant(r)

    def stale_grant(self, req):
        """A grant routed to a request the job no longer serves (the
        stale-plan waiver path): granted bumps, the engine must ignore it."""
        req.granted += 1
        self.sched.on_grant(req)

    def finish_round(self, job_id, t, resubmit: bool):
        j = self.jobs[job_id]
        r = j.current
        self.sched.on_complete(r, t)
        j.rounds_done += 1
        if resubmit and j.rounds_done < j.total_rounds:
            nxt = JobRequest(job=j, round_index=r.round_index + 1,
                             demand=j.demand_per_round, submit_time=t)
            j.current = nxt
            self.sched.on_request(nxt, t)
        else:
            j.current = None
        return r

    def feed(self, ids, times):
        self.sched.supply.record_batch(ids, times)

    def replan(self, t):
        self.sched._reschedule(t)


def _plan_sig(sched):
    plan = sched.plan
    return {
        "groups": [g.requirement.name for g in plan.groups],
        "order": {k: [j.job_id for j in v] for k, v in plan.job_order.items()},
        "keys": {k: list(v) for k, v in plan.job_keys.items()},
        "prio": [(tuple(sorted(a)), [g.requirement.name for g in order])
                 for a, order in plan.atom_priority.items()],
        "alloc": {g.requirement.name:
                  [(tuple(sorted(a)), r) for a, r in g.allocation.items()]
                  for g in plan.groups},
    }


def _table_sig(sched):
    return [row if row is None else
            [(r.job.job_id, r.round_index, lo, hi) for r, lo, hi in row]
            for row in sched.dispatch.snapshot()]


def _drive_script(seed: int, steps: int, epsilon: float = 0.0) -> None:
    """Run one randomized script through both universes, comparing plans
    after every replan (a replan follows every mutating step)."""
    rng = np.random.default_rng(seed)
    unis = [_Universe("scalar", epsilon), _Universe("array", epsilon)]
    caps = {"cpu": 4.0 * np.exp(0.6 * rng.standard_normal(80)),
            "mem": 4.0 * np.exp(0.6 * rng.standard_normal(80))}
    t = 0.0
    next_id = 0
    stale: list = [[], []]       # per-universe retired requests
    for _ in range(steps):
        t += float(rng.uniform(1.0, 50.0))
        open_ids = [jid for jid, j in unis[0].jobs.items()
                    if j.current is not None
                    and j.current.demand > j.current.granted]
        op = rng.uniform()
        if op < 0.35 or not open_ids:
            cls_i = int(rng.integers(0, len(REQUIREMENT_CLASSES)))
            demand = int(rng.integers(1, 8))
            rounds = int(rng.integers(1, 4))
            prio = float(rng.choice([0.5, 1.0, 1.0, 2.0]))
            for u in unis:
                u.arrive(next_id, cls_i, demand, rounds, prio, t)
            next_id += 1
        elif op < 0.70:
            jid = int(rng.choice(open_ids))
            # sometimes grant to the fill (exercises the on_grant removal)
            k = int(rng.integers(1, unis[0].jobs[jid].current.demand -
                                 unis[0].jobs[jid].current.granted + 1))
            for _g in range(k):
                for u in unis:
                    u.grant(jid)
        elif op < 0.90:
            jid = int(rng.choice(open_ids))
            resub = bool(rng.uniform() < 0.7)
            for ui, u in enumerate(unis):
                stale[ui].append(u.finish_round(jid, t, resub))
        else:
            # stale grant: a request retired by an earlier completion gets a
            # late grant (the documented stale-plan waiver) — both universes
            # mutate identically, the engine must not corrupt its mirror
            if stale[0]:
                pick = int(rng.integers(0, len(stale[0])))
                for ui, u in enumerate(unis):
                    u.stale_grant(stale[ui][pick])
        # identical supply feed through the (identical) classification ids
        times = np.sort(rng.uniform(t - 40.0, t, size=12))
        sel = rng.integers(0, 80, size=12)
        for u in unis:
            u.feed(u.sched.classify_caps(caps)[sel].astype(np.int64), times)
        for u in unis:
            u.replan(t)
        assert _plan_sig(unis[0].sched) == _plan_sig(unis[1].sched), \
            f"plan diverged at t={t:.1f} (seed {seed})"
        assert _table_sig(unis[0].sched) == _table_sig(unis[1].sched), \
            f"dispatch diverged at t={t:.1f} (seed {seed})"


@pytest.mark.parametrize("seed", range(8))
def test_incremental_equals_full_over_random_scripts(seed):
    _drive_script(seed, steps=40)


def test_incremental_equals_full_with_fairness():
    """ε > 0: keys drift with attained service/supply and are recomputed
    per replan through the shared policy callable — still plan-equivalent."""
    for seed in (0, 3):
        _drive_script(seed, steps=30, epsilon=2.0)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 10_000), st.integers(5, 60))
    def test_incremental_equals_full_hyp(seed, steps):
        os.environ["REPRO_REPLAN_CHECK"] = "1"
        try:
            _drive_script(seed, steps)
        finally:
            os.environ.pop("REPRO_REPLAN_CHECK", None)


# ------------------------------------------------------------ scenario level

def _tiny(spec):
    spec = fast_scaled(spec)
    return replace(
        spec,
        jobs=replace(spec.jobs, num_jobs=5),
        sim=replace(spec.sim, max_time=1.5 * 24 * 3600.0),
    )


# one plain scenario + one faulted one (blackout_storm drives revocation,
# retry resubmits and fault-instant replans through the delta protocol)
@pytest.mark.parametrize("scenario", ["baseline_even", "blackout_storm"])
def test_replan_modes_identical_end_to_end(scenario, tmp_path, monkeypatch):
    spec = _tiny(get_scenario(scenario))
    metrics, audits = {}, {}
    for mode in ("scalar", "auto"):
        monkeypatch.setenv("REPRO_REPLAN", mode)
        for engine in ("python", "array"):
            p = tmp_path / f"{mode}.{engine}.jsonl"
            res = run_scenario(spec, scheds=["venn"], seeds=[1],
                               engine=engine, audit_out=str(p))
            metrics[(mode, engine)] = res[0].metrics
            audits[(mode, engine)] = p.read_bytes()
    def sig(m):
        # SimMetrics.__eq__ compares _jobs by identity (Job is eq=False);
        # compare the cross-engine contract surface instead
        return (m.jcts, m.aborts, m.failed_rounds, m.unfinished, m.makespan,
                m.submitted_rounds, m.revoked_responses,
                [(r.job_id, r.round_index, r.submit, r.alloc_complete,
                  r.complete, r.demand, r.responses, r.failures, r.retries)
                 for r in m.rounds])

    base = sig(metrics[("scalar", "python")])
    for k, m in metrics.items():
        assert sig(m) == base, f"SimMetrics diverged for {k}"
    blob = audits[("scalar", "python")]
    assert len(blob) > 100
    for k, b in audits.items():
        assert b == blob, f"audit stream diverged for {k}"


def test_replan_engine_survives_pickle_restore(tmp_path):
    """The engine is a derived cache: a restored scheduler (``_replan``
    dropped by ``__getstate__``) must rebuild it and stay plan-equivalent."""
    import pickle

    unis = [_Universe("scalar"), _Universe("array")]
    rng = np.random.default_rng(5)
    caps = {"cpu": 4.0 * np.exp(0.6 * rng.standard_normal(40)),
            "mem": 4.0 * np.exp(0.6 * rng.standard_normal(40))}
    t = 0.0
    for jid in range(6):
        t += 10.0
        for u in unis:
            u.arrive(jid, jid, 5, 2, 1.0, t)
        times = np.sort(rng.uniform(t - 9.0, t, size=8))
        for u in unis:
            u.feed(u.sched.classify_caps(caps)[:8].astype(np.int64), times)
        for u in unis:
            u.replan(t)
    # snapshot/restore the array universe mid-flight
    blob = pickle.dumps(unis[1].sched)
    restored = pickle.loads(blob)
    assert restored._replan is None
    unis[1].sched = restored
    unis[1].jobs = {r.job.job_id: r.job for r in restored.pending}
    for jid in (0, 2):
        for u in unis:
            u.grant(jid)
    t += 10.0
    for u in unis:
        u.replan(t)
    assert _plan_sig(unis[0].sched) == _plan_sig(unis[1].sched)
    assert _table_sig(unis[0].sched) == _table_sig(unis[1].sched)


# ---------------------------------------------------- kernel order backend

def test_kernel_order_matches_lexsort():
    """REPRO_REPLAN_ORDER=kernel resolves ties and magnitudes exactly like
    the NumPy lexsort path (the f64 strict-order guard falls back on any
    f32-rank ambiguity, so the permutation is always the unique one)."""
    from repro.accel.replan import _kernel_order

    rng = np.random.default_rng(0)
    for n in (0, 1, 2, 7, 64, 257):
        # heavy duplication forces the id tie-break; near-equal f64 keys
        # force the f32-ambiguity fallback
        keys = rng.choice([0.5, 1.25, 1.25 + 1e-12, 2.0], size=n)
        ids = rng.permutation(n).astype(np.int64)
        got = _kernel_order(ids, keys)
        want = np.lexsort((ids, keys))
        assert np.array_equal(got, want), f"n={n}"


def test_kernel_order_pads_group_sizes_to_shared_buckets():
    """Group sizes that share a power-of-two bucket (>= 128) share one
    compiled kernel, and the padding rows never reach the permutation."""
    from repro import obs
    from repro.accel.kernels.replan_order import segmented_rank
    from repro.accel.replan import _kernel_order

    rng = np.random.default_rng(1)
    sizes = (2, 5, 100, 128, 129, 200, 256)
    before = segmented_rank._cache_size()
    with obs.session(tracing=False, metrics=True) as (_, reg):
        for n in sizes:
            keys = rng.choice([0.5, 1.0, 3.0, 7.25], size=n)
            ids = rng.permutation(10 * n)[:n].astype(np.int64)
            assert np.array_equal(_kernel_order(ids, keys),
                                  np.lexsort((ids, keys))), n
        calls = reg.counter("accel.kernel_order_calls").value
        fallbacks = reg.counter("accel.kernel_order_fallbacks").value
    assert (calls, fallbacks) == (len(sizes), 0)
    assert segmented_rank._cache_size() - before <= 2   # buckets 128, 256


def test_kernel_order_backend_plan_equivalent(monkeypatch):
    """Full step-level equivalence with the Pallas segmented_order resort
    path enabled (paranoid self-check active via the autouse fixture)."""
    monkeypatch.setenv("REPRO_REPLAN_ORDER", "kernel")
    _drive_script(2, steps=25)


def test_unknown_order_backend_rejected(monkeypatch):
    from repro.accel.replan import ReplanEngine
    with pytest.raises(ValueError, match="order backend"):
        ReplanEngine(order_backend="warp")
