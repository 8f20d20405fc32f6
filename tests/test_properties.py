"""Property-based tests (hypothesis) for the scheduler's system invariants."""
import math

import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (FifoScheduler, RandomScheduler, SrsfScheduler,
                        VennScheduler)
from repro.core.supply import SupplyEstimator
from repro.sim import (JobTraceConfig, PopulationConfig, SimConfig,
                       generate_jobs, run_workload)
from repro.sim.simulator import Simulator


@st.composite
def small_workload(draw):
    n_jobs = draw(st.integers(2, 8))
    seed = draw(st.integers(0, 10_000))
    rate = draw(st.sampled_from([1.0, 3.0, 8.0]))
    return n_jobs, seed, rate


@settings(max_examples=8, deadline=None)
@given(small_workload(), st.sampled_from(["venn", "random", "srsf", "fifo"]))
def test_simulation_invariants(wl, sched_name):
    """For any workload/scheduler: no device double-assignment (granted counts
    match responses+failures+outstanding), rounds complete monotonically, every
    completed round met quorum before its deadline."""
    n_jobs, seed, rate = wl
    jobs = generate_jobs(JobTraceConfig(
        num_jobs=n_jobs, seed=seed, demand_lo=5, demand_hi=40,
        rounds_lo=1, rounds_hi=4, mean_interarrival=600.0))
    cls = {"venn": VennScheduler, "random": RandomScheduler,
           "srsf": SrsfScheduler, "fifo": FifoScheduler}[sched_name]
    sim = Simulator(jobs, cls(seed=seed), PopulationConfig(seed=seed,
                    base_rate=rate), SimConfig(max_time=4 * 24 * 3600.0))
    m = sim.run()
    for r in m.rounds:
        job = jobs[r.job_id]
        quorum = math.ceil(job.quorum_fraction * r.demand)
        assert r.responses >= quorum, "completed round must meet quorum"
        assert r.complete >= r.submit
        if r.alloc_complete is not None:
            assert r.submit <= r.alloc_complete <= r.complete
            assert r.complete - r.alloc_complete <= job.deadline + 1e-6
    # per-job rounds completed are sequential and bounded
    for j in jobs:
        seen = sorted(r.round_index for r in m.rounds if r.job_id == j.job_id)
        assert seen == sorted(set(seen)), "no duplicate round completions"
        assert len(seen) <= j.total_rounds
    # JCTs are recorded for everyone (finished or censored)
    assert set(m.jcts) == {j.job_id for j in jobs}
    assert all(v >= 0 for v in m.jcts.values())


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.floats(0, 86_400), st.integers(0, 3)),
                min_size=1, max_size=200))
def test_supply_estimator_rate_bounds(events):
    """Windowed rate is nonnegative and never exceeds events/bucket."""
    est = SupplyEstimator(window=3600.0, prior_rate=0.5, bucket=60.0)
    atoms = [frozenset({f"a{i}"}) for i in range(4)]
    events = sorted(events)
    for t, a in events:
        est.record(atoms[a], t)
    for a in atoms:
        r = est.rate(a)
        assert r >= 0.0
        assert r <= max(len(events) / 60.0, est.prior_rate) + 1e-9


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 5000))
def test_venn_assign_respects_eligibility(seed):
    """Venn never assigns a device to a job whose requirement it fails.

    The simulator drives the fast check-in path, so the spy wraps ``checkin``
    (atom id + struct-of-arrays row) and reconstructs the Device to check
    ``Requirement.matches`` directly."""
    from repro.core.types import Device

    jobs = generate_jobs(JobTraceConfig(num_jobs=4, seed=seed, demand_lo=5,
                                        demand_hi=30, rounds_lo=1, rounds_hi=3))
    sched = VennScheduler(seed=seed)
    seen = []
    orig_checkin = sched.checkin

    def spying_checkin(atom_id, cpu, mem, speed, now):
        req = orig_checkin(atom_id, cpu, mem, speed, now)
        if req is not None:
            device = Device(caps={"cpu": cpu, "mem": mem}, speed=speed)
            assert req.requirement.matches(device), \
                f"{req.requirement.name} assigned incompatible device"
            seen.append(1)
        return req

    sched.checkin = spying_checkin
    sim = Simulator(jobs, sched, PopulationConfig(seed=seed, base_rate=3.0),
                    SimConfig(max_time=2 * 24 * 3600.0))
    sim.run()
    assert seen, "simulation assigned at least one device"
