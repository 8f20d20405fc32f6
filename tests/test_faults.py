"""Fault-injection layer + crash-consistent recovery + graceful degradation.

* FaultPlan validation and the injector's stream-contract guarantees
  (sortedness, recoverable dup/reorder transparency, carry-forward skew);
* resilience counters: nonzero exactly where faults are injected, zero on
  fault-free runs;
* crash-recovery equivalence: >=2 injected crashes reproduce the crash-free
  SimMetrics bit-identically on both drain engines (drift bound: zero);
* graceful degradation: NaN speeds degrade accel segments to the sequential
  oracle (cross-engine metrics stay identical), replan budget serves stale
  plans with a counter;
* overcommit satellites: factor math, Job.overcommit demand sizing,
  adaptive policy wiring;
* corrupted-trace replay tolerance and randomized fuzz over the registry.
"""
import math
import pickle
from dataclasses import replace

import numpy as np
import pytest

from repro.core import SCHEDULERS
from repro.faults import (Blackout, ChunkChaos, ClockSkew, FaultInjector,
                          FaultPlan, FlakyIngest, latest_snapshot_step,
                          restore_simulator, run_with_crashes,
                          snapshot_simulator)
from repro.fed.overcommit import OvercommitPolicy
from repro.scenarios import (ScenarioSpec, TraceReplayStream, build_jobs,
                             build_stream, fast_scaled, get_scenario, run_one)
from repro.scenarios.runner import comparison_table
from repro.scenarios.trace_io import RecordingStream
from repro.sim.simulator import Simulator

DAY = 24 * 3600.0


def _tiny(spec: ScenarioSpec) -> ScenarioSpec:
    spec = fast_scaled(spec)
    return replace(
        spec,
        jobs=replace(spec.jobs, num_jobs=5),
        sim=replace(spec.sim, max_time=1.5 * DAY),
    )


def _make_sim(spec: ScenarioSpec, seed: int = 0, engine=None,
              plan: FaultPlan = None) -> Simulator:
    jobs = build_jobs(spec, seed)
    stream = build_stream(spec, seed)
    if plan is not None and not plan.is_empty:
        stream = FaultInjector(stream, plan)
    sched = SCHEDULERS["venn"](seed=seed)
    return Simulator(jobs, sched, cfg=spec.sim, stream=stream, engine=engine,
                     faults=plan)


def _drain_all(stream):
    chunks = []
    while True:
        ck = stream.next_chunk()
        if ck is None:
            return chunks
        chunks.append(ck)


def _concat_times(chunks):
    return np.concatenate([ck.times for ck in chunks]) if chunks \
        else np.zeros(0)


# ----------------------------------------------------------------- validation

def test_fault_plan_validation_rejects_bad_values():
    with pytest.raises(ValueError, match="start < stop"):
        FaultPlan(blackouts=(Blackout(start=0.5, stop=0.5),)).validate()
    with pytest.raises(ValueError, match="before 1.0"):
        FaultPlan(blackouts=(Blackout(start=0.5, stop=1.5),)).validate()
    with pytest.raises(ValueError, match="drop_prob"):
        FaultPlan(blackouts=(Blackout(0.1, 0.2, drop_prob=1.5),)).validate()
    with pytest.raises(ValueError, match="dup_prob"):
        FaultPlan(chunk_chaos=ChunkChaos(dup_prob=-0.1)).validate()
    with pytest.raises(ValueError, match="fail_prob"):
        FaultPlan(flaky_ingest=FlakyIngest(fail_prob=1.0)).validate()
    with pytest.raises(ValueError, match="max_skew"):
        FaultPlan(clock_skew=ClockSkew(fraction=0.1, max_skew=-1.0)).validate()


def test_injector_requires_resolved_plan():
    spec = _tiny(get_scenario("baseline_even"))
    plan = FaultPlan(blackouts=(Blackout(0.1, 0.2),))   # still fractional
    with pytest.raises(ValueError, match="resolve"):
        FaultInjector(build_stream(spec, 0), plan)


def test_resolve_scales_windows_and_is_idempotent():
    plan = FaultPlan(blackouts=(Blackout(0.25, 0.5),))
    r = plan.resolve(1000.0)
    assert not r.fractional
    assert r.blackouts[0].start == 250.0 and r.blackouts[0].stop == 500.0
    assert r.resolve(77.0) is r                   # absolute plans pass through


# ------------------------------------------------------- stream-level faults

def test_empty_plan_is_identity():
    spec = _tiny(get_scenario("baseline_even"))
    plain = _drain_all(build_stream(spec, 0))
    plan = FaultPlan().resolve(spec.sim.max_time)
    faulted = _drain_all(FaultInjector(build_stream(spec, 0), plan))
    assert len(plain) == len(faulted)
    for a, b in zip(plain, faulted):
        np.testing.assert_array_equal(a.times, b.times)
        np.testing.assert_array_equal(a.speed, b.speed)


def test_dup_and_reorder_are_recovered_bit_identically():
    """The ingest side dedups + restores adjacent reorders, so a dup/reorder
    -only plan perturbs counters but not the delivered row stream."""
    spec = _tiny(get_scenario("baseline_even"))
    plain = _drain_all(build_stream(spec, 0))
    plan = FaultPlan(chunk_chaos=ChunkChaos(dup_prob=0.6, reorder_prob=0.6),
                     seed=3).resolve(spec.sim.max_time)
    inj = FaultInjector(build_stream(spec, 0), plan)
    faulted = _drain_all(inj)
    np.testing.assert_array_equal(_concat_times(plain),
                                  _concat_times(faulted))
    c = inj.fault_counters()
    assert c["chunks_duplicated"] > 0
    assert c["chunks_reordered"] > 0
    assert c["dup_chunks_discarded"] == c["chunks_duplicated"]
    assert c["rows_dropped_chunks"] == 0


def test_clock_skew_preserves_stream_ordering_contract():
    spec = _tiny(get_scenario("baseline_even"))
    plan = FaultPlan(clock_skew=ClockSkew(fraction=0.2, max_skew=7200.0),
                     seed=5).resolve(spec.sim.max_time)
    inj = FaultInjector(build_stream(spec, 0), plan)
    chunks = _drain_all(inj)
    last = -math.inf
    for ck in chunks:
        assert np.all(np.diff(ck.times) >= 0)     # sorted within chunk
        assert ck.times[0] >= last                # non-decreasing across
        last = float(ck.times[-1])
    c = inj.fault_counters()
    assert c["skewed_rows"] > 0
    assert c["carried_rows"] > 0                  # some rows crossed a chunk


def test_flaky_ingest_retries_and_gives_up_gracefully():
    spec = _tiny(get_scenario("baseline_even"))
    plan = FaultPlan(flaky_ingest=FlakyIngest(fail_prob=0.6, max_retries=1,
                                              backoff=2.0),
                     seed=1).resolve(spec.sim.max_time)
    inj = FaultInjector(build_stream(spec, 0), plan)
    _drain_all(inj)                               # must terminate, not raise
    c = inj.fault_counters()
    assert c["flaky_retries"] > 0
    assert c["flaky_giveups"] > 0                 # some chunks abandoned
    assert c["rows_dropped_chunks"] > 0
    assert c["backoff_total_s"] > 0


def test_blackout_drops_rows_only_inside_window():
    spec = _tiny(get_scenario("baseline_even"))
    horizon = spec.sim.max_time
    plan = FaultPlan(blackouts=(Blackout(0.02, 0.04, drop_prob=1.0),),
                     seed=1).resolve(horizon)
    plain = _concat_times(_drain_all(build_stream(spec, 0)))
    inj = FaultInjector(build_stream(spec, 0), plan)
    faulted = _concat_times(_drain_all(inj))
    lo, hi = 0.02 * horizon, 0.04 * horizon
    assert not np.any((faulted >= lo) & (faulted < hi))
    n_window = int(np.sum((plain >= lo) & (plain < hi)))
    assert n_window > 0
    assert inj.fault_counters()["rows_dropped_blackout"] == n_window
    np.testing.assert_array_equal(faulted,
                                  plain[(plain < lo) | (plain >= hi)])


# --------------------------------------------------------- simulator counters

def test_fault_free_run_has_zero_resilience_counters():
    spec = _tiny(get_scenario("baseline_even"))
    for engine in ("python", "array"):
        m = run_one(spec, "venn", seed=0, engine=engine).metrics
        res = m.resilience()
        assert res.pop("submitted_rounds") > 0
        assert all(v == 0 for v in res.values()), res


def test_blackout_storm_counters_nonzero_and_engines_identical():
    spec = _tiny(get_scenario("blackout_storm"))
    py = run_one(spec, "venn", seed=0, engine="python").metrics
    ar = run_one(spec, "venn", seed=0, engine="array").metrics
    assert py.jcts == ar.jcts
    assert py.summary() == ar.summary()
    for m in (py, ar):
        res = m.resilience()
        assert res["dropped_checkins"] > 0
        assert res["revoked_responses"] > 0
    assert py.resilience()["revoked_responses"] == \
        ar.resilience()["revoked_responses"]


def test_corrupt_speeds_degrade_accel_segments_not_metrics():
    """NaN speed readings: the array engine falls back per-segment to the
    sequential oracle (counted), and metrics stay engine-identical."""
    spec = _tiny(get_scenario("flaky_ingest"))
    py = run_one(spec, "venn", seed=0, engine="python").metrics
    ar = run_one(spec, "venn", seed=0, engine="array").metrics
    assert py.jcts == ar.jcts
    assert py.summary() == ar.summary()
    assert ar.resilience()["degraded_segments"] > 0
    assert py.resilience()["degraded_segments"] == 0    # scalar path


def test_severe_faults_hurt_jct_at_fixed_seed():
    """Fault-severity spot check at the extremes: a long total blackout
    cannot beat the fault-free run (fixed seed, identical workload)."""
    spec = _tiny(get_scenario("baseline_even"))
    base = run_one(spec, "venn", seed=0).metrics
    heavy = replace(spec, fault_plan=FaultPlan(
        blackouts=(Blackout(0.01, 0.08, drop_prob=1.0),), seed=2))
    hurt = run_one(heavy, "venn", seed=0).metrics
    assert hurt.avg_jct >= base.avg_jct
    assert hurt.resilience()["dropped_checkins"] > 0


def test_comparison_table_renders_resilience_block():
    spec = _tiny(get_scenario("blackout_storm"))
    runs = [run_one(spec, "venn", seed=0)]
    table = comparison_table(runs)
    assert "revoked_responses" in table
    plain = [run_one(_tiny(get_scenario("baseline_even")), "venn", seed=0)]
    assert "revoked_responses" not in comparison_table(plain)


# -------------------------------------------------------------- overcommit

def test_overcommit_factor_math():
    pol = OvercommitPolicy(base=1.3)
    # initial fail-rate estimate is 1 - 1/base; factor = quorum/(1 - fail)
    assert pol.factor(0.8) == pytest.approx(min(0.8 * 1.3, 2.0))
    pol.observe_round(granted=100, responded=10)    # heavy failure round
    assert pol.factor(0.8) > 0.8 * 1.3
    assert pol.factor(0.8) <= pol.max_factor
    pol2 = OvercommitPolicy(base=1.0)
    assert pol2.factor(0.8) == 1.0                  # min_factor floor
    assert pol2.demand(100, 0.8) == 100


def test_job_overcommit_inflates_demand_not_quorum():
    spec = _tiny(get_scenario("baseline_even"))
    jobs = build_jobs(spec, 0)
    nominal = [j.demand_per_round for j in jobs]
    for j in jobs:
        j.overcommit = 1.4
    sched = SCHEDULERS["venn"](seed=0)
    sim = Simulator(jobs, sched, cfg=spec.sim, stream=build_stream(spec, 0))
    m = sim.run()
    by_job = {j.job_id: n for j, n in zip(jobs, nominal)}
    for r in m.rounds:
        n = by_job[r.job_id]
        assert r.demand == max(n, int(round(n * 1.4)))
        # quorum attainment is judged against nominal: responses needed
        # never exceed ceil(qf * nominal) <= nominal < demand
        assert r.responses <= r.demand


def test_adaptive_overcommit_grows_demand_under_churn():
    spec = _tiny(get_scenario("churn_storm"))
    spec = replace(spec, sim=replace(spec.sim, adaptive_overcommit=True))
    m = run_one(spec, "venn", seed=0).metrics
    assert math.isfinite(m.avg_jct)
    base = _tiny(get_scenario("churn_storm"))
    mb = run_one(base, "venn", seed=0).metrics
    # churn rounds abort; the policy must have inflated at least one retry
    inflated = [r for r in m.rounds if r.retries > 0]
    if inflated:          # storm must actually bite for the spot check
        base_demand = {(r.job_id, r.round_index): r.demand
                       for r in mb.rounds}
        assert any(r.demand >= base_demand.get((r.job_id, r.round_index),
                                               r.demand)
                   for r in inflated)


# --------------------------------------------------------- crash recovery

@pytest.mark.parametrize("engine", ["python", "array"])
@pytest.mark.parametrize("scenario", ["baseline_even", "blackout_storm"])
def test_crash_recovery_bit_identical(engine, scenario, tmp_path):
    """>=2 injected crashes (with work lost since the snapshot) reproduce
    the crash-free metrics bit-identically — the tentpole acceptance bar."""
    spec = _tiny(get_scenario(scenario))
    plan = spec.fault_plan.resolve(spec.sim.max_time) \
        if spec.fault_plan is not None else None
    crash_free = _make_sim(spec, engine=engine, plan=plan).run()
    crashed = run_with_crashes(
        lambda: _make_sim(spec, engine=engine, plan=plan),
        crash_times=[2000.0, 5000.0], ckpt_dir=str(tmp_path),
        snapshot_lag=300.0)
    assert crashed.jcts == crash_free.jcts
    assert crashed.rounds == crash_free.rounds
    assert crashed.summary() == crash_free.summary()
    assert crashed.resilience()["recovery_events"] == 2
    assert crash_free.resilience()["recovery_events"] == 0


def test_snapshot_is_atomic_and_sweeps_stale_tmp(tmp_path):
    spec = _tiny(get_scenario("baseline_even"))
    sim = _make_sim(spec)
    sim.start()
    sim.step_until(1000.0)
    junk = tmp_path / ".tmp-step_00000007"
    junk.mkdir(parents=True)
    (junk / "state.pkl").write_bytes(b"partial")
    assert latest_snapshot_step(str(tmp_path)) is None
    snapshot_simulator(sim, str(tmp_path), 0)
    assert not junk.exists()                      # killed-writer leftovers
    assert latest_snapshot_step(str(tmp_path)) == 0
    restored = restore_simulator(str(tmp_path))
    assert restored.now == sim.now
    assert restored.finish().summary() == sim.finish().summary()


def test_restore_rejects_foreign_or_missing_snapshots(tmp_path):
    with pytest.raises(ValueError, match="no snapshot"):
        restore_simulator(str(tmp_path))
    bad = tmp_path / "step_00000003"
    bad.mkdir()
    with pytest.raises(ValueError, match="manifest"):
        restore_simulator(str(tmp_path), 3)
    (bad / "manifest.json").write_text('{"format": "something-else"}')
    with pytest.raises(ValueError, match="venn-sim-snapshot"):
        restore_simulator(str(tmp_path), 3)


def test_recording_stream_refuses_snapshot(tmp_path):
    spec = _tiny(get_scenario("baseline_even"))
    rec = RecordingStream(build_stream(spec, 0), str(tmp_path / "t.csv"))
    try:
        with pytest.raises(TypeError, match="RecordingStream"):
            pickle.dumps(rec)
    finally:
        rec.close()


def test_replay_stream_pickles_mid_stream(tmp_path):
    spec = _tiny(get_scenario("baseline_even"))
    path = str(tmp_path / "trace.csv")
    run_one(spec, "venn", seed=0, record=path)
    ref = TraceReplayStream(path, chunk_rows=1024, seed=0)
    cut = TraceReplayStream(path, chunk_rows=1024, seed=0)
    a, b = ref.next_chunk(), cut.next_chunk()
    np.testing.assert_array_equal(a.times, b.times)
    cut2 = pickle.loads(pickle.dumps(cut))        # snapshot mid-read
    cut.close()
    while True:
        a, b = ref.next_chunk(), cut2.next_chunk()
        if a is None or b is None:
            assert a is None and b is None
            break
        np.testing.assert_array_equal(a.times, b.times)
        np.testing.assert_array_equal(a.fail_u, b.fail_u)
    ref.close()
    cut2.close()


# ------------------------------------------------- degraded replan budget

def test_replan_budget_serves_stale_plans_and_completes():
    from repro.accel.engine import ArrayMatchEngine
    spec = _tiny(get_scenario("baseline_even"))
    engine = ArrayMatchEngine(replan_budget_s=600.0)
    sim = _make_sim(spec, engine=engine)
    m = sim.run()
    assert math.isfinite(m.avg_jct)
    assert len(m.jcts) == spec.jobs.num_jobs
    assert engine.stale_plans_served > 0
    assert m.resilience()["stale_plans_served"] == engine.stale_plans_served
    assert engine.staleness_s > 0


# ------------------------------------------------ corrupted trace replay

@pytest.mark.parametrize("suffix", ["csv", "jsonl"])
def test_corrupted_trace_replay_skips_and_counts(tmp_path, suffix):
    spec = _tiny(get_scenario("churn_storm"))
    path = str(tmp_path / f"trace.{suffix}")
    run_one(spec, "venn", seed=0, record=path)
    with open(path) as f:
        lines = f.read().splitlines()
    # corrupt three data rows near the start (inside the busy period, so the
    # sim actually reads them before its jobs finish): garbage text, a
    # truncated row, and a non-numeric field — skipped + counted, not raised
    k = 50
    lines[k] = "total garbage {{{"
    lines[k + 1] = lines[k + 1].rsplit(",", 2)[0] if suffix == "csv" \
        else lines[k + 1][: len(lines[k + 1]) // 2]
    lines[k + 2] = lines[k + 2].replace(".", "x", 1)
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    r = run_one(spec, "venn", seed=0, replay=path)
    assert math.isfinite(r.metrics.avg_jct)
    assert r.metrics.resilience()["skipped_rows"] == 3


# ----------------------------------------------------------------- fuzzing

def _random_plan(rng: np.random.Generator) -> FaultPlan:
    blackouts = []
    for _ in range(int(rng.integers(0, 3))):
        start = float(rng.uniform(0.0, 0.4))
        blackouts.append(Blackout(
            start=start, stop=min(1.0, start + float(rng.uniform(0.01, 0.5))),
            drop_prob=float(rng.uniform(0.1, 1.0))))
    return FaultPlan(
        blackouts=tuple(blackouts),
        chunk_chaos=ChunkChaos(
            drop_prob=float(rng.uniform(0, 0.5)),
            dup_prob=float(rng.uniform(0, 0.5)),
            reorder_prob=float(rng.uniform(0, 0.5)),
            corrupt_speed_prob=float(rng.uniform(0, 0.5))),
        clock_skew=ClockSkew(fraction=float(rng.uniform(0, 0.3)),
                             max_skew=3600.0),
        flaky_ingest=FlakyIngest(fail_prob=float(rng.uniform(0, 0.5)),
                                 max_retries=3, backoff=1.0),
        seed=int(rng.integers(0, 2 ** 16)))


def _assert_invariants(spec: ScenarioSpec, engine: str) -> None:
    m = run_one(spec, "venn", seed=0, engine=engine).metrics
    res = m.resilience()
    assert len(m.rounds) + m.failed_rounds <= res["submitted_rounds"]
    assert len(m.jcts) == spec.jobs.num_jobs
    assert m.makespan <= spec.sim.max_time
    assert all(v >= 0 for v in res.values())


def test_registry_sweep_under_random_plans_never_raises():
    """Acceptance bar: a registry-wide sweep under randomized fault plans
    completes with zero unhandled exceptions (numpy-RNG fuzz; the hypothesis
    variant below digs deeper when the library is available)."""
    rng = np.random.default_rng(2026)
    for i, name in enumerate(["baseline_even", "churn_storm", "flash_crowd",
                              "blackout_storm", "flaky_ingest", "hot_atom"]):
        spec = replace(_tiny(get_scenario(name)), fault_plan=_random_plan(rng))
        _assert_invariants(spec, engine="python" if i % 2 else "array")


def test_randomized_fault_plans_never_raise():
    from hypothesis import given, settings
    from hypothesis import strategies as st

    probs = st.floats(min_value=0.0, max_value=0.5)
    windows = st.tuples(st.floats(0.0, 0.4), st.floats(0.01, 0.5),
                        st.floats(0.1, 1.0)).map(
        lambda w: Blackout(start=w[0], stop=min(1.0, w[0] + w[1]),
                           drop_prob=w[2]))

    @settings(max_examples=8, deadline=None)
    @given(
        name=st.sampled_from(["baseline_even", "churn_storm", "flash_crowd"]),
        blackouts=st.lists(windows, max_size=2).map(tuple),
        chaos=st.tuples(probs, probs, probs, probs),
        skew=st.floats(0.0, 0.3),
        flaky=st.floats(0.0, 0.5),
        seed=st.integers(0, 2 ** 16),
        engine=st.sampled_from(["python", "array"]),
    )
    def run(name, blackouts, chaos, skew, flaky, seed, engine):
        plan = FaultPlan(
            blackouts=blackouts,
            chunk_chaos=ChunkChaos(drop_prob=chaos[0], dup_prob=chaos[1],
                                   reorder_prob=chaos[2],
                                   corrupt_speed_prob=chaos[3]),
            clock_skew=ClockSkew(fraction=skew, max_skew=3600.0),
            flaky_ingest=FlakyIngest(fail_prob=flaky, max_retries=3,
                                     backoff=1.0),
            seed=seed)
        spec = replace(_tiny(get_scenario(name)), fault_plan=plan)
        _assert_invariants(spec, engine)   # incl. rounds <= submitted_rounds

    run()
