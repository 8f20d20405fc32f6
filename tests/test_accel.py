"""Array-native scheduler engine (repro.accel) tests.

* fixed-point matcher vs the sequential oracle (randomized slots, tier
  bands, capacities) — NumPy, JAX, and JAX+Pallas-kernel backends;
* Pallas masked-first-fit kernel vs its pure-jnp oracle;
* adaptive candidate-cap expansion (truncated rows re-match exactly);
* supply-ring SoA views match the scalar estimator bit for bit;
* end-to-end: Simulator(engine="array") produces identical grant sequences
  and SimMetrics to the per-device loop on randomized workloads, for Venn
  and the baselines.
"""
import functools
import math

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.accel.engine import (ArrayMatchEngine, match_chunk,
                                match_chunk_jax, match_chunk_seq)
from repro.accel.state import MatchState, SupplyRings
from repro.core import SCHEDULERS, VennScheduler
from repro.core.supply import SupplyEstimator
from repro.sim import (JobTraceConfig, PopulationConfig, SimConfig,
                       generate_jobs)
from repro.sim.simulator import Simulator


class FakeReq:
    def __init__(self, demand, granted=0):
        self.demand, self.granted = demand, granted


class FakeSched:
    def __init__(self, slots):
        self._slots = slots

    def export_match_slots(self, limit=None):
        if limit is None:
            return self._slots
        return [s if s is None else s[:limit] for s in self._slots]


def _random_state(rng, kcap=8, export_limit=None):
    A = int(rng.integers(1, 6))
    R = int(rng.integers(1, 8))
    reqs = [FakeReq(int(rng.integers(1, 6))) for _ in range(R)]
    slots = []
    for _ in range(A):
        if rng.uniform() < 0.1:
            slots.append(None)
            continue
        row = []
        for r in rng.permutation(R)[:int(rng.integers(0, R + 1))]:
            if rng.uniform() < 0.3:
                lo, hi = sorted(rng.uniform(0, 3, 2))
            else:
                lo, hi = -math.inf, math.inf
            row.append((reqs[int(r)], float(lo), float(hi)))
        slots.append(row)
    return MatchState.from_scheduler(FakeSched(slots), token=("t",),
                                     kcap=kcap, export_limit=export_limit)


def _random_segment(rng, st_, n):
    cov = np.flatnonzero(st_.covered)
    if len(cov) == 0:
        return None, None
    aids = rng.choice(cov, size=n)
    speeds = rng.uniform(0, 3, size=n)
    return aids, speeds


# ------------------------------------------------------ matcher vs oracle

def _check_matcher_equals_oracle(seed: int, n: int) -> None:
    rng = np.random.default_rng(seed)
    state = _random_state(rng)
    aids, speeds = _random_segment(rng, state, n)
    if aids is None:
        return
    ref = match_chunk_seq(aids, speeds, state)
    got = match_chunk(aids, speeds, state)
    assert np.array_equal(ref.choice, got.choice)
    assert np.array_equal(ref.granted, got.granted)


@pytest.mark.parametrize("seed", range(40))
def test_match_chunk_equals_sequential_oracle(seed):
    _check_matcher_equals_oracle(seed, n=1 + 7 * seed % 80)


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 80))
def test_match_chunk_equals_sequential_oracle_hyp(seed, n):
    _check_matcher_equals_oracle(seed, n)


def _edge_segment(case):
    """A state and a segment at an edge of the packed device buffers."""
    inf = math.inf
    if case == "one_row":
        reqs = [FakeReq(2), FakeReq(1)]
        slots = [[(reqs[0], 1.0, 2.0), (reqs[1], -inf, inf)]]
        aids, speeds = [0], [0.5]
    elif case == "pow2_rows":                  # n == np_pad, no row padding
        reqs = [FakeReq(d) for d in (5, 9, 30)]
        slots = [[(reqs[0], -inf, inf), (reqs[1], 0.0, 1.5)],
                 [(reqs[1], -inf, inf), (reqs[2], -inf, inf)]]
        rng = np.random.default_rng(64)
        aids, speeds = rng.integers(0, 2, 64), rng.uniform(0, 3, 64)
    elif case == "one_request":                # R == 1
        req = FakeReq(3)
        slots = [[(req, -inf, inf)], [(req, 1.0, 2.0)]]
        aids, speeds = [1, 0, 1, 1, 0, 0, 1], [1.5, 0, 0.5, 1.2, 2, 2, 1.9]
    elif case == "at_kcap":                    # K == the candidate cap
        reqs = [FakeReq(1) for _ in range(40)]
        slots = [[(r, -inf, inf) for r in reqs], [(reqs[5], -inf, inf)]]
        aids, speeds = [0] * 30 + [1] + [0] * 19, [1.0] * 50
    elif case == "all_ineligible":             # no row has a live slot
        reqs = [FakeReq(4), FakeReq(4)]
        slots = [[(reqs[0], 0.0, 1.0), (reqs[1], 0.5, 1.0)], []]
        aids, speeds = [0, 1, 0, 1, 0], [2.0, 0.7, 1.0, 2.5, 1.5]
    elif case == "fills_mid_segment":          # A, then B fill: 3 passes
        reqs = [FakeReq(2), FakeReq(3), FakeReq(10)]
        slots = [[(r, -inf, inf) for r in reqs]]
        aids, speeds = [0] * 12, [1.0] * 12
    state = MatchState.from_scheduler(FakeSched(slots), token=("t",), kcap=32)
    return state, np.asarray(aids), np.asarray(speeds, dtype=float)


EDGE_CASES = ["one_row", "pow2_rows", "one_request", "at_kcap",
              "all_ineligible", "fills_mid_segment"]


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("case", [0, 3, 11, 29] + EDGE_CASES)
def test_jax_backend_equals_oracle(case, use_kernel):
    if isinstance(case, int):                  # a random state and segment
        rng = np.random.default_rng(case)
        state = _random_state(rng)
        aids, speeds = _random_segment(rng, state, 50)
        if aids is None:
            return
    else:
        state, aids, speeds = _edge_segment(case)
    ref = match_chunk_seq(aids, speeds, state)
    got = match_chunk_jax(aids, speeds, state, use_kernel=use_kernel)
    assert np.array_equal(ref.choice, got.choice)
    assert np.array_equal(ref.granted, got.granted)
    if case == "at_kcap":
        assert state.cand_req.shape[1] == state.kcap == 32
    if case == "all_ineligible":
        assert (got.choice == -1).all()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_jax_output_buffer_carries_iteration_count(use_kernel):
    from repro import obs
    from repro.accel._jax_impl import _match_jax
    from repro.accel.engine import _pack_jax
    state, aids, speeds = _edge_segment("fills_mid_segment")
    iters = []
    for match in (match_chunk, functools.partial(match_chunk_jax,
                                                  use_kernel=use_kernel)):
        with obs.session(tracing=False, metrics=True) as (_, reg):
            match(aids, speeds, state)
            hist = reg.get("accel.fixedpoint_iters")
            assert hist.count == 1 and hist.vmin == hist.vmax
            iters.append(hist.vmin)
    buf, np_pad, kp = _pack_jax(aids, speeds, state)
    out = np.asarray(_match_jax(buf, np_pad, kp, use_kernel=use_kernel))
    assert out.shape == (2 * np_pad + 1,)
    # NumPy's fixed point, the histogram and the buffer's last slot agree
    assert iters == [out[-1], out[-1]] and out[-1] == 3


def test_masked_first_fit_kernel_matches_ref():
    import jax.numpy as jnp

    from repro.accel.kernels import masked_first_fit, masked_first_fit_ref
    rng = np.random.default_rng(0)
    for n, K in ((1, 1), (7, 3), (64, 5), (300, 17), (1024, 130)):
        elig = rng.uniform(size=(n, K)) < 0.4
        fill = rng.integers(-1, n + 1, size=(n, K)).astype(np.int32)
        pos = np.arange(n, dtype=np.int32)
        want = masked_first_fit_ref(jnp.asarray(elig.astype(np.int32)),
                                    jnp.asarray(fill), jnp.asarray(pos))
        got = masked_first_fit(jnp.asarray(elig.astype(np.int32)),
                               jnp.asarray(fill), jnp.asarray(pos),
                               interpret=True)
        assert np.array_equal(np.asarray(want), np.asarray(got)), (n, K)


def test_segmented_rank_kernel_matches_ref():
    import jax.numpy as jnp

    from repro.accel.kernels import segmented_rank, segmented_rank_ref
    rng = np.random.default_rng(1)
    for n in (1, 2, 7, 64, 200, 513, 1024):
        seg = np.sort(rng.integers(0, max(1, n // 9) + 1, n)).astype(np.int32)
        keys = rng.uniform(0, 100, n).astype(np.float32)
        if n > 4:                      # exercise the tie-break axis
            keys[1] = keys[0]
            keys[3] = keys[2]
        ties = rng.permutation(n).astype(np.int32)
        want = segmented_rank_ref(jnp.asarray(seg), jnp.asarray(keys),
                                  jnp.asarray(ties))
        got = segmented_rank(jnp.asarray(seg), jnp.asarray(keys),
                             jnp.asarray(ties), interpret=True)
        assert np.array_equal(np.asarray(want), np.asarray(got)), n


def test_segmented_order_matches_lexsort():
    """ranks -> permutation ≡ np.lexsort((job_id, key, group)): the same
    (key, id)-ascending per-group layout the replan engine publishes."""
    import jax.numpy as jnp

    from repro.accel.kernels import segmented_order
    rng = np.random.default_rng(2)
    for n in (1, 6, 50, 257):
        seg = np.sort(rng.integers(0, max(1, n // 6) + 1, n)).astype(np.int32)
        keys = rng.uniform(0, 10, n).astype(np.float32)
        ties = rng.permutation(n).astype(np.int32)
        perm = np.asarray(segmented_order(jnp.asarray(seg), jnp.asarray(keys),
                                          jnp.asarray(ties), interpret=True))
        assert np.array_equal(perm, np.lexsort((ties, keys, seg))), n


# ------------------------------------------------------- state mechanics

def test_state_capacity_depletes_in_priority_order():
    r0, r1 = FakeReq(2), FakeReq(3)
    state = MatchState.from_scheduler(
        FakeSched([[(r0, -math.inf, math.inf), (r1, -math.inf, math.inf)]]),
        token=("t",))
    aids = np.zeros(6, dtype=np.int64)
    speeds = np.ones(6)
    res = match_chunk(aids, speeds, state)
    # first 2 -> r0, next 3 -> r1, last unmatched
    assert res.choice.tolist() == [0, 0, 1, 1, 1, -1]
    assert res.granted.tolist() == [True] * 5 + [False]


def test_state_tier_band_respected():
    r0 = FakeReq(10)
    state = MatchState.from_scheduler(
        FakeSched([[(r0, 1.0, 2.0)]]), token=("t",))
    aids = np.zeros(4, dtype=np.int64)
    speeds = np.array([0.5, 1.0, 1.99, 2.0])
    res = match_chunk(aids, speeds, state)
    assert res.granted.tolist() == [False, True, True, False]


def test_truncated_row_expands_exactly():
    # 40 requests on one atom, all with demand 1 and the first 39 filled:
    # with kcap=4 the row is truncated and the matcher must expand to find
    # the 40th
    reqs = [FakeReq(1, granted=1) for _ in range(39)] + [FakeReq(1)]
    row = [(r, -math.inf, math.inf) for r in reqs]
    sched = FakeSched([row])
    engine = ArrayMatchEngine(kcap=4)
    sched.prepare_match = lambda now: None
    sched.match_token = lambda: ("t",)
    sched.index = type("I", (), {"num_atoms": 1})()
    engine.prepare(sched, 0.0)
    res = engine.match(np.zeros(3, dtype=np.int64), np.ones(3))
    assert res.choice.tolist() == [39, -1, -1]
    assert res.granted.tolist() == [True, False, False]
    assert engine.expansions >= 1


def test_export_cap_exhaustion_widens_and_terminates():
    """A row whose exported prefix is entirely dead must trigger
    NeedWiderExport (not loop forever) and find the live slot after the
    caller re-prepares with the widened cap."""
    from repro.accel.engine import NeedWiderExport
    reqs = [FakeReq(1, granted=1) for _ in range(150)] + [FakeReq(1)]
    row = [(r, -math.inf, math.inf) for r in reqs]
    sched = FakeSched([row])
    sched.prepare_match = lambda now: None
    sched.match_token = lambda: ("t",)
    sched.index = type("I", (), {"num_atoms": 1})()
    engine = ArrayMatchEngine()
    aids = np.zeros(2, dtype=np.int64)
    speeds = np.ones(2)
    res = None
    for _ in range(12):
        engine.prepare(sched, 0.0)
        try:
            res = engine.match(aids, speeds)
            break
        except NeedWiderExport:
            continue
    assert res is not None, "match never terminated after widening"
    assert res.choice.tolist() == [150, -1]
    assert res.granted.tolist() == [True, False]


def test_new_atom_after_state_build_takes_miss_path():
    """classify() interns new atom ids without an index.version bump; a
    cached miss-free state must not blind the drain to them (regression:
    IndexError in engine.match on the fresh id)."""
    from repro.core.types import Job
    from repro.sim.devices import (DeviceChunk, REQ_COMPUTE, REQ_GENERAL)

    class TwoComboStream:
        fail_base = 0.0
        fail_slow_boost = 0.0

        def __init__(self):
            self._i = 0

        def next_chunk(self):
            self._i += 1
            n = 40
            if self._i == 1:        # compute-rich devices: atom {g, cr}
                t = np.linspace(10, 400, n)
                cpu, mem = np.full(n, 10.0), np.full(n, 1.0)
            elif self._i == 2:      # general-only devices: a NEW atom {g}
                t = np.linspace(500, 900, n)
                cpu, mem = np.full(n, 1.0), np.full(n, 10.0)
            else:
                return None
            return DeviceChunk(times=t, cpu=cpu, mem=mem, speed=np.ones(n),
                               resp_z=np.zeros(n), fail_u=np.full(n, 0.9))

    def jobs():
        return [Job(job_id=0, requirement=REQ_GENERAL, demand_per_round=500,
                    total_rounds=1, arrival_time=0.0),
                Job(job_id=1, requirement=REQ_COMPUTE, demand_per_round=500,
                    total_rounds=1, arrival_time=0.0)]

    cfg = SimConfig(max_time=1000.0)
    m_py = Simulator(jobs(), SCHEDULERS["fifo"](seed=0), cfg=cfg,
                     stream=TwoComboStream(), engine=None).run()
    m_ar = Simulator(jobs(), SCHEDULERS["fifo"](seed=0), cfg=cfg,
                     stream=TwoComboStream(), engine="array").run()
    assert m_py.jcts == m_ar.jcts
    assert m_py.rounds == m_ar.rounds


def test_first_miss_flags_uncovered_atoms():
    state = MatchState.from_scheduler(
        FakeSched([[], None, []]), token=("t",))
    assert state.first_miss(np.array([0, 2, 0])) == -1
    assert state.first_miss(np.array([0, 1, 0])) == 1
    assert state.first_miss(np.array([5])) == 0      # beyond the id space


# ------------------------------------------------------------ supply SoA

def test_supply_rings_match_scalar_rates():
    rng = np.random.default_rng(0)
    est = SupplyEstimator(window=3600.0, bucket=60.0)
    atoms = [frozenset({c}) for c in "abcd"]
    for t in np.sort(rng.uniform(0, 10_000, size=2000)):
        est.record(atoms[int(rng.integers(0, 4))], float(t))
    est.advance(10_500.0)
    view = SupplyRings.from_estimator(est)
    got = view.rates()
    want = np.array([est.rate_id(a) for a in range(4)])
    np.testing.assert_array_equal(got, want)


def test_snapshot_rates_matches_scalar_and_writes_back():
    rng = np.random.default_rng(1)
    est1 = SupplyEstimator(window=3600.0, bucket=60.0)
    est2 = SupplyEstimator(window=3600.0, bucket=60.0)
    atoms = [frozenset({c}) for c in "abc"]
    times = np.sort(rng.uniform(0, 20_000, size=3000))
    for t in times:
        a = atoms[int(rng.integers(0, 3))]
        est1.record(a, float(t))
        est2.record(a, float(t))
    est1.advance(21_000.0)
    est2.advance(21_000.0)
    seen, rates = est1.snapshot_rates()
    for aid in range(3):
        assert rates[aid] == est2.rate_id(aid)
        assert seen[aid] == (est2._totals[aid] > 0)
    # write-back left est1 consistent with the scalar path
    for aid in range(3):
        assert est1.rate_id(aid) == est2.rate_id(aid)


# ------------------------------------------------- end-to-end equivalence

def _run(jobs_cfg, pop, sim_cfg, sched_name, engine):
    sim = Simulator(generate_jobs(jobs_cfg), SCHEDULERS[sched_name](seed=1),
                    pop, sim_cfg, engine=engine, record_grants=True)
    metrics = sim.run()
    return metrics, sim


def _check_engine_equivalence(seed: int, sched_name: str, rate: float) -> None:
    jobs_cfg = JobTraceConfig(num_jobs=4, seed=seed, demand_lo=5,
                              demand_hi=60, rounds_lo=2, rounds_hi=6)
    pop = PopulationConfig(seed=seed + 7, base_rate=rate)
    sim_cfg = SimConfig(max_time=1.0 * 24 * 3600.0)
    m1, s1 = _run(jobs_cfg, pop, sim_cfg, sched_name, None)
    m2, s2 = _run(jobs_cfg, pop, sim_cfg, sched_name, "array")
    assert s1.grant_log == s2.grant_log       # identical grant sequences
    assert m1.jcts == m2.jcts
    assert m1.rounds == m2.rounds
    assert m1.summary() == m2.summary()


@pytest.mark.parametrize("seed,sched_name,rate", [
    (0, "venn", 1.5), (1, "random", 0.7), (2, "srsf", 3.0),
    (3, "venn", 4.0), (4, "fifo", 2.0), (5, "venn", 0.5),
])
def test_array_engine_equivalent_on_random_workloads(seed, sched_name, rate):
    _check_engine_equivalence(seed, sched_name, rate)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1_000),
       st.sampled_from(["venn", "random", "srsf"]), st.floats(0.5, 4.0))
def test_array_engine_equivalent_on_random_workloads_hyp(
        seed, sched_name, rate):
    _check_engine_equivalence(seed, sched_name, rate)


def test_array_engine_equivalent_with_tiering_and_contention():
    """Longer run that exercises tier bands, fills, aborts and replans."""
    jobs_cfg = JobTraceConfig(num_jobs=8, seed=5, demand_lo=20,
                              demand_hi=150, rounds_lo=3, rounds_hi=10)
    pop = PopulationConfig(seed=11, base_rate=3.0)
    sim_cfg = SimConfig(max_time=4.0 * 24 * 3600.0)
    m1, s1 = _run(jobs_cfg, pop, sim_cfg, "venn", None)
    m2, s2 = _run(jobs_cfg, pop, sim_cfg, "venn", "array")
    assert s1.grant_log == s2.grant_log
    assert m1.jcts == m2.jcts
    assert m1.rounds == m2.rounds
    assert s2.engine.segments > 0             # the array path actually ran


@pytest.mark.parametrize("platform,want", [("cpu", ("numpy", False)),
                                           ("tpu", ("jax", True))])
def test_backend_follows_platform(platform, want, monkeypatch):
    """The platform alone picks the matcher: NumPy on the CPU (the test
    path), the jitted fixed point with the Pallas kernel on a TPU."""
    import jax
    assert jax.default_backend() == "cpu"
    if platform == "tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engine = ArrayMatchEngine()
    assert (engine.backend, engine.use_kernel) == want
    sim = Simulator(generate_jobs(JobTraceConfig(num_jobs=1)),
                    VennScheduler(), engine="array")
    assert (sim.engine.backend, sim.engine.use_kernel) == want


def test_backend_exception_propagates(monkeypatch):
    """A device failure surfaces out of match(): no segment is quietly
    served by the host oracle."""
    import repro.accel.engine as engine_mod

    def lost(*args, **kwargs):
        raise RuntimeError("device lost")

    monkeypatch.setattr(engine_mod, "match_chunk_jax", lost)
    sched = FakeSched([[(FakeReq(5), -math.inf, math.inf)]])
    sched.prepare_match = lambda now: None
    sched.match_token = lambda: ("t",)
    sched.index = type("I", (), {"num_atoms": 1})()
    engine = ArrayMatchEngine(backend="jax")
    engine.prepare(sched, 0.0)
    with pytest.raises(RuntimeError, match="device lost"):
        engine.match(np.zeros(40, dtype=np.int64), np.ones(40))
    assert engine.degraded_segments == 0


def test_unknown_engine_rejected():
    with pytest.raises(ValueError, match="unknown engine"):
        Simulator(generate_jobs(JobTraceConfig(num_jobs=1)),
                  VennScheduler(), engine="warp")


# ------------------------------------------------------- mirror deltas

class DeltaFakeSched(FakeSched):
    """FakeSched speaking the mirror-delta protocol: mutate rows through
    :meth:`set_row` and the engine's next ``prepare`` patches exactly those
    atoms instead of rebuilding (``FakeSched`` has no ``match_delta``, so
    the plain fakes above always take the full-rebuild path)."""

    def __init__(self, slots):
        super().__init__(slots)
        self._inv = 0
        self._log = []          # (invocation, {dirty atom ids}) — unbounded
        self.index = type("I", (), {"num_atoms": len(slots)})()

    def prepare_match(self, now):
        pass

    def match_token(self):
        return (0, self._inv)

    def set_row(self, aid, row):
        self._slots[aid] = row
        self._inv += 1
        self._log.append((self._inv, {aid}))

    def match_delta(self, base_token):
        if base_token[0] != 0:
            return None
        dirty = set()
        for inv, entry in self._log:
            if inv > base_token[1]:
                dirty |= entry
        return dirty

    def export_match_rows(self, atom_ids, limit=None, copy=True):
        out = []
        for aid in atom_ids:
            s = self._slots[aid] if aid < len(self._slots) else None
            out.append(s if s is None or limit is None else s[:limit])
        return out


@pytest.mark.parametrize("seed", range(6))
def test_kcap_exhaustion_expands_on_patched_mirror(seed, monkeypatch):
    """A *patched* row longer than the candidate cap (but within the export
    limit) must widen-and-rematch in place: expansion fires, no rebuild."""
    monkeypatch.setenv("REPRO_MATCH_CHECK", "1")
    rng = np.random.default_rng(seed)
    A = int(rng.integers(2, 5))
    hot = int(rng.integers(0, A))
    slots = [[(FakeReq(int(rng.integers(1, 4))), -math.inf, math.inf)
              for _ in range(int(rng.integers(1, 4)))] for _ in range(A)]
    sched = DeltaFakeSched(slots)
    engine = ArrayMatchEngine()
    engine.prepare(sched, 0.0)
    assert engine.rebuilds == 1
    # dead prefix deeper than kcap but inside the export limit: the patched
    # mirror marks the row truncated and expand() finds the live tail
    n_dead = int(rng.integers(40, 100))
    tail = FakeReq(int(rng.integers(1, 3)))
    sched.set_row(hot, [(FakeReq(1, granted=1), -math.inf, math.inf)
                        for _ in range(n_dead)]
                  + [(tail, -math.inf, math.inf)])
    engine.prepare(sched, 0.0)
    assert engine.patches == 1 and engine.rebuilds == 1
    nseg = int(rng.integers(2, 6))
    res = engine.match(np.full(nseg, hot, dtype=np.int64), np.ones(nseg))
    assert engine.expansions >= 1 and engine.rebuilds == 1
    want = min(tail.demand, nseg)
    assert int(res.granted.sum()) == want
    assert all(engine.state.requests[res.choice[i]] is tail
               for i in range(want))


@pytest.mark.parametrize("seed", range(6))
def test_export_exhaustion_rewidens_from_patched_mirror(seed, monkeypatch):
    """A patched row whose *exported prefix* is entirely dead must raise
    NeedWiderExport and find the live slot after the wider re-export — the
    widen-and-rematch audit under the delta path."""
    from repro.accel.engine import NeedWiderExport
    monkeypatch.setenv("REPRO_MATCH_CHECK", "1")
    rng = np.random.default_rng(100 + seed)
    A = int(rng.integers(2, 5))
    hot = int(rng.integers(0, A))
    slots = [[(FakeReq(int(rng.integers(1, 4))), -math.inf, math.inf)
              for _ in range(int(rng.integers(1, 4)))] for _ in range(A)]
    sched = DeltaFakeSched(slots)
    engine = ArrayMatchEngine()
    engine.prepare(sched, 0.0)
    # beyond the default export limit (max(4*kcap, 128)): the patch keeps an
    # export-capped prefix, exhaustion must re-export wider via rebuild
    n_dead = int(rng.integers(130, 180))
    tail = FakeReq(int(rng.integers(1, 3)))
    sched.set_row(hot, [(FakeReq(1, granted=1), -math.inf, math.inf)
                        for _ in range(n_dead)]
                  + [(tail, -math.inf, math.inf)])
    nseg = int(rng.integers(2, 6))
    aids = np.full(nseg, hot, dtype=np.int64)
    res = None
    for _ in range(12):
        engine.prepare(sched, 0.0)
        try:
            res = engine.match(aids, np.ones(nseg))
            break
        except NeedWiderExport:
            continue
    assert res is not None, "match never terminated after widening"
    assert engine.patches >= 1, "exhaustion did not start from a patch"
    assert engine.rebuilds >= 2          # the wider re-export rebuilt
    want = min(tail.demand, nseg)
    assert int(res.granted.sum()) == want
    assert all(engine.state.requests[res.choice[i]] is tail
               for i in range(want))


def _drive_mirror_vs_truth(mode: str, seed: int, steps: int = 30) -> None:
    """Step-level dual-universe check: after every arrival / completion /
    grant / replan the delta-patched mirror must equal ``from_scheduler``
    truth (``verify_against`` compares rows, coverage, remaining)."""
    from repro.core.types import Job, JobRequest
    from repro.sim.devices import REQUIREMENT_CLASSES

    rng = np.random.default_rng(seed)
    sched = VennScheduler(seed=0, replan=mode)
    engine = ArrayMatchEngine()
    jobs = {}
    caps = {"cpu": 4.0 * np.exp(0.6 * rng.standard_normal(60)),
            "mem": 4.0 * np.exp(0.6 * rng.standard_normal(60))}
    t, next_id = 0.0, 0

    def verify(now):
        engine.prepare(sched, now)
        engine.state.verify_against(sched)

    for _ in range(steps):
        t += float(rng.uniform(1.0, 50.0))
        open_ids = [jid for jid, j in jobs.items()
                    if j.current is not None
                    and j.current.demand > j.current.granted]
        op = rng.uniform()
        if op < 0.35 or not open_ids:
            cls = REQUIREMENT_CLASSES[int(rng.integers(
                0, len(REQUIREMENT_CLASSES)))]
            j = Job(job_id=next_id, requirement=cls,
                    demand_per_round=int(rng.integers(1, 8)),
                    total_rounds=int(rng.integers(1, 4)), arrival_time=t,
                    priority=float(rng.choice([0.5, 1.0, 2.0])))
            r = JobRequest(job=j, round_index=0, demand=j.demand_per_round,
                           submit_time=t)
            j.current = r
            jobs[next_id] = j
            next_id += 1
            sched.on_request(r, t)
        elif op < 0.70:
            r = jobs[int(rng.choice(open_ids))].current
            # apply the grant exactly as the simulator does: scheduler event
            # plus the mirrored remaining decrement
            r.granted += 1
            sched.on_grant(r)
            ix = engine.state.request_index(r)
            if ix is not None:
                engine.state.consume(ix)
            else:
                engine.invalidate()
        else:
            j = jobs[int(rng.choice(open_ids))]
            r = j.current
            sched.on_complete(r, t)
            j.rounds_done += 1
            if rng.uniform() < 0.7 and j.rounds_done < j.total_rounds:
                nxt = JobRequest(job=j, round_index=r.round_index + 1,
                                 demand=j.demand_per_round, submit_time=t)
                j.current = nxt
                sched.on_request(nxt, t)
            else:
                j.current = None
        times = np.sort(rng.uniform(t - 40.0, t, size=10))
        sel = rng.integers(0, 60, size=10)
        sched.supply.record_batch(
            sched.classify_caps(caps)[sel].astype(np.int64), times)
        verify(t)
    assert engine.patches > 0, "the delta path never engaged"


@pytest.mark.parametrize("mode", ["scalar", "array"])
@pytest.mark.parametrize("seed", [0, 3])
def test_patched_mirror_equals_truth_stepwise(mode, seed):
    _drive_mirror_vs_truth(mode, seed)


def test_restore_drops_mirror_and_resyncs():
    """Pickle/restore drops the mirror (engine state) and the scheduler's
    delta log; the next prepare full-rebuilds and deltas resume after."""
    import pickle

    from repro.core.types import Job, JobRequest
    from repro.sim.devices import REQUIREMENT_CLASSES

    sched = VennScheduler(seed=0, replan="array")
    engine = ArrayMatchEngine()
    jobs = {}
    rng = np.random.default_rng(7)
    caps = {"cpu": 4.0 * np.exp(0.6 * rng.standard_normal(40)),
            "mem": 4.0 * np.exp(0.6 * rng.standard_normal(40))}
    t = 0.0
    for jid in range(6):
        t += 10.0
        cls = REQUIREMENT_CLASSES[jid % len(REQUIREMENT_CLASSES)]
        j = Job(job_id=jid, requirement=cls, demand_per_round=5,
                total_rounds=2, arrival_time=t, priority=1.0)
        r = JobRequest(job=j, round_index=0, demand=5, submit_time=t)
        j.current = r
        jobs[jid] = j
        sched.on_request(r, t)
        sched.supply.record_batch(
            sched.classify_caps(caps)[:8].astype(np.int64),
            np.sort(rng.uniform(t - 9.0, t, size=8)))
        engine.prepare(sched, t)
        engine.state.verify_against(sched)
    assert engine.patches > 0
    before = engine.patches
    # ---- snapshot / restore mid-flight
    sched, engine = pickle.loads(pickle.dumps((sched, engine)))
    assert engine.state is None              # the mirror did not survive
    jobs = {r.job.job_id: r.job for r in sched.pending}
    t += 10.0
    engine.prepare(sched, t)                 # resync: full rebuild
    engine.state.verify_against(sched)
    assert engine.patches == before          # no patch against a dropped log
    # deltas resume after the post-restore replan re-seeds the row mirror:
    # the first replanning event's log entry is None (nothing to diff
    # against the dropped log), the second patches again
    for k in (1, 2):
        t += 10.0
        cls = REQUIREMENT_CLASSES[k % len(REQUIREMENT_CLASSES)]
        j = Job(job_id=100 + k, requirement=cls, demand_per_round=3,
                total_rounds=1, arrival_time=t, priority=1.0)
        r = JobRequest(job=j, round_index=0, demand=3, submit_time=t)
        j.current = r
        sched.on_request(r, t)
        engine.prepare(sched, t)
        engine.state.verify_against(sched)
    assert engine.patches == before + 1
