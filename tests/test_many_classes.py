"""Many requirement classes (the benchmark's ``grid8x8`` configuration, 64
classes):

* past 63 classes ``EligibilityIndex.classify`` interns new atoms in
  ascending code order (bit b = the b-th class asked), the order of its
  narrower branches and of the plain reference ``bench/reference.py``, and
  the program agrees with the reference on a grid that asks all 64 classes;
* ``EligibilityIndex.refine`` folds new classes into a tail's atom ids and
  gives the ids and interning order of a full ``classify``, at every class
  count up to 70, in both drains and across a snapshot and restore;
* the ``sim.reclassify`` span and counters count every re-classification
  of a chunk's tail and its rows, and every one is a fold; the replan and
  expansion counters sum
  what their spans and the engine report; with the registry off none of
  them is touched;
* the ``reclassify_share`` and ``replan_share`` readers."""
import math
import pickle
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare, reference, run, system, workload   # noqa: E402
from bench.tests.test_bench_reference import (                # noqa: E402
    _hand_built, grid_config)
from bench.tests.test_bench_rehearsal import traffic_blocks   # noqa: E402
from repro import obs                                          # noqa: E402
from repro.accel.engine import (ArrayMatchEngine,              # noqa: E402
                                NeedWiderExport)
import repro.accel.engine as engine_mod                        # noqa: E402
from repro.core.eligibility import EligibilityIndex            # noqa: E402
from repro.core.types import Requirement                       # noqa: E402
from repro.faults import restore_simulator, snapshot_simulator  # noqa: E402
from repro.obs import metrics as obsmetrics                    # noqa: E402
from repro.sim.simulator import Simulator                      # noqa: E402

NEW_COUNTERS = ("sim.reclassify_wall_s", "sim.reclassify_rows",
                "sim.reclassifies", "venn.replan_wall_s",
                "venn.replan_groups", "venn.replan_atoms",
                "accel.expansions", "accel.wider_exports",
                "sim.reclassify_folded", "sim.reclassify_bits")


@pytest.fixture(autouse=True)
def _obs_disabled():
    obs.disable()
    yield
    obs.disable()


# ------------------------------------------------------ interning order

@pytest.mark.parametrize("R", [64, 70])
def test_classify_interns_ascending_codes_past_63_classes(R):
    ep, sat = _hand_built(R)
    asked = [j["cls"] for j in ep["jobs"]]
    mins = {r["name"]: r["mins"] for r in ep["requirements"]}
    index = EligibilityIndex([])
    for name in asked:
        index.add_requirement(Requirement.of(name, **mins[name]))
    ck = ep["chunks"][0]
    ids = index.classify({"cpu": ck["cpu"], "mem": ck["mem"]})

    bit = {name: b for b, name in enumerate(asked)}
    codes = [sum(1 << bit[c] for c in index.key_of(a))
             for a in range(index.num_atoms)]
    assert codes == sorted(codes)
    assert max(codes).bit_length() > 63
    assert [index.key_of(a) for a in ids.tolist()] == sat

    ref = reference.Reference(ep)
    for job in ref.jobs:
        ref._submit(job, 0, 0.0)
    ref._classify(0, 0)
    assert ref.names == asked
    assert ids.tolist() == ref.chunks[0]["atoms"].tolist()
    assert codes == ref.codes


# ------------------------------------------------------ folding new classes

def _asked_requirements(R):
    """``_hand_built(R)``'s classes in the order they are asked, and its
    chunk's capabilities."""
    ep, _ = _hand_built(R)
    mins = {r["name"]: r["mins"] for r in ep["requirements"]}
    reqs = [Requirement.of(j["cls"], **mins[j["cls"]]) for j in ep["jobs"]]
    ck = ep["chunks"][0]
    return reqs, {"cpu": ck["cpu"], "mem": ck["mem"]}


@pytest.mark.parametrize("step", [1, 3])
@pytest.mark.parametrize("start", [0, 250])
def test_refine_equals_classify(step, start):
    """Fold ``step`` classes at a time into the tail from row ``start`` on,
    up to 70 classes, crossing ``classify``'s R = 16/17 and 63/64 branches;
    after each fold the ids and the interned keys, in order, are those of
    an index that classifies the tail in full at every step."""
    reqs, caps = _asked_requirements(70)
    tail = {c: v[start:] for c, v in caps.items()}
    fold, full = EligibilityIndex([]), EligibilityIndex([])
    ids = fold.classify(caps)[start:]
    full.classify(caps)
    since = 0
    while since < len(reqs):
        R = min(since + step, len(reqs))
        for r in reqs[since:R]:
            fold.add_requirement(r)
            full.add_requirement(r)
        ids = fold.refine(ids, tail, since)
        assert ids.tolist() == full.classify(tail).tolist(), R
        assert fold.interner.keys() == full.interner.keys(), R
        since = R
    assert fold.num_atoms > 100


def test_refine_clears_rows_with_nan_in_a_new_column():
    """A NaN in a capability no earlier class named fails every class in
    ``classify``, the earlier ones too; the fold agrees."""
    caps = {"cpu": np.array([1.0, 3.0, 3.0, np.nan]),
            "mem": np.array([np.nan, 1.0, np.nan, 2.0])}
    fold, full = EligibilityIndex([]), EligibilityIndex([])
    fold.add_requirement(Requirement.of("c", cpu=2.0))
    full.add_requirement(Requirement.of("c", cpu=2.0))
    ids = fold.classify(caps)
    full.classify(caps)
    for idx in (fold, full):
        idx.add_requirement(Requirement.of("m", mem=1.0))
    assert fold.refine(ids, caps, 1).tolist() == full.classify(caps).tolist()
    assert fold.interner.keys() == full.interner.keys()


def _spaced_episode(R=70):
    """``_hand_built(R)`` with its classes asked one every 2 s, so each
    re-classification folds one class into a tail that starts mid-chunk."""
    ep, _ = _hand_built(R)
    for k, job in enumerate(ep["jobs"]):
        job["arrival"] = 1.0 + 2.0 * k
    ep["traffic"] = {"episode_sim_s": 180.0}
    return ep


@pytest.mark.parametrize("engine", ["python", "array"])
def test_drains_fold_as_classify_would(engine, monkeypatch):
    """Every fold either drain makes gives the ids and interning order that
    a full ``classify`` of the same tail gives on a copy of the index."""
    checked = []
    refine = EligibilityIndex.refine

    def checked_refine(index, ids, caps, since):
        twin = pickle.loads(pickle.dumps(index))
        want = twin.classify(caps)
        got = refine(index, ids, caps, since)
        assert got.tolist() == want.tolist()
        assert index.interner.keys() == twin.interner.keys()
        checked.append((len(ids), len(index.requirements) - since))
        return got

    monkeypatch.setattr(EligibilityIndex, "refine", checked_refine)
    system.make_simulator(_spaced_episode(), engine=engine).run()
    assert len(checked) == 70
    assert all(bits == 1 for _, bits in checked)
    assert 0 < min(n for n, _ in checked) < max(n for n, _ in checked) < 600


@pytest.mark.parametrize("engine", ["python", "array"])
def test_restore_between_new_class_and_fold(engine, tmp_path, monkeypatch):
    """A snapshot taken when a new class has bumped the atom version and
    the tail is not yet re-classified restores to the grants of a run
    never interrupted, and the restored run folds."""
    ep = _spaced_episode()
    whole = system.make_simulator(ep, engine=engine)
    whole.run()

    arrive = Simulator._on_job_arrival

    def arrive_and_snapshot(sim, job):
        arrive(sim, job)
        if job.job_id == 30:
            assert sim.sched.index.version != sim._chunk_version
            snapshot_simulator(sim, str(tmp_path), 0)

    monkeypatch.setattr(Simulator, "_on_job_arrival", arrive_and_snapshot)
    system.make_simulator(ep, engine=engine).run()
    monkeypatch.setattr(Simulator, "_on_job_arrival", arrive)
    with obs.session(tracing=False, metrics=True) as (_, reg):
        restored = restore_simulator(str(tmp_path))
        restored.run()
        c = {m["name"]: m["value"] for m in reg.snapshot()
             if m["kind"] == "counter"}
    assert restored.grant_log == whole.grant_log
    assert len(whole.grant_log) > 0
    assert c["sim.reclassify_folded"] == c["sim.reclassifies"] == 70 - 30


@pytest.fixture
def device_path(monkeypatch):
    monkeypatch.setattr(engine_mod, "platform_backend", lambda: ("jax", True))


def test_reference_agrees_with_program_asking_all_64_classes(device_path):
    """The ``grid8x8`` configuration at a size where every class is asked,
    so the program's classification crosses into its widest branch."""
    sizes = dict(traffic_blocks("rehearsal")["grid8x8.r500"], base_rate=6,
                 num_jobs=64, mean_interarrival_s=4, episode_sim_s=300)
    ep = workload.make_episode("grid8x8", "r500", 2**31 + 99, overrides=sizes)
    assert ep["config"]["requirements"] == grid_config(range(1, 9))[
        "requirements"]
    horizon = float(ep["traffic"]["episode_sim_s"])
    assert len({j["cls"] for j in ep["jobs"]
                if j["arrival"] < horizon}) == 64
    ans = run.play(ep, run.batch_edges(ep["traffic"]), float("inf"), [])[0]
    ref = reference.Reference(ep)
    want = ref.run(horizon)
    assert len(ref.names) == 64
    assert len(want["rounds"]) > 0
    assert compare.numbers(ans, want) == dict.fromkeys(compare.LIMITS, 0)


def test_every_reclassification_of_the_grid_folds():
    """On the ``grid8x8`` episode every re-classification is a fold, of
    one class each: the classes asked while a chunk was loaded."""
    sizes = dict(traffic_blocks("rehearsal")["grid8x8.r500"], base_rate=6,
                 num_jobs=64, mean_interarrival_s=4, episode_sim_s=300)
    ep = workload.make_episode("grid8x8", "r500", 2**31 + 7, overrides=sizes)
    with obs.session(tracing=False, metrics=True) as (_, reg):
        sim = system.make_simulator(ep)
        sim.run()
        c = {m["name"]: m["value"] for m in reg.snapshot()
             if m["kind"] == "counter"}
    asked = len(sim.sched.index.requirements)
    assert asked > 16
    assert c["sim.reclassify_folded"] == c["sim.reclassifies"] == asked
    assert c["sim.reclassify_bits"] == asked


# ------------------------------------------------------ counters

ARRIVALS = (10.0, 20.0, 30.0, 40.0)     # the last job repeats a class


def _episode(n=500):
    """Four jobs of three classes arriving at :data:`ARRIVALS`, and one
    chunk of ``n`` check-ins over 100 s."""
    rng = np.random.default_rng(3)
    reqs = [{"name": f"class{i}", "mins": {"cpu": 1.0 + i, "mem": 1.0}}
            for i in range(3)]
    chunk = {"times": np.sort(rng.uniform(0.0, 100.0, n)),
             "cpu": rng.uniform(0.5, 5.0, n), "mem": rng.uniform(0.5, 5.0, n),
             "speed": rng.uniform(0.5, 2.0, n), "resp_z": np.zeros(n),
             "fail_u": np.ones(n)}
    jobs = [{"job_id": k, "cls": f"class{k % 3}", "demand": 5, "rounds": 2,
             "arrival": t, "task_mean": 5.0, "task_sigma": 0.1,
             "quorum": 1.0, "deadline": 30.0}
            for k, t in enumerate(ARRIVALS)]
    return {"config": workload.load_json("configs", "even4"),
            "traffic": {"episode_sim_s": 100.0}, "requirements": reqs,
            "jobs": jobs, "chunks": [chunk], "sched_seed": 1,
            "fail_base": 0.0, "fail_slow_boost": 0.0}


@pytest.mark.parametrize("engine", ["python", "array"])
def test_reclassify_counts_rows_and_calls(engine):
    ep = _episode()
    times = ep["chunks"][0]["times"]
    # every new class bumps the atom version once; the next drain
    # re-classifies the rows after the arrival
    want_rows = [len(times) - int(np.searchsorted(times, t, side="right"))
                 for t in ARRIVALS[:3]]
    with obs.session(tracing=True, metrics=True) as (tr, reg):
        system.make_simulator(ep, engine=engine).run()
        c = {m["name"]: m["value"] for m in reg.snapshot()
             if m["kind"] == "counter"}
        spans = [e for e in tr.events
                 if e.get("ph") == "X" and e["name"] == "sim.reclassify"]
    assert c["sim.reclassifies"] == 3
    assert c["sim.reclassify_rows"] == sum(want_rows)
    assert c["sim.reclassify_wall_s"] > 0
    assert [s["args"]["rows"] for s in spans] == want_rows
    assert [s["args"]["classes"] for s in spans] == [1, 2, 3]


def test_replan_counters_sum_the_replans():
    ep = _episode()
    with obs.session(tracing=True, metrics=True) as (tr, reg):
        system.make_simulator(ep).run()
        c = {m["name"]: m["value"] for m in reg.snapshot()
             if m["kind"] == "counter"}
        hist = reg.get("venn.replan_latency_s")
        spans = [e for e in tr.events if e.get("ph") == "X"]
    replans = [s for s in spans if s["name"] == "venn.replan"]
    supply = [s for s in spans if s["name"] == "venn.replan.supply"]
    assert c["venn.replans"] == len(replans) == hist.count > 0
    assert c["venn.replan_wall_s"] == pytest.approx(hist.total)
    assert c["venn.replan_groups"] == sum(s["args"]["groups"]
                                          for s in replans) > 0
    assert c["venn.replan_atoms"] == sum(s["args"]["atoms"]
                                         for s in supply) > 0


class _Req:
    def __init__(self, demand, granted=0):
        self.demand, self.granted = demand, granted


def _deep_row_sched(n_filled):
    """One atom whose dispatch row holds ``n_filled`` filled requests and
    then one open request."""
    row = [(_Req(1, granted=1), -math.inf, math.inf)
           for _ in range(n_filled)] + [(_Req(1), -math.inf, math.inf)]
    return SimpleNamespace(
        export_match_slots=lambda limit=None: [row[:limit]],
        prepare_match=lambda now: None, match_token=lambda: ("t",),
        index=SimpleNamespace(num_atoms=1))


def _match_deep_row(n_filled, kcap):
    """Match two check-ins of the atom, re-preparing after each wider
    export, as the simulator's drain does."""
    eng = ArrayMatchEngine(backend="numpy", kcap=kcap)
    sched = _deep_row_sched(n_filled)
    for _ in range(12):
        eng.prepare(sched, 0.0)
        try:
            res = eng.match(np.zeros(2, dtype=np.int64), np.ones(2))
            break
        except NeedWiderExport:
            continue
    assert res.choice.tolist() == [n_filled, -1]
    return eng


@pytest.mark.parametrize("n_filled,kcap,wider", [(39, 4, False),
                                                  (150, 32, True)])
def test_expansion_counters_follow_the_engine(n_filled, kcap, wider):
    with obs.session(tracing=True, metrics=True) as (tr, reg):
        eng = _match_deep_row(n_filled, kcap)
        c = {m["name"]: m["value"] for m in reg.snapshot()
             if m["kind"] == "counter"}
        instants = [e for e in tr.events if e["name"] == "accel.expand"]
    assert eng.expansions >= 1
    assert c["accel.expansions"] == eng.expansions == len(instants)
    assert c.get("accel.wider_exports", 0) == (eng.rebuilds - 1 if wider
                                               else 0)
    assert (c.get("accel.wider_exports", 0) > 0) == wider


def test_new_counters_untouched_with_the_registry_off(monkeypatch):
    off = obsmetrics.MetricsRegistry()
    off.enabled = False
    with obs.session(tracing=True, metrics=False) as (tr, _):
        monkeypatch.setattr(obsmetrics, "REGISTRY", off)
        system.make_simulator(_episode()).run()
        _match_deep_row(150, 32)
        names = {e["name"] for e in tr.events}
    assert {"sim.reclassify", "venn.replan", "accel.expand"} <= names
    assert not set(NEW_COUNTERS) & set(off.names())
    # with observability off altogether the globals stay the null singletons
    system.make_simulator(_episode()).run()
    _match_deep_row(150, 32)
    assert obsmetrics.REGISTRY is obsmetrics.NULL_REGISTRY


# ------------------------------------------------------ readers

@pytest.mark.parametrize("name,counter", [
    ("reclassify_share", "sim.reclassify_wall_s"),
    ("replan_share", "venn.replan_wall_s"),
])
def test_share_readers(name, counter):
    reader = run.load_reader(name)
    ctx = {"window_s": 50.0, "counters": {counter: 2.0, "other": 9.0}}
    assert reader.read(ctx) == pytest.approx(4.0)
    assert reader.read({"window_s": 50.0, "counters": {"other": 9.0}}) is None
    assert reader.read({"window_s": 50.0, "counters": {counter: 0.0}}) == 0.0
