"""The plain reference (``bench/reference.py``) takes any number of
requirement classes: its records for the benchmark's configurations are
those it gave with a six-class cap, its atom codes, class tests and supply
counts are exact past 64 classes, and it agrees with the program (on the
CPU, device path patched as in the rehearsal) over grids of 16 and 49
classes."""
import hashlib
import json
import math
import random
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare, reference, run, workload          # noqa: E402
from bench.tests.test_bench_rehearsal import traffic_blocks   # noqa: E402
import repro.accel.engine as engine_mod                       # noqa: E402

# sha256 (first 16 hex digits) of the reference's grants, round records and
# finish times, as the reference with the six-class cap computed them
GOLDEN = {
    ("biased_hp", "r500", "rehearsal", 2147487890): "eab8271c2b86d6ef",
    ("biased_hp", "r500", "rehearsal", 3486784401): "ab8ca029b91c826f",
    ("biased_hp", "r500", "control", 2147487890): "cdad898dfae9a644",
    ("biased_hp", "r500", "control", 3486784401): "2ecc3e623fa57e66",
    ("even4", "r500", "rehearsal", 2147487890): "7615678990896306",
    ("even4", "r500", "rehearsal", 3486784401): "4f73f38e52305f5b",
    ("even4", "r500", "control", 2147487890): "de48add88d8277f4",
    ("even4", "r500", "control", 3486784401): "87bf577b8d6ce0fd",
    ("even4", "r2", "rehearsal", 2147487890): "8794ba9883a85433",
    ("even4", "r2", "rehearsal", 3486784401): "fef17e07f34e4ffd",
    ("even4", "r2", "control", 2147487890): "fb1af1676797bf0c",
    ("even4", "r2", "control", 3486784401): "17974c9ee4499e41",
}


def digest(rec: dict) -> str:
    blob = json.dumps([rec["grants"], rec["rounds"],
                       sorted(rec["finished"].items())])
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def grid_config(thresholds) -> dict:
    """``even4`` with its classes replaced by the grid ``cpu >= a``,
    ``mem >= b`` over ``thresholds``, the jobs spread evenly over them."""
    cfg = workload.load_json("configs", "even4")
    cfg["requirements"] = [
        {"name": f"cpu{a}_mem{b}", "mins": {"cpu": float(a), "mem": float(b)}}
        for a in thresholds for b in thresholds]
    cfg["job_classes"] = [r["name"] for r in cfg["requirements"]]
    return cfg


@pytest.mark.parametrize("config,traffic,kind,seed", sorted(GOLDEN),
                         ids=lambda v: str(v))
def test_records_match_six_class_reference(config, traffic, kind, seed):
    sizes = workload.load_json("traffic", traffic)[kind]
    ep = workload.make_episode(config, traffic, seed, overrides=sizes)
    rec = reference.run_reference(ep, float(ep["traffic"]["episode_sim_s"]))
    assert digest(rec) == GOLDEN[(config, traffic, kind, seed)]


def _hand_built(R: int):
    """``R`` classes over a grid of cpu and mem thresholds (one class with
    a cpu floor alone), asked in a shuffled order, and one chunk of 600
    check-ins over three supply buckets whose capacities sit on and
    between the thresholds."""
    side = math.ceil(math.sqrt(R))
    mins = [{"cpu": 1.0 + i % side, "mem": 1.0 + i // side} for i in range(R)]
    mins[-1] = {"cpu": 2.5}
    reqs = [{"name": f"class{i}", "mins": m} for i, m in enumerate(mins)]
    rng = np.random.default_rng(R)
    n = 600
    grid = np.arange(0.5, side + 1.5, 0.5)
    chunk = {"times": np.sort(rng.uniform(0.0, 180.0, n)),
             "cpu": rng.choice(grid, n), "mem": rng.choice(grid, n),
             "speed": np.ones(n), "resp_z": np.zeros(n),
             "fail_u": np.ones(n)}
    asked = list(range(R))
    random.Random(R).shuffle(asked)
    jobs = [{"job_id": k, "cls": f"class{i}", "demand": 2, "rounds": 1,
             "arrival": 0.0, "task_mean": 10.0, "task_sigma": 0.1,
             "quorum": 1.0, "deadline": 100.0} for k, i in enumerate(asked)]
    cfg = workload.load_json("configs", "even4")
    ep = {"config": cfg, "requirements": reqs, "jobs": jobs,
          "chunks": [chunk], "sched_seed": 1, "fail_base": 0.0,
          "fail_slow_boost": 0.0}
    sat = [frozenset(r["name"] for r in reqs
                     if all(chunk[c][i] >= v for c, v in r["mins"].items()))
           for i in range(n)]
    return ep, sat


@pytest.mark.parametrize("R", [64, 70])
def test_atoms_exact_past_64_classes(R):
    ep, sat = _hand_built(R)
    ref = reference.Reference(ep)
    for job in ref.jobs:
        ref._submit(job, 0, 0.0)
    assert len(ref.names) == R
    ref._classify(0, 0)
    atoms = ref.chunks[0]["atoms"].tolist()
    # one atom per distinct set of satisfied classes, and back
    by_set = {}
    for s, a in zip(sat, atoms):
        assert by_set.setdefault(s, a) == a
    assert len(set(by_set.values())) == len(by_set) > R // 2
    # each code's bit b is the b-th class asked; new codes interned ascending
    for s, a in by_set.items():
        assert ref.codes[a] == sum(1 << ref.names.index(c) for c in s)
    assert ref.codes == sorted(ref.codes)
    assert max(ref.codes).bit_length() > 63
    for s, a in by_set.items():
        for name in ref.names:
            assert ref._has(a, name) == (name in s)
    # supply counts per (bucket, atom), exactly
    ref._absorb(math.inf)
    buckets = (ep["chunks"][0]["times"] // 60.0).astype(int).tolist()
    want = Counter((b, by_set[s]) for b, s in zip(buckets, sat))
    got = Counter({(b, a): n for b, per in ref.counts.items()
                   for a, n in per.items()})
    assert got == want
    assert ref.totals == dict(Counter(atoms))


@pytest.fixture
def device_path(monkeypatch):
    monkeypatch.setattr(engine_mod, "platform_backend", lambda: ("jax", True))


@pytest.mark.parametrize("side,thresholds,extra", [
    (4, (2, 4, 6, 8), {}),
    # every one of the 49 classes asked within the episode
    (7, tuple(range(1, 8)), {"num_jobs": 49, "mean_interarrival_s": 5}),
], ids=["grid16", "grid49"])
def test_reference_agrees_with_program_on_grids(side, thresholds, extra,
                                                tmp_path, device_path):
    name = f"grid{side * side}"
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs" / f"{name}.json").write_text(
        json.dumps(grid_config(thresholds)))
    (tmp_path / "traffic" / "r500.json").write_text(
        (ROOT / "bench" / "traffic" / "r500.json").read_text())
    sizes = dict(traffic_blocks("rehearsal")["even4.r500"], **extra)
    ep = workload.make_episode(name, "r500", 2**31 + 99, tmp_path, sizes)
    horizon = float(ep["traffic"]["episode_sim_s"])
    ans = run.play(ep, run.batch_edges(ep["traffic"]), float("inf"), [])[0]
    ref = reference.Reference(ep)
    want = ref.run(horizon)
    assert len(ref.names) == side * side
    assert len(want["rounds"]) > 0
    assert compare.numbers(ans, want) == dict.fromkeys(compare.LIMITS, 0)
