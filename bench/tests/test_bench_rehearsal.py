"""Rehearsal of ``bench/run.py`` on the CPU at tiny sizes.

The harness's look for a chip is skipped (``require_tpu=False``) and the
engine is steered onto the device path (the jitted fixed point with the
Pallas kernel in interpret mode) by patching ``platform_backend``, as
``tests/test_chip_smoke.py`` does.  Each cell of ``BENCHMARK.json`` runs its
traffic at the tiny sizes of the traffic file's ``rehearsal`` block; the
rest of a run, the comparison with the reference included, runs as on the
chip.  Broken timed paths must come out not correct."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run, system, workload     # noqa: E402,F401 (program path)
import repro.accel.engine as engine_mod      # noqa: E402


def traffic_blocks(kind: str, root: Path = ROOT) -> dict:
    """Each cell of ``BENCHMARK.json`` -> the ``kind`` block (``rehearsal``
    or ``control``) of its traffic file, None where the file has none."""
    return {w["name"]: workload.load_json("traffic", w["traffic"],
                                          root / "bench").get(kind)
            for w in run.load_benchmark(root)["workloads"]}


TINY = traffic_blocks("rehearsal")
SEED = 2**31 + 4242


def _quiet(*args, **kwargs):
    pass


def _run(cell, trace=False, seconds=0.5, seed=SEED):
    assert TINY[cell], f"{cell}: no rehearsal block in its traffic file"
    return run.run_cell(cell, seed, seconds, trace, require_tpu=False,
                        traffic_overrides=TINY[cell], log=_quiet)


@pytest.fixture
def device_path(monkeypatch):
    monkeypatch.setattr(engine_mod, "platform_backend", lambda: ("jax", True))


def test_tiny_sizes_cover_every_cell():
    """Every cell's traffic file has a ``rehearsal`` block that overrides
    parameters the file itself sets."""
    for w in run.load_benchmark()["workloads"]:
        tr = workload.load_json("traffic", w["traffic"])
        sizes = tr.get("rehearsal")
        assert isinstance(sizes, dict) and sizes, w["name"]
        assert set(sizes) <= set(tr) - {"rehearsal", "control"}, w["name"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_rehearses_on_cpu(cell, device_path):
    line, checks = _run(cell)
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    names = {m["name"] for m in run.resolve(run.load_benchmark(), cell)
             ["end_to_end"]}
    assert set(line["metrics"]) == names
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == 1
    assert all(c["value"] == 0 == c["limit"] for c in checks.values())
    json.dumps(line)


def test_traced_run_reports_per_layer_metrics(device_path):
    line, _ = _run("biased_hp.r500", trace=True)
    assert line["correct"] is True
    m = line["metrics"]
    for name in ("classify_share", "device_calls_per_mcheckin",
                 "mirror_share", "device_idle_share", "window_compiles",
                 "replan_p95_ms"):
        assert name in m, name
    assert m["window_compiles"]["value"] == 0
    assert 0 < m["device_idle_share"]["value"] < 100
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    assert len(line["breakdown"]["device_ops"]) <= 10


def _broken_match(monkeypatch, fault):
    inner = engine_mod.ArrayMatchEngine.match

    def match(self, atom_ids, speeds):
        res = inner(self, atom_ids, speeds)
        fault(self.state, res)
        return res

    monkeypatch.setattr(engine_mod.ArrayMatchEngine, "match", match)


def _alter_answer(state, res):
    """The first grant of the segment goes to another open request."""
    rows = np.flatnonzero(res.granted)
    if len(rows):
        p = rows[0]
        other = [r for r in np.flatnonzero(state.remaining > 0)
                 if r != res.choice[p]]
        if other:
            res.choice[p] = other[0]


def _drop_half(state, res):
    """The second half of the segment's check-ins is left out."""
    res.granted[len(res.granted) // 2:] = False


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half])
@pytest.mark.parametrize("cell", ["biased_hp.r500", "even4.r500"])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    _broken_match(monkeypatch, fault)
    line, checks = _run(cell)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in checks.values())


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    rc = run.main(["--workload", "even4.r2", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "TPU" in out.err
