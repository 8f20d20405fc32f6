"""Cells, configurations, traffic mixes and per-layer metrics are found by
name: in a copy of the benchmark, new files plus new entries in
``BENCHMARK.json`` give a new cell and a new metric, and no file that was
there changes.  A configuration of 64 classes, its cell on an existing
traffic mix (whose rehearsal sizes it takes) and a metric that reads the
program's counters need nothing more."""
import hashlib
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run, workload                               # noqa: E402
from bench.tests.test_bench_reference import grid_config      # noqa: E402
from bench.tests.test_bench_rehearsal import traffic_blocks   # noqa: E402
import repro.accel.engine as engine_mod                       # noqa: E402

COUNTER_READER = '''"""Device calls per million check-ins, by the program's counter."""


def read(ctx):
    calls = ctx["counters"].get("accel.jax_calls")
    return calls * 1e6 / ctx["checkins"] if calls and ctx["checkins"] else None
'''

READER = '''"""Device calls per check-in consumed."""


def read(ctx):
    return ctx["checkins"] and ctx["backend_calls"] / ctx["checkins"]
'''


def _digests(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def _copy(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return _digests(tmp_path)


def test_added_files_are_found_by_name(tmp_path):
    before = _copy(tmp_path)

    cfg = json.loads((tmp_path / "bench/configs/even4.json").read_text())
    cfg["fleet"]["cpu_med"] = 3.0
    (tmp_path / "bench/configs/even4_poorer.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/r40.json").write_text(json.dumps(
        {"base_rate": 40, "num_jobs": 8, "mean_interarrival_s": 60.0,
         "episode_sim_s": 300.0, "batch_sim_s": 10.0}))
    (tmp_path / "bench/metrics/calls_per_checkin.py").write_text(READER)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "even4_poorer", "source": "test",
                             "file": "bench/configs/even4_poorer.json",
                             "reduced": ["fleet"], "why": "test"})
    bench["workloads"].append({"name": "even4_poorer.r40",
                               "config": "even4_poorer", "traffic": "r40",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "calls_per_checkin", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "drain", "moves": "checkins_per_s",
                               "workloads": ["even4_poorer.r40"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.resolve(run.load_benchmark(tmp_path), "even4_poorer.r40")
    assert (cell["config"], cell["traffic"]) == ("even4_poorer", "r40")
    assert "calls_per_checkin" in {m["name"] for m in cell["per_layer"]}
    assert "calls_per_checkin" not in {
        m["name"] for m in run.resolve(run.load_benchmark(tmp_path),
                                       "even4.r2")["per_layer"]}

    line, _ = run.run_cell("even4_poorer.r40", 3, 0.3, True, root=tmp_path,
                           require_tpu=False, log=lambda *a, **k: None)
    assert line["correct"] is True
    assert "calls_per_checkin" in line["metrics"]
    after = _digests(tmp_path)
    assert all(after[p] == d for p, d in before.items())


def test_many_class_config_and_counter_metric_are_added_by_files(
        tmp_path, monkeypatch):
    before = _copy(tmp_path)
    cfg = grid_config(range(1, 9))
    (tmp_path / "bench/configs/grid64.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/metrics/jax_calls_per_mcheckin.py").write_text(
        COUNTER_READER)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "grid64", "source": "test",
                             "file": "bench/configs/grid64.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "grid64.r500", "config": "grid64",
                               "traffic": "r500", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "jax_calls_per_mcheckin", "unit": "calls/Mcheckin",
        "better": "lower", "source": "program_counter", "layer": "drain",
        "moves": "checkins_per_s",
        "workloads": ["grid64.r500", "biased_hp.r500"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.resolve(run.load_benchmark(tmp_path), "grid64.r500")
    assert (cell["config"], cell["traffic"]) == ("grid64", "r500")
    assert "jax_calls_per_mcheckin" in {m["name"] for m in cell["per_layer"]}
    tiny = traffic_blocks("rehearsal", tmp_path)
    assert tiny["grid64.r500"] == tiny["even4.r500"]
    ep = workload.make_episode("grid64", "r500", 5, tmp_path / "bench",
                               tiny["grid64.r500"])
    assert len(ep["requirements"]) == 64
    assert len({j["cls"] for j in ep["jobs"]}) == len(ep["jobs"])

    # the counter reader in a traced rehearsal of an existing cell
    monkeypatch.setattr(engine_mod, "platform_backend", lambda: ("jax", True))
    line, _ = run.run_cell("biased_hp.r500", 2**31 + 5, 0.5, True,
                           root=tmp_path, require_tpu=False,
                           traffic_overrides=tiny["biased_hp.r500"],
                           log=lambda *a, **k: None)
    assert line["correct"] is True
    assert line["metrics"]["jax_calls_per_mcheckin"]["value"] > 0
    after = _digests(tmp_path)
    assert all(after[p] == d for p, d in before.items())
