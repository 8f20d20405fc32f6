"""The idle split by program span (``bench/spanclock.py``): by hand, on a
CPU profiler trace with the program's spans mirrored onto the profiler's
clock, through the new per-layer readers, and in a rehearsal of the
shared-clock run at a tiny size."""
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run, spanclock, tracereduce          # noqa: E402
import repro.accel.engine as engine_mod                 # noqa: E402
from repro import obs                                    # noqa: E402


@pytest.fixture(autouse=True)
def _obs_disabled():
    obs.disable()
    yield
    obs.disable()


def test_idle_split_by_hand():
    spans = [("sim.drain", 0.0, 10.0), ("sim.grants", 2.0, 3.0),
             ("py.gc", 3.0, 1.0), ("accel.match", 6.0, 2.0)]
    assert spanclock.innermost(spans) == [
        (0.0, 2.0, "sim.drain"), (2.0, 3.0, "sim.grants"),
        (3.0, 4.0, "py.gc"), (4.0, 5.0, "sim.grants"),
        (5.0, 6.0, "sim.drain"), (6.0, 8.0, "accel.match"),
        (8.0, 10.0, "sim.drain")]
    split = spanclock.idle_by_span([(1.0, 2.5), (7.0, 5.0)], spans)
    assert split == pytest.approx({"sim.drain": 3.0, "sim.grants": 1.0,
                                   "py.gc": 0.5, "accel.match": 1.0,
                                   "none": 2.0})
    pieces = spanclock.innermost(spans)
    assert spanclock.label_at(pieces, 3.5) == "py.gc"
    assert spanclock.label_at(pieces, 11.0) == "none"


def test_mirrored_spans_split_a_known_idle_interval(tmp_path):
    import jax
    import jax.numpy as jnp
    work = jax.jit(lambda x: jnp.sort(x * 2.0))
    x = jnp.arange(200_000, dtype=jnp.float32)[::-1]
    work(x).block_until_ready()                      # compiled outside
    tr, _ = obs.enable(tracing=True, metrics=False, profiler=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tracereduce.MARK):
            work(x).block_until_ready()
            drain = tr.begin("sim.drain", cat="sim")
            nap = tr.begin("test.nap", cat="sim")
            time.sleep(0.03)                        # the device idles here
            tr.end(nap)
            work(x).block_until_ready()
            tr.end(drain)
            work(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    pd = tracereduce.load(tracereduce.xplane_path(str(tmp_path)))
    mirrored = spanclock.host_spans(pd, {"sim.drain", "test.nap"})
    assert [n for n, _, _ in mirrored] == ["sim.drain", "test.nap"]
    drain_ev, nap_ev = mirrored
    assert drain_ev[1] <= nap_ev[1]
    assert nap_ev[1] + nap_ev[2] <= drain_ev[1] + drain_ev[2]
    assert 0.03 <= nap_ev[2] < 0.2
    gaps = spanclock.device_gaps(pd, *tracereduce.LAYOUT["cpu"][:2])
    split = spanclock.idle_by_span(gaps, mirrored)
    assert split["test.nap"] == pytest.approx(nap_ev[2], rel=0.05)
    longest = max(gaps, key=lambda g: g[1])
    assert spanclock.label_at(spanclock.innermost(mirrored),
                              longest[0] + longest[1] / 2) == "test.nap"


def _span_ctx(spans):
    return {"spans": spans, "traced_s": 2.0,
            "trace": {"gaps": [(0.0, 1.0)]}}


@pytest.mark.parametrize("metric,spans,want", [
    ("grant_share", [("sim.grants", 0.1, 0.2), ("sim.grants", 0.5, 0.3)],
     25.0),
    ("scalar_drain_share", [("sim.drain_scalar", 0.0, 0.5)], 25.0),
    ("device_roundtrip_share",
     [("accel.jax.pack", 0.0, 0.4), ("accel.jax.put", 0.4, 0.1),
      ("accel.jax.run", 0.5, 0.1), ("accel.jax.fetch", 0.6, 0.2)], 20.0),
    ("gc_share", [("py.gc", 1.0, 0.1)], 5.0),
    ("unspanned_idle_share",
     [("sim.drain", 0.0, 0.6), ("sim.grants", 0.1, 0.2),
      ("accel.match", 0.8, 0.1)], 70.0),
])
def test_new_readers(metric, spans, want):
    reader = run.load_reader(metric)
    assert reader.read(_span_ctx(spans)) == pytest.approx(want)
    if metric != "unspanned_idle_share":
        # a program without the span gives the metric nothing to read
        assert reader.read(_span_ctx([("sim.drain", 0.0, 1.0)])) is None
    else:
        assert reader.read({"spans": spans, "traced_s": 2.0,
                            "trace": {"gaps": []}}) is None


@pytest.mark.parametrize("mirror", [True, False])
def test_shared_clock_run_rehearses_on_cpu(monkeypatch, mirror):
    from bench.tests.test_bench_rehearsal import TINY
    monkeypatch.setattr(engine_mod, "platform_backend", lambda: ("jax", True))
    monkeypatch.setattr(run, "PROFILE_SECONDS", 0.3)
    out = spanclock.report("biased_hp.r500", 2**31 + 77, 0.8, True,
                           mirror=mirror, require_tpu=False,
                           traffic_overrides=TINY["biased_hp.r500"],
                           log=lambda *a, **k: None)
    assert out["line"]["correct"] is True
    m = out["line"]["metrics"]
    for name in ("grant_share", "scalar_drain_share",
                 "device_roundtrip_share", "gc_share",
                 "unspanned_idle_share", "match_share"):
        assert name in m, name
    assert m["device_roundtrip_share"]["value"] < m["match_share"]["value"]
    calls = out["calls"]
    assert all(calls[n]["count"] == calls["accel.jax.fetch"]["count"]
               for n in spanclock.CALL_SPANS)
    c = out["counters"]
    assert c["accel.jax_calls"] >= calls["accel.jax.fetch"]["count"] > 0
    assert c["accel.h2d_bytes"] > 0 and c["sim.scalar_rows"] > 0
    assert out["rate_first"] > 0 and out["rate_rest"] > 0
    # the profiler's stop lies between the two parts, in neither
    assert out["pause_s"] > 0 and out["rest_s"] > 0
    assert out["first_s"] + out["pause_s"] + out["rest_s"] <= 0.8 + 1.0
    assert run.Tracing.__name__ == "Tracing"       # patches undone
    if not mirror:
        assert "idle_by_span" not in out
        return
    assert out["mirrored_spans"] > 0
    assert sum(out["idle_by_span"].values()) == pytest.approx(
        out["line"]["device"]["window_s"]
        - out["line"]["device"]["busy_s"], rel=0.05)
    assert len(out["top_gaps"]) == 10


def test_script_without_a_tpu_exits_nonzero_without_a_result():
    import os
    import subprocess
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "spanclock.py"), "--workload",
         "even4.r2", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 3
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
