"""repro.obs tests — the two halves of the observability contract plus the
component math:

* **observe, never perturb**: tracing+metrics-enabled runs are bit-identical
  (``summary()``, jcts, rounds, the grant log) to disabled runs on BOTH
  drain engines and on the jax backend, across registry scenarios including
  a faulted one;
* the device-call, grant and scalar-tail spans nest under their parents,
  and the collection hook exists exactly while observability is on;
* **zero-overhead when disabled**: the null tracer/registry singletons are
  the module globals by default, record nothing, and allocate nothing;
* trace JSON round-trips and validates against the Chrome trace-event shape;
* histogram percentile math (log buckets, weighted records, merge);
* timeline decomposition sums to JCT; summarize self-time attribution.
"""
import gc
import json
import math
import os
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import pytest

from repro import obs
from repro.accel.engine import ArrayMatchEngine
from repro.core import SCHEDULERS
from repro.faults.injector import FaultInjector
from repro.obs import metrics as obsmetrics
from repro.obs import trace as obstrace
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.summarize import hist_table, span_stats, top_spans_table
from repro.obs.timeline import build_timelines, timelines_from_records
from repro.obs.trace import Tracer, validate_trace
from repro.scenarios import fast_scaled, get_scenario, run_one
from repro.scenarios.streams import build_jobs, build_stream
from repro.sim.simulator import Simulator


SRC = str(Path(__file__).resolve().parent.parent / "src")


def _tiny(spec):
    spec = fast_scaled(spec)
    return replace(
        spec,
        jobs=replace(spec.jobs, num_jobs=5),
        sim=replace(spec.sim, max_time=1.5 * 24 * 3600.0),
    )


@pytest.fixture(autouse=True)
def _obs_disabled():
    """Every test starts and ends with the null singletons installed."""
    obs.disable()
    yield
    obs.disable()


def _simulate(spec, seed, engine):
    """``run_one``'s Venn simulation, returning the simulator (grant log
    kept).  ``engine="jax"``: the array drain on the jitted fixed point with
    the Pallas kernel in interpret mode, the chip's path."""
    if engine == "jax":
        engine = ArrayMatchEngine(backend="jax", use_kernel=True)
    plan = spec.fault_plan.resolve(spec.sim.max_time) \
        if spec.fault_plan is not None else None
    stream = build_stream(spec, seed)
    if plan is not None and not plan.is_empty:
        stream = FaultInjector(stream, plan)
    sim = Simulator(build_jobs(spec, seed), SCHEDULERS["venn"](seed=seed),
                    cfg=spec.sim, stream=stream, engine=engine, faults=plan,
                    record_grants=True)
    sim.run()
    return sim


# --------------------------------------------------- observe, never perturb

# one plain scenario + one faulted scenario (blackout_storm exercises the
# injector instants and the simulator's fault.blackout path)
@pytest.mark.parametrize("scenario", ["baseline_even", "blackout_storm"])
@pytest.mark.parametrize("engine", ["python", "array", "jax"])
def test_traced_run_bit_identical(scenario, engine):
    spec = _tiny(get_scenario(scenario))
    plain = _simulate(spec, 1, engine)
    with obs.session(tracing=True, metrics=True) as (tr, reg):
        traced = _simulate(spec, 1, engine)
        n_events = tr.num_events
    assert traced.grant_log == plain.grant_log
    traced, plain = traced.metrics, plain.metrics
    assert traced.summary() == plain.summary()
    assert traced.jcts == plain.jcts
    assert traced.rounds == plain.rounds
    assert traced.resilience() == plain.resilience()
    assert n_events > 0          # the instrumentation actually fired


def _inside(child, parents):
    return any(p["tid"] == child["tid"] and p["ts"] <= child["ts"]
               and child["ts"] + child["dur"] <= p["ts"] + p["dur"]
               for p in parents)


def test_device_call_grant_and_scalar_spans_nest():
    spec = _tiny(get_scenario("baseline_even"))
    with obs.session() as (tr, reg):
        _simulate(spec, 0, "jax")
        events = [e for e in tr.events if e["ph"] == "X"]
        counters = {n: reg.get(n) for n in reg.names()}
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    calls = len(by["accel.jax.fetch"])
    assert calls > 0
    for name in ("accel.jax.pack", "accel.jax.put", "accel.jax.run",
                 "accel.jax.fetch"):
        assert len(by[name]) == calls
        assert all(_inside(e, by["accel.match"]) for e in by[name]), name
    for name in ("sim.grants", "sim.drain_scalar"):
        assert by[name] and all(_inside(e, by["sim.drain"])
                                for e in by[name]), name
    assert {"accel.jax.compile+exec", "accel.jax.exec"}.isdisjoint(by)
    put_bytes = sum(e["args"]["bytes"] for e in by["accel.jax.put"])
    assert counters["accel.jax_calls"].value == calls
    assert counters["accel.h2d_bytes"].value == put_bytes > 0
    # one copy in, one copy out: the packed buffer of each call
    assert counters["accel.host_copies"].value == 2 * calls
    packed = sum(4 * (p["args"]["n"] * p["args"]["k"] + p["args"]["r"])
                 for p in by["accel.jax.pack"])
    assert counters["accel.h2d_bytes"].value == packed
    # the device path feeds the fixed point's iteration histogram
    assert counters["accel.fixedpoint_iters"].count == calls
    assert counters["accel.fixedpoint_iters"].vmin >= 1
    assert counters["sim.scalar_rows"].value == sum(
        e["args"]["rows"] for e in by["sim.drain_scalar"])
    grants = sum(e["args"]["grants"] for e in by["sim.grants"]) \
        + sum(e["args"]["grants"] for e in by["sim.drain_scalar"])
    assert 0 < grants
    assert counters["sim.grant_wall_s"].value > 0
    assert counters["sim.scalar_wall_s"].value > 0


def _gc_hooks():
    return [cb for cb in gc.callbacks
            if type(cb).__module__.startswith("repro.obs")]


def test_gc_hook_records_collections_only_while_enabled():
    assert _gc_hooks() == []
    obs.enable(tracing=True, metrics=True)
    assert len(_gc_hooks()) == 1
    gc.collect()
    tr, reg = obs.get_tracer(), obs.get_registry()
    spans = [e for e in tr.events if e["name"] == "py.gc"]
    assert spans and spans[-1]["cat"] == "py"
    assert spans[-1]["args"]["generation"] == 2
    assert spans[-1]["args"]["collected"] >= 0
    assert reg.counter("py.gc_collections.gen2").value >= 1
    assert reg.counter("py.gc_wall_s").value > 0
    obs.disable()
    assert _gc_hooks() == []
    # metrics alone keep the hook (counters, no spans); leaving a session
    # restores the hook-free state
    with obs.session(tracing=False, metrics=True) as (_, reg):
        assert len(_gc_hooks()) == 1
        gc.collect()
        assert reg.counter("py.gc_collections.gen2").value >= 1
    assert _gc_hooks() == []


def test_trace_has_expected_span_taxonomy(tmp_path):
    spec = _tiny(get_scenario("baseline_even"))
    with obs.session() as (tr, _):
        run_one(spec, "venn", seed=0, engine="array")
        path = tr.write(str(tmp_path / "t.json"))
    doc = obs.load_trace(path)
    names = {e["name"] for e in doc["traceEvents"]}
    for must in ("sim.drain", "venn.replan", "venn.replan.irs",
                 "venn.replan.supply", "venn.replan.compile",
                 "accel.match", "accel.state_rebuild", "sim.event.response"):
        assert must in names, f"missing {must} in {sorted(names)}"


def test_faulted_trace_emits_fault_instants(tmp_path):
    spec = _tiny(get_scenario("blackout_storm"))
    with obs.session() as (tr, _):
        run_one(spec, "venn", seed=0, engine="python")
        events = list(tr.events)
    instants = {e["name"] for e in events if e["ph"] == "i"}
    assert "fault.blackout" in instants


# ------------------------------------------------- disabled no-op fast path

def test_disabled_singletons_record_nothing():
    assert obstrace.TRACER is obstrace.NULL_TRACER
    assert obsmetrics.REGISTRY is obsmetrics.NULL_REGISTRY
    assert obstrace.TRACER.enabled is False
    assert obsmetrics.REGISTRY.enabled is False
    # every call is a no-op; span contexts are the shared singleton
    s1 = obstrace.TRACER.span("x", cat="sim", a=1)
    s2 = obstrace.TRACER.span("y")
    assert s1 is s2 is obstrace.NULL_SPAN
    with s1:
        s1.add(b=2)
    obstrace.TRACER.end(obstrace.TRACER.begin("z"))
    obstrace.TRACER.instant("i")
    reg = obsmetrics.REGISTRY
    assert reg.counter("c") is reg
    reg.counter("c").inc()
    reg.histogram("h").record(1.0, n=5)
    # the null tracer has no event storage at all
    assert not hasattr(obstrace.TRACER, "events")


def test_disabled_run_emits_zero_events():
    spec = _tiny(get_scenario("baseline_even"))
    run_one(spec, "venn", seed=0, engine="array")
    assert obstrace.TRACER is obstrace.NULL_TRACER      # still the singleton


def test_obs_imports_and_runs_without_jax():
    # repro.obs (and faults/recovery through it) stays pure stdlib: JAX is
    # imported only for a tracer that mirrors into the profiler
    code = ("import sys, gc; sys.modules['jax'] = None\n"
            "from repro import obs\n"
            "from repro.faults import recovery\n"
            "tr, reg = obs.enable()\n"
            "gc.collect()\n"
            "assert reg.counter('py.gc_collections.gen2').value == 1\n"
            "try:\n"
            "    obs.enable(profiler=True)\n"
            "except ImportError:\n"
            "    print('no-jax-ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, PYTHONPATH=SRC))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "no-jax-ok"


def test_session_restores_singletons_on_error():
    with pytest.raises(RuntimeError):
        with obs.session():
            assert obstrace.TRACER.enabled
            raise RuntimeError("boom")
    assert obstrace.TRACER is obstrace.NULL_TRACER
    assert obsmetrics.REGISTRY is obsmetrics.NULL_REGISTRY


# ----------------------------------------------------- trace shape / export

def test_trace_round_trips_and_validates(tmp_path):
    tr = Tracer()
    with tr.span("outer", cat="a", k=1):
        tr.instant("mark", cat="a", sev=2)
        with tr.span("inner", cat="b"):
            pass
    path = tr.write(str(tmp_path / "t.json"))
    doc = obs.load_trace(path)                  # load_trace validates
    events = doc["traceEvents"]
    assert len(events) == 3
    by_name = {e["name"]: e for e in events}
    assert by_name["outer"]["ph"] == "X"
    assert by_name["mark"]["ph"] == "i"
    assert by_name["inner"]["dur"] <= by_name["outer"]["dur"]
    for e in events:
        assert e["ts"] >= 0 and isinstance(e["tid"], int)
    # writing is plain JSON — a second loader agrees
    assert json.loads((tmp_path / "t.json").read_text())["traceEvents"]


def test_recorded_events_leave_the_collectors_tracking():
    # a traced run keeps ~1M events; as plain tuples they drop out of the
    # collector's tracking at the first collection, so they add no work to
    # every later one (dicts with args stayed tracked)
    tr = Tracer()
    for i in range(100):
        tok = tr.begin("s", cat="sim", rows=i)
        tr.end(tok, grants=2, engine="array")
        tr.instant("i", cat="fault", revoked=i)
    gc.collect()
    assert not any(gc.is_tracked(e) for e in tr._events)
    ev = tr.events
    assert ev[0]["args"] == {"rows": 0, "grants": 2, "engine": "array"}
    assert ev[1] == {"name": "i", "ph": "i", "s": "t", "ts": ev[1]["ts"],
                     "pid": tr.pid, "tid": ev[1]["tid"], "cat": "fault",
                     "args": {"revoked": 0}}


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"name": "x", "ph": "Q",
                                         "ts": 0, "pid": 1, "tid": 1}]})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"name": "x", "ph": "X", "ts": 0,
                                         "pid": 1, "tid": 1}]})  # no dur
    with pytest.raises(ValueError):
        validate_trace({"notTraceEvents": []})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "i", "ts": 0,
                                         "pid": 1, "tid": 1}]})  # no name


def test_tracer_category_filter_and_event_cap():
    tr = Tracer(categories={"sched"})
    with tr.span("kept", cat="sched"):
        pass
    with tr.span("filtered", cat="sim"):
        pass
    tr.instant("also_filtered", cat="accel")
    assert [e["name"] for e in tr.events] == ["kept"]
    capped = Tracer(max_events=2)
    for i in range(5):
        capped.instant(f"e{i}")
    assert capped.num_events == 2 and capped.dropped == 3
    assert capped.export()["otherData"]["dropped_events"] == 3


# ------------------------------------------------------------ histogram math

def test_histogram_percentiles_log_buckets():
    h = Histogram("lat", lo=1e-6, hi=10.0, buckets_per_decade=10)
    for v in [1e-4] * 50 + [1e-2] * 45 + [1.0] * 5:
        h.record(v)
    assert h.count == 100
    # p50 lands in the 1e-4 bucket, p95 in 1e-2, p99 in 1.0 — geometric
    # bucket midpoints are within one bucket width (10^(1/10) ≈ 1.26x)
    assert h.percentile(50) == pytest.approx(1e-4, rel=0.3)
    assert h.percentile(95) == pytest.approx(1e-2, rel=0.3)
    assert h.percentile(99) == pytest.approx(1.0, rel=0.3)
    # estimates are clamped to the exactly-tracked observed range
    assert h.vmin <= h.percentile(1) <= h.percentile(99.9) <= h.vmax


def test_histogram_single_value_is_exact():
    h = Histogram("x", lo=1e-6, hi=1.0)
    h.record(0.002, n=1000)                     # weighted record
    assert h.count == 1000
    for q in (1, 50, 99):
        assert h.percentile(q) == pytest.approx(0.002)
    assert h.mean == pytest.approx(0.002)


def test_histogram_weighted_record_matches_repeats():
    a = Histogram("a", lo=1e-6, hi=1.0)
    b = Histogram("b", lo=1e-6, hi=1.0)
    for _ in range(7):
        a.record(3e-4)
    b.record(3e-4, n=7)
    assert a.counts == b.counts and a.count == b.count
    assert a.percentile(50) == b.percentile(50)


def test_histogram_under_overflow_and_junk_values():
    h = Histogram("x", lo=1e-3, hi=1.0)
    h.record(1e-9)           # underflow
    h.record(100.0)          # overflow
    h.record(0.0)            # non-positive -> underflow
    h.record(float("nan"))   # junk -> underflow, excluded from min/max/sum
    h.record(float("inf"))   # junk -> overflow
    assert h.count == 5
    assert h.counts[0] == 3 and h.counts[-1] == 2
    assert math.isfinite(h.percentile(50))


def test_histogram_empty_and_merge():
    h = Histogram("x")
    assert math.isnan(h.percentile(50)) and math.isnan(h.mean)
    a = Histogram("a", lo=1e-6, hi=1.0)
    b = Histogram("b", lo=1e-6, hi=1.0)
    for v in (1e-5, 1e-4, 1e-3):
        a.record(v)
    for v in (1e-2, 1e-1):
        b.record(v)
    c = Histogram("c", lo=1e-6, hi=1.0)
    for v in (1e-5, 1e-4, 1e-3, 1e-2, 1e-1):
        c.record(v)
    a.merge(b)
    assert a.counts == c.counts and a.count == c.count
    assert a.vmin == c.vmin and a.vmax == c.vmax
    with pytest.raises(ValueError):
        a.merge(Histogram("other", lo=1e-5, hi=1.0))


def test_histogram_snapshot_round_trip():
    h = Histogram("lat", lo=1e-6, hi=10.0)
    for v in (1e-4, 2e-3, 0.5):
        h.record(v)
    snap = json.loads(json.dumps(h.snapshot()))      # through JSON
    back = Histogram.from_snapshot(snap)
    assert back.counts == h.counts
    assert back.percentile(50) == h.percentile(50)
    assert "p99" in snap and snap["kind"] == "histogram"


def test_registry_snapshot_and_jsonl(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc(2)
    reg.counter("c").inc(3)
    reg.gauge("g").set(7.5)
    reg.histogram("h", lo=1e-6, hi=1.0).record(1e-3, n=4)
    assert reg.counter("c").value == 5.0
    path = reg.write_jsonl(str(tmp_path / "m.jsonl"), mode="w",
                           extra=[{"kind": "timeline", "job_id": 0,
                                   "arrival": 0.0, "completion": 1.0,
                                   "jct": 1.0, "rounds": []}])
    recs = obs.read_jsonl(path)
    kinds = sorted(r["kind"] for r in recs)
    assert kinds == ["counter", "gauge", "histogram", "timeline"]
    assert hist_table(recs)                       # renders without error


# ----------------------------------------------------------------- timeline

def test_timeline_decomposition_sums_to_jct():
    spec = _tiny(get_scenario("baseline_even"))
    m = run_one(spec, "venn", seed=0).metrics
    tls = build_timelines(m)
    assert set(tls) == set(m.jcts)
    for jid, tl in tls.items():
        assert tl.jct == pytest.approx(m.jcts[jid])
        total = tl.scheduling_delay_s + tl.response_collection_s + tl.other_s
        assert total == pytest.approx(tl.jct, abs=1e-6) or tl.other_s == 0.0
        assert tl.scheduling_delay_s >= 0 and tl.response_collection_s >= 0
    recs = obs.timeline_records(m, scenario="baseline_even")
    back = timelines_from_records(recs)
    assert len(back) == len(tls)
    by_id = {t.job_id: t for t in back}
    for jid, tl in tls.items():
        assert by_id[jid].scheduling_delay_s == pytest.approx(
            tl.scheduling_delay_s)
    out = obs.render_timelines(back)
    assert "JCT decomposition" in out and str(max(tls)) in out


# ---------------------------------------------------------------- summarize

def test_span_stats_self_time_attribution():
    # hand-built lane: parent 0..100us with child 10..40us -> parent self 70
    events = [
        {"name": "parent", "ph": "X", "ts": 0.0, "dur": 100.0,
         "pid": 1, "tid": 1},
        {"name": "child", "ph": "X", "ts": 10.0, "dur": 30.0,
         "pid": 1, "tid": 1},
        {"name": "mark", "ph": "i", "ts": 50.0, "pid": 1, "tid": 1},
    ]
    stats = span_stats(events)
    assert stats["parent"]["total_us"] == pytest.approx(100.0)
    assert stats["parent"]["self_us"] == pytest.approx(70.0)
    assert stats["child"]["self_us"] == pytest.approx(30.0)
    assert stats["mark"]["instants"] == 1
    table = top_spans_table(stats)
    assert "parent" in table and "child" in table


def test_obs_cli_summarize_and_validate(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main
    spec = _tiny(get_scenario("baseline_even"))
    tpath = str(tmp_path / "t.json")
    mpath = str(tmp_path / "m.jsonl")
    with obs.session() as (tr, reg):
        m = run_one(spec, "venn", seed=0, engine="array").metrics
        tr.write(tpath)
        reg.write_jsonl(mpath, mode="w", extra=obs.timeline_records(m))
    assert obs_main(["validate", tpath]) == 0
    assert obs_main(["summarize", tpath, mpath]) == 0
    out = capsys.readouterr().out
    assert "top spans by self-time" in out
    assert "sim.decision_latency_s" in out
    assert "JCT decomposition" in out
    assert obs_main(["timeline", mpath]) == 0


def test_scenarios_cli_trace_out(tmp_path, capsys):
    from repro.scenarios.__main__ import main as scen_main
    tpath = str(tmp_path / "t.json")
    mpath = str(tmp_path / "m.jsonl")
    rc = scen_main(["run", "baseline_even", "--fast", "--sched", "venn",
                    "--engine", "array",
                    "--trace-out", tpath, "--metrics-out", mpath])
    assert rc == 0
    doc = obs.load_trace(tpath)
    names = {e["name"] for e in doc["traceEvents"]}
    assert "venn.replan" in names and "sim.drain" in names \
        and "accel.match" in names
    assert any(n.startswith("run:baseline_even:venn") for n in names)
    recs = obs.read_jsonl(mpath)
    assert any(r["kind"] == "timeline" for r in recs)
    assert any(r.get("name") == "sim.decision_latency_s" for r in recs)
    # the CLI run left the globals disabled
    assert obstrace.TRACER is obstrace.NULL_TRACER