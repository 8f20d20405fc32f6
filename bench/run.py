#!/usr/bin/env python3
"""Chip benchmark of the Venn scheduler: replayed fleet episodes on the
array drain.

Usage::

    python3 bench/run.py --workload even4.r500 --seed 7 --seconds 51 \\
        --trace 0

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its per-layer metrics are read by
``bench/metrics/<name>.py``, whose ``read(ctx)`` takes spans, the device
trace, totals of the window and the program's counters
(``ctx["counters"]``).  All three are found by name, so a cell, a
configuration or a metric is added by adding files and entries.

Set-up (``setup_s``): JAX's persistent compilation cache inside the
checkout (every program kept, however quick its compile), the episode's
jobs and check-in chunks generated from ``--seed``, and one whole warm-up
episode, which compiles every padded shape the window will meet.

Window: the same episode replayed back to back, each replay a fresh
``Simulator`` (array drain, the backend the platform chooses) and a fresh
``VennScheduler``, advanced by ``step_until`` one ``batch_sim_s`` batch at a
time, until ``--seconds`` of wall time have passed.  ``checkins_per_s`` is
every check-in consumed over the window's wall time; ``batch_p95_ms`` the
95th percentile of the wall time of one batch.

Correctness: after the window, the plain reference (``bench/reference.py``)
simulates the episode and every replay's grants ``(time, job, round)``,
round records and job finish times are compared with it, exactly.

With ``--trace 1`` the window runs under ``repro.obs`` spans and counters
and the JAX profiler, and the line carries the per-layer metrics and a
breakdown of device time and idle gaps instead.

The last line of standard output is one JSON object; the compared numbers
and their limits end standard error and the line's ``checks``.  Without a
TPU, or with fewer chips than the cell asks for, it exits 3 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                     # noqa: E402
import gc                           # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path            # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np                  # noqa: E402

from bench import compare, tracereduce, workload      # noqa: E402

OUT = ROOT / ".bench_out"           # scratch inside the checkout (traces)
PROFILE_SECONDS = 10.0              # traced part of a --trace 1 window
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------ discovery

def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as fh:
        return json.load(fh)


def resolve(bench: dict, name: str) -> dict:
    """The cell ``name`` with its end-to-end and per-layer metric entries."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return dict(cells[name],
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def load_reader(name: str, root: Path = ROOT):
    """``bench/metrics/<name>.py``, whose ``read(ctx)`` returns the metric
    or None when the run gave it nothing to read."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ the chip

class CompileLog:
    """Counts XLA compiles from JAX's monitoring events."""

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration


def open_chip(chips: int, root: Path = ROOT):
    """The TPU devices, with the compile cache on inside the checkout and
    kept for every program, however quick its compile."""
    import jax
    devs = jax.devices()
    if jax.default_backend() != "tpu" or len(devs) < chips:
        raise NoChip(f"JAX found {len(devs)} {jax.default_backend()} "
                     f"device(s); the cell needs {chips} TPU chip(s)")
    use_compile_cache(root)
    return devs


def use_compile_cache(root: Path = ROOT) -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, the
    directory the program's entry points use, whatever the environment
    says, and every program kept, however quick its compile."""
    import jax
    cache = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_record(devs) -> dict:
    d = devs[0]
    peak = 0
    for dev in devs:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs),
            "memory_peak_bytes": peak}


# ------------------------------------------------------------ episodes

def batch_edges(traffic: dict):
    b, T = float(traffic["batch_sim_s"]), float(traffic["episode_sim_s"])
    n = int(np.ceil(T / b))
    return [min((i + 1) * b, T) for i in range(n)]


def play(ep: dict, edges, deadline: float, walls: list, tracing=None):
    """Replay the episode one batch at a time until it ends or the wall
    clock passes ``deadline``.  Returns ``(answers, reached, complete,
    checkins, engine)``."""
    from bench import system
    sim = system.make_simulator(ep)
    perf = time.perf_counter
    reached, complete = 0.0, False
    for edge in edges:
        with tracing.batch() if tracing is not None else nullcontext():
            t0 = perf()
            finished = sim.step_until(edge)
            t1 = perf()
        walls.append(t1 - t0)
        if tracing is not None:
            tracing.after_batch(t1)
        reached = edge
        if finished:
            complete = True
            break
        if t1 >= deadline:
            break
    return (system.answers(sim), reached, complete,
            sim.checkins_seen + sim.checkins_skipped, sim.engine)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, require_tpu: bool = True,
             traffic_overrides: dict | None = None,
             t_start: float = T_START, log=print):
    """One run of one cell: ``(result line, checks)``."""
    bench = load_benchmark(root)
    cell = resolve(bench, name)
    import jax
    devs = open_chip(cell["chips"], root) if require_tpu else jax.devices()
    clog = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(clog)
    try:
        return _run(cell, seed, seconds, trace, root, devs, clog,
                    traffic_overrides, t_start, log)
    finally:
        jax.monitoring.unregister_event_duration_listener(clog)


def _run(cell, seed, seconds, trace, root, devs, clog, overrides, t_start,
         log):
    ep = workload.make_episode(cell["config"], cell["traffic"], seed,
                               root / "bench", overrides)
    edges = batch_edges(ep["traffic"])
    t_gen = time.perf_counter()
    warm = []
    play(ep, edges, float("inf"), warm)                     # warm-up episode
    gc.collect()
    setup_s = time.perf_counter() - t_start
    compiles0 = clog.n
    log(f"set-up {setup_s:.3f} s (generation done at "
        f"{t_gen - t_start:.3f} s, warm-up episode {sum(warm):.3f} s, "
        f"{clog.n} compiles in {clog.seconds:.3f} s); "
        f"{workload.checkin_count(ep)} check-ins per episode",
        file=sys.stderr)

    tracing = Tracing() if trace else None
    walls, episodes = [], []
    checkins = backend_calls = 0
    mirror_s = 0.0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    if tracing is not None:
        tracing.start(t0)
    try:
        while True:
            ans, reached, complete, n, eng = play(ep, edges, deadline, walls,
                                                  tracing)
            t_end = time.perf_counter()
            episodes.append((ans, reached, complete))
            checkins += n
            backend_calls += eng.backend_calls
            mirror_s += eng.patch_s + eng.rebuild_s
            if t_end >= deadline:
                break
    finally:
        if tracing is not None:
            tracing.stop()
    window_compiles = clog.n - compiles0
    # the window: from its start to the end of its last replay's last
    # batch, each replay's construction included
    window_s = t_end - t0
    device = device_record(devs)
    del eng
    gc.collect()

    t_ref = time.perf_counter()
    checks, failed = compare.check(ep, episodes)
    log(f"window {window_s:.3f} s over {len(episodes)} replays "
        f"({sum(1 for e in episodes if e[2])} whole), {len(walls)} batches, "
        f"{checkins} check-ins, {backend_calls} device calls, "
        f"{window_compiles} compiles; reference and comparison "
        f"{time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": checkins, "failed": failed}
    if not trace:
        values = {
            "checkins_per_s": checkins / window_s,
            "batch_p95_ms": float(np.percentile(walls, 95)) * 1e3,
            "setup_s": setup_s,
        }
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell["end_to_end"]}
    else:
        red = tracing.reduce(device["platform"])
        ctx = {
            "window_s": window_s, "checkins": checkins,
            "backend_calls": backend_calls, "mirror_s": mirror_s,
            "stream_s": tracing.stream_s, "compiles": window_compiles,
            "spans": tracing.spans, "traced_s": tracing.traced_s,
            "trace": red, "calls": tracing.calls,
            "counters": tracing.counters,
            "device_kind": device["kind"],
        }
        line["metrics"] = {}
        for m in cell["per_layer"]:
            v = load_reader(m["name"], root).read(ctx)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {
            "device_ops": [list(o) for o in red["ops"][:10]],
            "idle_gaps": tracereduce.label_gaps(red["gaps"], tracing.spans),
        }
    line["device"] = device
    line["checks"] = checks
    return line, checks


# ------------------------------------------------------------ tracing

class Tracing:
    """The traced run's instruments.  Over the whole window: the
    ``repro.obs`` counters, every one read once the window has closed
    (``counters``: name -> value; ``stream_s``: ``sim.stream_wall_s``).
    Over its first :data:`PROFILE_SECONDS`: ``repro.obs`` spans, the JAX
    profiler (each batch inside an annotation, its start noted on the host
    clock), and the live rows and candidate slots of every device call."""

    def __init__(self):
        self.calls, self.marks = [], []
        self.active = False
        self.traced_s = 0.0

    def start(self, t0: float) -> None:
        import jax
        from repro import obs
        import repro.accel.engine as engine_mod
        self.t0 = t0
        self.stop_at = t0 + PROFILE_SECONDS
        self.engine_mod = engine_mod
        self.inner = inner = engine_mod.match_chunk_jax
        calls = self.calls

        def recorded(atom_ids, speeds, state, use_kernel=False):
            # the work each device call defines: live rows x candidate slots
            calls.append((time.perf_counter(), len(atom_ids),
                          int(state.cand_req.shape[1])))
            return inner(atom_ids, speeds, state, use_kernel=use_kernel)

        engine_mod.match_chunk_jax = recorded
        self.tracer, self.registry = obs.enable(tracing=True, metrics=True,
                                                max_events=4_000_000)
        self.dir = OUT / "trace"
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.active = True

    def batch(self):
        if not self.active:
            return nullcontext()
        import jax
        self.marks.append(time.perf_counter_ns())
        return jax.profiler.TraceAnnotation(tracereduce.MARK)

    def after_batch(self, t1: float) -> None:
        if self.active and t1 >= self.stop_at:
            self._end_profile(t1)

    def _end_profile(self, t1: float) -> None:
        import jax
        from repro.obs import trace as obs_trace
        self.active = False
        self.traced_s = t1 - self.t0
        self.engine_mod.match_chunk_jax = self.inner
        self.spans = _spans(self.tracer)
        obs_trace.TRACER = obs_trace.NULL_TRACER
        jax.profiler.stop_trace()

    def stop(self) -> None:
        from repro import obs
        if self.active:
            self._end_profile(time.perf_counter())
        self.counters = {m["name"]: m["value"]
                         for m in self.registry.snapshot()
                         if m["kind"] == "counter"}
        self.stream_s = self.counters.get("sim.stream_wall_s", 0.0)
        obs.disable()

    def reduce(self, platform: str) -> dict:
        plane, ops, mods = tracereduce.LAYOUT[platform]
        try:
            return tracereduce.reduce_trace(
                tracereduce.load(tracereduce.xplane_path(str(self.dir))),
                self.marks, plane, ops, mods)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _spans(tracer):
    """``(name, start_s, dur_s)`` of every recorded span, perf clock."""
    base = -tracer.us(0.0) * 1e-6
    return [(e["name"], base + e["ts"] * 1e-6, e["dur"] * 1e-6)
            for e in tracer.events if e.get("ph") == "X"]


# ------------------------------------------------------------ entry

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line, checks = run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
