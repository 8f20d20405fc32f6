#!/usr/bin/env python3
"""Bring-up check: the scheduler's array drain runs on one TPU.

Drives the main path through the public ``repro.sim`` API, in one process:

a. ``main``: the ``tenx_r500_j2000`` configuration (base_rate 500
   check-ins/s against a capability-poor population, 2000 jobs pinned to
   the scarce high-performance tier, a quarter simulated day, ~15M
   check-ins, seed 1) on ``Simulator(engine="array")`` with the backend the
   platform chooses: the jitted fixed point with the Pallas masked-first-fit
   kernel on a TPU.
b. ``reference``: the same run on the Python per-device drain; ``jcts``
   and ``rounds`` must equal (a)'s, with no degraded segment and at least
   one segment matched on the device.
c. ``jnp``: the same run with ``ArrayMatchEngine(backend="jax",
   use_kernel=False)``, the pure-jnp first-fit; identical too.
d. ``replan_kernel``: the ``replan_r500_j2000`` churn setup at a shorter
   horizon with ``REPRO_REPLAN_ORDER=kernel`` (the ``segmented_rank``
   kernel orders each group), against the same run on ``np.lexsort``;
   identical, and the kernel must serve more resorts than fall back.

Each phase prints one JSON line: wall seconds, check-ins (all, and those
the scheduler examined), segments matched on the device, XLA compiles and
their seconds, distinct padded shapes of the jitted matcher, and
``degraded_segments``.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or when any phase fails,
the script exits nonzero and prints no such line.

Usage::

    python3 chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# (base_rate, num_jobs, days): the largest stream and backlog the repo runs
TENX = (500.0, 2000, 0.25)
# the same traffic for the replan-kernel phase, at a fifth of the horizon
CHURN = (500.0, 2000, 0.05)


class CompileLog:
    """Counts XLA compiles (persistent-cache hits included) and their
    seconds, from JAX's monitoring events."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration


def _simulator(engine, size, seed=1):
    from repro.core import SCHEDULERS
    from repro.sim import (JobTraceConfig, PopulationConfig, SimConfig,
                           generate_jobs)
    from repro.sim.devices import REQ_HIGHPERF
    from repro.sim.simulator import Simulator

    base_rate, num_jobs, days = size
    jobs = generate_jobs(JobTraceConfig(num_jobs=num_jobs, seed=seed,
                                        mean_interarrival=60.0))
    for j in jobs:
        j.requirement = REQ_HIGHPERF
    pop = PopulationConfig(seed=1000 + seed, base_rate=base_rate,
                           cpu_med=1.8, mem_med=1.8)
    return Simulator(jobs, SCHEDULERS["venn"](seed=seed), pop,
                     SimConfig(max_time=days * 24 * 3600.0), engine=engine)


def _run(name, engine, size, log, replan_order=None):
    """One simulator run -> (phase record, metrics)."""
    from repro import obs
    from repro.accel._jax_impl import _match_jax

    prev = os.environ.get("REPRO_REPLAN_ORDER")
    if replan_order is not None:
        os.environ["REPRO_REPLAN_ORDER"] = replan_order
    c0, s0, k0 = log.compiles, log.seconds, _match_jax._cache_size()
    try:
        sim = _simulator(engine, size)
        with obs.session(tracing=False, metrics=True) as (_, reg):
            t0 = time.perf_counter()
            metrics = sim.run()
            wall = time.perf_counter() - t0
            counters = {k: int(reg.counter(k).value) for k in
                        ("accel.kernel_order_calls",
                         "accel.kernel_order_fallbacks")}
    finally:
        if prev is None:
            os.environ.pop("REPRO_REPLAN_ORDER", None)
        else:
            os.environ["REPRO_REPLAN_ORDER"] = prev
    eng = sim.engine
    rec = {
        "phase": name,
        "wall_s": wall,
        "checkins": sim.checkins_seen + sim.checkins_skipped,
        "checkins_seen": sim.checkins_seen,
        "rounds": len(metrics.rounds),
        "backend": getattr(eng, "backend", "python"),
        "use_kernel": getattr(eng, "use_kernel", False),
        "device_segments": eng.backend_calls
        if getattr(eng, "backend", None) == "jax" else 0,
        "compiles": log.compiles - c0,
        "compile_s": log.seconds - s0,
        "match_shapes": _match_jax._cache_size() - k0,
        "degraded_segments": metrics.resilience()["degraded_segments"],
    }
    if replan_order is not None:
        rec.update(counters)
    return rec, metrics


def _same(phase, m, ref):
    if m.jcts != ref.jcts or m.rounds != ref.rounds:
        raise AssertionError(f"{phase}: SimMetrics differ from the reference "
                             "(jcts or rounds)")


def _on_device(rec):
    if rec["backend"] != "jax" or rec["device_segments"] == 0:
        raise AssertionError(f"{rec['phase']}: no segment was matched on the "
                             f"device ({rec})")
    if rec["degraded_segments"]:
        raise AssertionError(f"{rec['phase']}: degraded segments ({rec})")


def check_phases(log, tenx=TENX, churn=CHURN):
    """Run phases a-d, yielding each phase record once its checks passed;
    raises ``AssertionError`` at the first failed check."""
    from repro.accel.engine import ArrayMatchEngine

    main, m_main = _run("main", "array", tenx, log)
    if not main["use_kernel"]:
        raise AssertionError(f"main: the platform chose no kernel ({main})")
    _on_device(main)
    ref, m_ref = _run("reference", "python", tenx, log)
    _same("main", m_main, m_ref)
    main["identical_to_reference"] = True
    yield main
    yield ref
    jnp_rec, m_jnp = _run(
        "jnp", ArrayMatchEngine(backend="jax", use_kernel=False), tenx, log)
    _on_device(jnp_rec)
    _same("jnp", m_jnp, m_ref)
    jnp_rec["identical_to_reference"] = True
    yield jnp_rec
    lex, m_lex = _run("replan_lexsort", "array", churn, log,
                         replan_order="numpy")
    ker, m_ker = _run("replan_kernel", "array", churn, log,
                         replan_order="kernel")
    _same("replan_kernel", m_ker, m_lex)
    calls = ker["accel.kernel_order_calls"]
    fallbacks = ker["accel.kernel_order_fallbacks"]
    if calls <= fallbacks:
        raise AssertionError(f"replan_kernel: {calls} kernel resorts against "
                             f"{fallbacks} fallbacks")
    ker["identical_to_lexsort"] = True
    yield lex
    yield ker


def main() -> int:
    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1
    from repro.accel.engine import use_compile_cache

    cache = use_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"device_kind": dev.device_kind, "compile_cache": cache}),
          flush=True)
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    try:
        for rec in check_phases(log):
            print(json.dumps(rec), flush=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(log)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
